"""``repro.api.fit`` — one front end over every algorithm and backend.

    from repro.api import fit
    res = fit(x, k=25, algo="soccer", backend="auto", epsilon=0.1)
    res.centers, res.rounds, res.uplink_points, res.cost(x)

``x`` is either flat ``(n, d)`` data (read to the host and placed on
``m`` machines by ``shard_policy`` — shuffled/contiguous/sorted/
imbalanced, see ``repro.data.sharding``) or pre-sharded ``(m, p, d)`` —
the latter is passed through untouched, so facade runs are bit-identical
to the legacy per-algorithm drivers for the same PRNG key. A pre-sharded
``jax.Array`` stays on its devices: the driver's placement moves it
device to device, or not at all where it already lies as the backend
places it.

Run *conditions* are facade knobs too: ``uplink_dtype`` sets the
machine->coordinator payload precision (quantized before the upload and
accounted in ``ClusterResult.uplink_bytes``), and ``failure_plan``
(a ``repro.ft.failures.FailurePlan``) injects machine deaths and
straggler deadlines through the host loop's ``on_round`` hook.
"""
from __future__ import annotations

import contextlib
from typing import Optional

import jax
import numpy as np

from repro.api.backends import resolve_backend
from repro.api.registry import get_algorithm
from repro.api.result import ClusterResult
from repro.obs import trace as obs_trace
from repro.obs.metrics import REGISTRY

# One line per traced fit, newest last: its wall, its counters and the
# seconds of each span name, readable by whoever holds the process (the
# chip benchmark's per-layer metrics read the window's fits from here).
TRACED_FITS = REGISTRY.event_log("api.fit.traces")


def _as_parts(x: np.ndarray, w, m: int, seed: int, policy):
    """(n, d) -> ((m, p, d), (m, p) weights, (m, p) alive); 3-d passthrough."""
    if x.ndim == 3:
        return x, w, None
    from repro.data.sharding import make_shards
    return make_shards(x, w, m, policy=policy, seed=seed)


def _check_plan_machines(plan, m: int):
    """Validate every fail_at machine id up front — a bad id must fail
    here, not as an IndexError rounds into the run."""
    bad = sorted({j for ids in plan.fail_at.values() for j in ids
                  if not 0 <= j < m})
    if bad:
        raise ValueError(
            f"failure_plan names machine(s) {bad} but the data has m={m}")


def _mask_failed_machines(parts, w, alive, ids):
    """Zero out machines dead before round 1 (FailurePlan.fail_at[0])."""
    m, p, _ = parts.shape
    alive = (np.ones((m, p), bool) if alive is None
             else np.array(alive, copy=True))
    w = (np.ones((m, p), np.float32) if w is None
         else np.array(w, np.float32, copy=True))
    alive[list(ids)] = False
    w[list(ids)] = 0.0
    return w, alive


def fit(x, k: int, algo: str = "soccer", backend="auto", *,
        m: Optional[int] = None, w=None, key: Optional[jax.Array] = None,
        seed: int = 0, shuffle: bool = True, shard_policy=None,
        uplink_dtype=None, uplink_wire=None, uplink_mode=None,
        failure_plan=None, trace=None, **algo_params) -> ClusterResult:
    """Cluster ``x`` into ``k`` groups with any registered algorithm.

    Args:
      x: ``(n, d)`` points or ``(m, p, d)`` machine-sharded points; a
        pre-sharded ``jax.Array`` is not read to the host.
      k: number of clusters.
      algo: registered algorithm name (``repro.api.list_algorithms()``).
      backend: "virtual" | "mesh" | "auto", a ``jax.sharding.Mesh``, or a
        ``repro.api.backends.Backend``. "auto" uses the mesh backend when
        the host has one device per machine, else the virtual one.
      m: machine count for flat input (default 8, the paper's setup);
        ignored for pre-sharded input.
      w: optional per-point weights, shaped like ``x`` minus the last axis.
      key: optional PRNG key (defaults to ``PRNGKey(seed)``).
      seed: seed for the default key and the shard placement.
      shuffle: legacy knob — ``shuffle=False`` is ``shard_policy=
        "contiguous"``; ignored when ``shard_policy`` is given.
      shard_policy: how flat input lands on machines — "shuffle" |
        "contiguous" | "sorted" | "imbalanced" or a callable (see
        ``repro.data.sharding``); rejected for pre-sharded input.
      uplink_dtype: machine->coordinator payload precision ("float32"
        default, "bfloat16", "float16", "int8" — the last via the affine
        quantizer in ``repro.ft.compression``); uploads are quantized
        and ``uplink_bytes`` accounted at this width.
      uplink_wire: payload *transport* — "codes" gathers 1-byte int8
        codes plus per-machine affine qparams and dequantizes on
        arrival (the mesh collective actually moves 1 byte/coordinate,
        so measured ``wire_bytes`` matches the int8 model); "values"
        moves the reconstructed storage-width values (honest: int8
        payloads travel as f32 and ``wire_bytes`` shows 4x the model);
        "auto" (default) picks "codes" iff ``uplink_dtype="int8"``.
        "codes" with a non-int8 dtype raises.
      uplink_mode: "points" (default) or "coreset" — "coreset" routes
        the per-round upload through a machine-side sensitivity coreset
        (``repro.coresets``), shrinking uplink rows independently of the
        sample size; algorithms advertising ``supports_uplink_mode``
        only. Composes with ``uplink_dtype``. Note: ``coreset_kmeans``
        accepts only "coreset" (or omitting the knob) — its uplink is a
        coreset by construction, so an explicit request for raw "points"
        upload raises rather than silently going unhonored.
      failure_plan: a ``repro.ft.failures.FailurePlan`` injecting machine
        deaths / straggler deadlines (algorithms with an ``on_round``
        hook only, i.e. SOCCER).
      trace: observability knob (``repro.obs``). ``None``/"off"
        (default) — no tracing, provably zero allocation; "rounds" —
        per-round structured records (live count, realized alpha,
        removal threshold, stopping-rule margin, uplink rows, achieved
        wire bytes, wall/compile split) land in
        ``result.extra["trace"]``, with per-fit counters (host<->device
        bytes, device-resident input kept, programs traced); "full" —
        additionally records the host phases as spans (input
        placement, program builds, each round, finalize, readback),
        mirrored into ``jax.profiler.TraceAnnotation`` so a captured
        profile shows them on its host plane, plus events, for the
        Chrome-trace/Perfetto export (``repro.obs.export``,
        ``python -m repro.obs.report``). Each traced fit also appends
        its totals to the ``api.fit.traces`` event log of
        ``repro.obs.REGISTRY``.
      **algo_params: algorithm-specific knobs (e.g. ``epsilon`` for
        soccer, ``rounds`` for kmeans_parallel); unknown names raise.

    Returns:
      A ``ClusterResult`` with a uniform telemetry shape for every
      algorithm x backend combination.
    """
    shape = np.shape(x)
    if len(shape) not in (2, 3):
        raise ValueError(f"x must be (n, d) or (m, p, d), got {shape}")
    if len(shape) == 3:
        if m is not None and m != shape[0]:
            raise ValueError(
                f"m={m} conflicts with pre-sharded x of {shape[0]} "
                f"machines")
        if shard_policy is not None:
            raise ValueError(
                "shard_policy only applies to flat (n, d) input; "
                "pre-sharded (m, p, d) data is passed through untouched")
        m = shape[0]
    else:
        m = 8 if m is None else m
    policy = shard_policy if shard_policy is not None else (
        "shuffle" if shuffle else "contiguous")

    bk = resolve_backend(backend, m, uplink_dtype=uplink_dtype,
                         uplink_wire=uplink_wire)
    driver = get_algorithm(algo)

    if uplink_mode is not None:
        if uplink_mode not in ("points", "coreset"):
            raise ValueError(
                f"unknown uplink_mode {uplink_mode!r}: expected 'points' "
                f"or 'coreset'")
        if not getattr(driver, "supports_uplink_mode", False):
            raise TypeError(
                f"fit(algo={algo!r}) does not support uplink_mode — the "
                f"algorithm has no compressible gather uplink; supported: "
                f"algorithms registered with supports_uplink_mode")
        algo_params["uplink_mode"] = uplink_mode

    init_dead = ()
    if failure_plan is not None:
        if not getattr(driver, "supports_failure_plan", False):
            raise TypeError(
                f"fit(algo={algo!r}) does not support failure_plan — the "
                f"algorithm has no per-round host hook; supported: "
                f"algorithms registered with supports_failure_plan")
        _check_plan_machines(failure_plan, m)
        init_dead = failure_plan.initial_failures()
        algo_params["on_round"] = failure_plan.chain(
            algo_params.get("on_round"))
        if failure_plan.straggler_rate:
            algo_params.setdefault("straggler_rate",
                                   failure_plan.straggler_rate)

    rt = None
    if trace not in (None, False, "off"):
        rt = obs_trace.RunTrace(mode=trace, meta=dict(
            algo=algo, backend=type(bk).__name__, k=k, m=m, seed=seed))

    # every fit is timed by the one obs clock (repro.obs.trace.clock) so
    # bench walls and trace walls can never come from different timers;
    # the wall covers the input's placement too
    t0 = obs_trace.clock()
    with (obs_trace.run_trace(rt) if rt is not None
          else contextlib.nullcontext()):
        with obs_trace.span("fit.place"):
            # a pre-sharded device array goes to the driver as it is: its
            # placement is then device to device, or nothing to do
            on_device = len(shape) == 3 and isinstance(x, jax.Array)
            obs_trace.count("input_on_device_bytes",
                            x.nbytes if on_device else 0)
            if not on_device:
                x = obs_trace.to_host(x)
            parts, w_parts, alive_parts = _as_parts(x, w, m, seed, policy)
            if init_dead:
                w_parts, alive_parts = _mask_failed_machines(
                    parts, w_parts, alive_parts, init_dead)
        res = driver(parts, k, backend=bk, key=key, w=w_parts,
                     alive=alive_parts, seed=seed, **algo_params)
    res.wall_time_s = obs_trace.clock() - t0
    if rt is not None:
        rt.wall_s = res.wall_time_s
        res.extra["trace"] = rt.summary()
        TRACED_FITS.append(algo=algo, mode=rt.mode, wall_s=rt.wall_s,
                           counters=dict(rt.counters),
                           span_s=rt.span_seconds())
    res.params = dict(k=k, m=m, seed=seed, **algo_params)
    if shard_policy is not None:
        res.params["shard_policy"] = getattr(policy, "__name__", policy)
    if uplink_dtype is not None:
        res.params["uplink_dtype"] = bk.uplink_dtype
    if uplink_wire is not None:
        res.params["uplink_wire"] = bk.uplink_wire
    if failure_plan is not None:
        res.params["failure_plan"] = failure_plan
        res.params.pop("on_round", None)
    return res


def fit_update(result: ClusterResult, x_new, **kwargs) -> ClusterResult:
    """Incrementally fold a new batch into a previous ``fit`` result.

    The streaming counterpart of ``fit``: machine-local merge-and-reduce
    coreset trees absorb the batch (zero uplink), Lloyd warm-starts from
    the previous centers over the tree coreset, and a full SOCCER
    re-cluster fires only when the drift trigger (SOCCER's own stopping
    rule on costs) says the centers went stale. See
    ``repro.streaming.update.fit_update`` for the knobs and the uplink
    accounting contract.
    """
    # local import: repro.streaming imports repro.api back (registry,
    # result), so binding at call time keeps the package import acyclic
    from repro.streaming.update import fit_update as _fit_update
    return _fit_update(result, x_new, **kwargs)
