"""Built-in algorithm drivers for ``repro.api.fit``.

Each driver adapts one core implementation to the registry contract
(``(x_parts, k, *, backend, key, w, alive, seed, **params) ->
ClusterResult``) and normalizes its telemetry: per-round uplink in points
*and* bytes, live-count / threshold histories where the algorithm has
them, and the raw core result under ``extra["raw"]`` for callers that
need algorithm-specific detail (SOCCER constants, k-means‖ oversampled
set, EIM11 broadcast volume, ...).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.registry import register_algorithm
from repro.api.result import ClusterResult, uplink_bytes
from repro.configs.soccer_paper import SoccerParams
from repro.core import programs
from repro.core.eim11 import run_eim11
from repro.core.kmeans import kmeans
from repro.core.kmeans_parallel import run_kmeans_parallel
from repro.core.minibatch import minibatch_kmeans
from repro.core.sampling import quantize_uplink
from repro.core.soccer import run_soccer
from repro.obs.trace import clock, current_trace

_SOCCER_FIELDS = {f.name for f in dataclasses.fields(SoccerParams)}


def _uplink_dtype(backend) -> str:
    return getattr(backend, "uplink_dtype", "float32")


def _uplink_wire(backend) -> str:
    from repro.api.backends import check_uplink_wire
    return check_uplink_wire(getattr(backend, "uplink_wire", "auto"),
                             _uplink_dtype(backend))


def _wire_fields(raw, rounds: int) -> dict:
    """Pick the core result's measured WireTally arrays into the
    ClusterResult, trimmed to the realized round count."""
    wp = np.asarray(getattr(raw, "wire_payload", ()), np.int64)
    wm = np.asarray(getattr(raw, "wire_meta", ()), np.int64)
    if wp.size == 0:
        return {}
    return {"wire_bytes": wp[:rounds], "wire_meta_bytes": wm[:rounds]}


def _reject_unknown(algo: str, params: dict, allowed: set):
    unknown = sorted(set(params) - allowed)
    if unknown:
        raise TypeError(
            f"fit(algo={algo!r}) got unexpected parameter(s) "
            f"{', '.join(unknown)}; allowed: {', '.join(sorted(allowed))}")


@register_algorithm("soccer")
def fit_soccer(x_parts, k: int, *, backend, key=None, w=None, alive=None,
               seed: int = 0, eta_override: int = 0, on_round=None,
               **params) -> ClusterResult:
    """SOCCER (the paper's Algorithm 1) via the unified host driver."""
    _reject_unknown("soccer", params,
                    _SOCCER_FIELDS - {"k", "seed", "n_machines"})
    m, _, d = x_parts.shape
    sp = SoccerParams(k=k, seed=seed, n_machines=m, **params)
    res = run_soccer(x_parts, sp, backend=backend, key=key, w=w,
                     alive=alive, eta_override=eta_override,
                     on_round=on_round)
    up = res.uplink[: res.rounds + 1]
    return ClusterResult(
        centers=res.centers, k=k, algo="soccer", backend=backend.name,
        rounds=res.rounds, uplink_points=np.asarray(up, np.int64),
        uplink_bytes=uplink_bytes(up, d, dtype=_uplink_dtype(backend)),
        n_hist=res.n_hist[: res.rounds + 1],
        v_hist=res.v_hist[: res.rounds],
        **_wire_fields(res, res.rounds + 1),
        extra={"const": res.const, "state": res.state, "raw": res})


# SOCCER's host loop exposes on_round, so fit(failure_plan=...) works.
fit_soccer.supports_failure_plan = True
# SOCCER's gather uplink can be coreset-compressed (repro.coresets), so
# fit(uplink_mode="coreset") routes through SoccerParams.uplink_mode.
fit_soccer.supports_uplink_mode = True


@register_algorithm("kmeans_parallel")
def fit_kmeans_parallel(x_parts, k: int, *, backend, key=None, w=None,
                        alive=None, seed: int = 0, rounds: int = 5,
                        l: Optional[float] = None, lloyd_iters: int = 25,
                        oversample_slack: float = 3.0) -> ClusterResult:
    """k-means‖ (Bahmani et al.) — fixed-round oversampling baseline."""
    m, p, d = x_parts.shape
    if alive is not None:   # dead/padding points are weight-0 for k-means‖
        w = jnp.ones((m, p), jnp.float32) if w is None else jnp.asarray(
            w, jnp.float32)
        w = w * jnp.asarray(alive, jnp.float32)
    res = run_kmeans_parallel(x_parts, k, rounds, l=l, w=w, backend=backend,
                              key=key, lloyd_iters=lloyd_iters,
                              oversample_slack=oversample_slack, seed=seed)
    sel = list(res.selected_hist)
    up = np.asarray([1 + sel[0]] + sel[1:] if sel else [1], np.int64)
    return ClusterResult(
        centers=res.centers, k=k, algo="kmeans_parallel",
        backend=backend.name, rounds=res.rounds, uplink_points=up,
        uplink_bytes=uplink_bytes(up, d, dtype=_uplink_dtype(backend)),
        **_wire_fields(res, len(up)),
        extra={"phi_hist": res.phi_hist, "oversampled": res.oversampled,
               "raw": res})


@register_algorithm("eim11")
def fit_eim11(x_parts, k: int, *, backend, key=None, w=None, alive=None,
              seed: int = 0, epsilon: float = 0.1, delta: float = 0.1,
              remove_frac: float = 0.5, max_rounds: int = 12
              ) -> ClusterResult:
    """EIM11 (Ene, Im, Moseley 2011) — sample-everything baseline."""
    d = x_parts.shape[-1]
    res = run_eim11(x_parts, k, epsilon, delta=delta,
                    remove_frac=remove_frac, w=w, alive=alive,
                    backend=backend, key=key, max_rounds=max_rounds,
                    seed=seed)
    return ClusterResult(
        centers=res.centers, k=k, algo="eim11", backend=backend.name,
        rounds=res.rounds, uplink_points=np.asarray(res.uplink, np.int64),
        uplink_bytes=uplink_bytes(res.uplink, d,
                                  dtype=_uplink_dtype(backend)),
        n_hist=res.n_hist,
        **_wire_fields(res, len(res.uplink)),
        extra={"broadcast_points": res.broadcast_points, "raw": res})


def _central(kk, xp, wp, *, comm, method: str, k: int, dtype: str,
             wire: str, bb_kw: tuple):
    """Every machine's shard to the coordinator, then the black box on the
    union."""
    if wire == "codes":
        # int8 codes + per-machine qparams on the wire, dequantized
        # on arrival (1 byte/coordinate actually moves)
        xa = comm.concat_machines_compressed(xp)
    else:
        xa = quantize_uplink(comm.concat_machines(xp), dtype)
    wa = comm.concat_machines(wp, meta=True)
    if method == "minibatch":
        return minibatch_kmeans(kk, xa, wa, k, **dict(bb_kw))
    return kmeans(kk, xa, wa, k, **dict(bb_kw))


def _fit_central(method: str, x_parts, k, backend, key, w, alive, seed,
                 **bb_kw) -> ClusterResult:
    """Centralized baseline: every machine uploads its full shard once,
    the coordinator runs the black box on the union."""
    m, p, d = x_parts.shape
    x = backend.put(jnp.asarray(x_parts, jnp.float32), "machine")
    w_np = np.ones((m, p), np.float32) if w is None else np.asarray(
        w, np.float32)
    if alive is not None:
        w_np = np.where(np.asarray(alive), w_np, 0.0).astype(np.float32)
    w_dev = backend.put(jnp.asarray(w_np), "machine")
    key = jax.random.PRNGKey(seed) if key is None else key

    fn, compile_s = programs.build(
        method, "central", _central, backend, ("rep", "machine", "machine"),
        ("rep", "rep"), (key, x, w_dev), static=dict(
            comm=backend.make_comm(m), method=method, k=k,
            dtype=_uplink_dtype(backend), wire=_uplink_wire(backend),
            bb_kw=tuple(sorted(bb_kw.items()))))
    trace = current_trace()
    t0 = clock() if trace is not None else 0.0
    centers, cost = fn(key, x, w_dev)
    wall_s = None
    if trace is not None:
        jax.block_until_ready(centers)
        wall_s = clock() - t0
    t = fn.tally
    n_up = int(np.sum(w_np > 0))
    up = np.asarray([n_up], np.int64)
    if trace is not None:
        # the whole algorithm is one gather + one black-box call: a
        # single phase="upload" record carries its entire telemetry
        trace.emit_round(
            round=1, phase="upload", n_live=n_up, uplink_rows=n_up,
            wire_payload_bytes=t.payload, wire_meta_bytes=t.meta,
            wall_s=wall_s, compile_s=compile_s)
        trace.stop_reason = "one_shot"
    return ClusterResult(
        centers=np.asarray(centers), k=k, algo=method,
        backend=backend.name, rounds=1, uplink_points=up,
        uplink_bytes=uplink_bytes(up, d, dtype=_uplink_dtype(backend)),
        wire_bytes=np.asarray([t.payload], np.int64),
        wire_meta_bytes=np.asarray([t.meta], np.int64),
        extra={"blackbox_cost": float(cost)})


@register_algorithm("lloyd")
def fit_lloyd(x_parts, k: int, *, backend, key=None, w=None, alive=None,
              seed: int = 0, iters: int = 25) -> ClusterResult:
    """Centralized k-means++ + Lloyd (gather everything, cluster once)."""
    return _fit_central("lloyd", x_parts, k, backend, key, w, alive, seed,
                        iters=iters)


@register_algorithm("minibatch")
def fit_minibatch(x_parts, k: int, *, backend, key=None, w=None, alive=None,
                  seed: int = 0, batch: int = 1024, steps: int = 60
                  ) -> ClusterResult:
    """Centralized mini-batch k-means (the paper's D.2 fast black box)."""
    return _fit_central("minibatch", x_parts, k, backend, key, w, alive,
                        seed, batch=batch, steps=steps)
