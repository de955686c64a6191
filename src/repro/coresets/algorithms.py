"""``coreset_kmeans`` — the one-round distributed coreset baseline.

The strongest single-round competitor from the literature (Balcan et al.
2013, "Distributed k-Means and k-Median Clustering on General
Topologies"): every machine compresses its shard to a small weighted
sensitivity coreset, the coordinator gathers the m coresets in ONE
communication round and runs the weighted black box on their union.
Registered with ``repro.api`` like any other algorithm::

    fit(x, k, algo="coreset_kmeans", coreset_size=2048)

Uplink is exactly the coreset rows (points and dtype-aware bytes in the
``ClusterResult``; the per-row weight rides the metadata channel at full
precision, like the HT weights of the sampling paths). Composes with
``uplink_dtype`` — the coreset points are quantized machine-side after
construction — and with both backends through the comm abstraction.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.registry import register_algorithm
from repro.api.result import ClusterResult, uplink_bytes
from repro.core import programs
from repro.core.kmeans import kmeans
from repro.core.minibatch import minibatch_kmeans
from repro.core.sampling import gather_weighted
from repro.coresets.sensitivity import build_coreset, default_coreset_size
from repro.obs.trace import clock, current_trace


def _one_round(kk, xp, wp, *, comm, k: int, t: int, kb: int, ud: str,
               wire: str, blackbox: str, minibatch_size: int,
               lloyd_iters: int):
    """Every machine's coreset, gathered once; the black box on their
    union."""
    m = comm.m
    ids = comm.machine_ids()
    keys = jax.vmap(jax.random.fold_in, (None, 0))(kk, ids)
    cpts, cw = jax.vmap(build_coreset, (0, 0, 0, None, None))(
        keys, xp, wp, t, kb)
    g_pts, g_w = gather_weighted(comm, cpts, cw, ud, wire=wire)
    k_bb = jax.random.fold_in(kk, m + 1)      # coordinator's key
    if blackbox == "minibatch":
        centers, cost = minibatch_kmeans(k_bb, g_pts, g_w, k,
                                         batch=minibatch_size)
    else:
        centers, cost = kmeans(k_bb, g_pts, g_w, k, lloyd_iters)
    # same accounting as the SOCCER coreset uplink: every machine
    # with any coreset mass ships its full fixed-width t-row block
    # (weight-0 padding rows ride along)
    machine_up = jnp.any(g_w.reshape(m, t) > 0, axis=1)
    realized = jnp.sum(machine_up.astype(jnp.int32)) * t
    return centers, cost, realized


@register_algorithm("coreset_kmeans")
def fit_coreset_kmeans(x_parts, k: int, *, backend, key=None, w=None,
                       alive=None, seed: int = 0, coreset_size: int = 0,
                       bicriteria: int = 0, lloyd_iters: int = 25,
                       blackbox: str = "kmeans", minibatch_size: int = 1024,
                       uplink_mode: str = None) -> ClusterResult:
    """One-round coreset clustering: compress, gather once, solve.

    Args:
      coreset_size: total coordinator-side coreset budget in rows
        (split evenly across machines; 0 = ``default_coreset_size``).
      bicriteria: machine-side bicriteria center count (0 = min(k, t)).
      blackbox: coordinator solver, "kmeans" | "minibatch".
      uplink_mode: accepted for facade symmetry; this algorithm's uplink
        IS a coreset, so only "coreset" (or None) is valid.
    """
    if blackbox not in ("kmeans", "minibatch"):
        raise ValueError(
            f"coreset_kmeans blackbox must be 'kmeans' or 'minibatch', "
            f"got {blackbox!r}")
    if uplink_mode not in (None, "coreset"):
        raise ValueError(
            f"coreset_kmeans always uploads coresets; uplink_mode="
            f"{uplink_mode!r} is contradictory")
    m, p, d = x_parts.shape
    total = coreset_size or default_coreset_size(k, m * p)
    t = max(1, -(-total // m))                    # per-machine rows
    kb = bicriteria or max(1, min(k, t))

    comm = backend.make_comm(m)
    ud = getattr(backend, "uplink_dtype", "float32")
    from repro.api.backends import check_uplink_wire
    wire = check_uplink_wire(getattr(backend, "uplink_wire", "auto"), ud)
    x = backend.put(jnp.asarray(x_parts, jnp.float32), "machine")
    w_np = np.ones((m, p), np.float32) if w is None else np.asarray(
        w, np.float32)
    if alive is not None:
        w_np = np.where(np.asarray(alive), w_np, 0.0).astype(np.float32)
    w_dev = backend.put(jnp.asarray(w_np), "machine")
    key = jax.random.PRNGKey(seed) if key is None else key

    fn, compile_s = programs.build(
        "coreset_kmeans", "one_round", _one_round, backend,
        ("rep", "machine", "machine"), ("rep", "rep", "rep"), (key, x, w_dev),
        static=dict(comm=comm, k=k, t=t, kb=kb, ud=ud, wire=wire,
                    blackbox=blackbox, minibatch_size=minibatch_size,
                    lloyd_iters=lloyd_iters))
    trace = current_trace()
    t0 = clock() if trace is not None else 0.0
    centers, cost, realized = fn(key, x, w_dev)
    wall_s = None
    if trace is not None:
        jax.block_until_ready(centers)
        wall_s = clock() - t0
    tally = fn.tally
    up = np.asarray([int(realized)], np.int64)
    if trace is not None:
        trace.emit_round(
            round=1, phase="upload", uplink_rows=up[0],
            wire_payload_bytes=tally.payload, wire_meta_bytes=tally.meta,
            wall_s=wall_s, compile_s=compile_s)
        trace.stop_reason = "one_shot"
    return ClusterResult(
        centers=np.asarray(centers), k=k, algo="coreset_kmeans",
        backend=backend.name, rounds=1, uplink_points=up,
        uplink_bytes=uplink_bytes(up, d, dtype=ud),
        wire_bytes=np.asarray([tally.payload], np.int64),
        wire_meta_bytes=np.asarray([tally.meta], np.int64),
        extra={"blackbox_cost": float(cost), "coreset_rows_per_machine": t,
               "bicriteria": kb})


# Its uplink is a coreset by construction, so fit(uplink_mode="coreset")
# is a (validated) no-op rather than an error — lets sweep conditions
# apply one composed-compression condition across soccer AND this.
fit_coreset_kmeans.supports_uplink_mode = True
