"""``kzmeans`` — one-round distributed (k, z)-means with outlier
pre-aggregation.

The (k, z)-means objective scores a center set by the cost of the best
``n - z`` points: up to ``z = outlier_frac * n`` weight mass may be
discarded for free. The distributed recipe follows the clusterz
decomposition (arXiv:2603.08615): the global top-z outliers are, by a
counting argument, contained in the union of the per-machine top-z
farthest points, so each machine ships those explicitly and compresses
only the remainder:

1. **Per machine**: seed a cheap bicriteria solution, rank the shard by
   min squared distance to it, and split off the ``t_out`` farthest
   live points as *outlier candidates* (shipped verbatim with their
   true weights). The rest of the shard — candidates zero-weighted out
   — compresses to a ``t``-row sensitivity coreset
   (``repro.coresets.build_coreset``). Every original point is thus
   represented exactly once: explicitly if locally far, else through
   the unbiased HT coreset.
2. **One gather**: the fixed-width ``(t + t_out)``-row blocks ride the
   standard weighted uplink (``gather_weighted`` — quantized points,
   full-precision weights on the metadata channel, optional int8 wire).
3. **Coordinator**: k-means++ seeding over the gathered rows with the
   candidate weights zeroed (a gross outlier must never seed), then
   trimmed Lloyd iterations — each step re-ranks the rows against the
   current centers, trims the top ``z`` weight mass
   (``trim_top_mass``), and refits on what remains. Candidates that
   were only *locally* far keep their mass and are clustered normally;
   the globally-far ones carry the trim.
4. **Scoring**: the trim threshold realized on the gathered rows is
   re-applied to the FULL data with the fused one-sweep
   ``ops.truncated_cost`` kernel — per-machine (kept cost, tail mass,
   tail cost) triples psum into the honest (k, z) objective without
   materializing any (n,)-sized intermediate.

Registered with ``repro.api``::

    fit(x, k, algo="kzmeans", outlier_frac=0.02)

With ``outlier_frac=0`` the candidate channel and the trim disappear
and this degrades to a plain one-round coreset clustering.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.registry import register_algorithm
from repro.api.result import ClusterResult, uplink_bytes
from repro.core import programs
from repro.core.kmeans import kmeans_plusplus
from repro.core.sampling import gather_weighted
from repro.core.truncated_cost import trim_top_mass
from repro.coresets.sensitivity import build_coreset, default_coreset_size
from repro.kernels import ops
from repro.obs.trace import clock, current_trace


def _machine_summary(key, xp, wp, t, t_out, kb):
    """One machine's uplink block: (t + t_out, d) rows, (t + t_out,)
    weights — [coreset | outlier candidates], candidates last."""
    k_bi, k_cs = jax.random.split(key)
    if t_out == 0:
        cpts, cw = build_coreset(k_cs, xp, wp, t, kb)
        return cpts, cw
    # Rank by distance to a bicriteria fit — but PEEL a provisional
    # top-far-from-mean mass before fitting it. A bicriteria seeded on
    # the raw shard places centers ON the outliers (their D² mass
    # dominates the k-means++ draw), which zeroes their distance and
    # hides them from the candidate ranking; peeled, they cannot seed,
    # so the final ranking sees their full distance.
    wf = wp.astype(jnp.float32)
    mu = (jnp.sum(xp.astype(jnp.float32) * wf[:, None], axis=0)
          / jnp.maximum(jnp.sum(wf), 1e-30))
    r2 = jnp.sum((xp.astype(jnp.float32) - mu) ** 2, axis=-1)
    _, idx0 = jax.lax.top_k(jnp.where(wp > 0, r2, -jnp.inf), t_out)
    bi = kmeans_plusplus(k_bi, xp, wp.at[idx0].set(0.0), kb)
    d2, _ = ops.min_dist(xp, bi)
    far = jnp.where(wp > 0, d2, -jnp.inf)         # dead rows never candidates
    _, idx = jax.lax.top_k(far, t_out)
    cand_pts = xp[idx]
    cand_w = jnp.where(jnp.isfinite(far[idx]), wp[idx], 0.0)
    wp_rest = wp.at[idx].set(0.0)                 # represented explicitly
    cpts, cw = build_coreset(k_cs, xp, wp_rest, t, kb)
    return (jnp.concatenate([cpts, cand_pts], axis=0),
            jnp.concatenate([cw, cand_w.astype(jnp.float32)], axis=0))


def _one_round(kk, xp, wp, *, comm, k: int, t: int, t_out: int, kb: int,
               ud: str, wire: str, outlier_frac: float, lloyd_iters: int):
    """Summaries gathered once, trimmed Lloyd at the coordinator, and the
    (k, z) objective of its centers on the full data."""
    m, rows = comm.m, t + t_out
    # candidate rows are seed-dead at the coordinator (per-machine
    # layout [t coreset | t_out candidates], replicated after gather)
    seed_mask = jnp.tile(jnp.concatenate(
        [jnp.ones((t,), jnp.float32), jnp.zeros((t_out,), jnp.float32)]), m)
    ids = comm.machine_ids()
    keys = jax.vmap(jax.random.fold_in, (None, 0))(kk, ids)
    pts, wts = jax.vmap(_machine_summary, (0, 0, 0, None, None, None))(
        keys, xp, wp, t, t_out, kb)
    g_pts, g_w = gather_weighted(comm, pts, wts, ud, wire=wire)

    n_mass = comm.psum(jnp.sum(wp, axis=-1))  # population weight mass
    z_mass = jnp.float32(outlier_frac) * n_mass
    k_seed = jax.random.fold_in(kk, m + 1)    # coordinator's key
    # best-of-R seeding: D² seeding alone merges light Zipf
    # components often enough to dominate the error budget, so draw
    # R independent seedings and keep the one with the lowest
    # TRIMMED cost (outliers must not get a vote) — all
    # coordinator-side, no extra communication
    def seed_once(r):
        c = kmeans_plusplus(jax.random.fold_in(k_seed, r), g_pts,
                            g_w * seed_mask, k).astype(jnp.float32)
        d2s, _ = ops.min_dist(g_pts, c)
        return c, jnp.sum(trim_top_mass(d2s, g_w, z_mass) * d2s)

    seeds = [seed_once(r) for r in range(4)]
    best = jnp.argmin(jnp.stack([s[1] for s in seeds]))
    c0 = jnp.stack([s[0] for s in seeds])[best]

    def step(_, c):
        d2, assign = ops.min_dist(g_pts, c)
        w_t = trim_top_mass(d2, g_w, z_mass)
        sums, counts = ops.lloyd_reduce(g_pts, w_t, assign, k)
        return jnp.where(counts[:, None] > 0,
                         sums / jnp.maximum(counts[:, None], 1e-30), c)

    centers = jax.lax.fori_loop(0, lloyd_iters, step, c0)

    # trim threshold realized on the gathered rows: the distance of
    # the first KEPT row when the top-z mass is peeled off in
    # descending order — re-applied to the full data below
    d2g, _ = ops.min_dist(g_pts, centers)
    order = jnp.argsort(-d2g)
    cum = jnp.cumsum(g_w[order])
    if outlier_frac > 0.0:
        j = jnp.minimum(jnp.searchsorted(cum, z_mass),
                        d2g.shape[0] - 1)
        v = d2g[order][j]
    else:
        v = jnp.float32(np.finfo(np.float32).max)

    # honest (k, z) objective: one fused sweep of the full data per
    # machine, triples psum'd — nothing (n,)-sized materializes
    kept, tmass, tcost = jax.vmap(
        lambda xm, wm: ops.truncated_cost(xm, wm, centers, v))(xp, wp)
    kept = comm.psum(kept)
    tmass = comm.psum(tmass)
    tcost = comm.psum(tcost)

    # same accounting as coreset_kmeans: every machine with any
    # uplink mass ships its full fixed-width rows-block
    machine_up = jnp.any(g_w.reshape(m, rows) > 0, axis=1)
    realized = jnp.sum(machine_up.astype(jnp.int32)) * rows
    return centers, kept, tmass, tcost, v, realized


@register_algorithm("kzmeans")
def fit_kzmeans(x_parts, k: int, *, backend, key=None, w=None, alive=None,
                seed: int = 0, outlier_frac: float = 0.0,
                coreset_size: int = 0, bicriteria: int = 0,
                lloyd_iters: int = 25,
                uplink_mode: str = None) -> ClusterResult:
    """One-round distributed (k, z)-means (see module docstring).

    Args:
      outlier_frac: fraction z/n of the total weight mass the objective
        may discard (0 = plain coreset clustering, no candidate channel).
      coreset_size: total coordinator-side uplink budget in rows, split
        evenly across machines (0 = ``default_coreset_size`` plus the
        candidate channel). The clusterz candidate rows are carved OUT
        of the budget, so the uplink is the same number of rows whether
        or not the robust channel is on — fits compare at equal
        communication.
      bicriteria: machine-side bicriteria center count (0 = min(k, t)).
      uplink_mode: facade symmetry; the uplink IS a coreset (+ candidate
        rows), so only "coreset" (or None) is valid.
    """
    if not 0.0 <= outlier_frac < 1.0:
        raise ValueError(f"outlier_frac must be in [0, 1), got "
                         f"{outlier_frac!r}")
    if uplink_mode not in (None, "coreset"):
        raise ValueError(
            f"kzmeans always uploads coresets + outlier candidates; "
            f"uplink_mode={uplink_mode!r} is contradictory")
    m, p, d = x_parts.shape
    # clusterz sizing: all z global outliers could sit on ONE machine,
    # so each ships up to z candidates (capped by its shard)
    t_out = min(p, int(math.ceil(outlier_frac * m * p)))
    total = coreset_size or (default_coreset_size(k, m * p) + m * t_out)
    rows = max(t_out + 1, -(-total // m))         # per-machine uplink rows
    t = rows - t_out                              # coreset rows
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
        "kzmeans", "one_round", _one_round, backend,
        ("rep", "machine", "machine"), ("rep",) * 6, (key, x, w_dev),
        static=dict(comm=comm, k=k, t=t, t_out=t_out, kb=kb, ud=ud,
                    wire=wire, outlier_frac=outlier_frac,
                    lloyd_iters=lloyd_iters))
    trace = current_trace()
    t0 = clock() if trace is not None else 0.0
    centers, kept, tmass, tcost, v, realized = fn(key, x, w_dev)
    wall_s = None
    if trace is not None:
        jax.block_until_ready(centers)
        wall_s = clock() - t0
    tally = fn.tally
    up = np.asarray([int(realized)], np.int64)
    if trace is not None:
        trace.emit_round(
            round=1, phase="upload", v=float(v), uplink_rows=up[0],
            wire_payload_bytes=tally.payload, wire_meta_bytes=tally.meta,
            wall_s=wall_s, compile_s=compile_s)
        trace.stop_reason = "one_shot"
    return ClusterResult(
        centers=np.asarray(centers), k=k, algo="kzmeans",
        backend=backend.name, rounds=1, uplink_points=up,
        uplink_bytes=uplink_bytes(up, d, dtype=ud),
        wire_bytes=np.asarray([tally.payload], np.int64),
        wire_meta_bytes=np.asarray([tally.meta], np.int64),
        extra={"kz_cost": float(kept), "trim_threshold": float(v),
               "trimmed_mass": float(tmass), "trimmed_cost": float(tcost),
               "outlier_frac": float(outlier_frac),
               "coreset_rows_per_machine": t,
               "candidate_rows_per_machine": t_out, "bicriteria": kb})


# The uplink is a coreset (+ explicit candidate rows) by construction,
# so fit(uplink_mode="coreset") is a validated no-op — sweep conditions
# can apply one composed-compression condition across soccer AND this.
fit_kzmeans.supports_uplink_mode = True
