"""EIM11 (Ene, Im, Moseley 2011) — the paper's second baseline.

Structure per round (paper §2's description): machines upload two samples;
the coordinator *adds the whole first sample to the clustering*, computes
a quantile threshold of the second sample's distances to the clustering,
and broadcasts the threshold **and the clustering** — whose size grows by
the full per-round sample (Θ(k·n^ε·log n) points, vs SOCCER's k₊). Every
machine then removes the points within the threshold; a fixed fraction of
the data is removed per round regardless of structure, so EIM11 *never
stops early*. The benchmark surfaces exactly the two costs the paper
criticizes: broadcast volume and machine-side distance work.

Runs on any ``repro.api.backends`` backend; the per-round clustering
write base is a traced scalar so one compilation serves every round.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import programs
from repro.core.comm import WireTally
from repro.core.kmeans import kmeans
from repro.core.metrics import assignment_counts
from repro.core.reduce import reduce_to_k
from repro.core.sampling import draw_global_sample
from repro.kernels import ops
from repro.obs.trace import clock, current_trace


@dataclasses.dataclass
class EIM11Result:
    centers: np.ndarray          # (k, d) final reduced centers
    rounds: int
    broadcast_points: int        # total points broadcast to machines
    n_hist: np.ndarray
    uplink: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros((0,), np.int64))
    # points uploaded per round (two samples each) + the finalize gather
    wire_payload: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros((0,), np.int64))
    wire_meta: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros((0,), np.int64))
    # achieved wire bytes per round (core.comm.WireTally accounting)


def _weighted_quantile(d2: jax.Array, w: jax.Array, q: float) -> jax.Array:
    order = jnp.argsort(d2)
    cum = jnp.cumsum(w[order])
    total = jnp.maximum(cum[-1], 1e-30)
    idx = jnp.searchsorted(cum / total, q)
    return d2[order][jnp.minimum(idx, d2.shape[0] - 1)]


def _round(kk, x, w, alive, centers, valid, base, *, comm, s: int, cap: int,
           rows: int, remove_frac: float, uplink_dtype: str,
           uplink_wire: str):
    """One EIM11 round: two uploads, the first joins the clustering, the
    second sets the removal quantile."""
    n_local = jnp.sum(alive, axis=1).astype(jnp.int32)
    n_vec = comm.all_machines(n_local)
    k1, k2 = jax.random.split(kk)
    s1, _, r1 = draw_global_sample(comm, k1, x, w, alive, n_vec, s, cap,
                                   upload_dtype=uplink_dtype,
                                   wire=uplink_wire)
    s2, w2, r2 = draw_global_sample(comm, k2, x, w, alive, n_vec, s, cap,
                                    upload_dtype=uplink_dtype,
                                    wire=uplink_wire)
    # coordinator adds the whole first sample to the clustering (the
    # clustering buffer is broadcast DOWNlink, so it stays f32; only
    # the uplink payload s1/s2 may arrive narrowed)
    centers = jax.lax.dynamic_update_slice(
        centers, s1.astype(jnp.float32), (base, 0))
    row_ids = jnp.arange(rows)
    valid = valid | ((row_ids >= base) & (row_ids < base + s))
    # quantile threshold from the second sample
    d2s, _ = ops.min_dist(s2, centers, valid)
    v = _weighted_quantile(d2s, w2, remove_frac)
    # machines: remove everything within the threshold
    d2x = jax.vmap(lambda xx: ops.min_dist(xx, centers, valid)[0])(x)
    alive = alive & (d2x > v)
    n_rem = comm.psum(jnp.sum(alive, axis=1).astype(jnp.int32))
    return alive, centers, valid, n_rem, r1 + r2


def _finalize(kk, x, w, alive, centers, valid, base, *, comm, s: int,
              cap: int, rows: int, k: int, uplink_dtype: str,
              uplink_wire: str):
    """Survivors to the coordinator, k-means on them, then the weighted
    reduction of the whole clustering to k."""
    n_local = jnp.sum(alive, axis=1).astype(jnp.int32)
    n_vec = comm.all_machines(n_local)
    kf1, kf2 = jax.random.split(kk)
    v_pts, v_w, real = draw_global_sample(comm, kf1, x, w, alive, n_vec, s,
                                          cap, upload_dtype=uplink_dtype,
                                          wire=uplink_wire)
    c_fin, _ = kmeans(kf2, v_pts, v_w, k)
    centers = jax.lax.dynamic_update_slice(centers, c_fin, (base, 0))
    row_ids = jnp.arange(rows)
    valid = valid | ((row_ids >= base) & (row_ids < base + k))
    counts = assignment_counts(comm, x, w, centers, valid)
    final = reduce_to_k(kf2, centers, counts * valid, k)
    return final, real


# (key, x, w, alive, centers, valid, base): both programs' arguments
_IN_MARKS = ("rep", "machine", "machine", "machine", "rep", "rep", "rep")


def run_eim11(x_parts: jax.Array, k: int, epsilon: float, *,
              delta: float = 0.1, remove_frac: float = 0.5,
              w: Optional[jax.Array] = None,
              alive: Optional[jax.Array] = None,
              backend=None,
              key: Optional[jax.Array] = None, max_rounds: int = 12,
              seed: int = 0) -> EIM11Result:
    from repro.api.backends import check_uplink_wire, resolve_backend
    m, p, d = x_parts.shape
    backend = resolve_backend(backend, m)
    comm = backend.make_comm(m)

    from repro.core.soccer import effective_n
    alive0 = jnp.ones((m, p), bool) if alive is None else jnp.asarray(
        alive, bool)
    n = int(np.sum(np.asarray(alive0)))
    # per-round upload / clustering growth (paper: 9·k·n^ε·log(n/δ));
    # sized from the live *weight* mass, like SOCCER's eta (weighted
    # input stands for duplicated points)
    n_w = effective_n(m, p, w, alive0)
    s = min(int(math.ceil(9 * k * (n_w ** epsilon)
                          * math.log(n_w / delta))), n)

    x = backend.put(jnp.asarray(x_parts, jnp.float32), "machine")
    w = jnp.ones((m, p), jnp.float32) if w is None else jnp.asarray(
        w, jnp.float32)
    w = backend.put(w, "machine")
    alive = backend.put(alive0, "machine")
    uplink_dtype = getattr(backend, "uplink_dtype", "float32")
    static = dict(comm=comm, s=s, cap=min(p, s), rows=max_rounds * s,
                  uplink_dtype=uplink_dtype,
                  uplink_wire=check_uplink_wire(
                      getattr(backend, "uplink_wire", "auto"),
                      uplink_dtype))
    key = jax.random.PRNGKey(seed) if key is None else key

    def program(name, body, out_marks, inputs, **more):
        return programs.build("eim11", name, body, backend, _IN_MARKS,
                              out_marks, inputs, static=dict(static, **more))

    centers = jnp.zeros((static["rows"], d), jnp.float32)
    valid = jnp.zeros((static["rows"],), bool)
    n_hist = [n]
    uplink = []
    rounds = 0
    broadcast = 0
    n_rem = n
    trace = current_trace()
    step = compile_round = fin_wall = None
    round_walls = []
    if trace is not None:
        trace.meta.setdefault("capacity", s)
        trace.meta.setdefault("max_rounds", max_rounds)
    while n_rem > s and rounds < max_rounds:
        kk, key = jax.random.split(key)
        inputs = (kk, x, w, alive, centers, valid, jnp.int32(rounds * s))
        if step is None:
            step, compile_round = program(
                "round", _round, ("machine", "rep", "rep", "rep", "rep"),
                inputs, remove_frac=remove_frac)
        t0 = clock() if trace is not None else 0.0
        alive, centers, valid, n_rem_a, up = step(*inputs)
        n_rem = int(n_rem_a)
        if trace is not None:
            round_walls.append(clock() - t0)
        rounds += 1
        broadcast += int(np.asarray(valid).sum())  # coordinator re-broadcasts C
        n_hist.append(n_rem)
        uplink.append(int(up))

    # final: survivors -> coordinator -> k-means; then weighted reduction
    kf, key = jax.random.split(key)
    inputs = (kf, x, w, alive, centers, valid,
              jnp.int32(min(rounds * s, static["rows"] - k)))
    finalize, compile_fin = program("finalize", _finalize, ("rep", "rep"),
                                    inputs, k=k)
    t0 = clock() if trace is not None else 0.0
    final, real = finalize(*inputs)
    if trace is not None:
        jax.block_until_ready(final)
        fin_wall = clock() - t0
    t_round = WireTally() if step is None else step.tally
    t_fin = finalize.tally
    uplink.append(int(real))
    up_arr = np.asarray(uplink, np.int64)
    wire_payload = np.concatenate(
        [t_round.bytes_at(up_arr[:rounds]),
         t_fin.bytes_at(up_arr[rounds:])])
    wire_meta = np.concatenate(
        [t_round.meta_bytes_at(up_arr[:rounds]),
         t_fin.meta_bytes_at(up_arr[rounds:])])
    if trace is not None:
        for r in range(1, rounds + 1):
            trace.emit_round(
                round=r, phase="round", n_live=n_hist[r - 1], capacity=s,
                removed=n_hist[r - 1] - n_hist[r],
                stop_ratio=n_hist[r] / s, stop_margin=n_hist[r] - s,
                uplink_rows=up_arr[r - 1],
                wire_payload_bytes=wire_payload[r - 1],
                wire_meta_bytes=wire_meta[r - 1],
                wall_s=round_walls[r - 1],
                compile_s=compile_round if r == 1 else None)
        trace.emit_round(
            round=rounds + 1, phase="finalize", n_live=n_hist[rounds],
            capacity=s, uplink_rows=up_arr[rounds],
            wire_payload_bytes=wire_payload[rounds],
            wire_meta_bytes=wire_meta[rounds],
            wall_s=fin_wall, compile_s=compile_fin)
        trace.stop_reason = "capacity" if n_rem <= s else "max_rounds"
    return EIM11Result(centers=np.asarray(final), rounds=rounds,
                       broadcast_points=broadcast,
                       n_hist=np.asarray(n_hist),
                       uplink=up_arr, wire_payload=wire_payload,
                       wire_meta=wire_meta)
