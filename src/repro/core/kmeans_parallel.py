"""k-means‖ (scalable k-means++, Bahmani et al. 2012) — the paper's baseline.

Distributed seeding over the same machine/coordinator abstraction as
SOCCER: per round every point is selected with probability
min(1, l·w·d²(x,C)/φ(C)) (expected ``l`` selections, paper/MLLib default
l = 2k), selections are scattered into the replicated center buffer, and
after ``rounds`` rounds the oversampled set is weighed by a full
assignment pass and reduced to k with weighted k-means. k-means‖ has **no
stopping mechanism** — ``rounds`` is the hyper-parameter the paper
criticizes.

The driver runs on any ``repro.api.backends`` backend (virtual or mesh).
All ``rounds`` oversampling rounds run as ONE ``lax.scan`` inside one
compiled call with the center/valid buffers donated — no per-round host
round-trip, no per-round dispatch, and the (rows, d) buffer is updated in
place instead of reallocated each round. ``TRACE_COUNTS`` tracks how many
times the round body is traced (tests assert it does not grow with
``rounds``).
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import programs
from repro.core.metrics import assignment_counts
from repro.core.reduce import reduce_to_k
from repro.core.sampling import (exclusive_cumsum, global_weighted_choice,
                                 quantize_uplink, scatter_at)
from repro.kernels import ops
from repro.obs.trace import clock, current_trace

# How many times each traced body below has been traced (NOT called):
# the regression tests assert the round body traces a constant number of
# times regardless of ``rounds`` — the scan conversion's contract.
TRACE_COUNTS = collections.Counter()


@dataclasses.dataclass
class KMeansParallelResult:
    centers: np.ndarray          # (k, d) final reduced centers
    oversampled: np.ndarray      # (C, d) the seeding set (valid rows)
    rounds: int
    phi_hist: np.ndarray         # cost after each round
    selected_hist: np.ndarray    # points added per round
    wire_payload: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros((0,), np.int64))
    wire_meta: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros((0,), np.int64))
    # achieved wire bytes per round — the dense rank-positioned scatter
    # ships its full (rows, d+1) buffer every round, so the pad is
    # measured honestly (contrast the ragged sampling gathers)


def _one_round(comm, l: float, cap: int, upload_dtype: str,
               key, x, w, centers, valid, base):
    """One k-means‖ oversampling round; writes into rows [base, base+cap)."""
    TRACE_COUNTS["one_round"] += 1
    d2 = jax.vmap(lambda xx: ops.min_dist(xx, centers, valid)[0])(x)
    phi = comm.psum(jnp.sum(w * d2, axis=1))
    prob = jnp.minimum(1.0, l * w * d2 / jnp.maximum(phi, 1e-30))

    ids = comm.machine_ids()
    keys = jax.vmap(jax.random.fold_in, (None, 0))(key, ids)
    sel = jax.vmap(lambda kk, p: jax.random.uniform(kk, p.shape) < p)(
        keys, prob)
    sel = sel & (w > 0)

    c_local = jnp.sum(sel, axis=1).astype(jnp.int32)
    c_vec = comm.all_machines(c_local)
    offs = exclusive_cumsum(jnp.minimum(c_vec, cap))
    rank = jnp.cumsum(sel.astype(jnp.int32), axis=1) - 1
    pos = base + offs[ids][:, None] + rank
    take = sel & (pos < base + cap)               # overflow beyond cap dropped

    ones = jnp.ones(x.shape[:2] + (1,), x.dtype)
    vals = jnp.concatenate([quantize_uplink(x, upload_dtype), ones],
                           axis=-1)
    buf = scatter_at(comm, vals, pos, take, centers.shape[0])
    new_centers = jnp.where(buf[:, -1:] > 0, buf[:, :-1], centers)
    new_valid = valid | (buf[:, -1] > 0)
    return new_centers, new_valid, phi, jnp.sum(jnp.minimum(c_vec, cap))


def _seed(kk, x, w, *, comm, rows: int):
    """The first center, drawn by weight, in a ``(rows, d)`` buffer."""
    c0 = global_weighted_choice(kk, comm, w, x)
    centers = jnp.zeros((rows, x.shape[-1]), jnp.float32).at[0].set(c0)
    valid = jnp.zeros((rows,), bool).at[0].set(True)
    return centers, valid


def _all_rounds(keys, bases, x, w, centers, valid, *, comm, l: float,
                cap: int, upload_dtype: str):
    """Every oversampling round in ONE lax.scan — a single device
    dispatch for the whole seeding phase instead of ``rounds`` host
    round-trips (and a single trace of the round body)."""
    def body(carry, kb):
        centers, valid = carry
        kk, base = kb
        centers, valid, phi, nsel = _one_round(comm, l, cap, upload_dtype,
                                               kk, x, w, centers, valid,
                                               base)
        return (centers, valid), (phi, nsel)

    (centers, valid), (phis, nsels) = lax.scan(
        body, (centers, valid), (keys, bases))
    return centers, valid, phis, nsels


def _counts(x, w, centers, valid, *, comm):
    return assignment_counts(comm, x, w, centers, valid)


def run_kmeans_parallel(x_parts: jax.Array, k: int, rounds: int, *,
                        l: Optional[float] = None,
                        w: Optional[jax.Array] = None,
                        backend=None,
                        key: Optional[jax.Array] = None,
                        lloyd_iters: int = 25,
                        oversample_slack: float = 3.0,
                        seed: int = 0) -> KMeansParallelResult:
    """Driver over any backend (virtual by default); x_parts is (m, p, d)."""
    from repro.api.backends import resolve_backend
    m, p, d = x_parts.shape
    backend = resolve_backend(backend, m)
    comm = backend.make_comm(m)

    x = backend.put(jnp.asarray(x_parts, jnp.float32), "machine")
    w = jnp.ones((m, p), jnp.float32) if w is None else jnp.asarray(
        w, jnp.float32)
    w = backend.put(w, "machine")
    l = float(l if l is not None else 2 * k)
    cap = int(oversample_slack * l) + 16
    rows = 1 + rounds * cap
    key = jax.random.PRNGKey(seed) if key is None else key

    def program(name, body, marks, out_marks, inputs, donate=(), **static):
        return programs.build("kmeans_parallel", name, body, backend, marks,
                              out_marks, inputs, static=dict(comm=comm,
                                                             **static),
                              donate=donate)

    trace = current_trace()
    k0, key = jax.random.split(key)
    seed_fn, c_seed = program("seed", _seed, ("rep", "machine", "machine"),
                              ("rep", "rep"), (k0, x, w), rows=rows)
    centers, valid = seed_fn(k0, x, w)
    round_keys = jax.random.split(key, rounds + 1)
    key = round_keys[0]
    bases = jnp.int32(1) + jnp.arange(rounds, dtype=jnp.int32) * cap
    inputs = (round_keys[1:], bases, x, w, centers, valid)
    rounds_fn, c_rounds = program(
        "rounds", _all_rounds,
        ("rep", "rep", "machine", "machine", "rep", "rep"),
        ("rep", "rep", "rep", "rep"), inputs,
        donate=(4, 5),                      # centers/valid update in place
        l=l, cap=cap, upload_dtype=getattr(backend, "uplink_dtype",
                                           "float32"))
    t0 = clock() if trace is not None else 0.0
    centers, valid, phis, nsels = rounds_fn(*inputs)
    phi_hist = [float(p) for p in phis]
    sel_hist = [int(s) for s in nsels]
    scan_wall = (clock() - t0) if trace is not None else None

    counts_fn, c_counts = program("counts", _counts,
                                  ("machine", "machine", "rep", "rep"),
                                  "rep", (x, w, centers, valid))
    counts = counts_fn(x, w, centers, valid)
    kf, key = jax.random.split(key)
    final = reduce_to_k(kf, centers, counts * valid, k, lloyd_iters)

    t_seed, t_body, t_counts = (f.tally for f in (seed_fn, rounds_fn,
                                                  counts_fn))
    # per-round achieved bytes: the scan body's (constant) traffic each
    # round; the seeding choice joins round 0, the weighing pass the last
    wire_payload = np.full((max(rounds, 1),), t_body.payload, np.int64)
    wire_meta = np.full((max(rounds, 1),), t_body.meta, np.int64)
    wire_payload[0] += t_seed.payload
    wire_meta[0] += t_seed.meta
    wire_payload[-1] += t_counts.payload
    wire_meta[-1] += t_counts.meta
    if trace is not None:
        # all rounds ran inside ONE scan dispatch: wall_s is amortized
        # over the rounds; fields k-means‖ has no notion of (alpha, v,
        # live counts, stopping margins) stay None in the pinned schema
        trace.meta.setdefault("rounds", rounds)
        per_round_wall = (None if scan_wall is None or rounds == 0
                          else scan_wall / rounds)
        for r in range(1, max(rounds, 1) + 1):
            trace.emit_round(
                round=r, phase="round",
                uplink_rows=(sel_hist[r - 1] + (1 if r == 1 else 0)
                             if r <= len(sel_hist) else None),
                wire_payload_bytes=wire_payload[r - 1],
                wire_meta_bytes=wire_meta[r - 1],
                wall_s=per_round_wall,
                compile_s=(c_seed + c_rounds + c_counts if r == 1
                           else None))
        trace.stop_reason = "fixed_rounds"
    return KMeansParallelResult(
        centers=np.asarray(final),
        oversampled=np.asarray(centers)[np.asarray(valid)],
        rounds=rounds, phi_hist=np.asarray(phi_hist),
        selected_hist=np.asarray(sel_hist),
        wire_payload=wire_payload, wire_meta=wire_meta)
