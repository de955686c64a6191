"""Exact-size distributed sampling with static shapes.

The paper samples each point independently with probability α = η/N; its
experiments fix |P1| = |P2| = α·N exactly "to reduce variance". We do the
same, jit-compatibly:

1. ``apportion`` — largest-remainder apportionment splits the global budget
   ``total`` across machines proportionally to their live counts
   (deterministic, replicated on every machine).
2. per-machine Gumbel top-k draws ``c_j`` live points uniformly without
   replacement (static cap, dynamic count), by an exact threshold select
   (``top_c_indices``) that takes ``lax.top_k``'s indices in its order
   without sorting all of a machine's points.
3. ``comm.gather_ragged`` — the length-prefixed ragged upload: machine j
   contributes exactly its ``c_j`` drawn rows, landing at offset
   ``sum(c[:j])`` of the global ``(rows, d)`` buffer. Payload is exactly
   the paper's communication bound (η·d per sample set) with **no
   padding waste under arbitrary machine imbalance** and no dense
   per-machine scatter buffer on the wire (``scatter_gather`` below is
   the legacy dense realization, kept for the rank-positioned scatters
   of k-means‖).

Sampled points carry Horvitz–Thompson importance weights ``w_i · n_j/c_j``
so every downstream estimator (black-box clustering, truncated cost)
remains consistent even when a machine's quota is truncated (capacity
limits, straggler deadlines — see repro.ft).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax


def quantize_uplink(x: jax.Array, upload_dtype: str) -> jax.Array:
    """Round an upload payload to the backend's uplink precision.

    Applied machine-side just before the scatter-psum "upload". For the
    float precisions the result is returned IN the uplink dtype: the
    clustering kernels (kernels/fused_lloyd) take bfloat16 points
    directly and widen on load with float32 accumulators, so
    reduced-precision payloads are clustered without an upcast
    materializing 2x the bytes. Call sites that mix the payload into an
    f32 scatter channel promote it back — the values are identical
    either way, only storage width differs.

    ``"int8"`` routes through the affine quantizer in ``ft/compression``:
    the wire format is one int8 code per coordinate plus an 8-byte
    (scale, zero-point) pair per payload per round, riding the metadata
    channel like the HT weights and the count vector. The returned array
    is the *dequantized* float32 reconstruction (exactly the values the
    coordinator would decode), so downstream clustering needs no int8
    kernel path — see ``uplink_storage_dtype``. Accounting still charges
    1 byte/coordinate (``ClusterResult.uplink_bytes``).
    """
    if upload_dtype == "float32":
        return x
    if upload_dtype == "int8":
        from repro.ft.compression import fake_quantize_int8
        return fake_quantize_int8(x)
    return x.astype(jnp.dtype(upload_dtype))


def uplink_storage_dtype(upload_dtype: str) -> str:
    """Device-side storage dtype of a quantized payload: the uplink dtype
    itself for the float precisions, float32 for ``"int8"`` (the stored
    values are the dequantized reconstruction on the int8 grid)."""
    return "float32" if upload_dtype == "int8" else upload_dtype


def apportion(counts: jax.Array, total: int) -> jax.Array:
    """Largest-remainder apportionment of ``total`` across machines.

    Args:
      counts: (m,) int32 live-point counts per machine.
      total: global sample budget (static).

    Returns:
      (m,) int32 with  c_j <= counts_j  and  sum(c) == min(total, sum(counts))
      up to float-rounding slack of a few units (buffer slots beyond the
      realized total are weight-0 padding, so slack is harmless).
    """
    m = counts.shape[0]
    cf = counts.astype(jnp.float32)
    n = jnp.sum(cf)
    total_eff = jnp.minimum(jnp.float32(total), n)
    quota = jnp.where(n > 0, total_eff * cf / jnp.maximum(n, 1.0), 0.0)
    base = jnp.minimum(jnp.floor(quota), cf)
    r = total_eff - jnp.sum(base)                      # leftover budget
    frac = quota - base
    eligible = base < cf
    # rank machines by fractional part (eligible first, ties by id)
    order = jnp.argsort(jnp.where(eligible, -frac, jnp.inf), stable=True)
    rank = jnp.zeros((m,), jnp.int32).at[order].set(
        jnp.arange(m, dtype=jnp.int32))
    add = (rank.astype(jnp.float32) < r) & eligible
    c = base + add.astype(jnp.float32)
    return jnp.minimum(c, cf).astype(jnp.int32)


def exclusive_cumsum(c: jax.Array) -> jax.Array:
    return jnp.concatenate([jnp.zeros((1,), c.dtype), jnp.cumsum(c)[:-1]])


# The select lays a machine's keys out as (p / _LANES, _LANES) blocks and
# resolves the ranks 1..c in steps of _RANKS, so a draw's work after its
# threshold grows with c and not with the buffer width cap.
_LANES = 128
_RANKS = 1024


def _count_below(ends: jax.Array, r: jax.Array) -> jax.Array:
    """``#{i : ends[i] < r}`` for each ``r``, ``ends`` (n,) non-decreasing:
    a compare against every ``_LANES``-th end, then one row gather of
    ``_LANES`` ends per ``r`` (no gather per step of a binary search)."""
    n = ends.shape[0]
    rows = -(-n // _LANES)
    e2 = jnp.pad(ends, (0, rows * _LANES - n),
                 constant_values=jnp.iinfo(jnp.int32).max
                 ).reshape(rows, _LANES)
    s = jnp.sum(e2[None, :, -1] < r[:, None], axis=1)
    s = jnp.minimum(s, rows - 1)
    return s * _LANES + jnp.sum(e2[s] < r[:, None], axis=1)


def top_c_indices(scores: jax.Array, c: jax.Array, cap: int) -> jax.Array:
    """``lax.top_k(scores, cap)``'s first ``c`` indices, without sorting
    all of ``scores``.

    Args:
      scores: (p,) float32; live scores >= 0, dead ones < 0.
      c: scalar int32, at most the number of live scores.
      cap: static buffer width.

    Returns:
      (cap,) int32: the first ``c`` entries index the ``c`` largest
      scores, largest first and equal scores lower index first (top_k's
      order); the entries from ``c`` on lie in [0, p), otherwise
      arbitrary.
    """
    p = scores.shape[0]
    nb = -(-p // _LANES)
    # non-negative float32s order as their int32 bit patterns; dead and
    # padding keys are < 0
    flat = lax.bitcast_convert_type(scores, jnp.int32)
    keys = jnp.pad(flat, (0, nb * _LANES - p),
                   constant_values=-1).reshape(nb, _LANES)
    # t = the c-th largest key: the largest t with #(keys >= t) >= c, set
    # one bit at a time from the top (c = 0 sets every bit)
    t = jnp.int32(0)
    for bit in range(30, -1, -1):
        cand = t | (1 << bit)
        t = jnp.where(jnp.sum(keys >= cand) >= c, cand, t)
    # members: every key above t, then the keys equal to t up to index
    # i_tie, where the (c - #(> t))-th of them lies
    above, at = keys > t, keys == t
    need = c - jnp.sum(above)
    at_n = jnp.sum(at, axis=1)
    at_end = jnp.cumsum(at_n)
    tb = jnp.minimum(_count_below(at_end, need[None])[0], nb - 1)
    at_row = jnp.cumsum(at[tb].astype(jnp.int32)) + at_end[tb] - at_n[tb]
    i_tie = jnp.where(need > 0, tb * _LANES + jnp.sum(at_row < need), -1)
    pos = jnp.arange(nb * _LANES, dtype=jnp.int32).reshape(nb, _LANES)
    member = above | (at & (pos <= i_tie))
    # each position's running member count: within its block by a
    # triangular matmul (0/1 products, sums <= 128: exact), then the
    # blocks' ends; packed beside the keys so one row gather serves a rank
    tri = jnp.arange(_LANES)[:, None] <= jnp.arange(_LANES)[None, :]
    within = jnp.dot(member.astype(jnp.bfloat16), tri.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32).astype(jnp.int32)
    ends = jnp.cumsum(within[:, -1])
    packed = jnp.concatenate(
        [within + (ends - within[:, -1])[:, None], keys], axis=1)
    lanes = jnp.arange(_LANES, dtype=jnp.int32)
    steps = -(-cap // _RANKS)

    def step(state):
        # the r-th member lies in the first block whose end reaches r, at
        # the first lane whose running count reaches r
        k, idx_buf, key_buf = state
        r = k * _RANKS + jnp.arange(1, _RANKS + 1, dtype=jnp.int32)
        b = jnp.minimum(_count_below(ends, r), nb - 1)
        rows = packed[b]
        lane = jnp.minimum(
            jnp.sum(rows[:, :_LANES] < r[:, None], axis=1), _LANES - 1)
        got = jnp.sum(jnp.where(lanes == lane[:, None], rows[:, _LANES:], 0),
                      axis=1)
        idx = jnp.minimum(b * _LANES + lane, p - 1)
        # sort key: keys descending, the slots past c (1) last
        order = jnp.where(r <= c, -got, 1)
        return (k + 1,
                lax.dynamic_update_slice(idx_buf, idx, (k * _RANKS,)),
                lax.dynamic_update_slice(key_buf, order, (k * _RANKS,)))

    _, idx, key = lax.while_loop(
        lambda state: (state[0] < steps) & (state[0] * _RANKS < c), step,
        (jnp.int32(0), jnp.full((steps * _RANKS,), p - 1, jnp.int32),
         jnp.ones((steps * _RANKS,), jnp.int32)))
    # members arrive in index order; the stable sort keeps equal keys in it
    return lax.sort((key[:cap], idx[:cap]), num_keys=1, is_stable=True)[1]


def sample_local(key: jax.Array, alive: jax.Array, c: jax.Array,
                 cap: int) -> Tuple[jax.Array, jax.Array]:
    """Draw ``c`` live points uniformly without replacement (Gumbel top-k).

    Args:
      key: per-machine PRNG key.
      alive: (p,) bool.
      c: scalar int32 draw count, guaranteed <= sum(alive).
      cap: static upper bound on c (buffer width).

    Returns:
      idx: (cap,) int32 point indices (first ``c`` entries are the draw).
      take: (cap,) bool — ``arange(cap) < c``.
    """
    p = alive.shape[0]
    g = jax.random.uniform(key, (p,), minval=1e-7, maxval=1.0)
    scores = jnp.where(alive, g, -1.0)
    idx = top_c_indices(scores, c, cap)
    take = jnp.arange(cap, dtype=jnp.int32) < c
    return idx.astype(jnp.int32), take


def scatter_at(comm, values: jax.Array, pos: jax.Array, take: jax.Array,
               rows: int) -> jax.Array:
    """Scatter machine-local rows at explicit global positions + psum.

    Args:
      values: (local_m, q, d); pos: (local_m, q) global row ids;
      take: (local_m, q) bool. Rows with pos outside [0, rows) are dropped.

    Returns:
      (rows, d) replicated buffer; untouched slots are exactly zero.
    """
    from repro.core.comm import record_wire, static_nbytes
    pos = jnp.where(take, pos, rows)  # out-of-range -> dropped by scatter

    def _one(vals, p):
        return jnp.zeros((rows, vals.shape[-1]), vals.dtype).at[p].add(
            vals, mode="drop")

    masked = values * take[..., None].astype(values.dtype)
    local = jax.vmap(_one)(masked, pos)            # (local_m, rows, d)
    # the dense (rows, d) per-machine buffers ARE this path's wire — the
    # pad rides along; record it honestly (the ragged gathers in
    # repro.core.comm are the padless alternative)
    record_wire(payload=static_nbytes(local) * (comm.m // comm.local_m))
    return comm._reduce(local)


def scatter_gather(comm, values: jax.Array, take: jax.Array,
                   offsets: jax.Array, rows: int) -> jax.Array:
    """Offset-scatter + psum: machine-local draws -> replicated global buffer.

    Args:
      comm: VirtualCluster/MeshCluster.
      values: (local_m, cap, d) sampled rows (garbage where not taken).
      take: (local_m, cap) bool — the first c_j entries per machine.
      offsets: (local_m,) int32 global row offset per machine.
      rows: static global buffer height (e.g. η).

    Returns:
      (rows, d) replicated buffer; untaken slots are exactly zero.
    """
    cap = values.shape[1]
    pos = offsets[:, None] + jnp.arange(cap, dtype=jnp.int32)[None, :]
    return scatter_at(comm, values, pos, take, rows)


def draw_global_sample(comm, key: jax.Array, x: jax.Array, w: jax.Array,
                       alive: jax.Array, n_vec_resp: jax.Array,
                       total: int, cap: int, upload_dtype: str = "float32",
                       wire: str = "values"):
    """Exact-size global uniform sample with HT weights.

    Args:
      x: (local_m, p, d); w: (local_m, p) data weights; alive: (local_m, p).
      n_vec_resp: (m,) live counts of *responding* machines (0 = skipped).
      total: global sample size (static, e.g. η); cap: per-machine buffer.
      upload_dtype: machine->coordinator payload precision; non-f32 rounds
        the point coordinates before the ragged "upload" (HT weights ride
        the metadata channel at full precision, like the count vector).
      wire: transport of the quantized payload (see ``api.backends.
        UPLINK_WIRES``). ``"values"`` gathers at the storage width (int8
        payloads move as their f32 reconstruction — compression ends at
        accounting); ``"codes"`` (int8 only) ships 1-byte codes + one
        per-machine qparams pair and dequantizes on arrival. Both
        reconstruct the SAME values — the wire changes achieved bytes,
        never the statistics.

    The upload is ``comm.gather_ragged``: machine j contributes exactly
    its ``c_j`` drawn rows (length-prefixed offsets, no dense (total, d)
    per-machine pad; dead/skipped machines contribute zero rows).

    Returns:
      pts (total, d) STORED in ``upload_dtype`` (the clustering kernels
      consume bf16 payloads directly with f32 accumulators — see
      kernels/fused_lloyd), weights (total,) f32, both replicated;
      realized draw count.
    """
    ids = comm.machine_ids()
    c_vec = apportion(n_vec_resp, total)
    my_c = c_vec[ids]
    keys = jax.vmap(jax.random.fold_in, (None, 0))(key, ids)
    idx, take = jax.vmap(sample_local, (0, 0, 0, None))(keys, alive, my_c, cap)
    pts = jnp.take_along_axis(x, idx[..., None], axis=1)
    # buffer rows beyond the draw (take=False) are never uploaded — the
    # ragged gather drops them — so overwrite them with row 0 before
    # quantization: an extreme never-uploaded point must not widen the
    # int8 code book the real payload is encoded with
    pts = jnp.where(take[..., None], pts, pts[:, :1])
    if wire == "codes":
        out = comm.gather_ragged_compressed(pts, c_vec, total)
        store = uplink_storage_dtype(upload_dtype)
        if store != "float32":
            out = out.astype(jnp.dtype(store))
    else:
        out = comm.gather_ragged(quantize_uplink(pts, upload_dtype),
                                 c_vec, total)
    w_pt = jnp.take_along_axis(w, idx, axis=1)
    n_local = jnp.sum(alive, axis=1).astype(jnp.float32)
    ht = n_local / jnp.maximum(my_c.astype(jnp.float32), 1.0)
    wts = comm.gather_ragged(w_pt * ht[:, None], c_vec, total, meta=True)
    return out, wts, jnp.sum(c_vec)


def gather_weighted(comm, pts: jax.Array, wts: jax.Array,
                    upload_dtype: str = "float32", wire: str = "values"
                    ) -> Tuple[jax.Array, jax.Array]:
    """Fixed-width weighted gather: per-machine summary blocks -> one
    replicated weighted point set.

    The coreset uplinks (repro.coresets) upload exactly ``t`` rows per
    machine — dead or empty machines contribute weight-0 rows — so unlike
    ``draw_global_sample`` no apportionment/offset bookkeeping is needed:
    the gather is a plain machine-axis concatenation.

    Args:
      pts: (local_m, t, d) summary points.
      wts: (local_m, t) summary weights (0 = padding row).
      upload_dtype: machine->coordinator payload precision; the points
        are quantized machine-side (the weights ride the metadata channel
        at full precision, like the HT weights).
      wire: "values" (blocks move at storage width) or "codes" (int8
        only: 1-byte codes + per-machine qparams through the collective,
        dequantized on arrival — same values, 1/4 the achieved bytes).

    Returns:
      ((m*t, d) points in the uplink storage dtype, (m*t,) f32 weights),
      both replicated.
    """
    if wire == "codes":
        g_pts = comm.concat_machines_compressed(pts)
    else:
        g_pts = comm.concat_machines(quantize_uplink(pts, upload_dtype))
    return g_pts, comm.concat_machines(wts.astype(jnp.float32), meta=True)


def global_weighted_choice(key: jax.Array, comm, weights: jax.Array,
                           x: jax.Array) -> jax.Array:
    """Sample one point globally with probability ∝ weights (two-stage).

    Args:
      weights: (local_m, p) nonneg, may be ragged-masked with zeros.
      x: (local_m, p, d).

    Returns:
      (d,) the selected point, replicated on every machine.
    """
    k_machine, k_point = jax.random.split(key)
    mass_local = jnp.sum(weights, axis=1)                # (local_m,)
    mass_all = comm.all_machines(mass_local)             # (m,)
    logits = jnp.log(jnp.maximum(mass_all, 1e-30))
    logits = jnp.where(mass_all > 0, logits, -jnp.inf)
    mid = jax.random.categorical(k_machine, logits)      # replicated

    ids = comm.machine_ids()                             # (local_m,)
    lw = jnp.log(jnp.maximum(weights, 1e-30))
    lw = jnp.where(weights > 0, lw, -jnp.inf)
    pidx = jax.vmap(lambda kk, l: jax.random.categorical(kk, l))(
        jax.vmap(jax.random.fold_in, (None, 0))(k_point, ids), lw)
    onehot = (ids == mid).astype(x.dtype)                # (local_m,)
    picked = jnp.take_along_axis(
        x, pidx[:, None, None].astype(jnp.int32), axis=1)[:, 0, :]
    return comm.psum(picked * onehot[:, None])
