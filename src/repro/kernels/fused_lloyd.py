"""Pallas TPU kernels: one-sweep fused clustering passes.

Both hot paths of SOCCER previously streamed the point set through HBM
twice: a Lloyd step ran ``min_dist`` then ``lloyd_reduce`` as separate
sweeps, and the per-round removal pass materialized the full per-machine
distance array before masking and re-reducing counts. For small k (the
common regime: k_plus a few hundred, d <= a few hundred) both kernels are
memory-bound, so halving HBM traffic halves the step time. The fused
kernels here each make exactly ONE grid walk over point panels:

* ``fused_assign_reduce``: per panel, drive ``-2 x @ c^T`` through the MXU,
  take the masked (min, argmin), build the weighted one-hot in VMEM, and
  accumulate per-center ``(sums, counts)`` plus the weighted cost — one HBM
  read of ``x`` per Lloyd iteration instead of two, and the (n,) assignment
  vector never round-trips through HBM.
* ``remove_below``: per (machine, panel), compute ``min_j rho(x, C)^2``,
  compare against the broadcast threshold ``v``, AND into the ``alive``
  mask, and accumulate per-machine live counts — the (m, p) distance array
  never exists.
* ``update_min_dist``: the D²-seeding hot path. One seeding step lowers
  the running min-d2 against the newly chosen center(s) AND totals the
  weighted sampling mass for the next categorical draw — fused here into
  one sweep of ``x`` instead of a distance pass plus three (n,) passes.
* ``*_chunked``: big-k variants for EIM11-sized center sets that do not
  fit VMEM. The center set is tiled through VMEM in ``tuning.chunk_sizes``
  panels with a running (min, argmin) per point panel; the assign-reduce
  variant is a SINGLE grid walk — the (kp, d) + (kp,) accumulators stay
  resident in VMEM for the whole walk and the weighted one-hot scatter
  runs chunk-by-chunk once each point panel's argmin is final, so ``x``
  is read from HBM exactly once (the old second scatter walk is gone;
  it survives only as a fallback for accumulator sets beyond
  ``_CHUNK_ACC_BUDGET``).

All kernels accept float32, bfloat16 or float16 points/centers (every
``UPLINK_DTYPES`` precision) and accumulate in float32 (inputs are
widened on load from VMEM, never in HBM), so reduced-precision uplink
payloads are clustered without an upcast materializing 2x the bytes.

Every walk streams its panels through BlockSpecs, which the TPU pipeline
already double-buffers (panel i+1's HBM->VMEM copy runs while panel i
computes). Hand-written DMA loops cannot replace them at small d: Mosaic
refuses a row slice of an HBM array whose minor dim is not a multiple of
128 lanes (the paper's d = 15).

Block sizes come from ``kernels.tuning`` (measured table first, analytic
fallback); every wrapper also takes explicit static size overrides
(``bn=``, ``k_chunk=``) — the hook ``kernels.autotune`` uses to time
candidates past the jit cache.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tuning import block_sizes, chunk_sizes, clamp_bn

_BIG = 3.0e38  # plain float so the kernels capture no traced constants

# Every distance and scatter product runs at full f32 precision: the
# paper's data sits ~5 from the origin with within-cluster d2 ~ 1e-5, so
# the single bf16 MXU pass a TPU takes by default would bury the removal
# threshold and the costs in the cross term's rounding error.
_HI = jax.lax.Precision.HIGHEST

# The single-walk chunked kernel keeps the full (kp, d) + (kp,)
# accumulators resident in VMEM; center sets whose accumulators exceed
# this fall back to the legacy two-walk scatter variant.
_CHUNK_ACC_BUDGET = 6 * 2**20

# TPU layout: centers sit on sublanes and points on lanes inside every
# kernel, so each per-point quantity (weights, min-d2, argmin, masks) is a
# lane-dense (1, bn) row block of a (1, n) or (m, 1, p) array; per-center
# quantities are (kp, 1) columns and scalars are (1, 1) tiles written
# whole (Mosaic refuses 1-D blocks that disagree with XLA's tiling and
# scalar stores to VMEM).


def _row(v, n_pad: int, dtype=jnp.float32):
    """(n,) per-point vector -> (1, n_pad) row, zero padded."""
    return jnp.pad(v.astype(dtype), (0, n_pad - v.shape[0]))[None, :]


def _valid_col(c_valid, k: int, kp: int):
    """(k,) validity mask -> (kp, 1) float32 column; padded centers are
    invalid (0). Widened here so no int8 block reaches the kernel."""
    cv = (jnp.ones((k,), jnp.float32) if c_valid is None
          else c_valid.astype(jnp.float32))
    return jnp.pad(cv, (0, kp - k))[:, None]


def _partial_d2(x, c, cv):
    """(kp, bn) masked ||c||^2 - 2 c.x and the (1, bn) ||x||^2 row."""
    dots = jax.lax.dot_general(
        c, x, (((1,), (1,)), ((), ())), precision=_HI,
        preferred_element_type=jnp.float32)         # (kp, bn)
    x2 = jax.lax.dot_general(
        jnp.ones((1, x.shape[1]), jnp.float32), x * x,
        (((1,), (1,)), ((), ())), precision=_HI,
        preferred_element_type=jnp.float32)         # (1, bn)
    c2 = jnp.sum(c * c, axis=1, keepdims=True)      # (kp, 1)
    return jnp.where(cv > 0, c2 - 2.0 * dots, _BIG), x2


def _panel_dmin(x, c, cv):
    """(1, bn) masked min squared distance against a center panel."""
    part, x2 = _partial_d2(x, c, cv)
    return jnp.maximum(jnp.min(part, axis=0, keepdims=True) + x2, 0.0)


def _panel_min(x, c, cv):
    """(1, bn) masked min squared distance + (1, bn) int32 argmin."""
    part, x2 = _partial_d2(x, c, cv)
    dmin = jnp.maximum(jnp.min(part, axis=0, keepdims=True) + x2, 0.0)
    return dmin, jnp.argmin(part, axis=0, keepdims=True).astype(jnp.int32)


def _onehot(a, w, rows: int, offset=0):
    """(rows, bn) weighted one-hot of the (1, bn) assignment row."""
    centers = jax.lax.broadcasted_iota(jnp.int32, (rows, a.shape[1]), 0)
    return jnp.where(centers + offset == a, w, 0.0)


def _scatter(onehot, x):
    """(rows, d) weighted sums of the one-hot's points."""
    return jax.lax.dot_general(
        onehot, x, (((1,), (0,)), ((), ())), precision=_HI,
        preferred_element_type=jnp.float32)


def _rowsum(v):
    """Sum of each row, kept 2-D: (r, bn) -> (r, 1)."""
    return jnp.sum(v, axis=1, keepdims=True)


def _assign_reduce_panel(x, w, c, cv):
    """One panel's fused contribution: ((kp, d) sums, (kp, 1) cnt,
    (1, 1) cost)."""
    dmin, a = _panel_min(x, c, cv)
    onehot = _onehot(a, w, c.shape[0])              # (kp, bn)
    return _scatter(onehot, x), _rowsum(onehot), _rowsum(w * dmin)


def _fused_kernel(x_ref, w_ref, c_ref, cv_ref,
                  sums_ref, cnt_ref, cost_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        sums_ref[...] = jnp.zeros(sums_ref.shape, jnp.float32)
        cnt_ref[...] = jnp.zeros(cnt_ref.shape, jnp.float32)
        cost_ref[...] = jnp.zeros(cost_ref.shape, jnp.float32)

    x = x_ref[...].astype(jnp.float32)              # (bn, d)
    c = c_ref[...].astype(jnp.float32)              # (kp, d)
    sums, cnt, cost = _assign_reduce_panel(x, w_ref[...], c, cv_ref[...])
    sums_ref[...] += sums
    cnt_ref[...] += cnt
    cost_ref[...] += cost


def _resident_bn(d: int, k: int, n: int, dtype, bn: Optional[int]) -> int:
    """Point-panel size for the resident-center kernels: the tuned (d, k)
    entry unless overridden, shrunk so the (kp, bn) one-hot panel stays
    inside the VMEM budget, then clamped toward n."""
    if bn is None:
        bn, _ = block_sizes(d, k, str(dtype))
        if -(-k // 128) * 128 >= 512:   # keep the (kp, bn) one-hot panel
            bn = min(bn, 256)           # inside the VMEM budget
    return clamp_bn(bn, n)


def _pad_points(x, w, c, c_valid, bn, kp=None):
    """Pad points to whole panels and centers to ``kp`` rows (default the
    128-row tile): ((n_pad, d) x, (1, n_pad) w (None without weights),
    (kp, d) c, (kp, 1) valid, kp)."""
    n, _ = x.shape
    k = c.shape[0]
    kp = -(-k // 128) * 128 if kp is None else kp
    n_pad = n + (-n % bn)
    xp = jnp.pad(x, ((0, n_pad - n), (0, 0)))
    wp = None if w is None else _row(w, n_pad)       # weight-0 rows: no-ops
    cp = jnp.pad(c, ((0, kp - k), (0, 0)))
    return xp, wp, cp, _valid_col(c_valid, k, kp), kp


def _pad_machines(x, c, alive, c_valid, bn, kp):
    """(m, p, d) removal inputs -> padded points, (m, 1, p_pad) int32 alive
    rows (padding is dead), (kp, d) centers and their (kp, 1) mask."""
    _, p, _ = x.shape
    k = c.shape[0]
    xp = jnp.pad(x, ((0, 0), (0, -p % bn), (0, 0)))
    ap = jnp.pad(alive.astype(jnp.int32), ((0, 0), (0, -p % bn)))[:, None]
    cp = jnp.pad(c, ((0, kp - k), (0, 0)))
    return xp, ap, cp, _valid_col(c_valid, k, kp)


def _scalar(v):
    """A () threshold as the (1, 1) float32 tile the kernels compare to."""
    return jnp.reshape(v, (1, 1)).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret", "bn"))
def fused_assign_reduce_pallas(x: jax.Array, w: jax.Array, c: jax.Array,
                               c_valid: Optional[jax.Array] = None,
                               *, interpret: bool = False,
                               bn: Optional[int] = None
                               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-sweep Lloyd step: ((k, d) sums, (k,) counts, () weighted cost)."""
    n, d = x.shape
    k = c.shape[0]
    bn = _resident_bn(d, k, n, x.dtype, bn)
    xp, wp, cp, cvp, kp = _pad_points(x, w, c, c_valid, bn)

    grid = (xp.shape[0] // bn,)
    sums, counts, cost = pl.pallas_call(
        _fused_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((1, bn), lambda i: (0, i)),
            pl.BlockSpec((kp, d), lambda i: (0, 0)),
            pl.BlockSpec((kp, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((kp, d), lambda i: (0, 0)),
            pl.BlockSpec((kp, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((kp, d), jnp.float32),
            jax.ShapeDtypeStruct((kp, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(xp, wp, cp, cvp)
    return sums[:k], counts[:k, 0], cost[0, 0]


def _remove_kernel(x_ref, a_ref, c_ref, cv_ref, v_ref, out_ref, live_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        live_ref[...] = jnp.zeros(live_ref.shape, jnp.int32)

    x = x_ref[0].astype(jnp.float32)                 # (bn, d)
    dmin = _panel_dmin(x, c_ref[...].astype(jnp.float32), cv_ref[...])
    keep = ((a_ref[0] != 0) & (dmin > v_ref[...])).astype(jnp.int32)
    out_ref[0] = keep                                # (1, bn)
    live_ref[0] += _rowsum(keep)


@functools.partial(jax.jit, static_argnames=("interpret", "bn"))
def remove_below_pallas(x: jax.Array, c: jax.Array, alive: jax.Array,
                        v: jax.Array,
                        c_valid: Optional[jax.Array] = None,
                        *, interpret: bool = False,
                        bn: Optional[int] = None
                        ) -> Tuple[jax.Array, jax.Array]:
    """Fused SOCCER removal over (m, p, d) machine-sharded points.

    Returns ((m, p) bool alive & d2 > v, (m,) int32 per-machine live counts).
    """
    m, p, d = x.shape
    k = c.shape[0]
    bn = _resident_bn(d, k, p, x.dtype, bn)
    kp = -(-k // 128) * 128
    xp, ap, cp, cvp = _pad_machines(x, c, alive, c_valid, bn, kp)

    grid = (m, xp.shape[1] // bn)                    # panel axis innermost
    alive_new, live = pl.pallas_call(
        _remove_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bn, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, bn), lambda i, j: (i, 0, j)),
            pl.BlockSpec((kp, d), lambda i, j: (0, 0)),
            pl.BlockSpec((kp, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bn), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, 1, 1), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(ap.shape, jnp.int32),
            jax.ShapeDtypeStruct((m, 1, 1), jnp.int32),
        ],
        interpret=interpret,
    )(xp, ap, cp, cvp, _scalar(v))
    return alive_new[:, 0, :p].astype(bool), live[:, 0, 0]


def _update_kernel(x_ref, w_ref, d2_ref, c_ref, cv_ref,
                   out_ref, mass_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        mass_ref[...] = jnp.zeros(mass_ref.shape, jnp.float32)

    x = x_ref[...].astype(jnp.float32)              # (bn, d)
    c = c_ref[...].astype(jnp.float32)              # (kp, d)
    cv = cv_ref[...]
    cand = _panel_dmin(x, c, cv)
    prev = d2_ref[...]
    # with every center masked off the update is a no-op (matches the
    # inf-masked oracle exactly even when the caller's running d2 is
    # still +inf); the mask is checked directly — cand's _BIG sentinel
    # cannot distinguish "no valid center" from a genuinely huge distance
    any_valid = jnp.max(cv, axis=0, keepdims=True) > 0      # (1, 1)
    new = jnp.where(any_valid, jnp.minimum(prev, cand), prev)
    out_ref[...] = new
    mass_ref[...] += _rowsum(w_ref[...] * new)


@functools.partial(jax.jit, static_argnames=("interpret", "bn"))
def update_min_dist_pallas(x: jax.Array, w: jax.Array, c: jax.Array,
                           d2: jax.Array,
                           c_valid: Optional[jax.Array] = None,
                           *, interpret: bool = False,
                           bn: Optional[int] = None
                           ) -> Tuple[jax.Array, jax.Array]:
    """Fused D²-seeding update: ((n,) new min-d2, () weighted mass).

    Semantics == ``min(d2, min_dist(x, c))`` plus ``sum(w * new_d2)``;
    one HBM sweep of ``x`` with the (small) new-center block resident.
    """
    n, d = x.shape
    kc = c.shape[0]
    bn = _resident_bn(d, kc, n, x.dtype, bn)
    xp, wp, cp, cvp, kp = _pad_points(x, w, c, c_valid, bn)
    dp = _row(d2, xp.shape[0])           # pad 0, not inf: 0 * w_pad = 0

    grid = (xp.shape[0] // bn,)
    out, mass = pl.pallas_call(
        _update_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((1, bn), lambda i: (0, i)),
            pl.BlockSpec((1, bn), lambda i: (0, i)),
            pl.BlockSpec((kp, d), lambda i: (0, 0)),
            pl.BlockSpec((kp, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bn), lambda i: (0, i)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(dp.shape, jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(xp, wp, dp, cp, cvp)
    return out[0, :n], mass[0, 0]


def _running_min(x, c_ref, cv_ref, d2_scr, idx_scr, offset):
    """Fold one center chunk into the panel's running (min, argmin)."""
    local_min, local_arg = _panel_min(x, c_ref[...].astype(jnp.float32),
                                      cv_ref[...])
    prev = d2_scr[...]
    better = local_min < prev
    idx_scr[...] = jnp.where(better, local_arg + offset, idx_scr[...])
    d2_scr[...] = jnp.where(better, local_min, prev)


def _fused_chunked_kernel(x_ref, w_ref, c_ref, cv_ref,
                          sums_ref, cnt_ref, cost_ref, d2_scr, idx_scr,
                          *, bk: int, nc: int):
    """Single-walk chunked-K fused step. Grid (point panel, center chunk)
    with the chunk axis innermost: the running (min, argmin) lives in
    VMEM scratch while x stays resident across chunks, and once the last
    chunk finalizes a panel's argmin the weighted one-hot scatter runs
    chunk-by-chunk into the walk-resident (kp, d) accumulators."""
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _init_walk():
        sums_ref[...] = jnp.zeros(sums_ref.shape, jnp.float32)
        cnt_ref[...] = jnp.zeros(cnt_ref.shape, jnp.float32)
        cost_ref[...] = jnp.zeros(cost_ref.shape, jnp.float32)

    @pl.when(j == 0)
    def _init_panel():
        d2_scr[...] = jnp.full(d2_scr.shape, _BIG, jnp.float32)
        idx_scr[...] = jnp.zeros(idx_scr.shape, jnp.int32)

    x = x_ref[...].astype(jnp.float32)              # (bn, d) resident over j
    _running_min(x, c_ref, cv_ref, d2_scr, idx_scr, j * bk)

    @pl.when(j == nc - 1)
    def _scatter_chunks():                          # argmin now final
        w = w_ref[...]
        a = idx_scr[...]
        for jj in range(nc):                        # static chunk unroll
            onehot = _onehot(a, w, bk, jj * bk)
            sums_ref[jj * bk:(jj + 1) * bk, :] += _scatter(onehot, x)
            cnt_ref[jj * bk:(jj + 1) * bk, :] += _rowsum(onehot)
        cost_ref[...] += _rowsum(w * d2_scr[...])


@functools.partial(jax.jit,
                   static_argnames=("interpret", "bn", "k_chunk",
                                    "acc_budget"))
def fused_assign_reduce_chunked_pallas(
        x: jax.Array, w: jax.Array, c: jax.Array,
        c_valid: Optional[jax.Array] = None,
        *, interpret: bool = False, bn: Optional[int] = None,
        k_chunk: Optional[int] = None,
        acc_budget: int = _CHUNK_ACC_BUDGET
        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Chunked-K one-sweep Lloyd step for center sets beyond VMEM.

    ONE grid walk: (point panel x center chunk, chunk innermost) keeps
    each x panel resident while center chunks stream by, tracks the
    running (min, argmin) in VMEM scratch, and — once the last chunk
    finalizes a panel — scatters the weighted one-hot into (kp, d) + (kp,)
    accumulators that stay resident for the entire walk. ``x`` is read
    from HBM exactly once; the (n,) assignment never exists in HBM.
    Center sets whose accumulators exceed ``acc_budget`` bytes fall back
    to the legacy two-walk variant (assign walk + scatter walk).
    """
    n, d = x.shape
    k = c.shape[0]
    t_bn, t_bk = chunk_sizes(d, str(x.dtype))
    bn = clamp_bn(t_bn if bn is None else bn, n)
    bk = t_bk if k_chunk is None else k_chunk
    kp = -(-k // bk) * bk
    if kp * (d + 1) * 4 > acc_budget:
        return _fused_assign_reduce_chunked_twopass(
            x, w, c, c_valid, interpret=interpret, bn=bn, bk=bk)
    xp, wp, cp, cvp, kp = _pad_points(x, w, c, c_valid, bn, kp)

    np_ = xp.shape[0] // bn
    nc = kp // bk
    sums, counts, cost = pl.pallas_call(
        functools.partial(_fused_chunked_kernel, bk=bk, nc=nc),
        grid=(np_, nc),                              # chunk axis innermost
        in_specs=[
            pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j: (0, i)),
            pl.BlockSpec((bk, d), lambda i, j: (j, 0)),
            pl.BlockSpec((bk, 1), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((kp, d), lambda i, j: (0, 0)),  # walk-resident
            pl.BlockSpec((kp, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((kp, d), jnp.float32),
            jax.ShapeDtypeStruct((kp, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, bn), jnp.float32),
                        pltpu.VMEM((1, bn), jnp.int32)],
        interpret=interpret,
    )(xp, wp, cp, cvp)
    return sums[:k], counts[:k, 0], cost[0, 0]


def _assign_chunked_kernel(x_ref, w_ref, c_ref, cv_ref,
                           idx_ref, cost_ref, d2_scr, *, bk: int):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _init_cost():
        cost_ref[...] = jnp.zeros(cost_ref.shape, jnp.float32)

    @pl.when(j == 0)
    def _init_panel():
        d2_scr[...] = jnp.full(d2_scr.shape, _BIG, jnp.float32)
        idx_ref[...] = jnp.zeros(idx_ref.shape, jnp.int32)

    # the running min stays in VMEM scratch; it is never written out
    x = x_ref[...].astype(jnp.float32)              # (bn, d) resident over j
    _running_min(x, c_ref, cv_ref, d2_scr, idx_ref, j * bk)

    @pl.when(j == pl.num_programs(1) - 1)
    def _cost():
        cost_ref[...] += _rowsum(w_ref[...] * d2_scr[...])


def _reduce_chunked_kernel(x_ref, w_ref, a_ref, sums_ref, cnt_ref,
                           *, bk: int):
    jc = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        sums_ref[...] = jnp.zeros(sums_ref.shape, jnp.float32)
        cnt_ref[...] = jnp.zeros(cnt_ref.shape, jnp.float32)

    # chunk-local assignment: points assigned outside [jc*bk, (jc+1)*bk)
    # match no row of the chunk and produce an all-zero one-hot column
    x = x_ref[...].astype(jnp.float32)              # (bn, d)
    onehot = _onehot(a_ref[...], w_ref[...], bk, jc * bk)
    sums_ref[...] += _scatter(onehot, x)            # (bk, d)
    cnt_ref[...] += _rowsum(onehot)


def _fused_assign_reduce_chunked_twopass(
        x: jax.Array, w: jax.Array, c: jax.Array,
        c_valid: Optional[jax.Array], *, interpret: bool,
        bn: int, bk: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Legacy two-walk chunked fused step — the fallback for center sets
    whose (kp, d) accumulators exceed ``_CHUNK_ACC_BUDGET``. Walk one
    (chunk innermost) computes (min, argmin) + cost with x resident
    across chunks; walk two (panel innermost) re-streams x once per
    center chunk to scatter into each chunk's resident accumulator."""
    n, d = x.shape
    k = c.shape[0]
    xp, wp, cp, cvp, kp = _pad_points(x, w, c, c_valid, bn,
                                      -(-k // bk) * bk)

    np_ = xp.shape[0] // bn
    nc = kp // bk
    assign, cost = pl.pallas_call(
        functools.partial(_assign_chunked_kernel, bk=bk),
        grid=(np_, nc),                              # chunk axis innermost
        in_specs=[
            pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j: (0, i)),
            pl.BlockSpec((bk, d), lambda i, j: (j, 0)),
            pl.BlockSpec((bk, 1), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bn), lambda i, j: (0, i)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(wp.shape, jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, bn), jnp.float32)],
        interpret=interpret,
    )(xp, wp, cp, cvp)

    sums, counts = pl.pallas_call(
        functools.partial(_reduce_chunked_kernel, bk=bk),
        grid=(nc, np_),                              # panel axis innermost
        in_specs=[
            pl.BlockSpec((bn, d), lambda jc, i: (i, 0)),
            pl.BlockSpec((1, bn), lambda jc, i: (0, i)),
            pl.BlockSpec((1, bn), lambda jc, i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((bk, d), lambda jc, i: (jc, 0)),
            pl.BlockSpec((bk, 1), lambda jc, i: (jc, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((kp, d), jnp.float32),
            jax.ShapeDtypeStruct((kp, 1), jnp.float32),
        ],
        interpret=interpret,
    )(xp, wp, assign)
    return sums[:k], counts[:k, 0], cost[0, 0]


def _remove_chunked_kernel(x_ref, a_ref, c_ref, cv_ref, v_ref,
                           out_ref, live_ref, d2_scr):
    j = pl.program_id(1)                             # point panel
    jc = pl.program_id(2)                            # center chunk

    @pl.when((j == 0) & (jc == 0))
    def _init_machine():
        live_ref[...] = jnp.zeros(live_ref.shape, jnp.int32)

    @pl.when(jc == 0)
    def _init_panel():
        d2_scr[...] = jnp.full(d2_scr.shape, _BIG, jnp.float32)

    x = x_ref[0].astype(jnp.float32)                 # (bn, d) resident over jc
    local_min = _panel_dmin(x, c_ref[...].astype(jnp.float32), cv_ref[...])
    d2_scr[...] = jnp.minimum(d2_scr[...], local_min)  # running min in VMEM

    @pl.when(jc == pl.num_programs(2) - 1)
    def _finish_panel():
        keep = ((a_ref[0] != 0) & (d2_scr[...] > v_ref[...])
                ).astype(jnp.int32)
        out_ref[0] = keep
        live_ref[0] += _rowsum(keep)


@functools.partial(jax.jit,
                   static_argnames=("interpret", "bn", "k_chunk"))
def remove_below_chunked_pallas(x: jax.Array, c: jax.Array,
                                alive: jax.Array, v: jax.Array,
                                c_valid: Optional[jax.Array] = None,
                                *, interpret: bool = False,
                                bn: Optional[int] = None,
                                k_chunk: Optional[int] = None
                                ) -> Tuple[jax.Array, jax.Array]:
    """Chunked-K fused SOCCER removal for center sets beyond VMEM.

    Same contract as ``remove_below_pallas``; the center set streams
    through VMEM in ``tuning.chunk_sizes`` panels (chunk axis innermost,
    each point panel resident across chunks) with a running min per point
    — already a single grid walk of ``x``.
    """
    m, p, d = x.shape
    k = c.shape[0]
    t_bn, t_bk = chunk_sizes(d, str(x.dtype))
    bn = clamp_bn(t_bn if bn is None else bn, p)
    bk = t_bk if k_chunk is None else k_chunk
    kp = -(-k // bk) * bk
    xp, ap, cp, cvp = _pad_machines(x, c, alive, c_valid, bn, kp)

    grid = (m, xp.shape[1] // bn, kp // bk)          # chunk axis innermost
    alive_new, live = pl.pallas_call(
        _remove_chunked_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bn, d), lambda i, j, jc: (i, j, 0)),
            pl.BlockSpec((1, 1, bn), lambda i, j, jc: (i, 0, j)),
            pl.BlockSpec((bk, d), lambda i, j, jc: (jc, 0)),
            pl.BlockSpec((bk, 1), lambda i, j, jc: (jc, 0)),
            pl.BlockSpec((1, 1), lambda i, j, jc: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bn), lambda i, j, jc: (i, 0, j)),
            pl.BlockSpec((1, 1, 1), lambda i, j, jc: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(ap.shape, jnp.int32),
            jax.ShapeDtypeStruct((m, 1, 1), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((1, bn), jnp.float32)],
        interpret=interpret,
    )(xp, ap, cp, cvp, _scalar(v))
    return alive_new[:, 0, :p].astype(bool), live[:, 0, 0]
