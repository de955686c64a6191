"""Pure-jnp oracles for the Pallas kernels.

These are the semantics of record: every Pallas kernel is validated against
these under shape/dtype sweeps (tests/test_kernels_*.py), and they are also
the XLA execution path on non-TPU backends.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


_CHUNK_K = 4096  # center-panel size; bounds the live (n, chunk) panel

# Full f32 matmuls: a TPU runs DEFAULT-precision f32 products as one bf16
# pass, whose cross-term error (~‖x‖²·2⁻⁹) dwarfs the paper's
# within-cluster d2 (~1e-5), as does its rounding of the weighted sums.
_HI = jax.lax.Precision.HIGHEST


def min_dist_ref(x: jax.Array, c: jax.Array,
                 c_valid: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, jax.Array]:
    """Fused pairwise min squared-distance.

    Large center sets are processed in panels with a running (min, argmin)
    — the same streaming structure as the Pallas kernel — so the full
    (n, k) matrix never materializes (EIM11 grows k into the 10^5 range).

    Args:
      x: (n, d) points.
      c: (k, d) centers.
      c_valid: optional (k,) bool mask; invalid centers are ignored.

    Returns:
      d2:  (n,) float32 — min_j ||x_i - c_j||^2 over valid centers (>= 0).
      idx: (n,) int32   — argmin_j.
    """
    xf = x.astype(jnp.float32)
    k = c.shape[0]
    if c_valid is None:
        c_valid = jnp.ones((k,), bool)

    def panel(cf, cv):
        c2 = jnp.sum(cf * cf, axis=-1)
        d2 = -2.0 * jnp.matmul(xf, cf.T, precision=_HI) + c2[None, :]
        d2 = jnp.where(cv[None, :], d2, jnp.inf)
        loc = jnp.argmin(d2, axis=-1).astype(jnp.int32)
        return jnp.min(d2, axis=-1), loc

    if k <= _CHUNK_K:
        dmin, idx = panel(c.astype(jnp.float32), c_valid)
    else:
        pad = -k % _CHUNK_K
        cp = jnp.pad(c.astype(jnp.float32), ((0, pad), (0, 0)))
        cvp = jnp.pad(c_valid, (0, pad))
        nc = cp.shape[0] // _CHUNK_K
        cp = cp.reshape(nc, _CHUNK_K, -1)
        cvp = cvp.reshape(nc, _CHUNK_K)

        def body(carry, ch):
            best, barg, j = carry
            cf, cv = ch
            dmin, loc = panel(cf, cv)
            better = dmin < best
            barg = jnp.where(better, loc + j * _CHUNK_K, barg)
            best = jnp.where(better, dmin, best)
            return (best, barg, j + 1), None

        n = xf.shape[0]
        init = (jnp.full((n,), jnp.inf, jnp.float32),
                jnp.zeros((n,), jnp.int32), jnp.int32(0))
        (dmin, idx, _), _ = jax.lax.scan(body, init, (cp, cvp))

    x2 = jnp.sum(xf * xf, axis=-1)
    return jnp.maximum(dmin + x2, 0.0), idx


def update_min_dist_ref(x: jax.Array, w: jax.Array, c: jax.Array,
                        d2: jax.Array,
                        c_valid: Optional[jax.Array] = None
                        ) -> Tuple[jax.Array, jax.Array]:
    """Oracle for the incremental D²-seeding update.

    One seeding step of (distributed) k-means++ lowers the running
    min-distance against the newly chosen center(s) and needs the total
    weighted sampling mass ``sum_i w_i * d2_i`` for the next categorical
    draw. The Pallas kernel fuses both into a single sweep of ``x``; the
    unfused path reads ``x`` plus three (n,) arrays per center.

    Args:
      x: (n, d) points.
      w: (n,) float weights (0 for padded rows).
      c: (kc, d) newly added centers (kc == 1 for sequential seeding;
         a whole candidate block for k-means‖-style rounds).
      d2: (n,) running min squared distance before this update.
      c_valid: optional (kc,) bool mask; with zero valid centers the
        update is a no-op (d2 passes through unchanged).

    Returns:
      d2_new: (n,) float32 — min(d2, min_j ||x_i - c_j||^2), elementwise
              monotone non-increasing in ``d2``.
      mass:   ()  float32 — sum_i w_i * d2_new_i.
    """
    # min_dist_ref returns +inf with zero valid centers, so the min below
    # is already the required no-op (and big candidate blocks inherit its
    # center-panel streaming)
    cand, _ = min_dist_ref(x, c, c_valid)
    d2_new = jnp.minimum(d2.astype(jnp.float32), cand)
    mass = jnp.sum(w.astype(jnp.float32) * d2_new)
    return d2_new, mass


def sensitivity_from_min(w: jax.Array, d2: jax.Array, assign: jax.Array,
                         k: int) -> Tuple[jax.Array, jax.Array, jax.Array,
                                          jax.Array]:
    """(scores, assign, mass, cost) from a completed min-distance pass.

    The shared tail of the sensitivity oracle and the chunked-K dispatch
    path in ``kernels/ops.py``: everything here is (n,)/(k,)-sized — no
    sweep of ``x``.
    """
    wf = w.astype(jnp.float32)
    scores = wf * d2.astype(jnp.float32)
    mass = jax.ops.segment_sum(wf, assign, num_segments=k)
    return scores, assign, mass, jnp.sum(scores)


def sensitivity_scores_ref(x: jax.Array, w: jax.Array, c: jax.Array,
                           c_valid: Optional[jax.Array] = None
                           ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                      jax.Array]:
    """Oracle for the coreset sensitivity pass (repro.coresets).

    Sensitivity sampling needs, per point, its weighted cost share
    against a bicriteria solution B and the weight mass of its B-cluster;
    the Pallas kernel produces all of it in ONE sweep of ``x`` instead of
    the min_dist -> lloyd_reduce-counts -> cost-reduction chain.

    Requires at least one valid center (the coreset builder seeds B with
    k-means++, which guarantees it); with zero valid centers the oracle's
    +inf distances and the kernel's finite sentinel diverge.

    Args:
      x: (n, d) points.
      w: (n,) float weights (0 for padded rows).
      c: (k, d) bicriteria centers B.
      c_valid: optional (k,) bool mask; invalid centers are ignored.

    Returns:
      scores: (n,) float32 — w_i * min-d2_i (the cost term's numerator).
      assign: (n,) int32   — argmin center per point.
      mass:   (k,) float32 — sum of w over the points assigned to each
              center (invalid centers receive no mass).
      cost:   ()   float32 — sum of scores (weighted cost of B).
    """
    d2, assign = min_dist_ref(x, c, c_valid)
    return sensitivity_from_min(w, d2, assign, c.shape[0])


def truncated_from_min(w: jax.Array, d2: jax.Array, v: jax.Array
                       ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(kept_cost, tail_mass, tail_cost) from a completed min-distance
    pass — the shared tail of the truncated-cost oracle and the chunked-K
    dispatch path in ``kernels/ops.py`` (everything here is (n,)-sized).
    """
    wf = w.astype(jnp.float32)
    s = jnp.where(wf > 0, wf * d2.astype(jnp.float32), 0.0)
    below = d2 <= v
    kept_cost = jnp.sum(jnp.where(below, s, 0.0))
    tail_mass = jnp.sum(jnp.where(below, 0.0, wf))
    tail_cost = jnp.sum(jnp.where(below, 0.0, s))
    return kept_cost, tail_mass, tail_cost


def truncated_cost_ref(x: jax.Array, w: jax.Array, c: jax.Array,
                       v: jax.Array,
                       c_valid: Optional[jax.Array] = None
                       ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Oracle for the fused threshold-split truncated-cost pass.

    The robust ((k, z)-means) tier's scoring statistic: one sweep of
    ``x`` splits the weighted cost of ``c`` at the distance threshold
    ``v`` — kept cost below, (mass, cost) of the tail above — without
    ever materializing the (n,) distance array for a sort. Summing the
    per-machine triples over a psum yields the global truncated cost and
    the weight mass the threshold would trim (repro.robust).

    Requires at least one valid center (like ``sensitivity_scores_ref``):
    with all centers invalid the oracle's +inf distances and the Pallas
    kernel's finite sentinel land the tail on different sides of any
    finite ``v``.

    Args:
      x: (n, d) points.
      w: (n,) float weights (0 for padded rows — they contribute to
         neither side regardless of where their distance lands).
      c: (k, d) centers.
      v: () distance threshold (squared units, inclusive below).
      c_valid: optional (k,) bool mask; invalid centers are ignored.

    Returns:
      kept_cost: () float32 — sum of w·d2 over points with d2 <= v.
      tail_mass: () float32 — sum of w over points with d2 > v.
      tail_cost: () float32 — sum of w·d2 over points with d2 > v.
    """
    d2, _ = min_dist_ref(x, c, c_valid)
    return truncated_from_min(w, d2, v)


def lloyd_reduce_ref(x: jax.Array, w: jax.Array, assign: jax.Array,
                     k: int) -> Tuple[jax.Array, jax.Array]:
    """Weighted per-center accumulation for one Lloyd step.

    Args:
      x: (n, d) points.
      w: (n,) float weights (0 for padded/removed points).
      assign: (n,) int32 center assignment in [0, k).

    Returns:
      sums:   (k, d) float32 — sum of w_i * x_i per center.
      counts: (k,)  float32 — sum of w_i per center.
    """
    if k > _CHUNK_K:
        # large center sets (EIM11): scatter-reduce, no (n, k) one-hot
        wf = w.astype(jnp.float32)
        sums = jax.ops.segment_sum(x.astype(jnp.float32) * wf[:, None],
                                   assign, num_segments=k)
        counts = jax.ops.segment_sum(wf, assign, num_segments=k)
        return sums, counts
    onehot = (assign[:, None] == jnp.arange(k, dtype=assign.dtype)[None, :])
    onehot = onehot.astype(jnp.float32) * w.astype(jnp.float32)[:, None]
    sums = jnp.matmul(onehot.T, x.astype(jnp.float32), precision=_HI)
    counts = jnp.sum(onehot, axis=0)
    return sums, counts


def fused_assign_reduce_ref(x: jax.Array, w: jax.Array, c: jax.Array,
                            c_valid: Optional[jax.Array] = None
                            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Oracle for the one-sweep Lloyd step: assignment + reduction + cost.

    Composes :func:`min_dist_ref` and :func:`lloyd_reduce_ref`; the Pallas
    kernel fuses both into a single HBM sweep of ``x``.

    Returns:
      sums:   (k, d) float32 — sum of w_i * x_i per assigned center.
      counts: (k,)  float32 — sum of w_i per assigned center.
      cost:   ()    float32 — sum of w_i * min-d2_i (the weighted cost of
              ``c`` on (x, w), i.e. the pre-update cost of this step).
    """
    d2, assign = min_dist_ref(x, c, c_valid)
    sums, counts = lloyd_reduce_ref(x, w, assign, c.shape[0])
    cost = jnp.sum(w.astype(jnp.float32) * d2)
    return sums, counts, cost


def remove_below_ref(x: jax.Array, c: jax.Array, alive: jax.Array,
                     v: jax.Array,
                     c_valid: Optional[jax.Array] = None
                     ) -> Tuple[jax.Array, jax.Array]:
    """Oracle for the fused SOCCER removal pass.

    Args:
      x: (m, p, d) machine-sharded points.
      c: (k, d) round centers C_iter.
      alive: (m, p) bool current mask.
      v: () removal threshold.
      c_valid: optional (k,) bool mask.

    Returns:
      alive_new: (m, p) bool — alive & (min_j ||x - c_j||^2 > v).
      live:      (m,) int32 — per-machine surviving counts.
    """
    m, p, d = x.shape
    d2, _ = min_dist_ref(x.reshape(m * p, d), c, c_valid)
    alive_new = alive & (d2.reshape(m, p) > v)
    return alive_new, jnp.sum(alive_new, axis=1).astype(jnp.int32)
