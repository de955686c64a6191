"""Pallas TPU kernel: fused threshold-split truncated cost.

The outlier-robust tier (truncated-cost SOCCER, the ``kzmeans``
baseline — repro.robust) repeatedly needs the weighted clustering cost
split at a distance threshold: the cost of the points within ``v`` of
their nearest center, and the (weight mass, cost) of the tail beyond it.
Unfused, that is a full min-distance sweep materializing the (n,)
distance array plus three (n,)-sized reductions; the kernel here makes
exactly one grid walk over (bn, d) point panels with the (padded) center
set resident in VMEM and accumulates the three scalars in place:

* ``kept_cost`` () — sum of w·min-d2 over points with min-d2 <= v,
  each panel's masked min driven through the MXU exactly like
  ``fused_assign_reduce``;
* ``tail_mass`` () — sum of w over points with min-d2 > v (the weight
  the threshold would trim — the (k, z) bookkeeping quantity);
* ``tail_cost`` () — sum of w·min-d2 over the same tail.

Nothing (n,)-sized is ever written back: HBM traffic is one read of
``x`` and three scalars out, so scoring a (k, z) objective over the full
(m, p, d) data costs the same sweep as a removal pass.

Center sets beyond ``ops._MAX_PALLAS_K`` run through the tiled
``min_dist`` kernel with the (n,)-sized tail in XLA (``ops.py``
composes them, mirroring ``sensitivity_scores``). Requires at least one
valid center: with all centers invalid the oracle's +inf and this
kernel's finite sentinel land the tail on different sides of ``v``.

All inputs may be float32, bfloat16 or float16 (every ``UPLINK_DTYPES``
precision); accumulation is float32. Block sizes come from the shared
autotune table in ``kernels.tuning``.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fused_lloyd import (_pad_points, _panel_dmin, _rowsum,
                                       _scalar)
from repro.kernels.tuning import block_sizes, clamp_bn


def _truncated_kernel(x_ref, w_ref, c_ref, cv_ref, v_ref,
                      kept_ref, tmass_ref, tcost_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        kept_ref[...] = jnp.zeros(kept_ref.shape, jnp.float32)
        tmass_ref[...] = jnp.zeros(tmass_ref.shape, jnp.float32)
        tcost_ref[...] = jnp.zeros(tcost_ref.shape, jnp.float32)

    x = x_ref[...].astype(jnp.float32)              # (bn, d)
    w = w_ref[...]                                  # (1, bn)
    c = c_ref[...].astype(jnp.float32)              # (kp, d)
    dmin = _panel_dmin(x, c, cv_ref[...])

    below = dmin <= v_ref[...]
    s = jnp.where(w > 0, w * dmin, 0.0)             # padded rows: no side
    kept_ref[...] += _rowsum(jnp.where(below, s, 0.0))
    tmass_ref[...] += _rowsum(jnp.where(below, 0.0, w))
    tcost_ref[...] += _rowsum(jnp.where(below, 0.0, s))


@functools.partial(jax.jit, static_argnames=("interpret", "bn"))
def truncated_cost_pallas(x: jax.Array, w: jax.Array, c: jax.Array,
                          v: jax.Array,
                          c_valid: Optional[jax.Array] = None,
                          *, interpret: bool = False,
                          bn: Optional[int] = None
                          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-sweep truncated-cost split: (() kept_cost, () tail_mass,
    () tail_cost). Semantics == ``kernels.ref.truncated_cost_ref``."""
    n, d = x.shape
    k = c.shape[0]
    if bn is None:
        bn, _ = block_sizes(d, k, str(x.dtype))
    bn = clamp_bn(bn, n)
    xp, wp, cp, cvp, kp = _pad_points(x, w, c, c_valid, bn)  # centers
    # stay resident; weight-0 padding rows land on neither side

    grid = (xp.shape[0] // bn,)
    scalar = pl.BlockSpec((1, 1), lambda i: (0, 0))
    kept, tmass, tcost = pl.pallas_call(
        _truncated_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((1, bn), lambda i: (0, i)),
            pl.BlockSpec((kp, d), lambda i: (0, 0)),
            pl.BlockSpec((kp, 1), lambda i: (0, 0)),
            scalar,
        ],
        out_specs=[scalar, scalar, scalar],
        out_shape=[jax.ShapeDtypeStruct((1, 1), jnp.float32)] * 3,
        interpret=interpret,
    )(xp, wp, cp, cvp, _scalar(v))
    return kept[0, 0], tmass[0, 0], tcost[0, 0]
