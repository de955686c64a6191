"""Pallas TPU kernel: fused coreset sensitivity pass.

Sensitivity sampling (repro.coresets) scores every point against a small
bicriteria solution B before importance-sampling the shard down to a
weighted (t, d) summary. Unfused, the scoring chain streams ``x`` through
HBM three times: a min-distance pass, a per-center weight reduction for
the cluster masses, and a weighted cost reduction. The kernel here makes
exactly one grid walk over (bn, d) point panels with the (padded) center
set resident in VMEM and produces all four outputs of that chain:

* ``scores`` (n,) — w_i * min-d2_i, each panel's masked (min, argmin)
  driven through the MXU exactly like ``fused_assign_reduce``;
* ``assign`` (n,) — the argmin center (the coreset builder gathers each
  point's cluster mass through it);
* ``mass``   (k,) — per-center weight totals via the weighted one-hot,
  accumulated in VMEM across panels;
* ``cost``   ()  — the weighted cost of B (the scores' normalizer).

Unlike ``fused_assign_reduce`` the per-point outputs are written OUT (the
coreset builder needs them), so HBM traffic is one read of ``x`` plus two
(n,) writes — still ~3x less than the unfused chain.

Center sets beyond ``ops._MAX_PALLAS_K`` do not come up on the coreset
path (B has O(k) centers), so there is no chunked twin: ``ops.py``
composes the tiled ``min_dist`` kernel with the (n,)-sized oracle tail
instead (see ``sensitivity_scores`` there).

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

from repro.kernels.fused_lloyd import (_onehot, _pad_points, _panel_min,
                                       _resident_bn, _rowsum)


def _sensitivity_kernel(x_ref, w_ref, c_ref, cv_ref,
                        scores_ref, assign_ref, mass_ref, cost_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        mass_ref[...] = jnp.zeros(mass_ref.shape, jnp.float32)
        cost_ref[...] = jnp.zeros(cost_ref.shape, jnp.float32)

    x = x_ref[...].astype(jnp.float32)              # (bn, d)
    w = w_ref[...]                                  # (1, bn)
    c = c_ref[...].astype(jnp.float32)              # (kp, d)
    dmin, a = _panel_min(x, c, cv_ref[...])

    s = w * dmin
    scores_ref[...] = s
    assign_ref[...] = a
    mass_ref[...] += _rowsum(_onehot(a, w, c.shape[0]))   # (kp, 1)
    cost_ref[...] += _rowsum(s)


@functools.partial(jax.jit, static_argnames=("interpret", "bn"))
def sensitivity_scores_pallas(x: jax.Array, w: jax.Array, c: jax.Array,
                              c_valid: Optional[jax.Array] = None,
                              *, interpret: bool = False,
                              bn: Optional[int] = None
                              ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                         jax.Array]:
    """One-sweep sensitivity pass: ((n,) scores, (n,) assign, (k,) mass,
    () cost). Semantics == ``kernels.ref.sensitivity_scores_ref``."""
    n, d = x.shape
    k = c.shape[0]
    bn = _resident_bn(d, k, n, x.dtype, bn)          # centers stay resident
    xp, wp, cp, cvp, kp = _pad_points(x, w, c, c_valid, bn)

    grid = (xp.shape[0] // bn,)
    scores, assign, mass, cost = pl.pallas_call(
        _sensitivity_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((1, bn), lambda i: (0, i)),
            pl.BlockSpec((kp, d), lambda i: (0, 0)),
            pl.BlockSpec((kp, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bn), lambda i: (0, i)),
            pl.BlockSpec((1, bn), lambda i: (0, i)),
            pl.BlockSpec((kp, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(wp.shape, jnp.float32),
            jax.ShapeDtypeStruct(wp.shape, jnp.int32),
            jax.ShapeDtypeStruct((kp, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(xp, wp, cp, cvp)
    return scores[0, :n], assign[0, :n], mass[:k, 0], cost[0, 0]
