"""Pallas TPU kernel: fused weighted assign-reduce for one Lloyd step.

Given points, weights, and an assignment vector, accumulates per-center
weighted sums and counts. The accumulators (k, d) and (k,) live in VMEM for
the whole grid walk (k*d is small for clustering workloads: k_plus ~ a few
hundred, d <= a few hundred -> <= ~1 MiB), so the kernel streams each point
panel from HBM exactly once, builds the (bn, k) weighted one-hot in
registers/VMEM and drives the (k, bn) @ (bn, d) product through the MXU.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fused_lloyd import _onehot, _row, _rowsum, _scatter
from repro.kernels.tuning import block_sizes, clamp_bn


def _lloyd_kernel(x_ref, w_ref, a_ref, sums_ref, cnt_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        sums_ref[...] = jnp.zeros(sums_ref.shape, jnp.float32)
        cnt_ref[...] = jnp.zeros(cnt_ref.shape, jnp.float32)

    x = x_ref[...].astype(jnp.float32)              # (bn, d)
    onehot = _onehot(a_ref[...], w_ref[...], sums_ref.shape[0])  # (kp, bn)
    sums_ref[...] += _scatter(onehot, x)             # (kp, d)
    cnt_ref[...] += _rowsum(onehot)


@functools.partial(jax.jit, static_argnames=("k", "interpret", "bn"))
def lloyd_reduce_pallas(x: jax.Array, w: jax.Array, assign: jax.Array,
                        k: int, *, interpret: bool = False,
                        bn: Optional[int] = None
                        ) -> Tuple[jax.Array, jax.Array]:
    n, d = x.shape
    if bn is None:
        bn, _ = block_sizes(d, k, str(x.dtype))   # shared autotune table
    bn = clamp_bn(bn, n)
    n_pad = n + (-n % bn)
    kp = -(-k // 128) * 128
    xp = jnp.pad(x, ((0, n_pad - n), (0, 0)))
    wp = _row(w, n_pad)                              # pad weight 0 -> no-op rows
    ap = _row(assign, n_pad, jnp.int32)

    grid = (n_pad // bn,)
    sums, counts = pl.pallas_call(
        _lloyd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((1, bn), lambda i: (0, i)),
            pl.BlockSpec((1, bn), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((kp, d), lambda i: (0, 0)),
            pl.BlockSpec((kp, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((kp, d), jnp.float32),
            jax.ShapeDtypeStruct((kp, 1), jnp.float32),
        ],
        interpret=interpret,
    )(xp, wp, ap)
    return sums[:k], counts[:k, 0]
