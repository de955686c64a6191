"""Pallas TPU kernel: fused pairwise min squared-distance (+ argmin).

This is the machines' per-round hot spot in SOCCER (the removal pass
``min_j rho(x_i, C_iter)^2``) and the assignment step of Lloyd. The GPU
reference implementations materialize the full (n, k) distance matrix in
HBM; on TPU we instead tile (bn x d) point panels and (bk x d) center
panels into VMEM, drive the cross term ``-2 x @ c^T`` through the MXU with
an f32 accumulator, and keep a running (min, argmin) per point across
center panels — the (n, k) matrix never exists. Arithmetic intensity per
point block is O(k·d / d) = O(k) flops/byte, so for k >= ~64 the kernel is
MXU-bound rather than HBM-bound.

Grid: (n/bn, k/bk), center panel innermost, so each point panel's running
min stays resident in VMEM across all center panels.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fused_lloyd import _BIG, _pad_points, _running_min
from repro.kernels.tuning import block_sizes, clamp_bn


def _min_dist_kernel(x_ref, c_ref, cv_ref, d2_ref, idx_ref, *, bk: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        d2_ref[...] = jnp.full(d2_ref.shape, _BIG, jnp.float32)
        idx_ref[...] = jnp.zeros(idx_ref.shape, jnp.int32)

    # ||x||^2 - 2 x.c + ||c||^2 with the cross term on the MXU; the
    # running (min, argmin) rows live in the resident output blocks
    x = x_ref[...].astype(jnp.float32)            # (bn, d)
    _running_min(x, c_ref, cv_ref, d2_ref, idx_ref, j * bk)


@functools.partial(jax.jit, static_argnames=("interpret", "bn", "bk"))
def min_dist_pallas(x: jax.Array, c: jax.Array,
                    c_valid: Optional[jax.Array] = None,
                    *, interpret: bool = False,
                    bn: Optional[int] = None, bk: Optional[int] = None
                    ) -> Tuple[jax.Array, jax.Array]:
    """Pallas min-distance; pads n/k to block multiples, trims on return.

    ``bn``/``bk`` override the tuned panel sizes (static, so the autotune
    sweep can retrace per candidate past the jit cache).
    """
    n, d = x.shape
    k = c.shape[0]
    t_bn, t_bk = block_sizes(d, k, str(x.dtype))  # shared autotune table
    bn = clamp_bn(t_bn if bn is None else bn, n)
    bk = clamp_bn(t_bk if bk is None else bk, k)
    xp, _, cp, cvp, kp = _pad_points(x, None, c, c_valid, bn,
                                     -(-k // bk) * bk)

    grid = (xp.shape[0] // bn, kp // bk)
    d2, idx = pl.pallas_call(
        functools.partial(_min_dist_kernel, bk=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bk, d), lambda i, j: (j, 0)),
            pl.BlockSpec((bk, 1), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bn), lambda i, j: (0, i)),
            pl.BlockSpec((1, bn), lambda i, j: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, xp.shape[0]), jnp.float32),
            jax.ShapeDtypeStruct((1, xp.shape[0]), jnp.int32),
        ],
        interpret=interpret,
    )(xp, cp, cvp)
    return d2[0, :n], idx[0, :n]
