"""Plain float32 reference for what a fit returns, and its control.

``exact_min`` is the difference-form nearest-center distance of
``chip_smoke.py``: explicit ``x - c`` in float32 and no matmul, so no MXU
pass and no cancellation between ``|x|^2`` and ``|c|^2``. It imports
nothing of the program. Every function here works in blocks of rows
inside one jitted ``lax.map``, so the reference fits next to the data.

``expanded_min`` is the control: the same minimum by the expansion
``|x|^2 - 2 x.c + |c|^2`` that the program's kernels use, with the cross
term from three bf16 passes: ``Precision.HIGH``, the step below the
configuration's ``HIGHEST`` (six). ``control_remove_below`` puts it in
the place of the program's removal kernel.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 8192


def _blocks(x, block: int):
    """(..., d) points -> ((nb, block, d) zero-padded blocks, n)."""
    d = x.shape[-1]
    x = x.reshape(-1, d).astype(jnp.float32)
    n = x.shape[0]
    return jnp.pad(x, ((0, -n % block), (0, 0))).reshape(-1, block, d), n


def exact_min(x, c, valid=None, block: int = BLOCK):
    """Float32 min over valid centers of sum((x - c)^2), by explicit
    differences: ``(n,)`` squared distances, leading axes of ``x``
    flattened."""
    ct = jnp.asarray(c, jnp.float32).T                  # (d, k) lanes = k
    ok = (jnp.ones((c.shape[0],), bool) if valid is None
          else jnp.asarray(valid, bool))
    xb, n = _blocks(x, block)

    def one(xs):
        diff = xs[:, :, None] - ct[None]                # (block, d, k)
        d2 = jnp.where(ok[None], jnp.sum(diff * diff, axis=1), jnp.inf)
        return jnp.min(d2, axis=1)

    return jax.lax.map(one, xb).reshape(-1)[:n]


def _bf16(a):
    """``a`` rounded to bfloat16, kept in float32: ``reduce_precision``,
    which XLA does not fold away as it may a pair of converts."""
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def high_dot(a, b):
    """``a @ b.T`` as ``Precision.HIGH`` computes it: three bf16 passes
    (hi*hi + hi*lo + lo*hi) accumulated in float32, written out so that it
    means the same on every backend (XLA's CPU backend ignores HIGH). Each
    pass multiplies values that bf16 holds exactly, at ``HIGHEST``, so its
    products are exact."""
    def dot(u, v):
        return jnp.dot(u, v.T, precision=jax.lax.Precision.HIGHEST)
    ah, bh = _bf16(a), _bf16(b)
    return dot(ah, bh) + dot(ah, _bf16(b - bh)) + dot(_bf16(a - ah), bh)


def expanded_min(x, c, block: int = BLOCK):
    """The control: min over centers of |x|^2 - 2 x.c + |c|^2 with the
    cross term from ``high_dot``."""
    c = jnp.asarray(c, jnp.float32)
    c2 = jnp.sum(c * c, axis=1)
    xb, n = _blocks(x, block)

    def one(xs):
        d2 = jnp.sum(xs * xs, axis=1, keepdims=True) - 2.0 * high_dot(
            xs, c) + c2
        return jnp.maximum(jnp.min(d2, axis=1), 0.0)

    return jax.lax.map(one, xb).reshape(-1)[:n]


def control_remove_below(x, c, alive, v, c_valid=None, *, backend=None):
    """The control in ``kernels.ops.remove_below``'s place: SOCCER's removal
    with the distances of ``expanded_min`` at three bf16 passes."""
    del backend
    if c_valid is not None:
        raise NotImplementedError("SOCCER's removal passes no c_valid")
    d2 = expanded_min(x, c).reshape(alive.shape)
    keep = alive & (d2 > v)
    return keep, jnp.sum(keep, axis=1).astype(jnp.int32)


@jax.jit
def _cost_parts(x, c):
    """Per-block float32 sums of the exact min-d2 (summed in float64 on
    the host)."""
    d2 = exact_min(x, c)
    return jnp.sum(jnp.pad(d2, (0, -d2.shape[0] % BLOCK)).reshape(-1, BLOCK),
                   axis=1)


def cost(x, c) -> float:
    """k-means cost sum_i min_j |x_i - c_j|^2 of centers ``c`` on ``x``."""
    c = np.asarray(c, np.float32)        # uncommitted: follows x's device
    return float(np.sum(np.asarray(_cost_parts(x, c), np.float64)))


@jax.jit
def _keep(x, c, v, alive):
    """Survivors of one removal: alive and min-d2 > v (flat (n,) mask)."""
    return alive & (exact_min(x, c) > v)


def removal_chain(x, rounds):
    """The survivors after each round, starting with every point alive.

    ``rounds`` is a list of ``(centers, v)``: each round removes the
    points within ``v`` of its centers. Returns ``[(n,) bool mask]`` on the
    device, one per round.
    """
    n = int(np.prod(x.shape[:-1]))
    alive = jnp.ones((n,), bool)
    out = []
    for c, v in rounds:
        alive = _keep(x, jnp.asarray(c, jnp.float32), jnp.float32(v), alive)
        out.append(alive)
    return out


@jax.jit
def count_diff(a, b):
    """Number of points on which two masks of one shape disagree."""
    return jnp.sum(a.reshape(-1) != b.reshape(-1))
