"""The paper's §8 Gaussian mixture, drawn on the device from a seed.

The recipe of ``repro.data.synthetic.gaussian_mixture``: ``k`` means
uniform in the unit cube of R^dim, Zipf(``zipf_gamma``) component weights
and spherical noise of standard deviation ``sigma``. Here it is drawn
with ``jax.random`` where the data lives: machine ``j`` draws its own
``(p, dim)`` share from the data key folded with ``j``, so one chip
holding all machines and a mesh holding one machine per chip get the same
points for the same seed, and no point crosses the host.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def base_key(seed: int) -> jax.Array:
    """PRNG key of a seed in [0, 2**64): low 32 bits seed it, the high 32
    are folded in, so seeds past 32 bits stay distinct."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"--seed must be in [0, 2**64), got {seed}")
    key = jax.random.PRNGKey(np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32(seed >> 32))


def fit_key(seed: int, i: int) -> jax.Array:
    """The key of the window's fit ``i`` (the warm-up fit is ``i = -1``)."""
    return jax.random.fold_in(jax.random.fold_in(base_key(seed), 1),
                              np.uint32(i & 0xFFFFFFFF))


def parts(arr) -> list:
    """Per-device pieces of ``arr`` in machine order (one on one chip)."""
    shards = sorted(arr.addressable_shards,
                    key=lambda s: s.index[0].start or 0)
    return [s.data for s in shards]


def _zipf_cdf(k: int, gamma: float) -> np.ndarray:
    w = np.arange(1, k + 1, dtype=np.float64) ** (-gamma)
    return (np.cumsum(w) / w.sum()).astype(np.float32)


def _machine(key, j, means, cdf, p: int, sigma: float):
    """One machine's ``(p, dim)`` points: labels by inverse Zipf CDF."""
    k_lab, k_noise = jax.random.split(jax.random.fold_in(key, j))
    u = jax.random.uniform(k_lab, (p,), jnp.float32)
    labels = jnp.minimum(jnp.searchsorted(cdf, u, side="right"),
                         cdf.shape[0] - 1)
    noise = jax.random.normal(k_noise, (p, means.shape[1]), jnp.float32)
    return means[labels] + jnp.float32(sigma) * noise


def mixture(cfg: dict, seed: int, mesh=None):
    """``(x, means)``: ``x`` of shape ``(machines, n // machines, dim)``
    float32 on the device(s), ``means`` ``(k, dim)`` float32 replicated.

    With ``mesh`` (one machine per device along its one axis) each device
    draws its own machines; otherwise one jitted call on the default
    device draws them all.
    """
    m, n, dim, k = cfg["machines"], cfg["n"], cfg["dim"], cfg["k"]
    if n % m:
        raise ValueError(f"n={n} is not a multiple of machines={m}")
    p = n // m
    k_means, k_data = jax.random.split(base_key(seed))
    cdf = jnp.asarray(_zipf_cdf(k, cfg["zipf_gamma"]))
    sigma = cfg["sigma"]

    def draw(k_means, k_data, machine_ids):
        means = jax.random.uniform(k_means, (k, dim), jnp.float32)
        x = jax.vmap(lambda j: _machine(k_data, j, means, cdf, p, sigma))(
            machine_ids)
        return x, means

    if mesh is None:
        return jax.jit(draw)(k_means, k_data, jnp.arange(m, dtype=jnp.int32))

    from jax.sharding import NamedSharding, PartitionSpec as P
    axis = mesh.axis_names[0]
    ids = jax.device_put(jnp.arange(m, dtype=jnp.int32),
                         NamedSharding(mesh, P(axis)))
    fn = jax.shard_map(draw, mesh=mesh, in_specs=(P(), P(), P(axis)),
                       out_specs=(P(axis), P()), check_vma=False)
    return jax.jit(fn)(k_means, k_data, ids)
