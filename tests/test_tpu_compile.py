"""Every Pallas kernel compiles for a TPU v5e at the paper's widths.

Interpret mode (the rest of the suite) cannot see what the chip's
compiler refuses: misaligned blocks, 1-D blocks that disagree with XLA's
tiling, scalar stores to VMEM, DMA slices the memory layout forbids.
These tests hand each ``pallas_call`` variant to the TPU compiler for a
*described* ``v5e:2x2`` chip — no chip needed — at the main path's
shapes: one machine's share of the §8 workload (1,280,000 points,
d = 15), k = 25 centers (k_plus = 103 for the removal pass) and
k = 2048 > ``ops._MAX_PALLAS_K`` for the chunked variants. Each compiled
program must hold the kernel (``tpu_custom_call``).

Nothing here runs a kernel, and the shapes cost no memory. The topology
is described inside a fixture, never while a module is imported: only
the test worker that runs this file loads the TPU compiler library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fused_lloyd import (fused_assign_reduce_chunked_pallas,
                                       fused_assign_reduce_pallas,
                                       remove_below_chunked_pallas,
                                       remove_below_pallas,
                                       update_min_dist_pallas)
from repro.kernels.lloyd import lloyd_reduce_pallas
from repro.kernels.min_dist import min_dist_pallas
from repro.kernels.sensitivity import sensitivity_scores_pallas
from repro.kernels.truncated import truncated_cost_pallas

N, D, M = 1_280_000, 15, 8        # one machine of the 10.24M-point workload
K, K_PLUS, K_BIG = 25, 103, 2048


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no compiler logs
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _points(n=N, dtype=jnp.float32):
    return [((n, D), dtype), ((n,), jnp.float32)]


# name -> (kernel call, [(shape, dtype) of each argument])
CASES = {
    "min_dist": (
        lambda x, c: min_dist_pallas(x, c),
        [((N, D), jnp.float32), ((K, D), jnp.float32)]),
    "min_dist_center_panels": (
        lambda x, c, v: min_dist_pallas(x, c, v),
        [((N, D), jnp.float32), ((K_BIG, D), jnp.float32),
         ((K_BIG,), jnp.bool_)]),
    "lloyd_reduce": (
        lambda x, w, a: lloyd_reduce_pallas(x, w, a, K),
        _points() + [((N,), jnp.int32)]),
    "fused_assign_reduce": (
        lambda x, w, c: fused_assign_reduce_pallas(x, w, c),
        _points() + [((K, D), jnp.float32)]),
    "fused_assign_reduce_bf16": (
        lambda x, w, c: fused_assign_reduce_pallas(x, w, c),
        _points(dtype=jnp.bfloat16) + [((K, D), jnp.bfloat16)]),
    "fused_assign_reduce_chunked": (
        lambda x, w, c: fused_assign_reduce_chunked_pallas(x, w, c),
        _points() + [((K_BIG, D), jnp.float32)]),
    "fused_assign_reduce_chunked_twopass": (
        lambda x, w, c: fused_assign_reduce_chunked_pallas(
            x, w, c, acc_budget=0),
        _points() + [((K_BIG, D), jnp.float32)]),
    "remove_below": (
        lambda x, c, a, v: remove_below_pallas(x, c, a, v),
        [((M, N, D), jnp.float32), ((K_PLUS, D), jnp.float32),
         ((M, N), jnp.bool_), ((), jnp.float32)]),
    "remove_below_mesh_local": (                 # one machine per chip
        lambda x, c, a, v: remove_below_pallas(x, c, a, v),
        [((1, N, D), jnp.float32), ((K_PLUS, D), jnp.float32),
         ((1, N), jnp.bool_), ((), jnp.float32)]),
    "remove_below_chunked": (
        lambda x, c, a, v: remove_below_chunked_pallas(x, c, a, v),
        [((M, N, D), jnp.float32), ((K_BIG, D), jnp.float32),
         ((M, N), jnp.bool_), ((), jnp.float32)]),
    "update_min_dist": (
        lambda x, w, c, d2: update_min_dist_pallas(x, w, c, d2),
        _points() + [((K, D), jnp.float32), ((N,), jnp.float32)]),
    "sensitivity_scores": (
        lambda x, w, c: sensitivity_scores_pallas(x, w, c),
        _points() + [((K, D), jnp.float32)]),
    "truncated_cost": (
        lambda x, w, c, v: truncated_cost_pallas(x, w, c, v),
        _points() + [((K, D), jnp.float32), ((), jnp.float32)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    fn, specs = CASES[name]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
