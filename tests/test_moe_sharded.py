"""Explicit-EP shard_map MoE == dense pjit MoE (subprocess, 8 devices)."""
import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np, jax, jax.numpy as jnp
from repro.configs.base import ArchConfig
from jax.sharding import AxisType
from repro.models import moe
from repro.sharding.activations import activation_mesh

out = {}
for E, name in ((8, "ep"), (2, "local")):
    cfg = ArchConfig(name='t', family='moe', d_model=32, n_heads=4,
                     n_kv_heads=4, d_ff=64, vocab_size=64, n_experts=E,
                     experts_per_token=2, d_ff_expert=48,
                     n_shared_experts=1, moe_capacity_factor=8.0,
                     param_dtype='float32', compute_dtype='float32')
    p = moe.init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 4, 32)) * 0.5
    out_d, aux_d = moe._moe_apply_dense(p, cfg, x, 8.0)
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    with mesh, activation_mesh(mesh):
        out_s, aux_s = jax.jit(lambda p, x: moe.moe_apply(p, cfg, x))(p, x)
        g = jax.jit(jax.grad(
            lambda p, x: jnp.sum(moe.moe_apply(p, cfg, x)[0] ** 2)))(p, x)
    out[name] = {
        "out_diff": float(jnp.max(jnp.abs(out_d - out_s))),
        "aux_diff": abs(float(aux_d) - float(aux_s)),
        "grad_finite": bool(all(jnp.all(jnp.isfinite(l))
                                for l in jax.tree.leaves(g))),
    }
print("RESULT " + json.dumps(out))
"""


@pytest.mark.slow
def test_sharded_moe_matches_dense():
    env = dict(os.environ)
    root = pathlib.Path(__file__).resolve().parents[1]
    env["PYTHONPATH"] = str(root / "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")]
    out = json.loads(line[0][len("RESULT "):])
    for path in ("ep", "local"):
        assert out[path]["out_diff"] < 1e-4, out
        assert out[path]["aux_diff"] < 1e-5, out
        assert out[path]["grad_finite"], out


def test_bisect_threshold_equals_topk():
    """The §Perf bisection threshold is exact (matches top-k gather)."""
    from repro.configs.soccer_paper import (GaussianMixtureSpec,
                                            SoccerParams)
    from repro.core.soccer import run_soccer
    from repro.data.synthetic import gaussian_mixture, shard_points
    x, _, _ = gaussian_mixture(
        GaussianMixtureSpec(n=8_000, dim=10, k=5, sigma=0.001, seed=4))
    parts = jnp.asarray(shard_points(x, 8))
    vs = {}
    for mode in ("topk", "bisect"):
        res = run_soccer(parts, SoccerParams(
            k=5, epsilon=0.1, sharded_coordinator=True,
            sharded_threshold=mode, seed=7))
        vs[mode] = float(res.v_hist[0])
    np.testing.assert_allclose(vs["bisect"], vs["topk"], rtol=1e-5)
