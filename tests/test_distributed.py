"""Mesh (shard_map) == Virtual equivalence through the facade, run in a
subprocess with 8 host devices so the main test process keeps its single
real device. Covers: SOCCER virtual/mesh numerics, facade bit-parity
with the legacy drivers on both backends, and one mesh fit() per
registered algorithm."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np, jax, jax.numpy as jnp
from repro.api import MeshBackend, fit, list_algorithms
from repro.configs.soccer_paper import GaussianMixtureSpec, SoccerParams
from repro.data.synthetic import gaussian_mixture, shard_points
from repro.core.soccer import run_soccer
from repro.core.metrics import centralized_cost

spec = GaussianMixtureSpec(n=8_000, dim=10, k=5, sigma=0.001, seed=3)
x, _, _ = gaussian_mixture(spec)
parts = jnp.asarray(shard_points(x, 8))
xg = jnp.asarray(x)
mesh = jax.make_mesh((4, 2), ("data", "model"))
out = {}
for sharded in (False, True):
    params = SoccerParams(k=5, epsilon=0.1, seed=3,
                          sharded_coordinator=sharded)
    rv = run_soccer(parts, params)
    rm = run_soccer(parts, params, backend=MeshBackend(mesh))
    out[f"virtual_cost_{sharded}"] = float(
        centralized_cost(xg, jnp.asarray(rv.centers)))
    out[f"mesh_cost_{sharded}"] = float(
        centralized_cost(xg, jnp.asarray(rm.centers)))
    out[f"rounds_match_{sharded}"] = (rv.rounds == rm.rounds)
    out[f"centers_allclose_{sharded}"] = bool(
        rv.centers.shape == rm.centers.shape
        and np.allclose(rv.centers, rm.centers, atol=1e-3))

# facade must be bit-identical to the legacy drivers on both backends
params = SoccerParams(k=5, epsilon=0.1, seed=3)
rv = run_soccer(parts, params)
rm = run_soccer(parts, params, backend=MeshBackend(mesh))
fv = fit(parts, 5, algo="soccer", backend="virtual", epsilon=0.1, seed=3)
fm = fit(parts, 5, algo="soccer", backend=MeshBackend(mesh), epsilon=0.1,
         seed=3)
out["facade_virtual_identical"] = bool(
    np.array_equal(fv.centers, rv.centers) and fv.rounds == rv.rounds)
out["facade_mesh_identical"] = bool(
    np.array_equal(fm.centers, rm.centers) and fm.rounds == rm.rounds)

# every registered algorithm runs on the mesh backend, and same-seed
# reruns are bit-identical (seed determinism on the mesh leg; the
# virtual leg lives in test_api.py)
tiny = {"soccer": dict(epsilon=0.2),
        "kmeans_parallel": dict(rounds=2, lloyd_iters=5),
        "eim11": dict(epsilon=0.2, max_rounds=3),
        "lloyd": dict(iters=5),
        "minibatch": dict(batch=128, steps=10),
        "coreset_kmeans": dict(coreset_size=512, lloyd_iters=5),
        "kzmeans": dict(coreset_size=512, lloyd_iters=5,
                        outlier_frac=0.02)}
mesh_ok, mesh_det = {}, {}
for algo in list_algorithms():
    r = fit(parts, 5, algo=algo, backend=MeshBackend(mesh), seed=4,
            **tiny.get(algo, {}))
    r2 = fit(parts, 5, algo=algo, backend=MeshBackend(mesh), seed=4,
             **tiny.get(algo, {}))
    mesh_ok[algo] = bool(np.all(np.isfinite(r.centers))
                         and r.backend == "mesh"
                         and np.isfinite(r.cost(xg)))
    mesh_det[algo] = bool(np.array_equal(r.centers, r2.centers)
                          and r.rounds == r2.rounds)
out["mesh_algos"] = mesh_ok
out["mesh_determinism"] = mesh_det

# coreset-compressed SOCCER uplink: virtual == mesh (same math, the
# fixed-width weighted gather is an all-gather on the mesh), and the
# compressed rows are fewer than the raw-sample upload on both
ckw = dict(epsilon=0.1, seed=3, eta_override=1600, uplink_mode="coreset")
ccv = fit(parts, 5, algo="soccer", backend="virtual", **ckw)
ccm = fit(parts, 5, algo="soccer", backend=MeshBackend(mesh), **ckw)
raw = fit(parts, 5, algo="soccer", backend="virtual", epsilon=0.1, seed=3,
          eta_override=1600)
out["coreset_uplink_mesh_matches_virtual"] = bool(
    ccv.rounds == ccm.rounds
    and np.array_equal(ccv.uplink_points, ccm.uplink_points)
    and ccv.centers.shape == ccm.centers.shape
    and np.allclose(ccv.centers, ccm.centers, atol=1e-3))
out["coreset_uplink_below_raw"] = bool(
    ccv.uplink_bytes_total < raw.uplink_bytes_total
    and ccm.uplink_bytes_total < raw.uplink_bytes_total)
print("RESULT " + json.dumps(out))
"""


@pytest.mark.slow
def test_virtual_equals_mesh_subprocess():
    env = dict(os.environ)
    root = pathlib.Path(__file__).resolve().parents[1]
    env["PYTHONPATH"] = str(root / "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")]
    assert line, proc.stdout
    out = json.loads(line[0][len("RESULT "):])
    # paper-faithful (gather) mode must be bit-comparable
    assert out["rounds_match_False"]
    assert out["centers_allclose_False"], out
    # sharded-coordinator mode: same rounds, comparable cost
    assert out["rounds_match_True"]
    assert out["mesh_cost_True"] <= 1.5 * out["virtual_cost_True"] + 1e-3
    # facade == legacy, bit-identical on both backends
    assert out["facade_virtual_identical"]
    assert out["facade_mesh_identical"]
    # every registered algorithm produces finite results on the mesh
    assert all(out["mesh_algos"].values()), out["mesh_algos"]
    # same seed -> bit-identical centers on the mesh backend
    assert all(out["mesh_determinism"].values()), out["mesh_determinism"]
    # coreset-compressed uplink: mesh == virtual, fewer bytes than raw
    assert out["coreset_uplink_mesh_matches_virtual"], out
    assert out["coreset_uplink_below_raw"], out
