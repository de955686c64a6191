"""The repro.api facade: registry, parity with legacy drivers, result
shape invariants, params validation, and the weighted-sizing fix."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ClusterResult, fit, list_algorithms
from repro.configs.soccer_paper import GaussianMixtureSpec, SoccerParams
from repro.core.metrics import centralized_cost
from repro.core.soccer import derive_constants, run_soccer
from repro.data.synthetic import gaussian_mixture, shard_points

M, K = 8, 5

# per-algorithm knobs keeping this suite fast at tiny n
TINY = {
    "soccer": dict(epsilon=0.2),
    "kmeans_parallel": dict(rounds=2, lloyd_iters=5),
    "eim11": dict(epsilon=0.2, max_rounds=3),
    "lloyd": dict(iters=5),
    "minibatch": dict(batch=128, steps=10),
    "coreset_kmeans": dict(coreset_size=512, lloyd_iters=5),
    "kzmeans": dict(coreset_size=512, lloyd_iters=5, outlier_frac=0.02),
}
# upper bound on communication rounds for each algorithm at TINY params
MAX_ROUNDS = {"soccer": 7 + 1, "kmeans_parallel": 2, "eim11": 3,
              "lloyd": 1, "minibatch": 1, "coreset_kmeans": 1,
              "kzmeans": 1}


@pytest.fixture(scope="module")
def data():
    spec = GaussianMixtureSpec(n=6_000, dim=8, k=K, sigma=0.001, seed=11)
    x, _, means = gaussian_mixture(spec)
    return x, jnp.asarray(shard_points(x, M)), means


def test_fit_soccer_bit_identical_to_legacy(data):
    x, parts, _ = data
    legacy = run_soccer(parts, SoccerParams(k=K, epsilon=0.1, seed=3))
    res = fit(parts, K, algo="soccer", backend="virtual", epsilon=0.1,
              seed=3)
    assert np.array_equal(res.centers, legacy.centers)
    assert res.rounds == legacy.rounds
    assert np.array_equal(res.uplink_points,
                          legacy.uplink[: legacy.rounds + 1])


def test_registry_all_algos_well_formed(data):
    x, parts, _ = data
    d = parts.shape[-1]
    algos = list_algorithms()
    assert set(algos) >= {"soccer", "kmeans_parallel", "eim11", "lloyd",
                          "minibatch", "coreset_kmeans"}
    for algo in algos:
        res = fit(parts, K, algo=algo, backend="virtual", seed=0,
                  **TINY.get(algo, {}))
        assert isinstance(res, ClusterResult)
        assert res.algo == algo and res.backend == "virtual"
        assert res.centers.ndim == 2 and res.centers.shape[1] == d
        assert np.all(np.isfinite(res.centers)), algo
        assert 0 <= res.rounds <= MAX_ROUNDS[algo], (algo, res.rounds)
        # uplink histories: parallel points/bytes, nonneg, bytes = pts*d*4
        assert len(res.uplink_points) == len(res.uplink_bytes)
        assert len(res.uplink_points) >= min(res.rounds, 1)
        assert np.all(res.uplink_points >= 0)
        assert np.array_equal(res.uplink_bytes, res.uplink_points * d * 4)
        if res.n_hist is not None:   # removal algorithms: N never grows
            assert all(res.n_hist[i + 1] <= res.n_hist[i]
                       for i in range(len(res.n_hist) - 1)), algo
        cost = res.cost(x)
        assert np.isfinite(cost) and cost >= 0.0
        assert res.wall_time_s > 0.0


def test_fit_flat_input_with_padding(data):
    x, _, means = data
    xf = np.asarray(x)[:5_995]          # not divisible by m=8 -> padding
    res = fit(xf, K, algo="soccer", backend="virtual", m=M, epsilon=0.2,
              seed=0)
    assert np.all(np.isfinite(res.centers))
    ref = float(centralized_cost(jnp.asarray(xf), jnp.asarray(means)))
    assert res.cost(xf) <= 5.0 * ref    # padding never becomes a center


def test_fit_unknown_algo_and_param():
    x = np.zeros((64, 3), np.float32)
    with pytest.raises(ValueError, match="soccer"):
        fit(x, 2, algo="nope")
    with pytest.raises(TypeError, match="not_a_knob"):
        fit(x, 2, algo="soccer", not_a_knob=1)


def test_weighted_input_sizes_instance_by_weight(data):
    """run_soccer with w=3 must derive the same eta as 3x the points."""
    _, parts, _ = data
    m, p, _ = parts.shape
    params = SoccerParams(k=K, epsilon=0.1, seed=0)
    w = jnp.full((m, p), 3.0)
    res = run_soccer(parts, params, w=w)
    const_3n = derive_constants(3 * m * p, p, params, m=m)
    const_1n = derive_constants(m * p, p, params, m=m)
    assert res.const.eta == const_3n.eta
    assert const_3n.eta > const_1n.eta  # the pre-fix (alive-count) value


def test_eim11_weighted_sizing(data):
    """EIM11's per-round sample is sized from weight mass, like eta."""
    import math

    from repro.core.eim11 import run_eim11
    _, parts, _ = data
    m, p, _ = parts.shape
    w = jnp.full((m, p), 3.0)
    res = run_eim11(parts, K, 0.1, w=w, max_rounds=2, seed=0)
    k, n_w, delta = K, 3 * m * p, 0.1
    s_expected = min(int(math.ceil(
        9 * k * (n_w ** 0.1) * math.log(n_w / delta))), m * p)
    # uplink per round is two samples of s points each (apportionment
    # may leave a few units of largest-remainder slack)
    assert abs(int(res.uplink[0]) - 2 * s_expected) <= 8


def test_soccer_params_validation():
    with pytest.raises(ValueError, match="blackbox"):
        SoccerParams(k=5, blackbox="minbatch")
    with pytest.raises(ValueError, match="epsilon"):
        SoccerParams(k=5, epsilon=0.0)
    with pytest.raises(ValueError, match="delta"):
        SoccerParams(k=5, delta=1.0)
    with pytest.raises(ValueError, match="k must be"):
        SoccerParams(k=0)
    with pytest.raises(ValueError, match="sharded_threshold"):
        SoccerParams(k=5, sharded_threshold="top-k")
    with pytest.raises(ValueError, match="sharded_seeding"):
        SoccerParams(k=5, sharded_seeding="kpp")
    with pytest.raises(ValueError, match="straggler_rate"):
        SoccerParams(k=5, straggler_rate=1.0)
    # valid construction untouched
    SoccerParams(k=5, epsilon=0.05, blackbox="minibatch",
                 sharded_threshold="topk", sharded_seeding="kmeanspar")


@pytest.mark.parametrize("algo", sorted(TINY))
def test_fit_seed_deterministic(data, algo):
    """Same seed -> bit-identical ClusterResult per algorithm (virtual
    backend; the mesh-backend leg lives in test_distributed.py's
    subprocess, which has the 8 host devices it needs)."""
    _, parts, _ = data
    r1 = fit(parts, K, algo=algo, backend="virtual", seed=7,
             **TINY.get(algo, {}))
    r2 = fit(parts, K, algo=algo, backend="virtual", seed=7,
             **TINY.get(algo, {}))
    assert np.array_equal(r1.centers, r2.centers), algo
    assert r1.rounds == r2.rounds
    assert np.array_equal(r1.uplink_points, r2.uplink_points)
    # and a different seed is allowed to (and here does) change something
    r3 = fit(parts, K, algo=algo, backend="virtual", seed=8,
             **TINY.get(algo, {}))
    assert r3.centers.shape == r1.centers.shape


def test_fit_ref_vs_pallas_cost_agreement(data, monkeypatch):
    """fit() through the interpret-mode Pallas kernels must land on the
    same clustering cost as through the jnp oracle. Caches are cleared
    between env flips: jit traces capture the kernel backend, so a stale
    executable would silently keep the previous backend."""
    x, parts, _ = data
    costs = {}
    for kb in ("ref", "pallas"):
        monkeypatch.setenv("REPRO_KERNEL_BACKEND", kb)
        jax.clear_caches()
        for algo in ("lloyd", "soccer"):
            res = fit(parts, K, algo=algo, backend="virtual", seed=1,
                      **TINY.get(algo, {}))
            costs[(algo, kb)] = float(res.cost(x))
    monkeypatch.delenv("REPRO_KERNEL_BACKEND")
    jax.clear_caches()                   # drop pallas-traced executables
    # backends may legitimately break exact distance ties differently
    # (different summation orders), shifting a few boundary points between
    # clusters — a broken kernel moves cost by orders of magnitude, not %
    for algo in ("lloyd", "soccer"):
        assert costs[(algo, "pallas")] == pytest.approx(
            costs[(algo, "ref")], rel=5e-2), (algo, costs)


def test_cost_helper_matches_centralized(data):
    x, parts, _ = data
    res = fit(parts, K, algo="lloyd", backend="virtual", iters=5, seed=0)
    direct = float(centralized_cost(jnp.asarray(x),
                                    jnp.asarray(res.centers)))
    assert res.cost(x) == pytest.approx(direct, rel=1e-6)
    # sharded input with weights gives the same total
    w = jnp.ones(parts.shape[:2])
    assert res.cost(parts, w) == pytest.approx(direct, rel=1e-5)


@pytest.mark.parametrize("algo", sorted(TINY))
def test_device_input_fits_as_its_host_copy(data, algo):
    """A pre-sharded device array goes to the driver without a host trip
    and clusters bit for bit as the same points given as NumPy."""
    _, parts, _ = data
    on_device = fit(parts, K, algo=algo, backend="virtual", seed=5,
                    trace="rounds", **TINY.get(algo, {}))
    on_host = fit(np.asarray(parts), K, algo=algo, backend="virtual",
                  seed=5, trace="rounds", **TINY.get(algo, {}))
    assert np.array_equal(on_device.centers, on_host.centers)
    assert on_device.rounds == on_host.rounds
    assert np.array_equal(on_device.uplink_points, on_host.uplink_points)
    kept = [r.extra["trace"]["counters"]["input_on_device_bytes"]
            for r in (on_device, on_host)]
    assert kept == [parts.nbytes, 0]


def test_multi_host_put_reshards_device_leaves(monkeypatch):
    """Across processes a jax.Array leaf is a global value: it is put on
    the mesh by device_put, never read to the host; a host leaf is the
    process's own data and still goes through
    make_array_from_process_local_data."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from repro.api import MeshBackend
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("machines",))
    bk = MeshBackend(mesh)
    local, put = [], []
    from_local = jax.make_array_from_process_local_data
    device_put = jax.device_put
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(jax, "make_array_from_process_local_data",
                        lambda s, a: local.append(a) or from_local(s, a))
    monkeypatch.setattr(jax, "device_put",
                        lambda a, s: put.append(a) or device_put(a, s))
    xd = jnp.arange(12, dtype=jnp.float32).reshape(1, 4, 3)
    key = np.asarray([0, 7], np.uint32)
    x_out, key_out = bk.put((xd, key), ("machine", "rep"))
    assert len(put) == 1 and put[0] is xd
    assert len(local) == 1 and local[0] is key
    assert x_out.sharding == NamedSharding(mesh, PartitionSpec("machines"))
    assert key_out.sharding == NamedSharding(mesh, PartitionSpec())
    assert np.array_equal(x_out, xd) and np.array_equal(key_out, key)
