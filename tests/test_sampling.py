"""Exact-size distributed sampling: apportionment + gather properties,
and the threshold select that draws a machine's sample."""
import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

pytest.importorskip("hypothesis")  # optional dev dep (requirements-dev.txt)
from hypothesis import given, settings, strategies as st

from repro.api import fit
from repro.core import programs, sampling
from repro.core.comm import VirtualCluster
from repro.core.sampling import (apportion, draw_global_sample,
                                 exclusive_cumsum, sample_local,
                                 top_c_indices)


@settings(max_examples=50, deadline=None)
@given(
    counts=st.lists(st.integers(0, 5000), min_size=1, max_size=24),
    total=st.integers(1, 8000),
)
def test_apportion_properties(counts, total):
    c = apportion(jnp.asarray(counts, jnp.int32), total)
    c = np.asarray(c)
    counts = np.asarray(counts)
    assert (c >= 0).all()
    assert (c <= counts).all(), "never draw more than a machine holds"
    want = min(total, counts.sum())
    assert abs(int(c.sum()) - want) <= len(counts), \
        "within float-rounding slack of the budget"


@settings(max_examples=30, deadline=None)
@given(
    p=st.integers(4, 64),
    alive_frac=st.floats(0.1, 1.0),
    seed=st.integers(0, 1000),
)
def test_sample_local_draws_only_alive(p, alive_frac, seed):
    rng = np.random.default_rng(seed)
    alive = jnp.asarray(rng.random(p) < alive_frac)
    n_alive = int(alive.sum())
    c = jnp.int32(max(min(n_alive, p // 2), 0))
    idx, take = sample_local(jax.random.PRNGKey(seed), alive, c, cap=p)
    idx, take = np.asarray(idx), np.asarray(take)
    assert take.sum() == int(c)
    chosen = idx[: int(c)]
    assert np.asarray(alive)[chosen].all()
    assert len(set(chosen.tolist())) == int(c), "without replacement"


def test_draw_global_sample_exact_and_weighted():
    m, p, d = 6, 100, 3
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(m, p, d)), jnp.float32)
    w = jnp.ones((m, p), jnp.float32)
    alive = jnp.asarray(rng.random((m, p)) < 0.8)
    comm = VirtualCluster(m)
    n_vec = jnp.sum(alive, axis=1).astype(jnp.int32)
    total = 120
    pts, ws, real = draw_global_sample(
        comm, jax.random.PRNGKey(1), x, w, alive, n_vec, total, p)
    ws = np.asarray(ws)
    got = int((ws > 0).sum())
    assert abs(got - total) <= m
    assert int(real) == got
    # HT weights: total estimated mass == population size
    n_alive = float(jnp.sum(alive))
    np.testing.assert_allclose(ws.sum(), n_alive, rtol=0.02)


def test_draw_global_sample_imbalanced_machines():
    """One machine holds almost everything; no padding overflow/loss."""
    m, p, d = 4, 200, 2
    alive = np.zeros((m, p), bool)
    alive[0, :] = True            # machine 0: 200 points
    alive[1, :5] = True           # machine 1: 5
    x = jnp.asarray(np.random.default_rng(1).normal(size=(m, p, d)),
                    jnp.float32)
    comm = VirtualCluster(m)
    n_vec = jnp.sum(jnp.asarray(alive), axis=1).astype(jnp.int32)
    pts, ws, real = draw_global_sample(
        comm, jax.random.PRNGKey(2), x, jnp.ones((m, p)),
        jnp.asarray(alive), n_vec, 64, p)
    assert abs(int(real) - 64) <= m
    np.testing.assert_allclose(float(jnp.sum(ws)), 205.0, rtol=0.05)


def test_exclusive_cumsum():
    c = jnp.asarray([3, 0, 5, 2], jnp.int32)
    np.testing.assert_array_equal(np.asarray(exclusive_cumsum(c)),
                                  [0, 3, 3, 8])


def _scores(rng, m, p, levels, dead):
    """(m, p) float32 scores: uniform in [0, 1), quantised to ``levels``
    values where given (so many keys tie), dead points at -1."""
    s = rng.random((m, p))
    if levels:
        s = np.floor(s * levels) / levels
    s[rng.random((m, p)) < dead] = -1.0
    return s.astype(np.float32)


# (m, p, cap, levels, dead share, per-row draw counts as a function of the
# rows' live counts)
SELECT_CASES = {
    "heavy_ties": (2, 50_000, 2_000, 4, 0.0, lambda live: [1_500, 2_000]),
    "ties_at_zero": (1, 20_000, 3_000, 7, 0.3, lambda live: [3_000]),
    "dead_points": (1, 200_000, 17_374, 0, 0.5, lambda live: [17_374]),
    "c_zero": (1, 10_000, 1_000, 3, 0.2, lambda live: [0]),
    "c_is_live_count": (1, 5_000, 5_000, 5, 0.9, lambda live: live),
    "cap_over_p": (1, 300, 1_000, 2, 0.4, lambda live: live),
    "rows_differ": (4, 30_000, 2_500, 9, 0.9,
                    lambda live: [0, 1, 1_234, 2_500]),
}


@pytest.mark.parametrize("case", sorted(SELECT_CASES))
def test_top_c_indices_is_top_ks_first_c(case):
    """The select returns lax.top_k's first c indices, in its order (ties
    lower index first), and only valid indices past c."""
    m, p, cap, levels, dead, counts = SELECT_CASES[case]
    s = _scores(np.random.default_rng(len(case)), m, p, levels, dead)
    live = (s >= 0).sum(axis=1).tolist()
    c = np.asarray(counts(live), np.int32)
    assert (c <= np.minimum(live, cap)).all()
    got = np.asarray(jax.jit(jax.vmap(
        lambda row, n: top_c_indices(row, n, cap)))(jnp.asarray(s),
                                                    jnp.asarray(c)))
    want = np.asarray(lax.top_k(jnp.asarray(s), min(cap, p))[1])
    assert got.shape == (m, cap)
    assert ((got >= 0) & (got < p)).all()
    for j in range(m):
        np.testing.assert_array_equal(got[j, :c[j]], want[j, :c[j]])


def _top_k_indices(scores, c, cap):
    """The draw as lax.top_k takes it: the reference for the select."""
    p = scores.shape[0]
    _, idx = lax.top_k(scores, min(cap, p))
    return jnp.pad(idx, (0, max(cap - p, 0)))


def _fit(x, seed, backend="virtual"):
    return fit(x, 3, algo="soccer", backend=backend, epsilon=0.2,
               eta_override=400, key=jax.random.PRNGKey(seed), seed=seed,
               trace="rounds")


def _same_fit(a, b):
    for u, v in ((a.centers, b.centers),
                 (a.extra["state"].alive, b.extra["state"].alive),
                 (a.n_hist, b.n_hist), (a.v_hist, b.v_hist)):
        np.testing.assert_array_equal(np.asarray(u), np.asarray(v))


@pytest.fixture
def empty_programs():
    programs.clear_program_cache()
    yield
    programs.clear_program_cache()


def test_fit_by_select_is_bit_equal_to_top_k(monkeypatch, empty_programs):
    """A fit that draws by the select returns, on its first fit and on a
    cached one, exactly what the same fit drawing by lax.top_k returns."""
    m, p = 4, 4096
    x = np.random.default_rng(0).normal(size=(m, p, 4)).astype(np.float32)
    first, cached = _fit(x, seed=1), _fit(x, seed=2)
    assert cached.extra["trace"]["counters"]["program_cache_hits"] == 3
    monkeypatch.setattr(sampling, "top_c_indices", _top_k_indices)
    programs.clear_program_cache()
    for res, seed in ((first, 1), (cached, 2)):
        assert res.rounds >= 1
        _same_fit(res, _fit(x, seed=seed))


_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax, jax.numpy as jnp, numpy as np
from jax import lax
from repro.api import fit
from repro.core import programs, sampling

x = np.random.default_rng(0).normal(size=(4, 4096, 4)).astype(np.float32)
def run(seed, backend):
    return fit(x, 3, algo="soccer", backend=backend, epsilon=0.2,
               eta_override=400, key=jax.random.PRNGKey(seed), seed=seed)
def key(r):
    return [np.asarray(a).tolist() for a in
            (r.centers, r.n_hist, r.v_hist, r.extra["state"].alive)]
first, cached, virt = run(1, "mesh"), run(2, "mesh"), run(2, "virtual")
def top_k(scores, c, cap):
    _, idx = lax.top_k(scores, min(cap, scores.shape[0]))
    return jnp.pad(idx, (0, max(cap - scores.shape[0], 0)))
sampling.top_c_indices = top_k
programs.clear_program_cache()
print("RESULT " + json.dumps({
    "devices": jax.device_count(),
    "rounds": [first.rounds, cached.rounds],
    "first": key(first) == key(run(1, "mesh")),
    "cached": key(cached) == key(run(2, "mesh")),
    "virtual": key(cached) == key(virt)}))
"""


def test_mesh_draw_is_bit_equal_to_top_k():
    """On a mesh, where each device draws for its one machine, a fit by
    the select equals the fit by lax.top_k (first fit and cached) and the
    virtual backend's fit."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(pathlib.Path(__file__).resolve().parents[1]
                            / "src")
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", _MESH_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    out = json.loads(line[len("RESULT "):])
    assert out["devices"] == 4 and min(out["rounds"]) >= 1
    assert out["first"] and out["cached"] and out["virtual"]
