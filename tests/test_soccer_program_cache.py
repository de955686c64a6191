"""Every driver's programs are built once per process and key
(``repro.core.programs``).

A fit whose placement, constants, input avals and trace-time kernel
settings an earlier fit already had traces nothing, reuses the wire
tallies recorded when its programs were lowered, runs the same
executables whether it is traced or not, and returns exactly what a fit
on an empty cache returns.
"""
import json
import os
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.api import fit
from repro.api.backends import VirtualBackend
from repro.core import programs, soccer
from repro.core.comm import VirtualCluster, WireTally, record_wire, wire_tally

M, K = 4, 4


@pytest.fixture(autouse=True)
def empty_cache():
    programs.clear_program_cache()
    yield
    programs.clear_program_cache()


def _data(p=512, d=8, m=M, seed=0):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(m, p, d)).astype(np.float32)


def _fit(x, seed, trace="rounds", k=K, epsilon=0.2, backend="virtual"):
    return fit(x, k, algo="soccer", backend=backend, epsilon=epsilon,
               key=jax.random.PRNGKey(seed), seed=seed, trace=trace)


def _counters(res):
    c = res.extra["trace"]["counters"]
    return c["programs_traced"], c["program_cache_hits"]


def _outcome(res):
    return (res.centers, res.n_hist, res.v_hist, res.rounds,
            res.wire_bytes_total)


def _assert_bit_equal(a, b):
    for u, v in zip(_outcome(a), _outcome(b)):
        np.testing.assert_array_equal(u, v)


def test_same_shapes_other_key_hits():
    x = _data()
    assert _counters(_fit(x, seed=1)) == (3, 0)   # init, round, finalize
    res = _fit(x, seed=2)
    assert _counters(res) == (0, 3)
    t = res.extra["trace"]
    assert "soccer.compile" not in [s["name"] for s in t["spans"]]
    assert [r["compile_s"] for r in t["records"]] == [0.0] * len(
        t["records"])
    assert len(programs._PROGRAMS) == 3


@pytest.mark.parametrize("trace", [None, "rounds"])
def test_cached_fit_is_bit_equal_to_a_fresh_one(trace):
    x = _data(p=2048)
    _fit(x, seed=1, trace=trace)
    cached = _fit(x, seed=2, trace=trace)
    programs.clear_program_cache()
    fresh = _fit(x, seed=2, trace=trace)
    assert cached.rounds >= 1
    _assert_bit_equal(cached, fresh)
    np.testing.assert_array_equal(cached.wire_bytes, fresh.wire_bytes)
    np.testing.assert_array_equal(cached.wire_meta_bytes,
                                  fresh.wire_meta_bytes)


CHANGES = {
    "d": lambda: dict(x=_data(d=6)),
    "k": lambda: dict(k=K + 1),
    "epsilon": lambda: dict(epsilon=0.25),
    "backend": lambda: dict(backend=VirtualBackend(uplink_dtype="bfloat16")),
}


@pytest.mark.parametrize("change", sorted(CHANGES))
def test_a_change_of_the_program_retraces(change):
    x = _data()
    _fit(x, seed=1)
    kw = dict(x=x, seed=2)
    kw.update(CHANGES[change]())
    assert _counters(_fit(**kw)) == (3, 0)
    assert len(programs._PROGRAMS) == 6


def test_mesh_after_virtual_retraces():
    x = _data(m=1)
    _fit(x, seed=1)
    assert _counters(_fit(x, seed=2, backend="mesh")) == (3, 0)
    assert _counters(_fit(x, seed=3, backend="mesh")) == (0, 3)


@pytest.mark.parametrize("var,value", [("REPRO_KERNEL_BACKEND", "pallas"),
                                       ("REPRO_AUTOTUNE", "off")])
def test_a_kernel_setting_retraces(monkeypatch, var, value):
    x = _data(p=256)
    _fit(x, seed=1)
    monkeypatch.setenv(var, value)
    assert _counters(_fit(x, seed=2)) == (3, 0)
    assert _counters(_fit(x, seed=3)) == (0, 3)


def _scaled(x, *, k, payload=0):
    record_wire(payload=payload, row_bytes=60)
    return x * k


def _build(k, x, payload=0):
    return programs.build("test", "scaled", _scaled, VirtualBackend(),
                          ("rep",), "rep", (x,),
                          static=dict(k=k, payload=payload))


def test_cache_holds_at_most_its_size():
    x = jax.numpy.ones((8,), jax.numpy.float32)
    built = []
    for k in range(1, programs.CACHE_SIZE + 4):
        built.append(_build(k, x)[0])
        assert len(programs._PROGRAMS) <= programs.CACHE_SIZE
    assert len(programs._PROGRAMS) == programs.CACHE_SIZE
    # least recently used goes first: the oldest were dropped, the newest
    # come back as the same objects
    assert _build(programs.CACHE_SIZE + 3, x)[0] is built[-1]
    assert _build(1, x)[0] is not built[0]


class _UnhashableCluster(VirtualCluster):
    __hash__ = None


class _UnhashableCommBackend:
    """A duck-typed Backend whose comm cannot be hashed."""
    name = "virtual"

    def make_comm(self, m):
        return _UnhashableCluster(m)

    def put(self, tree, marks):
        return jax.device_put(tree)

    def compile(self, fn, in_marks, out_marks, donate=()):
        return jax.jit(fn, donate_argnums=donate)


def test_unhashable_comm_runs_and_caches_nothing():
    x = _data()
    backend = _UnhashableCommBackend()
    first = _fit(x, seed=2, backend=backend)
    again = _fit(x, seed=2, backend=backend)
    assert _counters(first) == _counters(again) == (3, 0)
    assert len(programs._PROGRAMS) == 0
    _assert_bit_equal(first, _fit(x, seed=2))


def test_retrace_replaces_the_tally():
    """The tally is recorded once, when the program is lowered: a call
    records nothing, and a second build finds the first program."""
    x = jax.numpy.ones((8,), jax.numpy.float32)
    prog, _ = _build(3, x, payload=7)
    assert prog.tally == WireTally(payload=7, row_bytes=60)
    with wire_tally() as outer:
        np.testing.assert_array_equal(prog(x), 3 * np.ones(8))
        prog(x)
    assert outer == WireTally()
    assert prog.tally == WireTally(payload=7, row_bytes=60)
    again, compile_s = _build(3, x, payload=7)
    assert again is prog and compile_s == 0.0


def test_jax_retrace_of_a_cached_program_keeps_wire_bytes():
    """JAX's own caches cleared: the kept programs run the same
    executables, with the wire bytes of their one lowering."""
    x = _data(p=2048)
    first = _fit(x, seed=1, trace=None)
    assert first.rounds >= 1
    kept = dict(programs._PROGRAMS)
    jax.clear_caches()
    again = _fit(x, seed=1, trace=None)
    assert programs._PROGRAMS.keys() == kept.keys()
    assert all(programs._PROGRAMS[k] is p for k, p in kept.items())
    _assert_bit_equal(first, again)
    np.testing.assert_array_equal(first.wire_bytes, again.wire_bytes)
    np.testing.assert_array_equal(first.wire_meta_bytes,
                                  again.wire_meta_bytes)


def test_a_planted_body_takes_effect_after_clearing(monkeypatch):
    """The chip benchmark's fault plants replace ``soccer._blackbox`` (and
    ``soccer_round``, ``ops.remove_below``) in the module: a program built
    after the cache is emptied runs the plant."""
    x = _data()
    plain = _fit(x, seed=1)
    assert plain.rounds == 0                     # finalize only
    real = soccer._blackbox
    monkeypatch.setattr(soccer, "_blackbox",
                        lambda const, key, x, w, k:
                        real(const, key, x, w, k) + 0.05)
    assert _counters(_fit(x, seed=1)) == (0, 3)  # kept programs: no plant
    programs.clear_program_cache()
    planted = _fit(x, seed=1)
    assert _counters(planted) == (3, 0)
    np.testing.assert_allclose(planted.centers, plain.centers + 0.05,
                               rtol=0, atol=1e-6)


ALGOS = {
    "soccer": dict(epsilon=0.2),
    "kmeans_parallel": dict(rounds=2, lloyd_iters=3),
    "eim11": dict(epsilon=0.2, max_rounds=2),
    "lloyd": dict(iters=3),
    "minibatch": dict(batch=64, steps=5),
    "kzmeans": dict(coreset_size=128, lloyd_iters=3, outlier_frac=0.02),
    "coreset_kmeans": dict(coreset_size=128, lloyd_iters=3),
}


@pytest.mark.parametrize("algo", sorted(ALGOS))
def test_traced_fit_reuses_the_untraced_fits_program(algo):
    """An untraced fit, then a traced one on the same shapes: the traced
    fit builds nothing, and returns bit for bit what a traced fit on an
    emptied cache returns."""
    x = _data(p=2048)

    def run(trace):
        return fit(x, K, algo=algo, backend="virtual",
                   key=jax.random.PRNGKey(3), seed=3, trace=trace,
                   **ALGOS[algo])

    run(None)
    reused = run("rounds")
    assert reused.extra["trace"]["counters"]["programs_traced"] == 0
    programs.clear_program_cache()
    fresh = run("rounds")
    assert fresh.extra["trace"]["counters"]["programs_traced"] > 0
    assert reused.rounds == fresh.rounds
    for a, b in ((reused.centers, fresh.centers),
                 (reused.uplink_points, fresh.uplink_points),
                 (reused.wire_bytes, fresh.wire_bytes),
                 (reused.wire_meta_bytes, fresh.wire_meta_bytes)):
        np.testing.assert_array_equal(a, b)


_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax, numpy as np
from repro.api import fit
from repro.core import programs

x = np.random.default_rng(0).normal(size=(4, 512, 8)).astype(np.float32)
def run(seed):
    r = fit(x, 4, algo="soccer", backend="mesh", epsilon=0.2, seed=seed,
            key=jax.random.PRNGKey(seed), trace="rounds")
    c = r.extra["trace"]["counters"]
    return r, [c["programs_traced"], c["program_cache_hits"]]
_, first = run(1)
cached, second = run(2)
programs.clear_program_cache()
fresh, _ = run(2)
same = bool(np.array_equal(cached.centers, fresh.centers)
            and np.array_equal(cached.n_hist, fresh.n_hist)
            and cached.rounds == fresh.rounds
            and cached.wire_bytes_total == fresh.wire_bytes_total)
print("RESULT " + json.dumps({"devices": jax.device_count(),
                              "first": first, "second": second,
                              "same": same}))
"""


def test_mesh_backend_on_four_devices_hits():
    env = dict(os.environ)
    root = pathlib.Path(__file__).resolve().parents[1]
    env["PYTHONPATH"] = str(root / "src")
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", _MESH_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    out = json.loads(line[len("RESULT "):])
    assert out == {"devices": 4, "first": [3, 0], "second": [0, 3],
                   "same": True}
