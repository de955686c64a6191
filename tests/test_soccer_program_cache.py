"""SOCCER's round and finalize programs are built once per process and key.

A fit whose placement, constants, state avals and trace-time kernel
settings an earlier fit already had traces nothing, reuses that trace's
wire tallies, and returns exactly what a fit on an empty cache returns.
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
from repro.api.backends import CommBackend, VirtualBackend
from repro.configs.soccer_paper import SoccerParams
from repro.core import soccer
from repro.core.comm import VirtualCluster, WireTally, record_wire

M, K = 4, 4


@pytest.fixture(autouse=True)
def empty_cache():
    soccer.clear_program_cache()
    yield
    soccer.clear_program_cache()


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
    assert _counters(_fit(x, seed=1)) == (2, 0)
    res = _fit(x, seed=2)
    assert _counters(res) == (0, 2)
    t = res.extra["trace"]
    assert "soccer.compile" not in [s["name"] for s in t["spans"]]
    assert [r["compile_s"] for r in t["records"]] == [0.0] * len(
        t["records"])
    assert len(soccer._PROGRAMS) == 1


@pytest.mark.parametrize("trace", [None, "rounds"])
def test_cached_fit_is_bit_equal_to_a_fresh_one(trace):
    x = _data(p=2048)
    _fit(x, seed=1, trace=trace)
    cached = _fit(x, seed=2, trace=trace)
    soccer.clear_program_cache()
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
    "backend": lambda: dict(backend=CommBackend(VirtualCluster(M))),
}


@pytest.mark.parametrize("change", sorted(CHANGES))
def test_a_change_of_the_program_retraces(change):
    x = _data()
    _fit(x, seed=1)
    kw = dict(x=x, seed=2)
    kw.update(CHANGES[change]())
    assert _counters(_fit(**kw)) == (2, 0)
    assert len(soccer._PROGRAMS) == 2


def test_mesh_after_virtual_retraces():
    x = _data(m=1)
    _fit(x, seed=1)
    assert _counters(_fit(x, seed=2, backend="mesh")) == (2, 0)
    assert _counters(_fit(x, seed=3, backend="mesh")) == (0, 2)


@pytest.mark.parametrize("var,value", [("REPRO_KERNEL_BACKEND", "pallas"),
                                       ("REPRO_AUTOTUNE", "off")])
def test_a_kernel_setting_retraces(monkeypatch, var, value):
    x = _data(p=256)
    _fit(x, seed=1)
    monkeypatch.setenv(var, value)
    assert _counters(_fit(x, seed=2)) == (2, 0)
    assert _counters(_fit(x, seed=3)) == (0, 2)


def test_cache_holds_at_most_its_size():
    """Filled through the lookup alone (nothing is traced)."""
    x = jax.numpy.asarray(_data(p=64))
    backend = VirtualBackend()
    comm = backend.make_comm(M)
    built = []
    for k in range(1, soccer._PROGRAM_CACHE_SIZE + 4):
        const = soccer.derive_constants(M * 64, 64,
                                        SoccerParams(k=k, epsilon=0.2,
                                                     n_machines=M))
        inputs = (x, None, None, jax.random.PRNGKey(0))
        built.append((const, inputs, soccer._programs(backend, comm, const,
                                                      inputs)))
        assert len(soccer._PROGRAMS) <= soccer._PROGRAM_CACHE_SIZE
    assert len(soccer._PROGRAMS) == soccer._PROGRAM_CACHE_SIZE
    # least recently used goes first: the oldest were dropped, the newest
    # come back as the same objects
    const, inputs, progs = built[-1]
    assert soccer._programs(backend, comm, const, inputs) is progs
    const, inputs, progs = built[0]
    assert soccer._programs(backend, comm, const, inputs) is not progs


class _UnhashableCluster(VirtualCluster):
    __hash__ = None


def test_unhashable_comm_runs_and_caches_nothing():
    x = _data()
    backend = CommBackend(_UnhashableCluster(M))
    first = _fit(x, seed=2, backend=backend)
    again = _fit(x, seed=2, backend=backend)
    assert _counters(first) == _counters(again) == (2, 0)
    assert len(soccer._PROGRAMS) == 0
    _assert_bit_equal(first, _fit(x, seed=2))


def test_retrace_replaces_the_tally():
    prog = soccer._Program(fn=None)
    def trace():
        record_wire(payload=7, row_bytes=60)

    soccer._traced(prog, trace)                  # first trace
    assert prog.tally == WireTally(payload=7, row_bytes=60)
    soccer._traced(prog, lambda: None)           # a cached call
    assert prog.tally == WireTally(payload=7, row_bytes=60)
    soccer._traced(prog, trace)                  # JAX traced again
    assert prog.tally == WireTally(payload=7, row_bytes=60)


def test_jax_retrace_of_a_cached_program_keeps_wire_bytes():
    """JAX may retrace a cached jit by itself (its caches cleared here):
    the fit's wire bytes are those of one trace, not two."""
    x = _data(p=2048)
    first = _fit(x, seed=1, trace=None)
    (_, step, fin), = soccer._PROGRAMS.values()
    tallies = step.tally, fin.tally
    jax.clear_caches()
    again = _fit(x, seed=1, trace=None)
    assert (step.tally, fin.tally) == tallies
    assert step.tally is not tallies[0]          # traced again, replaced
    _assert_bit_equal(first, again)


_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax, numpy as np
from repro.api import fit
from repro.core import soccer

x = np.random.default_rng(0).normal(size=(4, 512, 8)).astype(np.float32)
def run(seed):
    r = fit(x, 4, algo="soccer", backend="mesh", epsilon=0.2, seed=seed,
            key=jax.random.PRNGKey(seed), trace="rounds")
    c = r.extra["trace"]["counters"]
    return r, [c["programs_traced"], c["program_cache_hits"]]
_, first = run(1)
cached, second = run(2)
soccer.clear_program_cache()
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
    assert out == {"devices": 4, "first": [2, 0], "second": [0, 2],
                   "same": True}
