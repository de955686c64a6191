"""The trace reduction, checked on a trace recorded on a TPU v5e.

``testdata/remove_below_v5e.xplane.pb``: inside one ``bench.small`` span,
three calls of ``kernels.ops.remove_below`` on ``(8, 8192, 15)`` points
and 103 centers, a 50 ms sleep, then a first call of ``x * 2`` (which
compiles) and a sum. Each number is computed a second way here, from the
raw events, and compared.

    pytest benchmarks/chip/test_xplane.py
"""
import os
import re
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import roofline  # noqa: E402
import xplane  # noqa: E402

TRACE = os.path.join(HERE, "testdata", "remove_below_v5e.xplane.pb")


@pytest.fixture(scope="module")
def raw():
    """(device op events, host python-line events) as (name, start, end)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(TRACE)
    dev, host = [], []
    for plane in data.planes:
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events]
            if plane.name == "/device:TPU:0" and line.name == "XLA Ops":
                dev += evs
            if plane.name == "/host:CPU" and line.name == "python":
                host += evs
    return dev, host


@pytest.fixture(scope="module")
def window(raw):
    (span,) = [(s, e) for name, s, e in raw[1] if name == "bench.small"]
    return span


@pytest.fixture(scope="module")
def reduced(window):
    return xplane.reduce(TRACE, chips=1, window=window)


def test_busy_matches_a_timeline(raw, window, reduced):
    """Busy time against a 10 ns timeline of the same events."""
    lo, hi = window
    t = np.zeros(int((hi - lo) // 10) + 1, bool)
    for _, s, e in raw[0]:
        a, b = max(s, lo), min(e, hi)
        if b > a:
            t[int((a - lo) // 10):int((b - lo) // 10)] = True
    assert reduced.window_s == pytest.approx((hi - lo) / 1e9)
    assert reduced.busy_s == pytest.approx(t.sum() * 10 / 1e9, rel=1e-3)
    assert 0 < reduced.busy_s < reduced.window_s


def test_idle_gaps(reduced):
    """The gaps are the longest first, each at most the idle time, and the
    50 ms sleep and the compile of ``x * 2`` are among them."""
    secs = [s for _, s in reduced.gaps]
    assert secs == sorted(secs, reverse=True)
    assert sum(secs) <= reduced.window_s - reduced.busy_s + 1e-9
    assert secs[0] >= 0.05
    assert "PjitFunction(multiply)" in [name for name, _ in reduced.gaps]


def test_kernel_events_and_shapes(reduced):
    mod = roofline.load("remove_below")
    ops = reduced.matching(mod.EVENT)
    assert len(ops) == 3
    for op in ops:
        assert xplane.operand_shapes(op.name)[0] == ("f32", (8, 8192, 15))
    assert xplane.short_name(ops[0].name) == (
        "remove_below_pallas (s32[8,1,8192], s32[8,1,1])")


def test_roofline_share(raw, reduced):
    """Least time from the logical work at 819 GB/s, over the kernel's
    device time read straight from the events."""
    kernel_ns = sum(e - s for name, s, e in raw[0]
                    if name.startswith("%remove_below_pallas"))
    nbytes = 4 * 8 * 8192 * 15 + 4 * 103 * 15 + 2 * 8 * 8192 + 4 * 8 + 4
    least = 3 * nbytes / 819e9
    run = types.SimpleNamespace(
        trace=reduced, device={"kind": "TPU v5 lite"},
        peaks={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    got = roofline.share(run, "remove_below", [(8, 8192, 15, 103)] * 3)
    assert got == pytest.approx(100 * least / (kernel_ns / 1e9), rel=1e-9)
    assert 0 < got < 100


def test_breakdown_shape(reduced):
    b = reduced.breakdown()
    assert 0 < len(b["device_ops"]) <= 10
    assert 0 < len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0].startswith("remove_below_pallas")
    assert all(isinstance(v, float) for _, v in b["device_ops"])


def test_collectives_absent_on_one_chip(reduced):
    assert reduced.seconds(xplane.COLLECTIVE) == 0.0
    assert re.match(xplane.COLLECTIVE,
                    "%all-reduce.3 = f32[] all-reduce(f32[] %x)")
    assert re.match(xplane.COLLECTIVE,
                    "%all-gather-start = (f32[1,4]) all-gather-start(...)")


def test_parts_of_an_instruction():
    op = ("%fusion.15 = f32[138992,15]{0,1:T(8,128)S(1)} fusion("
          "f32[8,1280000,15]{1,2,0:T(8,128)} %copy.50, s32[138992]"
          "{0:T(1024)} %reshape.379), kind=kCustom, calls=%f.2")
    assert xplane.short_name(op) == "fusion f32[138992,15]"
    assert xplane.operand_shapes(op) == [("f32", (8, 1280000, 15)),
                                         ("s32", (138992,))]


def test_host_clock_fallback(window):
    """A trace without bench.fit.<i> spans takes the window it is given as
    a fallback."""
    lo, hi = window
    r = xplane.reduce(TRACE, chips=1, fallback=(lo, hi))
    assert r.window_s == pytest.approx((hi - lo) / 1e9)
    with pytest.raises(RuntimeError, match="no bench.fit"):
        xplane.reduce(TRACE, chips=1)
