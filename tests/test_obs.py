"""Telemetry contracts: schema stability, the trace-off zero-cost
guarantee, the wire-sum identity, export round-trips, the report CLI,
registry mechanics, and the sequential-fit reproducibility gate.

The mesh legs run under ``make test-mesh``
(``XLA_FLAGS=--xla_force_host_platform_device_count=N``) like the other
``@pytest.mark.mesh`` suites.
"""
import json

import jax
import numpy as np
import pytest

from repro.api import fit
from repro.core import programs
from repro.obs import REGISTRY, MetricsRegistry
from repro.obs.export import (chrome_trace_events, load_jsonl, write_jsonl)
from repro.obs.report import format_diff, format_summary, main as report_main
from repro.obs import trace as obs_trace
from repro.obs.trace import (ROUND_FIELDS, ROUND_SCHEMA, RunTrace, _STATS,
                             round_record, run_trace)

M, K = 4, 4


def _data(seed=0, p=256, d=8):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(M, p, d)).astype(np.float32)


@pytest.fixture
def fresh_programs():
    """An empty program cache, so the fit under test is the first of its
    shapes in the process: it traces and compiles all three programs."""
    programs.clear_program_cache()


def _soccer(x, trace=None, seed=0):
    return fit(x, K, algo="soccer", backend="virtual", epsilon=0.2,
               seed=seed, trace=trace)


# ------------------------------------------------------------ schema


def test_round_schema_is_pinned():
    """The exporter/report/diff contract: these exact fields, these exact
    types. Extending the schema is fine; renaming or retyping a field
    breaks every archived JSONL and must show up here first."""
    assert dict(ROUND_SCHEMA) == {
        "round": int, "phase": str, "n_live": int, "capacity": int,
        "alpha": float, "v": float, "removed": int, "stop_ratio": float,
        "stop_margin": float, "uplink_rows": int,
        "wire_payload_bytes": int, "wire_meta_bytes": int,
        "wall_s": float, "compile_s": float,
    }
    assert ROUND_FIELDS == tuple(name for name, _ in ROUND_SCHEMA)


def test_round_record_coerces_and_rejects():
    rec = round_record(round=np.int64(2), phase="round",
                       n_live=np.int32(10), alpha=np.float32(0.5))
    assert rec["round"] == 2 and type(rec["round"]) is int
    assert type(rec["alpha"]) is float
    assert rec["v"] is None                    # missing -> None, key present
    assert set(rec) == set(ROUND_FIELDS)
    with pytest.raises(ValueError):
        round_record(round=1, phase="round", bogus_field=3)
    with pytest.raises(ValueError):
        round_record(round=1, phase="warmup")  # phases are pinned too


# ------------------------------------------------------------ off = free


def test_trace_off_allocates_nothing():
    """The zero-cost contract: an untraced fit touches none of the trace
    machinery — no RunTrace, no spans, no counts, no records, no 'trace'
    key — and the ambient hooks are no-ops with no trace active."""
    x = _data()
    _soccer(x)                                # warm (compile may span)
    before = dict(_STATS)
    res = _soccer(x)
    obs_trace.count("programs_traced")
    obs_trace.count_put(x)
    obs_trace.count_placement(x, jax.numpy.asarray(x))
    obs_trace.to_host(jax.numpy.zeros(3))
    with obs_trace.span("off"):
        pass
    assert dict(_STATS) == before
    assert "trace" not in res.extra


# ------------------------------------------------------------ rounds mode


def test_soccer_trace_wire_sum_and_stop_margin(fresh_programs):
    """Acceptance: the per-round records sum to the result's measured
    wire bytes, and the stopping-rule margin explains the round count —
    the first round whose post-removal live set fit the coordinator is
    the last round the loop ran (plus the finalize record)."""
    x = _data(p=2048)                         # big enough to need rounds
    res = _soccer(x, trace="rounds")
    assert res.rounds >= 1
    t = res.extra["trace"]
    recs = t["records"]
    assert len(recs) == res.rounds + 1        # rounds + finalize
    assert [r["phase"] for r in recs[:-1]] == ["round"] * res.rounds
    assert recs[-1]["phase"] == "finalize"
    wire = sum(r["wire_payload_bytes"] + r["wire_meta_bytes"] for r in recs)
    assert wire == res.wire_bytes_total
    assert t["wire_payload_bytes"] + t["wire_meta_bytes"] == wire
    # stopping-rule margin: capacity stops exactly when margin <= 0
    if t["stop_reason"] == "capacity":
        assert t["rounds_to_margin"] == res.rounds
        assert recs[res.rounds - 1]["stop_margin"] <= 0
    for r in recs[:-1]:
        assert r["n_live"] > 0 and r["uplink_rows"] >= 0
        assert r["wall_s"] is not None and r["wall_s"] >= 0
    assert t["compile_s"] is not None and t["compile_s"] > 0
    assert t["meta"]["algo"] == "soccer" and t["meta"]["eta"] > 0


def test_one_shot_drivers_trace_wire_sum():
    x = _data()
    for algo, params in (("lloyd", dict(iters=3)),
                         ("coreset_kmeans", dict(coreset_size=64,
                                                 lloyd_iters=3))):
        res = fit(x, K, algo=algo, backend="virtual", seed=0,
                  trace="rounds", **params)
        t = res.extra["trace"]
        assert t["stop_reason"] == "one_shot"
        assert len(t["records"]) == 1 and t["records"][0]["phase"] == "upload"
        wire = sum(r["wire_payload_bytes"] + r["wire_meta_bytes"]
                   for r in t["records"])
        assert wire == res.wire_bytes_total


def test_full_mode_records_spans_and_events():
    rt = RunTrace(mode="full")
    with run_trace(rt):
        from repro.obs.trace import event, span
        with span("outer", layer="test"):
            event("ping", n=1)
    assert [s["name"] for s in rt.spans] == ["outer"]
    assert rt.spans[0]["attrs"] == {"layer": "test"}
    assert rt.events[0]["name"] == "ping"
    summary = rt.summary()
    assert summary["mode"] == "full"
    assert len(summary["spans"]) == 1 and len(summary["events"]) == 1


# ------------------------------------------------------------ fit spans


def _full_soccer(x):
    res = _soccer(x, trace="full")
    assert res.rounds >= 1                    # the loop ran
    return res, res.extra["trace"]


def test_traced_fit_spans_every_host_phase_in_order(fresh_programs):
    """Spans are listed as they close: the init program is built while
    the input is placed, the round and finalize programs after it."""
    res, t = _full_soccer(_data(p=2048))
    assert [s["name"] for s in t["spans"]] == (
        ["fit.place", "soccer.compile", "soccer.place", "soccer.compile",
         "soccer.compile"]
        + ["soccer.round"] * res.rounds
        + ["soccer.finalize", "soccer.readback"])
    assert [s["attrs"] for s in t["spans"] if s["name"] == "soccer.compile"
            ] == [{"program": "init"}, {"program": "round"},
                  {"program": "finalize"}]
    assert [s["attrs"]["round"] for s in t["spans"]
            if s["name"] == "soccer.round"] == list(range(1, res.rounds + 1))
    # the init build inside the placement; the rest inside the fit's
    # wall, and not overlapping each other
    init, place = t["spans"][1:3]
    assert place["t0"] <= init["t0"] <= init["t1"] <= place["t1"]
    phases = [t["spans"][0]] + t["spans"][2:]
    for a, b in zip(phases, phases[1:]):
        assert a["t0"] <= a["t1"] <= b["t0"] <= b["t1"]
    covered = sum(s["t1"] - s["t0"] for s in phases)
    assert 0 < covered <= t["wall_s"]


def test_span_durations_are_the_records_walls(fresh_programs):
    """One measurement, not two: each round, finalize and compile span's
    interval is its record's wall_s / compile_s (the init program's build
    belongs to the placement, which has no record)."""
    res, t = _full_soccer(_data(p=2048))
    dur = {}
    for s in t["spans"]:
        dur.setdefault(s["name"], []).append(s["t1"] - s["t0"])
    recs = t["records"]
    assert [r["wall_s"] for r in recs[:-1]] == dur["soccer.round"]
    assert [recs[-1]["wall_s"]] == dur["soccer.finalize"]
    assert [recs[0]["compile_s"], recs[-1]["compile_s"]] == dur[
        "soccer.compile"][1:]


@pytest.mark.parametrize("on_device", [False, True])
def test_fit_counts_programs_and_host_device_bytes(on_device,
                                                 fresh_programs):
    """A host input is put on the device once; a device-resident one is
    handed on as it is: no host copy either way, nothing placed."""
    x = _data(p=2048)
    xin = jax.numpy.asarray(x) if on_device else x
    res = fit(xin, K, algo="soccer", backend="virtual", epsilon=0.2,
              seed=0, trace="rounds")
    t = res.extra["trace"]
    state = res.extra["state"]
    # the stopping rule read the live count before every round and once
    # more unless max_rounds ended the loop; the readback reads it again
    checks = res.rounds + (t["stop_reason"] != "max_rounds")
    readback = sum(a.nbytes for a in (
        state.uplink, state.n_hist, state.v_hist, state.alpha_hist,
        state.centers, state.centers_valid, state.n_remaining))
    host = {} if on_device else {"h2d_bytes": x.nbytes}
    assert t["counters"] == {
        "programs_traced": 3,
        "program_cache_hits": 0,
        "input_on_device_bytes": x.nbytes if on_device else 0,
        # one device takes all of a host x; a device x is already there
        "place_max_device_bytes": 0 if on_device else x.nbytes,
        "d2h_bytes": readback + checks * state.n_remaining.nbytes,
        **host}
    line = REGISTRY.read("api.fit.traces")["api.fit.traces"]["events"][-1]
    assert line["counters"] == t["counters"]
    assert line["wall_s"] == t["wall_s"] and line["span_s"] == {}


def test_flat_device_input_takes_the_host_shard_path(monkeypatch,
                                                     fresh_programs):
    """Flat (n, d) input is placed on machines by make_shards on the host
    whether it comes as NumPy or on a device: a device one is read back
    first, and both then cluster and count alike."""
    from repro.data import sharding
    seen = []
    make_shards = sharding.make_shards
    monkeypatch.setattr(sharding, "make_shards", lambda x, *a, **kw: (
        seen.append(type(x)) or make_shards(x, *a, **kw)))
    x = _data(p=512).reshape(M * 512, -1)
    runs = [fit(xin, K, algo="soccer", backend="virtual", m=M,
                epsilon=0.2, seed=0, trace="rounds")
            for xin in (jax.numpy.asarray(x), x)]
    assert seen == [np.ndarray, np.ndarray]
    dev, host = (r.extra["trace"]["counters"] for r in runs)
    assert dev["input_on_device_bytes"] == host["input_on_device_bytes"] == 0
    assert dev["h2d_bytes"] == host["h2d_bytes"] >= x.nbytes
    assert dev["d2h_bytes"] == host["d2h_bytes"] + x.nbytes
    assert dev["place_max_device_bytes"] == host["place_max_device_bytes"]
    assert np.array_equal(runs[0].centers, runs[1].centers)


def test_spans_land_on_the_profiler_host_plane(tmp_path):
    """Full mode mirrors every span into jax.profiler: a captured profile
    holds each on the host plane under its own name, as long as the
    RunTrace measured it (within 1 ms)."""
    x = _data(p=2048)
    _soccer(x, trace="full")                  # compile outside the capture
    with jax.profiler.trace(str(tmp_path)):
        res = _soccer(x, trace="full")
    spans = res.extra["trace"]["spans"]
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    prof = jax.profiler.ProfileData.from_file(str(path))
    host = [p for p in prof.planes if p.name == "/host:CPU"]
    assert len(host) == 1
    names = {s["name"] for s in spans}
    seen = {}
    for line in host[0].lines:
        for ev in line.events:
            if ev.name in names:
                seen.setdefault(ev.name, []).append(
                    (ev.start_ns, ev.duration_ns * 1e-9))
    assert set(seen) == names
    for name in names:
        ours = [s["t1"] - s["t0"] for s in spans if s["name"] == name]
        theirs = [d for _, d in sorted(seen[name])]
        assert len(theirs) == len(ours), name
        for a, b in zip(ours, theirs):
            assert abs(a - b) < 1e-3, (name, a, b)


# ------------------------------------------------------------ export


def test_jsonl_round_trip(tmp_path):
    x = _data()
    a = _soccer(x, trace="rounds").extra["trace"]
    b = _soccer(x, trace="rounds", seed=1).extra["trace"]
    path = write_jsonl([a, b], tmp_path / "t.jsonl")
    runs = load_jsonl(path)
    assert len(runs) == 2
    assert runs[0]["records"] == a["records"]
    assert runs[1]["stop_reason"] == b["stop_reason"]
    assert runs[0]["wire_payload_bytes"] == a["wire_payload_bytes"]
    assert runs[0]["counters"] == a["counters"] and a["counters"]


def test_jsonl_orphan_line_raises(tmp_path):
    path = tmp_path / "bad.jsonl"
    path.write_text(json.dumps({"kind": "round", "round": 1}) + "\n")
    with pytest.raises(ValueError):
        load_jsonl(path)


def test_chrome_trace_export(tmp_path):
    x = _data()
    t = _soccer(x, trace="rounds").extra["trace"]
    events = chrome_trace_events(t)
    complete = [e for e in events if e["ph"] == "X"]
    assert len(complete) == len(t["records"])
    assert all(e["ts"] >= 0 and e["dur"] >= 0 for e in complete)
    # rounds lie back to back on one timeline row
    rounds = sorted((e["ts"], e["dur"]) for e in complete
                    if e["tid"] == 1)
    for (ts0, d0), (ts1, _) in zip(rounds, rounds[1:]):
        assert abs((ts0 + d0) - ts1) < 1.0    # contiguous (us resolution)
    assert [e["args"] for e in events if e["ph"] == "C"] == [t["counters"]]


# ------------------------------------------------------------ report CLI


def test_report_cli_single_and_diff(tmp_path, capsys):
    x = _data()
    a = _soccer(x, trace="rounds").extra["trace"]
    b = _soccer(x, trace="rounds", seed=1).extra["trace"]
    pa = write_jsonl([a], tmp_path / "a.jsonl")
    pb = write_jsonl([b], tmp_path / "b.jsonl")
    assert report_main([str(pa)]) == 0
    out = capsys.readouterr().out
    assert "stop_reason" in out and "round" in out and "finalize" in out
    assert report_main([str(pa), str(pb)]) == 0
    out = capsys.readouterr().out
    assert "wall_s" in out                    # diff table rendered
    # formatter directly (what selfcheck prints)
    assert "wire_bytes" in format_summary(a)
    assert format_diff(a, b)


# ------------------------------------------------------------ registry


def test_registry_mechanics():
    reg = MetricsRegistry()
    c = reg.counter("t.hits")
    reg.gauge("t.depth", lambda: 7)
    h = reg.histogram("t.lat", buckets=(1.0, 10.0))
    c.inc()
    c.inc("", 2)
    c.inc("miss")
    h.observe(0.5)
    h.observe(5.0)
    h.observe(50.0)
    snap = reg.read()
    assert snap["t.hits"] == {"": 3, "miss": 1}
    assert snap["t.depth"] == {"value": 7}
    assert snap["t.lat"]["count"] == 3 and snap["t.lat"]["sum"] == 55.5
    assert snap["t.lat"]["buckets"]["le=+inf"] == 1
    assert reg.counter("t.hits") is c         # idempotent re-registration
    with reg.scope() as sc:
        c.inc("", 5)
        h.observe(2.0)
    delta = sc.delta()
    assert delta["t.hits"][""] == 5 and delta["t.hits"]["miss"] == 0
    assert delta["t.lat"]["count"] == 1
    reg.reset()
    assert reg.read()["t.hits"] == {}
    assert reg.read()["t.lat"]["count"] == 0
    assert reg.read()["t.depth"] == {"value": 7}  # callback gauge re-samples
    assert reg.summary_lines("t.hits", "t.lat")
    with pytest.raises(KeyError):
        reg.read("t.nonexistent")


def test_default_registry_adoptions_readable():
    """The global registry resolves every adopted legacy counter without
    import errors, and reset() leaves them usable."""
    snap = REGISTRY.read()
    for name in ("streaming.tree.trace_counts", "core.kmeans.trace_counts",
                 "core.sharded_kmeans.trace_counts",
                 "kernels.tuning.autotune", "core.comm.active_tallies"):
        assert name in snap, name
    x = _data()
    with REGISTRY.scope() as sc:
        _soccer(x)
    delta = sc.delta()                        # delta over a fit never errors
    assert "error" not in str(delta)
    REGISTRY.reset()
    assert REGISTRY.read()["core.comm.active_tallies"] == {"value": 0}


# ------------------------------------------------------------ hygiene


def test_sequential_fits_report_identical_metrics():
    """Global-mutable hygiene: the SAME fit twice in one process yields
    identical per-run telemetry — no counter bleed, no stale tally, no
    order dependence (walls excluded: time is not deterministic)."""
    x = _data()

    def run():
        t = _soccer(x, trace="rounds").extra["trace"]
        recs = [{k: v for k, v in r.items()
                 if k not in ("wall_s", "compile_s")} for r in t["records"]]
        return (recs, t["stop_reason"], t["rounds_to_margin"],
                t["wire_payload_bytes"], t["wire_meta_bytes"])

    assert run() == run()


# ------------------------------------------------------------ mesh leg


needs_mesh = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="mesh trace tests need >= 2 devices "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=N)")


@pytest.mark.mesh
@needs_mesh
def test_trace_wire_sum_both_backends():
    """The wire-sum identity holds on the REAL collectives too, and the
    mesh/virtual traces agree on everything but time."""
    m = jax.device_count()
    rng = np.random.default_rng(0)
    x = rng.normal(size=(m, 256, 8)).astype(np.float32)
    per = {}
    for backend in ("virtual", "mesh"):
        res = fit(x, K, algo="soccer", backend=backend, epsilon=0.2,
                  seed=0, trace="rounds")
        t = res.extra["trace"]
        wire = sum(r["wire_payload_bytes"] + r["wire_meta_bytes"]
                   for r in t["records"])
        assert wire == res.wire_bytes_total, backend
        per[backend] = [(r["round"], r["phase"], r["uplink_rows"])
                        for r in t["records"]]
    assert per["virtual"] == per["mesh"]
