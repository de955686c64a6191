#!/usr/bin/env python3
"""Chip benchmark of ``repro.api.fit``: one cell, one seed, one run.

    python benchmarks/chip/run.py --workload gm10m-k25.soccer --seed 7 \\
        --seconds 30 --trace 0

A cell (``workloads/<name>.json``) names a configuration
(``configs/<name>.json``: the data and how it is deployed) and a traffic
mix (``traffic/<name>.json``: the algorithm, its arguments and the check
of its results, ``checks/<name>.py``). A run:

1. set-up: draws the configuration's data on the device from ``--seed``,
   places it as the deployment holds it, and makes one warm-up ``fit()``;
2. window: calls ``fit()`` back to back, one caller at a time, each fit
   with its own key, until ``--seconds`` have passed; with ``--trace 1``
   under the profiler and with ``fit(trace="full")``;
3. check: once the window has closed, compares what every fit returned
   with the float32 reference (``reference.py``): the cost of its centers
   against the generating means' for every algorithm, and the traffic's
   own check; each number against the cell's limit;
4. prints the metrics that ``BENCHMARK.json`` lists for the cell, each read
   by ``metrics/<name>.py``, as the last line of standard output.

It runs only on a TPU: any other platform, or fewer chips than the cell
asks for, exits 2 with no result line.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up is timed from here

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(ROOT, "src"))

# JAX's compile spans: jaxpr tracing, lowering to MLIR, and backend
# compile, which holds the persistent-cache read. They nest (a jit traced
# inside another, the cache read inside the compile), so a fit's time in
# them is the union of their intervals.
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
BACKEND_COMPILE = COMPILE_EVENTS[2]
CACHE_HIT = "/jax/compilation_cache/cache_hits"


def load(kind: str, name: str) -> dict:
    """``<kind>/<name>.json`` under the benchmark's directory."""
    with open(os.path.join(HERE, kind, name + ".json")) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``<kind>/<name>.py`` under the benchmark's directory, imported."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


class CompileClock:
    """Keeps the intervals of JAX's compile spans and counts the programs
    compiled: backend compiles that the persistent cache did not serve."""

    def __init__(self):
        self.spans: List[tuple] = []
        self.compiled = 0

    def listen(self) -> None:
        import jax

        def on_span(event, start, end, **_):
            if event in COMPILE_EVENTS:
                self.spans.append((start, end))
            if event == BACKEND_COMPILE:
                self.compiled += 1

        def on_event(event, **_):
            if event == CACHE_HIT:
                self.compiled -= 1

        jax.monitoring.register_event_time_span_listener(on_span)
        jax.monitoring.register_event_listener(on_event)

    def seconds_since(self, first: int) -> float:
        """Length of the union of the spans from ``spans[first]`` on."""
        total, end = 0.0, float("-inf")
        for s, e in sorted(self.spans[first:]):
            if e > end:
                total += e - max(s, end)
                end = e
        return total


@dataclasses.dataclass
class FitRecord:
    """What one timed fit returned, kept for the check after the window."""
    compile_s: float           # union of its compile spans
    rounds: int
    centers: Any               # (c, d) every center the fit returned
    wire_bytes: int
    round_walls: List[float]
    own: dict                  # what the traffic's check keeps (``record``)


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read (``metrics/<name>.py``)."""
    config: dict
    device: dict
    setup_s: float
    window_s: float
    fits: List[FitRecord]
    cost_ratios: List[float]
    trace: Optional[Any] = None         # xplane.Reduced, with --trace 1
    peaks: Optional[dict] = None


# ------------------------------------------------------------------ device


def find_devices(chips: int) -> dict:
    """The chips this run may use; exits 2 without a TPU or enough of them."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"no TPU: JAX found platform {devs[0].platform!r}; this "
            f"benchmark never falls back to it")
        sys.exit(2)
    if len(devs) < chips:
        log(f"the cell asks for {chips} chips, JAX found {len(devs)}")
        sys.exit(2)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def machine_mesh(config: dict):
    """The one-axis mesh of a mesh deployment, else None."""
    if config["backend"] != "mesh":
        return None
    import jax
    import numpy as np
    from jax.sharding import Mesh
    m = config["machines"]
    return Mesh(np.asarray(jax.devices()[:m]).reshape(m), ("machines",))


def memory_peak(chips: int) -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


# ---------------------------------------------------------------- the fits


def fit_once(x, config: dict, traffic: dict, check, key, i: int,
             trace: bool, clock: CompileClock) -> FitRecord:
    """One ``fit()`` as a user calls it, on the device-resident data."""
    import numpy as np
    from repro.api import fit
    first = len(clock.spans)
    res = fit(x, k=config["k"], algo=traffic["algo"],
              backend=config["backend"], key=key, seed=i,
              trace="full" if trace else None, **traffic["params"])
    walls = []
    if trace:
        walls = [r["wall_s"] for r in res.extra["trace"]["records"]
                 if r["phase"] == "round" and r["wall_s"] is not None]
    return FitRecord(
        compile_s=clock.seconds_since(first), rounds=int(res.rounds),
        centers=np.asarray(res.centers),
        wire_bytes=int(res.wire_bytes_total), round_walls=walls,
        own=check.record(res))


# ------------------------------------------------------------------ check


def check_fits(xs, n: int, base_cost: float, fits: List[FitRecord],
               check, limits: dict):
    """Every fit of the window against the reference: ``cost_ratio``, the
    cost of every center the fit returned on the full data over the
    generating means' cost, and the numbers of the traffic's ``check``.

    Returns ``(checks, per_fit)``: ``checks`` maps each number compared to
    ``(worst reading over the fits, limit)``; ``per_fit`` lists each fit's
    readings.
    """
    import reference
    per_fit = [dict(cost_ratio=sum(reference.cost(p, f.centers) for p in xs)
                    / base_cost, **check.compare(xs, n, f)) for f in fits]
    checks = {k: (max(r[k] for r in per_fit), limits[k]) for k in limits}
    return checks, per_fit


# ----------------------------------------------------------------- metrics


def metric_specs(trace: bool, cell: str) -> List[dict]:
    """The cell's metrics from ``BENCHMARK.json``: end-to-end ones in a run
    with ``--trace 0``, per-layer ones with ``--trace 1``."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    specs = bench["per_layer" if trace else "end_to_end"]
    return [s for s in specs if cell in s.get("workloads", [cell])]


def read_metrics(run: Run, specs: List[dict]) -> Dict[str, dict]:
    out = {}
    for s in specs:
        value = load_module("metrics", s["name"]).read(run)
        if value is not None:
            out[s["name"]] = {"value": value, "unit": s["unit"]}
    return out


# -------------------------------------------------------------------- run


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool,
             cell: Optional[dict] = None, config: Optional[dict] = None,
             require_chip: bool = True) -> dict:
    """One run of a cell; returns the result line's object.

    ``cell`` and ``config`` default to the files of ``cell_name``; tests
    pass smaller ones and ``require_chip=False``.
    """
    cell = cell or load("workloads", cell_name)
    config = config or load("configs", cell["config"])
    traffic = load("traffic", cell["traffic"])
    check = load_module("checks", traffic["check"])
    specs = metric_specs(trace, cell_name)

    from repro.launch.cache import use_compile_cache
    # the program names the cache's directory but does not create it, and
    # JAX writes no entry into a directory that does not exist
    os.makedirs(use_compile_cache(), exist_ok=True)
    import jax
    if require_chip:
        device = find_devices(cell["chips"])
    else:
        device = {"platform": jax.devices()[0].platform,
                  "kind": jax.devices()[0].device_kind,
                  "count": len(jax.devices())}
    clock = CompileClock()
    clock.listen()

    import gen
    import reference
    mesh = machine_mesh(config)
    x, means = gen.mixture(config, seed, mesh)
    jax.block_until_ready(x)
    fit_once(x, config, traffic, check, gen.fit_key(seed, -1), -1, trace,
             clock)
    gc.collect()
    setup_s = time.perf_counter() - T_START
    compiled0 = clock.compiled

    prof_dir = None
    if trace:
        prof_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        t_prof = time.perf_counter()
        jax.profiler.start_trace(prof_dir, profiler_options=opts)
    fits: List[FitRecord] = []
    t0 = time.perf_counter()
    while not fits or time.perf_counter() - t0 < seconds:
        i = len(fits)
        with jax.profiler.TraceAnnotation(f"bench.fit.{i}"):
            fits.append(fit_once(x, config, traffic, check,
                                 gen.fit_key(seed, i), i, trace, clock))
    window_s = time.perf_counter() - t0
    device["memory_peak_bytes"] = memory_peak(cell["chips"])
    log(f"programs compiled in the window, not read from the persistent "
        f"cache: {clock.compiled - compiled0}")
    reduced = None
    if trace:
        jax.profiler.stop_trace()
    if trace and device["platform"] == "tpu":
        import xplane
        reduced = xplane.reduce(
            xplane.find(prof_dir), chips=cell["chips"],
            fallback=((t0 - t_prof) * 1e9, (t0 + window_s - t_prof) * 1e9))
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
    if prof_dir is not None:
        shutil.rmtree(prof_dir, ignore_errors=True)

    # the reference runs after the window and the memory reading
    import numpy as np
    xs = gen.parts(x)
    base_cost = sum(reference.cost(p, means) for p in xs)
    checks, per_fit = check_fits(xs, int(np.prod(x.shape[:-1])), base_cost,
                                 fits, check, cell["limits"])
    failed = sum(any(r[k] > lim for k, lim in cell["limits"].items())
                 for r in per_fit)
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    run = Run(config=config, device=device,
              setup_s=setup_s, window_s=window_s, fits=fits,
              cost_ratios=[r["cost_ratio"] for r in per_fit], trace=reduced,
              peaks=peaks.get(device["kind"]))
    out = {"correct": failed == 0, "attempted": len(fits), "failed": failed,
           "metrics": read_metrics(run, specs), "device": device}
    if reduced is not None:
        out["breakdown"] = reduced.breakdown()
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    for name, c in out["checks"].items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
