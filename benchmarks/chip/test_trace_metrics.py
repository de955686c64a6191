"""The per-layer metrics read from the program's own trace, off the chip.

    pytest benchmarks/chip/test_trace_metrics.py

A traced run of each cell that lists them, at 64,000 points on the CPU, reads every
metric that ``fit_traces.py`` takes from the program's log of traced
fits: both SOCCER programs traced once per fit, the input's bytes moved
each way, and the host I/O and finalize spans.
"""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
NEW = ("host_io_s", "host_io_MB", "programs_traced", "finalize_s")

SCRIPT = """
import json, sys
sys.path.insert(0, {here!r})
import run as bench
cell = bench.load("workloads", {cell!r})
config = bench.load("configs", cell["config"])
config["n"] = {n}
out = bench.run_cell({cell!r}, 2 ** 32 + 5, 0.5, True, cell=cell,
                     config=config, require_chip=False)
print(json.dumps(out["metrics"]))
"""


def _listed_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    chips = {w["name"]: w["chips"] for w in bench["workloads"]}
    cells = [m["workloads"] for m in bench["per_layer"] if m["name"] in NEW]
    return [(c, chips[c]) for c in cells[0]]


@pytest.mark.parametrize("cell,devices", _listed_cells())
def test_traced_run_reads_the_program_trace(cell, devices):
    n = 64000
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "-c", SCRIPT.format(here=HERE, cell=cell, n=n)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    metrics = json.loads(p.stdout.strip().splitlines()[-1])
    for name in NEW:
        assert metrics[name]["value"] is not None, name
    assert metrics["programs_traced"]["value"] == 2
    # the input crosses twice (to the host in fit(), back in run_soccer);
    # the readback of centers and histories is well under 1 MB
    x_mb = n * 15 * 4 / 1e6
    assert 2 * x_mb < metrics["host_io_MB"]["value"] < 2 * x_mb + 1
    assert metrics["host_io_s"]["value"] > 0
    assert metrics["finalize_s"]["value"] > 0
