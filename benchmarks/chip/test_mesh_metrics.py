"""The per-layer metrics of the four-chip cell, off the chip.

    pytest benchmarks/chip/test_mesh_metrics.py

A traced run of ``gm10m-k25-mesh4.soccer`` at 64,000 points on four CPU
devices: every metric the program's own trace feeds reads a number, among
them ``place_MB``, one machine's share of the input; those read from the
device trace (``collective_s``, ``device_idle``, the roofline) read
nothing off a TPU.
"""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "gm10m-k25-mesh4.soccer"

SCRIPT = """
import json, sys
sys.path.insert(0, {here!r})
import run as bench
cell = bench.load("workloads", {cell!r})
config = bench.load("configs", cell["config"])
config["n"] = {n}
out = bench.run_cell({cell!r}, 2 ** 32 + 7, 0.5, True, cell=cell,
                     config=config, require_chip=False)
print(json.dumps(out["metrics"]))
"""


def test_mesh_cell_reads_its_per_layer_metrics():
    n = 64000
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "-c", SCRIPT.format(here=HERE, cell=CELL, n=n)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    metrics = json.loads(p.stdout.strip().splitlines()[-1])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = {m["name"]: m["source"] for m in json.load(f)["per_layer"]
                  if CELL in m.get("workloads", [CELL])}
    device = {name for name, src in listed.items() if src == "device_trace"}
    assert "collective_s" in device
    assert set(metrics) == set(listed) - device
    for name, m in metrics.items():
        assert isinstance(m["value"], (int, float)), name
    # one machine's rows (a quarter of x) and the 8-byte key
    assert metrics["place_MB"]["value"] == pytest.approx(
        (n // 4 * 15 * 4 + 8) / 1e6, rel=1e-12)
    assert metrics["rounds"]["value"] >= 1
    assert metrics["wire_MB"]["value"] > 0
