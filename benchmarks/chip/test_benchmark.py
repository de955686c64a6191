"""The harness off the chip: its files, its refusals, and its check.

    pytest benchmarks/chip/test_benchmark.py

The check is shown to fail on the control (distances from three bf16
passes in place of the removal kernel) and on every fault a cell can
have, planted under the timed path (``plants.py``), at 64,000 points on
the CPU; the sound program passes the same runs.
"""
import importlib.util
import json
import os
import re
import shutil
import subprocess
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _load(path):
    spec = importlib.util.spec_from_file_location(
        os.path.basename(path)[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod        # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_name_has_its_files(bench):
    paths = bench["paths"]
    for c in bench["configs"]:
        assert NAME.match(c["name"])
        assert any(c["file"].startswith(p + "/") for p in paths)
        with open(os.path.join(ROOT, c["file"])) as f:
            assert json.load(f)["name"] == c["name"]
    for w in bench["workloads"]:
        with open(os.path.join(HERE, "workloads", w["name"] + ".json")) as f:
            cell = json.load(f)
        for key in ("config", "traffic", "chips", "why"):
            assert cell[key] == w[key], (w["name"], key)
        with open(os.path.join(HERE, "traffic", w["traffic"] + ".json")) as f:
            traffic = json.load(f)
        check = _load(os.path.join(HERE, "checks", traffic["check"] + ".py"))
        assert set(cell["limits"]) == {"cost_ratio", *check.NUMBERS}
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"])
        assert os.path.exists(os.path.join(HERE, "metrics",
                                           m["name"] + ".py"))
        assert set(m.get("workloads", cells)) <= cells
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
    for m in bench["per_layer"]:
        if m["name"].endswith("_roofline"):
            entry = m["name"][:-len("_roofline")]
            assert os.path.exists(os.path.join(HERE, "rooflines",
                                               entry + ".py"))


def test_nested_compile_spans_count_once():
    run = _load(os.path.join(HERE, "run.py"))
    clock = run.CompileClock()
    clock.spans = [(0.0, 2.0), (0.5, 1.5), (1.0, 1.2), (3.0, 4.0),
                   (3.5, 5.0)]
    assert clock.seconds_since(0) == pytest.approx(4.0)
    assert clock.seconds_since(3) == pytest.approx(2.0)


def test_another_algorithm_needs_only_its_check(monkeypatch):
    """The harness's own part of a fit and of its check holds for any
    algorithm: k-means‖ runs through it with a check that keeps nothing."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.syspath_prepend(HERE)
    run = _load(os.path.join(HERE, "run.py"))
    import gen
    config = {"n": 4096, "dim": 15, "k": 5, "sigma": 0.001,
              "zipf_gamma": 1.5, "machines": 4, "backend": "virtual"}
    traffic = {"algo": "kmeans_parallel", "params": {"rounds": 2}}
    check = types.SimpleNamespace(NUMBERS=(), record=lambda res: {},
                                  compare=lambda xs, n, fit: {})
    x, means = gen.mixture(config, 5, None)
    clock = run.CompileClock()
    clock.listen()
    fits = [run.fit_once(x, config, traffic, check, gen.fit_key(5, i), i,
                         True, clock) for i in range(2)]
    assert fits[0].rounds == 2 and fits[0].centers.shape == (5, 15)
    assert fits[0].round_walls and fits[0].own == {}
    checks, _ = run.check_fits(gen.parts(x), 4096, 1.0, fits, check,
                               {"cost_ratio": 1e9})
    assert set(checks) == {"cost_ratio"} and checks["cost_ratio"][0] > 0


def _run(args, cwd, env_extra=None, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run([sys.executable] + args, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_no_tpu_no_result():
    p = _run(["benchmarks/chip/run.py", "--workload", "gm10m-k25.soccer",
              "--seed", str(2 ** 31 + 9), "--seconds", "1", "--trace", "0"],
             ROOT)
    assert p.returncode == 2, p.stderr[-2000:]
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_bare_directory_fails(bench, tmp_path):
    """With only BENCHMARK.json and the benchmark's files, nothing runs."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in bench["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(bench["command"][1:] + ["--workload", "gm10m-k25.soccer",
                                     "--seed", "1", "--seconds", "1"],
             str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def _plant(cell, plant, devices=1):
    p = _run(["benchmarks/chip/plants.py", "--workload", cell, "--plant",
              plant, "--seeds", str(2 ** 32 + 17), "--seconds", "0.5",
              "--n", "64000", "--cpu"], ROOT,
             {"XLA_FLAGS": f"--xla_force_host_platform_device_count="
                           f"{devices}"})
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("plant", ["none", "control", "unchanged_step",
                                   "half_batch", "altered_centers",
                                   "altered_count"])
def test_one_chip_cell(plant):
    out = _plant("gm10m-k25.soccer", plant)
    assert out["correct"] is (plant == "none"), out["checks"]


@pytest.mark.parametrize("plant", ["none", "control", "no_exchange",
                                   "altered_count"])
def test_mesh_cell(plant):
    out = _plant("gm10m-k25-mesh4.soccer", plant, devices=4)
    assert out["device"]["count"] == 4
    assert out["correct"] is (plant == "none"), out["checks"]
