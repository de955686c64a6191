"""SOCCER over four machines, one per device: placement by shard.

``fit(backend="mesh")`` puts each machine's rows on its own device and
builds the rest of the state there, so no device ever holds another
machine's points. One subprocess with four CPU devices runs the paper's
mixture at 64,000 points (d = 15, k = 25) and reports what these tests
check: the fit against a plain float32 difference-form reference, the
mesh fit against the virtual one on the same key, the mesh fit of the
points already sharded on the devices against the one of their NumPy
copy, where each machine's leaves live, and the
``place_max_device_bytes`` counter.
"""
import json
import os
import pathlib
import subprocess
import sys

import pytest

M, P, D = 4, 16_000, 15

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec
from repro.api import fit
from repro.core import soccer
from repro.ft.failures import FailurePlan

M, P, D, K = 4, 16000, 15, 25
rng = np.random.default_rng(0)
means = rng.uniform(size=(K, D)).astype(np.float32)
zipf = np.arange(1, K + 1) ** -1.5
labels = rng.choice(K, size=(M, P), p=zipf / zipf.sum())
x = (means[labels] + np.float32(0.001) * rng.normal(size=(M, P, D))
     ).astype(np.float32)
w = rng.uniform(0.5, 2.0, size=(M, P)).astype(np.float32)
# the same points already on the devices, one machine on each
xd = jax.device_put(x, NamedSharding(
    Mesh(np.asarray(jax.devices()[:M]), ("machines",)),
    PartitionSpec("machines")))

placed = []
with_given = soccer._with_given
def spy(*args):
    placed.append(with_given(*args))
    return placed[-1]
soccer._with_given = spy

def run(backend, data=x, **kw):
    res = fit(data, K, algo="soccer", backend=backend, epsilon=0.05, delta=0.1,
              key=jax.random.PRNGKey(7), seed=7, trace="rounds", **kw)
    return res, placed[-1]

def min_d2(pts, c):
    # difference form, float32, no matmul
    out = np.empty(len(pts), np.float32)
    for i in range(0, len(pts), 4096):
        diff = pts[i:i + 4096, None, :] - c[None]
        out[i:i + 4096] = np.min(np.sum(diff * diff, axis=2), axis=1)
    return out

def outcome(res):
    st = res.extra["state"]
    return [np.asarray(a) for a in (res.centers, res.n_hist, res.v_hist,
                                    st.alive, st.n_remaining)] + [
        res.rounds, res.wire_bytes_total]

out = {"devices": jax.device_count()}
mesh, mesh_state = run("mesh")
virtual, _ = run("virtual")
out["bit_equal"] = all(np.array_equal(a, b) for a, b in
                       zip(outcome(mesh), outcome(virtual)))
on_device, _ = run("mesh", data=xd)
out["device_bit_equal"] = all(np.array_equal(a, b) for a, b in
                              zip(outcome(on_device), outcome(mesh)))
out["device_counters"] = {
    name: on_device.extra["trace"]["counters"].get(name)
    for name in ("h2d_bytes", "input_on_device_bytes")}

st = mesh.extra["state"]
flat = x.reshape(-1, D)
centers, valid = np.asarray(st.centers), np.asarray(st.centers_valid)
alive = np.ones(M * P, bool)
live = []
for r in range(mesh.rounds):
    gone = min_d2(flat, centers[r][valid[r]]) <= np.asarray(st.v_hist)[r]
    alive &= ~gone
    live.append(int(alive.sum()))
out["rounds"] = int(mesh.rounds)
out["flips"] = int((alive != np.asarray(st.alive).reshape(-1)).sum())
out["n_hist"] = [int(v) for v in np.asarray(st.n_hist)[:mesh.rounds + 1]]
out["n_remaining"] = int(st.n_remaining)
out["ref_live"] = [M * P] + live
out["cost_ratio"] = float(min_d2(flat, mesh.centers).sum(dtype=np.float64)
                          / min_d2(flat, means).sum(dtype=np.float64))

leaves = {}
for name in ("x", "w", "alive", "machine_ok"):
    a = getattr(mesh_state, name)
    leaves[name] = sorted((s.device.id, s.index[0].start or 0,
                           s.data.shape[0]) for s in a.addressable_shards)
out["leaves"] = leaves
out["own_rows"] = all(np.array_equal(np.asarray(s.data)[0],
                                     x[s.index[0].start or 0])
                      for s in mesh_state.x.addressable_shards)

def placed_bytes(backend, **kw):
    res, _ = run(backend, **kw)
    return res.extra["trace"]["counters"]["place_max_device_bytes"]

plan = FailurePlan(fail_at={0: (1,)})
out["place"] = {
    "mesh": placed_bytes("mesh"),
    "mesh_w": placed_bytes("mesh", w=w),
    "mesh_plan": placed_bytes("mesh", failure_plan=plan),
    "mesh_device": placed_bytes("mesh", data=xd),
    "virtual": placed_bytes("virtual"),
    "virtual_plan": placed_bytes("virtual", failure_plan=plan),
}
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def out():
    env = dict(os.environ)
    root = pathlib.Path(__file__).resolve().parents[1]
    env["PYTHONPATH"] = str(root / "src")
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_mesh_fit_matches_the_float32_reference(out):
    """Each round removes exactly the points the reference removes at the
    fit's own centers and threshold; every live count the fit reported is
    the reference's; its centers cost less than the generating means."""
    assert out["devices"] == M
    assert out["rounds"] >= 1
    assert out["flips"] == 0
    assert out["n_hist"] == out["ref_live"]
    assert out["n_remaining"] == out["ref_live"][-1]
    assert out["cost_ratio"] <= 1.0


def test_mesh_fit_is_bit_equal_to_virtual(out):
    assert out["bit_equal"]


def test_mesh_fit_of_sharded_device_input_is_bit_equal(out):
    """Points already sharded one machine per device are fitted where they
    lie, with no host copy, to the same result as their NumPy copy."""
    assert out["device_bit_equal"]
    assert out["device_counters"] == {"h2d_bytes": None,
                                      "input_on_device_bytes": M * P * D * 4}


@pytest.mark.parametrize("leaf", ["x", "w", "alive", "machine_ok"])
def test_each_machine_lives_on_its_own_device(out, leaf):
    """(device, first machine, machines held) of every shard."""
    assert out["leaves"][leaf] == [[j, j, 1] for j in range(M)]
    assert out["own_rows"]


# Bytes of one machine's rows: x is (M, P, D) float32, w float32, alive
# bool. The mesh's key (two uint32) is replicated from device 0, so the
# other devices receive its 8 bytes besides their rows; points already
# sharded on the devices move not at all.
X, W, ALIVE, KEY = P * D * 4, P * 4, P, 8
PLACED = {
    "mesh": X + KEY,
    "mesh_w": X + W + KEY,
    "mesh_plan": X + W + ALIVE + KEY,
    "mesh_device": KEY,
    "virtual": M * X,
    "virtual_plan": M * (X + W + ALIVE),
}


@pytest.mark.parametrize("case", sorted(PLACED))
def test_place_counter_is_one_machines_share(out, case):
    """The most any device receives while the input is placed: one
    machine's rows on the mesh, all of them on the one virtual device."""
    assert out["place"][case] == PLACED[case]


_FAILURE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec
from repro.api import fit
from repro.ft.failures import FailurePlan

x = np.random.default_rng(1).normal(size=(4, 4096, 8)).astype(np.float32)
def run(trace):
    res = fit(x, 4, algo="soccer", backend="mesh", epsilon=0.2,
              eta_override=400, key=jax.random.PRNGKey(5), seed=5,
              failure_plan=FailurePlan(fail_at={1: (2,)}), trace=trace)
    st = res.extra["state"]
    return [np.asarray(a).tolist() for a in (
        res.centers, res.n_hist, res.v_hist, res.wire_bytes,
        st.machine_ok, st.alive)] + [res.rounds]
plain, traced = run(None), run("rounds")
print("RESULT " + json.dumps({"devices": jax.device_count(),
                              "rounds": plain[-1],
                              "machine_ok": plain[4],
                              "same": plain == traced}))
"""


def test_mesh_failure_after_round_one_traced_and_untraced_agree():
    """A machine killed after round 1 on the mesh: the hook's state is
    placed for the next program, and a traced fit returns bit for bit
    what an untraced one does."""
    env = dict(os.environ)
    root = pathlib.Path(__file__).resolve().parents[1]
    env["PYTHONPATH"] = str(root / "src")
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", _FAILURE_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    out = json.loads(line[len("RESULT "):])
    assert out["devices"] == M
    assert out["rounds"] >= 1
    assert out["machine_ok"] == [True, True, False, True]
    assert out["same"]
