"""Roofline plumbing: trip-aware HLO stats calibrated on known programs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.roofline.hlo_stats import analyze_hlo, _shape_bytes


def test_shape_bytes():
    assert _shape_bytes("f32[4,8]") == 128
    assert _shape_bytes("bf16[10]") == 20
    assert _shape_bytes("(f32[2], s32[3])") == 8 + 12
    assert _shape_bytes("pred[]") == 1


def test_dot_flops_exact():
    a = jnp.zeros((64, 32))
    b = jnp.zeros((32, 16))
    compiled = jax.jit(lambda a, b: a @ b).lower(a, b).compile()
    st = analyze_hlo(compiled.as_text())
    assert st.flops == 2 * 64 * 32 * 16


def test_while_trip_multiplication():
    """A scanned matmul must count flops per iteration, not once."""
    a = jnp.zeros((8, 16, 16))   # 8 iterations

    def f(a):
        def body(x, w):
            return x @ w, ()
        x, _ = jax.lax.scan(body, jnp.eye(16), a)
        return x

    compiled = jax.jit(f).lower(a).compile()
    st = analyze_hlo(compiled.as_text())
    expect = 8 * 2 * 16 * 16 * 16
    assert abs(st.flops - expect) / expect < 0.01, st.flops
    # XLA's own cost model counts the body once -> ~8x lower
    ca = compiled.cost_analysis()
    assert ca["flops"] <= expect / 4


def test_bytes_scale_with_trips():
    big = jnp.zeros((4, 256, 256))

    def f(xs):
        def body(c, x):
            return c + 2 * x, ()
        c, _ = jax.lax.scan(body, jnp.zeros((256, 256)), xs)
        return c

    compiled = jax.jit(f).lower(big).compile()
    st = analyze_hlo(compiled.as_text())
    one_slice = 256 * 256 * 4
    assert st.bytes >= 4 * one_slice     # at least reads every slice


@pytest.mark.skipif(jax.device_count() != 1, reason="single device run")
def test_collectives_counted_in_subprocess():
    """SPMD collectives parsed with correct sizes (8 host devices)."""
    import json
    import os
    import pathlib
    import subprocess
    import sys
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from jax.sharding import AxisType
from repro.roofline.hlo_stats import analyze_hlo
mesh = jax.make_mesh((8,), ("d",), axis_types=(AxisType.Auto,))
x = jax.ShapeDtypeStruct((1024, 64), jnp.float32)
s = NamedSharding(mesh, P("d", None))
f = lambda a: jnp.sum(a)  # cross-shard reduction -> all-reduce f32[]
c = jax.jit(f, in_shardings=(s,),
            out_shardings=NamedSharding(mesh, P())).lower(x).compile()
st = analyze_hlo(c.as_text())
print("RESULT " + json.dumps({"coll": st.coll}))
"""
    env = dict(os.environ)
    root = pathlib.Path(__file__).resolve().parents[1]
    env["PYTHONPATH"] = str(root / "src")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")]
    out = json.loads(line[0][len("RESULT "):])
    assert "all-reduce" in out["coll"]
    assert out["coll"]["all-reduce"] >= 4        # at least one f32[]
