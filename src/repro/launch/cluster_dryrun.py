import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=512")

"""SOCCER production-mesh dry-run (the paper's own workload at scale).

Lowers one SOCCER round for n = 10.24M points (the paper's 10M synthetic
runs), d=15, k=100, eps=0.1 over the 16x16 (and 2x16x16) mesh — one
machine per chip — in both coordinator modes:

  * gather   — paper-faithful: P1/P2 materialized via offset-scatter psum
  * sharded  — beyond-paper:   samples stay sharded (core/sharded_kmeans)

and reports the three roofline terms per mode. This is the §Perf evidence
for the sharded-coordinator optimization.

  PYTHONPATH=src python -m repro.launch.cluster_dryrun [--multipod]
"""
import argparse
import functools
import json
import pathlib
import time

import jax
import jax.numpy as jnp

from repro.api.backends import MeshBackend, mesh_comm
from repro.configs.soccer_paper import SoccerParams
from repro.core.soccer import (STATE_MARKS, derive_constants, init_state,
                               soccer_round)
from repro.launch.mesh import make_production_mesh
from repro.roofline.analysis import Roofline
from repro.roofline.hlo_stats import analyze_hlo

RESULTS = pathlib.Path(__file__).resolve().parents[3] / \
    "benchmarks" / "results" / "dryrun"


def soccer_model_flops(const, n: int, d: int) -> float:
    """Useful flops per round: removal pass (n·k_plus·d·2) + coordinator
    lloyd (eta·k_plus·d·2·iters) + seeding + threshold pass."""
    removal = 2.0 * n * const.k_plus * d
    lloyd = 2.0 * const.eta * const.k_plus * d * (const.lloyd_iters + 1)
    thresh = 2.0 * const.eta * const.k_plus * d
    return removal + lloyd + thresh


def run(mode: str, multi_pod: bool, n: int = 10_240_000, d: int = 15,
        k: int = 100, tag: str = "baseline") -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    comm = mesh_comm(mesh)
    m = comm.m
    p_local = n // m
    params = SoccerParams(k=k, epsilon=0.1,
                          sharded_coordinator=mode.startswith("sharded"),
                          sharded_seeding=("kmeanspar" if
                                           mode == "sharded_kps" else "d2"))
    const = derive_constants(n, p_local, params, m=m)

    state_struct = jax.eval_shape(
        lambda: init_state(
            jnp.zeros((m, p_local, d), jnp.float32), const,
            jax.random.PRNGKey(0)))
    step = MeshBackend(mesh).compile(
        functools.partial(soccer_round, comm=comm, const=const),
        (STATE_MARKS,), STATE_MARKS)
    t0 = time.time()
    lowered = step.lower(state_struct)
    compiled = lowered.compile()
    dt = time.time() - t0
    mem = compiled.memory_analysis()
    st = analyze_hlo(compiled.as_text())
    roof = Roofline(flops=st.flops, hbm_bytes=st.bytes,
                    coll_bytes=st.coll_total, coll_by_kind=st.coll,
                    model_flops=soccer_model_flops(const, n, d),
                    chips=chips)
    rec = {
        "arch": "soccer-paper", "shape": f"n10M_k{k}_{mode}",
        "mesh": "multipod" if multi_pod else "single", "tag": tag,
        "status": "ok", "compile_s": round(dt, 1),
        "const": {"eta": const.eta, "k_plus": const.k_plus,
                  "machines": m},
        "memory": {"peak_per_device": int(mem.argument_size_in_bytes +
                                          mem.temp_size_in_bytes +
                                          mem.output_size_in_bytes -
                                          mem.alias_size_in_bytes),
                   "temp_bytes": int(mem.temp_size_in_bytes)},
        "roofline": roof.as_dict(),
        "collective_ops": {kk: int(vv) for kk, vv in st.coll_ops.items()},
    }
    RESULTS.mkdir(parents=True, exist_ok=True)
    name = f"{rec['mesh']}_soccer-paper_{mode}"
    if tag != "baseline":
        name += f"_{tag}"
    (RESULTS / f"{name}.json").write_text(json.dumps(rec, indent=1))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--mode", default="both",
                    choices=["gather", "sharded", "sharded_kps", "both"])
    ap.add_argument("--tag", default="baseline")
    args = ap.parse_args()
    modes = ["gather", "sharded"] if args.mode == "both" else [args.mode]
    for mode in modes:
        rec = run(mode, args.multipod, tag=args.tag)
        r = rec["roofline"]
        print(f"soccer/{mode:8s} mesh={rec['mesh']:9s} "
              f"tc={r['t_compute_s']:.4g} tm={r['t_memory_s']:.4g} "
              f"tx={r['t_collective_s']:.4g} "
              f"bottleneck={r['bottleneck']} "
              f"coll={ {kk: f'{vv:.3g}' for kk, vv in r['coll_by_kind'].items()} } "
              f"coll_ops={sum(rec['collective_ops'].values())} "
              f"mem={rec['memory']['peak_per_device']/2**30:.2f}G "
              f"compile={rec['compile_s']}s", flush=True)


if __name__ == "__main__":
    main()
