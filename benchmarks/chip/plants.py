#!/usr/bin/env python3
"""Faults and the control, planted under the timed path.

    python benchmarks/chip/plants.py --workload gm10m-k25.soccer \\
        --plant control --seeds 11 12 13 --seconds 3 [--n 64000] [--cpu]

Each plant replaces one piece of the program for the rest of the process,
so that a run of the cell shows whether its check catches it:

- ``none``: the program as it is (the sound readings);
- ``control``: ``reference.control_remove_below`` (distances from three
  bf16 passes, ``Precision.HIGH``) in place of ``kernels.ops.remove_below``;
- ``unchanged_step``: a SOCCER round that returns its state unchanged;
- ``half_batch``: the removal sweeps only the first half of the machines;
- ``no_exchange``: the collectives between machines deliver nothing from
  the other machines (a reduce keeps the local part, a gather leaves the
  others' rows zero);
- ``altered_centers``: the coordinator's centers moved by 0.05 where they
  are made;
- ``altered_count``: each machine's live count 64 too high where it is
  made.

For every seed it runs the cell once, in this one process, and prints the
result's ``checks`` as one JSON line. ``--n`` and ``--cpu`` make the run
small and let it run off the chip (the tests do); without them it is the
cell as committed, on the chip, at its own size. The benchmark's own runs
never plant anything.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run as bench  # noqa: E402  (puts the program's src on the path)


def plant(name: str) -> None:
    import jax.numpy as jnp
    from jax import lax

    import reference
    from repro.core import comm, soccer
    from repro.kernels import ops
    real_remove, real_blackbox = ops.remove_below, soccer._blackbox
    if name == "none":
        return
    if name == "control":
        ops.remove_below = reference.control_remove_below
    elif name == "unchanged_step":
        soccer.soccer_round = lambda state, comm, const: state
    elif name == "half_batch":
        def half(x, c, alive, v, c_valid=None, *, backend=None):
            h = max(x.shape[0] // 2, 1)
            a, live = real_remove(x[:h], c, alive[:h], v, c_valid)
            return (jnp.concatenate([a, alive[h:]]),
                    jnp.concatenate([live, jnp.zeros_like(live[h:])]))
        ops.remove_below = half
    elif name == "no_exchange":
        def local_reduce(self, x):
            return jnp.sum(x, axis=0)

        def local_gather(self, x):
            out = jnp.zeros((self.m * x.shape[0],) + x.shape[1:], x.dtype)
            return lax.dynamic_update_slice_in_dim(
                out, x, self.machine_ids()[0] * x.shape[0], 0)
        comm.MeshCluster._reduce = local_reduce
        comm.MeshCluster._gather = local_gather
    elif name == "altered_centers":
        def moved(const, key, x, w, k):
            return real_blackbox(const, key, x, w, k) + 0.05
        soccer._blackbox = moved
    elif name == "altered_count":
        def off_by_one(x, c, alive, v, c_valid=None, *, backend=None):
            a, live = real_remove(x, c, alive, v, c_valid)
            return a, live + 64
        ops.remove_below = off_by_one
    else:
        raise ValueError(f"unknown plant {name!r}")


PLANTS = ("none", "control", "unchanged_step", "half_batch", "no_exchange",
          "altered_centers", "altered_count")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--plant", choices=PLANTS, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--n", type=int, default=0,
                    help="points in all (default: the configuration's)")
    ap.add_argument("--cpu", action="store_true",
                    help="run without a chip (tests)")
    args = ap.parse_args(argv)
    cell = bench.load("workloads", args.workload)
    config = bench.load("configs", cell["config"])
    if args.n:
        config["n"] = args.n
    plant(args.plant)
    for seed in args.seeds:
        out = bench.run_cell(args.workload, seed, args.seconds, False,
                             cell=cell, config=config,
                             require_chip=not args.cpu)
        print(json.dumps({"plant": args.plant, "seed": seed,
                          "correct": out["correct"],
                          "attempted": out["attempted"],
                          "metrics": out["metrics"],
                          "device": out["device"],
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
