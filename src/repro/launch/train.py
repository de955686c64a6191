"""Production train driver: mesh + shardings + checkpoint/restart.

The mesh-aware counterpart of examples/train_lm.py: builds a mesh over
whatever devices exist (real TPUs in production; set
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to demo on CPU),
applies the Partitioner's parameter shardings and the activation-anchor
context, jits the train step with donation, and checkpoints/restores.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b \
      --reduced --steps 50 --mesh 2,2
"""
from __future__ import annotations

import argparse
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.checkpoint.checkpointer import Checkpointer
from repro.configs import get_config
from repro.launch.input_specs import make_partitioner, opt_shardings
from repro.sharding.activations import activation_mesh
from repro.train.optimizer import OptConfig
from repro.train.train_step import make_train_state, make_train_step


def build_mesh(spec: str):
    shape = tuple(int(s) for s in spec.split(","))
    names = ("data", "model")[: len(shape)] if len(shape) <= 2 else \
        ("pod", "data", "model")
    return jax.make_mesh(shape, names,
                         axis_types=(AxisType.Auto,) * len(names))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--mesh", default="1,1",
                    help="comma mesh shape, e.g. 2,2 or 2,16,16")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    mesh = build_mesh(args.mesh)
    part = make_partitioner(mesh, cfg)
    opt = OptConfig(name=cfg.optimizer, lr_peak=3e-3, warmup_steps=10,
                    decay_steps=args.steps)
    tp_axis = "__none__" if cfg.sharding_policy == "fsdp" else "model"

    with mesh, activation_mesh(mesh, tp_axis=tp_axis):
        state = make_train_state(jax.random.PRNGKey(0), cfg, opt)
        p_specs = part.specs(state["params"])
        p_shard = jax.tree.map(lambda s: NamedSharding(mesh, s), p_specs,
                               is_leaf=lambda s: isinstance(s, P))
        state_shard = {
            "params": p_shard,
            "opt": opt_shardings(mesh, p_specs, state["params"], opt.name),
            "step": NamedSharding(mesh, P()),
        }
        state = jax.device_put(state, state_shard)
        bspec = part.batch_spec()
        b_ax = bspec if args.batch % mesh.devices.size == 0 or \
            isinstance(bspec, str) else "data"
        b_shard = NamedSharding(mesh, P(b_ax, None))

        step_fn = jax.jit(make_train_step(cfg, opt),
                          in_shardings=(state_shard,
                                        {"tokens": b_shard,
                                         "targets": b_shard}),
                          donate_argnums=0)

        ckpt = Checkpointer(args.ckpt_dir or
                            tempfile.mkdtemp(prefix=f"mesh_{cfg.name}_"))
        if args.ckpt_dir and ckpt.latest_step() is not None:
            state = ckpt.restore(jax.eval_shape(lambda: state),
                                 shardings=state_shard)
            print(f"resumed from step {int(state['step'])}")

        rng = np.random.default_rng(0)
        t0 = time.time()
        for i in range(int(state["step"]), args.steps):
            toks = rng.integers(0, cfg.vocab_size,
                                size=(args.batch, args.seq + 1),
                                dtype=np.int32)
            batch = {"tokens": jnp.asarray(toks[:, :-1]),
                     "targets": jnp.asarray(toks[:, 1:])}
            state, m = step_fn(state, batch)
            if (i + 1) % args.ckpt_every == 0:
                ckpt.save(i + 1, state)
            if (i + 1) % 10 == 0 or i == 0:
                print(f"step {i+1:4d} loss={float(m['loss']):.4f} "
                      f"gnorm={float(m['grad_norm']):.3f} "
                      f"({(time.time()-t0):.1f}s)", flush=True)
                t0 = time.time()
        ckpt.wait()
        print(f"done; devices={mesh.devices.size} "
              f"checkpoints in {ckpt.dir}")


if __name__ == "__main__":
    main()
