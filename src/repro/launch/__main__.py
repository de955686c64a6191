"""``python -m repro.launch`` — the multi-host / many-device launch CLI.

Runs one ``repro.api.fit`` on a MeshBackend spanning every global device
(one machine per device) and prints the wire telemetry as JSON: achieved
uplink bytes per round next to the modeled bytes and the Ω(m·k)
communication frontier (Zhang et al., arXiv:1507.00026).

Single host, emulated machines::

    python -m repro.launch --devices 8 --algo soccer --k 16

``--devices N`` sets ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
**before jax is imported** — jax reads the flag once at import, which is
why this module defers every repro/jax import until after argument
parsing (and why ``repro.launch.__init__`` re-exports lazily).

Multi-host (one process per host, same command on each)::

    python -m repro.launch --coordinator host0:1234 \
        --num-processes 2 --process-id $RANK --algo soccer --k 16

Each process contributes its local devices; ``MeshBackend.put`` builds
global arrays from process-local shards, and the printed wire bytes are
the bytes the mesh collectives actually moved.
"""
from __future__ import annotations

import argparse
import json
import os
import sys


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="python -m repro.launch",
        description="Run a mesh-backend fit and print wire telemetry.")
    ap.add_argument("--devices", type=int, default=0,
                    help="emulate N host devices (sets XLA_FLAGS "
                         "--xla_force_host_platform_device_count before "
                         "jax import); 0 = use the devices jax finds")
    ap.add_argument("--coordinator", default=None,
                    help="multi-host coordinator address host:port")
    ap.add_argument("--num-processes", type=int, default=None)
    ap.add_argument("--process-id", type=int, default=None)
    ap.add_argument("--algo", default="soccer")
    ap.add_argument("--k", type=int, default=16)
    ap.add_argument("--n", type=int, default=1 << 14,
                    help="synthetic points (Gaussian blobs)")
    ap.add_argument("--d", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--uplink-dtype", default=None,
                    choices=[None, "float32", "bfloat16", "float16",
                             "int8"])
    ap.add_argument("--uplink-wire", default=None,
                    choices=[None, "auto", "codes", "values"])
    ap.add_argument("--param", action="append", default=[],
                    metavar="NAME=VALUE",
                    help="extra algorithm knob, repeatable "
                         "(values parsed as JSON, falling back to str)")
    return ap


def _parse_params(pairs):
    out = {}
    for pair in pairs:
        name, _, value = pair.partition("=")
        if not _:
            raise SystemExit(f"--param expects NAME=VALUE, got {pair!r}")
        try:
            out[name] = json.loads(value)
        except json.JSONDecodeError:
            out[name] = value
    return out


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if args.devices:
        if "jax" in sys.modules:
            raise SystemExit(
                "--devices must set XLA_FLAGS before jax is imported, "
                "but jax is already loaded in this process")
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={args.devices}"
        ).strip()

    from repro.launch.mesh import initialize_multi_host, machine_mesh
    initialize_multi_host(coordinator_address=args.coordinator,
                          num_processes=args.num_processes,
                          process_id=args.process_id)
    from repro.launch.cache import use_compile_cache
    use_compile_cache()

    import jax
    import numpy as np

    from repro.api import fit
    from repro.api.backends import MeshBackend
    from repro.api.result import omega_mk_bytes

    m = jax.device_count()
    backend = MeshBackend(machine_mesh(m))

    rng = np.random.default_rng(args.seed)
    centers = rng.normal(scale=4.0, size=(args.k, args.d))
    x = (centers[rng.integers(args.k, size=args.n)]
         + rng.normal(size=(args.n, args.d))).astype(np.float32)

    res = fit(x, args.k, algo=args.algo, backend=backend, m=m,
              seed=args.seed, uplink_dtype=args.uplink_dtype,
              uplink_wire=args.uplink_wire,
              **_parse_params(args.param))

    omega = omega_mk_bytes(m, args.k, args.d)
    wire_total = res.wire_bytes_total
    report = {
        "algo": res.algo, "backend": res.backend,
        "m": m, "processes": jax.process_count(),
        "k": args.k, "n": args.n, "d": args.d,
        "rounds": res.rounds,
        "uplink_points": [int(v) for v in res.uplink_points],
        "uplink_bytes_modeled": [int(v) for v in res.uplink_bytes],
        "wire_bytes": (None if res.wire_bytes is None
                       else [int(v) for v in res.wire_bytes]),
        "wire_meta_bytes": (None if res.wire_meta_bytes is None
                            else [int(v) for v in res.wire_meta_bytes]),
        "wire_bytes_total": wire_total,
        "omega_mk_bytes": omega,
        "bytes_vs_omega_mk": (None if wire_total is None
                              else round(wire_total / omega, 3)),
        "cost": res.cost(x),
        "wall_time_s": round(res.wall_time_s, 3),
    }
    if jax.process_index() == 0:
        print(json.dumps(report, indent=2))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
