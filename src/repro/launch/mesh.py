"""Production meshes.

Single pod: 16x16 = 256 chips ('data','model'); multi-pod: 2x16x16 = 512
('pod','data','model'). Defined as a FUNCTION so importing this module
never touches jax device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax
import; tests/benches see the single real device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh(n_data: int = 1, n_model: int = 1) -> Mesh:
    """Small mesh over however many (host) devices a test process has."""
    return jax.make_mesh((n_data, n_model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def fsdp_axes(mesh: Mesh):
    return tuple(a for a in mesh.axis_names if a != "model")


def initialize_multi_host(coordinator_address=None, num_processes=None,
                          process_id=None) -> int:
    """Join (or skip) the jax distributed runtime; returns process_count.

    With no arguments and no cluster environment this is a no-op
    single-process launch — the common local/test path. With arguments
    (or under a recognized cluster env: SLURM, Open MPI, GKE) it calls
    ``jax.distributed.initialize`` so every host contributes its local
    devices to the global device list; call BEFORE any other jax API.
    ``repro.launch.__main__`` exposes this as the CLI entry.
    """
    explicit = (coordinator_address is not None or num_processes is not None
                or process_id is not None)
    if explicit and (num_processes or 1) > 1:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes, process_id=process_id)
    return jax.process_count()


def machine_mesh(m=None) -> Mesh:
    """One-axis ("machines",) mesh over the GLOBAL device list.

    Process-count aware: under multi-host each process contributes its
    ``jax.local_device_count()`` devices and the mesh spans all
    ``jax.device_count()`` of them, so a MeshBackend built on it places
    machine ``j`` on global device ``j`` regardless of which host owns
    it. ``m`` defaults to the global device count and must divide into
    it one-machine-per-device.
    """
    n = jax.device_count()
    m = n if m is None else int(m)
    if m != n:
        raise ValueError(
            f"machine_mesh places one machine per device: m={m} but the "
            f"cluster has {n} global devices "
            f"({jax.process_count()} process(es) x "
            f"{jax.local_device_count()} local)")
    return jax.make_mesh((m,), ("machines",), axis_types=(AxisType.Auto,))
