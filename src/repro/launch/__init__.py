"""Launch utilities: mesh construction and the multi-host CLI.

``python -m repro.launch --devices 8 ...`` runs a MeshBackend fit over
one machine per (possibly multi-host) device and prints the achieved
wire-byte telemetry as JSON. Mesh builders live in ``repro.launch.mesh``.

Re-exports are lazy (module ``__getattr__``): ``python -m repro.launch``
runs this module BEFORE ``__main__`` gets to set
``XLA_FLAGS=--xla_force_host_platform_device_count=N``, and jax reads
the flag at first import — so nothing here may import jax eagerly.
"""
_MESH_EXPORTS = ("fsdp_axes", "initialize_multi_host", "machine_mesh",
                 "make_production_mesh", "make_test_mesh")

__all__ = list(_MESH_EXPORTS)


def __getattr__(name):
    if name in _MESH_EXPORTS:
        from repro.launch import mesh
        return getattr(mesh, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
