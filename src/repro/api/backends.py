"""Execution backends for the ``repro.api`` facade.

A *backend* decides where the machine axis of a ``(m, p, ...)`` array
lives; the algorithm drivers in ``repro.core`` are written once against
the comm abstraction (``repro.core.comm``) and bound to a backend:

* ``VirtualBackend`` — all ``m`` machines folded into axis 0 on one
  device (``VirtualCluster``); compiled functions are plain ``jax.jit``.
* ``MeshBackend``   — one machine per shard of a device mesh
  (``MeshCluster``); compiled functions are ``jit(shard_map(...))`` over
  the mesh's machine axes.

Drivers describe each compiled function's arguments/results with a
*marks* pytree whose leaves are ``MACHINE`` (leading machine axis,
sharded on a mesh) or ``REPLICATED`` (identical on every machine). The
backend translates marks into PartitionSpecs (mesh) or ignores them
(virtual) — the same driver loop then runs unchanged in both modes.

The backends are frozen dataclasses, so they hash by value:
``core.programs`` keys its cache of compiled programs on the backend and
its comm, and a backend or comm that cannot be hashed gets programs
built anew on every fit.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Protocol, Tuple, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.comm import MeshCluster, VirtualCluster

# Marks for the leaves of compiled-function argument/result pytrees.
MACHINE = "machine"        # (local_m, ...) leading machine axis
REPLICATED = "rep"         # identical value on every machine

# Supported machine->coordinator upload precisions (see uplink_dtype on
# the backends): points are rounded to this dtype before the scatter-psum
# "upload" and accounted at its width in ClusterResult.uplink_bytes.
# "int8" routes through the affine quantizer in ft/compression (device-
# side storage stays f32 — the dequantized 256-level grid — so the
# kernels need no int8 path; see core.sampling.uplink_storage_dtype).
UPLINK_DTYPES = ("float32", "bfloat16", "float16", "int8")


def check_uplink_dtype(dtype) -> str:
    name = str(jnp.dtype(dtype) if not isinstance(dtype, str) else dtype)
    if name not in UPLINK_DTYPES:
        raise ValueError(
            f"unsupported uplink_dtype {dtype!r}: expected one of "
            f"{', '.join(UPLINK_DTYPES)}")
    return name


# Wire transport of the (quantized) upload payload — the uplink_wire knob:
#   "values": payloads move at their storage width (int8 payloads move as
#       their f32 reconstruction — compression ends at accounting, the
#       pre-PR-8 behavior);
#   "codes":  int8 payloads move as 1-byte codes + one per-machine affine
#       (scale, zero_point) pair and are dequantized on arrival (the
#       *_compressed gathers in core/comm) — same reconstructed values,
#       1/4 the achieved wire bytes;
#   "auto":   "codes" whenever uplink_dtype="int8", else "values".
UPLINK_WIRES = ("auto", "codes", "values")


def check_uplink_wire(wire, dtype: str = "float32") -> str:
    """Validate and resolve an uplink_wire knob against the uplink dtype.

    Returns the resolved transport ("codes" | "values"); "auto" picks
    "codes" exactly when the payload is int8 (float payloads are already
    at wire width — there is nothing further to encode).
    """
    if wire not in UPLINK_WIRES:
        raise ValueError(
            f"unsupported uplink_wire {wire!r}: expected one of "
            f"{', '.join(UPLINK_WIRES)}")
    if wire == "auto":
        return "codes" if dtype == "int8" else "values"
    if wire == "codes" and dtype != "int8":
        raise ValueError(
            f"uplink_wire='codes' ships int8 codes + per-machine qparams "
            f"and needs uplink_dtype='int8', got uplink_dtype={dtype!r}")
    return wire


def mesh_comm(mesh: Mesh, axis_names: Optional[Tuple[str, ...]] = None
              ) -> MeshCluster:
    """MeshCluster over the given mesh axes (all axes by default)."""
    axis_names = tuple(axis_names or mesh.axis_names)
    sizes = tuple(int(mesh.shape[a]) for a in axis_names)
    return MeshCluster(m=int(np.prod(sizes)), axis_names=axis_names,
                       axis_sizes=sizes)


@runtime_checkable
class Backend(Protocol):
    """What a driver needs: a comm, data placement, and compilation.

    Backends may additionally carry ``uplink_dtype`` (one of
    ``UPLINK_DTYPES``) — drivers read it with ``getattr(backend,
    "uplink_dtype", "float32")``, quantize upload payloads with
    ``core.sampling.quantize_uplink`` and account
    ``ClusterResult.uplink_bytes`` at that width — and ``uplink_wire``
    (one of ``UPLINK_WIRES``), read via
    ``check_uplink_wire(getattr(backend, "uplink_wire", "auto"), dtype)``
    to pick the codes vs values transport of ``core.comm``.
    """
    name: str

    def make_comm(self, m: int):
        """Comm object for ``m`` machines (VirtualCluster/MeshCluster)."""

    def put(self, tree: Any, marks: Any) -> Any:
        """Place a pytree according to its marks: on a mesh each device
        receives only its shard of a host leaf; on one device the host
        leaves are put there whole and device leaves stay as they are."""

    def compile(self, fn, in_marks: Tuple, out_marks: Any,
                donate: Tuple[int, ...] = ()):
        """Compile ``fn(*args)``; marks mirror the args/result pytrees.

        ``donate`` lists argument positions whose buffers the caller
        hands over (jit ``donate_argnums``) — drivers donate the center
        buffers they thread through multi-round scans so each round
        updates in place instead of allocating a fresh (rows, d) block.
        """


@dataclasses.dataclass(frozen=True)
class VirtualBackend:
    """Single-device execution: machine axis is a plain array axis."""
    name: str = "virtual"
    uplink_dtype: str = "float32"
    uplink_wire: str = "auto"

    def make_comm(self, m: int) -> VirtualCluster:
        return VirtualCluster(m)

    def put(self, tree, marks):
        del marks
        return jax.device_put(tree)     # host leaves to the one device

    def compile(self, fn, in_marks, out_marks, donate=()):
        del in_marks, out_marks
        return jax.jit(fn, donate_argnums=donate)


@dataclasses.dataclass(frozen=True)
class MeshBackend:
    """One machine per shard of ``mesh``'s ``axis_names`` axes."""
    mesh: Mesh
    axis_names: Optional[Tuple[str, ...]] = None
    name: str = "mesh"
    uplink_dtype: str = "float32"
    uplink_wire: str = "auto"

    @property
    def machine_axes(self) -> Tuple[str, ...]:
        return tuple(self.axis_names or self.mesh.axis_names)

    def make_comm(self, m: int) -> MeshCluster:
        comm = mesh_comm(self.mesh, self.machine_axes)
        if comm.m != m:
            raise ValueError(
                f"mesh backend has {comm.m} machine shards over axes "
                f"{self.machine_axes} but the data has m={m} machines")
        return comm

    def _spec(self, mark: str) -> P:
        return P(self.machine_axes) if mark == MACHINE else P()

    def _specs(self, marks):
        return jax.tree.map(self._spec, marks)

    def put(self, tree, marks):
        if jax.process_count() > 1:
            # multi-host (repro.launch): device_put cannot build a global
            # array from host-local data — each process contributes the
            # machine rows of ITS devices (MACHINE leaves arrive as the
            # process-local (m // process_count, ...) slab; REPLICATED
            # leaves arrive whole on every process). A jax.Array leaf is
            # a global value already, possibly not fully addressable, and
            # is resharded on the devices without a host copy.
            def _place(leaf, mk):
                sharding = NamedSharding(self.mesh, self._spec(mk))
                if isinstance(leaf, jax.Array):
                    return jax.device_put(leaf, sharding)
                return jax.make_array_from_process_local_data(
                    sharding, np.asarray(leaf))
            return jax.tree.map(_place, tree, marks)
        return jax.tree.map(
            lambda leaf, mk: jax.device_put(
                leaf, NamedSharding(self.mesh, self._spec(mk))),
            tree, marks)

    def compile(self, fn, in_marks, out_marks, donate=()):
        mapped = jax.shard_map(fn, mesh=self.mesh,
                               in_specs=self._specs(in_marks),
                               out_specs=self._specs(out_marks),
                               check_vma=False)
        return jax.jit(mapped, donate_argnums=donate)


def _replace_knob(backend, field: str, value: str):
    if not (dataclasses.is_dataclass(backend) and any(
            f.name == field for f in dataclasses.fields(backend))):
        raise ValueError(
            f"backend {type(backend).__name__} does not carry an "
            f"{field} field; construct it with {field}={value!r} instead "
            f"of passing the knob to fit()")
    return dataclasses.replace(backend, **{field: value})


def resolve_backend(backend, m: int, uplink_dtype=None,
                    uplink_wire=None) -> Backend:
    """Accepts a Backend, a Mesh, or "virtual" | "mesh" | "auto".

    "auto" picks the mesh backend when the host has at least ``m``
    addressable devices (one machine per device), else the virtual one.
    ``uplink_dtype``/``uplink_wire`` (if given) set the upload precision
    and wire transport on the resolved backend; already-constructed
    Backend instances are rebuilt via ``dataclasses.replace`` when a
    knob conflicts with theirs. The final (dtype, wire) pair is
    validated — requesting the codes wire for a float payload raises
    here, not rounds into the run.
    """
    ud = None if uplink_dtype is None else check_uplink_dtype(uplink_dtype)
    uw = None
    if uplink_wire is not None:
        if uplink_wire not in UPLINK_WIRES:
            raise ValueError(
                f"unsupported uplink_wire {uplink_wire!r}: expected one "
                f"of {', '.join(UPLINK_WIRES)}")
        uw = uplink_wire

    def _check(bk):
        check_uplink_wire(getattr(bk, "uplink_wire", "auto"),
                          getattr(bk, "uplink_dtype", "float32"))
        return bk

    if backend is None:
        backend = "virtual"
    if isinstance(backend, Mesh):
        return _check(MeshBackend(backend, uplink_dtype=ud or "float32",
                                  uplink_wire=uw or "auto"))
    if not isinstance(backend, str):
        # already a Backend (duck-typed)
        if ud and getattr(backend, "uplink_dtype", "float32") != ud:
            backend = _replace_knob(backend, "uplink_dtype", ud)
        if uw and getattr(backend, "uplink_wire", "auto") != uw:
            backend = _replace_knob(backend, "uplink_wire", uw)
        return _check(backend)
    if backend == "auto":
        backend = "mesh" if (m > 1 and jax.device_count() >= m) else "virtual"
    if backend == "virtual":
        return _check(VirtualBackend(uplink_dtype=ud or "float32",
                                     uplink_wire=uw or "auto"))
    if backend == "mesh":
        if jax.device_count() < m:
            raise ValueError(
                f"backend='mesh' needs >= {m} devices (one per machine), "
                f"got {jax.device_count()}; use backend='virtual' or fewer "
                f"machines")
        devs = np.asarray(jax.devices()[:m]).reshape(m)
        return _check(MeshBackend(Mesh(devs, ("machines",)),
                                  uplink_dtype=ud or "float32",
                                  uplink_wire=uw or "auto"))
    raise ValueError(
        f"unknown backend {backend!r}: expected 'virtual', 'mesh', 'auto', "
        f"a jax Mesh, or a Backend instance")
