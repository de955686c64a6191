"""Machine/coordinator communication abstraction.

The paper's coordinator model has ``m`` machines that talk only to a
coordinator. On a TPU pod we realize this as SPMD over mesh axes; for
single-device tests/benchmarks we fold the machine axis into a leading
array axis. **The same algorithm code runs in both modes**: every
per-machine array has shape ``(local_m, ...)`` where

* ``VirtualCluster``:  ``local_m == m``   (one device holds all machines)
* ``MeshCluster``:     ``local_m == 1``   (one machine per mesh shard,
  collectives over the mesh axes)

Each cluster provides two raw collectives —

* ``_reduce(x)`` — sum over the machine axis of a ``(local_m, ...)``
                   array, returning the *replicated* unbatched result.
* ``_gather(x)`` — per-machine blocks: ``(local_m, ...) -> (m, ...)``
                   replicated, machine-id order.

— and everything else is derived in the shared ``_WireOps`` mixin:

* ``psum`` / ``all_machines`` / ``concat_machines`` — the recording
  wrappers every algorithm uses (count vectors, cost sums, fixed-width
  coreset blocks).
* ``gather_ragged`` — length-prefixed ragged gather: machine j
  contributes its first ``counts[j]`` rows, landing contiguously at
  offset ``sum(counts[:j])`` of a static ``(rows, ...)`` budget. Dead
  machines contribute ZERO rows (no weight-0 padding), and no dense
  ``(rows, d)`` per-machine scatter buffer ever rides the wire.
* ``*_compressed`` variants — the real int8 wire: machine-side affine
  quantization, 1-byte codes + one per-machine (scale, zero_point) pair
  through the collective, dequantization on arrival. Values land on each
  machine's own 256-level grid — bit-identical to ``fake_quantize_int8``
  before a plain gather, so results agree across wires and backends.

Wire accounting: every derived op calls ``record_wire`` at TRACE time
(shapes are static, so the recorded widths are exact for every later
execution). Drivers wrap compiled-function calls in ``wire_tally`` and
combine the static bytes with the realized ragged row counts they
already track — ``ClusterResult.wire_bytes`` reports *achieved* wire
traffic at the measured payload itemsize, one source of truth for
modeled-vs-measured comparisons.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import warnings
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# ------------------------------------------------------------------ tallies


@dataclasses.dataclass
class WireTally:
    """Machine->coordinator traffic recorded while tracing under one
    ``wire_tally`` context.

    ``payload``/``meta`` are static bytes per call of the traced
    function (fixed-shape collectives: coreset blocks, count vectors,
    qparams). ``row_bytes``/``row_meta_bytes`` are per-REALIZED-row
    widths of the ragged channels; the driver multiplies them by the
    realized row count it already tracks (the ``uplink`` history) via
    ``bytes_at``. Ragged gathers merge widths by max, so every ragged
    gather inside one traced function must share one row width — true
    for all drivers (a round's two sample uploads are the same shape).
    """
    payload: int = 0
    meta: int = 0
    row_bytes: int = 0
    row_meta_bytes: int = 0

    def bytes_at(self, rows) -> np.ndarray:
        """Achieved payload bytes for realized ragged ``rows`` (scalar
        or per-round array)."""
        return self.payload + self.row_bytes * np.asarray(rows, np.int64)

    def meta_bytes_at(self, rows) -> np.ndarray:
        return self.meta + self.row_meta_bytes * np.asarray(rows, np.int64)


_TALLY_STACK: List[WireTally] = []


@contextlib.contextmanager
def wire_tally(tally: Optional[WireTally] = None):
    """Collect wire-byte records from comm ops traced inside the block."""
    t = WireTally() if tally is None else tally
    _TALLY_STACK.append(t)
    try:
        yield t
    finally:
        _TALLY_STACK.pop()


def record_wire(*, payload: int = 0, meta: int = 0, row_bytes: int = 0,
                row_meta_bytes: int = 0) -> None:
    """Add to the innermost active tally (no-op outside any context).

    Static channels accumulate; per-row widths merge by max (see
    ``WireTally``).
    """
    if not _TALLY_STACK:
        return
    t = _TALLY_STACK[-1]
    t.payload += int(payload)
    t.meta += int(meta)
    t.row_bytes = max(t.row_bytes, int(row_bytes))
    t.row_meta_bytes = max(t.row_meta_bytes, int(row_meta_bytes))


def static_nbytes(x) -> int:
    """Wire width of a fixed-shape array (tracer-safe: shape/dtype only)."""
    return math.prod(x.shape) * jnp.dtype(x.dtype).itemsize


def _row_nbytes(x) -> int:
    """Bytes per (machine, slot) row of a ``(local_m, cap, ...)`` block."""
    return math.prod(x.shape[2:]) * jnp.dtype(x.dtype).itemsize


def _concrete_sum(counts) -> Optional[int]:
    """``int(sum(counts))`` when eager, None under tracing."""
    try:
        return int(jax.device_get(jnp.sum(counts)))
    except (jax.errors.ConcretizationTypeError,
            jax.errors.TracerArrayConversionError,
            jax.errors.TracerIntegerConversionError):
        return None


# ------------------------------------------------------------ shared ops


class _WireOps:
    """Derived collectives + wire recording over ``_reduce``/``_gather``."""

    @property
    def _fan(self) -> int:
        # one local op stands for m // local_m machines' worth of traffic
        return self.m // self.local_m

    # --- recording wrappers over the raw collectives
    def psum(self, x: jax.Array) -> jax.Array:
        record_wire(meta=static_nbytes(x) * self._fan)
        return self._reduce(x)

    def all_machines(self, x: jax.Array) -> jax.Array:
        record_wire(meta=static_nbytes(x) * self._fan)
        return self._gather(x)

    def concat_machines(self, x: jax.Array, *, meta: bool = False
                        ) -> jax.Array:
        """(local_m, t, ...) fixed-width blocks -> (m*t, ...) replicated.

        ``meta=True`` charges the bytes to the metadata channel (weight
        columns that ride alongside a payload, like the HT weights).
        """
        record_wire(**{"meta" if meta else "payload":
                       static_nbytes(x) * self._fan})
        g = self._gather(x)
        return g.reshape((-1,) + g.shape[2:])

    # --- compressed fixed-width gathers (int8 codes + per-machine qparams)
    def all_machines_compressed(self, x: jax.Array) -> jax.Array:
        """(local_m, t, ...) f32 blocks -> (m, t, ...) f32 replicated;
        the wire carries int8 codes plus one per-machine affine
        (scale, zero_point) pair on the metadata channel.

        Dequantization happens on arrival, so the result is every
        machine's block reconstructed on its own 256-level grid —
        bit-identical to ``fake_quantize_int8`` applied machine-side
        before a plain gather (same qparams, same rounding), which is
        what keeps codes-wire results equal to values-wire results for
        ``uplink_dtype="int8"`` on both backends.
        """
        from repro.ft.compression import (affine_qparams,
                                          dequantize_affine_int8,
                                          quantize_affine_int8)
        if x.ndim < 3:
            raise ValueError(
                f"compressed gathers need (local_m, rows, ...) blocks so "
                f"each machine gets its own code book; got shape {x.shape}")
        scale, zp = affine_qparams(x)          # one pair per machine
        codes = quantize_affine_int8(x, scale, zp)
        record_wire(
            payload=static_nbytes(codes) * self._fan,
            meta=(static_nbytes(scale) + static_nbytes(zp)) * self._fan)
        return dequantize_affine_int8(
            self._gather(codes), self._gather(scale), self._gather(zp))

    def concat_machines_compressed(self, x: jax.Array) -> jax.Array:
        """(local_m, t, ...) -> (m*t, ...) f32; int8 codes on the wire."""
        g = self.all_machines_compressed(x)
        return g.reshape((-1,) + g.shape[2:])

    # --- ragged gathers (length-prefixed, static row budget)
    def _budget_counts(self, counts: jax.Array, cap: int, rows: int
                       ) -> jax.Array:
        counts = jnp.minimum(counts.astype(jnp.int32), cap)
        total = _concrete_sum(counts)
        if total is not None and total > rows:
            warnings.warn(
                f"gather_ragged: machines contribute {total} rows but the "
                f"budget is {rows}; the tail is truncated", stacklevel=3)
        return counts

    def _compact(self, g: jax.Array, counts: jax.Array, rows: int
                 ) -> jax.Array:
        """(m, cap, ...) gathered blocks -> (rows, ...): machine j's first
        counts[j] rows at offset sum(counts[:j]); the rest exactly zero."""
        m, cap = g.shape[0], g.shape[1]
        offs = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])
        slot = jnp.arange(cap, dtype=jnp.int32)
        take = slot[None, :] < counts[:, None]
        # live rows map to disjoint, in-order positions; everything else
        # (untaken slots, budget overflow) lands on `rows` and is dropped
        pos = jnp.where(take, offs[:, None] + slot[None, :], rows)
        flat = g.reshape((m * cap,) + g.shape[2:])
        return jnp.zeros((rows,) + g.shape[2:], g.dtype).at[
            pos.reshape(-1)].set(flat, mode="drop")

    def gather_ragged(self, values: jax.Array, counts: jax.Array,
                      rows: int, *, meta: bool = False) -> jax.Array:
        """Length-prefixed ragged gather without the dense pad.

        Args:
          values: (local_m, cap, ...) per-machine blocks — the first
            ``counts[j]`` rows of machine j's block are live.
          counts: (m,) int32 live-row counts, replicated (every machine
            derives them from the gathered count vector).
          rows: static output row budget.
          meta: charge the per-row bytes to the metadata channel (weight
            vectors riding alongside a payload).

        Returns:
          (rows, ...) replicated, ``values.dtype``: live rows packed
          contiguously in machine order, remaining slots exactly zero.
          Dead machines (count 0) contribute nothing. Rows beyond the
          budget are truncated (warns when ``counts`` is concrete).

        The wire carries each machine's ``counts[j]`` live rows at
        ``values.dtype`` width plus the (m,) length prefix — accounted
        per realized row (``WireTally.row_bytes``), which is what makes
        achieved bytes equal modeled bytes on honest wires.
        """
        counts = self._budget_counts(counts, values.shape[1], rows)
        record_wire(meta=4 * self.m,
                    **{"row_meta_bytes" if meta else "row_bytes":
                       _row_nbytes(values)})
        return self._compact(self._gather(values), counts, rows)

    def gather_ragged_compressed(self, values: jax.Array, counts: jax.Array,
                                 rows: int) -> jax.Array:
        """Ragged gather whose wire carries int8 codes + per-machine
        affine qparams; returns the (rows, ...) f32 reconstruction.

        Callers must mask never-uploaded slots (e.g. with a live row)
        BEFORE the call so garbage can't widen a machine's code book.
        """
        from repro.ft.compression import (affine_qparams,
                                          dequantize_affine_int8,
                                          quantize_affine_int8)
        if values.ndim < 3:
            raise ValueError(
                f"compressed gathers need (local_m, cap, ...) blocks, got "
                f"shape {values.shape}")
        counts = self._budget_counts(counts, values.shape[1], rows)
        scale, zp = affine_qparams(values)     # one pair per machine
        codes = quantize_affine_int8(values, scale, zp)
        record_wire(
            meta=4 * self.m
            + (static_nbytes(scale) + static_nbytes(zp)) * self._fan,
            row_bytes=_row_nbytes(codes))
        vals = dequantize_affine_int8(
            self._gather(codes), self._gather(scale), self._gather(zp))
        return self._compact(vals, counts, rows)


# ------------------------------------------------------------ clusters


@dataclasses.dataclass(frozen=True)
class VirtualCluster(_WireOps):
    """All ``m`` machines folded into axis 0 of every array (single device)."""
    m: int

    @property
    def local_m(self) -> int:
        return self.m

    def _reduce(self, x: jax.Array) -> jax.Array:
        return jnp.sum(x, axis=0)

    def _gather(self, x: jax.Array) -> jax.Array:
        return x

    def machine_ids(self) -> jax.Array:
        return jnp.arange(self.m, dtype=jnp.int32)


@dataclasses.dataclass(frozen=True)
class MeshCluster(_WireOps):
    """One machine per shard of the given mesh axes (use inside shard_map)."""
    m: int
    axis_names: Tuple[str, ...]
    axis_sizes: Tuple[int, ...]

    def __post_init__(self):
        sz = 1
        for s in self.axis_sizes:
            sz *= s
        assert sz == self.m, (self.m, self.axis_sizes)

    @property
    def local_m(self) -> int:
        return 1

    def _reduce(self, x: jax.Array) -> jax.Array:
        return lax.psum(jnp.sum(x, axis=0), self.axis_names)

    def _gather(self, x: jax.Array) -> jax.Array:
        # int8 payloads gather at 1 byte/element — compression survives
        # the collective, unlike a psum (whose int8 sum would promote)
        return lax.all_gather(x, self.axis_names, tiled=True)

    def machine_ids(self) -> jax.Array:
        idx = jnp.int32(0)
        stride = 1
        # row-major global id over the machine axes (last axis fastest)
        for name, size in zip(reversed(self.axis_names),
                              reversed(self.axis_sizes)):
            idx = idx + lax.axis_index(name).astype(jnp.int32) * stride
            stride *= size
        return idx[None]


Comm = VirtualCluster  # structural typing; both classes share the interface
