"""Per-run structured tracing: spans, events, and per-round records.

Design constraints (the tentpole contract):

* **Off by default, provably near-zero-cost when off.** Every public
  hook (``span``/``event``/``count``/``emit_round``/``current_trace``)
  is one list-truthiness check when no trace is active — no allocation,
  no clock read, no string formatting. The module stat counter
  ``_STATS`` lets tests assert that an untraced ``fit()`` allocated zero
  spans, counts and traces.
* **Injectable clock.** All timestamps come from the module clock
  (``clock()``, default ``time.perf_counter``); ``set_clock`` swaps it
  for a fake in tests so span durations and round walls are
  deterministic. Everything in the repo that times a fit (the facade,
  the scenario sweep, the overhead gate) reads THIS clock, so bench
  numbers and trace numbers can never come from two different timers.
* **Spans on the profiler's clock.** In ``mode="full"`` each span also
  opens a ``jax.profiler.TraceAnnotation`` of the same name, so the
  repo's spans land on the host plane of a captured profile, on the
  clock of the device trace. jax is imported lazily, only then.
* **Per-run counters.** ``count(name, n)`` adds to the active trace's
  ``counters`` in either mode: the SOCCER path counts the bytes it moves
  between host and device (``h2d_bytes``/``d2h_bytes``, through
  ``to_host``/``count_put``), the most bytes any one device receives
  while its input is placed (``place_max_device_bytes``, through
  ``count_placement``); ``api.fit`` counts the bytes of a pre-sharded
  device input it hands on without a host trip (``input_on_device_bytes``,
  0 where the input goes through the host); ``core.programs`` counts, for
  every driver, the programs a fit builds (``programs_traced``) and those
  it finds already built (``program_cache_hits``).

The per-round record schema is pinned (``ROUND_SCHEMA``): field names
and value types are part of the exported JSONL contract and covered by a
schema-stability test. Fields that do not apply to an algorithm (e.g.
``v`` for k-means‖) are ``None``.
"""
from __future__ import annotations

import collections
import contextlib
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

# ------------------------------------------------------------------ clock

_CLOCK: Callable[[], float] = time.perf_counter


def clock() -> float:
    """The one wall-clock every timed path in the repo reads."""
    return _CLOCK()


def set_clock(fn: Optional[Callable[[], float]]) -> Callable[[], float]:
    """Swap the module clock (tests); returns the previous clock.
    ``set_clock(None)`` restores the default ``time.perf_counter``."""
    global _CLOCK
    prev = _CLOCK
    _CLOCK = time.perf_counter if fn is None else fn
    return prev


# ------------------------------------------------------------- the schema

# The pinned per-round record: (field name, type of non-None values).
# Appending a field is a schema EXTENSION (update the stability test and
# the README glossary together); renaming or retyping one is a break.
ROUND_SCHEMA = (
    ("round", int),               # 1-based communication round index
    ("phase", str),               # "round" | "finalize" | "upload"
    ("n_live", int),              # live points at the round's start
    ("capacity", int),            # stopping capacity (SOCCER eta, EIM11 s)
    ("alpha", float),             # realized P2 sampling rate (SOCCER)
    ("v", float),                 # removal threshold broadcast this round
    ("removed", int),             # points removed by this round
    ("stop_ratio", float),        # n_live_after / capacity
    ("stop_margin", float),       # n_live_after - capacity (<= 0: stop)
    ("uplink_rows", int),         # realized uploaded rows this round
    ("wire_payload_bytes", int),  # achieved payload bytes (WireTally)
    ("wire_meta_bytes", int),     # achieved metadata-sideband bytes
    ("wall_s", float),            # host wall time of this round's step
    ("compile_s", float),         # trace+compile time attributed here
)
ROUND_FIELDS = tuple(name for name, _ in ROUND_SCHEMA)
_ROUND_TYPES = dict(ROUND_SCHEMA)

PHASES = ("round", "finalize", "upload")

TRACE_MODES = ("rounds", "full")

# Allocation stats for the zero-overhead-when-off test: traces created,
# spans entered, counts added, records emitted. Incremented only on the
# active paths.
_STATS = collections.Counter()


def round_record(**fields) -> Dict[str, Any]:
    """Build one schema-conforming per-round record.

    Unknown field names raise (the schema is pinned); missing fields are
    ``None``; present values are coerced to the schema type so exported
    records are JSON-stable regardless of the numpy scalars drivers pass.
    """
    unknown = sorted(set(fields) - set(ROUND_FIELDS))
    if unknown:
        raise ValueError(
            f"unknown round-record field(s) {', '.join(unknown)}; the "
            f"schema is pinned to {ROUND_FIELDS}")
    phase = fields.get("phase")
    if phase is not None and phase not in PHASES:
        raise ValueError(f"unknown phase {phase!r}: expected one of {PHASES}")
    out: Dict[str, Any] = {}
    for name, typ in ROUND_SCHEMA:
        v = fields.get(name)
        out[name] = None if v is None else typ(v)
    return out


# ------------------------------------------------------------------- spans


class Span:
    """One named, timed interval of an active trace.

    Times ``(t0, t1)`` in either mode, so the SOCCER loop reads its
    round and compile walls from it. Only ``mode="full"`` records it in
    the trace's ``spans`` and mirrors it into
    ``jax.profiler.TraceAnnotation``, which puts it on the host plane of
    a captured profile.
    """

    __slots__ = ("name", "attrs", "t0", "t1", "_trace", "_annotation")

    def __init__(self, name: str, trace: "RunTrace", attrs: Dict[str, Any]):
        self.name = name
        self.attrs = attrs
        self.t0 = self.t1 = 0.0
        self._trace = trace
        self._annotation = None

    def __enter__(self) -> "Span":
        _STATS["spans"] += 1
        if self._trace.mode == "full":
            from jax.profiler import TraceAnnotation
            self._annotation = TraceAnnotation(self.name)
            self._annotation.__enter__()
        self.t0 = clock()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = clock()
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
            self._trace.spans.append(
                {"name": self.name, "t0": self.t0, "t1": self.t1,
                 "attrs": self.attrs})


class _NullSpan:
    """The do-nothing span handed out whenever tracing is off."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None


_NULL_SPAN = _NullSpan()


# --------------------------------------------------------------- RunTrace


class RunTrace:
    """The per-run trace container behind ``fit(trace=...)``.

    ``mode="rounds"`` collects per-round records, the run-level
    wall/compile split and the counters; ``mode="full"`` additionally
    records spans (mirrored into ``jax.profiler``) and events.
    ``summary()`` is the exported, JSON-clean shape that lands in
    ``ClusterResult.extra["trace"]`` and the JSONL/Chrome exporters
    consume.
    """

    def __init__(self, mode: str = "rounds", *,
                 meta: Optional[Dict[str, Any]] = None):
        if mode not in TRACE_MODES:
            raise ValueError(
                f"unknown trace mode {mode!r}: expected one of "
                f"{TRACE_MODES} (or trace off)")
        _STATS["traces"] += 1
        self.mode = mode
        self.meta: Dict[str, Any] = dict(meta or {})
        self.records: List[Dict[str, Any]] = []
        self.spans: List[Dict[str, Any]] = []
        self.events: List[Dict[str, Any]] = []
        self.counters: Dict[str, int] = {}
        self.stop_reason: Optional[str] = None
        self.wall_s: Optional[float] = None
        self.t_start = clock()

    # --- emission (drivers call these through the module helpers)
    def emit_round(self, **fields) -> Dict[str, Any]:
        _STATS["records"] += 1
        rec = round_record(**fields)
        self.records.append(rec)
        return rec

    def span(self, name: str, **attrs) -> Span:
        return Span(name, self, attrs)

    def count(self, name: str, n: int = 1) -> None:
        _STATS["counts"] += 1
        self.counters[name] = self.counters.get(name, 0) + int(n)

    def event(self, name: str, **attrs) -> None:
        if self.mode != "full":
            return
        self.events.append({"name": name, "t": clock(), "attrs": attrs})

    # --- derived summaries
    @property
    def compile_s(self) -> float:
        """Total trace+compile seconds attributed across the records."""
        return float(sum(r["compile_s"] or 0.0 for r in self.records))

    @property
    def wire_payload_total(self) -> int:
        return int(sum(r["wire_payload_bytes"] or 0 for r in self.records))

    @property
    def wire_meta_total(self) -> int:
        return int(sum(r["wire_meta_bytes"] or 0 for r in self.records))

    @property
    def rounds_to_margin(self) -> Optional[int]:
        """First round whose post-removal live set fit the coordinator
        (``stop_margin <= 0``), or None if no round got there — the
        "why did it stop at round r" number the reports surface."""
        for rec in self.records:
            if rec["stop_margin"] is not None and rec["stop_margin"] <= 0:
                return rec["round"]
        return None

    def span_seconds(self) -> Dict[str, float]:
        """Seconds of the recorded spans, summed per span name."""
        out: Dict[str, float] = {}
        for sp in self.spans:
            out[sp["name"]] = out.get(sp["name"], 0.0) + sp["t1"] - sp["t0"]
        return out

    def summary(self) -> Dict[str, Any]:
        return {
            "mode": self.mode,
            "meta": dict(self.meta),
            "stop_reason": self.stop_reason,
            "rounds_to_margin": self.rounds_to_margin,
            "wall_s": self.wall_s,
            "compile_s": self.compile_s,
            "wire_payload_bytes": self.wire_payload_total,
            "wire_meta_bytes": self.wire_meta_total,
            "counters": dict(self.counters),
            "records": [dict(r) for r in self.records],
            "spans": [dict(s) for s in self.spans],
            "events": [dict(e) for e in self.events],
        }


# --------------------------------------------------- ambient trace context

_STACK: List[RunTrace] = []


def current_trace() -> Optional[RunTrace]:
    """The innermost active RunTrace, or None (one truthiness check)."""
    return _STACK[-1] if _STACK else None


@contextlib.contextmanager
def run_trace(trace: RunTrace):
    """Activate ``trace`` for the block: drivers inside publish to it."""
    _STACK.append(trace)
    try:
        yield trace
    finally:
        _STACK.pop()


def span(name: str, **attrs):
    """Ambient span: a real Span inside an active trace (recorded and
    mirrored into the profiler in mode "full"), else a shared no-op (no
    allocation when tracing is off)."""
    if not _STACK:
        return _NULL_SPAN
    return _STACK[-1].span(name, **attrs)


def count(name: str, n: int = 1) -> None:
    """Ambient counter: adds ``n`` to the active trace's ``counters``
    (no-op unless a trace is active)."""
    if not _STACK:
        return
    _STACK[-1].count(name, n)


def to_host(a) -> np.ndarray:
    """``np.asarray(a)``; a device array's ``nbytes`` count as
    ``d2h_bytes`` of the active trace."""
    if _STACK:
        from jax import Array
        if isinstance(a, Array):
            _STACK[-1].count("d2h_bytes", a.nbytes)
    return np.asarray(a)


def count_put(*arrays) -> None:
    """Count the host (NumPy) arrays among ``arrays`` as ``h2d_bytes`` of
    the active trace. Call it where they are put on a device; arrays
    already on a device move device to device and are not counted."""
    if not _STACK:
        return
    for a in arrays:
        if isinstance(a, np.ndarray):
            _STACK[-1].count("h2d_bytes", a.nbytes)


def _extent(index, shape) -> List[range]:
    """The rows of each axis that a shard ``index`` (slices) covers."""
    return [range(*s.indices(n)) for s, n in zip(index, shape)]


def count_placement(src, dst) -> None:
    """Count as ``place_max_device_bytes`` of the active trace the most
    bytes any one device receives when the leaves of ``src`` are placed
    as the matching leaves of ``dst`` (device arrays): its shard of each
    host leaf, and each shard of a device leaf that it did not already
    hold. Computed from the shardings, so exact on any backend; arrays a
    program creates on the device are not placements. Call it once per
    placement: counts add, and a sum of maxima is no maximum."""
    if not _STACK:
        return
    from jax import Array, tree
    received: Dict[Any, int] = collections.Counter()
    for a, b in zip(tree.leaves(src), tree.leaves(dst)):
        held = (a.sharding.devices_indices_map(a.shape)
                if isinstance(a, Array) else {})
        for dev, index in b.sharding.addressable_devices_indices_map(
                b.shape).items():
            want = _extent(index, b.shape)
            have = _extent(held[dev], b.shape) if dev in held else None
            if have is None or any(w.start < h.start or w.stop > h.stop
                                   for w, h in zip(want, have)):
                received[dev] += (int(np.prod([len(r) for r in want]))
                                  * b.dtype.itemsize)
    _STACK[-1].count("place_max_device_bytes",
                     max(received.values(), default=0))


def event(name: str, **attrs) -> None:
    """Ambient event (no-op unless a full trace is active)."""
    if not _STACK:
        return
    _STACK[-1].event(name, **attrs)


def emit_round(**fields) -> None:
    """Ambient per-round record (no-op unless a trace is active)."""
    if not _STACK:
        return
    _STACK[-1].emit_round(**fields)
