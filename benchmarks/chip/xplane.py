"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

Read with ``jax.profiler.ProfileData`` alone. On a TPU the trace has one
``/device:TPU:<i>`` plane per chip, whose ``XLA Ops`` line holds every
operation that ran, named by its HLO instruction (``%name.N = <result
type> <opcode>(<operands>) ...``), and a ``/host:CPU`` plane whose
lines (one per host thread) hold the spans: the benchmark's own
``bench.*`` annotations and JAX's (``PjitFunction(...)``,
``lower_sharding_computation``, ``np.asarray(jax.Array)``, ...).

- busy: the union of the intervals in which an operation ran on a chip,
  inside the traced window, averaged over the chips;
- per-operation device time, matched by a regular expression on the
  instruction (kernels: ``rooflines/<entry>.py``; collectives below);
- idle gaps: the longest stretches with nothing running on the first
  chip, each named by the host span, on any thread, that covers most of
  it.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = re.compile(r"^bench\.fit\.\d+$")
COLLECTIVE = re.compile(
    r"^%(all-gather|all-reduce|collective-permute|all-to-all|"
    r"reduce-scatter)(-start|-done)?(\.\d+)? = ")
OPERAND = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")

Interval = Tuple[float, float]          # (start_ns, end_ns)


@dataclasses.dataclass
class Op:
    name: str           # the whole HLO instruction text
    start_ns: float
    end_ns: float


def find(log_dir: str) -> str:
    """The one ``.xplane.pb`` that ``jax.profiler`` wrote under a dir."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def union(intervals: List[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


OPCODE = re.compile(r" ([a-z][\w-]*)\(")


def _closing(text: str, i: int) -> int:
    """Index of the parenthesis that closes the one at ``text[i]``."""
    depth = 0
    for j in range(i, len(text)):
        depth += text[j] == "("
        depth -= text[j] == ")"
        if depth == 0:
            return j
    return len(text)


def _parts(op: str) -> Tuple[str, str, str]:
    """An HLO instruction's ``(name without its number, result type,
    operand list)``."""
    head, _, rest = op.partition(" = ")
    base = re.sub(r"\.\d+$", "", head.lstrip("%"))
    m = OPCODE.search(rest)
    if m is None:
        return base, rest, ""
    open_at = m.end() - 1
    return base, rest[:m.start()], rest[open_at + 1:_closing(rest, open_at)]


def short_name(op: str) -> str:
    """``%sort.1 = (f32[8,1280000]{...}, ...) sort(...)`` ->
    ``sort (f32[8,1280000], ...)``: the instruction without its number,
    and its result type without layouts."""
    base, result, _ = _parts(op)
    return f"{base} {re.sub(r'[{][^}]*[}]', '', result)}"


def operand_shapes(op: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """``(dtype, shape)`` of each array operand, in order."""
    return [(dt, tuple(int(v) for v in dims.split(",") if v))
            for dt, dims in OPERAND.findall(_parts(op)[2])]


@dataclasses.dataclass
class Reduced:
    """What the benchmark reads from one traced window."""
    window_s: float
    busy_s: float                        # mean over the chips
    ops: Dict[int, List[Op]]             # chip -> ops inside the window
    gaps: List[Tuple[str, float]]        # longest idle gaps of chip 0

    @property
    def chips(self) -> int:
        return len(self.ops)

    def matching(self, pattern: "re.Pattern") -> List[Op]:
        """Every op on every chip whose instruction matches."""
        return [op for ops in self.ops.values() for op in ops
                if pattern.search(op.name)]

    def seconds(self, pattern: "re.Pattern") -> float:
        """Device seconds of the matching ops, summed over the chips."""
        return sum(op.end_ns - op.start_ns
                   for op in self.matching(pattern)) / 1e9

    def breakdown(self) -> dict:
        """The ten operations with most device time (mean over chips) and
        the ten longest idle gaps of chip 0."""
        total: Dict[str, float] = {}
        for ops in self.ops.values():
            for op in ops:
                k = short_name(op.name)
                total[k] = total.get(k, 0.0) + (op.end_ns - op.start_ns)
        top = sorted(total.items(), key=lambda t: -t[1])[:10]
        return {"device_ops": [[k, v / 1e9 / self.chips] for k, v in top],
                "idle_gaps": [[k, v] for k, v in self.gaps[:10]]}


def _host_label(spans: List[Tuple[str, float, float]], lo: float,
                hi: float) -> str:
    """The host span that covers most of [lo, hi]; of those covering at
    least nine tenths as much, the shortest (the most specific)."""
    cover = [(min(e, hi) - max(s, lo), e - s, name) for name, s, e in spans
             if e > lo and s < hi and not name.startswith("bench.")]
    if not cover:
        return "host: no span"
    best = max(c[0] for c in cover)
    return min((c for c in cover if c[0] >= 0.9 * best),
               key=lambda c: c[1])[2]


def reduce(path: str, chips: int, window: Optional[Interval] = None,
           fallback: Optional[Interval] = None) -> Reduced:
    """Reduce the trace at ``path`` over the first ``chips`` chips.

    The window is ``window`` (ns on the trace's clock) or, by default,
    from the start of the first ``bench.fit.<i>`` span on any host line to
    the end of the last one; where the trace holds no such span, it is
    ``fallback``, the host clock's window counted from the profiler's
    start.
    """
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    spans: List[Tuple[str, float, float]] = []
    ops: Dict[int, List[Op]] = {}
    seen = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            seen.append(f"{plane.name}/{line.name}")
            if m and int(m.group(1)) < chips and line.name == OPS_LINE:
                ops[int(m.group(1))] = [
                    Op(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events]
            elif plane.name == HOST_PLANE:
                evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events]
                spans += evs
    if len(ops) != chips:
        raise RuntimeError(f"trace has ops for chips {sorted(ops)}, "
                           f"expected {chips}; lines: {seen}")
    fits = [(s, e) for name, s, e in spans if WINDOW_SPAN.match(name)]
    if window is None and fits:
        window = (min(s for s, _ in fits), max(e for _, e in fits))
    if window is None:
        if fallback is None:
            raise RuntimeError(f"no bench.fit.<i> span in the trace; "
                               f"lines: {seen}")
        window = fallback
    lo, hi = window
    ops = {c: [op for op in v if op.end_ns > lo and op.start_ns < hi]
           for c, v in ops.items()}
    busy = {c: union(clip([(op.start_ns, op.end_ns) for op in v], lo, hi))
            for c, v in ops.items()}
    busy_s = sum(e - s for b in busy.values() for s, e in b) / chips / 1e9
    edges = [lo] + [t for iv in busy[0] for t in iv] + [hi]
    idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    idle.sort(key=lambda iv: iv[0] - iv[1])
    gaps = [(_host_label(spans, s, e), (e - s) / 1e9) for s, e in idle[:10]]
    return Reduced(window_s=(hi - lo) / 1e9, busy_s=busy_s, ops=ops,
                   gaps=gaps)
