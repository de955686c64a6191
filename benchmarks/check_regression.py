"""Nightly regression gates (bench.yml): kernel roofline + wire bytes.

Roofline gate: compares a freshly produced ``results/kernels.json``
against a committed baseline and FAILS (exit 1) when any kernel row's
measured ``roofline_fraction`` dropped by more than ``--threshold``
(default 20%): the achieved fraction of this device's realizable peaks
falling that far means a kernel, the tuner, or the dispatch regressed —
the fraction is hardware-normalized, so the gate survives runner-speed
drift far better than raw wall time would.

Wire-bytes gate: compares a fresh ``BENCH_scenarios.json`` sweep against
the committed one and FAILS when any (scenario, algo, condition) row's
achieved uplink wire bytes grew by more than ``--wire-threshold``
(default 10%) — a widened collective, a lost compressed path, or a new
dense pad shows up here as measured bytes, not as a modeled estimate.
Each row prints its bytes-vs-Ω(m·k) ratio (Zhang et al.,
arXiv:1507.00026) so drift toward the communication frontier is visible
in the log even when the gate passes.

Telemetry-overhead gate (``--trace-overhead``): runs the same warm
SOCCER fit untraced and with ``trace="rounds"`` (min of ``--repeats``
each) and FAILS when the traced wall exceeds the untraced one by more
than ``--trace-overhead-threshold`` (default 2%) — the observability
layer's "near-zero-cost" contract, enforced on real runs instead of
asserted in a docstring.

Rows are matched on (kernel, n, k, d) / (scenario, algo, condition);
rows present on only one side are reported but do not fail the gate
(shape and scenario sets may evolve). Baseline rows without the gated
field (pre-autotune / pre-wire schema) are skipped.

Usage:
    python -m benchmarks.check_regression --current results/kernels.json \
        --baseline <committed kernels.json> [--threshold 0.20]
    python -m benchmarks.check_regression \
        --scenarios-current results/BENCH_scenarios.json \
        --scenarios-baseline BENCH_scenarios.json [--wire-threshold 0.10]
    python -m benchmarks.check_regression --trace-overhead

Any of the three gates (or several) may be selected; at least one is
required.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

DEFAULT_THRESHOLD = 0.20
DEFAULT_WIRE_THRESHOLD = 0.10
DEFAULT_TRACE_OVERHEAD = 0.02
DEFAULT_TRACE_REPEATS = 7


def _rows(path: pathlib.Path) -> dict:
    payload = json.loads(path.read_text())
    return {(r["kernel"], r["n"], r.get("k"), r["d"]): r
            for r in payload.get("rows", [])}


def check(current: pathlib.Path, baseline: pathlib.Path,
          threshold: float = DEFAULT_THRESHOLD) -> int:
    cur, base = _rows(current), _rows(baseline)
    failures = []
    for key, b in sorted(base.items(), key=str):
        c = cur.get(key)
        bf, cf = b.get("roofline_fraction"), (c or {}).get(
            "roofline_fraction")
        if c is None or bf is None:
            print(f"skip {key}: "
                  f"{'missing in current' if c is None else 'no baseline fraction'}")
            continue
        drop = (bf - cf) / bf if bf > 0 else 0.0
        status = "FAIL" if drop > threshold else "ok"
        print(f"{status} {key}: roofline_fraction {bf:.3f} -> {cf:.3f} "
              f"({-drop:+.1%})")
        if drop > threshold:
            failures.append(key)
    for key in sorted(set(cur) - set(base), key=str):
        print(f"new  {key}: roofline_fraction "
              f"{cur[key].get('roofline_fraction', float('nan')):.3f}")
    if failures:
        print(f"\n{len(failures)} row(s) regressed roofline_fraction by "
              f"more than {threshold:.0%}")
        return 1
    print("\nno roofline_fraction regressions")
    return 0


def _scenario_rows(path: pathlib.Path) -> dict:
    payload = json.loads(path.read_text())
    return {(r["scenario"], r["algo"], r["condition"]): r
            for r in payload.get("rows", [])
            if not r.get("skipped")}


def _wire_bytes(row: dict):
    """Achieved wire bytes of a sweep row, falling back to the modeled
    uplink bytes for baselines that predate the WireTally schema."""
    v = row.get("wire_bytes")
    return row.get("uplink_bytes") if v is None else v


def check_scenarios(current: pathlib.Path, baseline: pathlib.Path,
                    threshold: float = DEFAULT_WIRE_THRESHOLD) -> int:
    cur, base = _scenario_rows(current), _scenario_rows(baseline)
    failures = []
    for key, b in sorted(base.items(), key=str):
        c = cur.get(key)
        bw = _wire_bytes(b)
        if c is None or not bw:
            print(f"skip {key}: "
                  f"{'missing in current' if c is None else 'no baseline wire bytes'}")
            continue
        cw = _wire_bytes(c) or 0
        growth = (cw - bw) / bw
        ratio = c.get("bytes_vs_omega_mk")
        ratio_s = "—" if ratio is None else f"{ratio:.1f}x"
        status = "FAIL" if growth > threshold else "ok"
        print(f"{status} {key}: wire bytes {bw} -> {cw} ({growth:+.1%}), "
              f"{ratio_s} omega(mk)")
        if growth > threshold:
            failures.append(key)
    for key in sorted(set(cur) - set(base), key=str):
        print(f"new  {key}: wire bytes {_wire_bytes(cur[key])}")
    if failures:
        print(f"\n{len(failures)} row(s) grew achieved wire bytes by more "
              f"than {threshold:.0%}")
        return 1
    print("\nno wire-byte regressions")
    return 0


def check_trace_overhead(threshold: float = DEFAULT_TRACE_OVERHEAD,
                         repeats: int = DEFAULT_TRACE_REPEATS) -> int:
    """Traced fit must cost <= ``threshold`` over the untraced fit.

    Every ``fit()`` call builds fresh jitted step functions, so XLA
    recompiles per call — and compile jitter (~10% of a multi-second
    compile) would drown a 2% execution budget. The gate therefore
    points JAX's persistent compilation cache at its one directory first
    (``repro.launch.cache``): after
    one warm-up per arm, every XLA compile is a disk hit and both arms'
    walls measure trace + dispatch + kernels. Scoring is the MEDIAN of
    per-pair relative deltas over ``repeats`` pairs; within a pair the
    arms interleave (min-of-2 each, so a scheduler hiccup on one sample
    doesn't decide the pair) and the pair ORDER alternates between
    repeats (plain-first, traced-first, ...) to cancel thermal/boost
    drift that would otherwise bias whichever arm consistently runs
    second. Single-sample estimators — min-of-N included — measurably
    flake at a 2% resolution on shared CI runners; this one holds.
    """
    import jax
    import numpy as np

    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from repro.api import fit

    rng = np.random.RandomState(0)
    # big enough that kernel execution dominates host-side jitter: a 2%
    # budget needs the noise floor itself to sit well under 2%
    x = rng.randn(8, 32768, 16).astype(np.float32)
    kw = dict(k=16, algo="soccer", backend="virtual", epsilon=0.12, seed=0)

    import statistics

    def plain():
        return fit(x, **kw).wall_time_s

    def traced():
        return fit(x, trace="rounds", **kw).wall_time_s

    plain(), traced()                       # warm both arms' caches
    deltas = []
    for i in range(repeats):
        order = (plain, traced) if i % 2 == 0 else (traced, plain)
        walls = {plain: [], traced: []}
        for _ in range(2):
            for arm in order:
                walls[arm].append(arm())
        p, t = min(walls[plain]), min(walls[traced])
        deltas.append((t - p) / p)
    overhead = statistics.median(deltas)
    status = "FAIL" if overhead > threshold else "ok"
    print(f"{status} trace overhead: median of {repeats} paired runs "
          f"{overhead:+.2%} (budget {threshold:.0%}, pair deltas "
          f"{' '.join(f'{d:+.1%}' for d in sorted(deltas))})")
    if overhead > threshold:
        print(f"\ntraced fit() exceeded the {threshold:.0%} telemetry "
              f"overhead budget")
        return 1
    print("\ntelemetry overhead within budget")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="fail when kernel roofline_fraction regresses or "
                    "scenario wire bytes grow")
    ap.add_argument("--current", type=pathlib.Path)
    ap.add_argument("--baseline", type=pathlib.Path)
    ap.add_argument("--threshold", type=float, default=DEFAULT_THRESHOLD)
    ap.add_argument("--scenarios-current", type=pathlib.Path)
    ap.add_argument("--scenarios-baseline", type=pathlib.Path)
    ap.add_argument("--wire-threshold", type=float,
                    default=DEFAULT_WIRE_THRESHOLD)
    ap.add_argument("--trace-overhead", action="store_true",
                    help="gate fit(trace='rounds') wall overhead vs "
                         "untraced fit")
    ap.add_argument("--trace-overhead-threshold", type=float,
                    default=DEFAULT_TRACE_OVERHEAD)
    ap.add_argument("--trace-overhead-repeats", type=int,
                    default=DEFAULT_TRACE_REPEATS)
    args = ap.parse_args(argv)
    if bool(args.current) != bool(args.baseline):
        ap.error("--current and --baseline must be given together")
    if bool(args.scenarios_current) != bool(args.scenarios_baseline):
        ap.error("--scenarios-current and --scenarios-baseline must be "
                 "given together")
    if not (args.current or args.scenarios_current or args.trace_overhead):
        ap.error("nothing to check: give --current/--baseline, "
                 "--scenarios-current/--scenarios-baseline, and/or "
                 "--trace-overhead")
    rc = 0
    if args.current:
        rc |= check(args.current, args.baseline, args.threshold)
    if args.scenarios_current:
        rc |= check_scenarios(args.scenarios_current,
                              args.scenarios_baseline,
                              args.wire_threshold)
    if args.trace_overhead:
        rc |= check_trace_overhead(args.trace_overhead_threshold,
                                   args.trace_overhead_repeats)
    return rc


if __name__ == "__main__":
    sys.exit(main())
