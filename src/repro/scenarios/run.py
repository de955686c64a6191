"""CLI: run a scenario sweep and emit the comparable report.

    PYTHONPATH=src python -m repro.scenarios.run --suite paper --quick

prints one table covering every registered scenario x algorithm x
condition cell (cost ratio vs. the exact-k-means baseline, rounds,
uplink points/bytes, wall time) and writes the same rows to a
``BENCH_*.json`` perf-trajectory artifact.
"""
from __future__ import annotations

import argparse
import sys
import time

from repro.launch.cache import use_compile_cache
from repro.scenarios import library  # noqa: F401  (registers scenarios)
from repro.scenarios.registry import get_scenario, list_scenarios
from repro.scenarios.report import (format_table, summarize_gap,
                                    write_bench_json)
from repro.scenarios.sweep import DEFAULT_ALGOS, run_sweep


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="paper-style scenario sweeps through repro.api.fit()")
    ap.add_argument("--suite", default="paper",
                    help="scenario tag (e.g. paper) or comma-separated "
                         "scenario names")
    ap.add_argument("--algos", default=",".join(DEFAULT_ALGOS),
                    help="comma-separated fit() algorithms (scenarios "
                         "with a pinned algos list — e.g. coreset_budget "
                         "— run their own list regardless)")
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized data (each cell a few seconds)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--backend", default="virtual",
                    help="virtual | mesh | auto")
    ap.add_argument("--out", default="BENCH_scenarios.json",
                    help="perf-trajectory JSON path ('' to skip)")
    ap.add_argument("--trace-out", default="",
                    help="per-cell round-trace JSONL path (repro.obs "
                         "format; render with `python -m "
                         "repro.obs.report <path>`; '' to skip)")
    ap.add_argument("--list", action="store_true",
                    help="list registered scenarios and exit")
    args = ap.parse_args(argv)
    use_compile_cache()

    if args.list:
        for name in list_scenarios():
            print(f"{name:24s} {get_scenario(name).summary}")
        return 0

    names = (list_scenarios(tag=args.suite) if "," not in args.suite
             and args.suite not in list_scenarios()
             else tuple(s for s in args.suite.split(",") if s))
    if not names:
        print(f"no scenarios for suite {args.suite!r}; registered: "
              f"{', '.join(list_scenarios())}", file=sys.stderr)
        return 2
    algos = tuple(a for a in args.algos.split(",") if a)

    t0 = time.time()
    rows = run_sweep(names, algos=algos, quick=args.quick, seed=args.seed,
                     backend=args.backend)
    print()
    print(format_table(rows))
    gap = summarize_gap(rows)
    if gap:
        print(f"\n# {gap}")
    print(f"# sweep wall time: {time.time() - t0:.0f}s  "
          f"({len(names)} scenarios x {len(algos)} algos)")
    if args.out:
        path = write_bench_json(rows, args.out, suite=args.suite,
                                quick=args.quick, algos=algos,
                                seed=args.seed)
        print(f"# wrote {path}")
    if args.trace_out:
        from repro.obs.export import write_jsonl
        traces = [r["trace"] for r in rows if r.get("trace")]
        path = write_jsonl(traces, args.trace_out)
        print(f"# wrote {path} ({len(traces)} cell trace(s))")
    return 0


if __name__ == "__main__":
    sys.exit(main())
