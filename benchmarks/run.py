"""Benchmark harness — one function per paper table.

Prints ``name,us_per_call,derived`` CSV lines and writes JSON to
benchmarks/results/. All clustering tables run through the
``repro.api.fit`` facade and record uplink in points AND bytes
(``benchmarks.common.uplink_bytes``, dtype-aware). Sizes are scaled to
this CPU container (the paper's 10M-point runs are hardware-gated);
every ratio (eps, delta, k, Zipf, sigma) follows the paper.
Run:  PYTHONPATH=src python -m benchmarks.run
"""
from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller n, fewer baselines")
    ap.add_argument("--only", default=None,
                    help="table2|table3|minibatch|kernels|eim11|scenarios")
    args = ap.parse_args()

    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    from benchmarks import (bench_eim11, bench_kernels, bench_minibatch,
                            bench_table2, bench_table3)

    n2 = 40_000 if args.quick else 120_000
    n3 = 24_000 if args.quick else 80_000

    t0 = time.time()
    if args.only in (None, "table2"):
        print("# Table 2: SOCCER vs k-means|| (cost/time/rounds)")
        bench_table2.run(n=n2, quick=args.quick)
    if args.only in (None, "table3"):
        print("# Table 3: tiny coordinator (eta=7000), rounds-to-match")
        bench_table3.run(n=n3)
    if args.only in (None, "minibatch"):
        print("# Appendix D.2: MiniBatchKMeans black box")
        bench_minibatch.run(n=n3)
    if args.only in (None, "eim11"):
        print("# EIM11 baseline: broadcast/machine-work asymmetry")
        bench_eim11.run(n=min(n3, 24_000))
    if args.only in (None, "kernels"):
        print("# Kernel micro-benchmarks + TPU roofline projection")
        bench_kernels.run(quick=args.quick)
    if args.only == "scenarios":
        # full-suite sweeps have their own CLI (repro.scenarios.run);
        # this entry is the quick perf-trajectory slice CI tracks.
        print("# Scenario lab: paper suite (quick sweep)")
        from repro.scenarios.run import main as scenarios_main
        scenarios_main(["--suite", "paper", "--quick",
                        "--out", "BENCH_scenarios.json"])
    print(f"# total benchmark wall time: {time.time()-t0:.0f}s")


if __name__ == "__main__":
    main()
