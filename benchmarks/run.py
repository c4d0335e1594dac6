"""Benchmark entry point: one section per paper table/figure + extensions.

Prints ``name,us_per_call,derived`` CSV lines per benchmark plus the full
tables.  CI-speed by default; ``--full`` uses the paper's 3×45-min protocol.
"""
from __future__ import annotations

import argparse
import os
import time

from repro.compile_cache import enable_compile_cache


def main(argv=None) -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--skip-routing", action="store_true")
    a = ap.parse_args(argv)
    os.makedirs("results", exist_ok=True)

    print("=" * 72)
    print("== Table 1: routing performance (AIF vs baselines) ==")
    print("=" * 72)
    if not a.skip_routing:
        from benchmarks import table1_routing
        t0 = time.time()
        table1_routing.run(2700.0 if a.full else 300.0,
                           3 if a.full else 2,
                           out_json="results/table1.json")
        print(f"table1_routing,{(time.time()-t0)*1e6:.0f},"
              f"runs={'full' if a.full else 'ci'}")

    print()
    print("=" * 72)
    print("== Ablations (adaptive C / util scrape / dwell / beta) ==")
    print("=" * 72)
    from benchmarks import ablations
    t0 = time.time()
    ablations.run(1200.0 if a.full else 300.0, 2 if a.full else 1)
    print(f"ablations,{(time.time()-t0)*1e6:.0f},variants=6")

    print()
    print("=" * 72)
    print("== Kernel microbenchmarks ==")
    print("=" * 72)
    from benchmarks import kernel_bench
    kernel_bench.run()

    print()
    print("=" * 72)
    print("== §Roofline table (from the multi-pod dry-run artifacts) ==")
    print("=" * 72)
    from benchmarks import roofline_table
    try:
        print(roofline_table.render())
    except Exception as e:
        print(f"(no dry-run artifacts found: {e}; "
              "run PYTHONPATH=src python -m repro.launch.dryrun first)")


if __name__ == "__main__":
    main()
