"""Benchmark runner: one function per paper table/figure + roofline export.

``python -m benchmarks.run [--fast]`` prints ``name,metric,value`` CSV lines
and writes full CSVs under experiments/bench/.
"""

from __future__ import annotations

import argparse
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="smaller datasets (CI mode)")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from . import kernel_bench, paper_tables, roofline

    n = 3000 if args.fast else 6000
    nt = 6000 if args.fast else 20000
    jobs = [
        ("fig14_are_vs_d", lambda: paper_tables.fig14_are_vs_d(n_edges=n)),
        ("fig15_query_accuracy",
         lambda: paper_tables.fig15_query_accuracy(n_edges=n)),
        ("fig16_windowed", lambda: paper_tables.fig16_windowed(n_edges=n)),
        ("tab3_throughput",
         lambda: paper_tables.tab3_throughput(n_edges=nt)),
        ("tab5_query_latency",
         lambda: paper_tables.tab5_query_latency(n_edges=nt)),
        ("kernel_insert_throughput",
         lambda: kernel_bench.insert_throughput(n=nt)),
        ("engine_insert_throughput",
         lambda: kernel_bench.engine_insert_throughput(
             n=4096 if args.fast else 16384)),
        ("kernel_query_throughput",
         lambda: kernel_bench.query_throughput(n=nt)),
        ("roofline_tables",
         lambda: roofline.roofline_table() + roofline.dryrun_table()),
    ]
    failures = 0
    print("name,us_per_call,derived")
    for name, fn in jobs:
        if args.only and args.only not in name:
            continue
        t0 = time.time()
        try:
            rows = fn()
            dt = time.time() - t0
            print(f"{name},{dt * 1e6 / max(1, len(rows)):.1f},rows={len(rows)}")
            for r in rows[:4]:
                print(f"#   {','.join(str(x) for x in r)}")
        except Exception as e:  # noqa: BLE001
            failures += 1
            dt = time.time() - t0
            print(f"{name},{dt*1e6:.1f},ERROR={type(e).__name__}:{e}")
            traceback.print_exc()
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
