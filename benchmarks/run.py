"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. ``--quick`` trims seeds and
sweep widths for smoke use; default reproduces the full set.
"""
from __future__ import annotations

import argparse
import sys
import time


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default="",
                    help="comma-separated bench names (e.g. resource,slo)")
    args = ap.parse_args(argv)

    from benchmarks.common import Rows
    from benchmarks import (bench_resource, bench_latency, bench_repartition,
                            bench_merging, bench_grouping, bench_throughput,
                            bench_massive, bench_overhead, bench_slo,
                            bench_energy, bench_incremental,
                            bench_calibration, bench_controller)
    suites = {
        "calibration": bench_calibration.run, # Table 2 anchors
        "resource": bench_resource.run,       # Table 3 / Fig 7
        "latency": bench_latency.run,         # Figs 8-10
        "repartition": bench_repartition.run, # Figs 11-12
        "merging": bench_merging.run,         # Figs 13-15
        "grouping": bench_grouping.run,       # Fig 16
        "throughput": bench_throughput.run,   # Fig 17
        "massive": bench_massive.run,         # Fig 18
        "overhead": bench_overhead.run,       # Fig 19
        "slo": bench_slo.run,                 # Fig 20
        "energy": bench_energy.run,           # Fig 21
        "incremental": bench_incremental.run, # paper §6 extension
        "controller": bench_controller.run,   # online control loop (beyond paper)
    }
    only = set(args.only.split(",")) if args.only else None
    rows = Rows()
    print("name,us_per_call,derived")
    for name, fn in suites.items():
        if only and name not in only:
            continue
        t0 = time.perf_counter()
        fn(rows, quick=args.quick)
        rows.add(f"suite/{name}/total", (time.perf_counter() - t0) * 1e6,
                 "suite_wall_time")
        rows.emit()
        rows.rows.clear()
        sys.stdout.flush()


if __name__ == "__main__":
    main()
