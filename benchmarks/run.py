"""Benchmark entry point: one function per paper table/figure + the planner
bench.  Prints ``name,us_per_call,derived`` CSV lines.

    PYTHONPATH=src python -m benchmarks.run            # reduced scale (~minutes)
    PYTHONPATH=src python -m benchmarks.run --full     # paper-scale parameters
    PYTHONPATH=src python -m benchmarks.run --only table4
"""
from __future__ import annotations

import argparse
import time

from . import paper_tables, planner_tpu
from .common import scale


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale instances/budgets")
    ap.add_argument("--only", default=None,
                    help="substring filter: table3|table4|table5|fig3|fig56|fig7|portfolio|planner")
    ap.add_argument("--backend", default="numpy",
                    choices=("numpy", "jax", "scalar", "device"),
                    help="tabu engine for table4/fig7; 'device' runs table4 "
                         "rows through the vmapped device engine")
    args = ap.parse_args()
    sc = scale(args.full)

    benches = [
        ("table3", lambda: paper_tables.table3_init_strategies(sc)),
        ("table4", lambda: paper_tables.table4_ts_vs_lb(sc, backend=args.backend)),
        ("table5", lambda: paper_tables.table5_core_sweep(sc)),
        ("fig3", lambda: paper_tables.fig3_stability(sc, n_runs=20 if args.full else 8)),
        ("fig56", lambda: paper_tables.fig56_mixed_eval(sc)),
        ("fig7", lambda: paper_tables.fig7_memory_ratio(sc, backend=args.backend)),
        ("portfolio", lambda: paper_tables.portfolio_vs_single(sc)),
        ("planner", planner_tpu.main),
    ]
    t0 = time.monotonic()
    for name, fn in benches:
        if args.only and args.only not in name:
            continue
        t1 = time.monotonic()
        fn()
        print(f"# [{name}] {time.monotonic() - t1:.1f}s")
    print(f"# total {time.monotonic() - t0:.1f}s")


if __name__ == "__main__":
    main()
