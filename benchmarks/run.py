"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only figure1]

Prints ``name,us_per_call,derived`` CSV.
"""
from __future__ import annotations

import argparse
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    from benchmarks import (bench_engine, bench_figure1, bench_figure2,
                            bench_figure3, bench_figure4_wd,
                            bench_figure5, bench_figure6_zloss,
                            bench_lemma1, bench_serve, bench_table1)
    suites = {
        "figure1": bench_figure1,
        "table1": bench_table1,
        "figure2": bench_figure2,
        "figure3": bench_figure3,
        "figure4": bench_figure4_wd,
        "figure5": bench_figure5,
        "figure6": bench_figure6_zloss,
        "lemma1": bench_lemma1,
        "engine": bench_engine,
        "serve": bench_serve,
    }
    print("name,us_per_call,derived")
    failures = 0
    for name, mod in suites.items():
        if args.only and args.only != name:
            continue
        try:
            for row in mod.run():
                n, us, derived = row
                print(f"{n},{us:.1f},{derived}")
        except Exception as e:           # pragma: no cover
            failures += 1
            print(f"{name}/ERROR,0,{type(e).__name__}:{e}")
        sys.stdout.flush()
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
