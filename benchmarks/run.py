"""Benchmark entry point — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full]

Prints ``name,us_per_call,derived`` CSV rows.  --full runs paper-strength
event counts (minutes); the default is the quick profile used by CI.
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="run a single bench (convergence|scalability|lstm|"
                         "bandwidth|compression|roofline)")
    args = ap.parse_args()
    quick = not args.full

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    from . import (bench_bandwidth, bench_compression, bench_convergence,
                   bench_lstm, bench_scalability, roofline_table)
    benches = {
        "convergence": bench_convergence.run,     # Table I / Fig 1
        "scalability": bench_scalability.run,     # Table III / Fig 2
        "lstm": bench_lstm.run,                   # Table II
        "bandwidth": bench_bandwidth.run,         # Fig 4
        "compression": bench_compression.run,     # dual-way ratio + kernels
        "roofline": roofline_table.run,           # §Roofline (from dry-run)
    }
    if args.only:
        benches = {args.only: benches[args.only]}
    print("name,us_per_call,derived")
    failed = []
    for name, fn in benches.items():
        try:
            for row in fn(quick=quick):
                print(row)
                sys.stdout.flush()
        except Exception as e:  # noqa: BLE001
            failed.append((name, e))
            traceback.print_exc()
    from .common import write_bench_artifacts
    for path in write_bench_artifacts():
        print(f"# wrote {path}", file=sys.stderr)
    if failed:
        raise SystemExit(f"benchmarks failed: {[n for n, _ in failed]}")


if __name__ == "__main__":
    main()
