"""Benchmark orchestrator — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (harness contract) after the
human-readable tables.

  PYTHONPATH=src python -m benchmarks.run           # full paper suite
  PYTHONPATH=src python -m benchmarks.run --quick   # CI-speed subset

The roofline analysis (§Roofline) forces a 512-device host platform, so it
runs on its own (``python -m benchmarks.roofline``) and never from here;
this orchestrator only summarises a cached results/roofline.json.
"""
from __future__ import annotations

import argparse
import json
import os

from . import (
    core_distribution,
    embedding_viz,
    serve_latency,
    table_cora,
    table_facebook,
    table_github,
)
from .common import csv_line


def roofline_lines(path="results/roofline.json"):
    if not os.path.exists(path):
        return [csv_line("roofline", 0.0, "missing:run benchmarks.roofline")]
    with open(path) as f:
        rows = json.load(f)
    lines = []
    for r in rows:
        lines.append(csv_line(
            f"roofline_{r['arch']}_{r['shape']}",
            max(r["compute_s"], r["memory_s"], r["collective_s"]),
            f"dominant={r['dominant']};useful={r['useful_ratio']:.2f};"
            f"frac={r['roofline_fraction']:.2f}",
        ))
    return lines


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--skip-tables", action="store_true",
                    help="only the cheap benches + cached roofline summary")
    ap.add_argument("--retrain", action="store_true",
                    help="include the drift-triggered retrain + hot-swap "
                         "demo in the serve-latency section (one pass, so "
                         "the JSON artifact carries the retrain section "
                         "without re-running the whole serving benchmark)")
    args = ap.parse_args()

    lines = []
    lines += core_distribution.run(quick=args.quick)
    if not args.skip_tables:
        for frac in ([0.1] if args.quick else [0.1, 0.3]):
            _, l1 = table_cora.run(quick=args.quick, frac=frac)
            lines += l1
            _, l2 = table_facebook.run(quick=args.quick, frac=frac)
            lines += l2
        _, l3 = table_github.run(quick=args.quick, frac=0.1)
        lines += l3
    lines += embedding_viz.run(quick=args.quick)
    lines += serve_latency.run(quick=args.quick, retrain=args.retrain)
    lines += roofline_lines()

    print("\n# name,us_per_call,derived")
    for line in lines:
        print(line)


if __name__ == "__main__":
    main()
