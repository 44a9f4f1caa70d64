"""Sweep a query cell's open-loop rate once, to find the highest it sustains.

    PYTHONPATH=.:src python3 -m benchmarks.chip.sweep \
        --workload graph500-s14.query --seed 1 --seconds 8 \
        --rates 1000,2000,4000

Builds the cell once, then serves one window per rate, in order, and prints
one JSON line per rate: latency and queueing percentiles, and how late the
last tenth of the arrivals was dispatched against the first tenth. A rate is
sustained when that lateness does not grow over the window. The cell's
traffic file then fixes its rate below the knee; nothing searches for a rate
in a measured run.
"""
from __future__ import annotations

import contextlib
import json
import sys
from pathlib import Path

import numpy as np

from .graphgen import make_graph

__all__ = ["main"]


def main(argv=None, *, root: Path) -> int:
    import argparse

    from .harness import NoAccelerator, Spec, log, require_devices

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    spec = Spec(root, args.workload)
    if spec.kind != "query":
        raise SystemExit("only query cells have a rate")
    try:
        require_devices(int(spec.cell["chips"]))
    except NoAccelerator as e:
        log(str(e))
        return 1
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    cell = spec.driver(args.seed, lambda name: contextlib.nullcontext())
    cell.setup(make_graph(spec.config))
    for rate in (float(r) for r in args.rates.split(",")):
        cell.traffic = dict(cell.traffic, rate_per_s=rate)
        res = cell.window(args.seconds)
        lat, queue = res["samples"]["latency_ms"], res["samples"]["queue_ms"]
        tenth = max(len(queue) // 10, 1)
        print(json.dumps({
            "rate_per_s": rate, "requests": len(lat),
            "window_s": res["t1"] - res["t0"],
            "latency_ms": {str(q): float(np.percentile(lat, q))
                           for q in (50, 95, 99)},
            "queue_ms_p95": float(np.percentile(queue, 95)),
            "queue_ms_first_tenth": float(np.mean(queue[:tenth])),
            "queue_ms_last_tenth": float(np.mean(queue[-tenth:])),
            **{k: v for k, v in res["e2e"].items()},
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(root=Path(__file__).resolve().parents[2]))
