"""Runs the control of a cell beside the program, on several seeds.

Each cell's driver defines its control (``Cell.control``): the readings that
``correct`` must refuse. A query cell's is the reference computed one
precision below; an ingest cell's is the cores of the graph without the
window's last block, as if its repair had been skipped.

    PYTHONPATH=.:src python3 -m benchmarks.chip.control \
        --workload <cell> --seeds 1,2,3 --seconds <s>

runs the cell's set-up and a window of ``--seconds`` for each seed in one
process, and prints one JSON line per seed with the program's readings and
the control's, beside the limits.
"""
from __future__ import annotations

import contextlib
import json
import sys
import time
from pathlib import Path

from .graphgen import make_graph

__all__ = ["main"]


def main(argv=None, *, root: Path) -> int:
    import argparse

    from .harness import NoAccelerator, Spec, log, require_devices

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one window each")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    spec = Spec(root, args.workload)
    try:
        require_devices(int(spec.cell["chips"]))
    except NoAccelerator as e:
        log(str(e))
        return 1
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    graph = make_graph(spec.config)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        cell = spec.driver(seed, lambda name: contextlib.nullcontext())
        cell.setup(graph)
        cell.window(args.seconds)
        cell.collect()
        prog, ctrl = cell.check(), cell.control()
        print(json.dumps({
            "workload": spec.name, "seed": seed,
            "program": prog, "control": ctrl, "limits": spec.limits,
            "program_correct": all(v <= spec.limits[k]
                                   for k, v in prog.items()),
            "control_correct": all(v <= spec.limits[k]
                                   for k, v in ctrl.items()),
            "seconds": time.perf_counter() - t0,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(root=Path(__file__).resolve().parents[2]))
