"""The tiny benchmark copy of ``chipbench_tiny`` with a tiny refresh cell
added as files and entries only: graph500-s14-corewalk's settings on a
SCALE 8 graph at width 16, short walks and small SGNS batches."""
import json

from chipbench_tiny import tiny_root

CELL = "tiny.refresh"


def tiny_refresh_root(tmp_path, impl="ref"):
    """The copy, under ``tmp_path``, with ``tiny.refresh``; returns its
    root. ``impl`` is the SGNS kernel's dispatch."""
    root = tiny_root(tmp_path)
    chip = root / "benchmarks" / "chip"
    cfg = json.loads(
        (chip / "configs" / "graph500-s14-corewalk.json").read_text())
    cfg.update(name="tiny-corewalk", SCALE=8, nodes=240, capacity=240,
               dim=16, batch=8)
    cfg["refresh"] = dict(cfg["refresh"], walk_length=8, window=3,
                          sgns_batch=64, impl=impl)
    (chip / "configs" / "tiny-corewalk.json").write_text(json.dumps(cfg))
    traffic = json.loads(
        (chip / "traffic" / "ingest-refresh.json").read_text())
    traffic.update(block=64, warmup_blocks=2)
    (chip / "traffic" / "refresh-tiny.json").write_text(json.dumps(traffic))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "tiny-corewalk", "source": "test",
        "file": "benchmarks/chip/configs/tiny-corewalk.json",
        "reduced": [], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "tiny-corewalk",
                               "traffic": "refresh-tiny", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        ws = m.get("workloads")
        if ws is not None and "graph500-s14-corewalk.refresh" in ws:
            ws.append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
