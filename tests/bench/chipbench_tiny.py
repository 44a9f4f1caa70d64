"""A copy of the benchmark with a tiny configuration, two tiny traffic mixes
and two tiny cells added as files and entries only, for tests on the CPU."""
import json
import shutil
import time
from pathlib import Path

from benchmarks.chip import harness

ROOT = Path(__file__).resolve().parents[2]
CHIP = ROOT / "benchmarks" / "chip"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def tiny_root(tmp_path, *, block=64, rate=150):
    """The copy, under ``tmp_path``; returns its root."""
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    chip = tmp_path / "benchmarks" / "chip"
    bench = json.loads(json.dumps(BENCH))
    cfg = json.loads((CHIP / "configs" / "graph500-s14.json").read_text())
    cfg.update(name="tiny", SCALE=8, nodes=240, capacity=240, dim=16,
               batch=8)
    (chip / "configs" / "tiny.json").write_text(json.dumps(cfg))
    (chip / "traffic" / "ingest-tiny.json").write_text(json.dumps(
        {"kind": "ingest", "block": block, "churn": 0.1,
         "warmup_blocks": 2}))
    q = json.loads((CHIP / "traffic" / "query-open.json").read_text())
    q.update(rate_per_s=rate, link_group=2, topk_group=8, check_topk_calls=4)
    (chip / "traffic" / "query-tiny.json").write_text(json.dumps(q))
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "benchmarks/chip/configs/tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"] += [
        {"name": "tiny.ingest", "config": "tiny", "traffic": "ingest-tiny",
         "chips": 1, "why": "test"},
        {"name": "tiny.query", "config": "tiny", "traffic": "query-tiny",
         "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        ws = m.get("workloads")
        if ws is not None:
            ws.append("tiny.ingest" if any("ingest" in w for w in ws)
                      else "tiny.query")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path


def run_tiny(root, cell, seconds=1.5, seed=2**31 + 3):
    spec = harness.Spec(root, cell)
    return harness.run(spec, seed=seed, seconds=seconds, trace=False,
                       t_start=time.perf_counter(), require_tpu=False)
