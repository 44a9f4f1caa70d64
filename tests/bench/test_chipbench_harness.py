"""The harness finds every piece by name, its traffic is reproducible, the
ingest stream never runs dry, and it refuses to run without a TPU."""
import json
import re
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from benchmarks.chip import harness
from benchmarks.chip.graphgen import canonical_edges, make_graph
from benchmarks.chip.cells.ingest import Stream
from benchmarks.chip.cells.query import KINDS, make_schedule
from benchmarks.chip.refs.cores import core_numbers
from chipbench_tiny import run_tiny, tiny_root

ROOT = Path(__file__).resolve().parents[2]
CHIP = ROOT / "benchmarks" / "chip"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [c["name"] for c in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", CELLS)
def test_every_piece_of_a_cell_is_found_by_name(cell):
    spec = harness.Spec(ROOT, cell)
    assert (CHIP / "cells" / f"{spec.kind}.py").is_file()
    assert {m["name"] for m in spec.end_to_end} - {"setup_s"}
    assert spec.per_layer
    for m in spec.per_layer:
        assert callable(harness.load_reader(spec.chip_dir, m["name"]))
    assert set(spec.limits)  # checks/<kind>.json
    assert spec.driver(1, None).kind == spec.kind


@pytest.mark.parametrize("config", [c["file"] for c in BENCH["configs"]])
def test_config_states_the_graph_it_generates(config):
    """The sizes a configuration file states are those of its graph, and
    its ``reduced`` names what differs from the source."""
    cfg = json.loads((ROOT / config).read_text())
    n, edges = make_graph(cfg)
    assert (n, len(edges)) == (cfg["nodes"], cfg["edges"])
    assert int(np.bincount(edges.ravel()).max()) == cfg["max_degree"]
    assert int(core_numbers(n, edges).max()) == cfg["degeneracy"]
    entry = next(c for c in BENCH["configs"] if c["file"] == config)
    assert entry["reduced"] == cfg["reduced"]
    assert all(k in cfg for k in cfg["reduced"])


def test_benchmark_json_keeps_to_its_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir()
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for w in m["workloads"]:
            assert w in CELLS
            assert w in e2e[m["moves"]].get("workloads", CELLS)
    for w in BENCH["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200


def test_query_traffic_is_the_same_for_the_same_seed():
    spec = harness.Spec(ROOT, next(c for c in CELLS if c.endswith(".query")))
    big = 2**31 + 12345

    def make(seed):
        return make_schedule(spec.traffic, n_nodes=spec.config["nodes"],
                             seconds=10, rng=np.random.default_rng([seed, 4]))

    a, b, c = make(big), make(big), make(big + 1)
    for f in ("t", "kind", "a", "b"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f))
    assert not np.array_equal(a.a, c.a)
    # every seed gets the same work: the count and the mix of kinds
    assert len(a.t) == len(c.t) == round(spec.traffic["rate_per_s"] * 10)
    for k in range(len(KINDS)):
        assert (a.kind == k).sum() == (c.kind == k).sum()
    assert np.all(np.diff(a.t) >= 0) and a.t[-1] < 10


def _stream(held, seed, block=1024, churn=0.1):
    return Stream(held, block=block, churn=churn,
                  rng=np.random.default_rng([seed, 1])).blocks()


def test_ingest_traffic_is_the_same_for_the_same_seed():
    cfg = json.loads((CHIP / "configs" / "graph500-s14.json").read_text())
    n, edges = make_graph(dict(cfg, SCALE=9))
    held = edges[::7]
    a, b = _stream(held, 9, 64), _stream(held, 9, 64)
    for _ in range(40):
        (ka, ea), (kb, eb) = next(a), next(b)
        assert ka == kb
        np.testing.assert_array_equal(ea, eb)


def test_ingest_stream_never_runs_dry_at_fifty_times_the_rate():
    """50x the ~RATE edges/s measured on the chip, over a 51 s window, on
    the configuration's held-out edges: the stream keeps going, never
    inserts a present edge or retracts an absent one, and the graph stays
    between the base graph and the full graph."""
    cfg = json.loads((CHIP / "configs" / "graph500-s14.json").read_text())
    n, edges = make_graph(cfg)
    rng = np.random.default_rng(0)
    held = edges[rng.permutation(len(edges))[: round(0.15 * len(edges))]]
    held_keys = set((held[:, 0] * n + held[:, 1]).tolist())
    present = set()  # held-out edges in the graph
    gen = _stream(held, 2**31 + 7)
    need = 50 * 170 * 51
    applied = 0
    phases = set()
    while applied < need:
        kind, e = next(gen)
        assert len(e)
        keys = (canonical_edges(n, e) @ np.array([n, 1])).tolist()
        assert len(keys) == len(e)  # no duplicate within a block
        assert set(keys) <= held_keys
        if kind == "insert":
            assert not present & set(keys)
            present |= set(keys)
        else:
            assert set(keys) <= present
            present -= set(keys)
        applied += len(e)
    assert applied >= need


def test_run_refuses_a_cpu_device(capsys):
    rc = harness.main(["--workload", CELLS[0], "--seed", "1",
                       "--seconds", "1", "--trace", "0"], root=ROOT)
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
    assert "TPU" in out.err


def test_checkout_of_the_benchmark_alone_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert proc.stdout == ""


@pytest.mark.parametrize("cell,metrics", [
    ("tiny.ingest", {"ingest_edges_per_s", "setup_s"}),
    ("tiny.query", {"query_p95_ms", "topk_p95_ms", "setup_s"}),
])
def test_a_cell_added_as_files_and_entries_runs(tmp_path, cell, metrics):
    res = run_tiny(tiny_root(tmp_path), cell)
    assert res["correct"] is True
    assert set(res["metrics"]) == metrics
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())


def test_a_traffic_kind_added_as_files_runs(tmp_path):
    """A new kind of traffic is a driver ``cells/<kind>.py`` and its limits
    ``checks/<kind>.json``, found by the name in its traffic file."""
    root = tiny_root(tmp_path)
    chip = root / "benchmarks" / "chip"
    (chip / "cells" / "echo.py").write_text(
        "from .query import Cell as _Query\n\n\n"
        "class Cell(_Query):\n    kind = 'echo'\n")
    shutil.copy(chip / "checks" / "query.json", chip / "checks" / "echo.json")
    q = json.loads((chip / "traffic" / "query-tiny.json").read_text())
    (chip / "traffic" / "echo-tiny.json").write_text(
        json.dumps(dict(q, kind="echo")))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "tiny.echo", "config": "tiny",
                               "traffic": "echo-tiny", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "tiny.query" in m.get("workloads", []):
            m["workloads"].append("tiny.echo")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    assert harness.Spec(root, "tiny.echo").driver(1, None).kind == "echo"
    res = run_tiny(root, "tiny.echo", seconds=1.0)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"query_p95_ms", "topk_p95_ms", "setup_s"}
