"""The reader of ``repair.descend_cells_per_arc`` on hand-made span lists
whose answers are counted by hand."""
import importlib.util
from pathlib import Path

import pytest

from benchmarks.chip.layer import LayerContext

CHIP = Path(__file__).resolve().parents[2] / "benchmarks" / "chip"


def read(ctx):
    spec = importlib.util.spec_from_file_location(
        "repair_descend_cells_per_arc",
        CHIP / "layer_metrics" / "repair.descend_cells_per_arc.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def span(name, ts, dur, **attrs):
    return {"name": name, "ts": ts, "dur": dur, "attrs": attrs}


# three blocks, as the ingest cell's window holds them
BLOCKS = [
    span("serve.ingest", 0.0, 2.0), span("serve.retract", 2.0, 0.5),
    span("serve.ingest", 3.0, 2.0),
]


def ingest_ctx(spans):
    return LayerContext(kind="ingest", window=(0.0, 10.0), spans=spans,
                        counters={"blocks": 3})


def query_ctx():
    return LayerContext(kind="query", window=(0.0, 10.0),
                        spans=[span("serve.flush", 0.0, 0.004)])


def test_cells_per_arc_reads_every_fused_descent():
    # a block's descent, a re-peel's nested descent, and a host descent
    # (no ``cells``), which is left out: (4096 + 8192) / (1000 + 2000)
    spans = BLOCKS + [
        span("repair.descend", 0.5, 0.2, impl="fused[pallas]", rows=900,
             cells=4096, arcs=1000, tiers=2),
        span("repair.fallback", 3.0, 1.0, impl="descend"),
        span("repair.descend", 3.1, 0.8, impl="fused[pallas]", rows=1200,
             cells=8192, arcs=2000, tiers=4),
        span("repair.descend", 6.0, 0.1, impl="host", rows=5, arcs=12,
             tiers=1),
    ]
    assert read(ingest_ctx(spans)) == pytest.approx(12288 / 3000)
    assert read(query_ctx()) is None


def test_cells_per_arc_reads_nothing_without_arcs():
    # the parent's descent spans carry ``rows`` and ``cells`` only
    spans = BLOCKS + [
        span("repair.descend", 0.5, 0.2, impl="fused[pallas]", rows=900,
             cells=4096)]
    assert read(ingest_ctx(spans)) is None
    assert read(ingest_ctx([])) is None
