"""Readers of the per-layer metrics that read the program's inner spans,
on hand-made span lists whose answers are counted by hand."""
import importlib.util
from pathlib import Path

import pytest

from benchmarks.chip.layer import LayerContext

CHIP = Path(__file__).resolve().parents[2] / "benchmarks" / "chip"


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), CHIP / "layer_metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def span(name, ts, dur, **attrs):
    return {"name": name, "ts": ts, "dur": dur, "attrs": attrs}


# two flushes and two top-k calls, one link call, on the query clock
QUERY_SPANS = [
    span("serve.flush", 0.0, 0.004), span("flush.stage", 0.0, 0.001),
    span("flush.wait", 0.001, 0.0005), span("flush.commit", 0.0015, 0.002),
    span("serve.flush", 1.0, 0.006), span("flush.stage", 1.0, 0.002),
    span("flush.wait", 1.002, 0.0015), span("flush.commit", 1.0035, 0.002),
    span("serve.link", 2.0, 0.008), span("serve.flush", 2.0, 0.005),
    span("flush.wait", 2.001, 0.001), span("link.score", 2.005, 0.003),
    span("serve.topk", 3.0, 0.015), span("topk.prep", 3.005, 0.002),
    span("topk.wait", 3.007, 0.004), span("topk.merge", 3.011, 0.004),
    span("serve.topk", 4.0, 0.013), span("topk.prep", 4.005, 0.004),
    span("topk.wait", 4.009, 0.002), span("topk.merge", 4.011, 0.002),
]

# three blocks; two compactions, one with its parts
INGEST_SPANS = [
    span("serve.ingest", 0.0, 2.0), span("graph.compact", 1.0, 0.9),
    span("compact.build", 1.0, 0.6), span("compact.upload", 1.6, 0.3),
    span("serve.retract", 2.0, 0.5),
    span("serve.ingest", 3.0, 2.0), span("graph.compact", 4.0, 0.6),
]


def query_ctx(spans=QUERY_SPANS):
    return LayerContext(kind="query", window=(0.0, 10.0), spans=spans)


def ingest_ctx(spans=INGEST_SPANS, blocks=3):
    return LayerContext(kind="ingest", window=(0.0, 10.0), spans=spans,
                        counters={"blocks": blocks})


@pytest.mark.parametrize("name, want", [
    ("flush.wait_ms_p50", 1.0),   # median of 0.5, 1.5 and 1.0 ms
    ("link.call_ms_p50", 8.0),
    ("topk.prep_ms_p50", 3.0),    # median of 2 and 4 ms
    ("topk.wait_ms_p50", 3.0),    # median of 4 and 2 ms
    ("compile.ms_in_window.query", 0.0),
])
def test_query_readers(name, want):
    read = reader(name)
    assert read(query_ctx()) == pytest.approx(want)
    assert read(ingest_ctx()) is None  # the other cell kind


@pytest.mark.parametrize("name, want", [
    ("compact.ms_per_block", 1500.0 / 3),
    ("compile.ms_in_window.ingest", 0.0),
])
def test_ingest_readers(name, want):
    read = reader(name)
    assert read(ingest_ctx()) == pytest.approx(want)
    assert read(query_ctx()) is None


@pytest.mark.parametrize("name", ["flush.wait_ms_p50", "link.call_ms_p50",
                                  "topk.prep_ms_p50", "topk.wait_ms_p50"])
def test_query_readers_without_their_span_read_nothing(name):
    # the parent commit records no such span: the metric is left out
    outer = [s for s in QUERY_SPANS
             if s["name"] in ("serve.flush", "serve.topk")]
    assert reader(name)(query_ctx(outer)) is None


def test_compaction_without_blocks_reads_nothing():
    assert reader("compact.ms_per_block")(ingest_ctx(blocks=0)) is None


def test_compile_readers_merge_nested_spans():
    # a trace nested in another's trace is counted once: union of
    # [1.0, 1.3] + [1.1, 1.2] + [1.25, 1.5] + [2.0, 2.1] is 0.6 s
    compiles = [span("jit.compile", 1.0, 0.3, stage="trace"),
                span("jit.compile", 1.1, 0.1, stage="trace"),
                span("jit.compile", 1.25, 0.25, stage="compile"),
                span("jit.compile", 2.0, 0.1, stage="lower")]
    ing = reader("compile.ms_in_window.ingest")
    qry = reader("compile.ms_in_window.query")
    assert ing(ingest_ctx(INGEST_SPANS + compiles)) == pytest.approx(600.0)
    assert qry(query_ctx(QUERY_SPANS + compiles)) == pytest.approx(600.0)
    assert ing(ingest_ctx([])) == 0.0 and qry(query_ctx([])) == 0.0
