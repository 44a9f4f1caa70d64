"""Trace reduction and the kernels' operation and byte counts, on a small
fixture trace whose answers are counted by hand."""
import importlib.util
from pathlib import Path

import pytest

from benchmarks.chip.layer import LayerContext
from benchmarks.chip.trace_reduce import (Op, Span, TraceData, busy_intervals,
                                          load_xplane, module_base, op_base,
                                          self_seconds, summarize)

CHIP = Path(__file__).resolve().parents[2] / "benchmarks" / "chip"

# HLO texts as a v5e trace of a 37.7k-node graph recorded them
HINDEX = ('%h_index_pallas.6 = s32[1,4096]{1,0:T(1,128)} custom-call('
          's32[2048,4096]{1,0:T(8,128)S(1)} %select_bitcast_fusion.4, '
          's32[1,4096]{1,0:T(1,128)S(1)} %bitcast.50), '
          'custom_call_target="tpu_custom_call"')
TOPK = ('%topk_pallas.1 = (f32[64,128]{1,0:T(8,128)}, '
        's32[64,128]{1,0:T(8,128)}) '
        'custom-call(f32[64,128]{1,0:T(8,128)} %p0, '
        'f32[37888,128]{1,0:T(8,128)} %p1, f32[1,37888]{1,0:T(1,128)} %p2), '
        'custom_call_target="tpu_custom_call"')
FUSION = '%fusion.42 = pred[1024]{0} fusion(pred[47126]{0} %x), kind=kCustom'


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), CHIP / "layer_metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def fixture_trace():
    """Window [0, 10] s: ops at [1, 3] and [2, 4] overlap (busy 1-4), one
    at [6, 7], and one that starts before the window at [-1, 0.5]."""
    ops = [Op(HINDEX, 1.0, 2.0), Op(FUSION, 2.0, 2.0), Op(TOPK, 6.0, 1.0),
           Op(FUSION, -1.0, 1.5)]
    mods = [Op("jit__fused_descent(123)", 0.9, 3.2),
            Op("jit_topk_pallas(77)", 5.9, 1.2)]
    spans = [Span("bench.window", 0.0, 10.0),
             Span("bench.insert_block", 0.0, 5.0),
             Span("bench.retract_block", 5.0, 4.0),
             Span("bench.sync", 4.2, 0.5)]
    return TraceData(ops=[ops], modules=[mods], spans=spans)


def test_busy_union_and_idle_share(fixture_trace):
    ops = fixture_trace.ops[0]
    assert busy_intervals(ops, 0.0, 10.0) == [(0.0, 0.5), (1.0, 4.0),
                                              (6.0, 7.0)]
    s = summarize(fixture_trace, 0.0, 10.0)
    assert s.busy_s == pytest.approx(4.5)
    assert s.window_s == pytest.approx(10.0)
    assert s.idle_share == pytest.approx(0.55)


def test_time_per_op_by_program_and_name(fixture_trace):
    s = summarize(fixture_trace, 0.0, 10.0)
    assert s.op_seconds == pytest.approx({
        "jit__fused_descent/h_index_pallas": 2.0,
        "jit__fused_descent/fusion": 2.0,
        "jit_topk_pallas/topk_pallas": 1.0,
    })
    assert op_base(HINDEX) == "h_index_pallas"
    assert module_base("jit__cold(7902813226705472258)") == "jit__cold"


def test_self_time_leaves_out_nested_ops():
    ops = [Op("%while.3 = (s32[4]) while(...)", 0.0, 5.0),
           Op(FUSION, 1.0, 1.0), Op(HINDEX, 3.0, 1.0), Op(TOPK, 6.0, 1.0)]
    assert self_seconds(ops) == [3.0, 1.0, 1.0, 1.0]
    s = summarize(TraceData(ops=[ops], modules=[[]], spans=[]), 0.0, 10.0)
    assert s.op_seconds["no program/while"] == pytest.approx(3.0)
    assert s.busy_s == pytest.approx(6.0)


def test_idle_gaps_named_by_innermost_span(fixture_trace):
    s = summarize(fixture_trace, 0.0, 10.0)
    # gaps [7, 10] and [4, 6] have midpoints in retract_block (the shorter
    # of the two spans that hold 5.0); [0.5, 1] lies in insert_block
    assert s.gaps == [("bench.retract_block", pytest.approx(3.0)),
                      ("bench.retract_block", pytest.approx(2.0)),
                      ("bench.insert_block", pytest.approx(0.5))]
    program = [Span("repair.candidates", 7.5, 1.0)]
    named = summarize(fixture_trace, 0.0, 10.0, spans=program)
    assert named.gaps[0][0] == "repair.candidates"


def test_hindex_bytes_at_github_shapes(fixture_trace):
    m = reader("hindex_roofline")
    assert m.bytes_of(2048, 4096) == 4 * (2048 * 4096 + 2 * 4096) == 33587200
    s = summarize(fixture_trace, 0.0, 10.0)
    assert m.launches(s) == [(2048, 4096, 2.0)]
    ctx = LayerContext(kind="ingest", window=(0, 10), spans=[], trace=s,
                       peaks={"hbm_bytes_per_s": 819e9,
                              "bf16_flops_per_s": 197e12})
    assert m.read(ctx) == pytest.approx(100 * 33587200 / 819e9 / 2.0)
    assert m.read(LayerContext(kind="ingest", window=(0, 1), spans=[])) \
        is None


def test_topk_flops_and_bytes_at_github_shapes(fixture_trace):
    m = reader("topk_roofline")
    Q, N, D, Kp = 64, 37888, 128, 128
    assert m.flops_of(Q, N, D) == 2 * 64 * 37888 * 128 == 620756992
    assert m.bytes_of(Q, N, D, Kp) == 4 * (N * D + Q * D + N) + 8 * Q * Kp
    s = summarize(fixture_trace, 0.0, 10.0)
    assert m.launches(s) == [(Q, N, D, Kp, 1.0)]
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    ctx = LayerContext(kind="query", window=(0, 10), spans=[], trace=s,
                       peaks=peaks)
    least = max(620756992 / 197e12, m.bytes_of(Q, N, D, Kp) / 819e9)
    assert least == m.bytes_of(Q, N, D, Kp) / 819e9  # bytes bound
    assert m.read(ctx) == pytest.approx(100 * least / 1.0)


def test_idle_share_readers(fixture_trace):
    s = summarize(fixture_trace, 0.0, 10.0)
    ing = reader("device.idle_share.ingest")
    qry = reader("device.idle_share.query")
    ctx = LayerContext(kind="ingest", window=(0, 10), spans=[], trace=s)
    assert ing.read(ctx) == pytest.approx(55.0)
    assert qry.read(ctx) is None


def test_host_spans_are_read_from_a_profiler_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    from benchmarks.chip.trace_reduce import find_xplane

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        jnp.ones(8).sum().block_until_ready()
    jax.profiler.stop_trace()
    data = load_xplane(find_xplane(str(tmp_path)))
    assert [s.name for s in data.spans] == ["bench.window"]
    assert data.ops == []  # no TPU plane on the CPU


def test_repair_phases_leave_out_spans_inside_a_re_peel():
    spans = [{"name": "repair.fallback", "ts": 10.0, "dur": 3.0},
             {"name": "repair.descend", "ts": 11.0, "dur": 1.5},
             {"name": "repair.candidates", "ts": 10.2, "dur": 0.5},
             {"name": "repair.descend", "ts": 20.0, "dur": 2.0},
             {"name": "repair.candidates", "ts": 19.0, "dur": 1.0}]
    ctx = LayerContext(kind="ingest", window=(0, 30), spans=spans,
                       counters={"blocks": 2})
    assert reader("repair.descend_ms_per_block").read(ctx) == 1000.0
    assert reader("repair.candidates_ms_per_block").read(ctx) == 500.0
    assert reader("repair.fallback_ms_per_block").read(ctx) == 1500.0
