"""Readers of the refresh cell's per-layer metrics, on hand-made spans and
a hand-made trace whose answers are counted by hand; and the benchmark's
copy of the plain refresh reference."""
import importlib.util
from pathlib import Path

import pytest

from benchmarks.chip.layer import LayerContext
from benchmarks.chip.trace_reduce import Op, TraceData, summarize

ROOT = Path(__file__).resolve().parents[2]
CHIP = ROOT / "benchmarks" / "chip"


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), CHIP / "layer_metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def span(name, ts, dur, **attrs):
    return {"name": name, "ts": ts, "dur": dur, "attrs": attrs}


def refresh_spans(t, steps, scale=1.0):
    """One refresh at ``t``: its children partition it."""
    d = [0.01, 0.02, 0.3, 1.5, 0.04, 0.1, 0.03]
    d = [x * scale for x in d]
    out = [span("serve.retrain", t, sum(d))]
    names = ["retrain.rollback_copy", "retrain.plan", "retrain.walks",
             "retrain.train", "retrain.align", "retrain.propagate",
             "retrain.swap"]
    for name, x in zip(names, d):
        attrs = {"steps": steps, "pairs": steps * 4096} \
            if name == "retrain.train" else {}
        out.append(span(name, t, x, **attrs))
        t += x
    return out


# two refreshes in a 10 s window, the second twice as long
SPANS = refresh_spans(1.0, 1000) + refresh_spans(5.0, 2000, scale=2.0)
ONE = 2.0  # seconds of the first refresh (0.01 + 0.02 + ... + 0.03)


def ctx(spans=SPANS, kind="refresh", **kw):
    return LayerContext(kind=kind, window=(0.0, 10.0), spans=spans, **kw)


@pytest.mark.parametrize("name, want", [
    ("refresh.ms_per_refresh", 1e3 * 3 * ONE / 2),
    ("refresh.window_share", 100 * 3 * ONE / 10),
    ("refresh.walks_ms_per_refresh", 1e3 * 3 * 0.3 / 2),
    ("refresh.train_ms_per_refresh", 1e3 * 3 * 1.5 / 2),
    ("refresh.host_ms_per_refresh",
     1e3 * 3 * (0.01 + 0.02 + 0.04 + 0.1 + 0.03) / 2),
    ("sgns.us_per_step", 1e6 * (1.5 + 3.0) / 3000),
])
def test_refresh_readers(name, want):
    read = reader(name).read
    assert ONE == pytest.approx(sum(s["dur"] for s in SPANS[1:8]))
    assert read(ctx()) == pytest.approx(want)
    assert read(ctx(kind="ingest")) is None  # another cell kind
    assert read(ctx(spans=[])) is None  # no refresh in the window


def test_refresh_readers_without_the_new_spans():
    # a program with refresh spans but no step counts or rollback copy
    old = [dict(s, attrs={}) for s in SPANS
           if s["name"] != "retrain.rollback_copy"]
    assert reader("sgns.us_per_step").read(ctx(old)) is None
    assert reader("refresh.host_ms_per_refresh").read(ctx(old)) == \
        pytest.approx(1e3 * 3 * (0.02 + 0.04 + 0.1 + 0.03) / 2)


# the SGNS kernels as the v5e compiler names them in the training step:
# operands with their shapes inline, all in HBM; operands named, with
# their shapes as layout constraints, outputs in VMEM; and everything in
# VMEM (memory space 1)
FWD = ('%sgns_loss_fwd_pallas.1 = f32[4096,1]{1,0:T(8,128)} custom-call('
       'f32[4096,128]{1,0:T(8,128)} %c, f32[4096,128]{1,0:T(8,128)} %x, '
       'f32[4096,640]{1,0:T(8,128)} %n), '
       'custom_call_target="tpu_custom_call"')
BWD = ('%sgns_loss_bwd_pallas.1 = (f32[4096,128]{1,0:T(8,128)S(1)}, '
       'f32[4096,128]{1,0:T(8,128)S(1)}, f32[4096,640]{1,0:T(8,128)S(1)}) '
       'custom-call(%custom-call.25, %custom-call.26, %reshape.9, '
       '%broadcast.290), custom_call_target="tpu_custom_call", '
       'operand_layout_constraints={f32[4096,128]{1,0}, f32[4096,128]{1,0}, '
       'f32[4096,640]{1,0}, f32[4096,1]{1,0}}')
VMEM = ('%sgns_loss_fwd_pallas.2 = f32[4096,1]{1,0:T(8,128)S(1)} '
        'custom-call(f32[4096,128]{1,0:T(8,128)S(1)} %c, '
        'f32[4096,128]{1,0:T(8,128)S(1)} %x, '
        'f32[4096,640]{1,0:T(8,128)S(1)} %n), '
        'custom_call_target="tpu_custom_call"')
FUSION = '%fusion.3 = f32[12517,128]{1,0} fusion(f32[12517,128]{1,0} %p)'
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


@pytest.fixture
def sgns_trace():
    """Window [0, 10] s: a forward launch of 40 us, a backward of 80 us, a
    forward in VMEM of 1 us and a fusion, busy 1 s in all."""
    ops = [Op(FWD, 1.0, 40e-6), Op(BWD, 2.0, 80e-6), Op(VMEM, 2.5, 1e-6),
           Op(FUSION, 3.0, 1.0 - 121e-6)]
    mods = [Op("jit__train_step(5)", 0.9, 3.0)]
    return summarize(TraceData(ops=[ops], modules=[mods], spans=[]),
                     0.0, 10.0)


def test_sgns_operations_and_bytes_at_the_timed_shape(sgns_trace):
    m = reader("sgns_roofline")
    B, D, K = 4096, 128, 5
    # forward: K + 1 = 6 dot products of D multiply-adds per row
    fwd_flops = m.flops_of(True, B, D, K)
    assert fwd_flops == 2 * 4096 * 128 * 6 == 6291456
    # backward: 6 dot products again (12 BD), the centre gradient
    # (BD + 2 BDK), the context (BD) and negative (BDK) gradients
    assert m.flops_of(False, B, D, K) == 4096 * 128 * (12 + 1 + 10 + 1 + 5)
    # forward, all in HBM: 7 rows of 128 floats per example read, one
    # float written
    rows = 4 * 4096 * 128 * 7
    assert m.hbm_bytes(FWD) == rows + 4 * 4096 == 14696448
    # backward: its gradients stay in VMEM; it reads the rows and the
    # cotangent from HBM
    assert m.hbm_bytes(BWD) == rows + 4 * 4096
    assert m.hbm_bytes(VMEM) == 0
    assert m.launches(sgns_trace) == [
        (True, B, D, K, 14696448, 40e-6), (False, B, D, K, 14696448, 80e-6),
        (True, B, D, K, 0, 1e-6)]
    c = LayerContext(kind="refresh", window=(0, 10), spans=[],
                     trace=sgns_trace, peaks=PEAKS)
    # bytes bound the first two, operations the one in VMEM
    assert fwd_flops / 197e12 < 14696448 / 819e9
    least = 2 * 14696448 / 819e9 + fwd_flops / 197e12
    assert m.read(c) == pytest.approx(100 * least / 121e-6)
    assert 0 < m.read(c) < 100
    assert m.read(LayerContext(kind="refresh", window=(0, 1), spans=[])) \
        is None
    assert m.read(LayerContext(kind="refresh", window=(0, 10), spans=[],
                               trace=sgns_trace)) is None  # no peaks


def test_refresh_idle_share(sgns_trace):
    read = reader("device.idle_share.refresh").read
    c = LayerContext(kind="refresh", window=(0, 10), spans=[],
                     trace=sgns_trace)
    assert read(c) == pytest.approx(90.0)
    assert read(LayerContext(kind="ingest", window=(0, 10), spans=[],
                             trace=sgns_trace)) is None


def test_the_benchmark_reference_is_the_programs_copy():
    """``refs/refresh.py`` is ``repro/serve/refresh_ref.py`` as it stands,
    and imports nothing but numpy."""
    bench = (CHIP / "refs" / "refresh.py").read_text()
    prog = (ROOT / "src" / "repro" / "serve" / "refresh_ref.py").read_text()
    assert bench == prog
    imports = [line for line in bench.splitlines()
               if line.startswith(("import ", "from "))]
    assert imports == ["from __future__ import annotations",
                       "import numpy as np"]
