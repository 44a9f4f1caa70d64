"""Span tracer: nesting, attributes, exports, and the zero-work no-op path."""
import json

import pytest

from repro.obs import trace as obs
from repro.obs.trace import NULL_SPAN, Span, Tracer


class FakeClock:
    """Deterministic monotonic clock that counts how often it is read."""

    def __init__(self):
        self.t = 0.0
        self.reads = 0

    def __call__(self):
        self.reads += 1
        self.t += 1.0
        return self.t


@pytest.fixture
def default_tracer():
    """Swap in a fresh enabled default tracer; restore the original after."""
    prev = obs.tracer()
    t = obs.set_tracer(Tracer(enabled=True))
    yield t
    obs.set_tracer(prev)


# ------------------------------------------------------------------ recording


def test_nested_spans_record_depth_and_attrs():
    t = Tracer(enabled=True, clock=FakeClock())
    with t.span("outer", block=256) as outer:
        with t.span("inner") as inner:
            inner.set(rows=7)
        outer.set(accepted=250)
    # inner closes (and emits) first
    assert [e["name"] for e in t.events] == ["inner", "outer"]
    inner_e, outer_e = t.events
    assert inner_e["depth"] == 1 and outer_e["depth"] == 0
    assert inner_e["attrs"] == {"rows": 7}
    assert outer_e["attrs"] == {"block": 256, "accepted": 250}
    # fake clock ticks 1s per read: outer [1, 4], inner [2, 3]
    assert outer_e["ts"] == 1.0 and outer_e["dur"] == 3.0
    assert inner_e["ts"] == 2.0 and inner_e["dur"] == 1.0


def test_late_attrs_after_exit_still_land():
    # service code closes a span then attaches results computed right after;
    # the event holds the attrs dict by reference, so this must work
    t = Tracer(enabled=True, clock=FakeClock())
    sp = t.span("flush").__enter__()
    sp.__exit__(None, None, None)
    sp.set(hits=3)
    assert t.events[0]["attrs"] == {"hits": 3}


def test_decorator_and_record():
    t = Tracer(enabled=True, clock=FakeClock())

    @t.wrap("work")
    def work(x):
        return x + 1

    assert work(1) == 2
    t.record("pretimed", 10.0, 12.5, impl="np")
    names = [e["name"] for e in t.events]
    assert names == ["work", "pretimed"]
    pre = t.events[1]
    assert pre["ts"] == 10.0 and pre["dur"] == 2.5
    assert pre["attrs"] == {"impl": "np"}


def test_exception_still_emits_span():
    t = Tracer(enabled=True, clock=FakeClock())
    with pytest.raises(RuntimeError):
        with t.span("boom"):
            raise RuntimeError("x")
    assert t.span_names() == {"boom"}


def test_max_events_drops_and_counts():
    t = Tracer(enabled=True, clock=FakeClock(), max_events=2)
    for i in range(5):
        with t.span(f"s{i}"):
            pass
    assert len(t.events) == 2
    assert t.dropped == 3


def test_reset_clears_events():
    t = Tracer(enabled=True, clock=FakeClock())
    with t.span("a"):
        pass
    t.reset()
    assert t.events == [] and t.dropped == 0


# ----------------------------------------------------------- disabled = no-op


def test_disabled_tracer_is_zero_work():
    clock = FakeClock()
    t = Tracer(enabled=False, clock=clock)
    sp = t.span("hot", block=1024)
    assert sp is NULL_SPAN  # the one shared singleton — no allocation
    with sp as s:
        s.set(anything=1)
    t.record("hot2", 0.0, 1.0)
    assert clock.reads == 0  # clock never touched
    assert t.events == []


def test_module_level_fast_path_disabled(default_tracer):
    clock = FakeClock()
    obs.set_tracer(Tracer(enabled=False, clock=clock))
    assert obs.span("x") is NULL_SPAN
    obs.record("y", 0.0, 1.0)
    assert clock.reads == 0


def test_module_enable_disable(default_tracer):
    t = obs.enable()
    with obs.span("a", k=1):
        pass
    assert t.span_names() == {"a"}
    obs.disable()
    assert obs.span("b") is NULL_SPAN
    assert t.span_names() == {"a"}  # nothing new recorded


def test_wrap_disabled_calls_through():
    t = Tracer(enabled=False, clock=FakeClock())

    @t.wrap("work")
    def work():
        return 42

    assert work() == 42
    assert t.events == []


# -------------------------------------------------------------------- exports


def test_export_jsonl_round_trip(tmp_path):
    t = Tracer(enabled=True, clock=FakeClock())
    with t.span("a", n=1):
        with t.span("b"):
            pass
    path = tmp_path / "spans.jsonl"
    assert t.export_jsonl(str(path)) == 2
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert [l["name"] for l in lines] == ["b", "a"]
    assert lines[1]["attrs"] == {"n": 1}


def test_chrome_export_is_loadable_complete_events(tmp_path):
    t = Tracer(enabled=True, clock=FakeClock())
    with t.span("outer"):
        with t.span("inner"):
            pass
    path = tmp_path / "trace.json"
    assert t.export_chrome(str(path)) == 2
    doc = json.loads(path.read_text())
    ev = doc["traceEvents"]
    assert all(e["ph"] == "X" for e in ev)
    by_name = {e["name"]: e for e in ev}
    outer, inner = by_name["outer"], by_name["inner"]
    # microseconds, rebased to the earliest span start (outer opens first)
    assert outer["ts"] == 0.0
    assert inner["ts"] == 1e6 and inner["dur"] == 1e6
    assert outer["dur"] == 3e6
    # containment (what the viewers use to nest) + depth rides in args
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    assert inner["args"]["depth"] == 1


def test_chrome_export_records_drops(tmp_path):
    t = Tracer(enabled=True, clock=FakeClock(), max_events=1)
    for _ in range(3):
        with t.span("s"):
            pass
    path = tmp_path / "trace.json"
    t.export_chrome(str(path))
    doc = json.loads(path.read_text())
    assert doc["metadata"]["dropped_events"] == 2


# ------------------------------------------------------- tail-sampled exemplars


def feed(t, name, dur, n=1, start=0.0, gap=100.0):
    """Record n back-to-back pre-timed spans of the given duration."""
    for i in range(n):
        t0 = start + i * gap
        t.record(name, t0, t0 + dur)


def test_tail_span_becomes_exemplar():
    t = Tracer(enabled=True, exemplar_min_samples=4)
    feed(t, "serve.flush", 0.010, n=8)
    assert t.exemplars == {}  # steady state: nothing crosses its own tail
    feed(t, "serve.flush", 0.080, start=10_000.0)
    recs = t.exemplar_records()
    assert len(recs) == 1
    rec = recs[0]
    assert rec["name"] == "serve.flush"
    assert rec["dur"] == pytest.approx(0.080)
    assert rec["threshold"] == pytest.approx(0.010)
    # the bucket link invariant: lower < root duration <= le
    assert rec["bucket_lower_s"] < rec["dur"]
    assert rec["dur"] <= rec["bucket_le_s"]


def test_no_capture_before_min_samples():
    t = Tracer(enabled=True, exemplar_min_samples=16)
    feed(t, "serve.flush", 0.001, n=10)
    feed(t, "serve.flush", 5.0, start=10_000.0)  # huge, but ring too young
    assert t.exemplars == {}


def test_watch_prefix_matches_namespace():
    t = Tracer(enabled=True, exemplar_min_samples=1)
    # "repair." watches the whole namespace; serve.query is not watched
    feed(t, "repair.repeel", 0.001)
    feed(t, "repair.repeel", 0.050, start=100.0)
    feed(t, "serve.query", 0.001)
    feed(t, "serve.query", 0.050, start=100.0)
    names = {r["name"] for r in t.exemplar_records()}
    assert names == {"repair.repeel"}
    assert "serve.query" not in t._tail_durs  # unwatched: zero state kept


def test_same_bucket_keeps_slowest():
    t = Tracer(enabled=True, exemplar_min_samples=1)
    feed(t, "serve.flush", 0.001)
    feed(t, "serve.flush", 0.009, start=1_000.0)  # captured
    feed(t, "serve.flush", 0.012, start=2_000.0)  # same bucket, slower
    recs = t.exemplar_records()
    assert len(recs) == 1
    assert recs[0]["dur"] == pytest.approx(0.012)
    # a direct slower->faster attempt must keep the slow representative
    t._capture_exemplar("serve.flush", 0.0, 0.009, 0, recs[0]["tid"], 0.001)
    assert t.exemplar_records()[0]["dur"] == pytest.approx(0.012)


def test_max_exemplars_cap_counts_drops():
    t = Tracer(enabled=True, exemplar_min_samples=1, max_exemplars=2)
    feed(t, "serve.flush", 0.0001)
    feed(t, "serve.flush", 0.003, start=1_000.0)   # bucket A
    feed(t, "serve.flush", 0.006, start=2_000.0)   # bucket B -> at cap
    feed(t, "serve.flush", 0.024, start=3_000.0)   # bucket C -> dropped
    assert len(t.exemplars) == 2
    assert t.exemplars_dropped == 1


def test_exemplar_retains_full_subtree():
    t = Tracer(enabled=True, clock=FakeClock(), exemplar_min_samples=1)
    with t.span("serve.flush"):          # [1, 2] seeds the ring
        pass
    with t.span("serve.flush"):          # [3, 8], dur 5 > threshold 1
        with t.span("store.gather"):     # [4, 5]
            pass
        with t.span("merge"):            # [6, 7]
            pass
    recs = t.exemplar_records()
    assert len(recs) == 1
    rec = recs[0]
    assert [s["name"] for s in rec["spans"]] == \
        ["store.gather", "merge", "serve.flush"]
    t0, t1 = rec["ts"], rec["ts"] + rec["dur"]
    for s in rec["spans"]:
        assert t0 <= s["ts"] and s["ts"] + s["dur"] <= t1
    # the earlier steady-state flush is NOT part of the subtree
    assert all(s["ts"] != 1.0 for s in rec["spans"])
    assert rec["dur"] > rec["bucket_lower_s"]


def test_reset_clears_exemplar_state():
    t = Tracer(enabled=True, exemplar_min_samples=1)
    feed(t, "serve.flush", 0.001)
    feed(t, "serve.flush", 0.050, start=100.0)
    assert t.exemplars
    t.reset()
    assert t.exemplars == {} and t.exemplars_dropped == 0
    assert t._tail_durs == {}


def test_export_exemplars_loads(tmp_path):
    t = Tracer(enabled=True, exemplar_min_samples=1)
    feed(t, "serve.flush", 0.001)
    feed(t, "serve.flush", 0.050, start=100.0)
    path = tmp_path / "ex.json"
    assert t.export_exemplars(str(path)) == 1
    doc = json.loads(path.read_text())
    assert doc["dropped"] == 0
    assert doc["quantile"] == 99.0
    assert "serve.flush" in doc["watch"]
    rec = doc["exemplars"][0]
    assert isinstance(rec["spans"], list)
    assert rec["bucket_lower_s"] < rec["dur"]
    assert rec["bucket_le_s"] is None or rec["dur"] <= rec["bucket_le_s"]


def test_disabled_tracer_keeps_no_exemplar_state():
    t = Tracer(enabled=False, exemplar_min_samples=1)
    assert t.span("serve.flush") is NULL_SPAN
    t.record("serve.flush", 0.0, 9.0)
    assert t.events == [] and t.exemplars == {} and t._tail_durs == {}


# ------------------------------------------- pipelined ingest trace integrity


def test_chrome_export_nests_under_pipelined_ingest(default_tracer, tmp_path):
    """Overlapped block staging must not produce interleaved (half-
    overlapping) spans: within each thread lane the exported Chrome trace
    has to stay strictly containment-nested, or the viewers render garbage
    nesting for exactly the runs where the pipeline is interesting."""
    np = pytest.importorskip("numpy")
    from repro.graph import generators
    from repro.launch.serve_embed import build_service

    g = generators.barabasi_albert_varying(240, 4.0, seed=5)
    svc, stream, _, _ = build_service(
        g, seed=5, batch=32, compact_every=64)
    rng = np.random.default_rng(7)
    for start in range(0, len(stream), 48):
        svc.ingest_block(stream[start:start + 48])
        if (start // 48) % 2:
            # queries settle the in-flight block mid-stream
            svc.embed(rng.integers(0, svc.graph.n_nodes, size=8))
    svc.sync()

    path = tmp_path / "pipeline_trace.json"
    n = default_tracer.export_chrome(str(path))
    assert n > 0
    doc = json.loads(path.read_text())  # loads cleanly, no torn events
    events = doc["traceEvents"]
    assert {e["ph"] for e in events} == {"X"}
    lanes = {}
    for e in events:
        lanes.setdefault(e["tid"], []).append(e)
    assert any(len(v) > 1 for v in lanes.values())
    for lane in lanes.values():
        # sweep with an interval stack: every pair of spans in a lane must
        # be disjoint or fully nested — a span may never half-overlap the
        # one below it
        lane.sort(key=lambda e: (e["ts"], -e["dur"]))
        stack = []  # open end-times
        for e in lane:
            t0, t1 = e["ts"], e["ts"] + e["dur"]
            while stack and stack[-1] <= t0:
                stack.pop()
            assert not stack or t1 <= stack[-1], (
                f"span {e['name']} [{t0}, {t1}] half-overlaps an "
                f"enclosing span ending at {stack[-1]}")
            stack.append(t1)


# ------------------------------------------------------------ ids and parents


def test_spans_carry_ids_and_parents():
    t = Tracer(enabled=True, clock=FakeClock())
    with t.span("outer") as outer:
        with t.span("inner") as inner:
            t.record("pretimed", 2.5, 2.6)  # takes the innermost open span
        t.record("sibling", 3.5, 3.6)
    t.record("top", 10.0, 11.0)
    by_name = {e["name"]: e for e in t.events}
    assert by_name["outer"]["parent"] is None
    assert by_name["inner"]["parent"] == outer.id
    assert by_name["pretimed"]["parent"] == inner.id
    assert by_name["pretimed"]["depth"] == 2
    assert by_name["sibling"]["parent"] == outer.id
    assert by_name["top"]["parent"] is None
    ids = [e["id"] for e in t.events]
    assert len(set(ids)) == len(ids)
    assert by_name["outer"]["id"] == outer.id


def test_exports_carry_parent(tmp_path):
    t = Tracer(enabled=True, clock=FakeClock())
    with t.span("a") as a:
        with t.span("b"):
            pass
    t.export_jsonl(str(tmp_path / "s.jsonl"))
    lines = [json.loads(l)
             for l in (tmp_path / "s.jsonl").read_text().splitlines()]
    assert [(l["name"], l["parent"]) for l in lines] == \
        [("b", a.id), ("a", None)]
    t.export_chrome(str(tmp_path / "t.json"))
    ev = {e["name"]: e for e in
          json.loads((tmp_path / "t.json").read_text())["traceEvents"]}
    assert ev["b"]["args"]["parent"] == ev["a"]["args"]["id"] == a.id
    assert ev["a"]["args"]["parent"] is None


def test_self_time_is_duration_less_children():
    # fake clock: outer [1, 8], children [2, 3] and [4, 7] -> self 3
    t = Tracer(enabled=True, clock=FakeClock())
    with t.span("outer") as outer:
        with t.span("c1"):
            pass
        with t.span("c2"):
            with t.span("grandchild"):
                pass
    by_name = {e["name"]: e for e in t.events}
    kids = [e for e in t.events if e["parent"] == outer.id]
    assert {e["name"] for e in kids} == {"c1", "c2"}
    own = by_name["outer"]["dur"] - sum(e["dur"] for e in kids)
    assert own == 3.0


def test_timed_span_is_timed_either_way():
    clock = FakeClock()
    off = Tracer(enabled=False, clock=clock)
    with off.timed("repair.region", impl="np") as sp:
        sp.set(rows=3)
    assert sp.t1 >= sp.t0 > 0.0  # timed on perf_counter
    assert sp.attrs == {"impl": "np", "rows": 3}
    assert clock.reads == 0 and off.events == []
    on = Tracer(enabled=True, clock=FakeClock())
    with on.timed("repair.region", impl="np") as sp:
        pass
    assert (sp.t0, sp.t1) == (1.0, 2.0)
    assert on.events[0]["name"] == "repair.region"
    assert on.events[0]["attrs"] == {"impl": "np"}


# ----------------------------------------------------------- profiler mirror


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation`` and logs its use."""

    def __init__(self):
        self.log = []

    def __call__(self, name):
        log = self.log

        class Ann:
            def __enter__(self):
                log.append(("enter", name))

            def __exit__(self, *exc):
                log.append(("exit", name))

        return Ann()


def test_span_mirrors_a_profiler_annotation(monkeypatch):
    ann = _Annotations()
    monkeypatch.setattr(obs, "_TraceAnnotation", ann)
    t = Tracer(enabled=True, clock=FakeClock())
    with t.span("a"):
        with pytest.raises(RuntimeError):
            with t.span("b"):
                raise RuntimeError("x")
    # the annotation exits on the exception too
    assert ann.log == [("enter", "a"), ("enter", "b"), ("exit", "b"),
                       ("exit", "a")]
    assert [e["name"] for e in t.events] == ["b", "a"]


# ------------------------------------------------------------- compile spans


def test_compile_spans_only_while_enabled(default_tracer):
    np = pytest.importorskip("numpy")
    import jax
    import jax.numpy as jnp

    t = obs.enable(exemplar_watch=())
    # a constant drawn per run keeps the program out of any compile cache
    scale = float(np.random.default_rng().integers(1, 1 << 30))

    def scaled(x):
        return x * scale + 1.0

    f = jax.jit(scaled)
    with t.span("outer") as outer:
        f(jnp.ones(3)).block_until_ready()
    comp = [e for e in t.events if e["name"] == "jit.compile"]
    assert {"trace", "lower"} <= {e["attrs"]["stage"] for e in comp}
    assert all(e["parent"] == outer.id for e in comp)
    assert any(e["attrs"]["fun"] == "scaled" for e in comp)
    out = t.events[-1]
    assert out["name"] == "outer"
    for e in comp:  # they end inside the span that was open
        assert out["ts"] <= e["ts"] + e["dur"] <= out["ts"] + out["dur"]
        assert e["dur"] >= 0.0
    obs.disable()
    n = len(t.events)
    f(jnp.ones(5)).block_until_ready()  # a new shape compiles again
    assert len(t.events) == n


# ------------------------------------------- disabled serving path = no work


def _small_service():
    np = pytest.importorskip("numpy")
    from repro.graph import generators
    from repro.serve import (DynamicGraph, EmbeddingService, EmbeddingStore,
                             IncrementalCore)

    g = generators.barabasi_albert_varying(120, 4.0, seed=3)
    dyn = DynamicGraph(g.n_nodes, g.edge_list(), width=4)
    inc = IncrementalCore(dyn)
    store = EmbeddingStore(capacity=g.n_nodes, dim=8, node_cap=dyn.node_cap)
    emb = np.random.default_rng(0).normal(size=(g.n_nodes, 8))
    stored = np.arange(0, g.n_nodes, 2)  # odd nodes cold-start
    store.put_many(stored, emb[stored].astype(np.float32),
                   inc.core[stored])
    return EmbeddingService(dyn, inc, store, batch=16)


def _serve(svc, rounds=1):
    np = pytest.importorskip("numpy")
    rng = np.random.default_rng(1)
    n = svc.graph.n_nodes
    for _ in range(rounds):
        svc.embed(rng.integers(0, n, size=24))
        svc.link_scores(rng.integers(0, n, size=(6, 2)))
        svc.top_k_neighbors(rng.integers(0, n, size=8), 5)
        block = rng.integers(0, n, size=(12, 2))
        svc.ingest_block(block[block[:, 0] != block[:, 1]])
        svc.sync()
        svc.graph.compact()


def test_disabled_serving_path_is_zero_work(default_tracer, monkeypatch):
    """Disabled tracing over flush, link, top-k, ingest and compaction: no
    clock read, no profiler annotation, and a listener that returns at
    once."""
    svc = _small_service()
    _serve(svc)  # compiles outside the check
    clock = FakeClock()
    off = obs.set_tracer(Tracer(enabled=False, clock=clock))
    ann = _Annotations()
    monkeypatch.setattr(obs, "_TraceAnnotation", ann)
    _serve(svc)
    obs._on_compile_event("/jax/core/compile/backend_compile_duration",
                          1.5, fun_name="f")
    assert clock.reads == 0
    assert ann.log == []
    assert off.events == []


def test_program_spans_land_in_the_profiler_trace(default_tracer, tmp_path):
    """Under a profiler session the program's spans are in the host trace,
    and each start, mapped by one offset taken at a ``bench.window``
    annotation, lies within 1 ms of the tracer's own."""
    import time

    import jax

    from benchmarks.chip.trace_reduce import find_xplane, load_xplane

    svc = _small_service()
    _serve(svc)  # compiles outside the profile
    t = obs.enable(exemplar_watch=())
    jax.profiler.start_trace(str(tmp_path))
    try:
        t_window = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            _serve(svc, rounds=2)
    finally:
        jax.profiler.stop_trace()
        obs.disable()
    path = find_xplane(str(tmp_path))
    mark = [s for s in load_xplane(path).spans if s.name == "bench.window"]
    offset = mark[-1].start - t_window  # the harness's one-offset rule
    profiled = load_xplane(path, prefix="serve.").spans
    ours = sorted((e for e in t.events if e["name"].startswith("serve.")),
                  key=lambda e: e["ts"])
    assert {"serve.flush", "serve.link", "serve.topk", "serve.ingest"} <= \
        {s.name for s in profiled}
    assert [s.name for s in profiled] == [e["name"] for e in ours]
    for s, e in zip(profiled, ours):
        assert abs(s.start - offset - e["ts"]) < 1e-3, s.name
