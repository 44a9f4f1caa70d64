"""EmbeddingService: microbatched serving + §2.2 cold-start propagation."""
import numpy as np
import pytest

from repro.core.kcore import core_numbers_host
from repro.core.propagation import propagate
from repro.graph import generators
from repro.graph.csr import Graph
from repro.serve import DynamicGraph, EmbeddingService, EmbeddingStore, IncrementalCore

DIM = 8


def _service_from(graph, emb_nodes, emb, *, batch=16, capacity=None, **kw):
    dyn = DynamicGraph(graph.n_nodes, graph.edge_list(), width=16)
    inc = IncrementalCore(dyn)
    store = EmbeddingStore(
        capacity=capacity or graph.n_nodes, dim=DIM, node_cap=dyn.node_cap
    )
    store.put_many(emb_nodes, emb[emb_nodes], inc.core[emb_nodes])
    return EmbeddingService(dyn, inc, store, batch=batch, **kw)


def test_cold_start_equals_propagate_on_clique_pendant():
    """One-shot neighbour mean == propagate() restricted to the queried node.

    K6 clique (core 5) + node 6 attached to three clique members (core 3):
    propagate's shell-3 system for node 6 has only fixed (k0-core) neighbours,
    so every Jacobi iterate equals the one-shot mean the service computes.
    """
    edges = [(i, j) for i in range(6) for j in range(i + 1, 6)] + [
        (6, 0), (6, 1), (6, 2)
    ]
    g = Graph.from_edges(7, np.array(edges))
    core = core_numbers_host(g)
    np.testing.assert_array_equal(core, [5] * 6 + [3])
    k0 = 5
    rng = np.random.default_rng(0)
    base = np.zeros((7, DIM), np.float32)
    base[:6] = rng.normal(size=(6, DIM)).astype(np.float32)

    want = propagate(g, core, k0, base, n_iters=17)

    svc = _service_from(g, np.arange(6), base)
    got = svc.embed([6])
    np.testing.assert_allclose(got[0], want[6], rtol=1e-5, atol=1e-6)
    assert svc.stats.cold_starts == 1 and svc.stats.unresolved == 0


def test_cold_start_equals_propagate_on_random_graph():
    """Same equivalence on a random graph, for every shell-(k0-1) node whose
    allowed neighbours are all inside the k0-core (no same-shell coupling)."""
    g = generators.barabasi_albert_varying(200, 5.0, seed=3)
    core = core_numbers_host(g)
    rng = np.random.default_rng(1)
    checked = 0
    for k0 in range(int(core.max()), 2, -1):
        fixed = core >= k0
        cands = [
            int(t)
            for t in np.where(core == k0 - 1)[0]
            if np.all(core[g.neighbours(t)] >= k0)
        ]
        if not cands:
            continue
        base = np.zeros((g.n_nodes, DIM), np.float32)
        base[fixed] = rng.normal(size=(int(fixed.sum()), DIM)).astype(np.float32)
        want = propagate(g, core, k0, base, n_iters=9)
        svc = _service_from(g, np.where(fixed)[0], base)
        got = svc.embed(cands)
        for i, t in enumerate(cands):
            np.testing.assert_allclose(got[i], want[t], rtol=1e-5, atol=1e-6)
        checked += len(cands)
    assert checked > 0, "graph/seed must yield at least one decoupled shell node"


def test_cold_start_sees_spilled_neighbours():
    """Neighbour embeddings evicted to host spill still feed the §2.2 mean."""
    edges = [(i, j) for i in range(6) for j in range(i + 1, 6)] + [
        (6, 0), (6, 1), (6, 2)
    ]
    g = Graph.from_edges(7, np.array(edges))
    rng = np.random.default_rng(7)
    base = np.zeros((7, DIM), np.float32)
    base[:6] = rng.normal(size=(6, DIM)).astype(np.float32)
    # capacity 4 < 6 embedded nodes: some of node 6's neighbours are spilled
    svc = _service_from(g, np.arange(6), base, capacity=4)
    assert svc.store.spilled > 0
    got = svc.embed([6])
    np.testing.assert_allclose(got[0], base[:3].mean(axis=0), rtol=1e-5, atol=1e-6)
    assert svc.stats.unresolved == 0


def test_working_set_beyond_capacity_is_served_from_spill():
    """Querying more stored nodes than the device table holds must serve the
    spill-tier rows correctly — never zeros, never cold-start overwrites."""
    g = generators.barabasi_albert(30, 2, seed=9)
    rng = np.random.default_rng(8)
    emb = rng.normal(size=(30, DIM)).astype(np.float32)
    svc = _service_from(g, np.arange(30), emb, capacity=2, batch=8)
    out = svc.embed(list(range(30)))  # working set 4x the table capacity
    for v in range(30):
        np.testing.assert_allclose(out[v], emb[v], rtol=1e-6)
    assert svc.stats.cold_starts == 0  # every row was a store hit
    # nothing got overwritten by a cold-start write-back
    out2 = svc.embed(list(range(30)))
    np.testing.assert_allclose(out2, out, rtol=1e-6)


def test_static_batches_pad_and_preserve_order():
    g = generators.barabasi_albert(60, 3, seed=4)
    rng = np.random.default_rng(2)
    emb = rng.normal(size=(60, DIM)).astype(np.float32)
    svc = _service_from(g, np.arange(60), emb, batch=16)
    nodes = [5, 3, 41, 17, 3]  # shorter than batch; duplicates allowed
    out = svc.embed(nodes)
    assert out.shape == (5, DIM)
    for i, v in enumerate(nodes):
        np.testing.assert_allclose(out[i], emb[v], rtol=1e-6)
    assert svc.stats.queries == 5  # padding slots are not counted
    assert svc.stats.flushes == 1


def test_write_back_turns_cold_into_hit():
    g = generators.barabasi_albert(40, 3, seed=5)
    rng = np.random.default_rng(3)
    emb = rng.normal(size=(40, DIM)).astype(np.float32)
    known = np.arange(39)  # node 39 is cold
    svc = _service_from(g, known, emb, write_back=True)
    svc.embed([39])
    assert svc.stats.cold_starts == 1
    svc.embed([39])
    assert svc.stats.cold_starts == 1  # second hit comes from the store
    assert svc.stats.store_hits == 1
    # write-back stamped the node's current core level for staleness tracking
    assert 39 in svc.store
    assert svc.store.staleness(svc.cores.core) == 0.0


def test_isolated_cold_node_is_unresolved_zero():
    g = Graph.from_edges(4, np.array([[0, 1], [1, 2]]))
    rng = np.random.default_rng(4)
    emb = rng.normal(size=(4, DIM)).astype(np.float32)
    svc = _service_from(g, np.array([0, 1, 2]), emb)
    out = svc.embed([3])  # node 3 has no edges at all
    np.testing.assert_allclose(out[0], 0.0)
    assert svc.stats.unresolved == 1


def test_link_scores_are_cosines():
    """Scores are cosine (matching the retrain-eval AUC ranking), and a
    self-pair scores exactly 1 regardless of the embedding's norm."""
    g = generators.barabasi_albert(30, 2, seed=6)
    rng = np.random.default_rng(5)
    emb = rng.normal(size=(30, DIM)).astype(np.float32)
    svc = _service_from(g, np.arange(30), emb)
    pairs = np.array([[0, 1], [5, 9], [2, 2]])
    got = svc.link_scores(pairs)

    def cos(u, v):
        a, b = emb[u], emb[v]
        return a @ b / (np.linalg.norm(a) * np.linalg.norm(b))

    want = np.array([cos(u, v) for u, v in pairs])
    np.testing.assert_allclose(got, want, rtol=1e-5)
    np.testing.assert_allclose(got[2], 1.0, rtol=1e-6)


def test_link_scores_dedup_endpoints():
    """A pair list with few distinct endpoints flushes each node once —
    duplicate cold endpoints must not inflate the cold-start count."""
    g = generators.barabasi_albert(30, 2, seed=6)
    rng = np.random.default_rng(5)
    emb = rng.normal(size=(30, DIM)).astype(np.float32)
    svc = _service_from(g, np.arange(29), emb)  # node 29 is cold
    pairs = np.array([[29, 0], [29, 1], [0, 29], [29, 29]])
    svc.link_scores(pairs)
    assert svc.stats.cold_starts == 1
    assert svc.stats.queries == 3  # 29, 0, 1 — one flush slot each


def test_duplicate_cold_nodes_in_one_batch_count_once():
    """Regression: duplicates of one cold id inside a single padded batch
    must share one write-back slot and count as one cold start."""
    g = generators.barabasi_albert(40, 3, seed=5)
    rng = np.random.default_rng(3)
    emb = rng.normal(size=(40, DIM)).astype(np.float32)
    svc = _service_from(g, np.arange(39), emb, batch=16)
    free_before = svc.store.capacity - svc.store.resident - svc.store.spilled
    out = svc.embed([39, 39, 5, 39])
    assert svc.stats.cold_starts == 1
    np.testing.assert_allclose(out[0], out[1])
    np.testing.assert_allclose(out[0], out[3])
    # exactly one slot was consumed by the write-back, not three
    free_after = svc.store.capacity - svc.store.resident - svc.store.spilled
    assert free_before - free_after == 1
    svc.embed([39])
    assert svc.stats.cold_starts == 1  # resident now


def test_graph_growth_between_submit_and_flush():
    """Regression: flush() padding must survive node_cap growth. Queries
    enqueued before ingest_edges mints new ids (growing the sentinel) must
    still resolve — a padding value snapshotted from the old node_cap could
    alias a freshly minted real node."""
    g = generators.barabasi_albert(30, 2, seed=12)
    rng = np.random.default_rng(15)
    emb = rng.normal(size=(30, DIM)).astype(np.float32)
    svc = _service_from(g, np.arange(30), emb, batch=8)
    svc.submit_many([3, 7, 11])  # short batch -> 5 padding lanes
    cap_before = svc.graph.node_cap
    # grow the graph past its node capacity so the sentinel moves
    new_edges = [(30 + i, int(rng.integers(0, 30))) for i in range(40)]
    svc.ingest_edges(new_edges)
    assert svc.graph.node_cap > cap_before
    out = svc.flush()
    assert out.shape == (3, DIM)
    for i, v in enumerate([3, 7, 11]):
        np.testing.assert_allclose(out[i], emb[v], rtol=1e-6)
    assert svc.stats.queries == 3  # padding lanes never counted


def test_top_k_neighbors_matches_oracle():
    g = generators.barabasi_albert(40, 3, seed=5)
    rng = np.random.default_rng(3)
    emb = rng.normal(size=(40, DIM)).astype(np.float32)
    svc = _service_from(g, np.arange(40), emb)
    q = [0, 7, 13]
    ids, scores = svc.top_k_neighbors(q, 5)
    assert ids.shape == (3, 5) and scores.shape == (3, 5)
    en = emb / np.maximum(
        np.linalg.norm(emb, axis=1, keepdims=True), 1e-9
    )
    sim = en @ en.T
    for qi, v in enumerate(q):
        s = sim[v].copy()
        s[v] = -np.inf  # self-exclusion
        want = np.lexsort((np.arange(40), -s))[:5]
        slots = svc.store.slots_of(ids[qi])
        np.testing.assert_array_equal(np.sort(slots), np.sort(
            svc.store.slots_of(want)
        ))
        np.testing.assert_allclose(
            np.sort(scores[qi]), np.sort(s[want]), rtol=1e-5
        )
        assert v not in ids[qi]
        # descending score order
        assert np.all(np.diff(scores[qi]) <= 1e-7)


def test_top_k_neighbors_pads_when_few_candidates():
    g = generators.barabasi_albert(10, 2, seed=11)
    rng = np.random.default_rng(16)
    emb = rng.normal(size=(10, DIM)).astype(np.float32)
    svc = _service_from(g, np.arange(3), emb, capacity=10)
    ids, scores = svc.top_k_neighbors([0], 6)
    # only nodes 1, 2 are candidates (0 excludes itself)
    assert set(ids[0][ids[0] >= 0]) == {1, 2}
    np.testing.assert_array_equal(ids[0][2:], -1)
    assert np.all(scores[0][2:] == -np.inf)
    # empty / degenerate shapes
    i0, s0 = svc.top_k_neighbors([], 4)
    assert i0.shape == (0, 4) and s0.shape == (0, 4)
    i1, s1 = svc.top_k_neighbors([1], 0)
    assert i1.shape == (1, 0)
    assert svc.stats.topk_queries == 1


def test_ingest_compacts_and_stays_exact():
    g = generators.barabasi_albert_varying(120, 4.0, seed=7)
    edges = g.edge_list()
    half = len(edges) // 2
    dyn = DynamicGraph(g.n_nodes, edges[:half], width=4)
    inc = IncrementalCore(dyn)
    store = EmbeddingStore(capacity=g.n_nodes, dim=DIM, node_cap=dyn.node_cap)
    svc = EmbeddingService(dyn, inc, store, batch=8, compact_every=64)
    n = svc.ingest_edges(edges[half:])
    assert n == len(edges) - half
    assert svc.stats.compactions >= 1
    oracle = core_numbers_host(dyn.snapshot())
    np.testing.assert_array_equal(inc.core, oracle)


def test_submit_many_matches_per_node_submits():
    g = generators.barabasi_albert(40, 3, seed=10)
    rng = np.random.default_rng(9)
    emb = rng.normal(size=(40, DIM)).astype(np.float32)
    svc_a = _service_from(g, np.arange(40), emb, batch=16)
    svc_b = _service_from(g, np.arange(40), emb, batch=16)
    nodes = [5, 3, 3, 17, 39, 0, 12]
    idx = svc_a.submit_many(nodes)
    np.testing.assert_array_equal(idx, np.arange(len(nodes)))
    assert svc_a.pending == len(nodes)
    for n in nodes:
        svc_b.submit(n)
    out_a, out_b = svc_a.flush(), svc_b.flush()
    np.testing.assert_allclose(out_a, out_b, rtol=1e-6)
    assert svc_a.pending == 0
    # indices keep accumulating across mixed submit/submit_many calls
    assert svc_a.submit(2) == 0
    np.testing.assert_array_equal(svc_a.submit_many([4, 6]), [1, 2])
    assert svc_a.flush().shape == (3, DIM)


def test_submit_many_rejects_negative_ids_and_accepts_empty():
    g = generators.barabasi_albert(10, 2, seed=11)
    emb = np.zeros((10, DIM), np.float32)
    svc = _service_from(g, np.arange(10), emb)
    with pytest.raises(ValueError):
        svc.submit_many([1, -2, 3])
    assert svc.pending == 0  # the failed batch queued nothing
    assert svc.submit_many([]).size == 0
    assert svc.embed([]).shape == (0, DIM)


def test_retrain_pressure_rises_with_membership_churn():
    g = generators.barabasi_albert(80, 3, seed=8)
    rng = np.random.default_rng(6)
    emb = rng.normal(size=(80, DIM)).astype(np.float32)
    core = core_numbers_host(g)
    k0 = int(core.max())
    svc = _service_from(g, np.arange(80), emb, k0=k0, retrain_threshold=0.01)
    svc.cores.mark_refresh()
    assert svc.retrain_pressure() == 0.0
    # wire low-core nodes into a dense pocket to push them into the k0-core
    low = np.argsort(core)[:10]
    with pytest.raises(AssertionError):
        np.testing.assert_array_equal(core[low], k0)  # genuinely below k0
    for i in range(len(low)):
        for j in range(i + 1, len(low)):
            svc.ingest(int(low[i]), int(low[j]))
    assert svc.retrain_pressure() > 0.0
    assert svc.should_retrain()


@pytest.fixture(scope="module")
def traced_serving():
    """Spans of warm flushes, link and top-k calls and compactions."""
    import gc

    from repro.obs import trace as obs

    g = generators.barabasi_albert_varying(150, 4.0, seed=11)
    emb = np.random.default_rng(2).normal(size=(g.n_nodes, DIM))
    svc = _service_from(g, np.arange(0, g.n_nodes, 2), emb.astype(np.float32))
    rng = np.random.default_rng(3)

    def serve():
        svc.embed(rng.integers(0, g.n_nodes, size=40))
        svc.link_scores(rng.integers(0, g.n_nodes, size=(8, 2)))
        svc.top_k_neighbors(rng.integers(0, g.n_nodes, size=16), 5)
        block = rng.integers(0, g.n_nodes, size=(64, 2))
        svc.ingest_block(block[block[:, 0] != block[:, 1]])
        svc.sync()
        svc.graph.compact()

    serve()  # compiles the shapes first
    prev = obs.tracer()
    t = obs.enable(exemplar_watch=())
    gc.collect()
    try:
        for _ in range(6):
            serve()
    finally:
        obs.set_tracer(prev)
    return t.events


@pytest.mark.parametrize("outer, children", [
    ("serve.flush", {"flush.stage", "flush.wait", "flush.commit"}),
    ("serve.topk", {"serve.flush", "topk.prep", "topk.wait", "topk.merge"}),
    ("serve.link", {"serve.flush", "link.score"}),
    ("graph.compact", {"compact.build", "compact.upload"}),
])
def test_child_spans_partition_their_parent(traced_serving, outer, children):
    """The children of each outer span name it as their parent, lie inside
    it, and cover at least 90% of its time, so an idle gap inside it gets
    a specific name."""
    events = traced_serving
    outers = {e["id"]: e for e in events if e["name"] == outer}
    assert len(outers) >= 6
    kids = [e for e in events if e["parent"] in outers]
    assert {e["name"] for e in kids} == children
    covered = 0.0
    for e in kids:
        o = outers[e["parent"]]
        assert o["ts"] <= e["ts"] and \
            e["ts"] + e["dur"] <= o["ts"] + o["dur"]
        covered += e["dur"]
    assert covered >= 0.9 * sum(o["dur"] for o in outers.values())
