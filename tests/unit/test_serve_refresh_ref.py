"""One service refresh against the plain float64 reference
(``repro.serve.refresh_ref``) at a small seeded size on the CPU, with the
SGNS kernel in interpret mode; the refresh's spans; its steady shapes."""
import jax
import numpy as np
import pytest

from repro.core.kcore import core_numbers_host, degeneracy
from repro.graph import generators
from repro.serve import (DynamicGraph, EmbeddingService, EmbeddingStore,
                         IncrementalCore, RetrainConfig, Retrainer)
from repro.serve import refresh_ref as ref
from repro.serve.retrain import WALK_BUCKET
from repro.skipgram.corpus import sample_batch
from repro.skipgram.trainer import SGNSConfig, sgns_step

DIM = 16


def _service(seed=3, n=160):
    g = generators.barabasi_albert_varying(n, 5.0, seed=seed)
    dyn = DynamicGraph(g.n_nodes, g.edge_list(), width=16)
    inc = IncrementalCore(dyn)
    store = EmbeddingStore(capacity=dyn.node_cap, dim=DIM,
                           node_cap=dyn.node_cap)
    rng = np.random.default_rng(seed)
    emb = (rng.normal(size=(g.n_nodes, DIM)) / np.sqrt(DIM)).astype(
        np.float32)
    served = np.where(g.degrees() > 0)[0]
    store.put_many(served, emb[served], inc.core[served])
    k0 = max(2, degeneracy(inc.core) // 2)
    svc = EmbeddingService(dyn, inc, store, batch=16, k0=k0)
    inc.mark_refresh()
    return svc


def _cfg(impl="pallas_interpret", **kw):
    return RetrainConfig(
        n_walks=10, walk_length=12, min_sgns_steps=5, prop_iters=20,
        sgns=SGNSConfig(dim=DIM, window=3, n_neg=5, batch=64, epochs=1.0,
                        impl=impl, seed=5), seed=7, **kw)


def _churn(svc, seed):
    """A block of new edges and one of retractions, landed."""
    rng = np.random.default_rng(seed)
    n = svc.graph.n_nodes
    e = rng.integers(0, n, size=(40, 2))
    svc.ingest_block(e[e[:, 0] != e[:, 1]])
    svc.retract_block(svc.graph.snapshot().edge_list()[::37])
    svc.sync()


@pytest.fixture(scope="module")
def refreshed():
    svc = _service()
    rt = Retrainer(svc, _cfg())
    svc.set_retrainer(rt)
    _churn(svc, 1)
    assert svc.maybe_retrain(force=True) is not None
    return svc, rt.last


def test_walks_follow_core_edges_with_corewalk_budgets(refreshed):
    svc, rec = refreshed
    snap = rec.plan.snapshot
    core = core_numbers_host(snap)
    np.testing.assert_array_equal(core, rec.plan.core)
    walks = np.asarray(rec.corpus.walks)
    assert walks.shape[0] % WALK_BUCKET == 0  # padded to the bucket
    real = walks[: rec.walk_plan.n_real]
    assert ref.walk_faults(real, snap.n_nodes, snap.edge_list(), core,
                           rec.plan.k0, 10) == 0
    np.testing.assert_array_equal(
        rec.walk_plan.per_node, ref.corewalk_budgets(core, rec.plan.k0, 10))
    # a walk read backwards from its second step is not rooted right
    assert ref.walk_faults(real[:, 1::-1], snap.n_nodes, snap.edge_list(),
                           core, rec.plan.k0, 10) > 0


def test_one_sgns_step_matches_the_reference(refreshed):
    _, rec = refreshed
    cfg = _cfg().sgns
    key = jax.random.PRNGKey(11)
    ids = [np.asarray(a) for a in sample_batch(
        rec.corpus, key, batch=cfg.batch, window=cfg.window,
        n_neg=cfg.n_neg)]
    p0 = {k: np.asarray(v) for k, v in rec.sgns.params.items()}
    adam = rec.sgns.opt_state[0]
    params = jax.tree.map(jax.numpy.copy, rec.sgns.params)
    state = jax.tree.map(jax.numpy.copy, rec.sgns.opt_state)
    got, new_state, loss = sgns_step(params, state, rec.corpus, key, cfg)
    want_loss, want, mu, nu = ref.sgns_step(
        p0["emb_in"], p0["emb_out"],
        {k: np.asarray(v) for k, v in adam.mu.items()},
        {k: np.asarray(v) for k, v in adam.nu.items()},
        int(adam.count), *ids, lr=cfg.lr)
    assert float(loss) == pytest.approx(want_loss, rel=1e-5)
    assert want_loss == pytest.approx(ref.sgns_loss(
        p0["emb_in"], p0["emb_out"], *ids))
    for k in want:
        np.testing.assert_allclose(np.asarray(got[k]), want[k], atol=1e-5)
        np.testing.assert_allclose(np.asarray(new_state[0].mu[k]), mu[k],
                                   atol=1e-6)
        assert np.abs(want[k] - p0[k]).max() > 1e-4  # the step moved it


def test_trained_aligned_propagated_and_stored(refreshed):
    svc, rec = refreshed
    snap, core, k0 = rec.plan.snapshot, rec.plan.core, rec.plan.k0
    in_core = core >= k0
    trained = rec.sgns.embeddings
    # every k0-core row moved off its warm start
    assert not np.any(np.all(trained == rec.warm, axis=1) & in_core)
    stored, found, _, _ = svc.store.peek_many(np.arange(snap.n_nodes))
    nodes, vecs, _ = rec.staged
    # the store holds what the refresh staged, bit for bit
    assert found[nodes].all()
    np.testing.assert_array_equal(stored[nodes], vecs)
    # the core rows are the reference's rotation of the trained table
    assert len(rec.anchors) >= DIM
    aligned = ref.align(trained, rec.anchors, rec.anchor_vecs)
    np.testing.assert_allclose(stored[in_core], aligned[in_core], atol=1e-5)
    # the shells are the reference's propagation of the stored core rows
    prop = ref.propagate(snap.n_nodes, snap.edge_list(), core, k0,
                         np.where(in_core[:, None], stored, 0.0), 20)
    shell = np.zeros(snap.n_nodes, bool)
    shell[nodes] = True
    shell &= ~in_core
    assert shell.sum() > 0
    np.testing.assert_allclose(stored[shell], prop[shell], atol=1e-5)


def test_reference_alignment_leaves_too_few_anchors_alone():
    rng = np.random.default_rng(0)
    t = rng.normal(size=(30, 6))
    np.testing.assert_array_equal(ref.align(t, np.arange(5), t[:5] + 1), t)
    q, _ = np.linalg.qr(rng.normal(size=(6, 6)))
    np.testing.assert_allclose(ref.align(t, np.arange(6), t[:6] @ q),
                               t @ q, atol=1e-9)


def test_refresh_spans_partition_serve_retrain():
    from repro.obs import trace as obs

    svc = _service(seed=4)
    svc.set_retrainer(Retrainer(svc, _cfg(impl="ref")))
    svc.maybe_retrain(force=True)  # compiles the refresh's shapes first
    prev = obs.tracer()
    t = obs.enable(exemplar_watch=())
    try:
        for i in range(3):
            _churn(svc, 10 + i)
            svc.maybe_retrain(force=True)
    finally:
        obs.set_tracer(prev)
    ev = t.events
    outers = {e["id"]: e for e in ev if e["name"] == "serve.retrain"}
    assert len(outers) == 3
    kids = [e for e in ev if e["parent"] in outers]
    assert {e["name"] for e in kids} == {
        "retrain.rollback_copy", "retrain.plan", "retrain.walks",
        "retrain.train", "retrain.align", "retrain.propagate",
        "retrain.swap"}
    for e in kids:
        o = outers[e["parent"]]
        assert o["ts"] <= e["ts"] and e["ts"] + e["dur"] <= o["ts"] + o["dur"]
    assert sum(e["dur"] for e in kids) >= 0.9 * sum(
        o["dur"] for o in outers.values())
    for e in kids:
        a = e["attrs"]
        if e["name"] == "retrain.walks":
            assert a["walks"] > 0 and a["tokens"] == a["walks"] * 12
        if e["name"] == "retrain.train":
            assert a["steps"] >= 5 and a["pairs"] > 0
            inner = [x for x in ev if x["parent"] == e["id"]]
            assert {x["name"] for x in inner} == {"sgns.steps", "sgns.wait"}


def test_a_warm_refresh_compiles_nothing():
    """After one refresh, the next ones, on a graph that churned, reuse
    every program: the corpus is padded to the walk bucket, the subcore's
    table is a power of two wide, the swap's scatters are chunk-sized."""
    svc = _service(seed=5)
    svc.set_retrainer(Retrainer(svc, _cfg(impl="ref", swap_chunk=64)))
    svc.maybe_retrain(force=True)
    compiles = []
    refreshing = [False]

    def listen(event, duration, **kw):
        if refreshing[0] and \
                event == "/jax/core/compile/backend_compile_duration":
            compiles.append(kw.get("fun_name"))

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        for i in range(2):
            _churn(svc, 20 + i)
            refreshing[0] = True
            svc.maybe_retrain(force=True)
            refreshing[0] = False
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert compiles == []
