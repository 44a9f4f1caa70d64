"""Retraining subsystem: planner, Procrustes aligner, rollout, full loop."""
import numpy as np
import pytest

from repro.core.kcore import core_numbers_host, degeneracy
from repro.graph import generators
from repro.serve import (
    DynamicGraph,
    EmbeddingAligner,
    EmbeddingService,
    EmbeddingStore,
    IncrementalCore,
    RetrainConfig,
    RetrainPlanner,
    Retrainer,
    VersionRollout,
    procrustes_rotation,
)
from repro.skipgram.trainer import SGNSConfig

DIM = 12


def _random_rotation(dim, rng):
    q, r = np.linalg.qr(rng.normal(size=(dim, dim)))
    return (q * np.sign(np.diag(r))).astype(np.float32)


def _service(n=120, seed=0, k0=None, **kw):
    g = generators.barabasi_albert_varying(n, 4.0, seed=seed)
    dyn = DynamicGraph(g.n_nodes, g.edge_list(), width=16)
    inc = IncrementalCore(dyn)
    store = EmbeddingStore(capacity=dyn.node_cap, dim=DIM,
                           node_cap=dyn.node_cap)
    rng = np.random.default_rng(seed)
    emb = rng.normal(size=(g.n_nodes, DIM)).astype(np.float32)
    served = np.where(g.degrees() > 0)[0]
    store.put_many(served, emb[served], inc.core[served])
    if k0 is None:
        k0 = max(2, degeneracy(inc.core) // 2)
    svc = EmbeddingService(dyn, inc, store, batch=16, k0=k0, **kw)
    inc.mark_refresh()
    return svc, g, emb


def _tiny_cfg(**kw):
    kw.setdefault("n_walks", 3)
    kw.setdefault("walk_length", 8)
    kw.setdefault("min_sgns_steps", 5)
    kw.setdefault("prop_iters", 4)
    kw.setdefault("sgns", SGNSConfig(dim=DIM, epochs=0.05, impl="ref"))
    return RetrainConfig(**kw)


def _force_drift(svc, n_wire=8):
    """Wire low-core nodes into a dense pocket to flip k0-core membership."""
    core = svc.cores.core
    low = np.argsort(core)[:n_wire]
    assert (core[low] < svc.k0).any()
    edges = [(int(low[i]), int(low[j]))
             for i in range(n_wire) for j in range(i + 1, n_wire)]
    svc.ingest_block(np.asarray(edges, np.int64))
    svc.sync()  # land the pipelined repair + deferred auto-retrain tail


# ------------------------------------------------------------- procrustes


def test_procrustes_recovers_a_planted_rotation():
    rng = np.random.default_rng(0)
    X = rng.normal(size=(40, DIM)).astype(np.float32)
    R0 = _random_rotation(DIM, rng)
    R = procrustes_rotation(X, X @ R0)
    np.testing.assert_allclose(R, R0, atol=1e-4)
    np.testing.assert_allclose(R @ R.T, np.eye(DIM), atol=1e-5)


def test_procrustes_is_orthogonal_even_for_unrelated_clouds():
    rng = np.random.default_rng(1)
    X = rng.normal(size=(25, DIM)).astype(np.float32)
    Y = rng.normal(size=(25, DIM)).astype(np.float32)
    R = procrustes_rotation(X, Y)
    np.testing.assert_allclose(R @ R.T, np.eye(DIM), atol=1e-5)
    # applying R preserves norms and pairwise dot products of ANY table
    A = rng.normal(size=(30, DIM)).astype(np.float32)
    np.testing.assert_allclose(
        np.linalg.norm(A @ R, axis=1), np.linalg.norm(A, axis=1), rtol=1e-4
    )
    np.testing.assert_allclose((A @ R) @ (A @ R).T, A @ A.T, atol=1e-3)


def test_aligner_identity_below_min_anchors():
    # a rotation in DIM dimensions needs DIM anchors: one fewer is identity
    rng = np.random.default_rng(2)
    emb = rng.normal(size=(2 * DIM, DIM)).astype(np.float32)
    aligner = EmbeddingAligner()
    for n in (3, DIM - 1):
        out, rep = aligner.align(emb, emb[:n] + 1.0, np.arange(n))
        assert not rep["aligned"] and rep["anchors"] == n
        np.testing.assert_array_equal(out, emb)
    out, rep = aligner.align(emb, emb[:DIM] + 1.0, np.arange(DIM))
    assert rep["aligned"] and rep["anchors"] == DIM


def test_aligner_maps_back_into_old_space():
    rng = np.random.default_rng(3)
    old = rng.normal(size=(50, DIM)).astype(np.float32)
    R0 = _random_rotation(DIM, rng)
    new = old @ R0.T  # the fresh run landed in a rotated copy of the space
    aligner = EmbeddingAligner()
    anchors = np.arange(0, 50, 2)
    out, rep = aligner.align(new, old[anchors], anchors)
    assert rep["aligned"] and rep["residual"] < 1e-4
    np.testing.assert_allclose(out, old, atol=1e-3)


# ---------------------------------------------------------------- planner


def test_planner_snapshots_exact_drifted_core():
    svc, _, _ = _service(seed=4)
    _force_drift(svc)
    plan = RetrainPlanner(svc.graph, svc.cores, svc.k0).plan()
    oracle = core_numbers_host(plan.snapshot)
    np.testing.assert_array_equal(plan.core, oracle)
    np.testing.assert_array_equal(plan.nodes, np.where(oracle >= plan.k0)[0])
    assert plan.drifted > 0  # the pocket flipped membership
    # the subgraph is induced on the k0-core with original ids
    in_core = oracle >= plan.k0
    deg_sub = plan.sub.degrees()
    assert (deg_sub[~in_core] == 0).all()
    assert deg_sub[in_core].min() >= plan.k0


def test_planner_clamps_k0_to_current_degeneracy():
    svc, _, _ = _service(seed=5)
    kdeg = degeneracy(svc.cores.core)
    plan = RetrainPlanner(svc.graph, svc.cores, kdeg + 10).plan()
    assert plan.k0 == kdeg
    assert len(plan.nodes) > 0


# ---------------------------------------------------------------- rollout


def test_rollout_chunked_swap_interleaves_and_tags_versions():
    store = EmbeddingStore(capacity=16, dim=DIM, node_cap=32)
    rng = np.random.default_rng(6)
    old = rng.normal(size=(8, DIM)).astype(np.float32)
    store.put_many(np.arange(8), old, np.ones(8))
    assert store.version_counts() == {0: 8}

    new = rng.normal(size=(6, DIM)).astype(np.float32)
    rollout = VersionRollout(store, chunk=2)
    rollout.stage(np.arange(6), new, np.full(6, 2))
    calls = []
    rep = rollout.commit(between=lambda: calls.append(store.version))
    assert rep["version"] == 1 and rep["rows"] == 6 and rep["chunks"] == 3
    assert len(calls) == 3  # serving yielded between every chunk
    # per-node version reconciliation: swapped rows new, the rest old
    assert store.version_counts() == {0: 2, 1: 6}
    vecs, found = store.gather(np.arange(8))
    assert found.all()
    np.testing.assert_allclose(np.asarray(vecs)[:6], new, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(vecs)[6:], old[6:], rtol=1e-6)


def test_rollout_requires_staging():
    store = EmbeddingStore(capacity=4, dim=DIM, node_cap=8)
    with pytest.raises(RuntimeError):
        VersionRollout(store).commit()


# ------------------------------------------------------------- full loop


def test_maybe_retrain_gates_on_threshold_and_budget():
    svc, _, _ = _service(seed=7, retrain_threshold=0.9)
    svc.set_retrainer(Retrainer(svc, _tiny_cfg()), budget=1)
    assert svc.maybe_retrain() is None  # pressure 0 < 0.9
    assert svc.maybe_retrain(force=True) is not None
    assert svc.stats.retrains == 1
    assert svc.maybe_retrain(force=True) is None  # budget spent
    assert svc.stats.retrains == 1


@pytest.mark.slow
def test_drift_triggered_retrain_hot_swap_end_to_end():
    """The CI smoke: forced drift -> auto retrain -> aligned hot swap, with
    cores oracle-exact and staleness back to ~0 afterwards."""
    svc, _, _ = _service(seed=8, retrain_threshold=0.02)
    svc.set_retrainer(Retrainer(svc, _tiny_cfg()), auto=True, budget=1)
    v0 = svc.store.version
    _force_drift(svc)  # auto mode retrains inside ingest_block
    assert svc.stats.retrains == 1
    assert svc.stats.last_swap_version == svc.store.version == v0 + 1
    rep_pressure = svc.retrain_pressure()
    assert rep_pressure < svc.retrain_threshold  # baseline was reset
    assert svc.store.staleness(svc.cores.core) == 0.0
    assert svc.cores.resync() == 0  # maintained cores still oracle-exact
    out = svc.embed(list(range(20)))
    assert np.isfinite(out).all()
    # swapped rows carry the new version; spill/untouched rows may keep old
    counts = svc.store.version_counts()
    assert counts.get(v0 + 1, 0) > 0


def test_retrain_warm_start_and_anchor_accounting():
    svc, _, _ = _service(seed=9)
    _force_drift(svc)
    rep = Retrainer(svc, _tiny_cfg()).run()
    assert rep is not None
    assert rep.core_size == len(np.where(svc.cores.core >= rep.k0)[0])
    assert rep.warm_rows > 0  # persisted nodes seeded emb_in
    assert rep.anchors >= DIM and rep.aligned
    assert rep.rows_swapped >= rep.core_size  # propagation covers shells
    assert rep.staleness_after == 0.0
    assert rep.times["total"] > 0


def test_retrain_without_alignment_or_propagation():
    svc, _, _ = _service(seed=10)
    _force_drift(svc)
    cfg = _tiny_cfg(align=False, propagate=False)
    rep = Retrainer(svc, cfg).run()
    assert rep is not None and not rep.aligned
    assert rep.rows_swapped == rep.core_size  # only the subcore was written
