"""Core-local SGNS tables, on the CPU.

``train_sgns`` trains only the corpus's support, the tokens of its real
walks, in tables of a multiple of ``LOCAL_BUCKET`` rows. Here the corpus is
CoreWalk's on a k-core that leaves out most nodes, node 0 among them, and
the core-local steps are held to the full-table steps under the same keys.

The one draw where the two differ is ``u == 0.0`` exactly (about 2⁻²³ per
negative): ``searchsorted`` on the full cdf then returns node 0, whatever
its count, and on the restricted cdf the first support node, which is what
unigram^0.75 sampling asks for. ``test_a_zero_draw_takes_the_first_support
_node`` states it; no draw of these tests is 0.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.corewalk import corewalk_plan, plan_from_counts
from repro.core.kcore import core_numbers_host, degeneracy, kcore_subgraph
from repro.graph import generators
from repro.obs import trace as obs
from repro.serve import (DynamicGraph, EmbeddingService, EmbeddingStore,
                         IncrementalCore, RetrainConfig, Retrainer)
from repro.skipgram import trainer
from repro.skipgram.corpus import build_corpus, sample_batch
from repro.skipgram.model import init_params
from repro.skipgram.trainer import LOCAL_BUCKET, SGNSConfig, train_sgns

N, K0, DIM, STEPS = 700, 10, 16, 8
CFG = SGNSConfig(dim=DIM, window=3, n_neg=5, batch=256, lr=0.025, seed=4,
                 impl="ref")


@pytest.fixture(scope="module")
def corpus():
    g = generators.barabasi_albert_varying(N, 5.0, seed=0)
    core = core_numbers_host(g)
    budgets = np.where(core >= K0, corewalk_plan(core, 4).per_node, 0)
    # padding walks are rooted at node 0, off the support
    plan = plan_from_counts(budgets, 256)
    sub = kcore_subgraph(g, core, K0)
    c = build_corpus(sub.to_ell(), plan, 12, jax.random.PRNGKey(3))
    support = np.flatnonzero(c.counts)
    assert plan.n_real < c.walks.shape[0]
    assert 0 not in support and 0 < len(support) <= LOCAL_BUCKET < N
    return c, support


def _warm():
    return init_params(N, DIM, jax.random.PRNGKey(1))


def _full_tables(corpus, steps):
    """The steps over the whole tables."""
    ktrain = jax.random.split(jax.random.PRNGKey(CFG.seed))[1]
    params = _warm()
    opt = trainer._adam(CFG.lr).init(params)
    for s in range(steps):
        params, opt, _ = trainer.sgns_step(
            params, opt, corpus, jax.random.fold_in(ktrain, s), CFG)
    return params, opt


def _core_local(corpus):
    res = train_sgns(corpus, CFG, params=_warm(), steps=STEPS)
    assert res.rows == LOCAL_BUCKET
    return res


def test_core_local_steps_with_the_scatter_are_the_full_tables(corpus):
    """The core-local steps give the full tables' result bit for bit:
    support rows trained alike, the warm table outside the support, Adam's
    moments zero there, the same step count, full shapes."""
    c, support = corpus
    want_p, want_o = _full_tables(c, STEPS)
    res = _core_local(c)
    for a, b in zip(jax.tree.leaves((want_p, want_o)),
                    jax.tree.leaves((res.params, res.opt_state))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    off = np.setdiff1d(np.arange(N), support)
    warm = np.asarray(_warm()["emb_in"])
    got = {"params": res.params, "mu": res.opt_state[0].mu,
           "nu": res.opt_state[0].nu}
    for group, leaves in got.items():
        for k, v in leaves.items():
            v = np.asarray(v)
            assert v.shape == (N, DIM)  # the record stays whole
            if group == "params" and k == "emb_in":
                np.testing.assert_array_equal(v[off], warm[off])
            else:
                assert not np.any(v[off])  # emb_out, mu, nu: zero
    assert np.abs(np.asarray(res.params["emb_in"])[support]
                  - warm[support]).max() > 1e-3  # the steps moved it
    np.testing.assert_array_equal(res.embeddings,
                                  np.asarray(res.params["emb_in"]))
    assert int(res.opt_state[0].count) == int(want_o[0].count) == STEPS
    assert int(res.opt_state[2]) == int(want_o[2]) == STEPS


def _local_corpus(c):
    idx, g2l = trainer._local_ids(c)
    _, walks, cdf = trainer._to_local(_warm(), c.walks, c.noise_cdf,
                                      jnp.asarray(idx), jnp.asarray(g2l))
    return idx, walks, cdf


def test_core_local_samples_are_the_full_samples(corpus):
    c, support = corpus
    idx, walks, cdf = _local_corpus(c)
    np.testing.assert_array_equal(idx[: len(support)], support)
    assert np.all(idx[len(support):] == N)  # padding rows
    local = trainer.dataclasses.replace(c, walks=walks, noise_cdf=cdf,
                                        n_nodes=len(idx), counts=None)
    for s in range(20):
        key = jax.random.PRNGKey(100 + s)
        full = sample_batch(c, key, batch=512, window=3, n_neg=5)
        loc = sample_batch(local, key, batch=512, window=3, n_neg=5)
        for f, l in zip(full, loc):
            np.testing.assert_array_equal(idx[np.asarray(l)], np.asarray(f))


def test_a_zero_draw_takes_the_first_support_node(corpus):
    c, support = corpus
    idx, _, cdf = _local_corpus(c)
    # node 0 has no count, yet the full cdf returns it for u == 0.0
    assert int(jnp.searchsorted(c.noise_cdf, 0.0)) == 0 != support[0]
    assert idx[int(jnp.searchsorted(cdf, 0.0))] == support[0]
    # any u > 0 lands on the same node both ways
    u = jnp.asarray([1e-30, 1e-7, 0.5, 1.0 - 2 ** -24], jnp.float32)
    np.testing.assert_array_equal(
        idx[np.asarray(jnp.searchsorted(cdf, u))],
        np.asarray(jnp.searchsorted(c.noise_cdf, u)))


def _service(n=N):
    g = generators.barabasi_albert_varying(n, 5.0, seed=0)
    dyn = DynamicGraph(g.n_nodes, g.edge_list(), width=16)
    inc = IncrementalCore(dyn)
    store = EmbeddingStore(capacity=dyn.node_cap, dim=DIM,
                           node_cap=dyn.node_cap)
    emb = np.random.default_rng(0).normal(size=(g.n_nodes, DIM)).astype(
        np.float32)
    served = np.where(g.degrees() > 0)[0]
    store.put_many(served, emb[served], inc.core[served])
    svc = EmbeddingService(dyn, inc, store, batch=16,
                           k0=max(2, degeneracy(inc.core) // 2))
    inc.mark_refresh()
    svc.set_retrainer(Retrainer(svc, RetrainConfig(
        n_walks=4, walk_length=12, min_sgns_steps=5, prop_iters=4,
        swap_chunk=256, sgns=SGNSConfig(dim=DIM, window=3, batch=64,
                                        impl="ref", seed=5), seed=7)))
    return svc


def test_a_refresh_trains_core_local_tables_and_says_so():
    """The ``retrain.train`` span carries the rows the steps trained; a
    warm refresh on a churned graph compiles
    nothing, the core-local tables keeping their bucket's shape."""
    svc = _service()
    svc.maybe_retrain(force=True)
    prev = obs.tracer()
    t = obs.enable(exemplar_watch=())
    compiles = []

    def listen(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(kw.get("fun_name"))

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        rng = np.random.default_rng(1)
        e = rng.integers(0, N, size=(40, 2))
        svc.ingest_block(e[e[:, 0] != e[:, 1]])
        svc.sync()
        compiles.clear()
        assert svc.maybe_retrain(force=True) is not None
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
        obs.set_tracer(prev)
    train = [e for e in t.events if e["name"] == "retrain.train"]
    assert len(train) == 1
    assert train[0]["attrs"]["rows"] == LOCAL_BUCKET < N
    rec = svc.retrainer.last
    assert rec.sgns.params["emb_in"].shape == (N, DIM)
    assert rec.corpus.walks.max() < N  # the record keeps global ids
    assert compiles == []
