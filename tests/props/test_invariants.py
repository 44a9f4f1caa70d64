"""Hypothesis property tests on the system's graph/degeneracy invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # optional dev dependency (pip extra: dev)
from hypothesis import given, settings, strategies as st

from repro.core import corewalk, kcore
from repro.graph.csr import Graph
from repro.kernels import ops, ref
from repro.serve import DynamicGraph, EmbeddingStore, IncrementalCore, ShardPlan
from repro.walks.engine import random_walks


@st.composite
def graphs(draw, max_nodes=40):
    n = draw(st.integers(5, max_nodes))
    n_edges = draw(st.integers(n - 1, min(3 * n, n * (n - 1) // 2)))
    seed = draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    edges = set()
    # spanning chain ensures no isolated nodes
    perm = rng.permutation(n)
    for a, b in zip(perm[:-1], perm[1:]):
        edges.add((min(a, b), max(a, b)))
    while len(edges) < n_edges:
        u, v = rng.integers(0, n, size=2)
        if u != v:
            edges.add((min(u, v), max(u, v)))
    return Graph.from_edges(n, np.array(sorted(edges)))


@given(graphs())
@settings(max_examples=40, deadline=None)
def test_core_number_invariants(g):
    core = kcore.core_numbers_host(g)
    deg = g.degrees()
    # 1. core number is at most the degree
    assert np.all(core <= deg)
    # 2. k-core has min degree >= k inside itself, for every k
    for k in range(1, kcore.degeneracy(core) + 1):
        sub = kcore.kcore_subgraph(g, core, k)
        members = core >= k
        if members.any():
            assert sub.degrees()[members].min() >= k
    # 3. degeneracy bounds: <= max degree
    assert kcore.degeneracy(core) <= deg.max()


@given(graphs(max_nodes=30))
@settings(max_examples=25, deadline=None)
def test_jax_core_equals_host_core(g):
    host = kcore.core_numbers_host(g)
    dev = np.asarray(kcore.core_numbers_jax(g.to_ell()))
    np.testing.assert_array_equal(host, dev)


@given(
    graphs(max_nodes=35),
    st.integers(1, 48),  # insert block size
    st.integers(0, 2**31 - 1),
)
@settings(max_examples=20, deadline=None)
def test_block_repair_and_deletion_match_peeling(g, block_size, seed):
    """``on_edge_block`` / ``on_remove`` agree exactly with Matula–Beck
    peeling on random insert/delete interleavings, across compaction
    boundaries."""
    rng = np.random.default_rng(seed)
    edges = g.edge_list()
    edges = edges[rng.permutation(len(edges))]
    dyn = DynamicGraph(g.n_nodes, width=2)  # tiny width: overflow + compaction
    inc = IncrementalCore(dyn)
    live: list = []
    step = 0
    for start in range(0, len(edges), block_size):
        step += 1
        accepted = dyn.add_edges(edges[start : start + block_size])
        inc.on_edge_block(accepted)
        live.extend(map(tuple, accepted))
        if step % 2 == 0 and len(live) > 4:
            k = int(rng.integers(1, max(len(live) // 3, 2)))
            pick = rng.choice(len(live), size=k, replace=False)
            removed = dyn.remove_edges(np.array([live[i] for i in pick]))
            inc.on_remove(removed)
            gone = {tuple(e) for e in removed}
            live = [e for e in live if e not in gone]
        if step % 3 == 0:
            dyn.compact()  # double-buffered swap must not disturb repair
        oracle = kcore.core_numbers_host(dyn.snapshot())
        np.testing.assert_array_equal(inc.core, oracle)
    assert inc.resync() == 0


@given(
    graphs(max_nodes=30),
    st.integers(1, 40),  # insert block size
    st.integers(0, 2**31 - 1),
)
@settings(max_examples=15, deadline=None)
def test_device_region_matches_host_bfs(g, block_size, seed):
    """Frontier-masked region growing (the jitted device ELL traversal with
    the removed-edge/overflow side table) returns exactly the host BFS
    ``_region`` node set on random graphs under mixed insert/delete blocks
    with compaction boundaries."""
    rng = np.random.default_rng(seed)
    edges = g.edge_list()
    edges = edges[rng.permutation(len(edges))]
    dyn = DynamicGraph(g.n_nodes, width=2)  # tiny width: overflow side arcs
    inc = IncrementalCore(dyn)
    live: list = []
    step = 0
    for start in range(0, len(edges), block_size):
        step += 1
        added = dyn.add_edges(edges[start : start + block_size])
        inc.on_edge_block(added)
        live.extend(map(tuple, added))
        removed = np.zeros((0, 2), np.int64)
        if step % 2 == 0 and len(live) > 4:
            k = int(rng.integers(1, max(len(live) // 3, 2)))
            pick = rng.choice(len(live), size=k, replace=False)
            removed = dyn.remove_edges(np.array([live[i] for i in pick]))
            inc.on_remove(removed)
            gone = {tuple(e) for e in removed}
            live = [e for e in live if e not in gone]
        if step % 3 == 0:
            dyn.compact()
        touched = np.concatenate([added, removed]) if len(removed) else added
        if not len(touched):
            continue
        core = inc.core
        k_edge = np.minimum(core[touched[:, 0]], core[touched[:, 1]])
        lo = max(0, int(k_edge.min()) - 2)
        hi = int(k_edge.max()) + 2
        ends = np.unique(touched.reshape(-1))
        want = np.asarray(inc._region(ends, lo, hi, removed), np.int64)
        ov_src, ov_dst = dyn.overflow_arc_arrays()
        side_src = np.concatenate([ov_src, removed[:, 0], removed[:, 1]])
        side_dst = np.concatenate([ov_dst, removed[:, 1], removed[:, 0]])
        got = inc._region_device(ends, lo, hi, side_src, side_dst)
        np.testing.assert_array_equal(got, want)


@given(
    graphs(max_nodes=30),
    st.sampled_from([1, 2, 4, 8]),  # shard counts
    st.integers(1, 32),  # insert block size
    st.integers(0, 2**31 - 1),
)
@settings(max_examples=10, deadline=None)
def test_sharded_repair_and_store_are_shard_count_invariant(
    g, n_shards, block_size, seed
):
    """Row-sharding is placement-only: for any shard count, sharded core
    numbers equal the peeling oracle on random mixed insert/delete blocks,
    and the store's staleness / version histogram / eviction count are
    identical to the single-device run of the same seeded op stream."""
    if jax.device_count() < n_shards:
        pytest.skip(f"needs {n_shards} devices")
    plan = ShardPlan.build(n_shards)
    rng = np.random.default_rng(seed)
    edges = g.edge_list()
    edges = edges[rng.permutation(len(edges))]
    dyn = DynamicGraph(g.n_nodes, width=2, plan=plan)  # tiny width: overflow
    inc = IncrementalCore(dyn)
    ref_store = EmbeddingStore(capacity=8, dim=4, node_cap=g.n_nodes)
    sh_store = EmbeddingStore(capacity=8, dim=4, node_cap=g.n_nodes, plan=plan)
    live: list = []
    step = 0
    for start in range(0, len(edges), block_size):
        step += 1
        accepted = dyn.add_edges(edges[start : start + block_size])
        inc.on_edge_block(accepted)
        live.extend(map(tuple, accepted))
        if step % 2 == 0 and len(live) > 4:
            k = int(rng.integers(1, max(len(live) // 3, 2)))
            pick = rng.choice(len(live), size=k, replace=False)
            removed = dyn.remove_edges(np.array([live[i] for i in pick]))
            inc.on_remove(removed)
            gone = {tuple(e) for e in removed}
            live = [e for e in live if e not in gone]
        if step % 3 == 0:
            dyn.compact()
        oracle = kcore.core_numbers_host(dyn.snapshot())
        np.testing.assert_array_equal(inc.core, oracle)
        # same store ops against both placements
        nodes = rng.integers(0, g.n_nodes, size=3)
        vecs = rng.normal(size=(3, 4)).astype(np.float32)
        cores_w = oracle[nodes]
        ref_store.put_many(nodes, vecs, cores_w)
        sh_store.put_many(nodes, vecs, cores_w)
        q = rng.integers(0, g.n_nodes, size=4)
        vr, fr = ref_store.gather(q)
        vs, fs = sh_store.gather(q)
        np.testing.assert_array_equal(fr, fs)
        np.testing.assert_array_equal(np.asarray(vr), np.asarray(vs))
    assert inc.resync() == 0
    assert ref_store.evictions == sh_store.evictions
    assert ref_store.version_counts() == sh_store.version_counts()
    assert ref_store.staleness(inc.core) == sh_store.staleness(inc.core)


@given(
    graphs(max_nodes=35),
    st.sampled_from([None, "descend", "repeel"]),
    st.integers(1, 40),  # insert block size
    st.integers(0, 2**31 - 1),
)
@settings(max_examples=15, deadline=None)
def test_repair_policy_is_cost_only(forced_policy, g, path, block_size, seed):
    """The repair policy decides *which* exact path runs, never the result:
    the measured crossover, always-descend and always-re-peel each match the
    peeling oracle on random mixed insert/delete streams — and the pipelined
    begin/finish split matches the synchronous entry point."""
    rng = np.random.default_rng(seed)
    edges = g.edge_list()
    edges = edges[rng.permutation(len(edges))]
    dyn = DynamicGraph(g.n_nodes, width=2)  # tiny width: overflow side arcs
    inc = IncrementalCore(dyn, policy=forced_policy(path))
    live: list = []
    step = 0
    for start in range(0, len(edges), block_size):
        step += 1
        accepted = dyn.add_edges(edges[start : start + block_size])
        if step % 2:
            inc.on_edge_block(accepted)
        else:  # pipelined split: overlapped begin/finish must commit the same
            inc.finish_update(inc.begin_update(added=accepted))
        live.extend(map(tuple, accepted))
        if step % 2 == 0 and len(live) > 4:
            k = int(rng.integers(1, max(len(live) // 3, 2)))
            pick = rng.choice(len(live), size=k, replace=False)
            removed = dyn.remove_edges(np.array([live[i] for i in pick]))
            inc.on_remove(removed)
            gone = {tuple(e) for e in removed}
            live = [e for e in live if e not in gone]
        oracle = kcore.core_numbers_host(dyn.snapshot())
        np.testing.assert_array_equal(inc.core, oracle)
    assert inc.resync() == 0


@given(graphs(max_nodes=30), st.integers(2, 10), st.integers(0, 2**31 - 1))
@settings(max_examples=20, deadline=None)
def test_walks_follow_edges(g, length, seed):
    ell = g.to_ell()
    roots = jnp.arange(g.n_nodes, dtype=jnp.int32)
    walks = np.asarray(random_walks(ell, roots, length, jax.random.PRNGKey(seed)))
    assert walks.shape == (g.n_nodes, length)
    for w in walks:
        for a, b in zip(w[:-1], w[1:]):
            assert a == b or g.has_edge(int(a), int(b))


@given(graphs(max_nodes=30), st.integers(1, 20))
@settings(max_examples=25, deadline=None)
def test_corewalk_budget_bounds(g, n):
    core = kcore.core_numbers_host(g)
    plan = corewalk.corewalk_plan(core, n)
    # Eq.13 bounds: 1 <= n_v <= n; degeneracy nodes get exactly n
    assert plan.per_node.min() >= 1
    assert plan.per_node.max() <= max(n, 1)
    kdeg = kcore.degeneracy(core)
    assert np.all(plan.per_node[core == kdeg] == max(n, 1))
    # monotone in core index
    order = np.argsort(core)
    assert np.all(np.diff(plan.per_node[order]) >= 0)


@given(
    st.integers(1, 12),  # N
    st.integers(1, 6),  # L
    st.integers(2, 10),  # M
    st.integers(1, 40),  # D
    st.integers(0, 2**31 - 1),
)
@settings(max_examples=30, deadline=None)
def test_ell_mean_ref_matches_manual(N, L, M, D, seed):
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, M, size=(N, L)).astype(np.int32)
    valid = rng.random((N, L)) < 0.6
    emb = rng.standard_normal((M, D)).astype(np.float32)
    got = np.asarray(ref.ell_mean_ref(jnp.asarray(idx), jnp.asarray(valid), jnp.asarray(emb)))
    for i in range(N):
        rows = idx[i][valid[i]]
        want = emb[rows].mean(axis=0) if len(rows) else np.zeros(D, np.float32)
        np.testing.assert_allclose(got[i], want, rtol=1e-5, atol=1e-6)


@given(st.integers(1, 16), st.integers(1, 8), st.integers(0, 2**31 - 1))
@settings(max_examples=25, deadline=None)
def test_sgns_loss_positive_and_monotone_in_negatives(B, K, seed):
    rng = np.random.default_rng(seed)
    c = jnp.asarray(rng.standard_normal((B, 16)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((B, 16)), jnp.float32)
    n = jnp.asarray(rng.standard_normal((B, K, 16)), jnp.float32)
    loss_k = ref.sgns_loss_ref(c, x, n)
    assert np.all(np.asarray(loss_k) > 0)
    if K > 1:
        loss_k1 = ref.sgns_loss_ref(c, x, n[:, :1])
        assert np.all(np.asarray(loss_k) >= np.asarray(loss_k1) - 1e-5)


@given(
    st.integers(8, 40),  # anchors
    st.integers(2, 24),  # dim
    st.integers(0, 2**31 - 1),
)
@settings(max_examples=40, deadline=None)
def test_procrustes_alignment_is_orthogonal(n, d, seed):
    """The retraining aligner's rotation is always orthogonal: row norms and
    anchor dot products survive alignment within tolerance, for any pair of
    anchor clouds (related by a planted rotation or not). With at least d
    anchors a planted rotation is recovered; with fewer it is not
    determined, and the aligner leaves the table as it is."""
    from repro.serve import EmbeddingAligner, procrustes_rotation

    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, d)).astype(np.float32)
    q, r = np.linalg.qr(rng.standard_normal((d, d)))
    planted = (q * np.sign(np.diag(r))).astype(np.float32)
    for Y in (X @ planted, rng.standard_normal((n, d)).astype(np.float32)):
        R = procrustes_rotation(X, Y)
        np.testing.assert_allclose(R @ R.T, np.eye(d), atol=1e-4)
        np.testing.assert_allclose(R.T @ R, np.eye(d), atol=1e-4)
        aligned = X @ R
        np.testing.assert_allclose(
            np.linalg.norm(aligned, axis=1),
            np.linalg.norm(X, axis=1),
            rtol=1e-3, atol=1e-4,
        )
        np.testing.assert_allclose(
            aligned @ aligned.T, X @ X.T, rtol=1e-3, atol=1e-3
        )
    out, rep = EmbeddingAligner().align(X, X @ planted, np.arange(n))
    if n < d:
        assert not rep["aligned"]
        np.testing.assert_array_equal(out, X)
    else:
        # a planted rotation is recovered exactly (up to float error)
        assert rep["aligned"]
        np.testing.assert_allclose(
            procrustes_rotation(X, X @ planted), planted, atol=1e-3
        )
        np.testing.assert_allclose(out, X @ planted, atol=1e-3)
