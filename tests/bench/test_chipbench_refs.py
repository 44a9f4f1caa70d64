"""The benchmark's plain references agree with the program's own oracles at
a tiny size on the CPU."""
import numpy as np
import pytest

import json
from pathlib import Path

from benchmarks.chip.cells.query import TopkReadings
from benchmarks.chip.graphgen import (canonical_edges, kronecker_bits,
                                      make_graph)
from benchmarks.chip.refs.cores import core_numbers
from benchmarks.chip.refs.serving import ServedStore, cosine, normalize

CHIP = Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
TINY = dict(json.loads((CHIP / "configs" / "graph500-s14.json").read_text()),
            SCALE=8)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_core_numbers_match_the_program_peel(seed):
    from repro.core.kcore import core_numbers_host
    from repro.graph.csr import Graph

    rng = np.random.default_rng(seed)
    n, edges = make_graph(dict(TINY, graph_seed=seed))
    extra = canonical_edges(n, rng.integers(0, n, (400, 2)))
    edges = canonical_edges(n, np.concatenate([edges, extra]))
    want = core_numbers_host(Graph.from_edges(n, edges))
    np.testing.assert_array_equal(core_numbers(n, edges), want)


def test_core_numbers_of_isolated_nodes_and_a_clique():
    clique = np.array([(i, j) for i in range(5) for j in range(i + 1, 5)])
    got = core_numbers(8, clique)
    np.testing.assert_array_equal(got, [4, 4, 4, 4, 4, 0, 0, 0])


def test_kronecker_generator_follows_the_specification():
    """Every bit level of every tuple falls in the initiator's quadrants
    with probabilities A, B, C, D; there are edgefactor * 2**SCALE tuples,
    and the graph is their simple undirected graph without idle vertices."""
    a, b, c, d = TINY["initiator"]
    ij = kronecker_bits(10, 200_000, a, b, c, np.random.default_rng(0))
    assert ij.shape == (2, 200_000) and ij.min() >= 0 and ij.max() < 2**10
    for bit in range(10):
        i, j = (ij >> bit) & 1
        share = [np.mean((i == x) & (j == y)) for x, y in
                 ((0, 0), (0, 1), (1, 0), (1, 1))]
        np.testing.assert_allclose(share, [a, b, c, d], atol=0.005)
    n, edges = make_graph(TINY)
    assert len(edges) <= TINY["edgefactor"] * 2 ** TINY["SCALE"]
    np.testing.assert_array_equal(edges, canonical_edges(n, edges))
    assert np.all(np.bincount(edges.ravel(), minlength=n) > 0)


@pytest.fixture(scope="module")
def tiny_service():
    """A built service over a tiny graph with a cold node joined to stored
    ones, its ELL mirror compacted so that every neighbour is visible."""
    from repro.graph.csr import Graph
    from repro.launch.serve_embed import build_service

    n, edges = make_graph(TINY)
    svc, held, _, _ = build_service(Graph.from_edges(n, edges), dim=16,
                                    batch=8, seed=3)
    keys = np.setdiff1d(edges[:, 0] * n + edges[:, 1],
                        canonical_edges(n, held) @ np.array([n, 1]))
    base = np.stack([keys // n, keys % n], axis=1)
    stored = np.bincount(base.ravel(), minlength=n) > 0
    rows = np.random.default_rng(4).normal(size=(n, 16)).astype(np.float32)
    svc.store.put_many(np.nonzero(stored)[0], rows[stored], 0)
    cold = int(np.nonzero(~stored)[0][0])
    hubs = np.nonzero(stored)[0][:3]
    join = np.stack([np.full(3, cold), hubs], axis=1)
    svc.ingest_block(join)
    svc.sync()
    svc.graph.compact()
    live = canonical_edges(n, np.concatenate([base, join]))
    src = np.concatenate([live[:, 0], live[:, 1]])
    dst = np.concatenate([live[:, 1], live[:, 0]])
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(src, minlength=n), out=indptr[1:])
    order = np.argsort(src, kind="stable")
    ref = ServedStore(rows, stored, indptr, dst[order],
                      batch=8)
    return svc, ref, cold, n


def test_served_rows_and_cold_start_mean_match(tiny_service):
    svc, ref, cold, n = tiny_service
    nodes = np.concatenate([[cold], np.arange(0, n, 7), [cold]])
    want = ref.embed(nodes)
    got = svc.embed(nodes)
    stored = np.asarray([v != cold for v in nodes])
    assert np.array_equal(got[stored], want[stored].astype(np.float32))
    np.testing.assert_allclose(got[~stored], want[~stored], rtol=1e-6,
                               atol=1e-7)
    assert ref.stored[cold]  # written back, as the service does


def test_link_scores_match(tiny_service):
    svc, ref, _, n = tiny_service
    pairs = np.random.default_rng(5).integers(0, n, (20, 2))
    np.testing.assert_allclose(svc.link_scores(pairs), ref.link_scores(pairs),
                               atol=1e-6)
    np.testing.assert_allclose(cosine(np.ones((1, 3)), np.ones((1, 3))), 1.0)


def test_topk_matches_the_program_oracle(tiny_service):
    from repro.serve.service import top_k_reference

    svc, ref, _, n = tiny_service
    nodes = np.random.default_rng(6).integers(0, n, 8)
    ids, scores = svc.top_k_neighbors(nodes, 5)
    np.testing.assert_array_equal(
        ids, top_k_reference(svc.store, nodes, svc.embed(nodes), 5))
    queries = ref.embed(nodes)
    cand_ids, units = ref.candidates()
    tk = TopkReadings()
    for r, v in enumerate(nodes.tolist()):
        tk.add(v, ids[r], scores[r], queries[r], cand_ids, units)
    assert tk.max_err < 1e-6
    bad = TopkReadings()
    swapped = ids.copy()
    swapped[0, 0] = nodes[0]  # the query itself is no candidate
    bad.add(int(nodes[0]), swapped[0], scores[0], queries[0], cand_ids, units)
    assert bad.max_err == TopkReadings.BAD_ID
    np.testing.assert_allclose(np.linalg.norm(normalize(queries), axis=1), 1)
