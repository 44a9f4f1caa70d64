"""Sharded ELL mirror + sharded block core repair == single-device, exactly.

Twin ``DynamicGraph``/``IncrementalCore`` stacks are driven with identical
seeded streams (inserts, deletions, churn, compaction boundaries); the
sharded stack must match the unsharded one *and* the peeling oracle at
every step.
"""
import numpy as np
import pytest

from repro.core.kcore import core_numbers_host
from repro.graph import generators
from repro.serve import DynamicGraph, IncrementalCore


def _mirror_equal(d1, d8):
    e1, e8 = d1.ell(), d8.ell()
    n1 = d1.node_cap + 1
    nbr8 = np.asarray(e8.neighbours)
    deg8 = np.asarray(e8.degrees)
    np.testing.assert_array_equal(np.asarray(e1.neighbours), nbr8[:n1])
    np.testing.assert_array_equal(np.asarray(e1.degrees), deg8[:n1])
    # shard-padding rows are pure sentinel
    assert (nbr8[n1:] == d8.node_cap).all()
    assert not deg8[n1:].any()


def test_mirror_parity_under_mixed_blocks_and_compaction(plan8):
    g = generators.barabasi_albert_varying(120, 4.0, seed=3)
    edges = g.edge_list()
    rng = np.random.default_rng(4)
    edges = edges[rng.permutation(len(edges))]
    d1 = DynamicGraph(g.n_nodes, width=3)
    d8 = DynamicGraph(g.n_nodes, width=3, plan=plan8)
    live = []
    for step, start in enumerate(range(0, len(edges), 24)):
        block = edges[start : start + 24]
        a1, a8 = d1.add_edges(block), d8.add_edges(block)
        np.testing.assert_array_equal(a1, a8)
        live.extend(map(tuple, a1))
        if step % 2 and len(live) > 8:
            pick = rng.choice(len(live), size=6, replace=False)
            rm = np.array([live[i] for i in pick])
            np.testing.assert_array_equal(
                d1.remove_edges(rm), d8.remove_edges(rm)
            )
            gone = {tuple(e) for e in rm}
            live = [e for e in live if e not in gone]
        if step % 3 == 2:
            d1.compact()
            d8.compact()
        _mirror_equal(d1, d8)
    assert d8.compactions >= 2


@pytest.mark.parametrize("churn", [False, True], ids=["insert", "churn"])
def test_block_repair_parity_insert_delete_churn(plan8, churn):
    """Sharded repair (the jitted sharded region traversal and fused
    descent) matches the unsharded stack and the peeling oracle, on an
    insert-only stream and on the same stream with churny retractions."""
    g = generators.barabasi_albert_varying(130, 4.0, seed=5)
    edges = g.edge_list()
    rng = np.random.default_rng(6)
    edges = edges[rng.permutation(len(edges))]
    d1 = DynamicGraph(g.n_nodes, width=3)
    d8 = DynamicGraph(g.n_nodes, width=3, plan=plan8)
    i1 = IncrementalCore(d1)
    i8 = IncrementalCore(d8)
    live = []
    for step, start in enumerate(range(0, len(edges), 32)):
        block = edges[start : start + 32]
        a1, a8 = d1.add_edges(block), d8.add_edges(block)
        i1.on_edge_block(a1)
        i8.on_edge_block(a8)
        live.extend(map(tuple, a1))
        if churn and step % 2 and len(live) > 8:
            pick = rng.choice(len(live), size=6, replace=False)
            rm = np.array([live[i] for i in pick])
            i1.on_remove(d1.remove_edges(rm))
            i8.on_remove(d8.remove_edges(rm))
            gone = {tuple(e) for e in rm}
            live = [e for e in live if e not in gone]
        if step % 3 == 2:
            d1.compact()
            d8.compact()
        np.testing.assert_array_equal(i1.core, i8.core)
        np.testing.assert_array_equal(
            i8.core, core_numbers_host(d8.snapshot())
        )
    assert i8.promoted > 0 and (i8.demoted > 0) == churn
    assert i8.descends > 0  # the sharded fused descent actually ran
    assert i8.resync() == 0


def test_sharded_fallback_repeel_stays_exact(plan8, forced_policy):
    """A graph-sized block on the sharded stack sent to the re-peel (the
    full-graph fused descent, row-sharded) still lands on the oracle."""
    g = generators.barabasi_albert_varying(400, 5.0, seed=7)
    d8 = DynamicGraph(g.n_nodes, width=4, plan=plan8)
    i8 = IncrementalCore(d8, policy=forced_policy("repeel"))
    i8.on_edge_block(d8.add_edges(g.edge_list()))
    assert i8.repeels >= 1
    np.testing.assert_array_equal(
        i8.core, core_numbers_host(d8.snapshot())
    )
