"""DynamicGraph: streaming ingestion, ELL slack, compaction, device mirror."""
import numpy as np

from repro.graph import generators
from repro.graph.csr import Graph
from repro.serve import DynamicGraph


def _random_graph(seed):
    return generators.barabasi_albert_varying(150, 4.0, seed=seed)


def test_snapshot_matches_batch_csr():
    g = _random_graph(0)
    dyn = DynamicGraph(g.n_nodes, g.edge_list(), width=4)
    snap = dyn.snapshot()
    ref = Graph.from_edges(g.n_nodes, g.edge_list())
    np.testing.assert_array_equal(snap.indptr, ref.indptr)
    np.testing.assert_array_equal(snap.indices, ref.indices)
    assert dyn.n_edges == g.n_edges


def test_duplicate_and_self_loop_rejected():
    dyn = DynamicGraph(4)
    assert dyn.add_edge(0, 1)
    assert not dyn.add_edge(1, 0)  # same undirected edge
    assert not dyn.add_edge(2, 2)
    assert dyn.n_edges == 1


def test_negative_node_ids_rejected():
    import pytest

    dyn = DynamicGraph(4)
    with pytest.raises(ValueError):
        dyn.add_edge(-1, 2)  # would wrap into the sentinel row
    # sentinel row untouched
    assert dyn.degree(dyn.node_cap) == 0
    np.testing.assert_array_equal(dyn._nbr[-1], dyn.node_cap)


def test_overflow_spills_then_compaction_repacks():
    # width 2 forces overflow on a star centre
    dyn = DynamicGraph(10, width=2)
    for v in range(1, 8):
        dyn.add_edge(0, v)
    assert dyn.needs_compact and dyn.overflow_arcs > 0
    assert dyn.degree(0) == 7  # host adjacency sees every arc
    assert set(dyn.neighbours(0).tolist()) == set(range(1, 8))
    # device view is capped until compaction
    ell = dyn.ell()
    in_table = (np.asarray(ell.neighbours)[0] != dyn.node_cap).sum()
    assert in_table == 2
    dyn.compact()
    assert not dyn.needs_compact
    assert dyn.width >= 7
    ell = dyn.ell()
    row = np.asarray(ell.neighbours)[0]
    assert set(row[row != dyn.node_cap].tolist()) == set(range(1, 8))


def test_device_mirror_applies_incremental_writes():
    g = _random_graph(1)
    edges = g.edge_list()
    dyn = DynamicGraph(g.n_nodes, edges[: len(edges) // 2], width=16)
    dyn.ell()  # force the initial full upload
    for u, v in edges[len(edges) // 2 :]:
        dyn.add_edge(int(u), int(v))
    ell = dyn.ell()  # batched scatter of the pending writes
    nbr = np.asarray(ell.neighbours)
    for v in range(g.n_nodes):
        row = nbr[v][nbr[v] != dyn.node_cap]
        in_table = set(row.tolist())
        true = set(dyn.neighbours(v).tolist())
        overflow = true - in_table
        assert in_table | overflow == true
        assert len(overflow) == 0 or dyn.needs_compact


def test_node_growth_preserves_adjacency():
    dyn = DynamicGraph(4, np.array([[0, 1], [1, 2]]), width=4)
    cap0 = dyn.node_cap
    big = cap0 + 100
    assert dyn.add_edge(1, big)  # forces node growth + re-upload
    assert dyn.n_nodes == big + 1
    assert dyn.node_cap > big
    assert set(dyn.neighbours(1).tolist()) == {0, 2, big}
    snap = dyn.snapshot()
    assert snap.has_edge(1, big) and snap.has_edge(0, 1)
    ell = dyn.ell()
    row = np.asarray(ell.neighbours)[big]
    assert set(row[row != dyn.node_cap].tolist()) == {1}


def test_add_edges_block_dedups_and_matches_sequential():
    """One staged block == the same edges inserted one at a time."""
    g = _random_graph(3)
    edges = g.edge_list()
    rng = np.random.default_rng(4)
    # duplicates within the block, reversed arcs, and self-loops
    block = np.concatenate([edges, edges[::-1, ::-1], [[5, 5], [7, 7]]])
    block = block[rng.permutation(len(block))]
    blk = DynamicGraph(g.n_nodes, width=4)
    accepted = blk.add_edges(block)
    assert len(accepted) == g.n_edges
    assert blk.n_edges == g.n_edges
    seq = DynamicGraph(g.n_nodes, width=4)
    for u, v in edges:
        assert seq.add_edge(int(u), int(v))
    snap_b, snap_s = blk.snapshot(), seq.snapshot()
    np.testing.assert_array_equal(snap_b.indptr, snap_s.indptr)
    np.testing.assert_array_equal(snap_b.indices, snap_s.indices)
    # a second staging of the same block is a full dedup no-op
    assert len(blk.add_edges(block)) == 0


def test_remove_edges_block_and_reinsert_round_trip():
    g = _random_graph(4)
    edges = g.edge_list()
    dyn = DynamicGraph(g.n_nodes, edges, width=4)  # width 4 forces overflow
    rng = np.random.default_rng(5)
    sel = edges[rng.choice(len(edges), 80, replace=False)]
    removed = dyn.remove_edges(np.concatenate([sel, sel]))  # dup-tolerant
    assert len(removed) == 80
    assert dyn.n_edges == g.n_edges - 80
    for u, v in sel:
        assert not dyn.has_edge(int(u), int(v))
    # unknown edges and unknown ids are skipped, not errors
    assert len(dyn.remove_edges(np.array([sel[0], [0, dyn.node_cap + 9]]))) == 0
    assert len(dyn.add_edges(sel)) == 80
    ref = Graph.from_edges(g.n_nodes, edges)
    snap = dyn.snapshot()
    np.testing.assert_array_equal(snap.indptr, ref.indptr)
    np.testing.assert_array_equal(snap.indices, ref.indices)


def test_remove_edge_backfills_from_overflow():
    dyn = DynamicGraph(10, width=2)  # star centre overflows
    for v in range(1, 8):
        dyn.add_edge(0, v)
    assert dyn.overflow_arcs > 0
    in_table_before = set(dyn._nbr[0, : dyn._deg[0]].tolist())
    victim = next(iter(in_table_before))
    assert dyn.remove_edge(0, victim)
    # the freed slot was backfilled from overflow: table stays full
    assert int(dyn._deg[0]) == 2
    assert dyn.degree(0) == 6
    assert set(dyn.neighbours(0).tolist()) == set(range(1, 8)) - {victim}


def test_device_mirror_tracks_removals():
    g = _random_graph(5)
    edges = g.edge_list()
    dyn = DynamicGraph(g.n_nodes, edges, width=16)
    dyn.ell()  # full upload; later mutations go through the pending scatter
    rng = np.random.default_rng(6)
    sel = edges[rng.choice(len(edges), 60, replace=False)]
    dyn.remove_edges(sel)
    dyn.add_edges(sel[:30])  # re-insert some into the freed slots
    ell = dyn.ell()
    nbr, deg = np.asarray(ell.neighbours), np.asarray(ell.degrees)
    for v in range(g.n_nodes):
        true = set(dyn.neighbours(v).tolist())
        in_table = set(nbr[v, : deg[v]].tolist())
        overflow_rows = dyn._overflow.get(v, [])
        assert in_table | set(overflow_rows) == true


def test_compact_is_double_buffered():
    """Old ELL views survive compaction; the new view needs no re-upload."""
    g = _random_graph(6)
    dyn = DynamicGraph(g.n_nodes, g.edge_list(), width=2)
    assert dyn.needs_compact
    old = dyn.ell()
    old_nbr = np.asarray(old.neighbours).copy()
    dyn.compact()
    # the pre-swap view is untouched (immutable device buffer)
    np.testing.assert_array_equal(np.asarray(old.neighbours), old_nbr)
    # the swap pre-uploaded the new buffer: no dirty flag, no pending writes
    assert dyn._dirty_full is False and not dyn._pending
    new = dyn.ell()
    nbr = np.asarray(new.neighbours)
    for v in range(g.n_nodes):
        row = nbr[v][nbr[v] != dyn.node_cap]
        np.testing.assert_array_equal(np.sort(row), g.neighbours(v))


def test_ell_view_consistent_with_to_ell_after_compact():
    g = _random_graph(2)
    dyn = DynamicGraph(g.n_nodes, g.edge_list(), width=2)
    dyn.compact()
    ell = dyn.ell()
    nbr = np.asarray(ell.neighbours)
    deg = np.asarray(ell.degrees)
    for v in range(g.n_nodes):
        row = np.sort(nbr[v][nbr[v] != dyn.node_cap])
        np.testing.assert_array_equal(row, g.neighbours(v))
        assert deg[v] == len(g.neighbours(v))


def test_gather_rows_at_a_given_width_merges_overflow():
    # width 4: node 0 spills 5 arcs to overflow, node 9 holds 2 arcs
    dyn = DynamicGraph(12, width=4)
    dyn.add_edges([[0, v] for v in range(1, 10)] + [[9, 10]])
    nodes = np.array([9, 0, 11])
    for width in (9, 16):
        idx, valid = dyn.gather_rows(nodes, width=width)
        assert idx.shape == valid.shape == (3, width)
        for row, v in zip(range(3), nodes):
            assert sorted(idx[row][valid[row]].tolist()) == \
                sorted(dyn.neighbours(v).tolist())
            # left-packed: the true degree's first columns, padding after
            assert valid[row].sum() == dyn.degree(v)
            assert valid[row, : dyn.degree(v)].all()
        assert (idx[~valid] == dyn.node_cap).all()
    # rows that fit: only the asked-for columns are gathered
    idx, valid = dyn.gather_rows(np.array([9, 11]), width=2)
    assert idx.shape == (2, 2) and valid[0].all() and not valid[1].any()
