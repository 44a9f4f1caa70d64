"""Pipelined block ingest == a synchronous ``impl="ref"`` oracle, at 1 and 8
shards.

``EmbeddingService`` stages block N+1's host dedup while block N's
fused-descent dispatch is in flight and defers the per-block tail to the
next sync point. That overlap must be pure scheduling: a synchronous
``IncrementalCore(impl="ref")`` on its own copy of the graph, fed every block
the service's repair begins (ingest blocks, churny retractions, interleaved
queries that force a mid-stream settle), must hold exactly the service's
cores at every step.
"""
import numpy as np
import pytest

from repro.core.kcore import core_numbers_host
from repro.graph import generators
from repro.launch.serve_embed import build_service
from repro.serve import DynamicGraph, IncrementalCore


def _with_ref_twin(shards, *, seed=21, n=300):
    """A pipelined service, and a synchronous ``impl="ref"`` maintainer fed
    every block that the service's repair begins."""
    g = generators.barabasi_albert_varying(n, 5.0, seed=seed)
    svc, stream, core, _ = build_service(
        g, seed=seed, batch=32, compact_every=128, shards=shards
    )
    twin = DynamicGraph(svc.graph.n_nodes, svc.graph.snapshot().edge_list(),
                        width=16)
    ref = IncrementalCore(twin, core, impl="ref")
    begin = svc.cores.begin_update

    def mirrored(added=None, removed=None):
        if added is not None:
            np.testing.assert_array_equal(twin.add_edges(added), added)
            ref.on_edge_block(added)
        if removed is not None:
            np.testing.assert_array_equal(twin.remove_edges(removed), removed)
            ref.on_remove(removed)
        return begin(added=added, removed=removed)

    svc.cores.begin_update = mirrored
    return svc, ref, stream


def _assert_same_cores(svc, ref):
    np.testing.assert_array_equal(svc.cores.core, ref.core)
    assert svc.cores.resync() == 0 and ref.resync() == 0


@pytest.mark.parametrize("shards", [1, 8])
def test_pipelined_ingest_matches_serial(plan8, shards):
    svc, ref, stream = _with_ref_twin(shards)
    rng_q = np.random.default_rng(22)
    n_now = svc.graph.n_nodes
    ingested = removed = 0
    for start in range(0, len(stream), 48):
        block = stream[start : start + 48]
        ingested += len(svc.ingest_block(block))
        if (start // 48) % 2:
            removed += svc.retract_block(block[: len(block) // 3])
        if (start // 48) % 3 == 2:
            # queries settle the in-flight repair mid-stream
            svc.embed(rng_q.integers(0, n_now, size=16))
            assert svc.cores._inflight is None
            np.testing.assert_array_equal(
                svc.cores._core[: svc.graph.n_nodes], ref.core)
    svc.sync()
    _assert_same_cores(svc, ref)
    np.testing.assert_array_equal(
        svc.cores.core, core_numbers_host(svc.graph.snapshot()))
    assert svc.stats.edges_ingested == ingested > 0
    assert svc.stats.edges_removed == removed > 0
    assert svc.stats.compactions > 0


def test_pipelined_churn_replay_matches_serial(plan8):
    """The churny replay driver at 8 shards lands the oracle's cores, and
    its counts are the service's."""
    svc, ref, stream = _with_ref_twin(8, seed=23)
    n_in, n_out = svc.stream_with_churn(stream, block_size=64, churn=0.2,
                                        rng=np.random.default_rng(24))
    assert (n_in, n_out) == (svc.stats.edges_ingested,
                             svc.stats.edges_removed)
    assert n_in > 0 and n_out > 0
    _assert_same_cores(svc, ref)
