"""``correct`` comes out false for the control and for every fault a cell
can have, planted under a run of a tiny cell on the CPU.

Faults: a repair that leaves the cores unchanged; a flush that leaves the
store unchanged (no cold start written back); half of each block or flush
batch left out; an answer altered where it is produced (a core, a top-k id,
a link score, the cold-start mean). A one-chip cell has no exchange between
chips to leave out.
"""
import contextlib

import numpy as np
import pytest

from benchmarks.chip import harness
from benchmarks.chip.graphgen import make_graph
from chipbench_tiny import run_tiny, tiny_root


def _window(root, cell, seed):
    spec = harness.Spec(root, cell)
    c = spec.driver(seed, lambda name: contextlib.nullcontext())
    c.setup(make_graph(spec.config))
    c.window(1.0)
    c.collect()
    return spec, c


@pytest.mark.parametrize("cell", ["tiny.ingest", "tiny.query"])
def test_control_is_not_correct(tmp_path, cell):
    spec, c = _window(tiny_root(tmp_path), cell, 2**31 + 11)
    prog, ctrl = c.check(), c.control()
    assert all(v <= spec.limits[k] for k, v in prog.items()), prog
    assert any(v > spec.limits[k] for k, v in ctrl.items()), ctrl


def _unchanged_repair(mp):
    from repro.serve import kcore_inc

    mp.setattr(kcore_inc.IncrementalCore, "begin_update",
               lambda self, added=None, removed=None:
               kcore_inc._RepairTicket(changed=0))


def _half_block(mp):
    from repro.serve.service import EmbeddingService

    orig = EmbeddingService.ingest_block

    def half(self, edges):
        edges = np.asarray(edges)
        orig(self, edges[: len(edges) // 2])
        return edges  # claims the whole block

    mp.setattr(EmbeddingService, "ingest_block", half)


def _altered_core(mp):
    from repro.serve.kcore_inc import IncrementalCore

    orig = IncrementalCore.finish_update

    def altered(self, ticket=None):
        out = orig(self, ticket)
        self._core[0] += 1
        return out

    mp.setattr(IncrementalCore, "finish_update", altered)


def _half_flush(mp):
    from repro.serve.service import EmbeddingService

    orig = EmbeddingService._flush_batch

    def half(self, nodes):
        out = np.array(orig(self, nodes))
        out[len(out) // 2:] = out[0]
        return out

    mp.setattr(EmbeddingService, "_flush_batch", half)


def _altered_topk(mp):
    from repro.serve.service import EmbeddingService

    orig = EmbeddingService.top_k_neighbors

    def altered(self, nodes, k):
        ids, scores = orig(self, nodes, k)
        ids[:, 0] = ids[:, -1]
        return ids, scores

    mp.setattr(EmbeddingService, "top_k_neighbors", altered)


def _altered_link(mp):
    from repro.serve.service import EmbeddingService

    orig = EmbeddingService.link_scores
    mp.setattr(EmbeddingService, "link_scores",
               lambda self, pairs: orig(self, pairs) + 1e-3)


def _no_write_back(mp):
    from repro.serve.service import EmbeddingService

    orig = EmbeddingService.__init__

    def init(self, *a, **kw):
        orig(self, *a, **kw)
        self.write_back = False

    mp.setattr(EmbeddingService, "__init__", init)


def _altered_cold_mean(mp):
    from repro.kernels import ops

    orig = ops.ell_mean
    mp.setattr(ops, "ell_mean", lambda *a, **kw: orig(*a, **kw) * 0.999)


@pytest.mark.parametrize("cell,fault", [
    ("tiny.ingest", _unchanged_repair),
    ("tiny.ingest", _half_block),
    ("tiny.ingest", _altered_core),
    ("tiny.query", _half_flush),
    ("tiny.query", _altered_topk),
    ("tiny.query", _altered_link),
    ("tiny.query", _no_write_back),
    ("tiny.query", _altered_cold_mean),
])
def test_a_planted_fault_is_not_correct(tmp_path, monkeypatch, cell, fault):
    fault(monkeypatch)
    res = run_tiny(tiny_root(tmp_path), cell, seconds=1.0)
    assert res["correct"] is False, res["checks"]
