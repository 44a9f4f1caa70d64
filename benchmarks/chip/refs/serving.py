"""Plain references of what the query path serves, in float64 numpy.

``ServedStore`` replays the service's answers call by call. A node whose row
is stored answers with that row. A node without one is a cold start and
answers with the §2.2 rule: the mean of the stored rows of its neighbours,
written back as a stored float32 row once its batch is answered, or zeros
when no neighbour is stored (then nothing is written). Answers are computed
batch by batch in the service's batch size, and a cold start sees only the
rows stored before its batch, as the fused flush does.

``cosine`` is the link score, and ``topk_scores`` the cosine of a query
against every stored row but its own, in float64.
"""
from __future__ import annotations

import numpy as np

__all__ = ["ServedStore", "normalize", "cosine", "topk_scores"]


def normalize(x: np.ndarray, eps: float = 1e-9) -> np.ndarray:
    x = np.asarray(x, np.float64)
    n = np.sqrt(np.sum(x * x, axis=-1, keepdims=True))
    return x / np.maximum(n, eps)


def cosine(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    return np.sum(normalize(a) * normalize(b), axis=-1)


class ServedStore:
    """Stored rows plus the graph's adjacency (CSR ``indptr``/``indices``)."""

    def __init__(self, rows: np.ndarray, stored: np.ndarray,
                 indptr: np.ndarray, indices: np.ndarray, batch: int):
        self.rows = np.asarray(rows, np.float32).copy()
        self.stored = np.asarray(stored, bool).copy()
        self.indptr = np.asarray(indptr)
        self.indices = np.asarray(indices)
        self.batch = int(batch)
        self.derived = np.zeros(len(self.stored), bool)  # written back
        self.version = 0  # bumped by every write-back

    def _batch(self, nodes: np.ndarray):
        derived = self.derived[nodes] | ~self.stored[nodes]
        out = self.rows[nodes].astype(np.float64)
        out[~self.stored[nodes]] = 0.0
        writes = {}
        for v in np.unique(nodes[~self.stored[nodes]]).tolist():
            nb = self.indices[self.indptr[v]:self.indptr[v + 1]]
            nb = nb[self.stored[nb]]
            if len(nb):
                writes[v] = self.rows[nb].astype(np.float64).mean(axis=0)
        for v, mean in writes.items():
            out[nodes == v] = mean
        for v, mean in writes.items():
            self.rows[v] = mean.astype(np.float32)
            self.stored[v] = self.derived[v] = True
        self.version += bool(writes)
        return out, derived

    def answer(self, nodes):
        """``(len(nodes), dim)`` float64 answers, in batches of ``batch``,
        and which of them came from a cold start: the mean itself, or a row
        written back from one."""
        nodes = np.asarray(nodes, np.int64).reshape(-1)
        parts = [self._batch(nodes[s:s + self.batch])
                 for s in range(0, len(nodes), self.batch)]
        if not parts:
            return np.zeros((0, self.rows.shape[1])), np.zeros(0, bool)
        return (np.concatenate([p[0] for p in parts]),
                np.concatenate([p[1] for p in parts]))

    def embed(self, nodes) -> np.ndarray:
        """(len(nodes), dim) float64 answers, in batches of ``batch``."""
        return self.answer(nodes)[0]

    def link_ends(self, pairs):
        """The two rows of every pair; the service embeds the sorted
        distinct ends in one call."""
        flat = np.asarray(pairs, np.int64).reshape(-1)
        uniq, inv = np.unique(flat, return_inverse=True)
        e = self.embed(uniq)[inv]
        return e[0::2], e[1::2]

    def link_scores(self, pairs) -> np.ndarray:
        """Cosine per pair."""
        return cosine(*self.link_ends(pairs))

    def candidates(self):
        """(node ids, float64 unit rows) of every stored row."""
        ids = np.nonzero(self.stored)[0]
        return ids, normalize(self.rows[ids])


def topk_scores(query: np.ndarray, node: int, ids: np.ndarray,
                unit_rows: np.ndarray) -> np.ndarray:
    """Cosine of ``query`` against every candidate row but ``node``'s own,
    as ``{node id: score}`` arrays ``(ids, scores)`` sorted by score desc."""
    s = unit_rows @ normalize(query)
    keep = ids != node
    ids, s = ids[keep], s[keep]
    order = np.argsort(-s, kind="stable")
    return ids[order], s[order]
