"""Seeded Graph500 Kronecker graphs.

A configuration file names its ``generator`` and holds its parameters;
``make_graph`` returns ``(n_nodes, edges)`` with ``edges`` an (E, 2) int64
array of distinct undirected edges, ``u < v``, sorted by ``(u, v)``.

``kronecker`` follows the Graph500 specification's Kernel 1 generator
(https://graph500.org/?page_id=12): ``edgefactor * 2**SCALE`` edge tuples,
each endpoint bit drawn from the initiator ``A, B, C`` (``D = 1 - A - B -
C``), then a random permutation of the vertex labels. The service holds a
simple undirected graph, so self-loops and repeated tuples are dropped, and
vertices left with no edge are left out with ids compacted in order (the
specification never draws them as search keys either). The benchmark keeps
its own generator so that the data it measures on cannot change under a
later change to the program.
"""
from __future__ import annotations

import numpy as np

__all__ = ["make_graph", "canonical_edges", "kronecker_bits",
           "kronecker_tuples"]


def canonical_edges(n: int, edges: np.ndarray) -> np.ndarray:
    """Distinct undirected edges as (u < v) rows sorted by (u, v)."""
    e = np.asarray(edges, np.int64).reshape(-1, 2)
    e = e[e[:, 0] != e[:, 1]]
    e = np.sort(e, axis=1)
    key = np.unique(e[:, 0] * n + e[:, 1])
    return np.stack([key // n, key % n], axis=1)


def kronecker_bits(scale: int, m: int, a: float, b: float, c: float,
                   rng: np.random.Generator) -> np.ndarray:
    """(2, m) endpoints before the permutations: at every bit level the
    pair of bits is (0, 0), (0, 1), (1, 0), (1, 1) with probability A, B, C,
    D."""
    ab = a + b
    c_norm, a_norm = c / (1.0 - ab), a / ab
    ij = np.zeros((2, m), np.int64)
    for bit in range(scale):
        ii = rng.random(m) > ab
        jj = rng.random(m) > np.where(ii, c_norm, a_norm)
        ij += np.stack([ii, jj]).astype(np.int64) << bit
    return ij


def kronecker_tuples(scale: int, edgefactor: int, a: float, b: float,
                     c: float, seed: int) -> np.ndarray:
    """The specification's (M, 2) edge tuples over ``2**scale`` vertices."""
    rng = np.random.default_rng(seed)
    n, m = 2 ** scale, edgefactor * 2 ** scale
    ij = kronecker_bits(scale, m, a, b, c, rng)
    ij = rng.permutation(n)[ij]
    return ij[:, rng.permutation(m)].T


def make_graph(config: dict):
    """``(n_nodes, edges)`` of a configuration."""
    if config["generator"] != "kronecker":
        raise ValueError(f"unknown graph generator {config['generator']!r}")
    a, b, c, _ = config["initiator"]
    scale = int(config["SCALE"])
    tuples = kronecker_tuples(scale, int(config["edgefactor"]), a, b, c,
                              int(config["graph_seed"]))
    edges = canonical_edges(2 ** scale, tuples)
    live = np.zeros(2 ** scale, bool)
    live[edges.ravel()] = True
    new_id = np.cumsum(live) - 1
    return int(live.sum()), new_id[edges]
