"""Plain reference of core numbers: Matula–Beck peeling.

Batagelj–Zaversnik's bucket form of the Matula–Beck order: repeatedly remove
a node of least remaining degree; its core number is that degree at removal.
O(V + E), numpy and Python only, and independent of the program's sources.
"""
from __future__ import annotations

import numpy as np

__all__ = ["core_numbers"]


def core_numbers(n: int, edges: np.ndarray) -> np.ndarray:
    """(n,) int32 core numbers of the undirected graph on ``edges``."""
    e = np.asarray(edges, np.int64).reshape(-1, 2)
    src = np.concatenate([e[:, 0], e[:, 1]])
    dst = np.concatenate([e[:, 1], e[:, 0]])
    order = np.argsort(src, kind="stable")
    nbr = dst[order].tolist()
    deg_arr = np.bincount(src, minlength=n).astype(np.int64)
    start = np.zeros(n + 1, np.int64)
    np.cumsum(deg_arr, out=start[1:])
    start = start.tolist()
    deg = deg_arr.tolist()
    md = max(deg) if n else 0
    # bin[d] = first position of degree-d nodes in ``vert``
    counts = np.bincount(deg_arr, minlength=md + 1)
    bins = np.zeros(md + 1, np.int64)
    np.cumsum(counts[:-1], out=bins[1:])
    vert = np.argsort(deg_arr, kind="stable").tolist()
    pos = [0] * n
    for i, v in enumerate(vert):
        pos[v] = i
    bins = bins.tolist()
    for i in range(n):
        v = vert[i]
        dv = deg[v]
        for j in range(start[v], start[v + 1]):
            u = nbr[j]
            du = deg[u]
            if du > dv:
                pu, pw = pos[u], bins[du]
                w = vert[pw]
                if u != w:
                    pos[u], pos[w] = pw, pu
                    vert[pu], vert[pw] = w, u
                bins[du] += 1
                deg[u] = du - 1
    return np.asarray(deg, np.int32)
