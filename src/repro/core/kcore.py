"""K-core decomposition (paper §1.2.3) — the degeneracy primitive.

Two implementations:

* ``core_numbers_host`` — Matula–Beck bucket peeling, O(E), numpy. Used for
  dataset preparation and as the oracle for the device path.
* ``core_numbers_jax`` — jit-able fixed point of the neighbourhood h-index
  operator on the padded ELL adjacency (Lü et al., "The H-index of a network
  node", 2016): initialise c⁰ = deg and iterate
  c^{t+1}(v) = H({c^t(u) : u ∈ N(v)}) until convergence; the fixed point is
  exactly the core number. Each sweep is a gather + per-row sorted reduction,
  i.e. TPU-friendly (no serial peeling), and converges in a few dozen sweeps
  on real graphs.

Shell/core helpers used by CoreWalk (§2.1) and propagation (§2.2) live here
too: ``core_mask`` (k-core membership) and ``shells`` (nodes per core index).
"""
from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.graph.csr import EllGraph, Graph
from repro.kernels import ops as _kernel_ops

__all__ = [
    "core_numbers_host",
    "core_numbers_rounds",
    "core_numbers_jax",
    "h_index_sweep",
    "degeneracy",
    "core_mask",
    "shells",
    "kcore_subgraph",
]


def core_numbers_host(g: Graph) -> np.ndarray:
    """Matula–Beck O(E) peeling. Returns (n_nodes,) int32 core numbers."""
    n = g.n_nodes
    deg = g.degrees().astype(np.int64)
    max_deg = int(deg.max()) if n else 0
    # bucket sort nodes by degree
    bin_start = np.zeros(max_deg + 2, dtype=np.int64)
    counts = np.bincount(deg, minlength=max_deg + 1)
    np.cumsum(counts, out=bin_start[1:])
    pos = np.empty(n, dtype=np.int64)
    vert = np.empty(n, dtype=np.int64)
    fill = bin_start[:-1].copy()
    for v in range(n):
        pos[v] = fill[deg[v]]
        vert[pos[v]] = v
        fill[deg[v]] += 1
    bin_ptr = bin_start[:-1].copy()
    core = deg.copy()
    for i in range(n):
        v = vert[i]
        for u in g.neighbours(v):
            u = int(u)
            if core[u] > core[v]:
                du = core[u]
                pu = pos[u]
                pw = bin_ptr[du]
                w = vert[pw]
                if u != w:
                    pos[u], pos[w] = pw, pu
                    vert[pu], vert[pw] = w, u
                bin_ptr[du] += 1
                core[u] -= 1
    return core.astype(np.int32)


def core_numbers_rounds(n_nodes: int, arc_src: np.ndarray,
                        arc_dst: np.ndarray) -> np.ndarray:
    """Vectorized Matula–Beck: peel whole degree-``<=k`` layers per round.

    ``arc_src``/``arc_dst`` hold every arc (both directions of each edge),
    unsorted. Same exact core numbers as ``core_numbers_host``, but each
    round strips *all* currently peelable nodes with numpy boolean masks and
    one grouped degree decrement, so the Python-level loop runs O(#rounds)
    times (graph-diameter-ish) instead of O(n). This is the host fallback of
    the online block repair: it reads the streaming graph's arc arrays
    directly, no CSR snapshot required.
    """
    n = int(n_nodes)
    if n == 0:
        return np.zeros(0, np.int32)
    arc_src = np.asarray(arc_src, np.int64)
    arc_dst = np.asarray(arc_dst, np.int64)
    deg = np.bincount(arc_src, minlength=n).astype(np.int64)
    core = np.zeros(n, np.int32)
    active = deg > 0
    k = 0
    n_active = int(active.sum())
    while n_active:
        k = max(k, int(deg[active].min()))
        frontier = active & (deg <= k)
        while frontier.any():
            core[frontier] = k
            active &= ~frontier
            n_active -= int(frontier.sum())
            # arcs leaving the peeled layer into still-active nodes; arcs
            # between two peeled nodes need no decrement (both are gone)
            m = frontier[arc_src] & active[arc_dst]
            if m.any():
                deg -= np.bincount(arc_dst[m], minlength=n)
            frontier = active & (deg <= k)
        # every inner round scans the arc arrays: drop arcs whose endpoints
        # are peeled once a level finishes, so the scans shrink geometrically
        if len(arc_src) > 1024:
            keep = active[arc_src]
            keep &= active[arc_dst]
            if int(keep.sum()) * 2 < len(arc_src):
                arc_src, arc_dst = arc_src[keep], arc_dst[keep]
    return core


def h_index_sweep(values: jnp.ndarray, valid: jnp.ndarray,
                  est: jnp.ndarray, *, impl: str = "ref") -> jnp.ndarray:
    """One row-masked h-index repair sweep (the shared operator).

    ``values`` is the (R, W) matrix of neighbour core estimates for R
    candidate rows, ``valid`` masks the real entries, ``est`` is the (R,)
    current estimate of the candidate rows themselves. Returns
    ``min(est, H(row))`` — monotone non-increasing, so iterating from any
    upper bound descends to the greatest fixed point below it. Both the
    offline fixpoint (``core_numbers_jax``, all rows) and the incremental
    repair (``repro.serve.kcore_inc``, candidate rows only) drive this same
    operator; the mask is simply which rows the caller gathers. ``impl``
    selects the backend (``kernels.ops.h_index_sweep``): the sort-based ref,
    the sort-free counting search, or the Pallas kernel.
    """
    return _kernel_ops.h_index_sweep(values, valid, est, impl=impl)


_h_index_sweep_jit = jax.jit(h_index_sweep, static_argnames=("impl",))


@partial(jax.jit, static_argnames=("max_sweeps", "impl"))
def _core_fixpoint(neighbours, degrees, max_sweeps: int, impl: str = "ref"):
    n_plus_1 = neighbours.shape[0]
    valid = neighbours != (n_plus_1 - 1)
    core0 = degrees.astype(jnp.int32)

    def cond(state):
        core, prev, it = state
        return jnp.logical_and(it < max_sweeps, jnp.any(core != prev))

    def body(state):
        core, _, it = state
        nbr_core = core[neighbours]  # (N+1, L)
        new = h_index_sweep(nbr_core, valid, core, impl=impl)
        new = new.at[-1].set(0)  # sentinel row
        return new, core, it + 1

    core, _, sweeps = jax.lax.while_loop(cond, body, (core0, core0 - 1, 0))
    return core, sweeps


def core_numbers_jax(ell: EllGraph, max_sweeps: int = 256,
                     impl: str = "auto") -> jnp.ndarray:
    """Core numbers via the h-index fixed point. Returns (n_nodes,) int32.

    Exact when the ELL table is not width-capped (uses true degrees); with a
    capped table the result is a lower bound (documented; tests use uncapped).
    ``impl="auto"`` backs each sweep with the Pallas h-index kernel on TPU
    and the counting search elsewhere (XLA sort is the slow path on both).
    """
    if impl == "auto":
        impl = _kernel_ops.h_index_impl()
    core, _ = _core_fixpoint(ell.neighbours, ell.degrees, max_sweeps, impl)
    return core[: ell.n_nodes]


def degeneracy(core: np.ndarray) -> int:
    return int(np.max(core)) if len(core) else 0


def core_mask(core: np.ndarray, k: int) -> np.ndarray:
    """Membership mask of the k-core (nodes with core number >= k)."""
    return np.asarray(core) >= k


def shells(core: np.ndarray) -> Dict[int, np.ndarray]:
    """core index -> node ids whose core number equals that index."""
    core = np.asarray(core)
    return {int(k): np.where(core == k)[0] for k in np.unique(core)}


def kcore_subgraph(g: Graph, core: np.ndarray, k: int) -> Graph:
    """Induced subgraph on the k-core (original node ids preserved)."""
    return g.subgraph(core_mask(core, k))
