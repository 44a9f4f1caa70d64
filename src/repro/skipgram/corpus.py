"""Walk corpus construction and SGNS batch sampling.

The corpus is the set of random walks (W, L) generated from a WalkPlan —
the *size* of this corpus is what the paper's CoreWalk shrinks. Training
samples (center, context) pairs exactly like word2vec: uniform walk, uniform
position, uniform offset in [1, window] with random sign (equivalent to the
standard dynamic-window trick in expectation), and draws K negatives from the
unigram^0.75 noise distribution over corpus token counts.

Epoch accounting follows the paper: one epoch = ``pairs_per_walk * n_real``
sampled pairs, so a smaller corpus (CoreWalk / k-core) trains in
proportionally fewer steps — the hardware-independent speedup.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.corewalk import WalkPlan
from repro.graph.csr import EllGraph
from repro.walks.engine import node2vec_walks, random_walks

__all__ = ["WalkCorpus", "build_corpus", "sample_batch"]


@dataclasses.dataclass
class WalkCorpus:
    walks: jnp.ndarray  # (W, L) int32, padding walks included
    n_real: int  # number of real (non-padding) walks
    length: int
    noise_cdf: jnp.ndarray  # (V,) float32 cumulative unigram^0.75
    n_nodes: int

    @property
    def n_tokens(self) -> int:
        return self.n_real * self.length

    def pairs_per_epoch(self, window: int) -> int:
        # every position pairs with ~window contexts on average (edge-clipped)
        return self.n_real * self.length * window


def build_corpus(
    ell: EllGraph,
    plan: WalkPlan,
    length: int,
    key,
    *,
    p: float = 1.0,
    q: float = 1.0,
    chunk: int = 65536,
) -> WalkCorpus:
    """Run the plan's walks in bounded-memory chunks and assemble the corpus."""
    roots = jnp.asarray(plan.roots)
    outs = []
    for start in range(0, plan.n_slots, chunk):
        sub = roots[start : start + chunk]
        k = jax.random.fold_in(key, start)
        if p == 1.0 and q == 1.0:
            outs.append(random_walks(ell, sub, length, k))
        else:
            outs.append(node2vec_walks(ell, sub, length, k, p=p, q=q))
    walks = jnp.concatenate(outs, axis=0) if len(outs) > 1 else outs[0]

    # slice on the host: a device slice would compile once per walk count
    counts = np.bincount(
        np.asarray(walks)[: plan.n_real].reshape(-1), minlength=ell.n_nodes
    ).astype(np.float64)
    probs = counts**0.75
    total = probs.sum()
    probs = probs / total if total > 0 else np.full_like(probs, 1.0 / len(probs))
    cdf = jnp.asarray(np.cumsum(probs), dtype=jnp.float32)
    return WalkCorpus(
        walks=walks,
        n_real=plan.n_real,
        length=length,
        noise_cdf=cdf,
        n_nodes=ell.n_nodes,
    )


@partial(jax.jit, static_argnames=("batch", "n_neg"))
def _sample(walks, noise_cdf, key, batch, window, n_neg, length, n_real):
    kw, kp, ko, ks, kn = jax.random.split(key, 5)
    w = jax.random.randint(kw, (batch,), 0, n_real)
    i = jax.random.randint(kp, (batch,), 0, length)
    off = jax.random.randint(ko, (batch,), 1, window + 1)
    sign = jax.random.bernoulli(ks, 0.5, (batch,)).astype(jnp.int32) * 2 - 1
    j = i + sign * off
    # reflect at the boundaries (keeps offset magnitude, stays in-walk)
    j = jnp.where(j < 0, i + off, j)
    j = jnp.where(j >= length, i - off, j)
    centers = walks[w, i]
    contexts = walks[w, j]
    u = jax.random.uniform(kn, (batch, n_neg))
    negatives = jnp.searchsorted(noise_cdf, u).astype(jnp.int32)
    negatives = jnp.minimum(negatives, noise_cdf.shape[0] - 1)
    return centers, contexts, negatives


def sample_batch(corpus: WalkCorpus, key, *, batch: int, window: int, n_neg: int):
    """-> centers (B,), contexts (B,), negatives (B, K) int32 node ids."""
    return _sample(
        corpus.walks,
        corpus.noise_cdf,
        key,
        batch,
        window,
        n_neg,
        corpus.length,
        corpus.n_real,
    )
