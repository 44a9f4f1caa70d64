"""SGNS trainer: jit'd step, epoch accounting proportional to corpus size.

The paper's speedups come from corpus reduction; this trainer makes that
explicit: ``steps = pairs_per_epoch(window) * epochs / batch``. Wall-clock on
this CPU container tracks step count (same step shape for all plans), so the
paper's speedup columns are reproduced both in wall-clock and in step counts.

The steps train only the corpus's support, the tokens of its real walks (a
refresh's k0-core, ~1.5k of 12.5k nodes): ``train_sgns`` gathers those rows
into core-local tables of a multiple of ``LOCAL_BUCKET`` rows, relabels the
walks, restricts the noise cdf to them, and scatters the trained rows back
into full tables once at the end. It is the same computation as the full
tables': ``_sample``'s draws do not depend on the table, ``searchsorted`` on
the restricted cdf returns the local row of exactly the node the full cdf
returns, and outside the support gradients, Adam's moments and updates are
exactly zero. The one exception is a draw of ``u == 0.0`` exactly (about
2⁻²³ per draw), where the full cdf returns node 0 whatever its count and the
restricted one the first support node, which is what unigram^0.75 sampling
asks for.
"""
from __future__ import annotations

import dataclasses
import time
from functools import lru_cache, partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import trace as obs
from repro.train import optim

from .corpus import WalkCorpus, sample_batch
from .model import batch_loss, init_params

__all__ = ["SGNSConfig", "SGNSResult", "train_sgns", "sgns_step",
           "LOCAL_BUCKET"]

# core-local tables are padded to a multiple of this many rows, so a support
# that churn moves by a few nodes (1,460-1,470 in a refresh) keeps one shape
# (1,536 rows) and the step never recompiles
LOCAL_BUCKET = 512


@dataclasses.dataclass
class SGNSConfig:
    dim: int = 150  # paper §3.1.2
    window: int = 4
    n_neg: int = 5
    batch: int = 4096
    epochs: float = 1.0
    lr: float = 0.025
    seed: int = 0
    impl: str = "auto"  # kernel dispatch: auto | ref | pallas | pallas_interpret


@dataclasses.dataclass
class SGNSResult:
    embeddings: np.ndarray  # (V, dim) float32 — emb_in
    n_steps: int
    train_seconds: float
    final_loss: float
    params: Any = None  # the trained tables, on the device
    opt_state: Any = None  # Adam's state after the last step
    rows: int = 0  # rows of the tables the steps trained


@partial(jax.jit, static_argnames=("impl", "window", "n_neg", "batch", "opt_update"), donate_argnums=(0, 1))
def _train_step(params, opt_state, walks_nreal_cdf, key, *, impl, window, n_neg, batch, opt_update):
    walks, n_real, noise_cdf = walks_nreal_cdf
    from .corpus import _sample  # jit-inlined

    centers, contexts, negatives = _sample(
        walks, noise_cdf, key, batch, window, n_neg, walks.shape[1], n_real
    )
    loss, grads = jax.value_and_grad(batch_loss)(
        params, centers, contexts, negatives, impl
    )
    updates, opt_state = opt_update(grads, opt_state, params)
    params = optim.apply_updates(params, updates)
    return params, opt_state, loss


@lru_cache(maxsize=None)
def _adam(lr: float) -> optim.GradientTransform:
    """One optimizer per learning rate: ``_train_step`` compiles per update
    function, so a fresh closure on every call would recompile it."""
    return optim.adam(lr)


def sgns_step(params, opt_state, corpus: WalkCorpus, key, cfg: SGNSConfig):
    """One jitted step: sample a batch from ``corpus`` with ``key``, loss,
    gradients and Adam update. Donates ``params`` and ``opt_state``; returns
    ``(params, opt_state, loss)``."""
    return _train_step(
        params,
        opt_state,
        (corpus.walks, corpus.n_real, corpus.noise_cdf),
        key,
        impl=cfg.impl,
        window=cfg.window,
        n_neg=cfg.n_neg,
        batch=cfg.batch,
        opt_update=_adam(cfg.lr).update,
    )


@jax.jit
def _to_local(params, walks, noise_cdf, idx, g2l):
    """Core-local tables (rows ``idx``; padding rows, ``idx == V``, zero),
    walks relabelled by ``g2l`` and the cdf at ``idx`` (padding rows take
    its last value, which the support's last row already holds)."""
    local = {k: jnp.take(v, idx, axis=0, mode="fill", fill_value=0)
             for k, v in params.items()}
    return local, g2l[walks], jnp.take(noise_cdf, idx, mode="clip")


@jax.jit
def _to_full(params, local, opt_state, idx):
    """Full tables again: support rows from the local tables, the others
    from ``params``; Adam's moments zero outside the support."""
    n = params["emb_in"].shape[0]

    def full(x, base=None):
        if x.ndim == 0:
            return x
        if base is None:
            base = jnp.zeros((n,) + x.shape[1:], x.dtype)
        return base.at[idx].set(x, mode="drop")

    return ({k: full(local[k], params[k]) for k in params},
            jax.tree.map(full, opt_state))


def _local_ids(corpus: WalkCorpus):
    """``(idx, g2l)`` for the corpus's support, or None where its tables
    would be no smaller than the full ones (or its counts are unknown):
    ``idx`` the support's node ids, ascending, padded with ``V`` to a
    multiple of ``LOCAL_BUCKET``; ``g2l`` each node's local row (0 off the
    support, which only padding walks read and no draw samples)."""
    if corpus.counts is None or len(corpus.counts) != corpus.n_nodes:
        return None
    support = np.flatnonzero(corpus.counts)
    rows = -(-support.size // LOCAL_BUCKET) * LOCAL_BUCKET
    if support.size == 0 or rows >= corpus.n_nodes:
        return None
    idx = np.full(rows, corpus.n_nodes, np.int32)
    idx[: support.size] = support
    g2l = np.zeros(corpus.n_nodes, np.int32)
    g2l[support] = np.arange(support.size, dtype=np.int32)
    return idx, g2l


def train_sgns(
    corpus: WalkCorpus, cfg: SGNSConfig, *, params=None, steps: Optional[int] = None
) -> SGNSResult:
    """``steps`` SGNS steps (an epoch count's worth by default) from
    ``params`` (seeded init by default) on the corpus's support; returns
    full tables, with Adam's state over them."""
    key = jax.random.PRNGKey(cfg.seed)
    kinit, ktrain = jax.random.split(key)
    if params is None:
        params = init_params(corpus.n_nodes, cfg.dim, kinit)
    if steps is None:
        steps = max(1, int(cfg.epochs * corpus.pairs_per_epoch(cfg.window) // cfg.batch))

    loss = jnp.zeros(())
    t0 = time.perf_counter()
    with obs.span("sgns.steps", steps=steps):
        ids = _local_ids(corpus)
        train, train_corpus = params, corpus
        if ids is not None:
            idx, g2l = (jnp.asarray(a) for a in ids)
            train, walks, cdf = _to_local(
                params, corpus.walks, corpus.noise_cdf, idx, g2l)
            train_corpus = dataclasses.replace(
                corpus, walks=walks, noise_cdf=cdf, n_nodes=len(ids[0]),
                counts=None)
        rows = int(train["emb_in"].shape[0])
        opt_state = _adam(cfg.lr).init(train)
        for s in range(steps):
            train, opt_state, loss = sgns_step(
                train, opt_state, train_corpus, jax.random.fold_in(ktrain, s), cfg
            )
        if ids is not None:
            train, opt_state = _to_full(params, train, opt_state, idx)
    with obs.span("sgns.wait"):
        loss = float(loss)
        emb = np.asarray(train["emb_in"], dtype=np.float32)
    dt = time.perf_counter() - t0
    return SGNSResult(
        embeddings=emb,
        n_steps=steps,
        train_seconds=dt,
        final_loss=loss,
        params=train,
        opt_state=opt_state,
        rows=rows,
    )
