"""SGNS trainer: jit'd step, epoch accounting proportional to corpus size.

The paper's speedups come from corpus reduction; this trainer makes that
explicit: ``steps = pairs_per_epoch(window) * epochs / batch``. Wall-clock on
this CPU container tracks step count (same step shape for all plans), so the
paper's speedup columns are reproduced both in wall-clock and in step counts.
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

__all__ = ["SGNSConfig", "SGNSResult", "train_sgns", "sgns_step"]


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


def train_sgns(
    corpus: WalkCorpus, cfg: SGNSConfig, *, params=None, steps: Optional[int] = None
) -> SGNSResult:
    key = jax.random.PRNGKey(cfg.seed)
    kinit, ktrain = jax.random.split(key)
    if params is None:
        params = init_params(corpus.n_nodes, cfg.dim, kinit)
    opt_state = _adam(cfg.lr).init(params)
    if steps is None:
        steps = max(1, int(cfg.epochs * corpus.pairs_per_epoch(cfg.window) // cfg.batch))

    loss = jnp.zeros(())
    t0 = time.perf_counter()
    with obs.span("sgns.steps", steps=steps):
        for s in range(steps):
            params, opt_state, loss = sgns_step(
                params, opt_state, corpus, jax.random.fold_in(ktrain, s), cfg
            )
    with obs.span("sgns.wait"):
        loss = float(loss)
        emb = np.asarray(params["emb_in"], dtype=np.float32)
    dt = time.perf_counter() - t0
    return SGNSResult(
        embeddings=emb,
        n_steps=steps,
        train_seconds=dt,
        final_loss=loss,
        params=params,
        opt_state=opt_state,
    )
