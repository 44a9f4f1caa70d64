"""``correct`` comes out false for the refresh cell's control and for every
fault a refresh can have, planted under a run of a tiny refresh cell on the
CPU.

Faults: an SGNS step in bfloat16; one walk step off the k0-core's edges; a
table stored without its rotation; a shell left unpropagated; a refresh
that takes no SGNS step.
"""
import contextlib

import numpy as np
import pytest

from benchmarks.chip import harness
from benchmarks.chip.graphgen import make_graph
from chipbench_tiny import run_tiny
from chipbench_tiny_refresh import CELL, tiny_refresh_root


def test_refresh_control_is_not_correct(tmp_path):
    spec = harness.Spec(tiny_refresh_root(tmp_path), CELL)
    c = spec.driver(2**31 + 11, lambda name: contextlib.nullcontext())
    c.setup(make_graph(spec.config))
    c.window(1.0)
    c.collect()
    prog, ctrl = c.check(), c.control()
    assert all(v <= spec.limits[k] for k, v in prog.items()), prog
    # one precision below fails on every number it moves
    for k in ("sgns_step_max_abs", "align_max_abs", "prop_max_abs"):
        assert ctrl[k] > spec.limits[k], (k, ctrl)


def _bf16_step(mp):
    import jax.numpy as jnp

    from repro.skipgram import trainer

    orig = trainer.sgns_step

    def bf16(params, opt_state, corpus, key, cfg):
        low = {k: v.astype(jnp.bfloat16) for k, v in params.items()}
        out, opt_state, loss = orig(low, opt_state, corpus, key, cfg)
        return ({k: v.astype(jnp.float32) for k, v in out.items()},
                opt_state, loss)

    mp.setattr(trainer, "sgns_step", bf16)


def _off_edge_walk_step(mp):
    from repro.serve import retrain

    orig = retrain.build_corpus

    def off(*a, **kw):
        corpus = orig(*a, **kw)
        w = corpus.walks
        corpus.walks = w.at[0, 1].set(w[0, 0])  # no node is its own neighbour
        return corpus

    mp.setattr(retrain, "build_corpus", off)


def _unrotated_table(mp):
    from repro.serve.retrain import EmbeddingAligner

    mp.setattr(EmbeddingAligner, "align",
               lambda self, new_emb, old_vecs, anchors: (
                   np.asarray(new_emb, np.float32),
                   {"aligned": True, "anchors": len(anchors),
                    "residual": 0.0}))


def _unpropagated_shell(mp):
    from repro.serve import retrain

    mp.setattr(retrain, "propagate",
               lambda g, core, k0, emb, **kw: np.array(emb, np.float32))


def _no_sgns_steps(mp):
    from repro.serve import retrain

    orig = retrain.train_sgns
    mp.setattr(retrain, "train_sgns",
               lambda corpus, cfg, *, params=None, steps=None:
               orig(corpus, cfg, params=params, steps=0))


@pytest.mark.parametrize("fault", [
    _bf16_step, _off_edge_walk_step, _unrotated_table, _unpropagated_shell,
    _no_sgns_steps,
])
def test_a_planted_refresh_fault_is_not_correct(tmp_path, monkeypatch,
                                                fault):
    fault(monkeypatch)
    res = run_tiny(tiny_refresh_root(tmp_path), CELL, seconds=1.0)
    assert res["correct"] is False, res["checks"]
