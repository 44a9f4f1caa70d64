"""Refresh cells: the ingest stream with the paper's refresh after every block.

The mix (``traffic/<name>.json``, ``kind`` ``refresh``) is an ingest mix
(``block``, ``churn``, ``warmup_blocks``; ``cells/ingest.py``'s ``Stream``)
plus ``refresh_every_blocks``, which must be 1: the configuration's
``refresh`` block sets the drift threshold to 0, so the service refreshes in
the sync tail of every block, inserts and retractions alike.

Set-up builds the service through ``build_service`` with the
configuration's refresh settings (``RetrainConfig``: CoreWalk walks on the
k0-core, warm-started SGNS, Procrustes alignment, §2.2 propagation, a
chunked swap into the store), attached in auto mode, and applies the
stream's first ``warmup_blocks`` blocks, each with its refresh. The window
is the ingest cell's: blocks one after the other until ``--seconds`` have
passed, then the ``sync()`` that lands the last block and runs its refresh;
``ingest_edges_per_s`` counts the edges applied over that time, refreshes
included.

Correct means, against the benchmark's own record of the edge set and the
float64 reference ``refs/refresh.py`` (limits in ``checks/refresh.json``):

* ``core_mismatches``: core numbers against a peeling of the edge set;
* ``walk_bad_steps``: steps of the last refresh's real walks that are not
  edges of the k0-core of the final graph, walks not rooted where the
  CoreWalk budgets put them, and per-node walk counts off their budgets;
* ``sgns_step_max_abs``: one program step (loss, gradients, Adam) at the
  timed shape, on a batch sampled from the last refresh's corpus with its
  final tables and Adam state, against the reference step;
* ``untrained_core_rows``: k0-core rows the last refresh left equal to their
  warm start;
* ``align_max_abs``: the stored k0-core rows against the reference rotation
  of the program's own trained table onto its anchors;
* ``prop_max_abs``: the stored shell rows against the reference §2.2
  propagation of the stored k0-core rows;
* ``stored_mismatches``: served rows not bit for bit those the last refresh
  staged.

The control is the refresh one precision below: the SGNS step on bfloat16
tables, the rotation skipped (the trained table stored as it is), and the
shell rows rounded to bfloat16.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from ..graphgen import canonical_edges
from ..refs import refresh as ref
from ..refs.cores import core_numbers
from .ingest import Cell as _Ingest
from .ingest import EdgeSet, Stream

__all__ = ["Cell", "refresh_config"]


def refresh_config(config: dict, seed: int):
    """The configuration's ``refresh`` block as the program's settings."""
    from repro.serve.retrain import RetrainConfig
    from repro.skipgram.trainer import SGNSConfig

    r = config["refresh"]
    return RetrainConfig(
        method=r["method"], n_walks=r["n_walks"],
        walk_length=r["walk_length"],
        sgns=SGNSConfig(dim=config["dim"], window=r["window"],
                        n_neg=r["n_neg"], batch=r["sgns_batch"],
                        epochs=r["epochs"], lr=r["lr"], seed=seed,
                        impl=r.get("impl", "auto")),
        warm_start=r["warm_start"], min_sgns_steps=r["min_sgns_steps"],
        align=r["align"], propagate=r["propagate"],
        prop_iters=config["prop_iters"], swap_chunk=r["swap_chunk"],
        seed=seed,
    )


class Cell(_Ingest):
    kind = "refresh"

    # ------------------------------------------------------------ set-up

    def setup(self, graph) -> None:
        from repro.graph.csr import Graph
        from repro.launch.serve_embed import build_service

        if int(self.traffic["refresh_every_blocks"]) != 1:
            raise ValueError("the auto path refreshes after every block: "
                             "refresh_every_blocks must be 1")
        n, edges = graph
        c = self.cfg
        svc, held, _, k0 = build_service(
            Graph.from_edges(n, edges), stream_frac=c["stream_frac"],
            k0_frac=c["k0_frac"], dim=c["dim"], batch=c["batch"],
            capacity=c["capacity"], compact_every=c["compact_every"],
            train=False, prop_iters=c["prop_iters"], seed=self.seed,
            shards=c["shards"], refresh=refresh_config(c, self.seed),
            retrain_threshold=c["refresh"]["threshold"],
        )
        self.svc = svc
        self.n = n
        self.k0 = k0
        held = canonical_edges(n, held)
        self.model = EdgeSet(n, edges)
        self.model.retract(held)
        self.stream = Stream(
            held, block=self.traffic["block"], churn=self.traffic["churn"],
            rng=np.random.default_rng([self.seed, 1]),
        )
        self.blocks = self.stream.blocks()
        self.refused = 0
        for _ in range(int(self.traffic["warmup_blocks"])):
            self._apply()
        svc.sync()

    # ------------------------------------------------------------ window

    def window(self, seconds: float) -> Dict:
        st = self.svc.stats
        before = (st.retrains, st.retrain_failures)
        res = super().window(seconds)
        refreshes = st.retrains - before[0]
        failures = st.retrain_failures - before[1]
        res["failed"] = int(res["failed"] or failures > 0
                            or refreshes != res["attempted"])
        res["notes"].append(f"{refreshes} refreshes, {failures} rolled back")
        return res

    # ------------------------------------------------------------ check

    def collect(self) -> None:
        """Keep the last refresh's record and the store's rows, then let
        the service go."""
        svc = self.svc
        self.rec = svc.retrainer.last
        self.sgns_cfg = svc.retrainer.cfg.sgns
        self.stored, self.found, _, _ = svc.store.peek_many(
            np.arange(self.n))
        super().collect()

    def _step(self, bf16: bool) -> float:
        """Max gap between one program step on the last refresh's tables
        (in bfloat16 where ``bf16``) and the float64 reference step."""
        import jax
        import jax.numpy as jnp

        from repro.skipgram.corpus import sample_batch
        from repro.skipgram.trainer import sgns_step

        rec, cfg = self.rec, self.sgns_cfg
        key = jax.random.PRNGKey(self.seed + 17)
        ids = [np.asarray(a) for a in sample_batch(
            rec.corpus, key, batch=cfg.batch, window=cfg.window,
            n_neg=cfg.n_neg)]
        adam = rec.sgns.opt_state[0]
        dtype = jnp.bfloat16 if bf16 else jnp.float32
        params = {k: jnp.array(v, dtype) for k, v in rec.sgns.params.items()}
        state = jax.tree.map(jnp.copy, rec.sgns.opt_state)
        got, _, loss = sgns_step(params, state, rec.corpus, key, cfg)
        want_loss, want, _, _ = ref.sgns_step(
            np.asarray(rec.sgns.params["emb_in"]),
            np.asarray(rec.sgns.params["emb_out"]),
            {k: np.asarray(v) for k, v in adam.mu.items()},
            {k: np.asarray(v) for k, v in adam.nu.items()},
            int(adam.count), *ids, lr=cfg.lr)
        gap = abs(float(loss) - want_loss)
        for k, v in want.items():
            gap = max(gap, float(np.max(np.abs(
                np.asarray(got[k], np.float64) - v))))
        return gap

    def readings(self, control: bool = False) -> Dict[str, float]:
        """The numbers compared; ``control`` gives the control's."""
        r = self.cfg["refresh"]
        rec = self.rec
        edges = self.model.edges()
        core = core_numbers(self.n, edges)
        got = np.zeros(self.n, np.int32)
        got[: min(len(self.cores), self.n)] = self.cores[: self.n]
        k0 = max(1, min(self.k0, int(core.max())))
        in_core = core >= k0
        walks = np.asarray(rec.corpus.walks)[: rec.walk_plan.n_real]
        trained = rec.sgns.embeddings
        untrained = np.all(trained == rec.warm, axis=1) & in_core
        nodes, vecs, _ = rec.staged
        shell = np.zeros(self.n, bool)
        shell[nodes] = True
        shell &= ~in_core
        stored = self.stored.astype(np.float64)
        aligned = ref.align(trained, rec.anchors, rec.anchor_vecs)
        prop = ref.propagate(self.n, edges, core, k0,
                             np.where(in_core[:, None], stored, 0.0),
                             self.cfg["prop_iters"])
        if control:  # no rotation; shell rows in bfloat16
            stored[in_core] = trained[in_core]
            stored[shell] = _bf16(stored[shell])
        same = np.all(self.stored[nodes] == vecs, axis=1) & self.found[nodes]
        return {
            "core_mismatches": int(np.sum(got != core)),
            "walk_bad_steps": ref.walk_faults(walks, self.n, edges, core, k0,
                                              r["n_walks"]),
            "sgns_step_max_abs": self._step(bf16=control),
            "untrained_core_rows": int(untrained.sum()),
            "align_max_abs": float(np.max(
                np.abs(stored[in_core] - aligned[in_core]), initial=0.0)),
            "prop_max_abs": float(np.max(
                np.abs(stored[shell] - prop[shell]), initial=0.0)),
            "stored_mismatches": int(np.sum(~same)),
        }

    def check(self) -> Dict[str, float]:
        return self.readings()

    def control(self) -> Dict[str, float]:
        """Readings of the refresh one precision below: a bfloat16 SGNS
        step, no rotation, and shell rows rounded to bfloat16."""
        return self.readings(control=True)


def _bf16(x: np.ndarray) -> np.ndarray:
    import jax.numpy as jnp

    return np.asarray(jnp.asarray(x, jnp.bfloat16).astype(jnp.float32),
                      np.float64)
