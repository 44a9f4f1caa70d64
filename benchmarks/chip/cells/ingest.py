"""Ingest cells: a closed-loop stream of edge blocks through the service.

The mix (``traffic/<name>.json``, ``kind`` ``ingest``) gives ``block``,
``churn`` and ``warmup_blocks``. ``Stream`` turns it into blocks that never
run dry:

* phase 1 inserts the held-out edges in blocks of ``block`` and, after each
  insert block, retracts ``round(churn * len(block))`` edges drawn uniformly
  from the streamed edges that are live (the churn loop of the service's
  ``stream_with_churn``, kept here so the benchmark owns it);
* phase 2 begins once every held-out edge has been inserted: each step
  retracts the ``block`` oldest live streamed edges and re-inserts ``block``
  edges from the front of the retracted pool (oldest retraction first).

Retracted edges join the pool, so the graph always lies between the base
graph and the full graph, and no edge is inserted while present or retracted
while absent. Everything is drawn from the run's seed; every seed gets blocks
of the same sizes.

Set-up builds the service and applies the stream's first ``warmup_blocks``
blocks; the window continues the same stream through
``EmbeddingService.ingest_block`` / ``retract_block``, one block after the
other, until ``--seconds`` have passed, then ``sync()``\\ s the last one.
``ingest_edges_per_s`` is the edges applied in the window (accepted inserts
plus retractions) over the time from the window's start to the return of that
``sync()``.

Correct means that the core numbers the window left equal a Matula–Beck
peeling of the benchmark's own record of the edge set (``core_mismatches``
is 0). A block the service did not apply in full (an insert not accepted, a
retraction not removed) counts as failed. Core numbers have no lower
precision, so the control breaks the guarantee the way a faster program
would be tempted to: it gives the cores of the graph without the window's
last block, as if that block's repair had been skipped.
"""
from __future__ import annotations

import time
from typing import Dict

import numpy as np

from ..graphgen import canonical_edges
from ..refs.cores import core_numbers

__all__ = ["Cell", "Stream", "EdgeSet"]


class Stream:
    """Infinite stream of ``("insert" | "retract", (m, 2) edges)`` blocks."""

    def __init__(self, held: np.ndarray, *, block: int, churn: float,
                 rng: np.random.Generator):
        self.block = int(block)
        self.churn = float(churn)
        self.rng = rng
        held = np.asarray(held, np.int64).reshape(-1, 2)
        self._held = held[rng.permutation(len(held))]
        self._next_held = 0
        # streamed and present, oldest first
        self._live = np.zeros((0, 2), np.int64)
        self._pool = np.zeros((0, 2), np.int64)  # retracted, oldest first
        self.phase = 1

    def _insert_from_pool(self, m: int) -> np.ndarray:
        take, self._pool = self._pool[:m], self._pool[m:]
        return take

    def _retract(self, idx: np.ndarray) -> np.ndarray:
        keep = np.ones(len(self._live), bool)
        keep[idx] = False
        gone = self._live[idx]
        self._live = self._live[keep]
        self._pool = np.concatenate([self._pool, gone])
        return gone

    def blocks(self):
        """Generator of the stream's blocks; call it once per stream."""
        B = self.block
        while True:
            if self._next_held < len(self._held):
                ins = self._held[self._next_held:self._next_held + B]
                self._next_held += len(ins)
                if len(ins) < B:
                    ins = np.concatenate(
                        [ins, self._insert_from_pool(B - len(ins))])
                self._live = np.concatenate([self._live, ins])
                yield "insert", ins
                r = min(int(round(self.churn * len(ins))), len(self._live))
                if r:
                    pick = self.rng.choice(len(self._live), size=r,
                                           replace=False)
                    yield "retract", self._retract(np.sort(pick))
            else:
                self.phase = 2
                r = min(B, len(self._live))
                if r:
                    yield "retract", self._retract(np.arange(r))
                ins = self._insert_from_pool(B)
                if not len(ins):
                    raise RuntimeError("ingest stream has no edges to insert")
                self._live = np.concatenate([self._live, ins])
                yield "insert", ins


class EdgeSet:
    """The benchmark's own record of which edges the graph holds."""

    def __init__(self, n: int, edges: np.ndarray):
        self.n = int(n)
        self.keys = set((edges[:, 0] * n + edges[:, 1]).tolist())

    def _keys(self, e: np.ndarray):
        e = np.sort(np.asarray(e, np.int64).reshape(-1, 2), axis=1)
        return (e[:, 0] * self.n + e[:, 1]).tolist()

    def insert(self, e: np.ndarray) -> None:
        self.keys.update(self._keys(e))

    def retract(self, e: np.ndarray) -> None:
        self.keys.difference_update(self._keys(e))

    def edges(self) -> np.ndarray:
        k = np.fromiter(self.keys, np.int64, len(self.keys))
        return np.stack([k // self.n, k % self.n], axis=1)


class Cell:
    kind = "ingest"

    def __init__(self, config: dict, traffic: dict, seed: int, annotate):
        self.cfg = config
        self.traffic = traffic
        self.seed = int(seed)
        self.annotate = annotate
        self.svc = None

    # ------------------------------------------------------------ set-up

    def setup(self, graph) -> None:
        from repro.graph.csr import Graph
        from repro.launch.serve_embed import build_service

        n, edges = graph
        c = self.cfg
        svc, held, _, _ = build_service(
            Graph.from_edges(n, edges), stream_frac=c["stream_frac"],
            k0_frac=c["k0_frac"], dim=c["dim"], batch=c["batch"],
            capacity=c["capacity"], compact_every=c["compact_every"],
            train=False, prop_iters=c["prop_iters"], seed=self.seed,
            shards=c["shards"],
        )
        self.svc = svc
        self.n = n
        held = canonical_edges(n, held)
        self.model = EdgeSet(n, edges)
        self.model.retract(held)
        self.stream = Stream(
            held, block=self.traffic["block"], churn=self.traffic["churn"],
            rng=np.random.default_rng([self.seed, 1]),
        )
        self.blocks = self.stream.blocks()
        self.refused = 0  # inserts not accepted + retractions not removed
        for _ in range(int(self.traffic["warmup_blocks"])):
            self._apply()
        svc.sync()

    def _apply(self) -> int:
        kind, e = next(self.blocks)
        self.last = (kind, e)
        with self.annotate(f"bench.{kind}_block"):
            if kind == "insert":
                done = len(self.svc.ingest_block(e))
            else:
                done = int(self.svc.retract_block(e))
        getattr(self.model, kind)(e)
        self.refused += len(e) - done
        return done

    # ------------------------------------------------------------ window

    def window(self, seconds: float) -> Dict:
        svc = self.svc
        before = dict(svc.cores.policy.decisions)
        refused0 = self.refused
        blocks = applied = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            applied += self._apply()
            blocks += 1
        with self.annotate("bench.sync"):
            svc.sync()
        t1 = time.perf_counter()
        after = svc.cores.policy.decisions
        decisions = {k: after[k] - before.get(k, 0) for k in after}
        return {
            "t0": t0, "t1": t1, "attempted": blocks,
            "failed": int(self.refused > refused0),
            "e2e": {"ingest_edges_per_s": applied / (t1 - t0)},
            "counters": {"blocks": blocks,
                         "descend_decisions": decisions.get("descend", 0),
                         "repeel_decisions": decisions.get("repeel", 0)},
            "samples": {},
            "notes": [f"stream phase {self.stream.phase}, {blocks} blocks, "
                      f"{applied} edges applied",
                      f"repair decisions in the window: {decisions}"],
        }

    # ------------------------------------------------------------ check

    def collect(self) -> None:
        """Read what the window left, then let the service go."""
        self.cores = np.array(self.svc.cores.core, np.int32)
        self.svc = None

    def readings(self, cores: np.ndarray) -> Dict[str, float]:
        edges = self.model.edges()
        want = core_numbers(self.n, edges)
        got = np.zeros(self.n, np.int32)
        got[: min(len(cores), self.n)] = cores[: self.n]
        return {"core_mismatches": int(np.sum(got != want))}

    def check(self) -> Dict[str, float]:
        return self.readings(self.cores)

    def control(self) -> Dict[str, float]:
        """Readings of the cores of the graph before the window's last
        block."""
        kind, e = self.last
        undo = EdgeSet(self.n, self.model.edges())
        getattr(undo, "retract" if kind == "insert" else "insert")(e)
        return self.readings(core_numbers(self.n, undo.edges()))
