"""Online retraining subsystem: drift-triggered CoreWalk+SGNS refresh.

``EmbeddingService.should_retrain()`` detects k0-core membership drift; this
module closes the loop that acts on it. The paper's whole economy (walks and
SGNS restricted to the k0-core, §2.1/§2.2 propagation for everyone else)
makes the refresh cheap enough to run *online*: the drifted subcore is a
small fraction of the graph, and the previous run's vectors warm-start the
new one, so a refresh is a few SGNS epochs on a subgraph — not a cold
offline rebuild.

Four stages, each its own component so tests/benchmarks can drive them
separately:

* :class:`RetrainPlanner` — snapshots the **drifted k0-core** from the live
  ``DynamicGraph`` (one vectorized ``snapshot()`` CSR conversion) using the
  maintainer's *exact* incremental core numbers — no re-peel needed — and
  clamps k0 to the current degeneracy (deletion churn can lower it).
* :class:`Retrainer` — re-runs CoreWalk walks + SGNS on the subcore (the
  same components ``core/pipeline.embed_graph`` composes: ``corewalk_plan``
  -> ``build_corpus`` -> ``train_sgns``), **warm-starting** ``emb_in`` rows
  from the previous vectors of nodes that persist in the store.
* :class:`EmbeddingAligner` — SGNS is rotation-invariant, so a fresh run
  lands in an arbitrarily rotated copy of the old space. Orthogonal
  Procrustes on **stable anchor nodes** (in-core, core number unchanged
  since the last refresh, previous vector held) maps the new table back
  into the old space, so mixed-version ``gather`` results and §2.2
  cold-start propagation stay mutually comparable during rollout.
* :class:`VersionRollout` — stages the aligned table off to the side (the
  store's double buffer) and hot-swaps it: ``bump_version`` then **chunked**
  ``put_many`` scatters, optionally yielding to the serving loop between
  chunks, so query flushes interleave with the swap and p99 is unaffected.
  Rows not refreshed keep their old version (the store's per-row version
  tags reconcile the mixture); sharded stores swap through the same
  ``ShardPlan`` scatter path, so the rollout composes with ``--shards N``.

``Retrainer.run()`` chains the four stages and finishes with
``IncrementalCore.mark_refresh()``, resetting the drift baseline the next
trigger measures against. Each stage is a ``retrain.<stage>`` span; a
refresh keeps its shapes from one run to the next (the walk corpus padded
to a multiple of ``WALK_BUCKET`` walks, the subcore's ELL at a power-of-two
width, the SGNS tables at the corpus's support padded to a multiple of
``trainer.LOCAL_BUCKET`` rows, the swap's scatters at ``swap_chunk`` rows),
so a service that refreshes after every block compiles nothing once warm.
``Retrainer.last`` keeps what the latest refresh computed, for inspection.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, Optional

import jax
import numpy as np

from repro.core.corewalk import (
    WalkPlan,
    corewalk_plan,
    deepwalk_plan,
    plan_from_counts,
)
from repro.obs import metrics
from repro.obs import trace as obs
from repro.core.kcore import degeneracy, kcore_subgraph
from repro.core.propagation import propagate
from repro.graph.csr import Graph
from repro.skipgram.corpus import WalkCorpus, build_corpus
from repro.skipgram.model import init_params
from repro.skipgram.trainer import SGNSConfig, SGNSResult, train_sgns

from . import faults
from .kcore_inc import IncrementalCore
from .store import EmbeddingStore
from .stream import DynamicGraph
from .util import pow2

__all__ = [
    "RetrainConfig",
    "RetrainPlan",
    "RetrainPlanner",
    "RetrainReport",
    "RefreshRecord",
    "Retrainer",
    "EmbeddingAligner",
    "VersionRollout",
    "procrustes_rotation",
]


# walks per corpus bucket: the corpus is padded to a multiple of this, so a
# refresh's walk and SGNS programs keep their shapes while churn moves the
# CoreWalk budget by a few walks
WALK_BUCKET = 1024


@contextlib.contextmanager
def _stage(stage: str, times: dict, **attrs):
    """One retrain stage: a ``retrain.<stage>`` span (timed whether or not
    tracing is on) whose duration lands in ``times`` and the stage-latency
    histogram."""
    with obs.timed(f"retrain.{stage}", **attrs) as sp:
        yield sp
    times[stage] = sp.t1 - sp.t0
    metrics().histogram("retrain_stage_seconds", stage=stage).observe(
        times[stage])


# --------------------------------------------------------------- planning


@dataclasses.dataclass
class RetrainConfig:
    """Knobs for one drift-triggered refresh (defaults sized for serving)."""

    method: str = "corewalk"  # corewalk | deepwalk (budget plan on the core)
    n_walks: int = 10
    walk_length: int = 20
    sgns: SGNSConfig = dataclasses.field(
        default_factory=lambda: SGNSConfig(dim=64, epochs=0.5)
    )
    warm_start: bool = True  # seed emb_in from the previous vectors
    # epoch accounting scales steps with the (small) subcore corpus; the
    # floor matters because emb_out restarts at zero on every refresh, so
    # the first step's emb_in gradient is exactly zero — a 1-step "refresh"
    # would be a no-op on the served table
    min_sgns_steps: int = 50
    align: bool = True  # Procrustes back into the old space (anchors >= dim)
    propagate: bool = True  # refill every shell below k0 (§2.2) in the swap
    prop_iters: int = 10
    swap_chunk: int = 1024  # put_many rows per rollout chunk
    seed: int = 0


@dataclasses.dataclass
class RetrainPlan:
    """A snapshot of the drifted k0-core, ready to walk and train."""

    snapshot: Graph  # immutable CSR of the whole live graph
    sub: Graph  # induced k0-core subgraph (original node ids)
    core: np.ndarray  # (n,) exact current core numbers (copied)
    baseline: np.ndarray  # (n,) core numbers at the last refresh
    k0: int  # effective k0 (clamped to current degeneracy)
    nodes: np.ndarray  # (m,) k0-core node ids
    drifted: int  # nodes whose (core >= k0) flag flipped since refresh


class RetrainPlanner:
    """Turns the live ``DynamicGraph`` + ``IncrementalCore`` into a plan.

    The maintainer's core numbers are exact (oracle-checked elsewhere), so
    planning costs one vectorized snapshot + one induced-subgraph build —
    no re-peel of the full graph.
    """

    def __init__(self, graph: DynamicGraph, cores: IncrementalCore, k0: int):
        if k0 is None or k0 < 1:
            raise ValueError(f"k0 must be a positive int, got {k0!r}")
        self.graph = graph
        self.cores = cores
        self.k0 = int(k0)

    def plan(self) -> RetrainPlan:
        snap = self.graph.snapshot()
        core = self.cores.core.copy()
        base = self.cores.baseline.copy()
        # deletions can drop the degeneracy below the configured k0; an empty
        # subcore would leave nothing to train on
        k0 = max(1, min(self.k0, degeneracy(core)))
        nodes = np.where(core >= k0)[0]
        drifted = int(np.sum((core >= k0) != (base >= k0)))
        return RetrainPlan(
            snapshot=snap,
            sub=kcore_subgraph(snap, core, k0),
            core=core,
            baseline=base,
            k0=k0,
            nodes=nodes,
            drifted=drifted,
        )


# -------------------------------------------------------------- alignment


def procrustes_rotation(X: np.ndarray, Y: np.ndarray) -> np.ndarray:
    """Orthogonal Procrustes: R = argmin_{R orthogonal} ||X R - Y||_F.

    Closed form (Schönemann 1966): with M = Xᵀ Y = U S Vᵀ, R = U Vᵀ.
    R is exactly orthogonal by construction, so applying it preserves row
    norms and pairwise dot products — the property the alignment tests
    assert.
    """
    X = np.asarray(X, np.float64)
    Y = np.asarray(Y, np.float64)
    if X.shape != Y.shape or X.ndim != 2:
        raise ValueError(f"anchor shapes must match, got {X.shape} vs {Y.shape}")
    U, _, Vt = np.linalg.svd(X.T @ Y)
    return (U @ Vt).astype(np.float32)


class EmbeddingAligner:
    """Maps a freshly trained table back into the serving embedding space.

    Anchors should be nodes whose representation has no reason to have
    moved: still in the k0-core, core number unchanged since the last
    refresh, previous vector available. A rotation of dimension d is
    determined only by at least d anchors in general position; with fewer
    the aligner returns the input unchanged (identity), which the report
    flags.
    """

    def align(
        self, new_emb: np.ndarray, old_vecs: np.ndarray, anchors: np.ndarray
    ) -> tuple:
        """-> (aligned (n, d) float32, report dict).

        ``old_vecs`` is (len(anchors), d): the previous vector of each
        anchor node; ``anchors`` indexes rows of ``new_emb``.
        """
        anchors = np.asarray(anchors, np.int64)
        if len(anchors) < new_emb.shape[1]:
            return np.asarray(new_emb, np.float32), {
                "aligned": False,
                "anchors": int(len(anchors)),
                "residual": 0.0,
            }
        X = new_emb[anchors]
        R = procrustes_rotation(X, old_vecs)
        aligned = np.asarray(new_emb, np.float32) @ R
        resid = float(
            np.linalg.norm(aligned[anchors] - old_vecs)
            / max(np.linalg.norm(old_vecs), 1e-12)
        )
        return aligned, {
            "aligned": True,
            "anchors": int(len(anchors)),
            "residual": resid,
        }


# ---------------------------------------------------------------- rollout


class VersionRollout:
    """Double-buffered hot swap of a refreshed table into the store.

    ``stage()`` keeps the new rows host-side (the store's live device table
    is untouched — that is the double buffer); ``commit()`` bumps the store
    version once, then scatters the staged rows in bounded ``chunk``-row
    ``put_many`` batches, invoking ``between()`` after each so the caller
    can interleave query flushes — the serving loop never pauses for a
    monolithic rebuild. Rows the refresh did not cover keep their previous
    version tag; the store's per-row versions (and ``version_counts()``)
    reconcile the mixture, and promotion from spill preserves old tags, so
    mixed-version gathers stay well-defined mid-rollout. Under a
    ``ShardPlan`` every chunk goes through the plan's shard-local scatter,
    so the swap is shard-aware for free.
    """

    def __init__(self, store: EmbeddingStore, *, chunk: int = 1024):
        self.store = store
        self.chunk = max(int(chunk), 1)
        self._staged: Optional[tuple] = None

    def stage(self, nodes: np.ndarray, vecs: np.ndarray, cores: np.ndarray):
        nodes = np.asarray(nodes, np.int64)
        vecs = np.asarray(vecs, np.float32)
        cores = np.broadcast_to(np.asarray(cores, np.int32), nodes.shape)
        if len(nodes) != len(vecs):
            raise ValueError("nodes/vecs row counts differ")
        self._staged = (nodes, vecs, cores)

    def commit(self, between: Optional[Callable[[], None]] = None) -> dict:
        if self._staged is None:
            raise RuntimeError("nothing staged; call stage() first")
        nodes, vecs, cores = self._staged
        self._staged = None
        version = self.store.bump_version()
        chunk_seconds = []
        for s in range(0, len(nodes), self.chunk):
            faults.check("retrain_swap_chunk")
            rows = int(min(self.chunk, len(nodes) - s))
            with obs.timed("retrain.swap_chunk", rows=rows) as sp:
                # every chunk's scatter at the chunk's shape, the last too
                self.store.put_many(
                    nodes[s : s + self.chunk],
                    vecs[s : s + self.chunk],
                    cores[s : s + self.chunk],
                    min_rows=self.chunk,
                )
            chunk_seconds.append(sp.t1 - sp.t0)
            if between is not None:
                between()
        return {
            "version": int(version),
            "rows": int(len(nodes)),
            "chunks": len(chunk_seconds),
            "swap_seconds": float(sum(chunk_seconds)),
            "max_chunk_seconds": float(max(chunk_seconds, default=0.0)),
            "version_counts": self.store.version_counts(),
        }


# -------------------------------------------------------------- retrainer


@dataclasses.dataclass
class RetrainReport:
    k0: int
    core_size: int  # nodes in the retrained subcore
    drifted: int  # membership flips that triggered the refresh
    n_walks: int
    sgns_steps: int
    warm_rows: int  # emb_in rows seeded from previous vectors
    anchors: int
    aligned: bool
    align_residual: float
    version: int  # store version the swap installed
    rows_swapped: int
    swap_chunks: int
    staleness_before: float
    staleness_after: float
    pressure_before: float
    pressure_after: float
    times: dict  # plan / walks / train / align / propagate / swap / total


@dataclasses.dataclass
class RefreshRecord:
    """What one refresh computed, kept by ``Retrainer.last``."""

    plan: RetrainPlan
    walk_plan: WalkPlan  # CoreWalk roots and budgets (n_real of them real)
    corpus: WalkCorpus
    warm: np.ndarray  # (n, d) emb_in before the first SGNS step
    sgns: SGNSResult  # trained emb_in, final tables and Adam state
    anchors: np.ndarray  # (a,) anchor node ids (empty when not aligned)
    anchor_vecs: np.ndarray  # (a, d) their previous vectors
    staged: tuple  # (nodes, vecs, cores) handed to the rollout


class Retrainer:
    """Drives one full detect→snapshot→retrain→align→swap cycle.

    Holds the service only by reference; ``run()`` reads the live graph /
    cores / store through it, and the optional ``between`` callback is
    forwarded to the rollout so callers can keep serving mid-swap.
    """

    def __init__(self, service, cfg: Optional[RetrainConfig] = None):
        if service.k0 is None:
            raise ValueError("service.k0 must be set to retrain (drift gate)")
        self.service = service
        self.cfg = cfg or RetrainConfig()
        self.planner = RetrainPlanner(service.graph, service.cores, service.k0)
        self.aligner = EmbeddingAligner()
        self.last: Optional[RefreshRecord] = None

    # one stage per method so components stay independently testable

    def _walks(self, plan: RetrainPlan) -> tuple:
        """CoreWalk budgets on the subcore and the walk corpus.

        The corpus is padded to a multiple of ``WALK_BUCKET`` walks (padding
        walks are never sampled) and the subcore's ELL is a power of two
        wide, so successive refreshes reuse the same programs.
        """
        cfg = self.cfg
        n = plan.snapshot.n_nodes
        if cfg.method == "corewalk":
            budgets = corewalk_plan(plan.core, cfg.n_walks).per_node
        elif cfg.method == "deepwalk":
            budgets = deepwalk_plan(n, cfg.n_walks).per_node
        else:
            raise ValueError(cfg.method)
        budgets = np.where(plan.core >= plan.k0, budgets, 0)
        wplan = plan_from_counts(budgets, WALK_BUCKET)
        deg = plan.sub.degrees()
        ell = plan.sub.to_ell(width=pow2(int(deg.max()) if deg.size else 1))
        corpus = build_corpus(
            ell, wplan, cfg.walk_length, jax.random.PRNGKey(cfg.seed)
        )
        corpus.walks.block_until_ready()
        return wplan, corpus

    def _warm_table(self, plan: RetrainPlan) -> tuple:
        """(n, d) emb_in to start from: the seeded init, with the previous
        vectors of subcore nodes the store still holds. Built on the host,
        so the warm rows never shape a device program."""
        cfg = self.cfg
        params = init_params(
            plan.snapshot.n_nodes, cfg.sgns.dim,
            jax.random.PRNGKey(cfg.sgns.seed),
        )
        warm = np.array(params["emb_in"], np.float32)
        warm_rows = 0
        if cfg.warm_start:
            old, found, _, _ = self.service.store.peek_many(plan.nodes)
            keep = found & (np.linalg.norm(old, axis=1) > 1e-12)
            warm_rows = int(keep.sum())
            warm[plan.nodes[keep]] = old[keep]
        params["emb_in"] = jax.numpy.asarray(warm)
        return params, warm, warm_rows

    def _train(self, corpus: WalkCorpus, params) -> tuple:
        """Warm-started SGNS on the corpus -> (SGNSResult, pairs)."""
        cfg = self.cfg
        pairs = int(cfg.sgns.epochs * corpus.pairs_per_epoch(cfg.sgns.window))
        steps = max(cfg.min_sgns_steps, pairs // cfg.sgns.batch)
        return train_sgns(corpus, cfg.sgns, params=params, steps=steps), pairs

    def _anchors(self, plan: RetrainPlan) -> tuple:
        """Stable anchors + their previous vectors (store peek, no LRU churn)."""
        stable = plan.nodes[
            plan.core[plan.nodes] == plan.baseline[plan.nodes]
        ]
        old, found, _, _ = self.service.store.peek_many(stable)
        keep = found & (np.linalg.norm(old, axis=1) > 1e-12)
        if int(keep.sum()) < self.cfg.sgns.dim:
            # heavy churn can leave too few level-stable survivors; fall back
            # to every in-core node whose previous vector is still held
            old, found, _, _ = self.service.store.peek_many(plan.nodes)
            keep = found & (np.linalg.norm(old, axis=1) > 1e-12)
            return plan.nodes[keep], old[keep]
        return stable[keep], old[keep]

    def run(
        self, between: Optional[Callable[[], None]] = None
    ) -> Optional[RetrainReport]:
        svc = self.service
        cfg = self.cfg
        times = {}
        t_total = time.perf_counter()
        pressure_before = svc.retrain_pressure()
        staleness_before = svc.store.staleness(svc.cores.core)

        svc.pet_watchdog()
        faults.check("retrain_plan")
        with _stage("plan", times):
            plan = self.planner.plan()
        if len(plan.nodes) == 0:
            return None  # nothing alive at any k0 — nothing to refresh

        svc.pet_watchdog()
        faults.check("retrain_walks")
        with _stage("walks", times) as sp:
            wplan, corpus = self._walks(plan)
            sp.set(walks=int(wplan.n_real), tokens=int(corpus.n_tokens))

        svc.pet_watchdog()
        faults.check("retrain_train")
        with _stage("train", times) as sp:
            params, warm, warm_rows = self._warm_table(plan)
            res, pairs = self._train(corpus, params)
            sp.set(steps=int(res.n_steps), pairs=pairs, rows=res.rows)
        emb = res.embeddings

        svc.pet_watchdog()
        faults.check("retrain_align")
        with _stage("align", times):
            if cfg.align:
                anchors, old_vecs = self._anchors(plan)
                emb, align_rep = self.aligner.align(emb, old_vecs, anchors)
            else:
                anchors = np.zeros(0, np.int64)
                old_vecs = np.zeros((0, emb.shape[1]), np.float32)
                align_rep = {"aligned": False, "anchors": 0, "residual": 0.0}

        svc.pet_watchdog()
        faults.check("retrain_propagate")
        with _stage("propagate", times):
            if cfg.propagate:
                # §2.2: refill every shell below k0 from the aligned
                # subcore, so the swap covers the whole served id space,
                # not just the core
                emb = propagate(
                    plan.snapshot, plan.core, plan.k0, emb,
                    n_iters=cfg.prop_iters,
                )
                served = np.where(
                    (plan.snapshot.degrees() > 0) | (plan.core >= plan.k0)
                )[0]
            else:
                served = plan.nodes

        svc.pet_watchdog()
        faults.check("retrain_swap")
        with _stage("swap", times):
            staged = (served, emb[served], plan.core[served])
            rollout = VersionRollout(svc.store, chunk=cfg.swap_chunk)
            rollout.stage(*staged)
            roll = rollout.commit(between)
            svc.cores.mark_refresh()
        times["total"] = time.perf_counter() - t_total
        self.last = RefreshRecord(
            plan=plan, walk_plan=wplan, corpus=corpus, warm=warm, sgns=res,
            anchors=anchors, anchor_vecs=old_vecs, staged=staged,
        )

        return RetrainReport(
            k0=plan.k0,
            core_size=int(len(plan.nodes)),
            drifted=plan.drifted,
            n_walks=int(wplan.n_real),
            sgns_steps=int(res.n_steps),
            warm_rows=warm_rows,
            anchors=align_rep["anchors"],
            aligned=align_rep["aligned"],
            align_residual=align_rep["residual"],
            version=roll["version"],
            rows_swapped=roll["rows"],
            swap_chunks=roll["chunks"],
            staleness_before=float(staleness_before),
            staleness_after=float(svc.store.staleness(svc.cores.core)),
            pressure_before=float(pressure_before),
            pressure_after=float(svc.retrain_pressure()),
            times={k: round(v, 6) for k, v in times.items()},
        )
