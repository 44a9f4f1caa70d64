"""Incremental core-number maintenance under edge insertion *and* deletion.

The offline path peels the whole graph (``core_numbers_host``, O(E)); doing
that per streamed edge would make ingestion quadratic. Streams admit exact
local repair instead (Sarıyüce et al., "Streaming algorithms for k-core
decomposition", VLDB 2013), and this module batches that repair over whole
**edge blocks**: one region discovery + one h-index descent per block,
instead of one per edge.

Block repair (``on_edge_block`` / ``on_remove`` / ``on_update``):

* All mutations of the block are first applied to the graph. The nodes whose
  core number can change lie in a **union subcore**: nodes reachable from any
  block endpoint through nodes whose old core number falls in a level window
  around the block's endpoint levels (purecore-style traversal; for a single
  insertion the window degenerates to the classical "core == K" subcore).
* Candidates are seeded at an upper bound of their new core number
  (``min(new_degree, old_core + #inserted)``, one vectorized gather from the
  graph's maintained degree array) and swept with the *same* row-masked
  h-index operator the offline device fixpoint uses
  (``repro.kernels.ops.h_index_sweep``), with non-candidate neighbours
  frozen at their true (unchanged) core numbers. The operator is monotone,
  so the sweep descends to the exact new core numbers (Lü et al. 2016).
* A block can cascade promotions/demotions across several levels, so the
  window half-width is **adaptive**: the repair re-runs with a wider window
  whenever the computed level changes touch the window boundary. Single-edge
  repairs never widen.
* **Measured repair policy** (:class:`RepairPolicy`): the descent over the
  region and a re-peel of the whole graph are both exact, so the choice
  between them is cost only. The policy predicts both paths' cost from
  per-regime EMAs of its own measured phase seconds (the same intervals
  exported as ``repair_phase_seconds{phase=}`` through the metrics registry,
  which also warm-starts the priors across maintainer instances in one
  process) and runs whichever is cheaper. Before the descent has been
  measured it falls back to a shape heuristic: descend unless the padded
  candidate matrix dwarfs the affected-shell arc mass.
* **Pipelined handoff**: ``begin_update`` runs region discovery + the policy
  decision and *dispatches* the fused descent without reading it back
  (``jax`` async dispatch); ``finish_update`` blocks on the result, runs any
  window widenings, and commits. ``on_update`` is simply the two
  back-to-back; the serving layer calls them split so block N+1's host-side
  dedup/scatter overlaps block N's in-flight descent. Every other public
  entry point settles an in-flight ticket first, so the results are those
  of ``on_update``.

One repair path on every backend (``impl="device"``, the default), every
stage vectorized or fused:

* **Region growing** is a frontier-masked traversal: boolean frontier /
  visited masks expanded one level per step with the ``[lo, hi]`` core-window
  filter applied in bulk, plus a static-shaped **side table** of extra arcs
  (the removed block edges, so deletions keep their discovery path, and the
  overflow arcs the device mirror cannot see between compactions), as one
  jitted ``lax.while_loop`` over the ``DynamicGraph`` device ELL mirror
  (``_region_fixpoint``).
* **Candidate matrices** are degree tiers: each row goes in the narrowest
  width of a fixed ladder (32, 128, 512, then pow2 of the largest candidate
  degree) that holds it, and each tier is one vectorized gather
  (``DynamicGraph.gather_rows``) of only its own columns, so hub-width
  padding is neither built nor swept for the many low-degree rows.
* **The h-index descent is one fused jitted fixpoint** (``_fused_descent``):
  seeding, every sweep over every tier, the convergence test, and the
  adaptive-window boundary statistics all run inside a single
  ``lax.while_loop`` dispatch — no per-iteration ``est[cand]`` ping-pong
  between host and device. Each sweep applies the h-index kernel that
  ``kernels.ops.h_index_impl`` names (the Pallas kernel on TPU, the
  sort-free counting search elsewhere).
* **The re-peel** is the same fused descent seeded over *all* nodes (still
  one dispatch). Should the sweep cap truncate it, the vectorized rounds
  peel (``core_numbers_rounds``) over the graph's arc arrays recovers.

``impl="ref"`` is the correctness oracle for that path: the dict/set BFS,
the per-iteration jitted host sweep, and the snapshot re-peel
(``core_numbers_host``). The snapshot peel also repairs a block whose device
dispatch keeps failing (``_recover_ref``). ``phase_report()`` exposes
per-phase wall time (region / candidates / descend / fallback) and which
backend each phase ran on, so benchmarks can show *where* repair time goes.

Core-number **drift** (how many nodes changed level since the embedding table
was last refreshed) is the staleness signal the store/service use to gate
retraining: the paper's §2.2 propagation stays valid while the k0-core is
stable, and drift in deep shells — in either direction, now that edges can
be retracted — is what invalidates it.
"""
from __future__ import annotations

import time
from collections import deque
from functools import partial
from itertools import accumulate, combinations
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.kcore import (
    _h_index_sweep_jit,
    core_numbers_host,
    core_numbers_rounds,
)
from repro.kernels import ops as kops
from repro.obs import metrics
from repro.obs import trace as obs

from . import faults
from .stream import DynamicGraph
from .util import pow2

__all__ = ["IncrementalCore", "RepairPolicy"]

_EMPTY = np.zeros((0, 2), np.int64)

# narrow tier of the region traversal, and the first rung of the descent's
_W_SMALL = 32

# widths of the fused descent's tiers below its widest, which is pow2 of the
# largest candidate degree: a row sweeps in the narrowest tier that holds it
_LADDER = (_W_SMALL, 128, 512)

# smallest padded side table of the device region traversal: padding arcs
# are masked and near-free, and every distinct size is one more compile
_SIDE_FLOOR = 1024

# size-distribution buckets (region node counts): powers of 4 up to ~4M
_COUNT_BUCKETS = 4.0 ** np.arange(12)

# the measured crossover runs the descent while its predicted cost is at
# most this multiple of the re-peel's
_CROSSOVER_MARGIN = 1.0

# cold-start shape heuristic: descend while the padded candidate cells are
# at most this many per arc of the shells a re-peel would touch
_COLD_CELLS_PER_ARC = 32.0


@partial(jax.jit, static_argnames=("impl", "max_sweeps"))
def _fused_descent(tiers, cand, seed, old, est_full, lo, hi, *,
                   impl: str, max_sweeps: int):
    """Whole h-index descent as one device dispatch.

    ``tiers``: tuple of ``(idx, valid)`` candidate neighbour matrices
    (global node ids, padding = sentinel), each ``(R_t, W_t)``; ``cand``:
    (R,) candidate ids of the concatenated rows in tier order (padded rows
    point at the sentinel, whose estimate stays 0); ``seed``: (R,) upper
    bound on the new cores; ``old``: (R,) old cores (0 on padded rows);
    ``est_full``: (node_cap + 1,) frozen boundary = current cores. Runs the
    row-masked sweep to its fixed point inside one ``lax.while_loop`` and
    returns ``(new, max_gain, max_loss, ceiling_hit, floor_hit, sweeps,
    truncated)`` — the adaptive-window boundary statistics ride along so
    the caller reads back a few scalars plus the repaired levels, never
    per-sweep intermediates.

    One ELL matrix pays the hub tax: a few high-degree rows set its width
    and most swept cells are padding. So the rows come split by degree into
    tiers of growing width (``IncrementalCore._tier_plan``). Each sweep
    applies the identical row operator to every tier against the shared
    estimate of the previous sweep, so the fixpoint trajectory — and the
    result — is bit-identical to the single-matrix descent, at a fraction
    of the swept cells. The number of tiers is static.
    """
    bounds = list(accumulate((idx.shape[0] for idx, _ in tiers), initial=0))
    est = est_full.at[cand].set(seed)

    def cond(state):
        _, _, changed, it = state
        return jnp.logical_and(changed, it < max_sweeps)

    def body(state):
        est, cur, _, it = state
        new = jnp.concatenate([
            kops.h_index_sweep(est[idx], valid, cur[a:b], impl=impl)
            for (idx, valid), a, b in zip(tiers, bounds[:-1], bounds[1:])
        ])
        est = est.at[cand].set(new)
        return est, new, jnp.any(new != cur), it + 1

    _, new, changed, sweeps = jax.lax.while_loop(
        cond, body, (est, seed, jnp.asarray(True), jnp.asarray(0, jnp.int32))
    )
    gain = jnp.max(jnp.maximum(new - old, 0), initial=0)
    loss = jnp.max(jnp.maximum(old - new, 0), initial=0)
    # only *changed* nodes at/past the boundary suggest a truncated cascade;
    # an unchanged high-core endpoint legitimately sits above the window
    ceiling = jnp.any((new > hi) & (new > old))
    floor = jnp.any((new < lo) & (new < old))
    # ``changed`` still true at exit means the sweep cap truncated the
    # descent — the estimates are NOT a fixed point and must not be committed
    return new, gain, loss, ceiling, floor, sweeps, changed


@jax.jit
def _region_fixpoint(nbr, deg, hubs, core, ends, side_src, side_dst,
                     side_valid, lo, hi):
    """Frontier-masked union-subcore traversal, one jitted while-loop.

    ``nbr``/``deg`` are the device ELL mirror; ``side_*`` is the padded side
    table of extra arcs (removed block edges + overflow arcs the mirror
    cannot see). Expands boolean frontier/visited masks one level per
    iteration, filtering discovered nodes by old core in ``[lo, hi]``;
    endpoints are pre-seeded regardless of their level.

    The mirror is as wide as its largest row, and on a power-law graph
    nearly all of it is padding. So each level sweeps two tiers: the first
    ``_W_SMALL`` columns of every row, and the whole rows of ``hubs`` (the
    rows with more entries; padding ids point at the empty sentinel row).
    Together they hold every arc of the mirror.
    """
    n1, width = nbr.shape
    w = min(width, _W_SMALL)
    narrow = nbr[:, :w]
    valid = jnp.arange(w, dtype=jnp.int32)[None, :] < deg[:, None]
    hub_nbr = nbr[hubs]
    hub_valid = (
        jnp.arange(width, dtype=jnp.int32)[None, :] < deg[hubs][:, None]
    )
    eligible = (core >= lo) & (core <= hi)

    def body(state):
        frontier, visited = state
        nxt = jnp.zeros(n1, bool).at[narrow].max(frontier[:, None] & valid)
        nxt = nxt.at[hub_nbr].max(frontier[hubs][:, None] & hub_valid)
        nxt = nxt.at[side_dst].max(frontier[side_src] & side_valid)
        newf = nxt & eligible & ~visited
        return newf, visited | newf

    _, visited = jax.lax.while_loop(
        lambda state: state[0].any(), body, (ends, ends)
    )
    return visited


def _pad_rows(idx: np.ndarray, valid: np.ndarray, r_pad: int, sentinel: int):
    """Pad the candidate matrix to a static ``r_pad`` rows (sentinel rows)."""
    rows, w = idx.shape
    if rows == r_pad:
        return idx, valid
    pad = r_pad - rows
    idx = np.concatenate([idx, np.full((pad, w), sentinel, np.int32)])
    valid = np.concatenate([valid, np.zeros((pad, w), bool)])
    return idx, valid


class RepairPolicy:
    """Measured-crossover choice of which *exact* repair path runs.

    Both paths (window-validated fused descent, whole-graph re-peel) are
    exact, so the policy only affects cost, never results. Per decision
    it predicts each path's wall time at the block's work scale — descend
    work = padded candidate-matrix cells, re-peel work = affected-shell arc
    mass — from an EMA kept per ``(path, regime)`` where a regime is a
    power-of-4 work bucket (nearest-regime predictions extrapolate linearly
    in work). Observations come from the maintainer's own phase timers, the
    very intervals exported as ``repair_phase_seconds{phase=}``; the
    registry feeds back in two ways: :meth:`refresh_from_metrics`
    warm-starts absolute priors from the live histograms (so a fresh
    maintainer in a warmed process doesn't start cold), and every
    observation lands back in the registry via the phase histograms.
    """

    def __init__(
        self,
        *,
        alpha: float = 0.25,
        probe_every: int = 6,
        history: int = 512,
    ):
        self.alpha = float(alpha)
        self.probe_every = int(probe_every)
        self._ema: dict = {}  # (path, regime) -> [ema_seconds, ema_work]
        self._prior: dict = {}  # path -> absolute prior seconds (registry)
        self._stale = {"descend": 0, "repeel": 0}  # decisions since measured
        self.decisions = {"descend": 0, "repeel": 0}
        self.cold_decisions = 0
        self.probes = 0
        self._history: deque = deque(maxlen=int(history))
        self._pending: dict = {}  # path -> (work, predicted) awaiting actual
        self.refresh_from_metrics()

    @staticmethod
    def _regime(work: float) -> int:
        return max(int(work).bit_length() // 2, 1)

    def refresh_from_metrics(self, registry=None) -> None:
        """Warm-start absolute cost priors from the live phase histograms."""
        reg = metrics() if registry is None else registry
        for path, phase in (("descend", "descend"), ("repeel", "fallback")):
            h = reg.get("repair_phase_seconds", phase=phase)
            if h is not None and len(h):
                self._prior[path] = float(np.mean(h.values()))

    def _measured(self, path: str, work: float) -> Optional[float]:
        """EMA-predicted seconds from this policy's own observations only."""
        b = self._regime(work)
        cell = self._ema.get((path, b))
        if cell is not None:
            return cell[0]
        near = [r for (p, r) in self._ema if p == path]
        if near:
            r = min(near, key=lambda r: abs(r - b))
            sec, w = self._ema[(path, r)]
            return sec * (float(work) / max(w, 1.0))
        return None

    def predict(self, path: str, work: float) -> Optional[float]:
        """Predicted seconds for ``path`` at ``work`` units; None = no data.

        Own measurements first; the registry-fed absolute prior stands in
        until then (work-blind, so only a coarse magnitude).
        """
        m = self._measured(path, work)
        return m if m is not None else self._prior.get(path)

    def observe(self, path: str, work: float, seconds: float) -> None:
        """Feed one measured phase interval back into the regime EMAs."""
        self._stale[path] = 0
        cell = self._ema.get((path, self._regime(work)))
        if cell is None:
            self._ema[(path, self._regime(work))] = [
                float(seconds), float(work)
            ]
        else:
            cell[0] += self.alpha * (float(seconds) - cell[0])
            cell[1] += self.alpha * (float(work) - cell[1])
        pend = self._pending.pop(path, None)
        if pend is not None:
            self._history.append(
                (path, int(work), float(pend[1]), float(seconds))
            )

    def choose(self, *, cells: int, repeel_work: int) -> str:
        """``"descend"`` or ``"repeel"`` for one block repair.

        ``cells``: padded candidate-matrix area the fused descent would
        sweep; ``repeel_work``: arc mass of the shells a re-peel would
        touch.
        """
        pd = self._measured("descend", cells)
        pred = None
        if pd is None:
            # the descent has not been *measured* yet — a work-blind prior
            # (possibly from some other maintainer's regime) must not starve
            # it, or the crossover never gets data. Run it unless the shape
            # heuristic says the padded matrix dwarfs the affected-shell
            # arc mass (block size x shell span).
            self.cold_decisions += 1
            choice = (
                "descend"
                if cells <= _COLD_CELLS_PER_ARC * max(repeel_work, 64)
                else "repeel"
            )
        else:
            pr = self._measured("repeel", repeel_work)
            if pr is None:
                # descend is measured but the re-peel side never has been —
                # explore it once (it is exact too) so the crossover gets
                # data for both paths instead of riding the first
                # measurement forever
                choice = "repeel"
                pred = self._prior.get("repeel")
            else:
                choice = (
                    "descend" if pd <= _CROSSOVER_MARGIN * pr
                    else "repeel"
                )
                pred = pd if choice == "descend" else pr
                # EMA freshness: the losing path stops getting measured the
                # moment it loses, so its estimate would never track drift
                # (bigger graph, warmer caches, changed shapes). Probe it
                # after ``probe_every`` consecutive unmeasured decisions —
                # bounded overhead, and the crossover stays live both ways.
                loser = "repeel" if choice == "descend" else "descend"
                if self.probe_every and \
                        self._stale[loser] >= self.probe_every:
                    choice, pred = loser, (pd if loser == "descend" else pr)
                    self.probes += 1
        self.decisions[choice] += 1
        self._stale["descend"] += 1
        self._stale["repeel"] += 1
        if pred is not None:
            self._pending[choice] = (
                cells if choice == "descend" else repeel_work, pred
            )
        return choice

    def report(self) -> dict:
        """Decision counts, predicted-vs-actual error, learned regimes."""
        by: dict = {}
        for path, _work, pred, act in self._history:
            d = by.setdefault(path, {"n": 0, "_err": 0.0})
            d["n"] += 1
            d["_err"] += abs(pred - act) / max(act, 1e-9)
        for d in by.values():
            d["mean_abs_rel_err"] = round(d.pop("_err") / d["n"], 3)
        return {
            "decisions": dict(self.decisions),
            "cold_decisions": int(self.cold_decisions),
            "probes": int(self.probes),
            "predicted_vs_actual": by,
            "regimes": {
                f"{p}/{r}": [round(s, 6), round(w, 1)]
                for (p, r), (s, w) in sorted(self._ema.items())
            },
        }


class _RepairTicket:
    """Handle for one block repair started by ``begin_update``.

    ``done`` tickets already committed (synchronous paths); live tickets
    hold the in-flight fused-descent dispatch plus everything
    ``finish_update`` needs to validate the window and commit.
    """

    __slots__ = ("done", "changed", "pending", "ctx", "margin", "lo", "cand")

    def __init__(self, *, changed=None, pending=None, ctx=None, margin=0,
                 lo=0, cand=None):
        self.done = changed is not None
        self.changed = int(changed or 0)
        self.pending = pending
        self.ctx = ctx
        self.margin = margin
        self.lo = lo
        self.cand = cand


class IncrementalCore:
    """Block-repaired core numbers of a :class:`DynamicGraph`.

    ``impl``: ``"device"`` (the one repair path, on every backend) or
    ``"ref"`` (its host oracle). ``policy`` chooses descend or re-peel per
    block; tests pass a double to force one path.
    """

    def __init__(
        self,
        g: DynamicGraph,
        core: Optional[np.ndarray] = None,
        *,
        margin0: int = 8,
        impl: str = "device",
        max_sweeps: int = 512,
        policy: Optional[RepairPolicy] = None,
    ):
        self.g = g
        if core is None:
            core = (
                core_numbers_host(g.snapshot())
                if g.n_nodes
                else np.zeros(0, np.int32)
            )
        self._core = np.asarray(core, np.int32).copy()
        self._baseline = self._core.copy()  # levels at last embedding refresh
        self.margin0 = int(margin0)
        if impl not in ("device", "ref"):
            raise ValueError(f"unknown impl {impl!r}")
        self.impl = impl
        self.max_sweeps = int(max_sweeps)
        self.policy = policy if policy is not None else RepairPolicy()
        self.repairs = 0
        self.sweeps = 0
        self.descends = 0
        # bounded retry around the fused-descent dispatch; exhaustion falls
        # back to the exact host peel (never an inexact answer)
        self.dispatch_retries = 2
        self.retry_backoff = 0.05
        self.dispatch_failures = 0
        self.dispatch_recoveries = 0
        self.promoted = 0
        self.demoted = 0
        self.repeels = 0
        self._inflight: Optional[_RepairTicket] = None
        self.phase_seconds: dict = {}
        self.phase_impl: dict = {}
        self._executables: dict = {}  # fused descents compiled per shape

    # ---------------------------------------------------------- dispatch

    def _compiled(self, fn, args, **static):
        """``fn`` compiled ahead of time for these arguments' shapes.

        The repair path compiles here, before its retry wrapper and host
        fallback, so a lowering or compile error raises to the caller
        instead of being absorbed as a failed dispatch.
        """
        key = (fn, tuple(sorted(static.items())), jax.tree.structure(args),
               tuple((a.shape, a.dtype, a.sharding)
                     for a in jax.tree.leaves(args)))
        exe = self._executables.get(key)
        if exe is None:
            exe = self._executables[key] = fn.lower(*args, **static).compile()
        return exe

    def _device(self) -> bool:
        return self.impl == "device"

    def _tick(self, phase: str, mode: str, t0: float, t1: float) -> None:
        """Charge ``[t0, t1]`` to ``phase``.

        The interval is that of the phase's ``repair.<phase>`` span (an
        ``obs.timed`` block, or a pre-timed ``obs.record`` where it spans two
        calls), so phase_report(), the metrics registry, the repair policy
        and the trace all describe one measurement.
        """
        self.phase_seconds[phase] = (
            self.phase_seconds.get(phase, 0.0) + t1 - t0
        )
        self.phase_impl[phase] = mode
        metrics().histogram("repair_phase_seconds", phase=phase).observe(
            t1 - t0
        )

    def phase_report(self) -> dict:
        """Per-phase repair wall time + which backend each phase ran on."""
        return {
            k: {"seconds": round(v, 6), "impl": self.phase_impl.get(k, "")}
            for k, v in sorted(self.phase_seconds.items())
        }

    def policy_report(self) -> dict:
        """:meth:`RepairPolicy.report` plus this maintainer's re-peel and
        fused-descent counts."""
        rep = self.policy.report()
        rep["repeels"] = int(self.repeels)
        rep["descends"] = int(self.descends)
        return rep

    def reset_phases(self) -> None:
        """Zero the per-phase timers (benchmarks call this after warmup)."""
        self.phase_seconds = {}

    # ------------------------------------------------------------- views

    @property
    def core(self) -> np.ndarray:
        """(n_nodes,) int32 current core numbers (live view, do not mutate).

        Settles any in-flight pipelined repair first — readers always see
        committed levels.
        """
        self._settle()
        return self._core[: self.g.n_nodes]

    @property
    def baseline(self) -> np.ndarray:
        """(n_nodes,) int32 core numbers at the last ``mark_refresh``.

        The retraining subsystem reads this to pick alignment anchors
        (nodes whose level has not moved since the serving table was built).
        """
        self._ensure_size()
        return self._baseline[: self.g.n_nodes]

    def _ensure_size(self) -> None:
        n = self.g.n_nodes
        if len(self._core) < n:
            pad = np.zeros(n - len(self._core), np.int32)
            self._core = np.concatenate([self._core, pad])
            self._baseline = np.concatenate([self._baseline, pad])

    # ------------------------------------------------------------ regions

    def _region(self, ends: np.ndarray, lo: int, hi: int, removed) -> list:
        """Union subcore, host reference: nodes reachable from the block
        endpoints through nodes with old core in [lo, hi], over the
        post-block adjacency plus the removed block edges (a deletion must
        not sever its own discovery path). Endpoints are always included.

        Must cover every node whose core changes — truncating it would seed
        only part of the repair region and silently break exactness; the
        caller guards that with the adaptive window + boundary check.
        """
        extra = {}
        for u, v in removed:
            extra.setdefault(int(u), set()).add(int(v))
            extra.setdefault(int(v), set()).add(int(u))
        seen = {int(r) for r in ends}
        stack = list(seen)
        while stack:
            w = stack.pop()
            nbrs = self.g.neighbours(w)
            ex = extra.get(w)
            if ex:
                nbrs = np.concatenate(
                    [nbrs, np.fromiter(ex, np.int64, len(ex))]
                )
            for x in nbrs:
                x = int(x)
                if x not in seen and lo <= self._core[x] <= hi:
                    seen.add(x)
                    stack.append(x)
        return sorted(seen)

    def _region_device(self, ends, lo, hi, side_src, side_dst):
        """Jitted frontier traversal over the device ELL mirror + side table.

        Under a ShardPlan the mirror arrives row-sharded (and row-padded);
        the frontier/visited masks and the static-shaped side table — the
        halo buffer carrying the arcs shards cannot see locally (removed
        block edges + overflow arcs) — stay replicated, so each traversal
        level is still one dispatch with GSPMD exchanging the frontier.
        """
        g = self.g
        n = g.n_nodes
        ell = g.ell()
        n1 = ell.neighbours.shape[0]  # node_cap + 1, plus any shard padding
        ends_mask = np.zeros(n1, bool)
        ends_mask[ends] = True
        core = np.zeros(n1, np.int32)
        core[:n] = self._core[:n]
        s_pad = max(pow2(len(side_src)), _SIDE_FLOOR)
        ss = np.zeros(s_pad, np.int32)
        sd = np.zeros(s_pad, np.int32)
        sv = np.zeros(s_pad, bool)
        ss[: len(side_src)] = side_src
        sd[: len(side_dst)] = side_dst
        sv[: len(side_src)] = True
        big = np.where(g._deg > _W_SMALL)[0]
        hubs = np.full(max(pow2(len(big)), 64), g.node_cap, np.int32)
        hubs[: len(big)] = big
        plan = g.plan
        rep = jnp.asarray if plan is None else plan.replicate
        visited = _region_fixpoint(
            ell.neighbours, ell.degrees, rep(hubs), rep(core),
            rep(ends_mask), rep(ss), rep(sd),
            rep(sv), lo, hi,
        )
        return np.where(np.asarray(visited)[:n])[0].astype(np.int64)

    # ------------------------------------------------------------ repairs

    def _repeel(self, old: np.ndarray, m_ins: int, *,
                host: bool = False) -> int:
        """Exact re-peel of the whole graph: the fused descent seeded over
        all nodes, or the snapshot peel for ``impl="ref"`` and ``host``."""
        n = self.g.n_nodes
        mode = "peel" if host or not self._device() else "descend"
        with obs.timed("repair.fallback") as sp:
            if mode == "descend":
                deg = self.g.degrees_of(np.arange(n))
                seed = np.maximum(
                    np.minimum(deg.astype(np.int64),
                               old.astype(np.int64) + m_ins),
                    0,
                ).astype(np.int32)
                # the inner gather/descent ticks belong to the fallback
                # bucket: roll them back so the phase report stays
                # non-overlapping
                before = {
                    k: self.phase_seconds.get(k)
                    for k in ("candidates", "descend")
                }
                pending = self._launch(self._stage(
                    np.arange(n, dtype=np.int64), seed, old, 0, 1 << 30,
                    cand_deg=deg,
                ))
                res = self._descend_read(pending)
                for k, b in before.items():
                    if b is None:
                        self.phase_seconds.pop(k, None)
                        self.phase_impl.pop(k, None)
                    else:
                        self.phase_seconds[k] = b
                if res is None:
                    # the sweep cap truncated the full descent
                    # (pathological cascade depth) — recover with the
                    # uncapped exact peel
                    src, dst = self.g.arc_arrays()
                    oracle = core_numbers_rounds(n, src, dst)
                    mode = "rounds"
                else:
                    # the dispatch may tier-reorder rows: scatter back by id
                    oracle = np.zeros(n, np.int32)
                    oracle[pending["cand"]] = res[0]
            else:
                oracle = core_numbers_host(self.g.snapshot())
            sp.set(impl=mode)
        self._tick("fallback", mode, sp.t0, sp.t1)
        self.policy.observe("repeel", max(2 * self.g.n_edges, 1),
                            sp.t1 - sp.t0)
        changed = oracle != self._core[:n]
        self.promoted += int((oracle > self._core[:n]).sum())
        self.demoted += int((oracle < self._core[:n]).sum())
        self._core[:n] = oracle
        self.repeels += 1
        metrics().counter("repair_repeels_total").inc()
        return int(changed.sum())

    @staticmethod
    def _tier_plan(cand_deg: np.ndarray):
        """Static tier shapes of the descent matrix, plus padded cell count.

        Returns ``(tiers, tier, cells)``: ``tiers`` is ``((width, rows),
        ...)``, narrowest first, and ``tier`` the tier of each candidate
        row: the narrowest whose width holds the row's true degree. Widths
        are rungs of ``_LADDER`` below the widest, ``pow2`` of the largest
        degree; that width and every tier's rows are powers of two floored
        at 64 (masked rows/lanes are near-free to sweep, fewer distinct
        shapes mean fewer compiles, and row-sharding stays divisible). A
        rung becomes a tier only where that strictly shrinks the swept
        cells: of every set of rungs (empty tiers skipped) the plan with
        fewest cells wins, the fewer tiers on a tie. A set whose largest
        degree is at most 64 stays one matrix. The per-row operator is
        unchanged, so the fixpoint (and the policy's cells-proportional cost
        model) is the same computation over fewer cells.
        """
        w_top = max(pow2(int(cand_deg.max(initial=1))), 64)
        best = ((w_top, max(pow2(len(cand_deg)), 64)),)
        best_tier = np.zeros(len(cand_deg), np.int64)
        best_cells = w_top * best[0][1]
        rungs = [w for w in _LADDER if w < w_top] if w_top > 64 else []
        for k in range(1, len(rungs) + 1):
            for cut in combinations(rungs, k):
                widths = cut + (w_top,)
                tier = np.searchsorted(widths, cand_deg)
                counts = np.bincount(tier, minlength=len(widths))
                if not counts.all():
                    continue
                tiers = tuple((w, max(pow2(c), 64))
                              for w, c in zip(widths, counts))
                cells = sum(w * r for w, r in tiers)
                if cells < best_cells:
                    best, best_tier, best_cells = tiers, tier, cells
        return best, best_tier, best_cells

    def _stage(self, cand, seed, old_cand, lo, hi, *, cand_deg):
        """Host half of a descent: build each tier's matrix at its own
        width, place them, and compile the fused descent for their shapes.
        Nothing here is retried or caught on the repair path: a compile
        error propagates."""
        g = self.g
        node_cap = g.node_cap
        with obs.timed("repair.candidates", impl="gather") as sp:
            n_rows = len(cand)
            tiers, tier, cells = self._tier_plan(cand_deg)
            sp.set(rows=n_rows, cells=cells)
            # tier by tier; the stable sort keeps each tier's rows in input
            # order, and ``keep`` maps the padded concat back to them
            order = np.argsort(tier, kind="stable")
            cand, seed, old_cand = cand[order], seed[order], old_cand[order]
            counts = np.bincount(tier, minlength=len(tiers))
            starts = np.cumsum([0] + [r for _, r in tiers])
            keep = np.concatenate(
                [s + np.arange(c) for s, c in zip(starts, counts)]
            )
            mats = [
                _pad_rows(*g.gather_rows(rows, width=w), r, node_cap)
                for (w, r), rows in zip(
                    tiers, np.split(cand, np.cumsum(counts)[:-1]))
            ]
            est_full = np.zeros(node_cap + 1, np.int32)
            est_full[: g.n_nodes] = self._core[: g.n_nodes]

            def vec(x, fill):
                out = np.full(starts[-1], fill, np.int32)
                out[keep] = x
                return out

            cand_p = vec(cand, node_cap)
            seed_p, old_p = vec(seed, 0), vec(old_cand, 0)
        self._tick("candidates", "gather", sp.t0, sp.t1)

        # under a ShardPlan (and a GSPMD-partitionable kernel impl) each
        # tier's rows are split across the mesh: each shard sweeps its own
        # rows and the frozen-boundary estimate stays replicated. A Pallas
        # kernel cannot be partitioned, so its descent runs on one device.
        impl = kops.h_index_impl()
        plan = g.plan if impl in ("count", "ref") else None
        row = jnp.asarray if plan is None else plan.place_rows
        rep = jnp.asarray if plan is None else plan.replicate
        args = (
            tuple((row(i), row(v)) for i, v in mats),
            row(cand_p), row(seed_p), row(old_p),
            rep(est_full), rep(np.int32(lo)), rep(np.int32(hi)),
        )
        exe = self._compiled(
            _fused_descent, args,
            impl=impl, max_sweeps=self.max_sweeps,
        )
        return {"exe": exe, "args": args, "impl": impl,
                "n_rows": n_rows, "cells": cells,
                "arcs": int(cand_deg.sum()), "tiers": len(tiers),
                "cand": cand, "keep": keep}

    @staticmethod
    def _launch(staged):
        """Device half of a descent: one asynchronous dispatch.

        Returns the pending dispatch (in-flight device arrays plus readback
        bookkeeping) without blocking, so the host is free until
        ``_descend_read`` — the pipelined ingest stages the next block's
        dedup/scatter in that gap.
        """
        faults.check("device_dispatch")
        t0 = time.perf_counter()
        out = staged["exe"](*staged["args"])
        return {**staged, "out": out, "t0": t0}

    def _descend_read(self, pending, *, full_interval: bool = True):
        """Block on a pending descent and pull the result back.

        ``full_interval=True`` charges the descend phase from the dispatch
        (the serial semantics); ``False`` charges only the blocking wait —
        in pipelined mode that is the descent's *non-overlapped* cost, which
        is both what the phase report should show and the right quantity for
        the policy's crossover (overlapped device time is free wall-clock).
        Returns ``(new, max_gain, max_loss, ceiling_hit, floor_hit)`` or
        None when the sweep cap truncated the descent.
        """
        impl = f"fused[{pending['impl']}]"
        attrs = {k: pending[k] for k in ("cells", "arcs", "tiers")}
        attrs.update(impl=impl, rows=pending["n_rows"])
        if full_interval:
            # the interval opened at the dispatch, in another call
            res, truncated = self._read_descent(pending)
            t0, t1 = pending["t0"], time.perf_counter()
            obs.record("repair.descend", t0, t1, **attrs)
        else:
            with obs.timed("repair.descend", **attrs) as sp:
                res, truncated = self._read_descent(pending)
            t0, t1 = sp.t0, sp.t1
        self._tick("descend", impl, t0, t1)
        self.policy.observe("descend", pending["cells"], t1 - t0)
        # None: the max_sweeps cap was hit before the fixed point
        return None if truncated else res

    def _read_descent(self, pending):
        """Block on a pending descent's outputs and count it; returns
        ``((new, max_gain, max_loss, ceiling_hit, floor_hit), truncated)``."""
        new, gain, loss, ceiling, floor, sweeps, truncated = pending["out"]
        new = np.asarray(new, np.int32)[pending["keep"]]
        self.sweeps += int(sweeps)
        self.descends += 1
        metrics().counter("repair_descends_total").inc()
        res = (new, int(gain), int(loss), bool(ceiling), bool(floor))
        return res, bool(truncated)

    def _descend(self, cand: np.ndarray, seed: np.ndarray,
                 cand_deg: np.ndarray) -> np.ndarray:
        """Reference host descent: per-iteration jitted sweeps over a
        host-maintained estimate (the PR 2 path, kept as the oracle)."""
        g = self.g
        w_pad = pow2(int(cand_deg.max(initial=1)))
        n_rows = pow2(len(cand))
        idx, valid = _pad_rows(*g.gather_rows(cand, width=w_pad), n_rows,
                               g.node_cap)

        est = np.zeros(g.node_cap + 1, np.int32)
        est[: len(self._core)] = self._core
        est[cand] = seed
        est_p = np.zeros(n_rows, np.int32)  # padded rows descend from 0 to 0
        while True:
            self.sweeps += 1
            vals = est[idx].astype(np.int32)
            est_p[: len(cand)] = est[cand]
            new = np.asarray(
                _h_index_sweep_jit(vals, valid, est_p, impl="ref"), np.int32
            )[: len(cand)]
            if np.array_equal(new, est[cand]):
                return new
            est[cand] = new

    def _finish_repeel(self, ctx: dict, *,
                       host: bool = False) -> _RepairTicket:
        changed = self._repeel(ctx["old"], ctx["m_ins"], host=host)
        self.repairs += 1
        return _RepairTicket(changed=changed)

    def _dispatch_with_retry(self, staged):
        """Bounded retry-with-backoff around the fused-descent dispatch of a
        staged (already compiled) descent.

        Re-raises the last error after ``dispatch_retries`` retries; the
        callers then fall back to :meth:`_recover_ref`.
        """
        for attempt in range(self.dispatch_retries + 1):
            try:
                return self._launch(staged)
            except Exception:
                self.dispatch_failures += 1
                metrics().counter("repair_dispatch_failures_total").inc()
                if attempt >= self.dispatch_retries:
                    raise
                time.sleep(self.retry_backoff * (2 ** attempt))

    def _recover_ref(self, ctx: dict) -> _RepairTicket:
        """Device repair kept failing: force the exact host snapshot peel
        for this block so cores stay exact while the device path recovers.

        InjectedCrash is a BaseException and never lands here — a simulated
        process death must not be absorbed into a host fallback."""
        self.dispatch_recoveries += 1
        metrics().counter("repair_dispatch_recoveries_total").inc()
        return self._finish_repeel(ctx, host=True)

    def _commit(self, ctx: dict, cand, new) -> _RepairTicket:
        old = ctx["old"]
        self.repairs += 1
        self._core[cand] = new
        self.promoted += int((new > old[cand]).sum())
        self.demoted += int((new < old[cand]).sum())
        return _RepairTicket(changed=int((new != old[cand]).sum()))

    def _resolve(self, ctx: dict, margin: int, lo: int, cand,
                 res) -> _RepairTicket:
        """Validate one descent result against the window; commit or widen."""
        if res is None:  # sweep cap hit: recover via exact recompute
            return self._finish_repeel(ctx)
        new, max_gain, max_loss, ceil_hit, floor_hit = res
        ceiling_hit = bool(ctx["m_ins"]) and ceil_hit
        floor_hit = bool(ctx["m_del"] and lo > 0) and floor_hit
        if ctx["m"] == 1 or (
            max_gain < margin
            and max_loss < margin
            and not ceiling_hit
            and not floor_hit
        ):
            return self._commit(ctx, cand, new)
        # a change at the boundary may be a truncated cascade: re-run wider
        # (synchronously — widenings are rare and already mid-repair)
        return self._advance(
            ctx, 2 * margin + max_gain + max_loss + 1, pipeline=False
        )

    def _advance(self, ctx: dict, margin: int, *,
                 pipeline: bool) -> _RepairTicket:
        """One window attempt: region discovery, policy decision, repair.

        Adaptive window: the half-width grows until the computed level
        changes sit strictly inside it (a change at the boundary may be a
        truncated cascade). A single mutation cannot cascade, so it never
        widens. With ``pipeline=True`` a device fused descent is returned
        in-flight (live ticket) instead of read back here.
        """
        m_ins, m_del = ctx["m_ins"], ctx["m_del"]
        old, n = ctx["old"], ctx["n"]
        device = self._device()
        where = "jit" if device else "ref"
        lo = max(0, ctx["k_min"] - (margin if m_del else 0))
        hi = ctx["k_max"] + (margin if m_ins else 0)

        with obs.timed("repair.region", impl=where) as sp:
            if device:
                cand = self._region_device(
                    ctx["ends"], lo, hi, ctx["side_src"], ctx["side_dst"]
                )
            else:
                cand = np.asarray(
                    self._region(ctx["ends"], lo, hi, ctx["removed"]), np.int64
                )
        self._tick("region", where, sp.t0, sp.t1)
        metrics().histogram(
            "repair_region_nodes", buckets=_COUNT_BUCKETS
        ).observe(len(cand))

        with obs.timed("repair.candidates", impl="gather",
                       rows=len(cand)) as sp:
            cand_deg = self.g.degrees_of(cand)
            seed = np.minimum(
                cand_deg.astype(np.int64), old[cand].astype(np.int64) + m_ins
            )
            seed = np.maximum(seed, 0).astype(np.int32)
            if device:
                cells = self._tier_plan(cand_deg)[2]
                sp.set(cells=cells)
        self._tick("candidates", "gather", sp.t0, sp.t1)

        if device:
            deg = self.g.degrees()
            repeel_work = int(deg[old[:n] <= hi].sum()) + n
            if self.policy.choose(
                cells=cells, repeel_work=repeel_work
            ) == "repeel":
                return self._finish_repeel(ctx)
            staged = self._stage(
                cand, seed, old[cand], lo, hi, cand_deg=cand_deg
            )
            try:
                pending = self._dispatch_with_retry(staged)
            except Exception:
                return self._recover_ref(ctx)
            # the dispatch may tier-reorder the rows: resolve/commit against
            # the ordering the result actually maps to
            if pipeline:
                return _RepairTicket(pending=pending, ctx=ctx,
                                     margin=margin, lo=lo,
                                     cand=pending["cand"])
            try:
                res = self._descend_read(pending)
            except Exception:
                metrics().counter("repair_dispatch_failures_total").inc()
                return self._recover_ref(ctx)
            return self._resolve(ctx, margin, lo, pending["cand"], res)

        with obs.timed("repair.descend", impl="host", rows=len(cand),
                       arcs=int(cand_deg.sum()), tiers=1) as sp:
            new = self._descend(cand, seed, cand_deg)
            # a changed node's old level sits within the *deepest per-node
            # cascade* of the block's endpoint levels, so the window is
            # sufficient as long as the margin exceeds the largest
            # single-node level change
            max_gain = int(np.maximum(new - old[cand], 0).max(initial=0))
            max_loss = int(np.maximum(old[cand] - new, 0).max(initial=0))
            ceil_hit = bool(((new > hi) & (new > old[cand])).any())
            floor_hit = bool(((new < lo) & (new < old[cand])).any())
        self._tick("descend", "host", sp.t0, sp.t1)
        return self._resolve(
            ctx, margin, lo, cand,
            (new, max_gain, max_loss, ceil_hit, floor_hit),
        )

    def begin_update(self, added=None, removed=None) -> _RepairTicket:
        """Start a block repair; ``finish_update`` completes it.

        The returned ticket is either already committed (fallback re-peel,
        host impl, empty block) or holds an *in-flight* fused-descent
        dispatch. In the latter case the caller may overlap host work (the
        pipelined ingest stages the next block's dedup/scatter here) before
        ``finish_update`` reads the result back — but must not mutate the
        graph until then.
        """
        self._settle()
        faults.check("repair")
        added = (
            np.asarray(added, np.int64).reshape(-1, 2)
            if added is not None else _EMPTY
        )
        removed = (
            np.asarray(removed, np.int64).reshape(-1, 2)
            if removed is not None else _EMPTY
        )
        m_ins, m_del = len(added), len(removed)
        m = m_ins + m_del
        if m == 0:
            return _RepairTicket(changed=0)
        self._ensure_size()
        n = self.g.n_nodes
        old = self._core[:n].copy()

        touched = np.concatenate([added, removed]) if m_del and m_ins else (
            added if m_ins else removed
        )
        k_edge = np.minimum(
            self._core[touched[:, 0]], self._core[touched[:, 1]]
        )
        ctx = {
            "added": added, "removed": removed, "m_ins": m_ins,
            "m_del": m_del, "m": m, "n": n, "old": old,
            "ends": np.unique(touched.reshape(-1)),
            "k_min": int(k_edge.min()), "k_max": int(k_edge.max()),
        }
        if self._device():
            # side table: removed block edges (both arcs) + overflow arcs the
            # table/mirror cannot carry — built once, reused across widenings
            ov_src, ov_dst = self.g.overflow_arc_arrays()
            ctx["side_src"] = np.concatenate(
                [ov_src, removed[:, 0], removed[:, 1]]
            )
            ctx["side_dst"] = np.concatenate(
                [ov_dst, removed[:, 1], removed[:, 0]]
            )
        ticket = self._advance(
            ctx, 0 if m == 1 else self.margin0, pipeline=True
        )
        if not ticket.done:
            self._inflight = ticket
        return ticket

    def finish_update(self, ticket: Optional[_RepairTicket] = None) -> int:
        """Complete a repair started by ``begin_update``.

        Blocks on the in-flight descent (charging only the non-overlapped
        wait to the descend phase), validates the window, widens/commits.
        Returns the number of nodes whose core number changed.
        """
        if ticket is None:
            ticket = self._inflight
        if ticket is None:
            return 0
        if ticket.done:
            return ticket.changed
        if ticket is self._inflight:
            self._inflight = None
        try:
            res = self._descend_read(ticket.pending, full_interval=False)
        except Exception:
            # the in-flight device result is unreadable (device error
            # surfaced at the sync point): recover with the exact host peel
            metrics().counter("repair_dispatch_failures_total").inc()
            return self._recover_ref(ticket.ctx).changed
        return self._resolve(
            ticket.ctx, ticket.margin, ticket.lo, ticket.cand,
            res,
        ).changed

    def _settle(self) -> None:
        """Finish any in-flight ticket (public entry points call this so an
        overlapped repair is never observable)."""
        if self._inflight is not None:
            self.finish_update(self._inflight)

    def on_update(self, added=None, removed=None) -> int:
        """Repair after a mixed block of graph mutations has been applied.

        ``added``/``removed`` are the (m, 2) edge arrays the graph actually
        accepted (the return values of ``add_edges``/``remove_edges``).
        Returns the number of nodes whose core number changed. Synchronous:
        ``begin_update`` + ``finish_update`` back to back.
        """
        return self.finish_update(self.begin_update(added, removed))

    def on_edge_block(self, edges) -> int:
        """Repair after ``g.add_edges(edges)`` accepted ``edges`` (one union
        subcore sweep for the whole block). Returns #nodes promoted."""
        before = self.promoted
        self.on_update(added=edges)
        return self.promoted - before

    def on_remove(self, edges) -> int:
        """Repair after ``g.remove_edges(edges)`` removed ``edges``.
        Returns #nodes demoted."""
        before = self.demoted
        self.on_update(removed=edges)
        return self.demoted - before

    def on_edge(self, u: int, v: int) -> int:
        """Repair after ``g.add_edge(u, v)`` returned True.

        Single-edge compatibility wrapper over ``on_edge_block``; returns the
        number of nodes whose core number was promoted.
        """
        return self.on_edge_block(np.array([[u, v]], np.int64))

    # ------------------------------------------------------------- oracle

    def resync(self) -> int:
        """Recompute from the oracle; returns #mismatches found (0 expected).

        Called after compaction as a safety net — block maintenance is exact,
        so a nonzero return indicates a bug upstream.
        """
        self._settle()
        self._ensure_size()
        oracle = core_numbers_host(self.g.snapshot())
        n = self.g.n_nodes
        mismatches = int(np.sum(oracle != self._core[:n]))
        self._core[:n] = oracle
        return mismatches

    # ------------------------------------------------------------- drift

    def drift(self) -> int:
        """#nodes whose core number changed since the last ``mark_refresh``.

        Newly appeared nodes count (their baseline level is 0); so do nodes
        demoted by deletions — drift is direction-agnostic.
        """
        self._settle()
        self._ensure_size()
        n = self.g.n_nodes
        return int(np.sum(self._core[:n] != self._baseline[:n]))

    def membership_drift(self, k0: int) -> tuple:
        """k0-core membership churn since the last ``mark_refresh``.

        Returns (#nodes whose (core >= k0) flag flipped, current k0-core
        size). Counts departures (deletion-driven demotion out of the core)
        as well as arrivals.
        """
        self._settle()
        self._ensure_size()
        n = self.g.n_nodes
        now = self._core[:n] >= k0
        was = self._baseline[:n] >= k0
        return int(np.sum(now != was)), int(now.sum())

    def mark_refresh(self) -> None:
        """Record current levels as the embedding-table baseline."""
        self._settle()
        self._ensure_size()
        self._baseline = self._core.copy()
