"""Microbatching query front end of the online embedding service.

Queries (embedding lookups and link scores) are coalesced into fixed-size
batches so every flush runs the same static-shaped jit program regardless of
traffic: node lists are padded with the graph's sentinel id, and the sentinel
threads through every gather (sentinel ELL row -> no valid neighbours; slot
sentinel -> zero table row), so padding costs nothing and never branches.

Per flush:

1. known nodes answer straight from the store's device table;
2. unknown ("cold-start") nodes get the paper's §2.2 rule, one shot: the
   masked mean of their *already-embedded* neighbours, computed by the same
   ``ell_mean`` kernel path the offline propagation uses — a gather over the
   ELL rows remapped node->slot into the store table;
3. resolved cold starts are written back (with the node's current core
   number, so staleness tracking covers them), turning one-shot propagation
   into a cascade as traffic touches successive shells.

The service also owns ingestion policy: streamed edges arrive in **blocks**
through ``ingest_block`` (``DynamicGraph.add_edges`` + one block repair,
begun with ``IncrementalCore.begin_update`` and landed at the next sync
point) and are retracted through ``retract_block`` (``remove_edges`` + the
same repair), with periodic double-buffered compaction. ``retrain_pressure`` (k0-core membership
drift since the last refresh — arrivals *and* deletion-driven departures)
gates when retraining is actually needed, and ``maybe_retrain`` acts on it:
with a :class:`~repro.serve.retrain.Retrainer` attached (``set_retrainer``),
the drifted k0-core is re-embedded (CoreWalk+SGNS, warm start), Procrustes-
aligned into the serving space, and hot-swapped into the store with query
flushes interleaved between the swap's chunked scatters.
"""
from __future__ import annotations

import dataclasses
import time
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.obs import Histogram, compiled_cost, metrics
from repro.obs import trace as obs

from . import faults
from .kcore_inc import IncrementalCore
from .recovery import KIND_INGEST, KIND_RETRACT
from .store import EmbeddingStore
from .stream import DynamicGraph

# failures a retrain rolls back and survives; any other error propagates
_RECOVERABLE = (faults.InjectedFault, jax.errors.JaxRuntimeError)

__all__ = ["EmbeddingService", "ServiceStats", "top_k_reference"]

# exact-percentile retention: latency percentiles describe the most recent
# FLUSH_WINDOW flushes / RETRAIN_WINDOW retrains (steady state, bounded
# memory); the histograms' bucket counts still cover the whole lifetime
FLUSH_WINDOW = 4096
RETRAIN_WINDOW = 64


@dataclasses.dataclass
class ServiceStats:
    queries: int = 0
    store_hits: int = 0
    cold_starts: int = 0
    unresolved: int = 0
    flushes: int = 0
    edges_ingested: int = 0
    edges_removed: int = 0
    ingest_blocks: int = 0
    compactions: int = 0
    retrains: int = 0
    last_swap_version: int = -1  # -1 = no retrain swap has happened yet
    degraded_queries: int = 0  # answered from stale rows (flush fallback)
    retrain_failures: int = 0  # retrains rolled back transactionally
    hangs: int = 0  # HangWatchdog firings around blocking device syncs
    # bounded fixed-bucket histograms (obs.metrics.Histogram): percentiles
    # are exact over the retained window (FLUSH_WINDOW / RETRAIN_WINDOW most
    # recent samples), lifetime bucket counts feed the metrics exporters —
    # long-lived services keep steady-state percentiles without unbounded
    # growth or warm-up skew
    topk_queries: int = 0  # nodes served through top_k_neighbors
    flush_seconds: Histogram = dataclasses.field(
        default_factory=lambda: Histogram(window=FLUSH_WINDOW)
    )
    retrain_seconds: Histogram = dataclasses.field(
        default_factory=lambda: Histogram(window=RETRAIN_WINDOW)
    )
    topk_seconds: Histogram = dataclasses.field(
        default_factory=lambda: Histogram(window=FLUSH_WINDOW)
    )

    @property
    def cold_fraction(self) -> float:
        return self.cold_starts / max(self.queries, 1)


class EmbeddingService:
    def __init__(
        self,
        graph: DynamicGraph,
        cores: IncrementalCore,
        store: EmbeddingStore,
        *,
        batch: int = 64,
        write_back: bool = True,
        compact_every: int = 1024,
        k0: Optional[int] = None,
        retrain_threshold: float = 0.1,
        impl: str = "auto",
        hang_timeout: Optional[float] = None,
        flush_retries: int = 1,
        retry_backoff: float = 0.05,
        transactional_retrain: bool = True,
    ):
        self.graph = graph
        self.cores = cores
        self.store = store
        self.batch = int(batch)
        self.write_back = write_back
        self.compact_every = int(compact_every)
        self.k0 = k0
        self.retrain_threshold = float(retrain_threshold)
        self.impl = impl
        # pipelined ingest: stage block N+1 (host dedup/canonicalise) while
        # block N's jitted descent dispatch is still in flight, then land the
        # repair + deferred per-block tail at the next sync point
        self._tail_due = False
        self.stats = ServiceStats()
        # retraining loop: a Retrainer (serve.retrain) attached via
        # set_retrainer; auto mode re-checks drift after every ingested block
        self.retrainer = None
        self.auto_retrain = False
        self.retrain_budget = 0  # max retrains per service life (0 = no cap)
        self._pending: List[np.ndarray] = []
        self._n_pending = 0
        # fault tolerance: optional recovery manager (WAL + snapshots),
        # bounded flush retries with a stale-row degraded fallback, a
        # transactional retrain (store rolled back on any stage failure),
        # and an optional hang watchdog around blocking device syncs
        self._recovery = None
        # live SLO engine (obs.slo): attach_slo() wires the stock
        # objectives; hot paths feed it only when attached (None check)
        self._slo = None
        self.flush_retries = max(int(flush_retries), 0)
        self.retry_backoff = float(retry_backoff)
        self.transactional_retrain = bool(transactional_retrain)
        self.degraded = False
        self._watchdog = None
        if hang_timeout is not None and hang_timeout > 0:
            from repro.distributed.watchdog import HangWatchdog

            self._watchdog = HangWatchdog(float(hang_timeout), self._on_hang)

        plan = store.plan
        ell_mean = ops.ell_mean if plan is None else plan.ell_mean

        def _cold(nodes, nbr, slot_of, table, sentinel, cap, found):
            # sentinel / cap arrive as data: under a ShardPlan both the ELL
            # mirror and the store table carry shard-padding rows, so the
            # sentinel id / slot bound are NOT shape[0] - 1
            idx = nbr[nodes]  # (B, W) neighbour node ids
            slots = slot_of[idx]  # (B, W) store slots (sentinel = capacity)
            valid = (idx != sentinel) & (slots < cap)
            cold = ell_mean(slots, valid, table, impl=impl)
            resolved = valid.any(axis=1)
            # slot gather of the found rows + select against the cold-start
            # means — spill-tier rows carry found=True with a sentinel slot
            # (zero row) and are overlaid host-side after the readback
            own = jnp.where(found, slot_of[nodes], cap)
            out = jnp.where(found[:, None], table[own], cold)
            return out, resolved

        def _fused_wb(nodes, nbr, slot_of, table, sentinel, cap, found,
                      wb_slots):
            # the full fused dispatch: gather -> §2.2 cold-start -> select
            # -> write-back scatter, one program, one device round trip.
            # wb_slots[i] is the pre-reserved target slot for a cold row
            # (``cap`` = no write-back); unresolved rows redirect to the
            # zero sentinel row and scatter zeros, so the sentinel stays
            # zero and no branch depends on the readback
            out, resolved = _cold(
                nodes, nbr, slot_of, table, sentinel, cap, found
            )
            do_wb = (~found) & resolved & (wb_slots < cap)
            wslots = jnp.where(do_wb, wb_slots, cap)
            wvals = jnp.where(do_wb[:, None], out, 0.0)
            return out, resolved, table.at[wslots].set(wvals)

        # recompile only when ELL width / table capacity / node_cap change;
        # under a ShardPlan the scattered table must come back row-sharded
        if plan is None:
            self._fused_ro_fn = jax.jit(_cold)
            self._fused_wb_fn = jax.jit(_fused_wb)
        else:
            rep = plan.replicated()
            self._fused_ro_fn = jax.jit(_cold, out_shardings=(rep, rep))
            self._fused_wb_fn = jax.jit(
                _fused_wb, out_shardings=(rep, rep, plan.row_sharding(2))
            )
        self._fused_key = None  # last (capacity, ELL, node_cap) compiled

    # ------------------------------------------------------------ ingestion

    def attach_recovery(self, manager) -> None:
        """Attach a :class:`~repro.serve.recovery.RecoveryManager`: every
        block is WAL-logged before mutation, snapshots run on its cadence."""
        self._recovery = manager

    def attach_slo(self, engine=None, **thresholds):
        """Attach a live :class:`~repro.obs.slo.SLOEngine` (or build the
        stock one, ``thresholds`` forwarded to
        :func:`~repro.obs.slo.default_slos`).

        Event objectives (flush latency, per-block ingest rate, degraded
        fraction) are fed from the hot paths at one comparison + one deque
        append per event; the staleness objective is provider-backed (the
        stale-row walk is O(resident rows)) and sampled only when
        ``slo_health()`` / ``publish_metrics`` pull it. Returns the engine.
        """
        if engine is None:
            from repro.obs.slo import default_slos

            thresholds.setdefault(
                "staleness_provider",
                lambda: self.store.staleness(self.cores.core),
            )
            engine = default_slos(**thresholds)
        self._slo = engine
        return engine

    def slo_health(self):
        """Current SLO snapshot (``None`` when no engine is attached)."""
        return None if self._slo is None else self._slo.health()

    def _on_hang(self) -> None:
        """HangWatchdog callback: count the hang, enter degraded mode."""
        self.stats.hangs += 1
        self.degraded = True
        metrics().counter("serve_hangs_total").inc()
        metrics().gauge("serve_degraded").set(1)

    def pet_watchdog(self) -> None:
        """Reset the hang timer from inside a long multi-stage section
        (the retrainer pets between stages)."""
        if self._watchdog is not None and self._watchdog.armed:
            self._watchdog.pet()

    @staticmethod
    def _validate_block(edges) -> np.ndarray:
        """Strict block validation: the graph layer silently drops
        self-loops/duplicates, but at the service boundary malformed input
        is an error — a negative id or a float block would otherwise wrap
        into the sentinel row and corrupt the grouped scatter silently."""
        arr = np.asarray(edges)
        if arr.dtype == object or not np.issubdtype(arr.dtype, np.integer):
            raise ValueError(
                f"edge block must have an integer dtype, got {arr.dtype}"
            )
        try:
            arr = arr.reshape(-1, 2)
        except ValueError:
            raise ValueError(
                f"edge block must be (m, 2)-shaped, got shape {arr.shape}"
            )
        arr = arr.astype(np.int64, copy=False)
        if arr.size:
            if int(arr.min()) < 0:
                bad = arr[(arr < 0).any(axis=1)][0]
                raise ValueError(
                    f"node ids must be non-negative, got edge {tuple(bad)}"
                )
            loops = arr[:, 0] == arr[:, 1]
            if loops.any():
                v = int(arr[loops][0, 0])
                raise ValueError(f"self-loops are not allowed, got ({v}, {v})")
        return arr

    def _maybe_compact(self) -> None:
        if self.graph.edges_since_compact >= self.compact_every or (
            self.graph.overflow_arcs > max(16, self.graph.n_edges // 20)
        ):
            self.graph.compact()
            self.stats.compactions += 1
            metrics().counter("serve_compactions_total").inc()

    def _sync_ingest(self) -> None:
        """Land the in-flight repair and run the deferred per-block tail.

        Ingest defers the post-repair tail (compaction check, auto retrain)
        to the next sync point. Running it here — after the repair landed
        and *before* any new mutation — runs the tail on the graph state of
        a synchronous repair (``IncrementalCore.on_update``). The flag flips
        before the tail runs so a retrain-triggered flush re-entering this
        method is a no-op.
        """
        self.cores.finish_update()
        if self._tail_due:
            self._tail_due = False
            self._maybe_compact()
            if self.auto_retrain:
                self.maybe_retrain()

    def sync(self) -> None:
        """Explicit flush boundary: block until pipelined ingest fully lands."""
        self._sync_ingest()

    def ingest_block(self, edges: np.ndarray) -> np.ndarray:
        """Stream an edge block: one staged insert + one block core repair.

        Returns the (m', 2) edges accepted (self-loops, duplicates, and
        edges already present are dropped by the graph). This block's
        canonicalisation overlaps the previous block's in-flight descent
        dispatch, and the repair readback + per-block tail are deferred to
        the next ingest/retract/flush/``sync()``.
        """
        edges = self._validate_block(edges)
        t_slo = time.perf_counter() if self._slo is not None else 0.0
        with obs.span("serve.ingest", block=len(edges)) as sp:
            if self._recovery is not None:  # durable *before* any mutation
                self._recovery.log_block(KIND_INGEST, edges)
            faults.check("ingest_apply")
            # host-only staging overlaps block N-1's device dispatch
            staged = self.graph.stage_block(edges)
            self._sync_ingest()
            accepted = self.graph.add_edges(staged, staged=True)
            if len(accepted):
                self.cores.begin_update(added=accepted)
            self._tail_due = True
            sp.set(accepted=len(accepted))
            self.stats.edges_ingested += len(accepted)
            self.stats.ingest_blocks += 1
            metrics().counter("serve_edges_ingested_total").inc(len(accepted))
        if self._slo is not None and len(accepted):
            # pipelined blocks measure staging + the previous block's sync —
            # the rate traffic actually experiences at this boundary
            self._slo.observe(
                "ingest_rate",
                len(accepted) / max(time.perf_counter() - t_slo, 1e-9),
            )
        if self._recovery is not None:
            self._recovery.after_block()
        return accepted

    def retract_block(self, edges: np.ndarray) -> int:
        """Retract an edge block: staged delete + one block core repair.

        Unknown edges are skipped; returns the number actually removed.
        Demotions feed the same drift/staleness signals as promotions.
        Pipelines exactly like ``ingest_block``.
        """
        edges = self._validate_block(edges)
        with obs.span("serve.retract", block=len(edges)) as sp:
            if self._recovery is not None:  # durable *before* any mutation
                self._recovery.log_block(KIND_RETRACT, edges)
            faults.check("ingest_apply")
            staged = self.graph.stage_block(edges)
            self._sync_ingest()
            removed = self.graph.remove_edges(staged, staged=True)
            if len(removed):
                self.cores.begin_update(removed=removed)
            self._tail_due = True
            sp.set(removed=len(removed))
            self.stats.edges_removed += len(removed)
            metrics().counter("serve_edges_removed_total").inc(len(removed))
        if self._recovery is not None:
            self._recovery.after_block()
        return len(removed)

    def ingest(self, u: int, v: int) -> bool:
        """Stream one edge (single-edge convenience over ``ingest_block``)."""
        return bool(len(self.ingest_block(np.array([[u, v]], np.int64))))

    def retract(self, u: int, v: int) -> bool:
        """Retract one edge (single-edge convenience over ``retract_block``)."""
        return bool(self.retract_block(np.array([[u, v]], np.int64)))

    def ingest_edges(self, edges: np.ndarray, block_size: int = 256) -> int:
        """Stream an edge array in ``block_size`` chunks; returns #accepted."""
        edges = np.asarray(edges)
        block_size = max(int(block_size), 1)
        n = sum(
            len(self.ingest_block(edges[s : s + block_size]))
            for s in range(0, len(edges), block_size)
        )
        self.sync()  # land the last block's in-flight repair + deferred tail
        return n

    def stream_with_churn(
        self,
        edges: np.ndarray,
        *,
        block_size: int = 256,
        churn: float = 0.0,
        rng: Optional[np.random.Generator] = None,
    ) -> Tuple[int, int]:
        """Stream ``edges`` in blocks, retracting a ``churn`` fraction of the
        previously streamed (and not yet retracted) edges after each block.

        The replay loop the launcher and the serving benchmark share; returns
        (#edges accepted, #edges retracted).
        """
        edges = np.asarray(edges)
        block_size = max(int(block_size), 1)
        rng = np.random.default_rng() if rng is None else rng
        live: List[Tuple[int, int]] = []  # accepted and not yet retracted
        n_in = n_out = 0
        for start in range(0, len(edges), block_size):
            block = edges[start : start + block_size]
            accepted = self.ingest_block(block)
            n_in += len(accepted)
            live.extend(map(tuple, accepted))
            n_churn = min(int(round(churn * len(block))), len(live))
            if n_churn:
                pick = rng.choice(len(live), size=n_churn, replace=False)
                gone = set(pick.tolist())
                n_out += self.retract_block(np.array([live[i] for i in pick]))
                live = [e for i, e in enumerate(live) if i not in gone]
        self.sync()  # land the last block's in-flight repair + deferred tail
        return n_in, n_out

    # ------------------------------------------------------------- queries

    def submit(self, node: int) -> int:
        """Queue an embedding query; returns its index in the next flush."""
        return int(self.submit_many(np.asarray([node], np.int64))[0])

    def submit_many(self, nodes: Sequence[int]) -> np.ndarray:
        """Queue a whole batch of queries in one vectorized append.

        Returns the (len(nodes),) indices the queries will occupy in the
        next ``flush()`` output. The pending queue holds arrays, not Python
        ints, so submitting N nodes costs O(1) list work — the per-node
        Python loop the old ``embed`` path paid is gone.
        """
        nodes = np.asarray(nodes, np.int64).reshape(-1)
        if nodes.size and int(nodes.min()) < 0:
            bad = int(nodes[nodes < 0][0])
            raise ValueError(f"node id must be non-negative, got {bad}")
        start = self._n_pending
        if nodes.size:
            self._pending.append(nodes)
            self._n_pending += len(nodes)
        return np.arange(start, start + len(nodes))

    @property
    def pending(self) -> int:
        return self._n_pending

    def _wb_cores(self, wb_nodes: np.ndarray) -> np.ndarray:
        """Current core numbers for write-back staleness tagging."""
        core = self.cores.core
        return np.where(
            wb_nodes < len(core),
            core[np.minimum(wb_nodes, max(len(core) - 1, 0))], 0
        )

    def _compile_fused(self, sentinel: int) -> None:
        """Compile both flush dispatch variants when a shape changes.

        Which variant a batch takes depends on its cold/warm mix, and a
        steady-state flush must never eat the other variant's cold compile
        mid-run. This runs before the flush's retry loop, so a lowering or
        compile error propagates instead of degrading the batch. The warm
        call runs on an all-padding batch: its scatter targets only the zero
        sentinel row (all slots == capacity, values 0) and both outputs are
        discarded.
        """
        st = self.store
        ell = self.graph.ell()
        key = (int(st.capacity), ell.neighbours.shape, int(sentinel))
        if key == self._fused_key:
            return
        args = (
            jnp.asarray(np.full(self.batch, sentinel, np.int64)),
            ell.neighbours,
            st.slot_table_dev(),
            st.table(),
            jnp.int32(sentinel),
            jnp.int32(st.capacity),
            jnp.asarray(np.zeros(self.batch, bool)),
        )
        self._fused_ro_fn(*args)
        self._fused_wb_fn(
            *args, jnp.asarray(np.full(self.batch, st.capacity, np.int32))
        )
        self._fused_key = key

    def _flush_batch(self, nodes: np.ndarray) -> np.ndarray:
        """One static-shaped batch (len == self.batch, padded with -1).

        The whole batch touches the device **once**: slot gather, §2.2
        ELL neighbour-mean cold start, found/cold select, and the write-back
        scatter of resolved cold rows all run inside one jitted dispatch
        (``_fused_wb_fn``). The host's only jobs are slot reservation
        before the dispatch and metadata commit after the readback.

        The ``serve.flush`` span's children partition it: ``flush.stage``
        (slot map, compile check, promotion, slot reservation, the device
        arguments), ``flush.wait`` (dispatch, blocking sync, readback),
        ``flush.commit`` (write-back bookkeeping, spill overlay, stats) and,
        once every retry has failed, ``flush.degraded``.
        """
        t0 = time.perf_counter()
        with obs.span("serve.flush", batch=self.batch) as sp:
            st = self.store
            sentinel = self.graph.node_cap
            with obs.span("flush.stage"):
                # align the slot map with the graph's id space up front so
                # its device shape only changes when the graph grows
                # (O(log n) jit recompiles). Padding travels as -1 and is
                # masked here — a sentinel snapshotted at enqueue time could
                # alias a node id minted by later growth
                st.ensure_nodes(sentinel)
                real = (nodes >= 0) & (nodes < sentinel)
                nodes_c = np.where(real, nodes, sentinel)
                self._compile_fused(sentinel)
            degraded_batch = False
            wb_slots_u = None
            for attempt in range(self.flush_retries + 1):
                try:
                    with obs.span("flush.stage"):
                        # spill-tier rows must answer queries directly
                        # (capacity < working set must never thrash real
                        # embeddings into cold-start means): restore what
                        # fits, overlay the rest
                        st.promote(nodes_c)
                        slots = st.slots_of(nodes_c)
                        resident = slots < st.capacity
                        bounced = {}  # row -> spilled vec served host-side
                        if st.spilled:
                            for i in np.where(real & ~resident)[0]:
                                hit = st.peek_spill(int(nodes_c[i]))
                                if hit is not None:
                                    bounced[int(i)] = hit
                        st.note_fused_gather(slots, resident, len(bounced))
                        found = resident.copy()
                        if bounced:
                            found[list(bounced)] = True
                        cold = real & ~found
                        # cold-start means must see every *embedded*
                        # neighbour, including rows currently spilled to host
                        if cold.any() and st.spilled:
                            nbrs = np.concatenate(
                                [self.graph.neighbours(int(v))
                                 for v in nodes_c[cold]]
                            )
                            st.promote(nbrs)
                        # dedup within the batch: duplicate cold ids share
                        # one reserved slot (and later count as one cold
                        # start)
                        uniq_cold, first_pos = np.unique(
                            nodes_c[cold], return_index=True
                        )
                        if self.write_back and len(uniq_cold):
                            wb_slots_u = st.reserve_slots(len(uniq_cold))
                        wb_slots = np.full(len(nodes), st.capacity, np.int32)
                        if wb_slots_u is not None:
                            slot_of_cold = dict(
                                zip(uniq_cold.tolist(), wb_slots_u.tolist())
                            )
                            for i in np.where(cold)[0]:
                                wb_slots[i] = slot_of_cold[int(nodes_c[i])]

                        ell = self.graph.ell()
                        faults.check("flush_dispatch")
                        args = (
                            jnp.asarray(nodes_c),
                            ell.neighbours,
                            st.slot_table_dev(),
                            st.table(),
                            jnp.int32(sentinel),
                            jnp.int32(st.capacity),
                            jnp.asarray(found),
                        )
                    with obs.span("flush.wait"):
                        if wb_slots_u is not None:
                            out, resolved, table2 = self._fused_wb_fn(
                                *args, jnp.asarray(wb_slots)
                            )
                        else:  # nothing to scatter: skip the table write
                            out, resolved = self._fused_ro_fn(*args)
                        wd = self._watchdog
                        if wd is not None:
                            wd.arm()
                        try:
                            out = np.asarray(out)  # the blocking device sync
                        finally:
                            if wd is not None:
                                wd.disarm()
                        resolved = np.asarray(resolved)
                    with obs.span("flush.commit"):
                        # commit the scattered rows: adopt the post-scatter
                        # table, tag versions/cores, return unresolved slots
                        # to the pool
                        if wb_slots_u is not None:
                            cold_rows = np.where(cold)[0][first_pos]
                            ok = resolved[cold_rows]
                            st.adopt_fused(
                                table2, uniq_cold[ok], wb_slots_u[ok],
                                self._wb_cores(uniq_cold[ok]),
                            )
                            if (~ok).any():
                                st.release_slots(wb_slots_u[~ok])
                        elif self.write_back and len(uniq_cold):
                            # free list could not cover the batch: evicting
                            # write-back through put_many (host readback
                            # path)
                            cold_rows = np.where(cold)[0][first_pos]
                            ok = resolved[cold_rows]
                            if ok.any():
                                st.put_many(
                                    uniq_cold[ok], out[cold_rows[ok]],
                                    self._wb_cores(uniq_cold[ok]),
                                )
                        for i, vec in bounced.items():  # spill-tier overlay
                            out[i] = vec
                        if self.degraded:  # a healthy flush clears degraded
                            self.degraded = False
                            metrics().gauge("serve_degraded").set(0)
                    break
                except Exception:
                    metrics().counter("serve_flush_failures_total").inc()
                    if wb_slots_u is not None:  # undo the reservation exactly
                        st.release_slots(wb_slots_u)
                        wb_slots_u = None
                    if attempt < self.flush_retries:
                        time.sleep(self.retry_backoff * (2 ** attempt))
                        continue
                    # degraded serving: answer from whatever rows both store
                    # tiers already hold (side-effect free peek — no promote,
                    # no device dispatch), cold starts stay unresolved
                    with obs.span("flush.degraded"):
                        vecs, found, _, _ = self.store.peek_many(nodes_c)
                        cold = real & ~found
                        uniq_cold = np.unique(nodes_c[cold])
                        out = np.asarray(vecs, np.float32).copy()
                        resolved = np.zeros(len(nodes), bool)
                        degraded_batch = True
                        if not self.degraded:
                            self.degraded = True
                            metrics().gauge("serve_degraded").set(1)

            with obs.span("flush.commit"):
                n_real = int(real.sum())
                n_hits = int((real & found).sum())
                # duplicates within one batch are one cold start, not several
                n_cold = int(len(uniq_cold))
                if len(uniq_cold):
                    uniq_resolved = resolved[
                        np.where(cold)[0][
                            np.unique(nodes_c[cold], return_index=True)[1]
                        ]
                    ]
                    n_unresolved = int((~uniq_resolved).sum())
                else:
                    n_unresolved = 0
                self.stats.queries += n_real
                self.stats.store_hits += n_hits
                self.stats.cold_starts += n_cold
                self.stats.unresolved += n_unresolved
                reg = metrics()
                if degraded_batch:
                    self.stats.degraded_queries += n_real
                    reg.counter("serve_degraded_queries_total").inc(n_real)
                reg.counter("serve_queries_total").inc(n_real)
                reg.counter("serve_store_hits_total").inc(n_hits)
                reg.counter("serve_cold_starts_total").inc(n_cold)
                reg.counter("serve_unresolved_total").inc(n_unresolved)
                self.stats.flushes += 1
                dt = time.perf_counter() - t0
                self.stats.flush_seconds.observe(dt)
                if self._slo is not None:
                    self._slo.observe("flush_latency", dt)
                    self._slo.observe(
                        "degraded_serving", 1.0 if degraded_batch else 0.0
                    )
            sp.set(hits=n_hits, cold=n_cold, unresolved=n_unresolved)
        return out

    def flush(self) -> np.ndarray:
        """Drain the pending queue in static batches; returns (Q, dim)."""
        self._sync_ingest()  # queries must see fully-landed cores/compaction
        queue = (
            np.concatenate(self._pending)
            if self._pending
            else np.zeros(0, np.int64)
        )
        self._pending = []
        self._n_pending = 0
        outs = []
        for start in range(0, len(queue), self.batch):
            chunk = queue[start : start + self.batch]
            # pad with -1, not the current graph sentinel: node_cap grows
            # under ensure_nodes/compaction, so a sentinel snapshotted here
            # could alias a node id that is valid by the time the batch
            # dispatches — -1 can never collide with a real id
            padded = np.full(self.batch, -1, np.int64)
            padded[: len(chunk)] = chunk
            outs.append(self._flush_batch(padded)[: len(chunk)])
        if not outs:
            return np.zeros((0, self.store.dim), np.float32)
        return np.concatenate(outs, axis=0)

    def embed(self, nodes: Sequence[int]) -> np.ndarray:
        """Convenience: submit_many + flush. Returns (len(nodes), dim)."""
        self.submit_many(nodes)
        return self.flush()

    def link_scores(self, pairs: np.ndarray) -> np.ndarray:
        """Cosine link scores for (P, 2) node pairs (cold-starts both ends).

        Cosine, matching the retrain-eval AUC ranking (propagation shrinks
        norms shell by shell, so raw dot products rank by depth as much as
        affinity); normalisation goes through the same
        :func:`~repro.kernels.ops.normalize_rows` scoring tile the top-k
        retrieval kernel uses, so link scores and ``top_k_neighbors``
        scores are the same numbers. Repeated endpoints are deduplicated
        into a single flush slot — a pair list touching few distinct nodes
        no longer triggers redundant cold-start dispatches.
        """
        pairs = np.asarray(pairs, np.int64)
        flat = pairs.reshape(-1)
        uniq, inv = np.unique(flat, return_inverse=True)
        with obs.span("serve.link", pairs=len(pairs), uniq=len(uniq)):
            vecs = self.embed(uniq)
            with obs.span("link.score"):
                emb = np.asarray(ops.normalize_rows(jnp.asarray(vecs)))
                e = emb[inv]
                scores = np.sum(e[0::2] * e[1::2], axis=1)
        return scores

    def top_k_neighbors(
        self, nodes: Sequence[int], k: int
    ) -> Tuple[np.ndarray, np.ndarray]:
        """k nearest resident embeddings by cosine, per query node.

        Queries resolve through the normal flush path first (cold starts
        get their §2.2 propagation mean and are written back), then score
        against every *live* device-table row via the blockwise top-k
        kernel (``kernels.topk``) — the (Q, N) score matrix is never
        materialised. Each query node is excluded from its own result.

        Returns ``(node_ids (Q, k) int64, scores (Q, k) float32)`` ordered
        by (score desc, node-slot asc); -1 / -inf pad when fewer than k
        candidates are resident. Under a ShardPlan every shard reduces a
        partial top-k over its own rows and the host stitches the lists.
        """
        nodes = np.asarray(nodes, np.int64).reshape(-1)
        k = int(k)
        if k < 1 or not len(nodes):
            return (np.zeros((len(nodes), max(k, 0)), np.int64),
                    np.zeros((len(nodes), max(k, 0)), np.float32))
        t0 = time.perf_counter()
        with obs.span("serve.topk", batch=len(nodes), k=k) as sp:
            qv = self.embed(nodes)  # resolves cold starts + write-back
            st = self.store
            with obs.span("topk.prep"):
                qn = ops.normalize_rows(jnp.asarray(qv))
                tn = ops.normalize_rows(st.table())
                valid = jnp.asarray(st.row_valid())
            # ask for k+1 candidates: the query's own row (when resident)
            # is dropped host-side, leaving a full k for everyone
            kk = k + 1
            with obs.span("topk.wait"):
                if st.plan is None:
                    vals, idx = ops.top_k_scores(
                        qn, tn, kk, valid=valid, impl=self.impl,
                    )
                    vals = np.asarray(vals)
                    idx = np.asarray(idx, np.int64)
                else:
                    pv, pi = st.plan.partial_topk_fn(
                        qn, tn, valid, kk, self.impl,
                    )
                    vals, idx = st.plan.merge_topk(pv, pi, kk)
            with obs.span("topk.merge"):
                own = st.slots_of(nodes).astype(np.int64)  # capacity = absent
                keep = (idx >= 0) & (idx != own[:, None])
                order = np.argsort(~keep, axis=1, kind="stable")
                sel = np.take_along_axis(idx, order, 1)[:, :k]
                sval = np.take_along_axis(vals, order, 1)[:, :k]
                kept = np.take_along_axis(keep, order, 1)[:, :k]
                ids = np.where(
                    kept, st.node_of_slots(np.maximum(sel, 0)), -1
                )
                scores = np.where(kept, sval, -np.inf).astype(np.float32)
                self.stats.topk_queries += len(nodes)
                dt = time.perf_counter() - t0
                self.stats.topk_seconds.observe(dt)
                reg = metrics()
                reg.counter("serve_topk_queries_total").inc(len(nodes))
            sp.set(candidates=int(st.resident))
        return ids, scores

    # ----------------------------------------------------------- retraining

    def retrain_pressure(self) -> float:
        """Fraction of the k0-core whose membership flipped since refresh."""
        if self.k0 is None:
            return 0.0
        changed, size = self.cores.membership_drift(self.k0)
        return changed / max(size, 1)

    def should_retrain(self) -> bool:
        return self.retrain_pressure() >= self.retrain_threshold

    def set_retrainer(self, retrainer, *, auto: bool = False,
                      budget: int = 0) -> None:
        """Attach a :class:`~repro.serve.retrain.Retrainer` to close the loop.

        ``auto=True`` re-checks drift after every ingested/retracted block
        and refreshes in place; ``budget`` caps how many refreshes this
        service will run (0 = uncapped).
        """
        self.retrainer = retrainer
        self.auto_retrain = bool(auto)
        self.retrain_budget = int(budget)

    def maybe_retrain(self, force: bool = False, between=None):
        """Run one drift-triggered retrain+hot-swap cycle when due.

        Returns the :class:`~repro.serve.retrain.RetrainReport` (or None if
        no retrainer is attached, pressure is below threshold and ``force``
        is unset, the budget is spent, or the planner found nothing to
        refresh). ``between`` is forwarded to the rollout so query flushes
        can interleave with the swap's chunked scatters.
        """
        if self.retrainer is None:
            return None
        if self.retrain_budget and self.stats.retrains >= self.retrain_budget:
            return None
        if not force and not self.should_retrain():
            return None
        t0 = time.perf_counter()
        # transactional: capture the store (host copy) before any stage
        # runs, restore it wholesale on failure — a retrain that dies
        # mid-VersionRollout must not leave mixed-version rows. The core
        # baseline needs no rollback: mark_refresh only runs after a
        # successful swap. InjectedCrash (simulated process death) is a
        # BaseException and deliberately passes through. Only a device error
        # or an injected fault is absorbed; anything else, such as a kernel
        # the compiler refuses, is rolled back and re-raised.
        pre = None
        wd = self._watchdog
        if wd is not None:
            wd.arm()
        try:
            with obs.span("serve.retrain") as sp:
                if self.transactional_retrain:
                    with obs.span("retrain.rollback_copy"):
                        pre = self.store.state_dict()
                report = self.retrainer.run(between=between)
        except Exception as e:
            self.stats.retrain_failures += 1
            metrics().counter("serve_retrain_failures_total").inc()
            if pre is not None:
                self.store.load_state_dict(pre)
                if isinstance(e, _RECOVERABLE):
                    return None
            raise
        finally:
            if wd is not None:
                wd.disarm()
        if report is None:
            return None
        sp.set(version=report.version, rows=report.rows_swapped)
        self.stats.retrains += 1
        self.stats.last_swap_version = report.version
        dt = time.perf_counter() - t0
        self.stats.retrain_seconds.observe(dt)
        metrics().counter("serve_retrains_total").inc()
        return report

    def mark_refreshed(self) -> None:
        """Call after reloading the store from an offline retrain."""
        self.cores.mark_refresh()
        self.store.bump_version()

    # ------------------------------------------------------------- reports

    def latency_percentiles(self) -> Tuple[float, float]:
        """(p50, p99) per-flush seconds (each flush serves ``batch`` slots).

        Exact percentiles over the histogram's retained window — the most
        recent ``FLUSH_WINDOW`` (4096) flushes; earlier flushes still count
        in the histogram's bucket totals but no longer move the percentiles.
        """
        h = self.stats.flush_seconds
        if not len(h):
            return 0.0, 0.0
        p50, p99 = h.percentile([50, 99])
        return float(p50), float(p99)

    def topk_latency_percentiles(self) -> Tuple[float, float]:
        """(p50, p99) per-call ``top_k_neighbors`` seconds (same retained
        window semantics as :meth:`latency_percentiles`)."""
        h = self.stats.topk_seconds
        if not len(h):
            return 0.0, 0.0
        p50, p99 = h.percentile([50, 99])
        return float(p50), float(p99)

    def publish_metrics(self, registry=None) -> None:
        """Register this service's live stats into a metrics registry.

        The flush/retrain histograms are adopted by reference (the exporter
        reads the very objects ``_flush_batch`` observes into — one source
        of truth), counters/gauges are set to the current totals. Launchers
        call this right before exporting a snapshot; calling it again after
        a ``stats`` reset re-points the registry at the new histograms.
        """
        reg = metrics() if registry is None else registry
        st = self.stats
        reg.register("serve_flush_seconds", st.flush_seconds, replace=True)
        reg.register("serve_retrain_seconds", st.retrain_seconds,
                     replace=True)
        reg.register("serve_topk_seconds", st.topk_seconds, replace=True)
        for name, value in (
            ("serve_queries", st.queries),
            ("serve_store_hits", st.store_hits),
            ("serve_cold_starts", st.cold_starts),
            ("serve_unresolved", st.unresolved),
            ("serve_flushes", st.flushes),
            ("serve_ingest_blocks", st.ingest_blocks),
            ("serve_edges_ingested", st.edges_ingested),
            ("serve_edges_removed", st.edges_removed),
            ("serve_compactions", st.compactions),
            ("serve_retrains", st.retrains),
            ("serve_topk_queries", st.topk_queries),
            ("serve_degraded_queries", st.degraded_queries),
            ("serve_retrain_failures", st.retrain_failures),
            ("serve_hangs", st.hangs),
            ("serve_pending_queries", self.pending),
            ("store_resident_rows", self.store.resident),
            ("store_spilled_rows", self.store.spilled),
            ("store_evictions", self.store.evictions),
            ("graph_nodes", self.graph.n_nodes),
            ("graph_edges", self.graph.n_edges),
            ("graph_overflow_arcs", self.graph.overflow_arcs),
        ):
            reg.gauge(name).set(value)
        reg.gauge("serve_degraded").set(int(self.degraded))
        reg.gauge("serve_retrain_pressure").set(self.retrain_pressure())
        reg.gauge("store_staleness").set(
            self.store.staleness(self.cores.core)
        )
        if self.store.plan is not None:
            for s, rows in enumerate(self.store.shard_gather_rows):
                reg.gauge("store_gather_rows", shard=s).set(int(rows))
            reg.gauge("store_cross_shard_row_copies").set(
                int(self.store.cross_shard_row_copies)
            )
        if self._slo is not None:
            self._slo.publish(reg)

    def dispatch_cost_report(self) -> dict:
        """Measured per-dispatch cost of the fused flush program.

        AOT-compiles ``_fused_wb_fn`` (gather -> cold-start -> select ->
        write-back scatter) on the shapes the serving path currently
        dispatches and returns its ``cost_analysis``/``memory_analysis``
        numbers (flops, bytes accessed, argument/output/temp bytes) — the
        fused program's cost measured, not guessed. Cheap enough to call
        at export time only (one extra AOT compile, never on the hot path).
        """
        sentinel = self.graph.node_cap
        self.store.ensure_nodes(sentinel)
        ell = self.graph.ell()
        # mirror the flush path's host->device conversion so the AOT trace
        # sees the exact dtypes the live dispatch uses
        nodes = jnp.asarray(np.zeros(self.batch, np.int64))
        return compiled_cost(
            self._fused_wb_fn,
            nodes,
            ell.neighbours,
            self.store.slot_table_dev(),
            self.store.table(),
            jnp.int32(sentinel),
            jnp.int32(self.store.capacity),
            jnp.asarray(np.zeros(self.batch, bool)),
            jnp.asarray(np.full(self.batch, self.store.capacity, np.int32)),
        )


def top_k_reference(store: EmbeddingStore, nodes: np.ndarray,
                    queries: np.ndarray, k: int) -> np.ndarray:
    """Numpy all-pairs oracle for :meth:`EmbeddingService.top_k_neighbors`.

    ``queries`` are the embeddings served for ``nodes`` (call after the
    top-k call, whose flush wrote cold rows back). Scores every live store
    row by cosine (the same ``normalize_rows`` prep), drops each query's
    own row, and orders by (score desc, slot asc). Returns (Q, k) node ids,
    -1 padded.
    """
    cap = store.capacity
    table = np.asarray(store.table())[:cap]
    tn = np.asarray(ops.normalize_rows(jnp.asarray(table)))
    qn = np.asarray(ops.normalize_rows(jnp.asarray(queries)))
    sim = qn @ tn.T
    sim[:, ~np.asarray(store.row_valid())[:cap]] = -np.inf
    own = store.slots_of(np.asarray(nodes, np.int64))
    want = np.full((len(nodes), k), -1, np.int64)
    for i in range(len(nodes)):
        s = sim[i]
        if own[i] < cap:
            s[own[i]] = -np.inf
        order = np.lexsort((np.arange(len(s)), -s))[:k]
        live = order[s[order] > -np.inf]
        want[i, : len(live)] = store.node_of_slots(live)
    return want
