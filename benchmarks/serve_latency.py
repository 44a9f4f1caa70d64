"""Online-serving benchmark: ingest throughput + query latency.

Sweeps ingest throughput over block sizes — block size 1 is the per-edge
baseline (one core repair per edge), larger blocks stage the whole block and
run one union-subcore repair — then streams a mixed insert/delete workload to
exercise deletion-aware maintenance, and finally replays synthetic query
traffic through the microbatching front end for steady-state latency
percentiles.

Emits ``name,us_per_call,derived`` CSV lines (harness contract) and writes
``results/serve_latency.json`` with the block-size sweep (edges/s each, plus
the speedup of the largest block over the per-edge baseline), mixed-churn
oracle mismatches, query p50/p99, QPS, and the cold-start fraction. Every
ingest run also records a per-phase repair breakdown (region /
candidate-build / descend / fallback seconds, each tagged host vs device
backend) so the trajectory shows *where* repair time goes, not just edges/s.

``--shards N`` additionally runs the row-sharded serve stack (store table +
ELL mirror split over N devices via ``ShardPlan``) through the same ingest
and query replay, and records a ``sharding`` section: per-shard resident
balance, gather-row ownership per shard, cross-shard row copies, and the
sharded run's oracle mismatches (0 expected — sharding is placement-only).
On CPU run it under ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.

``--topk`` replays ``top_k_neighbors`` retrieval traffic through the
blockwise score+reduce kernel and records a ``topk`` section — query
p50/p99, QPS, and exact-match recall against a numpy all-pairs cosine
oracle (recall@k must be 1.0 with zero mismatches: the kernel is exact).
Combined with ``--shards N`` the sharded leg gets its own ``topk`` section
through the per-shard partial top-k + host stitch.

``--retrain`` adds the end-to-end retraining demo: a churny stream forces
k0-core drift, one drift-triggered CoreWalk+SGNS refresh + Procrustes
alignment + chunked hot swap runs with query flushes interleaved between
swap chunks, and the JSON gains a ``retrain`` section — retrain wall-time
per stage, swap latency, flush p99 before vs during the swap (the no-pause
check), the staleness trajectory (before -> after), and pre/post link-pred
AUC on held-out streamed edges (cosine ranking; primary metric restricted
to pairs inside the k0-core, where retraining actually re-embeds — the
all-known-endpoint AUC rides along for transparency).
``scripts/trend_serve_latency.py`` diffs two of these JSON artifacts
across runs.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

from repro.graph import generators
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.serve_embed import build_service
from repro.obs import device_profile, load_schema, record_memory, validate_or_raise
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs
from repro.obs.history import SCHEMA_VERSION, append_record
from repro.serve import ServiceStats
from repro.serve.service import top_k_reference


from .common import csv_line

BASELINE_CAP = 256  # per-edge baseline is slow by design; time a slice of it


WARMUP_EDGES = 32  # untimed prefix: jit-compiles the repair sweep shapes

SCHEMA_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "results", "serve_latency.schema.json",
)


def _ingest_run(g, block_size: int, *, seed: int, churn: float = 0.0,
                compact_every: int = 1024, max_edges: int = 0,
                shards: int = 1, repair_policy: str = "adaptive",
                pipeline: bool = True, slo: bool = False):
    """Fresh service; stream held-out edges in blocks.

    Returns ``(service, metrics dict)`` — the fully ingested service so the
    sharded leg can replay queries without re-streaming. The first
    ``WARMUP_EDGES`` of the stream are ingested untimed so the per-edge
    baseline does not amortise first-use jit compilation over its (short)
    timed run while the block runs start warm.
    """
    svc, stream_edges, _, _ = build_service(
        g, seed=seed, compact_every=compact_every, shards=shards,
        repair_policy=repair_policy, pipeline=pipeline,
    )
    if slo:
        svc.attach_slo()
    # two full blocks of warmup when the stream affords it: the adaptive
    # policy's cold-start decision and its one-shot exploration of the
    # other path land before timing, so the timed window measures the
    # settled crossover. Large blocks on a short stream keep the flat
    # warmup instead of starving the timed run.
    warm_n = max(WARMUP_EDGES, 2 * block_size)
    if len(stream_edges) - warm_n < 2 * block_size:
        warm_n = WARMUP_EDGES
    warm, stream_edges = stream_edges[:warm_n], stream_edges[warm_n:]
    if max_edges:
        stream_edges = stream_edges[:max_edges]
    svc.stream_with_churn(warm, block_size=block_size, churn=churn,
                          rng=np.random.default_rng(seed + 6))
    svc.cores.reset_phases()  # report where *timed* repair seconds go
    repeels0, descends0 = svc.cores.repeels, svc.cores.descends
    t0 = time.perf_counter()
    n_in, n_out = svc.stream_with_churn(
        stream_edges, block_size=block_size, churn=churn,
        rng=np.random.default_rng(seed + 7),
    )
    dt = time.perf_counter() - t0
    mismatches = svc.cores.resync()
    return svc, {
        "block_size": block_size,
        "edges_in": int(n_in),
        "edges_out": int(n_out),
        "edges_per_s": float((n_in + n_out) / max(dt, 1e-9)),
        "seconds": dt,
        "mismatches": int(mismatches),
        "compactions": int(svc.graph.compactions),
        # counters as timed-run deltas, matching the post-warmup phase timers
        "repeels": int(svc.cores.repeels - repeels0),
        "descends": int(svc.cores.descends - descends0),
        # region / candidate-build / descend / fallback split, each tagged
        # with the backend it ran on (host numpy vs jitted device path)
        "phases": svc.cores.phase_report(),
        # per-block repair-policy decisions, predicted-vs-actual phase cost,
        # and the shell-incremental re-peel depth histogram
        "policy": svc.cores.policy_report(),
    }


def _sharded_run(g, *, seed: int, shards: int, requests: int, batch: int,
                 compact_every: int, topk: bool = False):
    """Ingest + query replay on the row-sharded stack; returns the JSON
    ``sharding`` section (balance, traffic, oracle mismatches)."""
    # churn-free like the sweep's block-256 row, so sharded vs unsharded
    # ingest edges/s measure the same stream (deletions are parity-tested
    # in tests/multidevice, not timed here); the fully ingested service is
    # reused for the query replay rather than rebuilt and re-streamed
    svc, ingest = _ingest_run(
        g, 256, seed=seed, compact_every=compact_every, shards=shards
    )
    rng = np.random.default_rng(seed + 1)
    n_now = svc.graph.n_nodes
    for _ in range(4):  # untimed warmup (sharded jit programs)
        svc.embed(rng.integers(0, n_now, size=batch))
    svc.stats = ServiceStats()
    # traffic counters restart with the timed run, like the phase timers,
    # so balance/copies describe the same window as qps/p50
    svc.store.reset_shard_traffic()
    t0 = time.perf_counter()
    for _ in range(max(requests // (2 * batch), 1)):
        svc.embed(rng.integers(0, n_now, size=batch))
    t_query = time.perf_counter() - t0
    p50, p99 = svc.latency_percentiles()
    report = svc.store.shard_report()
    report.update(
        ingest_edges_per_s=ingest["edges_per_s"],
        mismatches=int(ingest["mismatches"]),
        query_p50_s=p50,
        query_p99_s=p99,
        qps=float(svc.stats.queries / max(t_query, 1e-9)),
    )
    if topk:
        # same replay through the per-shard partial top-k + host stitch;
        # recall vs the oracle must stay exactly 1.0 under sharding too
        report["topk"] = _topk_run(
            svc, seed=seed, requests=requests, batch=batch
        )
    return report


def _topk_run(svc, *, seed: int, requests: int, batch: int, k: int = 10):
    """Timed ``top_k_neighbors`` replay + exact-match recall vs the oracle.

    Replays random query batches through the retrieval endpoint for
    latency percentiles, then checks one batch against a numpy all-pairs
    cosine oracle (same ``normalize_rows`` epsilon, same self-exclusion,
    same (score desc, slot asc) tie order): ``recall_at_k`` must be 1.0
    with ``oracle_mismatches == 0`` — the blockwise kernel is exact, not
    approximate. Returns the JSON ``topk`` section.
    """
    rng = np.random.default_rng(seed + 5)
    n_now = svc.graph.n_nodes
    for _ in range(2):  # untimed warmup (top-k program compile)
        svc.top_k_neighbors(rng.integers(0, n_now, size=batch), k)
    svc.stats.topk_seconds.clear()
    queries0 = svc.stats.topk_queries
    n_calls = max(requests // (2 * batch), 2)
    t0 = time.perf_counter()
    for _ in range(n_calls):
        svc.top_k_neighbors(rng.integers(0, n_now, size=batch), k)
    dt = time.perf_counter() - t0
    p50, p99 = svc.topk_latency_percentiles()
    qps = (svc.stats.topk_queries - queries0) / max(dt, 1e-9)

    # exact-match recall vs the all-pairs oracle on one held-out batch
    st = svc.store
    q = rng.integers(0, n_now, size=batch)
    ids, scores = svc.top_k_neighbors(q, k)
    want = top_k_reference(st, q, svc.embed(q), k)
    mismatches = int((ids != want).sum())
    hits = total = 0
    for i in range(len(q)):
        live_ids = want[i][want[i] >= 0]
        total += len(live_ids)
        hits += len(np.intersect1d(ids[i][ids[i] >= 0], live_ids))
    return {
        "k": int(k),
        "queries": int(svc.stats.topk_queries - queries0),
        "query_p50_s": float(p50),
        "query_p99_s": float(p99),
        "qps": float(qps),
        "oracle_mismatches": int(mismatches),
        "recall_at_k": float(hits / max(total, 1)),
        "candidates": int(st.resident),
    }


def _negative_pairs(svc, pool: np.ndarray, n: int, rng) -> np.ndarray:
    """(<=n, 2) random non-edge pairs drawn from the ``pool`` node ids.

    Bounded rejection sampling: a near-clique pool (few non-edges) returns
    fewer pairs instead of spinning — the AUC is rank-based and does not
    need balanced classes.
    """
    if n <= 0 or len(pool) < 2:
        return np.zeros((0, 2), np.int64)
    out = []
    for _ in range(200 * n):
        u, v = rng.choice(pool, size=2)
        if u != v and not svc.graph.has_edge(int(u), int(v)):
            out.append((int(u), int(v)))
            if len(out) == n:
                break
    return np.asarray(out, np.int64).reshape(-1, 2)


def _link_auc(svc, pos: np.ndarray, neg: np.ndarray) -> float:
    """Cosine-similarity ranking AUC over served embeddings.

    Cosine, not the service's raw dot products: propagation shrinks norms
    shell by shell, so dot scores rank by depth as much as by affinity —
    cosine isolates the directional signal the retrain actually changes.
    """
    from repro.eval.linkpred import auc_score

    pairs = np.concatenate([pos, neg])
    emb = svc.embed(pairs.reshape(-1))
    e = emb / (np.linalg.norm(emb, axis=1, keepdims=True) + 1e-9)
    scores = np.sum(e[0::2] * e[1::2], axis=1)
    labels = np.concatenate([np.ones(len(pos)), np.zeros(len(neg))])
    return auc_score(labels, scores)


def _retrain_run(g, *, seed: int, quick: bool, batch: int = 64):
    """End-to-end drift->retrain->align->swap demo; returns the JSON section.

    A churny stream drives k0-core membership drift; the retrain is then
    triggered through the service's own pressure gate, with query flushes
    interleaved between the rollout's chunked scatters so the section can
    report flush p99 *during* the swap next to the pre-swap baseline (the
    zero-pause check). Link-pred AUC is measured on held-out streamed edges
    (never ingested) against random non-edges, before and after the swap.
    """
    from repro.launch.serve_embed import build_service
    from repro.serve.retrain import RetrainConfig, Retrainer
    from repro.skipgram.trainer import SGNSConfig

    svc, stream_edges, _, k0 = build_service(
        g, seed=seed, batch=batch, stream_frac=0.3,
        compact_every=256 if quick else 1024,
    )
    cfg = RetrainConfig(
        n_walks=8 if quick else 12,
        walk_length=16 if quick else 24,
        min_sgns_steps=200 if quick else 400,
        sgns=SGNSConfig(dim=svc.store.dim, epochs=0.25 if quick else 0.5,
                        seed=seed),
        prop_iters=8,
        swap_chunk=128,  # more chunks -> more interleaved flush samples
        seed=seed,
    )
    # manual trigger (auto off): the run must measure the swap, not bury it
    # inside stream_with_churn; the threshold still gates via should_retrain
    svc.retrain_threshold = 0.02
    svc.set_retrainer(Retrainer(svc, cfg))

    # hold out the stream tail for evaluation; churn-stream the rest
    n_tail = min(512, max(32, len(stream_edges) // 5))
    tail = np.asarray(stream_edges[-n_tail:], np.int64)
    rng = np.random.default_rng(seed + 3)
    svc.stream_with_churn(
        stream_edges[:-n_tail], block_size=256, churn=0.25, rng=rng
    )
    mismatches = svc.cores.resync()

    # eval sets from the held-out (never ingested) tail. Primary: edges with
    # both endpoints inside the current k0-core — the region retraining
    # actually re-embeds (below it, vectors are iterated neighbour means
    # both before and after the swap, so core-external pairs measure
    # propagation wash, not refresh quality). The all-known-endpoint AUC is
    # reported alongside for transparency.
    core_now = svc.cores.core
    deg_now = svc.graph.degrees()
    in_core = np.zeros(svc.graph.n_nodes, bool)
    in_core[: len(core_now)] = core_now >= svc.k0
    valid = (tail < svc.graph.n_nodes).all(axis=1)
    tail = tail[valid]
    known = deg_now[tail[:, 0]] > 0
    known &= deg_now[tail[:, 1]] > 0
    pos_all = tail[known][:128]
    pos_core = tail[in_core[tail[:, 0]] & in_core[tail[:, 1]]][:128]
    core_pool = np.where(in_core)[0]
    neg_core = _negative_pairs(svc, core_pool, len(pos_core), rng)
    neg_all = _negative_pairs(svc, np.where(deg_now > 0)[0], len(pos_all), rng)

    n_now = svc.graph.n_nodes
    for _ in range(4):  # jit warmup
        svc.embed(rng.integers(0, n_now, size=batch))

    pressure = svc.retrain_pressure()
    staleness_before = svc.store.staleness(svc.cores.core)
    auc_before = _link_auc(svc, pos_core, neg_core)
    auc_all_before = _link_auc(svc, pos_all, neg_all)

    # pre-swap flush latency baseline
    svc.stats.flush_seconds.clear()
    for _ in range(8):
        svc.embed(rng.integers(0, n_now, size=batch))
    _, p99_before = svc.latency_percentiles()

    # drift-triggered retrain with serving interleaved between swap chunks
    svc.stats.flush_seconds.clear()
    flushes_before_swap = svc.stats.flushes

    def serve_between():
        for _ in range(2):
            svc.embed(rng.integers(0, n_now, size=batch))

    report = svc.maybe_retrain(between=serve_between)
    during = np.asarray(svc.stats.flush_seconds, np.float64)
    p99_during = float(np.percentile(during, 99)) if during.size else 0.0
    flushes_during = int(svc.stats.flushes - flushes_before_swap)

    staleness_after = svc.store.staleness(svc.cores.core)
    auc_after = _link_auc(svc, pos_core, neg_core)
    auc_all_after = _link_auc(svc, pos_all, neg_all)
    section = {
        "triggered": report is not None,
        "pressure": float(pressure),
        "mismatches": int(mismatches),
        "eval_pairs_core": int(len(pos_core)),
        "eval_pairs_all": int(len(pos_all)),
        "auc_before": float(auc_before),
        "auc_after": float(auc_after),
        "auc_all_before": float(auc_all_before),
        "auc_all_after": float(auc_all_after),
        "staleness_before": float(staleness_before),
        "staleness_after": float(staleness_after),
        "flush_p99_before_s": float(p99_before),
        "flush_p99_during_swap_s": p99_during,
        "flushes_during_swap": flushes_during,
    }
    if report is not None:
        section.update(
            k0=int(report.k0),
            core_size=int(report.core_size),
            drifted=int(report.drifted),
            n_walks=int(report.n_walks),
            sgns_steps=int(report.sgns_steps),
            warm_rows=int(report.warm_rows),
            anchors=int(report.anchors),
            aligned=bool(report.aligned),
            align_residual=float(report.align_residual),
            version=int(report.version),
            rows_swapped=int(report.rows_swapped),
            swap_chunks=int(report.swap_chunks),
            retrain_seconds=report.times,
        )
    return section


# every crash point the sweep drives; hit indices are chosen so the crash
# lands mid-stream (mid-WAL-append, mid-snapshot, inside each retrain stage)
CRASH_POINTS = (
    ("wal_append", 7),
    ("wal_fsync", 9),
    ("snapshot_write", 2),
    ("snapshot_commit", 1),
    ("ingest_apply", 5),
    ("device_dispatch", 1),
    ("repair", 6),
    ("retrain_plan", 1),
    ("retrain_walks", 1),
    ("retrain_train", 1),
    ("retrain_align", 1),
    ("retrain_propagate", 1),
    ("retrain_swap", 1),
    ("retrain_swap_chunk", 2),
)


def _plan_ops(stream_edges, *, block_size: int, churn: float, seed: int):
    """Pre-generate the deterministic ingest/retract op list.

    Mirrors ``stream_with_churn`` but draws churn from *submitted* edges, so
    the ops are a pure function of ``(stream_edges, seed)`` — the crash run,
    the recovery resume, and the uninterrupted twin all replay the exact
    same list. Ops map 1:1 onto WAL records (every block is logged), so the
    durable WAL sequence number *is* the resume index.
    """
    rng = np.random.default_rng(seed)
    live = []
    ops = []
    for start in range(0, len(stream_edges), block_size):
        block = np.asarray(stream_edges[start:start + block_size], np.int64)
        ops.append(("ingest", block))
        live.extend(map(tuple, block))
        n_churn = min(int(round(churn * len(block))), len(live))
        if n_churn:
            pick = rng.choice(len(live), size=n_churn, replace=False)
            gone = set(pick.tolist())
            ops.append(
                ("retract", np.asarray([live[i] for i in pick], np.int64))
            )
            live = [e for i, e in enumerate(live) if i not in gone]
    return ops


def _apply_ops(svc, ops, start: int = 0):
    for kind, edges in ops[start:]:
        if kind == "ingest":
            svc.ingest_block(edges)
        else:
            svc.retract_block(edges)
    svc.sync()


def _attach_retrainer(seed: int):
    """Retrain loop factory shared by the twin, the crash runs, and the
    post-crash ``RecoveryManager.recover(configure=...)`` hook — replayed
    auto-retrains must re-fire with the identical configuration."""
    def attach(svc):
        from repro.serve.retrain import RetrainConfig, Retrainer
        from repro.skipgram.trainer import SGNSConfig

        cfg = RetrainConfig(
            n_walks=6, walk_length=12, min_sgns_steps=60,
            sgns=SGNSConfig(dim=svc.store.dim, epochs=0.1, seed=seed),
            prop_iters=6, swap_chunk=256, seed=seed,
        )
        svc.retrain_threshold = 0.02
        svc.set_retrainer(Retrainer(svc, cfg), auto=True, budget=2)
    return attach


def _fingerprint(svc):
    """Full serving state as host arrays (graph + store + cores + baseline);
    byte-equality of this dict is the bit-identical-recovery check."""
    from repro.serve.recovery import capture_state

    arrays, _ = capture_state(svc, 0)
    return arrays


def _diff_states(a, b):
    keys = sorted(set(a) | set(b))
    return [
        k for k in keys
        if k not in a or k not in b or not np.array_equal(a[k], b[k])
    ]


def _oracle_mismatches(svc) -> int:
    from repro.core.kcore import core_numbers_host

    oracle = core_numbers_host(svc.graph.snapshot())
    return int((np.asarray(svc.cores.core[: len(oracle)]) != oracle).sum())


def _recovery_run(g, *, seed: int, quick: bool, shards: int = 1):
    """Crash-point sweep: for every injection point, run the deterministic
    op stream under WAL + snapshots, crash at the point, recover from
    durable state, resume the remaining ops, and compare the final state
    byte-for-byte against an uninterrupted twin (plus the peeling oracle).

    Returns the JSON ``recovery`` section.
    """
    import shutil
    import tempfile

    from repro.serve import RecoveryManager, ShardPlan, faults

    block_size = 48
    churn = 0.2
    snapshot_every = 4
    attach = _attach_retrainer(seed)

    def fresh(n_shards=1):
        svc, stream_edges, _, _ = build_service(
            g, seed=seed, stream_frac=0.3, compact_every=256,
            shards=n_shards,
        )
        attach(svc)
        return svc, stream_edges

    # --- uninterrupted twin: the ground truth every crash run must match
    svc0, stream_edges = fresh()
    ops = _plan_ops(stream_edges, block_size=block_size, churn=churn,
                    seed=seed + 21)
    _apply_ops(svc0, ops)
    truth = _fingerprint(svc0)
    truth_retrains = int(svc0.stats.retrains)

    def crash_and_recover(point, hit, n_shards=1, plan_obj=None,
                          cross_restore=False):
        """-> one sweep row. ``plan_obj`` is the ShardPlan for restore;
        ``cross_restore`` additionally restores the finished run's durable
        state single-device and checks it against the twin too."""
        waldir = tempfile.mkdtemp(prefix=f"recov_{point}_")
        svc, _ = fresh(n_shards)
        mgr = RecoveryManager(svc, waldir, snapshot_every=snapshot_every,
                              fsync=False)
        faults.install(faults.FaultPlan.parse(f"{point}:{hit}:crash"))
        crashed = False
        try:
            _apply_ops(svc, ops)
        except faults.InjectedCrash:
            crashed = True
        finally:
            fired = faults.active().total_fired if faults.active() else 0
            faults.install(None)
        # quiesce the dead process's background writer so nothing races
        # the recovery scan (a real crash would have killed it mid-write,
        # which the torn-dir skip covers separately)
        try:
            mgr.wait()
        except BaseException:
            pass
        mgr.wal.close()
        row = {"point": point, "hit": int(hit), "crashed": crashed,
               "fired": int(fired)}
        if not crashed:  # the plan never reached its hit on this workload
            shutil.rmtree(waldir, ignore_errors=True)
            return row
        svc2, mgr2, report = RecoveryManager.recover(
            waldir, plan=plan_obj, snapshot_every=snapshot_every,
            fsync=False, configure=attach,
        )
        # ops map 1:1 onto WAL records: resume right after the durable tail
        _apply_ops(svc2, ops, start=report["wal_seq"])
        mgr2.close()
        bad = _diff_states(truth, _fingerprint(svc2))
        row.update(
            recovered=True,
            snapshot_wal_seq=int(report["snapshot_wal_seq"]),
            replayed_records=int(report["replayed_records"]),
            replayed_edges=int(report["replayed_edges"]),
            torn_wal_bytes=int(report["torn_wal_bytes"]),
            snapshots_skipped=int(report["snapshots_skipped"]),
            recovery_seconds=float(report["recovery_seconds"]),
            resumed_from_op=int(report["wal_seq"]),
            state_mismatch_keys=bad,
            core_mismatches=_oracle_mismatches(svc2),
            retrains=int(svc2.stats.retrains),
        )
        if cross_restore:
            # the WAL now also holds the resumed tail, so a second recovery
            # reproduces the *final* state — here placed on a single device
            svc1, mgr1, _ = RecoveryManager.recover(
                waldir, plan=None, snapshot_every=snapshot_every,
                fsync=False, configure=attach,
            )
            mgr1.close()
            row["restore_single_bit_identical"] = not _diff_states(
                truth, _fingerprint(svc1)
            )
        shutil.rmtree(waldir, ignore_errors=True)
        return row

    sweep = [crash_and_recover(point, hit) for point, hit in CRASH_POINTS]

    # --- graceful-degradation demos (fault mode: errors, not crashes) ---
    # 1) transactional retrain: a fault mid-swap rolls the store back —
    #    zero rows of the aborted version survive, state is byte-identical
    svc, _ = fresh()
    _apply_ops(svc, ops[: len(ops) // 2])
    svc.retrain_budget = 0  # the auto budget may be spent; force must run
    pre_versions = dict(svc.store.version_counts())
    pre_state = svc.store.state_dict()
    faults.install(faults.FaultPlan.parse("retrain_swap_chunk:2:fault"))
    rep = svc.maybe_retrain(force=True)
    faults.install(None)
    post_versions = dict(svc.store.version_counts())
    post_state = svc.store.state_dict()
    rollback = {
        "retrain_returned_none": rep is None,
        "retrain_failures": int(svc.stats.retrain_failures),
        "mixed_version_rows": int(
            sum(v for k, v in post_versions.items()
                if k not in pre_versions)
        ),
        "store_rolled_back": not _diff_states(pre_state, post_state),
    }

    # 2) degraded serving: a sticky flush fault exhausts the retries and
    #    queries are answered from stale resident rows, flagged in stats
    faults.install(faults.FaultPlan.parse("flush_dispatch:1+:fault"))
    rng = np.random.default_rng(seed + 4)
    svc.embed(rng.integers(0, svc.graph.n_nodes, size=svc.batch))
    degraded_during = bool(svc.degraded)
    faults.install(None)
    svc.embed(rng.integers(0, svc.graph.n_nodes, size=svc.batch))
    degradation = {
        "degraded_queries": int(svc.stats.degraded_queries),
        "entered_degraded": degraded_during,
        "recovered_after_fault": not svc.degraded,
    }

    # 3) dispatch fallback: sticky device faults are absorbed by the host
    #    re-peel fallback — ingest completes and cores stay oracle-exact
    svc3, _ = fresh()
    faults.install(faults.FaultPlan.parse("device_dispatch:1+:fault"))
    _apply_ops(svc3, ops[: max(len(ops) // 3, 2)])
    faults.install(None)
    fallback = {
        "dispatch_failures": int(svc3.cores.dispatch_failures),
        "dispatch_recoveries": int(svc3.cores.dispatch_recoveries),
        "core_mismatches": _oracle_mismatches(svc3),
    }

    recovered_rows = [r for r in sweep if r.get("recovered")]
    section = {
        "ops": int(len(ops)),
        "block_size": int(block_size),
        "snapshot_every": int(snapshot_every),
        "twin_retrains": truth_retrains,
        "crash_points": sweep,
        "points_crashed": int(sum(r["crashed"] for r in sweep)),
        "points_recovered_bit_identical": int(
            sum(not r["state_mismatch_keys"] for r in recovered_rows)
        ),
        "state_mismatches": int(
            sum(len(r["state_mismatch_keys"]) for r in recovered_rows)
        ),
        "core_mismatches": int(
            max((r["core_mismatches"] for r in recovered_rows), default=0)
        ),
        "recovery_seconds_max": float(
            max((r["recovery_seconds"] for r in recovered_rows), default=0.0)
        ),
        "replayed_edges_total": int(
            sum(r["replayed_edges"] for r in recovered_rows)
        ),
        "retrain_rollback": rollback,
        "degradation": degradation,
        "dispatch_fallback": fallback,
    }

    # --- sharded leg: crash under --shards N, recover at N *and* at 1 —
    # the snapshot strips shard padding, so restore is placement-agnostic
    if shards > 1:
        row = crash_and_recover(
            "ingest_apply", 5, n_shards=shards,
            plan_obj=ShardPlan.build(shards), cross_restore=True,
        )
        section["sharded"] = {"n_shards": int(shards), "crash": row}
    return section


def _hindex_kernel_run(*, seed: int, quick: bool):
    """Time the shared h-index sweep operator across kernel backends.

    The Pallas kernel (``kernels/hindex.py``) finally gets measured outside
    ``impl="ref"``: on TPU the compiled kernel itself, elsewhere its
    interpret mode (same lowering, python-executed — semantics timing, not a
    speed claim) next to the sort-free counting search the CPU path serves
    with and the sort-based reference. One jitted sweep per impl, best of a
    few repeats after an untimed compile call.
    """
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops as kops

    R, W = (512, 128) if quick else (2048, 256)
    rng = np.random.default_rng(seed)
    on_tpu = jax.default_backend() == "tpu"
    impls = ["ref", "count"] + (["pallas"] if on_tpu else ["pallas_interpret"])
    fn = jax.jit(kops.h_index_sweep, static_argnames=("impl",))
    section = {"backend": str(jax.default_backend()), "impls": {}}
    for impl in impls:
        # interpret mode runs the kernel grid in python: keep its shape small
        r, w = (128, 128) if impl == "pallas_interpret" else (R, W)
        values = jnp.asarray(rng.integers(0, 64, size=(r, w)), jnp.int32)
        valid = jnp.asarray(rng.random((r, w)) < 0.8)
        est = jnp.asarray(rng.integers(0, 64, size=r), jnp.int32)
        fn(values, valid, est, impl=impl).block_until_ready()  # compile
        best = float("inf")
        for _ in range(2 if impl == "pallas_interpret" else 5):
            t0 = time.perf_counter()
            fn(values, valid, est, impl=impl).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        section["impls"][impl] = {
            "rows": int(r), "width": int(w), "seconds_per_sweep": float(best),
            "rows_per_s": float(r / max(best, 1e-9)),
        }
    return section


def _overhead_guard(*, seed: int, repeats: int = 6, block_size: int = 1024):
    """Full-observability vs bare cost of a block-1024 ingest stream.

    The enabled leg runs with the tracer on (tail-sampled exemplar capture
    included — ``serve.flush`` is in the default watch set) *and* the SLO
    engine attached, so the ``--assert-overhead`` budget covers every
    always-on observability hook the serving hot path carries, not just
    span emission.

    Runs its own fixed workload (independent of ``--full``): the quick
    sweep's timed window is ~25 ms, where multi-ms scheduler/GC noise dwarfs
    the microseconds spans actually cost — a 4000-node stream keeps the
    timed region >100 ms so a 5% budget is meaningful. Every repetition
    replays the *identical* seeded stream (workload variance would otherwise
    dominate the comparison); timing noise is strictly additive, so
    best-of-``repeats`` per leg estimates each leg's floor, with off/on
    runs interleaved so both legs sample the same load drift. A fresh
    service per repetition keeps build + jit warm-up outside the timed
    window. The tracer is left disabled afterwards — the caller re-enables
    it when a trace export was requested.
    """
    g = generators.barabasi_albert_varying(4000, 6.0, seed=seed)
    off_times, on_times = [], []
    for r in range(repeats):
        # alternate which leg goes first so neither systematically benefits
        # from the warmer cache / quieter moment within a pair
        order = ((False, off_times), (True, on_times))
        for enabled, sink in (order if r % 2 == 0 else order[::-1]):
            if enabled:
                obs.enable()
            else:
                obs.disable()
            try:
                _, m = _ingest_run(
                    g, block_size, seed=seed, compact_every=1024,
                    slo=enabled,
                )
                sink.append(m["seconds"])
            finally:
                obs.disable()
    off = min(off_times)
    on = min(on_times)
    return {
        "block_size": int(block_size),
        "repeats": int(repeats),
        "seconds_off": float(off),
        "seconds_on": float(on),
        "overhead_pct": float(100.0 * (on / max(off, 1e-9) - 1.0)),
    }


def run(quick: bool = False, seed: int = 0, shards: int = 1,
        retrain: bool = False, trace: str = None, metrics_out: str = None,
        jax_profile: str = None, assert_overhead: float = None,
        repair_policy: str = "adaptive", pipeline: bool = True,
        recovery: bool = False, topk: bool = False,
        history: str = "results/history/serve_latency.jsonl"):
    n = 1000 if quick else 4000
    requests = 256 if quick else 1024
    batch = 64
    g = generators.barabasi_albert_varying(n, 6.0, seed=seed)

    # --- tracing overhead guard (before the tracer is enabled for the run:
    # its disabled leg must measure the true zero-instrumentation path)
    sweep_blocks = [1, 64, 256, 1024]  # 1 = per-edge baseline
    overhead = _overhead_guard(seed=seed + 11)
    if assert_overhead is not None and \
            overhead["overhead_pct"] > assert_overhead:
        # one retry before failing: the measured quantity is ~100 ms of
        # wall time, and a single load burst on a shared runner can fake an
        # overhead the second sample won't reproduce
        retry = _overhead_guard(seed=seed + 11)
        if retry["overhead_pct"] < overhead["overhead_pct"]:
            overhead = retry
        if overhead["overhead_pct"] > assert_overhead:
            raise SystemExit(
                f"tracing overhead {overhead['overhead_pct']:.2f}% exceeds "
                f"the --assert-overhead budget of {assert_overhead:.2f}% "
                f"(block {overhead['block_size']}: "
                f"{overhead['seconds_off']:.3f}s off vs "
                f"{overhead['seconds_on']:.3f}s on)"
            )
    if trace:
        obs.enable()

    # --- ingest-throughput sweep over block sizes (1 = per-edge baseline)
    sweep = []
    with device_profile(jax_profile):
        for bs in sweep_blocks:
            _, metrics = _ingest_run(
                g, bs, seed=seed,
                compact_every=256 if quick else 1024,
                max_edges=BASELINE_CAP if bs == 1 else 0,
                repair_policy=repair_policy, pipeline=pipeline,
            )
            sweep.append(metrics)
    base_eps = sweep[0]["edges_per_s"]
    best = sweep[-1]
    speedup_256 = next(
        (s["edges_per_s"] / max(base_eps, 1e-9) for s in sweep
         if s["block_size"] == 256), 0.0
    )

    # --- mixed insert/delete stream (deletion-aware maintenance, exactness)
    _, churn_run = _ingest_run(
        g, 256, seed=seed + 1, churn=0.25,
        compact_every=256 if quick else 1024,
        repair_policy=repair_policy, pipeline=pipeline,
    )

    # --- h-index kernel backends (the Pallas kernel measured directly)
    hindex_sec = _hindex_kernel_run(seed=seed + 13, quick=quick)

    # --- query-latency replay on a fully ingested service, with the live
    # SLO engine attached so the payload carries a real health snapshot
    svc, stream_edges, _, k0 = build_service(
        g, seed=seed, batch=batch, compact_every=256 if quick else 1024
    )
    svc.attach_slo()
    n_in = svc.ingest_edges(stream_edges, block_size=256)
    rng = np.random.default_rng(seed + 1)
    n_now = svc.graph.n_nodes
    for _ in range(6):  # untimed warmup (jit compiles incl. write-back shapes)
        svc.embed(rng.integers(0, n_now, size=batch))
    svc.stats = ServiceStats()

    t0 = time.perf_counter()
    for _ in range(requests // batch):
        svc.embed(rng.integers(0, n_now, size=batch))
    t_query = time.perf_counter() - t0
    p50, p99 = svc.latency_percentiles()
    st = svc.stats
    qps = st.queries / max(t_query, 1e-9)

    # --- top-k retrieval replay (blockwise kernel; recall must be exact)
    topk_sec = None
    if topk:
        topk_sec = _topk_run(svc, seed=seed, requests=requests, batch=batch)

    # --- row-sharded stack (placement-only: must stay oracle-exact)
    sharded = None
    if shards > 1:
        sharded = _sharded_run(
            g, seed=seed, shards=shards, requests=requests, batch=batch,
            compact_every=256 if quick else 1024, topk=topk,
        )

    # --- drift-triggered retrain + hot swap (end-to-end loop demo)
    retrain_sec = None
    if retrain:
        retrain_sec = _retrain_run(g, seed=seed + 2, quick=quick, batch=batch)

    # --- crash-point sweep: WAL + snapshot recovery must be bit-identical
    recovery_sec = None
    if recovery:
        g_rec = generators.barabasi_albert_varying(
            600 if quick else 1200, 5.0, seed=seed + 17
        )
        recovery_sec = _recovery_run(
            g_rec, seed=seed + 17, quick=quick, shards=shards
        )

    # --- observability section: measured overhead + per-dispatch cost of
    # the cold-start gather program on the replay service's live shapes
    obs_section = {
        "overhead": overhead,
        "dispatch_cost": svc.dispatch_cost_report(),
    }
    if trace:
        t = obs.tracer()
        obs_section["trace"] = {
            "spans": len(t.events),
            "kinds": sorted(t.span_names()),
            "dropped": int(t.dropped),
            "exemplars": len(t.exemplars),
            "exemplars_dropped": int(t.exemplars_dropped),
        }

    os.makedirs("results", exist_ok=True)
    payload = {
        "schema_version": int(SCHEMA_VERSION),
        "n_nodes": int(n_now),
        "n_edges": int(svc.graph.n_edges),
        "k0": int(k0),
        "ingest_edges": int(n_in),
        "ingest_sweep": sweep,
        "ingest_edges_per_s": best["edges_per_s"],
        "ingest_speedup_block256_vs_per_edge": float(speedup_256),
        "churn": churn_run,
        "core_mismatches": int(
            max(s["mismatches"] for s in sweep + [churn_run])
        ),
        "compactions": int(svc.graph.compactions),
        "queries": int(st.queries),
        "batch": batch,
        "query_p50_s": p50,
        "query_p99_s": p99,
        "qps": float(qps),
        "cold_start_fraction": float(st.cold_fraction),
        "unresolved": int(st.unresolved),
        "sharding": sharded if sharded is not None else {"n_shards": 1},
        "repair_policy": {"mode": repair_policy, "pipeline": bool(pipeline)},
        "hindex_kernel": hindex_sec,
        "obs": obs_section,
        "slo": svc.slo_health(),
    }
    if topk_sec is not None:
        payload["topk"] = topk_sec
    if sharded is not None:
        payload["core_mismatches"] = int(
            max(payload["core_mismatches"], sharded["mismatches"])
        )
    if retrain_sec is not None:
        payload["retrain"] = retrain_sec
        payload["core_mismatches"] = int(
            max(payload["core_mismatches"], retrain_sec["mismatches"])
        )
    if recovery_sec is not None:
        payload["recovery"] = recovery_sec
        payload["core_mismatches"] = int(
            max(payload["core_mismatches"], recovery_sec["core_mismatches"])
        )
    # refuse to emit an artifact the trend differ would refuse to read
    validate_or_raise(payload, load_schema(SCHEMA_PATH),
                      "results/serve_latency.json payload")
    with open("results/serve_latency.json", "w") as f:
        json.dump(payload, f, indent=2)
    if history:
        # one schema-validated line per run: the series the slope gate fits
        append_record(history, payload, quick=quick)

    if metrics_out:
        # the registry adopts the replay service's live histograms, so the
        # snapshot's serve_flush_seconds window reproduces the payload's
        # query_p50_s / query_p99_s exactly
        svc.publish_metrics()
        record_memory()
        reg = obs_metrics()
        reg.export_json(metrics_out)
        reg.export_prometheus(metrics_out.rsplit(".", 1)[0] + ".prom")
    if trace:
        obs.tracer().export_chrome(trace)
        # tail exemplars ride along as a sibling artifact: each histogram
        # outlier resolves to the span subtree of the dispatch behind it
        obs.tracer().export_exemplars(
            trace.rsplit(".", 1)[0] + ".exemplars.json"
        )

    lines = [
        csv_line(
            f"serve_ingest_block{s['block_size']}",
            1.0 / max(s["edges_per_s"], 1e-9),
            f"edges_per_s={s['edges_per_s']:.0f};mismatches={s['mismatches']};"
            f"repeels={s['repeels']}",
        )
        for s in sweep
    ]
    best_phases = ";".join(
        f"{k}={v['seconds'] * 1e3:.0f}ms[{v['impl']}]"
        for k, v in best.get("phases", {}).items()
    )
    lines += [
        csv_line(
            f"serve_repair_phases_block{best['block_size']}", 0.0,
            best_phases or "none",
        ),
        csv_line(
            "serve_ingest_churn",
            1.0 / max(churn_run["edges_per_s"], 1e-9),
            f"edges_per_s={churn_run['edges_per_s']:.0f};"
            f"removed={churn_run['edges_out']};"
            f"mismatches={churn_run['mismatches']}",
        ),
        csv_line("serve_ingest_speedup", 0.0,
                 f"block256_vs_per_edge={speedup_256:.1f}x"),
        csv_line(
            "serve_repair_policy", 0.0,
            f"mode={repair_policy};pipeline={int(pipeline)};"
            f"decisions={best['policy']['decisions']};"
            f"shell_repeels={best['policy']['shell_repeel']['count']}",
        ),
    ]
    lines += [
        csv_line(
            f"serve_hindex_{impl}", m["seconds_per_sweep"],
            f"rows={m['rows']};width={m['width']};"
            f"rows_per_s={m['rows_per_s']:.0f};"
            f"backend={hindex_sec['backend']}",
        )
        for impl, m in hindex_sec["impls"].items()
    ]
    lines += [
        csv_line("serve_query_p50", p50, f"qps={qps:.0f};batch={batch}"),
        csv_line("serve_query_p99", p99,
                 f"cold_frac={st.cold_fraction:.3f};unresolved={st.unresolved}"),
        csv_line(
            "serve_trace_overhead", 0.0,
            f"block{overhead['block_size']}_pct="
            f"{overhead['overhead_pct']:.2f};"
            f"off={overhead['seconds_off']:.3f}s;"
            f"on={overhead['seconds_on']:.3f}s",
        ),
    ]
    if topk_sec is not None:
        lines += [
            csv_line(
                "serve_topk_p50", topk_sec["query_p50_s"],
                f"k={topk_sec['k']};qps={topk_sec['qps']:.0f};"
                f"candidates={topk_sec['candidates']}",
            ),
            csv_line(
                "serve_topk_p99", topk_sec["query_p99_s"],
                f"recall={topk_sec['recall_at_k']:.3f};"
                f"oracle_mismatches={topk_sec['oracle_mismatches']}",
            ),
        ]
    if sharded is not None:
        balance = ",".join(str(c) for c in sharded["resident_per_shard"])
        lines += [
            csv_line(
                f"serve_shard{shards}_ingest",
                1.0 / max(sharded["ingest_edges_per_s"], 1e-9),
                f"edges_per_s={sharded['ingest_edges_per_s']:.0f};"
                f"mismatches={sharded['mismatches']}",
            ),
            csv_line(
                f"serve_shard{shards}_query_p50",
                sharded["query_p50_s"],
                f"qps={sharded['qps']:.0f};"
                f"imbalance={sharded['imbalance']:.2f}x",
            ),
            csv_line(
                f"serve_shard{shards}_balance", 0.0,
                f"resident={balance};"
                f"cross_shard_copies={sharded['cross_shard_row_copies']}",
            ),
        ]
        if "topk" in sharded:
            tk = sharded["topk"]
            lines.append(csv_line(
                f"serve_shard{shards}_topk_p99", tk["query_p99_s"],
                f"recall={tk['recall_at_k']:.3f};"
                f"oracle_mismatches={tk['oracle_mismatches']};"
                f"qps={tk['qps']:.0f}",
            ))
    if retrain_sec is not None:
        rt = retrain_sec.get("retrain_seconds", {})
        lines += [
            csv_line(
                "serve_retrain_walltime", float(rt.get("total", 0.0)),
                f"triggered={retrain_sec['triggered']};"
                f"core_size={retrain_sec.get('core_size', 0)};"
                f"sgns_steps={retrain_sec.get('sgns_steps', 0)};"
                f"warm_rows={retrain_sec.get('warm_rows', 0)}",
            ),
            csv_line(
                "serve_retrain_swap", float(rt.get("swap", 0.0)),
                f"rows={retrain_sec.get('rows_swapped', 0)};"
                f"chunks={retrain_sec.get('swap_chunks', 0)};"
                f"p99_before={retrain_sec['flush_p99_before_s']:.5f}s;"
                f"p99_during={retrain_sec['flush_p99_during_swap_s']:.5f}s",
            ),
            csv_line(
                "serve_retrain_quality", 0.0,
                f"auc_before={retrain_sec['auc_before']:.3f};"
                f"auc_after={retrain_sec['auc_after']:.3f};"
                f"staleness_before={retrain_sec['staleness_before']:.3f};"
                f"staleness_after={retrain_sec['staleness_after']:.3f};"
                f"anchors={retrain_sec.get('anchors', 0)}",
            ),
        ]
    if recovery_sec is not None:
        rb = recovery_sec["retrain_rollback"]
        dg = recovery_sec["degradation"]
        lines += [
            csv_line(
                "serve_recovery_sweep", recovery_sec["recovery_seconds_max"],
                f"points_crashed={recovery_sec['points_crashed']};"
                f"bit_identical="
                f"{recovery_sec['points_recovered_bit_identical']};"
                f"state_mismatches={recovery_sec['state_mismatches']};"
                f"core_mismatches={recovery_sec['core_mismatches']};"
                f"replayed_edges={recovery_sec['replayed_edges_total']}",
            ),
            csv_line(
                "serve_recovery_degradation", 0.0,
                f"mixed_version_rows={rb['mixed_version_rows']};"
                f"store_rolled_back={int(rb['store_rolled_back'])};"
                f"degraded_queries={dg['degraded_queries']};"
                f"dispatch_recoveries="
                f"{recovery_sec['dispatch_fallback']['dispatch_recoveries']}",
            ),
        ]
    return lines


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="full-size sweep (default: quick)")
    ap.add_argument("--shards", type=int, default=1,
                    help="also run the row-sharded stack over N devices "
                         "(power of two; on CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--retrain", action="store_true",
                    help="also run the drift-triggered retrain + hot-swap "
                         "demo and record the retrain section (wall time, "
                         "swap latency, pre/post AUC, staleness trajectory)")
    ap.add_argument("--topk", action="store_true",
                    help="also replay top_k_neighbors retrieval traffic: "
                         "query p50/p99 + exact-match recall vs a numpy "
                         "all-pairs oracle (on the sharded leg too when "
                         "--shards is given)")
    ap.add_argument("--recovery", action="store_true",
                    help="also run the crash-point sweep: WAL + snapshot "
                         "recovery at every injection point, bit-identical "
                         "vs an uninterrupted twin, plus the degraded-"
                         "serving and transactional-retrain demos")
    ap.add_argument("--trace", nargs="?", const="results/serve_trace.json",
                    default=None, metavar="PATH",
                    help="record spans for the whole run and export a Chrome "
                         "trace_event JSON (default results/serve_trace.json)")
    ap.add_argument("--metrics-out", nargs="?",
                    const="results/serve_metrics.json", default=None,
                    metavar="PATH",
                    help="export the metrics registry as JSON (+ a .prom "
                         "Prometheus text sibling; default "
                         "results/serve_metrics.json)")
    ap.add_argument("--jax-profile", metavar="DIR", default=None,
                    help="capture a jax.profiler device trace of the ingest "
                         "sweep into DIR")
    ap.add_argument("--assert-overhead", type=float, default=None,
                    metavar="PCT",
                    help="fail the run if enabling tracing slows the "
                         "largest-block ingest by more than PCT percent")
    ap.add_argument("--repair-policy", default="adaptive",
                    choices=["adaptive", "region", "fallback"],
                    help="block core-repair decision rule (A/B runs: "
                         "region = legacy static trigger, fallback = "
                         "always re-peel)")
    ap.add_argument("--no-pipeline", action="store_true",
                    help="disable pipelined block ingest (serial staging)")
    ap.add_argument("--history", default="results/history/serve_latency.jsonl",
                    metavar="PATH",
                    help="JSON-lines history file this run appends its "
                         "trend record to (the slope gate's series)")
    ap.add_argument("--no-history", action="store_true",
                    help="do not append this run to the history series")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()
    for line in run(quick=not args.full, seed=args.seed, shards=args.shards,
                    retrain=args.retrain, trace=args.trace,
                    metrics_out=args.metrics_out,
                    jax_profile=args.jax_profile,
                    assert_overhead=args.assert_overhead,
                    repair_policy=args.repair_policy,
                    pipeline=not args.no_pipeline,
                    recovery=args.recovery, topk=args.topk,
                    history=None if args.no_history else args.history):
        print(line)


if __name__ == "__main__":
    main()
