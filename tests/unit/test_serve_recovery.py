"""Crash safety: WAL torn-tail handling, atomic snapshots, fault plans,
input validation, crash/recover bit-identity, and graceful degradation.

Every durability claim in ``serve.recovery`` is exercised directly: a WAL
crash mid-append must leave a tail the next open truncates; a snapshot
directory without ``_COMMITTED`` (or with a corrupt manifest / payload)
must be skipped even when newest; recovery from snapshot + WAL replay must
reproduce the uninterrupted run byte-for-byte; and the degradation paths
(flush retry → stale-row fallback, transactional retrain rollback, hang
watchdog) must absorb injected faults without corrupting state.
"""
import os
import struct
import time
import zlib

import numpy as np
import pytest

from repro.graph import generators
from repro.launch.serve_embed import build_service
from repro.serve import faults
from repro.serve.faults import FaultPlan, InjectedCrash, InjectedFault
from repro.serve.recovery import (
    _HEADER,
    _MAGIC,
    KIND_INGEST,
    KIND_RETRACT,
    RecoveryManager,
    SnapshotStore,
    WriteAheadLog,
    capture_state,
)


@pytest.fixture(autouse=True)
def _no_leaked_fault_plan():
    faults.install(None)
    yield
    faults.install(None)


def _edges(*pairs):
    return np.asarray(pairs, np.int64)


# ------------------------------------------------------------------- WAL --


def test_wal_roundtrip_and_seq(tmp_path):
    path = str(tmp_path / "wal.log")
    wal = WriteAheadLog(path, fsync=False)
    a = _edges((0, 1), (1, 2))
    b = _edges((2, 3))
    assert wal.append(KIND_INGEST, a) == 1
    assert wal.append(KIND_RETRACT, b) == 2
    wal.close()

    wal2 = WriteAheadLog(path, fsync=False)
    assert wal2.seq == 2 and wal2.torn_truncated == 0
    recs = list(wal2.records())
    assert [(s, k) for s, k, _ in recs] == [(1, KIND_INGEST), (2, KIND_RETRACT)]
    np.testing.assert_array_equal(recs[0][2], a)
    np.testing.assert_array_equal(recs[1][2], b)
    # replay-from-offset skips already-applied records
    assert [s for s, _, _ in wal2.records(after_seq=1)] == [2]
    # appends continue the sequence
    assert wal2.append(KIND_INGEST, _edges((5, 6))) == 3
    wal2.close()


def test_wal_truncates_garbage_tail(tmp_path):
    path = str(tmp_path / "wal.log")
    wal = WriteAheadLog(path, fsync=False)
    wal.append(KIND_INGEST, _edges((0, 1)))
    wal.close()
    good = os.path.getsize(path)
    with open(path, "ab") as f:
        f.write(b"\x00garbage-that-is-not-a-record")

    wal2 = WriteAheadLog(path, fsync=False)
    assert wal2.seq == 1 and wal2.torn_truncated > 0
    assert os.path.getsize(path) == good
    assert len(list(wal2.records())) == 1
    wal2.close()


def test_wal_truncates_partial_record_and_bad_crc(tmp_path):
    path = str(tmp_path / "wal.log")
    wal = WriteAheadLog(path, fsync=False)
    wal.append(KIND_INGEST, _edges((0, 1), (1, 2)))
    wal.close()
    good = os.path.getsize(path)

    # a half-written record: header promises 4 edges, payload cut short
    head = _HEADER.pack(_MAGIC, KIND_INGEST, 2, 4)
    with open(path, "ab") as f:
        f.write(head + b"\x01" * 24)
    wal2 = WriteAheadLog(path, fsync=False)
    assert wal2.seq == 1 and wal2.torn_truncated > 0
    assert os.path.getsize(path) == good
    wal2.close()

    # a complete record with a corrupted CRC trailer
    payload = _edges((7, 8)).tobytes()
    head = _HEADER.pack(_MAGIC, KIND_INGEST, 2, 1)
    crc = struct.pack("<I", zlib.crc32(head + payload) ^ 0xFFFF)
    with open(path, "ab") as f:
        f.write(head + payload + crc)
    wal3 = WriteAheadLog(path, fsync=False)
    assert wal3.seq == 1 and wal3.torn_truncated > 0
    assert len(list(wal3.records())) == 1
    wal3.close()


def test_wal_crash_mid_append_leaves_real_torn_tail(tmp_path):
    """``wal_append`` fires mid-record: half the bytes reach the file, the
    crash propagates, and the next open truncates back to the last good
    record — exactly the torn tail a power loss mid-write produces."""
    path = str(tmp_path / "wal.log")
    wal = WriteAheadLog(path, fsync=False)
    wal.append(KIND_INGEST, _edges((0, 1)))
    good = os.path.getsize(path)

    faults.install(FaultPlan.parse("wal_append:1:crash"))
    with pytest.raises(InjectedCrash):
        wal.append(KIND_INGEST, _edges((2, 3), (3, 4)))
    faults.install(None)
    wal.close()
    assert os.path.getsize(path) > good  # partial bytes really hit disk

    wal2 = WriteAheadLog(path, fsync=False)
    assert wal2.seq == 1 and wal2.torn_truncated > 0
    assert os.path.getsize(path) == good
    # the log is append-ready again at the right sequence number
    assert wal2.append(KIND_RETRACT, _edges((2, 3))) == 2
    wal2.close()


def test_wal_fsync_fault_loses_record_cleanly(tmp_path):
    path = str(tmp_path / "wal.log")
    wal = WriteAheadLog(path, fsync=False)
    wal.append(KIND_INGEST, _edges((0, 1)))
    good = os.path.getsize(path)

    faults.install(FaultPlan.parse("wal_fsync:1"))
    with pytest.raises(InjectedFault):
        wal.append(KIND_INGEST, _edges((2, 3)))
    faults.install(None)
    # the record is gone entirely — as if the OS never wrote it back
    assert os.path.getsize(path) == good and wal.seq == 1
    assert wal.append(KIND_INGEST, _edges((2, 3))) == 2
    wal.close()


# ------------------------------------------------------------- snapshots --


def _snap_payload(seed=0, wal_seq=7):
    rng = np.random.default_rng(seed)
    arrays = {"a": rng.normal(size=(4, 3)).astype(np.float32),
              "b": np.arange(5, dtype=np.int64)}
    return arrays, {"wal_seq": wal_seq, "stats": {"queries": 3}}


def test_snapshot_roundtrip_and_gc(tmp_path):
    store = SnapshotStore(str(tmp_path), keep=2)
    for seq in (3, 7, 11):
        arrays, manifest = _snap_payload(seed=seq, wal_seq=seq)
        store.write(arrays, manifest)
    got, manifest, skipped = store.load_latest()
    assert skipped == 0 and manifest["wal_seq"] == 11
    want, _ = _snap_payload(seed=11, wal_seq=11)
    for k in want:
        np.testing.assert_array_equal(got[k], want[k])
    # retention: only the newest ``keep`` survive
    names = sorted(d for d in os.listdir(tmp_path) if d.startswith("snap_"))
    assert names == ["snap_000000000007", "snap_000000000011"]


def test_snapshot_skips_torn_dirs_even_when_newest(tmp_path):
    store = SnapshotStore(str(tmp_path), keep=5)
    arrays, manifest = _snap_payload(wal_seq=5)
    store.write(arrays, manifest)

    # newest dir, no _COMMITTED: a crash before the marker
    torn = tmp_path / "snap_000000000009"
    torn.mkdir()
    (torn / "state.npz").write_bytes(b"\x00\x01")
    got, m, skipped = store.load_latest()
    assert m["wal_seq"] == 5 and skipped == 1

    # newer still, committed but the manifest is torn mid-write
    torn2 = tmp_path / "snap_000000000010"
    torn2.mkdir()
    (torn2 / "manifest.json").write_text('{"wal_seq": 10, "npz')
    (torn2 / "_COMMITTED").write_text("ok")
    got, m, skipped = store.load_latest()
    assert m["wal_seq"] == 5 and skipped == 2

    # newest of all: committed, manifest fine, payload corrupted (CRC)
    import shutil

    torn3 = tmp_path / "snap_000000000012"
    shutil.copytree(tmp_path / "snap_000000000005", torn3)
    raw = bytearray((torn3 / "state.npz").read_bytes())
    raw[len(raw) // 2] ^= 0xFF
    (torn3 / "state.npz").write_bytes(bytes(raw))
    got, m, skipped = store.load_latest()
    assert m["wal_seq"] == 5 and skipped == 3
    np.testing.assert_array_equal(got["a"], arrays["a"])


def test_snapshot_crash_before_commit_is_invisible(tmp_path):
    store = SnapshotStore(str(tmp_path), keep=5)
    faults.install(FaultPlan.parse("snapshot_write:1:crash"))
    with pytest.raises(InjectedCrash):
        store.write(*_snap_payload(wal_seq=3))
    faults.install(None)
    assert store.load_latest() == (None, None, 0)  # tmp dir never visible

    # crash after _COMMITTED but before the rename: tmp is garbage, a
    # retried write of the same snapshot succeeds over it
    faults.install(FaultPlan.parse("snapshot_commit:1:crash"))
    with pytest.raises(InjectedCrash):
        store.write(*_snap_payload(wal_seq=3))
    faults.install(None)
    assert store.load_latest()[1] is None
    store.write(*_snap_payload(wal_seq=3))
    assert store.load_latest()[1]["wal_seq"] == 3


# ------------------------------------------------------------ fault plans --


def test_fault_plan_parse_validates():
    with pytest.raises(ValueError, match="unknown fault point"):
        FaultPlan.parse("not_a_point:1")
    with pytest.raises(ValueError, match="bad fault spec"):
        FaultPlan.parse("wal_append")
    with pytest.raises(ValueError, match="bad fault mode"):
        FaultPlan.parse("wal_append:1:explode")
    with pytest.raises(ValueError, match="hit index"):
        FaultPlan.parse("wal_append:0")
    # every published point parses
    plan = FaultPlan.parse(",".join(f"{p}:1" for p in faults.POINTS))
    assert set(plan.rules) == set(faults.POINTS)


def test_fault_plan_hit_and_sticky_semantics():
    plan = FaultPlan.parse("repair:2,device_dispatch:3+:crash")
    faults.install(plan)
    faults.check("repair")  # hit 1: silent
    with pytest.raises(InjectedFault):
        faults.check("repair")  # hit 2: fires
    faults.check("repair")  # hit 3: one-shot rule is spent

    faults.check("device_dispatch")
    faults.check("device_dispatch")
    for _ in range(3):  # sticky: every hit from the 3rd on
        with pytest.raises(InjectedCrash):
            faults.check("device_dispatch")
    assert plan.fired == {"repair": 1, "device_dispatch": 3}
    assert plan.total_fired == 4


def test_injected_crash_is_not_an_exception():
    """The whole degradation design rests on this: ``except Exception``
    recovery paths must never swallow a simulated process death."""
    assert not issubclass(InjectedCrash, Exception)
    assert issubclass(InjectedFault, Exception)
    faults.install(FaultPlan.parse("repair:1:crash"))
    with pytest.raises(InjectedCrash):
        try:
            faults.check("repair")
        except Exception:  # noqa: BLE001 - the point of the test
            pytest.fail("InjectedCrash was swallowed by `except Exception`")


# ------------------------------------------------------- input validation --


def _svc(n=150, seed=0, **kw):
    g = generators.barabasi_albert_varying(n, 4.0, seed=seed)
    svc, stream, _, _ = build_service(g, seed=seed, batch=16,
                                      stream_frac=0.3, **kw)
    return svc, stream


def test_ingest_block_rejects_malformed_input():
    svc, _ = _svc()
    with pytest.raises(ValueError, match="non-negative"):
        svc.ingest_block(_edges((0, 1), (-3, 2)))
    with pytest.raises(ValueError, match="self-loops"):
        svc.ingest_block(_edges((0, 1), (4, 4)))
    with pytest.raises(ValueError, match="integer dtype"):
        svc.ingest_block(np.array([[0.5, 1.5]]))
    with pytest.raises(ValueError, match="integer dtype"):
        svc.ingest_block(np.array([["a", "b"]], dtype=object))
    with pytest.raises(ValueError, match=r"\(m, 2\)-shaped"):
        svc.ingest_block(np.arange(9, dtype=np.int64))
    with pytest.raises(ValueError, match="non-negative"):
        svc.retract_block(_edges((-1, 0)))
    with pytest.raises(ValueError, match="self-loops"):
        svc.retract_block(_edges((2, 2)))
    # rejected blocks mutate nothing
    assert svc.stats.edges_ingested == 0 and svc.stats.ingest_blocks == 0


def test_validation_happens_before_wal_logging(tmp_path):
    """A malformed block must not reach the durable log: replaying it after
    a crash would re-raise during recovery."""
    svc, _ = _svc()
    mgr = RecoveryManager(svc, str(tmp_path), snapshot_every=1000,
                          fsync=False)
    with pytest.raises(ValueError):
        svc.ingest_block(_edges((0, 0)))
    assert mgr.wal.seq == 0
    mgr.close()


# ------------------------------------------------- crash/recover identity --


def _ops_from(stream, block=24):
    """Deterministic ingest/retract mix: every third block retracts half of
    the block ingested two steps earlier."""
    ops = []
    blocks = [np.asarray(stream[s:s + block], np.int64)
              for s in range(0, len(stream), block)]
    for i, blk in enumerate(blocks):
        ops.append(("ingest", blk))
        if i % 3 == 2:
            prev = blocks[i - 2]
            ops.append(("retract", prev[: len(prev) // 2]))
    return ops


def _apply(svc, ops, start=0):
    for kind, blk in ops[start:]:
        (svc.ingest_block if kind == "ingest" else svc.retract_block)(blk)
    svc.sync()


def _arrays(svc):
    arrays, _ = capture_state(svc, 0)
    return arrays


def test_crash_recover_resume_matches_uninterrupted_twin(tmp_path):
    svc0, stream = _svc(n=250, seed=3)
    ops = _ops_from(stream)
    _apply(svc0, ops)
    truth = _arrays(svc0)

    svc, _ = _svc(n=250, seed=3)
    mgr = RecoveryManager(svc, str(tmp_path), snapshot_every=3, fsync=False)
    faults.install(FaultPlan.parse("ingest_apply:5:crash"))
    with pytest.raises(InjectedCrash):
        _apply(svc, ops)
    faults.install(None)
    try:
        mgr.wait()
    except BaseException:
        pass
    mgr.wal.close()

    svc2, mgr2, report = RecoveryManager.recover(
        str(tmp_path), snapshot_every=3, fsync=False
    )
    # the WAL append runs before the injected ingest_apply crash, so the
    # crashing op IS logged and replayed; ops map 1:1 onto WAL records, so
    # the durable seq is exactly the resume index
    assert report["wal_seq"] == 5 and report["replayed_records"] >= 1
    _apply(svc2, ops, start=report["wal_seq"])
    got = _arrays(svc2)
    bad = [k for k in truth
           if k not in got or not np.array_equal(truth[k], got[k])]
    assert bad == [], f"state diverged after recovery: {bad}"

    from repro.core.kcore import core_numbers_host

    oracle = core_numbers_host(svc2.graph.snapshot())
    assert (np.asarray(svc2.cores.core[: len(oracle)]) == oracle).all()
    mgr2.close()


def test_recover_requires_a_committed_snapshot(tmp_path):
    with pytest.raises(FileNotFoundError):
        RecoveryManager.recover(str(tmp_path))


# --------------------------------------------------- graceful degradation --


def test_flush_falls_back_to_stale_rows_then_recovers():
    svc, stream = _svc()
    svc.ingest_edges(stream, block_size=64)
    svc.flush_retries = 0  # no retry sleeps in tests
    known = np.arange(8)
    healthy = svc.embed(known)
    assert not svc.degraded

    faults.install(FaultPlan.parse("flush_dispatch:1+"))  # sticky fault
    degraded = svc.embed(known)
    assert svc.degraded and svc.stats.degraded_queries == len(known)
    # stale-row answers come straight from the store tiers
    np.testing.assert_array_equal(degraded, healthy)

    faults.install(None)  # the device comes back
    after = svc.embed(known)
    assert not svc.degraded  # a healthy flush clears degraded mode
    np.testing.assert_array_equal(after, healthy)


def test_flush_retry_absorbs_transient_fault():
    svc, stream = _svc()
    svc.ingest_edges(stream, block_size=64)
    svc.flush_retries, svc.retry_backoff = 2, 0.0
    faults.install(FaultPlan.parse("flush_dispatch:1"))  # one-shot fault
    out = svc.embed(np.arange(8))
    assert not svc.degraded and svc.stats.degraded_queries == 0
    assert np.isfinite(out).all()


def _flush_failures():
    from repro.obs import metrics

    return metrics().counter("serve_flush_failures_total").value


def test_flush_lowering_error_raises_instead_of_degrading():
    """A kernel the backend cannot compile is a bug, not a device fault:
    the fused flush's compile runs outside its retry loop, so the error
    reaches the caller and no query is answered from stale rows. (Pallas
    without interpret mode has no CPU lowering.)"""
    from repro.serve import EmbeddingService

    base, stream = _svc()
    base.ingest_edges(stream, block_size=64)
    svc = EmbeddingService(base.graph, base.cores, base.store, batch=16,
                           impl="pallas")
    before = _flush_failures()
    with pytest.raises(ValueError, match="interpret mode"):
        svc.embed(np.arange(8))
    assert _flush_failures() == before
    assert not svc.degraded and svc.stats.degraded_queries == 0


def test_descent_lowering_error_raises_instead_of_host_repeel():
    """The fused descent compiles ahead of its retry wrapper: a lowering
    error propagates, and is never absorbed as a failed dispatch that the
    exact host peel then papers over."""
    svc, stream = _svc()
    svc.cores.kernel_impl = "pallas"
    with pytest.raises(ValueError, match="interpret mode"):
        svc.ingest_block(stream[:64])
        svc.sync()
    assert svc.cores.dispatch_failures == 0
    assert svc.cores.dispatch_recoveries == 0


def test_injected_device_dispatch_fault_still_degrades_to_host_peel():
    svc, stream = _svc()
    svc.cores.retry_backoff = 0.0
    faults.install(FaultPlan.parse("device_dispatch:1+"))  # sticky fault
    svc.ingest_edges(stream, block_size=64)
    faults.install(None)
    assert svc.cores.dispatch_failures > 0
    assert svc.cores.dispatch_recoveries > 0
    assert svc.cores.resync() == 0  # the host peel kept cores exact


def _attach_retrainer(svc, seed=0):
    from repro.serve.retrain import RetrainConfig, Retrainer
    from repro.skipgram.trainer import SGNSConfig

    cfg = RetrainConfig(
        n_walks=4, walk_length=8, min_sgns_steps=30,
        sgns=SGNSConfig(dim=svc.store.dim, epochs=0.05, impl="ref",
                        seed=seed),
        prop_iters=3, swap_chunk=8, seed=seed,
    )
    svc.set_retrainer(Retrainer(svc, cfg), auto=False)


@pytest.mark.parametrize("point", ["retrain_swap_chunk:2", "retrain_train:1"])
def test_failed_retrain_rolls_back_store(point):
    """A retrain that dies mid-cycle — even mid-VersionRollout, inside the
    mixed-version window — must leave the store byte-identical to before
    and zero rows on the aborted version."""
    svc, stream = _svc(n=200, seed=1, dim=16)
    svc.ingest_edges(stream, block_size=64)
    _attach_retrainer(svc)
    pre = svc.store.state_dict()
    pre_counts = svc.store.version_counts()

    faults.install(FaultPlan.parse(f"{point}:fault"))
    report = svc.maybe_retrain(force=True)
    faults.install(None)
    assert report is None and svc.stats.retrain_failures == 1
    assert svc.stats.retrains == 0
    assert svc.store.version_counts() == pre_counts
    post = svc.store.state_dict()
    bad = [k for k in pre if not np.array_equal(pre[k], post[k])]
    assert bad == [], f"store not rolled back: {bad}"

    # and with the fault gone the same forced retrain completes
    assert svc.maybe_retrain(force=True) is not None
    assert svc.stats.retrains == 1


def test_retrain_non_runtime_error_rolls_back_and_raises(monkeypatch):
    """Only device errors and injected faults are absorbed; any other
    error, e.g. a kernel the compiler refuses, is rolled back and raised."""
    svc, stream = _svc(n=200, seed=1, dim=16)
    svc.ingest_edges(stream, block_size=64)
    _attach_retrainer(svc)
    pre = svc.store.state_dict()

    def refused(*a, **k):
        raise NotImplementedError("kernel refused by the compiler")

    monkeypatch.setattr(svc.retrainer, "_train", refused)
    with pytest.raises(NotImplementedError):
        svc.maybe_retrain(force=True)
    assert svc.stats.retrain_failures == 1 and svc.stats.retrains == 0
    post = svc.store.state_dict()
    assert all(np.array_equal(pre[k], post[k]) for k in pre)


def test_retrain_crash_passes_through_transaction():
    """InjectedCrash is process death: the transactional handler must NOT
    catch it — durable recovery owns that case."""
    svc, stream = _svc(n=200, seed=1, dim=16)
    svc.ingest_edges(stream, block_size=64)
    _attach_retrainer(svc)
    faults.install(FaultPlan.parse("retrain_plan:1:crash"))
    with pytest.raises(InjectedCrash):
        svc.maybe_retrain(force=True)
    assert svc.stats.retrain_failures == 0  # not a counted (handled) failure


def test_hang_watchdog_enters_degraded_mode():
    from repro.distributed.watchdog import HangWatchdog

    g = generators.barabasi_albert_varying(120, 4.0, seed=0)
    svc, stream, _, _ = build_service(g, seed=0, batch=16)
    svc._watchdog = HangWatchdog(0.02, svc._on_hang)
    svc._watchdog.arm()
    deadline = time.monotonic() + 2.0
    while not svc._watchdog.fired and time.monotonic() < deadline:
        time.sleep(0.005)
    assert svc.stats.hangs == 1 and svc.degraded

    # pet_watchdog only touches an armed watchdog
    svc._watchdog.disarm()
    svc.pet_watchdog()  # disarmed: no-op, must not re-arm
    assert not svc._watchdog.armed
    svc._watchdog.arm()
    svc.pet_watchdog()
    assert svc._watchdog.armed
    svc._watchdog.disarm()
