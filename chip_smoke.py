"""Bring-up check of the online embedding service on a TPU.

    python chip_smoke.py [--seed N]      # one chip
    python chip_smoke.py --chips 4       # the sharded path on four chips

One chip: builds the service on ``github-like`` (37,700 nodes, dim 128)
through ``build_service`` with CoreWalk+SGNS training, streams the held-out
edges in blocks of 1024 with churn 0.1, and checks the cores against the
peeling oracle. It then serves embed and link-score queries, checking every
row against the store or the §2.2 cold-start mean, serves top-k batches
checked against the numpy all-pairs oracle, and forces one retrain that
must swap a store version. No dispatch failure, recovery, degraded query,
retrain failure or flush failure may occur.

``--chips 4`` runs only the sharded path: the same stream prefix (its first
``SHARDED_BLOCKS`` blocks, to keep two full services within a four-chip
call) and queries at ``shards=4`` and, for comparison, at ``shards=1`` on
device 0. Cores, store rows, served rows and top-k ids must be
bit-identical between the two.

Times are single unrepeated runs and include compilation. The last line of
standard output is a JSON object naming the device; it is printed only when
every check passed on a TPU. Any failure exits nonzero.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

DATASET = "github-like"
DIM = 128
BATCH = 64
BLOCK = 1024
CHURN = 0.1
TOPK = 10
SHARDED_BLOCKS = 4


def log(msg: str) -> None:
    print(f"[chip-smoke] {msg}", flush=True)


class Phases:
    """Wall and compile seconds per named phase (one unrepeated run)."""

    def __init__(self):
        import jax

        self.compile = 0.0
        self.rows = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self.compile += duration

    @contextlib.contextmanager
    def __call__(self, name: str):
        c0, t0 = self.compile, time.perf_counter()
        yield
        wall, comp = time.perf_counter() - t0, self.compile - c0
        self.rows.append((name, wall, comp))
        log(f"phase {name}: wall {wall:.3f} s, compile {comp:.3f} s "
            "(one unrepeated run)")


class Checks:
    def __init__(self):
        self.failed = []

    def __call__(self, ok: bool, what: str) -> None:
        log(f"{'PASS' if ok else 'FAIL'} {what}")
        if not ok:
            self.failed.append(what)


def cold_start_reference(svc, nodes):
    """Host-side answer the next flush must give for ``nodes``: the stored
    row where there is one, else the §2.2 mean of the stored rows of the
    node's ELL neighbours (zeros when none is stored)."""
    import numpy as np

    vecs, found, _, _ = svc.store.peek_many(nodes)
    want = vecs.astype(np.float64)
    cold = np.where(~found)[0]
    ids = np.asarray(svc.graph.ell().neighbours)[nodes[cold]]  # (c, W)
    rows, ok, _, _ = svc.store.peek_many(ids.reshape(-1))
    rows = rows.reshape(*ids.shape, vecs.shape[-1])
    ok = ok.reshape(ids.shape) & (ids != svc.graph.node_cap)
    for j, i in enumerate(cold):
        if ok[j].any():
            want[i] = rows[j][ok[j]].astype(np.float64).mean(axis=0)
    return want, found


def serve_queries(svc, rng, check, n_batches=4, n_pairs=128):
    """Embed batches checked row by row, then cosine link scores."""
    import numpy as np

    n = svc.graph.n_nodes
    served = []
    exact = close = True
    for _ in range(n_batches):
        nodes = rng.integers(0, n, size=BATCH)
        want, found = cold_start_reference(svc, nodes)
        out = svc.embed(nodes)
        served.append(out)
        exact &= bool(np.array_equal(out[found], want[found].astype(out.dtype)))
        close &= bool(np.allclose(out[~found], want[~found],
                                  rtol=1e-5, atol=1e-6))
    out = np.concatenate(served)
    check(out.shape == (n_batches * BATCH, DIM) and np.isfinite(out).all(),
          f"{len(out)} embeds: finite, shape {out.shape}")
    check(exact, "stored rows served exactly")
    check(close, "cold starts equal the host neighbour mean")
    scores = svc.link_scores(rng.integers(0, n, size=(n_pairs, 2)))
    check(np.isfinite(scores).all() and np.abs(scores).max() <= 1 + 1e-5,
          f"{n_pairs} link scores finite cosines")
    return out


def serve_topk(svc, rng, check, n_batches=3):
    import numpy as np

    from repro.serve.service import top_k_reference

    n = svc.graph.n_nodes
    got, mism = [], 0
    for _ in range(n_batches):
        nodes = rng.integers(0, n, size=BATCH)
        ids, scores = svc.top_k_neighbors(nodes, TOPK)
        want = top_k_reference(svc.store, nodes, svc.embed(nodes), TOPK)
        mism += int((ids != want).sum())
        got.append(ids)
    check(mism == 0, f"top-{TOPK} ids equal the all-pairs oracle "
          f"({n_batches} batches of {BATCH}, {mism} mismatches)")
    return np.concatenate(got)


def failure_counters(svc) -> dict:
    from repro.obs import metrics

    return {
        "cores.dispatch_failures": svc.cores.dispatch_failures,
        "cores.dispatch_recoveries": svc.cores.dispatch_recoveries,
        "stats.degraded_queries": svc.stats.degraded_queries,
        "stats.retrain_failures": svc.stats.retrain_failures,
        "serve_flush_failures_total":
            metrics().counter("serve_flush_failures_total").value,
    }


def print_memory(devices) -> None:
    for d in devices:
        ms = d.memory_stats() or {}
        log(f"memory {d.platform}:{d.id}: bytes_in_use "
            f"{ms.get('bytes_in_use')}, peak_bytes_in_use "
            f"{ms.get('peak_bytes_in_use')}, bytes_limit "
            f"{ms.get('bytes_limit')}")


def stream(svc, edges, seed, phase, name):
    import numpy as np

    with phase(name):
        n_in, n_out = svc.stream_with_churn(
            edges, block_size=BLOCK, churn=CHURN,
            rng=np.random.default_rng(seed + 2),
        )
    log(f"{name}: {n_in} edges in, {n_out} retracted, "
        f"{svc.cores.descends} fused descents, {svc.cores.repeels} re-peels")


def one_chip(g, seed, phase, check) -> None:
    import numpy as np

    from repro.launch.serve_embed import build_service

    with phase("build (CoreWalk+SGNS on the base graph)"):
        svc, edges, _, k0 = build_service(
            g, dim=DIM, batch=BATCH, train=True, retrain=True,
            retrain_threshold=float("inf"), seed=seed,
        )
    log(f"base: {svc.graph.n_edges} edges, k0={k0}, "
        f"{svc.store.resident} rows stored")
    stream(svc, edges, seed, phase, "stream")
    check(svc.cores.resync() == 0, "cores equal the peeling oracle")
    rep = svc.cores.phase_report()
    log(f"repair phases: {json.dumps(rep)}")
    check(rep.get("descend", {}).get("impl") == "fused[pallas]",
          "descend ran fused[pallas]")
    check(rep.get("region", {}).get("impl") == "jit", "region ran jit")

    rng = np.random.default_rng(seed + 1)
    with phase("queries"):
        serve_queries(svc, rng, check)
    with phase("top-k"):
        serve_topk(svc, rng, check)
    with phase("retrain (forced)"):
        report = svc.maybe_retrain(force=True)
    check(report is not None and svc.stats.retrains == 1
          and svc.stats.last_swap_version == report.version,
          f"retrain swapped version "
          f"{None if report is None else report.version}")
    with phase("queries after the swap"):
        serve_queries(svc, rng, check, n_batches=1, n_pairs=32)
    for name, v in failure_counters(svc).items():
        check(v == 0, f"{name} == {v}")


def four_chips(g, seed, phase, check) -> None:
    import numpy as np

    from repro.launch.serve_embed import build_service

    runs = {}
    for shards in (1, 4):
        with phase(f"shards={shards} build"):
            svc, edges, _, _ = build_service(
                g, dim=DIM, batch=BATCH, shards=shards, seed=seed
            )
        stream(svc, edges[: SHARDED_BLOCKS * BLOCK], seed, phase,
               f"shards={shards} stream")
        check(svc.cores.resync() == 0,
              f"shards={shards}: cores equal the peeling oracle")
        rng = np.random.default_rng(seed + 1)
        with phase(f"shards={shards} queries"):
            out = serve_queries(svc, rng, check)
            ids = serve_topk(svc, rng, check)
        for name, v in failure_counters(svc).items():
            check(v == 0, f"shards={shards}: {name} == {v}")
        runs[shards] = (svc, out, ids)
        if shards > 1:
            for label, arr in (("store table", svc.store.table()),
                               ("ELL mirror", svc.graph.ell().neighbours)):
                per = {s.device.id: s.data.nbytes
                       for s in arr.addressable_shards}
                log(f"shards={shards} {label} bytes per device: {per}")
                check(len(per) == shards
                      and max(per.values()) < arr.nbytes,
                      f"{label} split over {shards} devices")
    (s1, out1, ids1), (s4, out4, ids4) = runs[1], runs[4]
    check(np.array_equal(s1.cores.core, s4.cores.core),
          "cores bit-identical, shards=4 vs shards=1")
    st1, st4 = s1.store.state_dict(), s4.store.state_dict()
    check(st1.keys() == st4.keys()
          and all(np.array_equal(st1[k], st4[k]) for k in st1),
          "store rows and metadata bit-identical, shards=4 vs shards=1")
    check(np.array_equal(out1, out4), "served rows bit-identical")
    check(np.array_equal(ids1, ids4), "top-k ids identical")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 = run only the sharded path, on four chips")
    args = ap.parse_args(argv)

    try:
        from repro.graph import datasets
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repository's sources are missing next to "
              f"this script ({e})", file=sys.stderr)
        return 2
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s), JAX found "
              f"{len(devices)} {dev.platform} device(s)", file=sys.stderr)
        return 1
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"compile cache {enable_compile_cache()}")

    phase, check = Phases(), Checks()
    try:
        g = datasets.load(DATASET, seed=args.seed)
        log(f"{DATASET}: {g.n_nodes} nodes, {g.n_edges} edges, dim {DIM}")
        if args.chips == 1:
            one_chip(g, args.seed, phase, check)
        else:
            four_chips(g, args.seed, phase, check)
    except Exception:
        traceback.print_exc()
        check(False, "the run raised")
    print_memory(devices[: args.chips])
    if check.failed:
        print(f"chip_smoke: {len(check.failed)} check(s) failed: "
              f"{check.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
