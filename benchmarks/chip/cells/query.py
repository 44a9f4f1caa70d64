"""Query cells: open-loop embed, link-score and top-k requests.

The mix (``traffic/<name>.json``, ``kind`` ``query``) is an open-loop
schedule (``make_schedule``): exactly ``round(rate_per_s * seconds)``
arrivals, their times the sorted draws of a uniform over the window (a
Poisson process conditioned on its count), their kinds shuffled in the shares
of ``mix``, and their node ids drawn uniformly over all nodes. Every seed gets
the same amount of work in another order.

Set-up builds the service, then takes the stored rows of the newcomers
away again (``unstore``): a seeded share ``newcomers`` of the nodes, drawn
among those whose every neighbour lies in the ELL view that the cold-start
mean reads, as if the offline pass had not embedded them. Their first
request is a cold start that takes the §2.2 mean over real neighbour rows.
It overwrites every other stored row with a table the benchmark draws from
the seed (so that the reference owns its data), and warms the shapes the
window uses on stored nodes only. The window serves the
schedule from one loop: whatever has arrived is served, embeds first (one
``submit_many`` + ``flush``), then link-score pairs in calls of at most
``link_group`` pairs, then top-k requests in calls of exactly ``topk_group``
nodes (short calls are padded with their first node, whose extra answers are
dropped). The loop sleeps when nothing has arrived.

A request's latency runs from its scheduled arrival to the return of the
call that answered it, so a stall counts against every request behind it;
its queueing time runs from the arrival to the start of that call.

Correct means every embed answer of a stored row in the window equals the
reference's bit for bit (``embed_max_abs``), and every cold-start answer (and
every later answer of a row written back from one: ``cold_max_abs``), every
link score (``link_max_abs``) and a seeded sample of the top-k answers
(``topk_max_err``, ``TopkReadings``) match the float64 reference within the
limits of ``checks/query.json``. The control is the reference computed one
precision below (``control_answer``): rows rounded to bfloat16, cosines in
bfloat16, and top-k scores from a ``Precision.HIGH`` (three bfloat16 passes)
matmul on the device.
"""
from __future__ import annotations

import copy
import gc
import time
from typing import Dict, List

import numpy as np

from dataclasses import dataclass

from ..graphgen import canonical_edges
from ..refs.cores import core_numbers
from ..refs.serving import ServedStore, cosine, topk_scores

__all__ = ["Cell", "TopkReadings", "Schedule", "make_schedule",
           "control_answer", "unstore", "KINDS"]

KINDS = ("embed", "link", "topk")
EMBED, LINK, TOPK = range(len(KINDS))


@dataclass
class Schedule:
    """Open-loop arrivals: ``t`` seconds from the window's start, ``kind``
    an index into ``KINDS``, ``a``/``b`` node ids (``b`` is used by
    link-score pairs only)."""

    t: np.ndarray
    kind: np.ndarray
    a: np.ndarray
    b: np.ndarray


def make_schedule(params: dict, *, n_nodes: int, seconds: float,
                  rng: np.random.Generator) -> Schedule:
    count = int(round(float(params["rate_per_s"]) * seconds))
    mix = params["mix"]
    shares = np.asarray([float(mix.get(k, 0.0)) for k in KINDS])
    per_kind = np.floor(shares / shares.sum() * count).astype(int)
    per_kind[0] += count - per_kind.sum()
    kind = np.repeat(np.arange(len(KINDS)), per_kind)
    kind = kind[rng.permutation(count)]
    t = np.sort(rng.uniform(0.0, seconds, size=count))
    a = rng.integers(0, n_nodes, size=count)
    b = rng.integers(0, n_nodes, size=count)
    return Schedule(t=t, kind=kind, a=a, b=b)


def make_table(seed: int, n: int, dim: int) -> np.ndarray:
    """The stored rows: N(0, 1/dim) float32, drawn on the device in one
    jitted call from the seed."""
    import jax
    import jax.numpy as jnp

    key_seed = int(np.random.default_rng([seed, 2]).integers(0, 2**31 - 1))

    @jax.jit
    def draw(key):
        return jax.random.normal(key, (n, dim), jnp.float32) / np.sqrt(dim)

    return np.asarray(draw(jax.random.PRNGKey(key_seed)))


def unstore(store, nodes: np.ndarray) -> None:
    """Takes the stored rows of ``nodes`` out of ``store``, through its
    snapshot state: their slots go back to the free list, zeroed."""
    state = store.state_dict()
    slots = state["slot_of"][nodes]
    state["slot_of"][nodes] = state["capacity"]
    state["node_at"][slots] = -1
    state["table"][slots] = 0.0
    state["free"] = np.concatenate([state["free"], slots])
    store.load_state_dict(state)


class TopkReadings:
    """The widest top-k error over the answers compared.

    At each rank of an answer, the error is the larger of how far the
    served score lies from the reference's cosine of the served id, and how
    far that cosine lies below the reference's own score at that rank. An
    id that is no candidate (dead, the query itself, or a repeat) counts
    ``BAD_ID``, more than any cosine gap can be.
    """

    BAD_ID = 10.0

    def __init__(self):
        self.max_err = 0.0

    def add(self, node: int, got_ids: np.ndarray, got_scores: np.ndarray,
            query: np.ndarray, cand_ids: np.ndarray,
            cand_units: np.ndarray) -> None:
        ref_ids, ref_s = topk_scores(query, node, cand_ids, cand_units)
        score_of = dict(zip(ref_ids.tolist(), ref_s.tolist()))
        seen = set()
        for r, gid in enumerate(np.asarray(got_ids).tolist()):
            if r >= len(ref_ids):  # fewer candidates than k: must be padding
                err = 0.0 if gid == -1 else self.BAD_ID
            elif gid not in score_of or gid in seen:
                err = self.BAD_ID
            else:
                ref = score_of[gid]
                err = max(abs(float(got_scores[r]) - ref),
                          float(ref_s[r]) - ref)
            seen.add(gid)
            self.max_err = max(self.max_err, err)


class Cell:
    kind = "query"

    def __init__(self, config: dict, traffic: dict, seed: int, annotate):
        self.cfg = config
        self.traffic = traffic
        self.seed = int(seed)
        self.annotate = annotate
        self.svc = None
        self.log: List[tuple] = []  # (in_window, kind, nodes, answer...)
        self.k = int(traffic["topk_k"])
        self.link_group = int(traffic["link_group"])
        self.topk_group = int(traffic["topk_group"])

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
        keys = np.setdiff1d(edges[:, 0] * n + edges[:, 1],
                            held[:, 0] * n + held[:, 1])
        base = np.stack([keys // n, keys % n], axis=1)
        deg = np.bincount(base.ravel(), minlength=n)
        # the ELL view holds a node's first ``width`` neighbours; the rest
        # wait in the host overflow until a compaction
        fits = np.nonzero((deg > 0) & (deg <= svc.graph.width))[0]
        rng = np.random.default_rng([self.seed, 7])
        new = rng.choice(fits, size=round(self.traffic["newcomers"] * n),
                         replace=False)
        unstore(svc.store, new)
        stored = deg > 0
        stored[new] = False
        self.newcomers = len(new)
        rows = make_table(self.seed, n, c["dim"])
        served = np.nonzero(stored)[0]
        svc.store.put_many(served, rows[served],
                           core_numbers(n, base)[served])
        src = np.concatenate([base[:, 0], base[:, 1]])
        dst = np.concatenate([base[:, 1], base[:, 0]])
        order = np.argsort(src, kind="stable")
        indptr = np.zeros(n + 1, np.int64)
        np.cumsum(np.bincount(src, minlength=n), out=indptr[1:])
        self.ref = ServedStore(rows, stored, indptr, dst[order], c["batch"])
        self._warm_up(np.random.default_rng([self.seed, 3]), served)
        # A full collection would scan the whole set-up heap; frozen, it
        # scans only what the window allocates.
        gc.collect()
        gc.freeze()

    def _warm_up(self, rng, nodes) -> None:
        """One call of each shape the window dispatches, on stored nodes
        only, so that every newcomer is still cold in the window: a flush
        batch, a link-score call per count of distinct ends, a top-k call."""
        self._embed(rng.choice(nodes, self.cfg["batch"]), False)
        for u in range(1, 2 * self.link_group + 1):
            ends = rng.choice(nodes, size=u, replace=False)
            flat = np.concatenate([ends, ends[:1]])[: 2 * ((u + 1) // 2)]
            self._link(flat.reshape(-1, 2), False)
        self._topk(rng.choice(nodes, self.topk_group), False)

    # ------------------------------------------------------------ calls

    def _embed(self, nodes, in_window: bool) -> np.ndarray:
        with self.annotate("bench.flush"):
            self.svc.submit_many(nodes)
            out = self.svc.flush()
        self.log.append((in_window, EMBED, np.asarray(nodes), out))
        return out

    def _link(self, pairs, in_window: bool) -> np.ndarray:
        with self.annotate("bench.link_scores"):
            out = self.svc.link_scores(pairs)
        self.log.append((in_window, LINK, np.asarray(pairs), out))
        return out

    def _topk(self, nodes, in_window: bool):
        with self.annotate("bench.top_k_neighbors"):
            ids, scores = self.svc.top_k_neighbors(nodes, self.k)
        self.log.append((in_window, TOPK, np.asarray(nodes), (ids, scores)))
        return ids

    # ------------------------------------------------------------ window

    def window(self, seconds: float) -> Dict:
        """Serves ``seconds`` of the schedule drawn from the seed."""
        rng = np.random.default_rng([self.seed, 4])
        sch = make_schedule(self.traffic, n_nodes=self.n, seconds=seconds,
                            rng=rng)
        cold0 = self.svc.stats.cold_starts
        N = len(sch.t)
        done = np.full(N, np.nan)
        start = np.full(N, np.nan)
        i = 0
        full0 = gc.get_stats()[2]["collections"]
        t0 = time.perf_counter()
        while i < N:
            now = time.perf_counter() - t0
            if sch.t[i] > now:
                time.sleep(sch.t[i] - now)
                continue
            j = int(np.searchsorted(sch.t, now, side="right"))
            idx = np.arange(i, j)
            i = j
            e = idx[sch.kind[idx] == EMBED]
            if len(e):
                start[e] = time.perf_counter() - t0
                self._embed(sch.a[e], True)
                done[e] = time.perf_counter() - t0
            li = idx[sch.kind[idx] == LINK]
            for s in range(0, len(li), self.link_group):
                g = li[s:s + self.link_group]
                start[g] = time.perf_counter() - t0
                self._link(np.stack([sch.a[g], sch.b[g]], axis=1), True)
                done[g] = time.perf_counter() - t0
            ti = idx[sch.kind[idx] == TOPK]
            for s in range(0, len(ti), self.topk_group):
                g = ti[s:s + self.topk_group]
                nodes = np.full(self.topk_group, sch.a[g[0]])
                nodes[: len(g)] = sch.a[g]
                start[g] = time.perf_counter() - t0
                self._topk(nodes, True)
                done[g] = time.perf_counter() - t0
        t1 = time.perf_counter()
        lat_ms = (done - sch.t) * 1e3
        queue_ms = (start - sch.t) * 1e3
        q = sch.kind != TOPK
        n_topk = int((~q).sum())
        call_ms = (done - start) * 1e3
        worst = int(np.argmax(call_ms))
        return {
            "t0": t0, "t1": t1, "attempted": N, "failed": 0,
            "e2e": {"query_p95_ms": float(np.percentile(lat_ms[q], 95)),
                    "topk_p95_ms": float(np.percentile(lat_ms[~q], 95))},
            "counters": {"requests": N, "topk_requests": n_topk,
                         "cold_starts": self.svc.stats.cold_starts - cold0},
            "samples": {"queue_ms": queue_ms, "latency_ms": lat_ms},
            "notes": [f"{N} requests ({int(q.sum())} embed/link, {n_topk} "
                      f"top-k) over {t1 - t0:.3f} s, last arrival at "
                      f"{sch.t[-1]:.3f} s; latency p50 "
                      f"{np.percentile(lat_ms, 50):.3f} ms, queueing p95 "
                      f"{np.percentile(queue_ms, 95):.3f} ms; cold starts "
                      f"{self.svc.stats.cold_starts - cold0} of "
                      f"{self.newcomers} newcomers",
                      f"longest call {call_ms[worst]:.3f} ms, at "
                      f"{start[worst] / 1e3:.3f} s; full collections in the "
                      f"window: {gc.get_stats()[2]['collections'] - full0}"],
        }

    # ------------------------------------------------------------ check

    def collect(self) -> None:
        """Read which nodes hold a stored row, then let the service go."""
        st = self.svc.store
        self.resident = st.slots_of(np.arange(self.n)) < st.capacity
        self.svc = None

    def replay(self, ref: ServedStore, sample: int):
        """Replays every call, set-up's too, through the reference.

        Yields ``(kind, nodes, served, reference, extra)`` for every embed
        and link call of the window and for ``sample`` top-k calls of it
        drawn from the seed. ``reference`` is the reference's answer: rows,
        cosines, or for a top-k call the query rows its own flush answers.
        ``extra`` is, for an embed call, which rows came from a cold start
        (the mean, or a row written back from it); for a link call the
        pair's two rows; for a top-k call the (ids, unit rows) it is scored
        against.
        """
        rng = np.random.default_rng([self.seed, 5])
        window_topk = [i for i, (w, kind, _, _) in enumerate(self.log)
                       if w and kind == TOPK]
        picked = set(rng.permutation(window_topk)[:sample].tolist())
        cand = None
        for i, (w, kind, nodes, served) in enumerate(self.log):
            if kind == LINK:
                extra = ref.link_ends(nodes)
                want = cosine(*extra)
            else:
                want, extra = ref.answer(nodes)
            if not w or (kind == TOPK and i not in picked):
                continue
            if kind == TOPK:
                if cand is None or cand[0] != ref.version:
                    cand = (ref.version, *ref.candidates())
                extra = cand[1:]
            yield kind, nodes, served, want, extra

    def check(self, answer=None) -> Dict[str, float]:
        """The numbers compared. ``answer(kind, nodes, served, reference,
        extra)`` stands in for what the program served; the control passes
        its own. ``stored_mismatches`` counts the nodes whose stored row the
        service holds where the reference holds none, or the reverse: a
        cold start that was not written back, or written back unresolved."""
        gap = {"embed_max_abs": 0.0, "cold_max_abs": 0.0, "link_max_abs": 0.0}
        tk = TopkReadings()
        sample = int(self.traffic["check_topk_calls"])
        ref = copy.deepcopy(self.ref)
        for kind, nodes, served, want, extra in self.replay(ref, sample):
            got = served if answer is None else answer(
                kind, nodes, served, want, extra)
            if kind == EMBED:
                rows = np.max(np.abs(got - want), axis=1, initial=0.0)
                for key, sel in (("embed_max_abs", ~extra),
                                 ("cold_max_abs", extra)):
                    gap[key] = max(gap[key], float(np.max(rows[sel],
                                                          initial=0.0)))
            elif kind == LINK:
                gap["link_max_abs"] = max(gap["link_max_abs"], float(
                    np.max(np.abs(got - want), initial=0.0)))
            else:
                ids, scores = got
                for r, v in enumerate(nodes.tolist()):
                    tk.add(v, ids[r], scores[r], want[r], *extra)
        return dict(gap, topk_max_err=tk.max_err, stored_mismatches=int(
            np.sum(self.resident != ref.stored)))

    def control(self) -> Dict[str, float]:
        return self.check(control_answer)


def _bf16(x):
    import jax.numpy as jnp

    return jnp.asarray(np.asarray(x, np.float32), jnp.bfloat16)


def control_answer(kind, nodes, served, want, extra):
    """The reference's answer computed one precision below."""
    import jax
    import jax.numpy as jnp

    if kind == EMBED:
        return np.asarray(_bf16(want).astype(jnp.float32), np.float64)
    if kind == LINK:
        a, b = (_bf16(x) for x in extra)
        na = a / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True))
        nb = b / jnp.sqrt(jnp.sum(b * b, axis=-1, keepdims=True))
        return np.asarray(jnp.sum(na * nb, axis=-1).astype(jnp.float32),
                          np.float64)
    ids, units = extra
    q = np.asarray(want, np.float64)
    q = q / np.maximum(np.linalg.norm(q, axis=-1, keepdims=True), 1e-9)
    s = np.asarray(jax.lax.dot_general(
        jnp.asarray(q, jnp.float32), jnp.asarray(units, jnp.float32),
        (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGH,
        preferred_element_type=jnp.float32))
    k = served[0].shape[1]
    out_ids = np.full((len(nodes), k), -1, np.int64)
    out_s = np.full((len(nodes), k), -np.inf, np.float32)
    for r, v in enumerate(nodes.tolist()):
        row = np.where(ids == v, -np.inf, s[r])
        top = np.argsort(-row, kind="stable")[:k]
        out_ids[r], out_s[r] = ids[top], row[top]
    return out_ids, out_s
