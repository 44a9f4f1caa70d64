"""Incremental core maintenance: insertion-only exactness vs the peeling oracle.

Stream/graph boilerplate lives in the shared ``stream_case`` fixture
(``tests/conftest.py``); the full-stream per-edge replays and the forced
fallback cases carry ``pytest.mark.slow`` (kept on in CI, deselect locally
with ``-m "not slow"``).
"""
import numpy as np
import pytest

from repro.core.kcore import core_numbers_host
from repro.graph import generators
from repro.serve import DynamicGraph, IncrementalCore


def _stream_and_check(edges, dyn, inc, check_every=50):
    """Stream ``edges`` one at a time, checking exactness periodically."""
    for i, (u, v) in enumerate(edges):
        assert dyn.add_edge(int(u), int(v))
        inc.on_edge(int(u), int(v))
        if (i + 1) % check_every == 0:
            oracle = core_numbers_host(dyn.snapshot())
            np.testing.assert_array_equal(inc.core, oracle)
    oracle = core_numbers_host(dyn.snapshot())
    np.testing.assert_array_equal(inc.core, oracle)
    return inc


@pytest.mark.slow
@pytest.mark.parametrize(
    "maker,seed",
    [
        (lambda: generators.barabasi_albert(100, 3, seed=1), 10),
        (lambda: generators.erdos_renyi(90, 260, seed=2), 11),
        (lambda: generators.powerlaw_cluster(95, 4, 0.3, seed=3), 12),
        (lambda: generators.barabasi_albert_varying(110, 5.0, seed=4), 13),
    ],
)
def test_streaming_exactness_random_graphs(stream_case, maker, seed):
    _, edges, dyn, inc = stream_case(maker, seed=seed)
    _stream_and_check(edges, dyn, inc)
    assert inc.repairs > 0 and inc.promoted > 0


@pytest.mark.slow
def test_exact_after_every_compaction(stream_case):
    _, edges, dyn, inc = stream_case(
        lambda: generators.barabasi_albert_varying(130, 5.0, seed=5),
        seed=6, width=2,  # tiny width: compaction matters
    )
    compactions = 0
    for i, (u, v) in enumerate(edges):
        dyn.add_edge(int(u), int(v))
        inc.on_edge(int(u), int(v))
        if (i + 1) % 100 == 0:
            dyn.compact()
            compactions += 1
            oracle = core_numbers_host(dyn.snapshot())
            np.testing.assert_array_equal(inc.core, oracle)
            assert inc.resync() == 0  # resync finds nothing to fix
    assert compactions >= 3


def test_new_nodes_enter_at_correct_level():
    dyn = DynamicGraph(3, np.array([[0, 1], [1, 2], [0, 2]]))  # triangle
    inc = IncrementalCore(dyn)
    np.testing.assert_array_equal(inc.core, [2, 2, 2])
    dyn.add_edge(0, 3)  # pendant: core 1
    inc.on_edge(0, 3)
    np.testing.assert_array_equal(inc.core, [2, 2, 2, 1])
    # attach node 3 to the rest of the triangle -> K4, everyone at core 3
    for t in (1, 2):
        dyn.add_edge(3, t)
        inc.on_edge(3, t)
    np.testing.assert_array_equal(inc.core, [3, 3, 3, 3])


def test_block_insert_cascade_promotes_multiple_levels():
    """K4 staged as one block: every core jumps 0 -> 3 in a single repair.

    Per-edge seeding (old core + 1) would cap the sweep at level 1; the block
    path must seed at the block-wide upper bound and cascade."""
    dyn = DynamicGraph(4)
    inc = IncrementalCore(dyn)
    accepted = dyn.add_edges([[i, j] for i in range(4) for j in range(i + 1, 4)])
    promoted = inc.on_edge_block(accepted)
    assert promoted == 4
    np.testing.assert_array_equal(inc.core, [3, 3, 3, 3])
    assert inc.repairs == 1  # one repair for the whole block


@pytest.mark.parametrize("block_size", [16, 64, 300])
def test_block_insert_stream_matches_oracle(stream_case, block_size):
    _, edges, dyn, inc = stream_case(
        lambda: generators.barabasi_albert_varying(200, 5.0, seed=21),
        seed=block_size,
    )
    for start in range(0, len(edges), block_size):
        accepted = dyn.add_edges(edges[start : start + block_size])
        inc.on_edge_block(accepted)
        oracle = core_numbers_host(dyn.snapshot())
        np.testing.assert_array_equal(inc.core, oracle)
    assert inc.repairs <= -(-len(edges) // block_size)


def test_block_delete_matches_oracle(stream_case):
    _, edges, dyn, inc = stream_case(
        lambda: generators.barabasi_albert_varying(180, 5.0, seed=22),
        width=6, preload=True, shuffle=False,
    )
    rng = np.random.default_rng(23)
    perm = rng.permutation(len(edges))
    for start in range(0, len(edges) // 2, 40):
        removed = dyn.remove_edges(edges[perm[start : start + 40]])
        inc.on_remove(removed)
        oracle = core_numbers_host(dyn.snapshot())
        np.testing.assert_array_equal(inc.core, oracle)
    assert inc.demoted > 0


def test_delete_then_reinsert_restores_levels():
    dyn = DynamicGraph(4, np.array([[i, j] for i in range(4)
                                    for j in range(i + 1, 4)]))  # K4
    inc = IncrementalCore(dyn)
    np.testing.assert_array_equal(inc.core, [3, 3, 3, 3])
    removed = dyn.remove_edges(np.array([[0, 1], [2, 3]]))
    demoted = inc.on_remove(removed)
    assert demoted == 4  # 4-cycle: everyone down to core 2
    np.testing.assert_array_equal(inc.core, [2, 2, 2, 2])
    accepted = dyn.add_edges(np.array([[0, 1], [2, 3]]))
    inc.on_edge_block(accepted)
    np.testing.assert_array_equal(inc.core, [3, 3, 3, 3])
    assert inc.resync() == 0


def test_isolating_deletion_drops_to_zero():
    dyn = DynamicGraph(3, np.array([[0, 1], [1, 2], [0, 2]]))
    inc = IncrementalCore(dyn)
    removed = dyn.remove_edges(np.array([[0, 1], [0, 2]]))
    inc.on_remove(removed)
    np.testing.assert_array_equal(inc.core, [0, 1, 1])
    assert inc.resync() == 0


@pytest.mark.slow
def test_repeel_fallback_is_exact_and_counted(stream_case):
    """A graph-sized block trips the bounded re-peel fallback, exactly."""
    _, edges, dyn, inc = stream_case(
        lambda: generators.barabasi_albert_varying(400, 5.0, seed=24),
        shuffle=False, repeel_frac=0.05,  # tiny bound: force fallback
        repair_policy="region",  # legacy static trigger (adaptive would descend)
    )
    accepted = dyn.add_edges(edges)
    inc.on_edge_block(accepted)
    assert inc.repeels >= 1
    np.testing.assert_array_equal(inc.core, core_numbers_host(dyn.snapshot()))


@pytest.mark.parametrize("impl", ["ref", "device"])
def test_mixed_blocks_with_compactions_stay_exact(stream_case, impl):
    _, edges, dyn, inc = stream_case(
        lambda: generators.barabasi_albert_varying(150, 4.0, seed=25),
        seed=26, width=3, impl=impl,
    )
    rng = np.random.default_rng(26)
    live: list = []
    for step, start in enumerate(range(0, len(edges), 24)):
        accepted = dyn.add_edges(edges[start : start + 24])
        inc.on_edge_block(accepted)
        live.extend(map(tuple, accepted))
        if step % 2 == 1 and len(live) > 10:
            pick = rng.choice(len(live), size=8, replace=False)
            removed = dyn.remove_edges(np.array([live[i] for i in pick]))
            inc.on_remove(removed)
            gone = {tuple(e) for e in removed}
            live = [e for e in live if e not in gone]
        if step % 3 == 2:
            dyn.compact()
        oracle = core_numbers_host(dyn.snapshot())
        np.testing.assert_array_equal(inc.core, oracle)
    assert inc.promoted > 0 and inc.demoted > 0
    assert inc.resync() == 0


@pytest.mark.slow
def test_fused_descent_matches_host_descent_on_blocks(stream_case):
    """The one-dispatch fused descent and the PR 2 host descent agree level
    by level on the same block/deletion stream (same graph, same blocks)."""
    maker = lambda: generators.barabasi_albert_varying(160, 4.0, seed=31)
    _, edges, dyn_ref, ref = stream_case(maker, seed=32, impl="ref")
    _, _, dyn_dev, dev = stream_case(maker, seed=32, impl="device")
    rng = np.random.default_rng(32)
    live: list = []
    for step, start in enumerate(range(0, len(edges), 32)):
        block = edges[start : start + 32]
        a_ref = dyn_ref.add_edges(block)
        a_dev = dyn_dev.add_edges(block)
        np.testing.assert_array_equal(a_ref, a_dev)
        ref.on_edge_block(a_ref)
        dev.on_edge_block(a_dev)
        live.extend(map(tuple, a_ref))
        if step % 2 == 1 and len(live) > 8:
            pick = rng.choice(len(live), size=6, replace=False)
            rm = np.array([live[i] for i in pick])
            ref.on_remove(dyn_ref.remove_edges(rm))
            dev.on_remove(dyn_dev.remove_edges(rm))
            gone = {tuple(e) for e in rm}
            live = [e for e in live if e not in gone]
        np.testing.assert_array_equal(ref.core, dev.core)
    assert dev.descends > 0  # the fused path actually ran
    assert ref.descends == 0  # and the host oracle never did
    assert ref.resync() == 0 and dev.resync() == 0


def test_kernel_backed_descent_stays_exact(stream_case):
    """End-to-end adoption check: the fused descent driven through the
    Pallas kernel (interpret mode) still matches the peeling oracle."""
    _, edges, dyn, inc = stream_case(
        lambda: generators.barabasi_albert(60, 3, seed=33),
        shuffle=False, impl="device", kernel_impl="pallas_interpret",
        region_impl="jit",
    )
    for start in range(0, len(edges), 40):
        accepted = dyn.add_edges(edges[start : start + 40])
        inc.on_edge_block(accepted)
    oracle = core_numbers_host(dyn.snapshot())
    np.testing.assert_array_equal(inc.core, oracle)
    assert inc.descends > 0


@pytest.mark.slow
@pytest.mark.parametrize("repeel_impl", ["rounds", "descend", "shell"])
def test_repeel_fallback_impls_are_exact(stream_case, repeel_impl):
    """Both device-path fallbacks (vectorized rounds peel, full-graph fused
    descent) recompute the exact core numbers, insertions and deletions."""
    _, edges, dyn, inc = stream_case(
        lambda: generators.barabasi_albert_varying(300, 5.0, seed=34),
        shuffle=False, repeel_frac=0.05, repeel_impl=repeel_impl,
        repair_policy="region",  # legacy static trigger (adaptive would descend)
    )
    inc.on_edge_block(dyn.add_edges(edges))
    assert inc.repeels >= 1
    np.testing.assert_array_equal(inc.core, core_numbers_host(dyn.snapshot()))
    rng = np.random.default_rng(35)
    rm = dyn.remove_edges(edges[rng.permutation(len(edges))[: len(edges) // 2]])
    inc.on_remove(rm)
    np.testing.assert_array_equal(inc.core, core_numbers_host(dyn.snapshot()))


@pytest.mark.slow
@pytest.mark.parametrize("repeel_impl", [None, "descend"])
def test_truncated_descent_falls_back_to_exact(repeel_impl):
    """A sweep cap below the cascade depth must never commit non-converged
    estimates: the repair detects the truncation and recovers through an
    uncapped exact recompute (even when the fallback itself is the capped
    full-graph descent)."""
    edges = np.array([[i, i + 1] for i in range(59)], np.int64)  # deep chain
    dyn = DynamicGraph(60, width=4)
    inc = IncrementalCore(dyn, max_sweeps=5, repeel_impl=repeel_impl)
    inc.on_edge_block(dyn.add_edges(edges))
    np.testing.assert_array_equal(inc.core, core_numbers_host(dyn.snapshot()))
    assert inc.repeels >= 1  # the truncation was detected, not ignored


def test_phase_report_tracks_repair_phases():
    g = generators.barabasi_albert(80, 3, seed=36)
    dyn = DynamicGraph(g.n_nodes, width=4)
    inc = IncrementalCore(dyn)
    inc.on_edge_block(dyn.add_edges(g.edge_list()))
    report = inc.phase_report()
    assert "region" in report
    assert report["region"]["seconds"] >= 0.0
    assert {"descend", "fallback"} & set(report)  # one of them repaired
    inc.reset_phases()
    assert inc.phase_report() == {}


def test_drift_and_membership_gate():
    g = generators.barabasi_albert(80, 3, seed=7)
    dyn = DynamicGraph(g.n_nodes, g.edge_list())
    inc = IncrementalCore(dyn)
    inc.mark_refresh()
    assert inc.drift() == 0
    k0 = 3
    changed0, size0 = inc.membership_drift(k0)
    assert changed0 == 0 and size0 > 0
    # densify a low-core pocket until levels move
    low = np.argsort(inc.core)[:6]
    for i in range(len(low)):
        for j in range(i + 1, len(low)):
            if dyn.add_edge(int(low[i]), int(low[j])):
                inc.on_edge(int(low[i]), int(low[j]))
    assert inc.drift() > 0
    oracle = core_numbers_host(dyn.snapshot())
    np.testing.assert_array_equal(inc.core, oracle)


@pytest.mark.parametrize("kw", [
    {},
    {"repair_policy": "fallback", "repeel_impl": "descend"},
], ids=["adaptive", "repeel-by-descent"])
def test_repair_spans_are_the_phase_intervals(stream_case, kw):
    """phase_report(), repair_phase_seconds, the repair policy and the trace
    are fed by one interval per phase occurrence: that of its span."""
    from repro.obs import MetricsRegistry, metrics, set_metrics
    from repro.obs import trace as obs

    prev_t, prev_m = obs.tracer(), metrics()
    t = obs.enable(exemplar_watch=())
    reg = set_metrics(MetricsRegistry())
    try:
        g, edges, dyn, inc = stream_case(
            lambda: generators.barabasi_albert(120, 3, seed=5), seed=2,
            **kw)
        seen = []
        observe = inc.policy.observe
        inc.policy.observe = lambda path, work, s: (
            seen.append((path, s)), observe(path, work, s))
        for start in range(0, len(edges), 48):
            inc.on_edge_block(dyn.add_edges(edges[start:start + 48]))
        np.testing.assert_array_equal(
            inc.core, core_numbers_host(dyn.snapshot()))
    finally:
        obs.set_tracer(prev_t)
        set_metrics(prev_m)
    by_id = {e["id"]: e for e in t.events}

    def in_repeel(e):
        while e["parent"] is not None:
            e = by_id[e["parent"]]
            if e["name"] == "repair.fallback":
                return True
        return False

    phases = [e for e in t.events if e["name"].startswith("repair.")]
    report = inc.phase_report()
    assert report
    for phase in ("region", "candidates", "descend", "fallback"):
        spans = [e for e in phases if e["name"] == f"repair.{phase}"]
        outside = sum(e["dur"] for e in spans if not in_repeel(e))
        assert report.get(phase, {"seconds": 0.0})["seconds"] == \
            pytest.approx(outside, abs=2e-6)
        h = reg.get("repair_phase_seconds", phase=phase)
        assert (h.sum if h is not None else 0.0) == \
            pytest.approx(sum(e["dur"] for e in spans), abs=1e-9)
    assert [s for p, s in seen if p == "descend"] == pytest.approx(
        [e["dur"] for e in phases if e["name"] == "repair.descend"])
    assert [s for p, s in seen if p == "repeel"] == pytest.approx(
        [e["dur"] for e in phases if e["name"] == "repair.fallback"])
    if kw:  # a re-peel's own gather and descent nest in its span
        assert report.keys() == {"fallback"}
        nested = [e for e in phases if e["name"] != "repair.fallback"]
        assert nested and all(
            by_id[e["parent"]]["name"] == "repair.fallback" for e in nested)
        assert all({"rows", "cells"} <= e["attrs"].keys() for e in nested)


# ------------------------------------------------- degree-tiered descent

def _two_tier_cells(deg):
    """Cells of the plain two-tier split at 32 (rows <= 32 wide, the rest
    at pow2 of the largest degree), or of one matrix where that is fewer."""
    pow2 = lambda x: 1 << max(int(x) - 1, 0).bit_length()
    w_top = max(pow2(deg.max()), 64)
    one = max(pow2(len(deg)), 64) * w_top
    n_big = int((deg > 32).sum())
    if w_top <= 64 or not 0 < n_big < len(deg):
        return one
    return min(one, max(pow2(len(deg) - n_big), 64) * 32
               + max(pow2(n_big), 64) * w_top)


def _degree_sets():
    rng = np.random.default_rng(40)
    return {
        "power-law": np.minimum(rng.zipf(1.8, 20000), 3654),
        "few-hubs": np.concatenate([rng.integers(1, 30, 5000), [700, 2000]]),
        "mid-heavy": np.concatenate([rng.integers(1, 33, 300),
                                     rng.integers(100, 500, 400), [1500]]),
        "all-wide": rng.integers(600, 900, 100),
        "one-row": np.array([3000]),
        "low": rng.integers(0, 65, 4000),
    }


@pytest.mark.parametrize("name", list(_degree_sets()))
def test_tier_plan_puts_each_row_in_one_tier_that_holds_it(name):
    deg = _degree_sets()[name]
    tiers, tier, cells = IncrementalCore._tier_plan(deg)
    widths = np.array([w for w, _ in tiers])
    rows = np.array([r for _, r in tiers])
    assert np.all(np.diff(widths) > 0)  # narrowest first
    assert tier.shape == deg.shape and tier.min() >= 0
    assert tier.max() < len(tiers)
    assert np.all(deg <= widths[tier])  # the row fits its tier
    # and it sits in the narrowest tier that holds it
    assert np.all(tier == np.searchsorted(widths, deg))
    counts = np.bincount(tier, minlength=len(tiers))
    assert np.all(counts > 0) and np.all(counts <= rows)  # no empty tier
    assert all(r >= 64 and r & (r - 1) == 0 for r in rows)
    assert cells == int((widths * rows).sum())
    assert cells <= _two_tier_cells(deg)


def test_tier_plan_ladder_cuts_the_cells_of_a_hub_heavy_set():
    deg = _degree_sets()["mid-heavy"]
    tiers, _, cells = IncrementalCore._tier_plan(deg)
    assert [w for w, _ in tiers] == [32, 512, 2048]
    assert cells < _two_tier_cells(deg)


@pytest.mark.parametrize("deg", [
    np.arange(65), np.full(5000, 64), np.array([1]), np.zeros(10, np.int64),
], ids=["0-64", "all-64", "one", "zeros"])
def test_tier_plan_keeps_low_degree_sets_one_matrix(deg):
    tiers, tier, cells = IncrementalCore._tier_plan(deg)
    assert tiers == ((64, max(64, 1 << (len(deg) - 1).bit_length())),)
    assert not tier.any() and cells == tiers[0][0] * tiers[0][1]


def _hub_graph(seed):
    """1,600 nodes of degree ~4, 110 of degree ~60, 70 of ~200 and one of
    ~1,100: candidate rows in every tier of the ladder."""
    rng = np.random.default_rng(seed)
    n = 1600
    edges = [np.stack([np.arange(n), rng.integers(0, n, n)], 1)]
    edges += [np.stack([np.full(k, h), rng.choice(n, k, replace=False)], 1)
              for h, k in [(0, 1100)] + [(h, 200) for h in range(1, 71)]
              + [(h, 60) for h in range(71, 181)]]
    e = np.concatenate(edges + [rng.integers(0, n, (2 * n, 2))])
    e = e[e[:, 0] != e[:, 1]]
    e = np.unique(np.sort(e, axis=1), axis=0)
    return n, e[rng.permutation(len(e))]


@pytest.mark.parametrize("kw", [
    {"repair_policy": "region", "repeel_frac": 1.0},
    {"repair_policy": "fallback", "repeel_impl": "descend"},
], ids=["region-descent", "repeel-by-descent"])
def test_tiered_descent_matches_ref_and_oracle_on_hub_stream(kw):
    """Insert and retract blocks on a hub-heavy graph: the ladder's fused
    descent commits the host descent's and the peeling oracle's cores,
    including while candidate rows hold overflow arcs."""
    from repro.obs import trace as obs

    n, edges = _hub_graph(41)
    base, stream = edges[: len(edges) * 3 // 4], edges[len(edges) * 3 // 4:]
    dyn_ref = DynamicGraph(n, base, width=64)
    dyn_dev = DynamicGraph(n, base, width=64)
    ref = IncrementalCore(dyn_ref, impl="ref", repeel_frac=1.0)
    prev = obs.tracer()
    t = obs.enable(exemplar_watch=())
    try:
        dev = IncrementalCore(dyn_dev, impl="device", **kw)
        rng = np.random.default_rng(42)
        overflowed = False
        for step, block in enumerate(np.array_split(stream, 3)):
            for dyn, inc in ((dyn_ref, ref), (dyn_dev, dev)):
                inc.on_edge_block(dyn.add_edges(block))
            overflowed |= dyn_dev.overflow_arcs > 0
            np.testing.assert_array_equal(ref.core, dev.core)
            gone = block[rng.choice(len(block), len(block) // 4,
                                    replace=False)]
            for dyn, inc in ((dyn_ref, ref), (dyn_dev, dev)):
                inc.on_remove(dyn.remove_edges(gone))
            overflowed |= dyn_dev.overflow_arcs > 0
            np.testing.assert_array_equal(ref.core, dev.core)
            np.testing.assert_array_equal(
                dev.core, core_numbers_host(dyn_dev.snapshot()))
            if step == 1:
                dyn_dev.compact()
                dyn_ref.compact()
    finally:
        obs.set_tracer(prev)
    assert overflowed  # some candidate rows held overflow arcs
    spans = [e for e in t.events if e["name"] == "repair.descend"
             and e["attrs"]["impl"].startswith("fused")]
    assert dev.descends == len(spans) > 0 and ref.descends == 0
    assert max(e["attrs"]["tiers"] for e in spans) >= 3
    for e in spans:
        a = e["attrs"]
        assert a["arcs"] > 0 and a["cells"] >= a["arcs"]
