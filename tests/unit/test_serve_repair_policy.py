"""Adaptive repair policy (exactness + decisions).

The policy only ever picks *which* exact repair path runs, so every test
here asserts two things: the decision machinery behaves (cold start, EMA
crossover, one-shot exploration, stale-path probing), and the computed core
numbers never deviate from the Matula–Beck oracle no matter what it picks.
"""
import numpy as np
import pytest

from repro.core.kcore import core_numbers_host
from repro.graph import generators
from repro.obs.metrics import MetricsRegistry
from repro.serve import DynamicGraph, IncrementalCore
from repro.serve.kcore_inc import RepairPolicy


# ------------------------------------------------------------ RepairPolicy


def test_cold_start_heuristic_shapes_first_decision():
    p = RepairPolicy()
    # modest matrix vs real arc mass: descend, counted as a cold decision
    assert p.choose(cells=4096, repeel_work=4096) == "descend"
    assert p.cold_decisions == 1
    # padded matrix dwarfs the shell arc mass: don't burn time measuring it
    assert p.choose(cells=1 << 24, repeel_work=128) == "repeel"
    # the arc mass is floored at 64: 32 cells per arc of that still descend
    assert p.choose(cells=32 * 64, repeel_work=1) == "descend"
    assert p.choose(cells=32 * 64 + 1, repeel_work=1) == "repeel"
    assert p.decisions == {"descend": 2, "repeel": 2}


def test_ema_observe_predict_and_regime_extrapolation():
    p = RepairPolicy()
    for _ in range(4):
        p.observe("descend", 4096, 0.010)
    assert p.predict("descend", 4096) == pytest.approx(0.010, rel=0.05)
    # an unmeasured regime extrapolates linearly in work from the nearest
    far = p.predict("descend", 4 * 4096)
    assert far == pytest.approx(4 * 0.010, rel=0.25)
    # EMA tracks drift toward new observations
    for _ in range(16):
        p.observe("descend", 4096, 0.002)
    assert p.predict("descend", 4096) < 0.004


def test_unmeasured_repeel_is_explored_once():
    p = RepairPolicy()
    p.observe("descend", 4096, 0.001)  # descend measured, repeel never
    assert p.choose(cells=4096, repeel_work=4096) == "repeel"
    p.observe("repeel", 4096, 0.010)
    # both measured now: the crossover picks the cheap path
    assert p.choose(cells=4096, repeel_work=4096) == "descend"
    assert p.cold_decisions == 0


def test_stale_loser_is_probed():
    p = RepairPolicy(probe_every=4)
    p.observe("descend", 4096, 0.001)
    p.observe("repeel", 4096, 0.100)  # repeel loses the crossover hard
    choices = [
        p.choose(cells=4096, repeel_work=4096)
        for _ in range(5)
    ]
    # the loser goes unmeasured for probe_every decisions, then gets probed
    assert choices[:4] == ["descend"] * 4
    assert choices[4] == "repeel"
    assert p.probes == 1
    # measuring the probed path resets its staleness: back to the winner
    p.observe("repeel", 4096, 0.100)
    assert p.choose(cells=4096, repeel_work=4096) == "descend"


def test_registry_prior_warm_starts_predictions():
    reg = MetricsRegistry()
    reg.histogram("repair_phase_seconds", phase="fallback").observe(0.02)
    reg.histogram("repair_phase_seconds", phase="descend").observe(0.004)
    p = RepairPolicy()
    p.refresh_from_metrics(reg)
    # no own measurements yet: the work-blind registry prior stands in
    assert p.predict("repeel", 10_000) == pytest.approx(0.02)
    assert p.predict("descend", 10_000) == pytest.approx(0.004)
    # own measurements take precedence once they exist
    p.observe("repeel", 10_000, 0.5)
    assert p.predict("repeel", 10_000) == pytest.approx(0.5)


def test_report_counts_probes_and_decisions():
    p = RepairPolicy(probe_every=2)
    p.observe("descend", 1024, 0.001)
    p.observe("repeel", 1024, 0.1)
    for _ in range(6):
        p.choose(cells=1024, repeel_work=1024)
    rep = p.report()
    assert rep["probes"] >= 1
    assert sum(rep["decisions"].values()) == 6
    assert rep["regimes"]  # learned EMA cells are exported


# ------------------------------------------------------ forced paths


def test_unknown_impl_rejected():
    """The maintainer has two implementations, the device path and its
    host oracle; anything else is refused, not silently mapped."""
    for impl in ("auto", "pallas", ""):
        with pytest.raises(ValueError, match="unknown impl"):
            IncrementalCore(DynamicGraph(3), impl=impl)


def test_fallback_policy_stays_shell_incremental_and_exact(forced_policy):
    """A policy that always re-peels: mixed inserts/deletes down to an empty
    graph (shell 0) stay oracle-exact, each block one re-peel."""
    g = generators.barabasi_albert_varying(250, 4.0, seed=42)
    edges = g.edge_list()
    rng = np.random.default_rng(43)
    edges = edges[rng.permutation(len(edges))]
    dyn = DynamicGraph(g.n_nodes, width=4)
    inc = IncrementalCore(dyn, policy=forced_policy("repeel"))
    for start in range(0, len(edges), 48):
        inc.on_edge_block(dyn.add_edges(edges[start : start + 48]))
        np.testing.assert_array_equal(
            inc.core, core_numbers_host(dyn.snapshot())
        )
    # drain the graph: deletion blocks drive every node to shell 0
    while dyn.n_edges:
        live = dyn.snapshot().edge_list()
        inc.on_remove(dyn.remove_edges(live[:64]))
        np.testing.assert_array_equal(
            inc.core, core_numbers_host(dyn.snapshot())
        )
    assert inc.repeels == inc.repairs == inc.policy.decisions["repeel"] > 0
    assert inc.phase_report()["fallback"]["impl"] == "descend"
    assert not inc.core.any()  # everyone drifted to shell 0
    assert inc.resync() == 0


def test_shell_peel_widens_on_ceiling_hit(forced_policy):
    """A clique block on the periphery vaults its nodes past the repair
    window's ceiling: the descent through the device path must catch it,
    re-run in a wider window, and still land exact."""
    g = generators.barabasi_albert_varying(300, 5.0, seed=44)
    dyn = DynamicGraph(g.n_nodes, width=16)
    # margin0=1: the window hugs the block's levels, so a big jump hits
    inc = IncrementalCore(dyn, policy=forced_policy("descend"), margin0=1)
    inc.on_edge_block(dyn.add_edges(g.edge_list()))
    base = inc.core.copy()
    assert base.max() >= 6  # enough frozen levels above the periphery
    # clique a handful of periphery nodes: their level jumps far past hi
    low = np.argsort(base, kind="stable")[:8]
    assert base[low].max() <= 2
    block = np.array(
        [[low[i], low[j]] for i in range(8) for j in range(i + 1, 8)],
        np.int64,
    )
    attempts0 = inc.policy.decisions["descend"]
    inc.on_edge_block(dyn.add_edges(block))
    # one policy decision per window attempt: the window widened
    assert inc.policy.decisions["descend"] - attempts0 >= 2
    np.testing.assert_array_equal(inc.core, core_numbers_host(dyn.snapshot()))
    assert inc.resync() == 0


# ------------------------------------------------------ adaptive == exact


def test_adaptive_policy_never_changes_results(forced_policy):
    """Three maintainers (measured policy / always descend / always re-peel)
    driven with the same mixed stream agree with each other and the oracle at
    every step — the policy is cost-only."""
    g = generators.barabasi_albert_varying(180, 4.0, seed=45)
    edges = g.edge_list()
    rng = np.random.default_rng(46)
    edges = edges[rng.permutation(len(edges))]
    stacks = [
        (DynamicGraph(g.n_nodes, width=4), path)
        for path in (None, "descend", "repeel")
    ]
    incs = [
        IncrementalCore(d, policy=forced_policy(path)) for d, path in stacks
    ]
    live: list = []
    for step, start in enumerate(range(0, len(edges), 40)):
        block = edges[start : start + 40]
        accepted = [d.add_edges(block) for d, _ in stacks]
        for a in accepted[1:]:
            np.testing.assert_array_equal(accepted[0], a)
        for inc, a in zip(incs, accepted):
            inc.on_edge_block(a)
        live.extend(map(tuple, accepted[0]))
        if step % 2 and len(live) > 8:
            pick = rng.choice(len(live), size=6, replace=False)
            rm = np.array([live[i] for i in pick])
            for (d, _), inc in zip(stacks, incs):
                inc.on_remove(d.remove_edges(rm))
            gone = {tuple(e) for e in rm}
            live = [e for e in live if e not in gone]
        oracle = core_numbers_host(stacks[0][0].snapshot())
        for inc in incs:
            np.testing.assert_array_equal(inc.core, oracle)
    assert all(inc.resync() == 0 for inc in incs)
