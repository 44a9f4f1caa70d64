"""Shared test configuration and fixtures.

The XLA flag below MUST be set before the first ``import jax`` anywhere in
the process: the whole suite runs against 8 forced host-platform devices so
the multi-device sharding parity tests (``tests/multidevice/``, the shard
property test in ``tests/props/``) exercise real multi-device placement on
CPU-only machines. Single-device tests are unaffected — unsharded arrays
live on device 0 exactly as before.
"""
import os

_FLAG = "--xla_force_host_platform_device_count=8"
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " " + _FLAG
    ).strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def stream_case():
    """Factory for the serve-stack streaming tests' shared boilerplate.

    ``make(maker, seed=..., width=..., preload=False, **inc_kw)`` builds the
    graph, shuffles its edge list with a seeded rng, and returns
    ``(g, edges, dyn, inc)`` where ``dyn`` is a fresh ``DynamicGraph``
    (pre-loaded with every edge when ``preload=True``, empty otherwise) and
    ``inc`` an ``IncrementalCore`` over it with ``inc_kw`` forwarded.
    """
    from repro.serve import DynamicGraph, IncrementalCore

    def make(maker, *, seed=0, width=4, preload=False, shuffle=True,
             plan=None, **inc_kw):
        g = maker() if callable(maker) else maker
        edges = g.edge_list()
        if shuffle:
            rng = np.random.default_rng(seed)
            edges = edges[rng.permutation(len(edges))]
        dyn = DynamicGraph(
            g.n_nodes, edges if preload else None, width=width, plan=plan
        )
        inc = IncrementalCore(dyn, **inc_kw)
        return g, edges, dyn, inc

    return make


@pytest.fixture(scope="session")
def forced_policy():
    """Factory of repair-policy test doubles: ``forced_policy(path)`` is an
    ``IncrementalCore(policy=...)`` that sends every block repair down
    ``path`` (``"descend"`` or ``"repeel"``), counting its decisions; ``None``
    gives the measured policy itself."""
    from repro.serve.kcore_inc import RepairPolicy

    class Forced(RepairPolicy):
        def __init__(self, path):
            super().__init__()
            self.path = path

        def choose(self, *, cells, repeel_work):
            self.decisions[self.path] += 1
            return self.path

    return lambda path: RepairPolicy() if path is None else Forced(path)
