"""The serving path's Pallas kernels compile for a TPU v5e.

Interpret mode (the other tests under ``tests/kernels``) checks what the
kernels compute; it cannot check that Mosaic accepts their block shapes and
layouts. Each case here compiles one kernel, at the widths the
``github-like`` service dispatches, for a described v5e chip that is not
attached, and asserts that the kernel survives as a ``tpu_custom_call``.
Nothing runs, so these say nothing of results or speed.

The topology is described inside a module-scoped fixture, never at import:
only one process may hold the TPU library, and pytest-xdist workers import
every test file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.serve import kcore_inc

D = 128  # embedding width (paper §3.1.2 / configs/deepwalk_web.py)
ROWS = 37701  # github-like store table rows (37,700 nodes + sentinel)
BATCH = 64  # query flush batch
# DynamicGraph ELL widths of the served graph: at build, and after the first
# compaction (pow2 of 1.5x the max degree)
ELL_WIDTHS = [16, 2048]
K_SGNS, B_SGNS = 5, 4096  # SGNSConfig negatives / batch
W_SMALL = kcore_inc._W_SMALL  # narrowest tier of the descent
W_HUB = 2048  # hub tier: pow2 of github-like's max degree (1,202)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    # compiles for a described chip are written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", saved)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def shape(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)


def _compile(fn, *args, **static):
    compiled = jax.jit(fn, static_argnames=tuple(static)).lower(
        *args, **static
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 0
    assert mem.temp_size_in_bytes >= 0
    return compiled


@pytest.mark.parametrize("rows,width", [
    (64, W_SMALL),  # smallest padded repair matrix, narrow tier
    (65536, W_SMALL),  # full-graph fallback descent, narrow tier
    (4096, W_HUB),  # full-graph fallback descent, hub tier
    (1, 128),  # a single row
], ids=["narrow-64", "narrow-65536", "hub-4096x2048", "one-row"])
def test_hindex_compiles(shape, rows, width):
    _compile(
        functools.partial(ops.h_index_sweep, impl="pallas"),
        shape((rows, width), jnp.int32),
        shape((rows, width), jnp.bool_),
        shape((rows,), jnp.int32),
    )


# (rows, width) of each tier of the fused descent's candidate matrix
DESCENT_TIERS = {
    "one": [(4096, 64)],
    # github-like's full-graph fallback: narrow tier + one hub tier
    "two": [(65536, W_SMALL), (4096, W_HUB)],
    # graph500-s14's whole graph on the ladder, at its largest degree (3,654)
    "ladder": [(16384, W_SMALL), (4096, 128), (512, 512), (128, 4096)],
}
DESCENT_EST = {"one": ROWS + 1, "two": ROWS + 1, "ladder": 12518}


@pytest.mark.parametrize("layout", list(DESCENT_TIERS))
def test_fused_descent_compiles(shape, layout):
    """The whole repair dispatch: the h-index kernel inside the fused
    ``lax.while_loop`` fixpoint, at the full-graph fallback's shapes."""
    i32 = jnp.int32
    tiers = tuple((shape((r, w), i32), shape((r, w), bool))
                  for r, w in DESCENT_TIERS[layout])
    vec = shape((sum(r for r, _ in DESCENT_TIERS[layout]),), i32)
    args = (tiers, vec, vec, vec, shape((DESCENT_EST[layout],), i32),
            shape((), i32), shape((), i32))
    compiled = kcore_inc._fused_descent.lower(
        *args, impl="pallas", max_sweeps=512).compile()
    assert compiled.as_text().count("tpu_custom_call") >= len(tiers)
    assert compiled.memory_analysis().argument_size_in_bytes > 0


@pytest.mark.parametrize("width", ELL_WIDTHS)
def test_ellmean_compiles(shape, width):
    _compile(
        functools.partial(ops.ell_mean, impl="pallas"),
        shape((BATCH, width), jnp.int32),
        shape((BATCH, width), jnp.bool_),
        shape((ROWS, D), jnp.float32),
    )


def test_topk_compiles(shape):
    def topk(q, table, valid):
        # top_k_neighbors asks for k + 1 = 11 (the query's own row)
        return ops.top_k_scores(q, table, 11, valid=valid, impl="pallas",
                                block_n=512)

    _compile(topk, shape((BATCH, D), jnp.float32),
             shape((ROWS, D), jnp.float32), shape((ROWS,), jnp.bool_))


def test_sgns_fwd_bwd_compile(shape):
    def loss(c, x, n):
        return ops.sgns_loss(c, x, n, impl="pallas").sum()

    args = (shape((B_SGNS, D), jnp.float32), shape((B_SGNS, D), jnp.float32),
            shape((B_SGNS, K_SGNS, D), jnp.float32))
    compiled = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), *args)
    # forward and backward are two kernels
    assert compiled.as_text().count("tpu_custom_call") >= 2


@pytest.fixture(scope="module")
def plan4(topo):
    """A four-chip ``ShardPlan`` over the described v5e:2x2 devices."""
    import numpy as np
    from jax.sharding import Mesh

    from repro.serve.shard import ShardPlan

    return ShardPlan(4, "data", Mesh(np.array(topo.devices[:4]), ("data",)))


def _per_device_args(compiled, one_chip_bytes):
    """The sharded program holds well under one chip's argument bytes."""
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().argument_size_in_bytes < one_chip_bytes


@pytest.mark.parametrize("width", ELL_WIDTHS)
def test_sharded_ellmean_compiles(plan4, width):
    """The fused flush's cold-start mean over a row-sharded table: Mosaic
    kernels cannot be partitioned, so ``ShardPlan.ell_mean`` gathers the
    rows and runs the kernel under ``shard_map``."""
    rows = plan4.pad_rows(ROWS)
    rep, row2 = plan4.replicated(), plan4.row_sharding(2)

    def cold(idx, valid, table):
        return plan4.ell_mean(idx, valid, table, impl="pallas")

    compiled = jax.jit(cold, out_shardings=rep).lower(
        jax.ShapeDtypeStruct((BATCH, width), jnp.int32, sharding=rep),
        jax.ShapeDtypeStruct((BATCH, width), jnp.bool_, sharding=rep),
        jax.ShapeDtypeStruct((rows, D), jnp.float32, sharding=row2),
    ).compile()
    _per_device_args(compiled, rows * D * 4)


def test_sharded_topk_compiles(plan4):
    rows = plan4.pad_rows(ROWS)
    rep = plan4.replicated()
    compiled = plan4.partial_topk_fn.lower(
        jax.ShapeDtypeStruct((BATCH, D), jnp.float32, sharding=rep),
        jax.ShapeDtypeStruct((rows, D), jnp.float32,
                             sharding=plan4.row_sharding(2)),
        jax.ShapeDtypeStruct((rows,), jnp.bool_,
                             sharding=plan4.row_sharding(1)),
        11, "pallas",
    ).compile()
    _per_device_args(compiled, rows * D * 4)
