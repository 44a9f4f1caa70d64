"""Distributed substrate: sharding rules, checkpoint, compression, watchdog,
data pipeline. Multi-device behaviours run in an 8-CPU-device subprocess so
the main test session keeps the default 1-device view."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.pipeline import PrefetchIterator, SyntheticLMData, pack_documents
from repro.distributed.compression import (
    ErrorFeedbackInt8,
    dequantize_int8,
    quantize_int8,
)
from repro.distributed.watchdog import HangWatchdog, StragglerMonitor

# ----------------------------------------------------------- compression --


def test_int8_quant_roundtrip_error_bounded():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(1000), jnp.float32)
    q, s = quantize_int8(x)
    err = np.abs(np.asarray(dequantize_int8(q, s) - x))
    assert err.max() <= float(s) / 2 + 1e-6


def test_error_feedback_telescopes():
    """With EF, the SUM of compressed grads tracks the sum of true grads —
    the residual carries over instead of accumulating."""
    rng = np.random.default_rng(1)
    comp = ErrorFeedbackInt8()
    g_true = {"w": jnp.zeros(64)}
    state = comp.init(g_true)
    total_true = np.zeros(64)
    total_comp = np.zeros(64)
    for step in range(50):
        g = {"w": jnp.asarray(rng.standard_normal(64) * 0.01, jnp.float32)}
        total_true += np.asarray(g["w"])
        out, state = comp.compress_decompress(g, state)
        total_comp += np.asarray(out["w"])
    # telescoping: |sum difference| = |final residual| <= one quantisation step
    resid = np.abs(total_true - total_comp)
    assert resid.max() < 1e-3, resid.max()


# -------------------------------------------------------------- watchdog --


def test_straggler_monitor_flags_slow_steps():
    t = [0.0]

    def clock():
        return t[0]

    mon = StragglerMonitor(threshold=2.0, clock=clock)
    for dt in [1.0, 1.0, 1.0, 5.0, 1.0]:
        mon.start_step()
        t[0] += dt
        mon.end_step()
    assert mon.slow_steps == [3]
    assert 0 < mon.straggler_fraction < 0.5


def test_hang_watchdog_fires_and_disarms():
    import time

    fired = []
    wd = HangWatchdog(0.05, lambda: fired.append(1))
    wd.arm()
    time.sleep(0.15)
    assert fired
    wd2 = HangWatchdog(0.2, lambda: fired.append(2))
    with wd2:
        wd2.pet()
    time.sleep(0.3)
    assert 2 not in fired  # disarmed on exit


# ------------------------------------------------------------------ data --


def test_synthetic_data_deterministic_restart():
    d = SyntheticLMData(vocab_size=100, batch=4, seq_len=16, seed=7)
    a = d.batch_at(12)
    b = d.batch_at(12)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    c = d.batch_at(13)
    assert not np.array_equal(a["tokens"], c["tokens"])
    # targets are next-token shifted
    full_a = d.batch_at(12)
    assert full_a["tokens"].shape == (4, 16)


def test_pack_documents_no_token_loss():
    docs = [[5, 6, 7], [8, 9], [10] * 7]
    toks, mask = pack_documents(docs, seq_len=8, eos_id=1)
    flat = toks[mask > 0]
    # all doc tokens present, in order, with EOS separators
    assert list(flat) == [5, 6, 7, 1, 8, 9, 1, 10, 10, 10, 10, 10, 10, 10, 1]


def test_prefetch_iterator_preserves_order_and_errors():
    it = PrefetchIterator(iter(range(10)), depth=3)
    assert list(it) == list(range(10))

    def boom():
        yield 1
        raise RuntimeError("boom")

    it2 = PrefetchIterator(boom())
    assert next(it2) == 1
    with pytest.raises(RuntimeError):
        next(it2)
        next(it2)


# -------------------------------------------- multi-device via subprocess --

MULTIDEV_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_mesh
    from repro.distributed.sharding import sharding_scope, constrain, named_sharding
    from repro.distributed.checkpoint import CheckpointManager

    assert jax.device_count() == 8, jax.device_count()

    # --- logical rules end-to-end: constrain inside jit on a (4,2) mesh ---
    mesh = make_mesh((4, 2), ("data", "model"))
    with jax.set_mesh(mesh), sharding_scope(mesh):
        x = jnp.arange(8 * 6, dtype=jnp.float32).reshape(8, 6)

        @jax.jit
        def f(x):
            return constrain(x * 2, "batch", "mlp")

        y = f(x)
        spec = y.sharding.spec
        assert spec == P("data", "model"), spec

        # divisibility fallback: dim 6 not divisible by model=2? it is; use 7
        z = jnp.zeros((8, 7))
        @jax.jit
        def g(z):
            return constrain(z + 1, "batch", "mlp")
        spec2 = g(z).sharding.spec
        # replicated fallback: trailing None may be omitted from the spec
        assert len(spec2) < 2 or spec2[1] is None, spec2

        # --- sharded checkpoint save -> restore on a DIFFERENT mesh ---
        sh = named_sharding((8, 6), ("batch", "mlp"))
        big = jax.device_put(jnp.arange(48, dtype=jnp.float32).reshape(8, 6), sh)
        tree = {"w": big, "step": jnp.asarray(3)}
        mgr = CheckpointManager(sys.argv[1], keep=2)
        mgr.save(100, tree)
        assert mgr.latest_step() == 100

    mesh2 = make_mesh((2, 4), ("data", "model"))
    with jax.set_mesh(mesh2), sharding_scope(mesh2):
        sh2 = {"w": named_sharding((8, 6), ("batch", None)),
               "step": named_sharding((), ())}
        target = {"w": jax.ShapeDtypeStruct((8, 6), jnp.float32),
                  "step": jax.ShapeDtypeStruct((), jnp.int32)}
        restored = mgr.restore(100, target, sh2)
        np.testing.assert_array_equal(
            np.asarray(restored["w"]), np.arange(48, dtype=np.float32).reshape(8, 6)
        )
        assert int(restored["step"]) == 3
        assert restored["w"].sharding.spec == P("data", None)

    # async save + retention
    with jax.set_mesh(mesh2), sharding_scope(mesh2):
        mgr.save(101, tree, blocking=False)
        mgr.wait()
        mgr.save(102, tree)
        assert mgr.all_steps() == [101, 102]  # keep=2 pruned step 100
    print("MULTIDEV_OK")
    """
)


def test_multidevice_sharding_and_checkpoint(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath("src")
    proc = subprocess.run(
        [sys.executable, "-c", MULTIDEV_SCRIPT, str(tmp_path / "ckpt")],
        capture_output=True,
        text=True,
        env=env,
        timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "MULTIDEV_OK" in proc.stdout


# ------------------------------------------------- torn checkpoint dirs --


def _ckpt_tree(v):
    return {"w": np.full((4, 3), float(v), np.float32),
            "step": np.asarray(v, np.int32)}


def _ckpt_target():
    return {"w": jax.ShapeDtypeStruct((4, 3), jnp.float32),
            "step": jax.ShapeDtypeStruct((), jnp.int32)}


def test_checkpoint_skips_torn_dirs_even_when_newest(tmp_path):
    """A crash can leave a ``step_*`` dir without ``_COMMITTED``, or — if it
    raced the rename — with the marker but a torn manifest. Neither may
    shadow an older committed step."""
    from repro.distributed.checkpoint import CheckpointManager

    mgr = CheckpointManager(str(tmp_path), keep=5)
    mgr.save(1, _ckpt_tree(1))
    mgr.save(2, _ckpt_tree(2))

    torn = tmp_path / "step_000000003"
    torn.mkdir()
    (torn / "leaf_00000_0000.npy").write_bytes(b"\x93NUMPY")
    assert mgr.all_steps() == [1, 2]

    torn2 = tmp_path / "step_000000004"
    torn2.mkdir()
    (torn2 / "manifest.json").write_text('{"step": 4, "leaves": [{"na')
    (torn2 / "_COMMITTED").write_text("ok")
    assert mgr.all_steps() == [1, 2] and mgr.latest_step() == 2

    step, tree = mgr.restore_latest(_ckpt_target())
    assert step == 2
    np.testing.assert_array_equal(np.asarray(tree["w"]), _ckpt_tree(2)["w"])


def test_checkpoint_restore_latest_falls_back_past_torn_shards(tmp_path):
    """A commit marker that raced the rename can cover missing shard files;
    ``restore_latest`` must fall back to the previous committed step instead
    of failing the restart."""
    from repro.distributed.checkpoint import CheckpointManager

    mgr = CheckpointManager(str(tmp_path), keep=5)
    mgr.save(1, _ckpt_tree(1))
    mgr.save(2, _ckpt_tree(2))
    # step 2 looks committed but a payload file is gone
    os.remove(tmp_path / "step_000000002" / "leaf_00000_0000.npy")

    step, tree = mgr.restore_latest(_ckpt_target())
    assert step == 1
    np.testing.assert_array_equal(np.asarray(tree["w"]), _ckpt_tree(1)["w"])
    assert int(tree["step"]) == 1

    os.remove(tmp_path / "step_000000001" / "leaf_00000_0000.npy")
    with pytest.raises(FileNotFoundError):
        mgr.restore_latest(_ckpt_target())
