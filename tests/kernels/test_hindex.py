"""Row-masked h-index kernel vs the sort-based oracle."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

CASES = [
    (1, 1),
    (3, 5),
    (8, 16),
    (17, 130),  # unaligned rows and lanes exercise both paddings
    (128, 256),
    (5, 300),
    (200, 7),
]


def _inputs(R, W, seed=0, max_val=25):
    rng = np.random.default_rng(seed)
    vals = jnp.asarray(rng.integers(0, max_val, (R, W)).astype(np.int32))
    valid = jnp.asarray(rng.random((R, W)) < 0.6)
    est = jnp.asarray(rng.integers(0, max_val + 5, R).astype(np.int32))
    return vals, valid, est


def _h_oracle(vals, valid, est):
    """Brute-force per-row h-index, independent of both implementations."""
    out = np.zeros(len(vals), np.int64)
    for i in range(len(vals)):
        row = np.sort(np.asarray(vals[i])[np.asarray(valid[i])])[::-1]
        h = 0
        for j, v in enumerate(row, start=1):
            if v >= j:
                h = j
        out[i] = min(h, int(est[i]))
    return out


@pytest.mark.parametrize("R,W", CASES)
@pytest.mark.parametrize("impl", ["count", "pallas_interpret"])
def test_h_index_matches_ref(R, W, impl):
    vals, valid, est = _inputs(R, W, seed=R * 31 + W)
    want = np.asarray(ref.h_index_ref(vals, valid, est))
    got = np.asarray(ops.h_index_sweep(vals, valid, est, impl=impl))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("seed", range(5))
def test_ref_matches_brute_force(seed):
    vals, valid, est = _inputs(13, 21, seed=seed)
    want = _h_oracle(vals, valid, est)
    got = np.asarray(ref.h_index_ref(vals, valid, est))
    np.testing.assert_array_equal(got, want)


def test_all_invalid_rows_are_zero():
    vals, valid, est = _inputs(6, 9, seed=3)
    valid = valid.at[2].set(False)
    for impl in ["ref", "count", "pallas_interpret"]:
        got = np.asarray(ops.h_index_sweep(vals, valid, est, impl=impl))
        assert got[2] == 0, impl


def test_est_caps_the_h_index():
    # a row of large values has H = W; est must clip it
    vals = jnp.full((4, 16), 100, jnp.int32)
    valid = jnp.ones((4, 16), bool)
    est = jnp.asarray([0, 3, 16, 99], jnp.int32)
    for impl in ["ref", "count", "pallas_interpret"]:
        got = np.asarray(ops.h_index_sweep(vals, valid, est, impl=impl))
        np.testing.assert_array_equal(got, [0, 3, 16, 16], impl)


def test_h_index_bounds():
    vals, valid, est = _inputs(32, 40, seed=9)
    got = np.asarray(ops.h_index_sweep(vals, valid, est, impl="count"))
    assert np.all(got >= 0)
    assert np.all(got <= np.asarray(est))
    assert np.all(got <= np.asarray(valid).sum(axis=1))


@pytest.mark.parametrize("tpu,want", [(True, "pallas"), (False, "count")])
def test_auto_impl_is_the_backends_kernel(monkeypatch, tpu, want):
    """``impl="auto"`` is resolved in one place for every caller: the
    Pallas kernel on TPU, the counting search elsewhere."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: tpu)
    assert ops.h_index_impl() == want
    if not tpu:
        vals, valid, est = _inputs(17, 130, seed=3)
        np.testing.assert_array_equal(
            ops.h_index_sweep(vals, valid, est, impl="auto"),
            ops.h_index_sweep(vals, valid, est, impl="count"))
