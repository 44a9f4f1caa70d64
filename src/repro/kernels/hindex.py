"""Row-masked h-index kernel — the repair sweep of the k-core fixpoint.

One repair sweep computes, per candidate row, ``min(est, H(row))`` where
``H(row)`` is the h-index of the row's neighbour core estimates (max h such
that at least h entries are >= h). The reference formulation sorts each row
(``kernels.ref.h_index_ref``); XLA sort is a comparator network and is the
wrong shape for both the TPU VPU and the CPU backend.

The kernel here never sorts. ``H`` bounded by ``est`` is the largest
``h <= est`` with ``count(row >= h) >= h``; ``count(row >= h)`` is
non-increasing in ``h``, so a branchless per-row **binary search** finds it in
``ceil(log2(W))`` masked count-reductions — each one compare + lane-sum over
the (rows, W) block resident in VMEM, an ideal VPU shape. Two equivalent
implementations share the search:

* ``h_index_count`` — pure jnp, jit-friendly (traces into ``lax.while_loop``
  bodies); the non-TPU execution path of ``ops.h_index_sweep`` and the
  operator inside the fused incremental-repair fixpoint.
* ``h_index_pallas`` — the Pallas kernel (same ref / ``pallas_interpret`` /
  tpu triple as ``ellmean``/``sgns``), gridded over row blocks. It takes the
  rows **transposed**, ``(W, R)``: candidate rows sit on the 128 lanes, so
  ``est``/``out`` are lane-dense ``(1, R)`` rows and each count is a
  reduction over sublanes (elementwise adds across vregs). Every block and
  value is 2-D, as the TPU's tiled layouts require.

Invalid lanes are encoded as ``-1`` (strictly below every threshold the
search probes), so padding the width costs nothing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# lanes (candidate rows) per grid step, and the VMEM bound on one vals block
MAX_BLOCK_R = 1024
BLOCK_BYTES = 2 << 20


def _bisect_h(vals: jnp.ndarray, est: jnp.ndarray, n_iters: int,
              axis: int) -> jnp.ndarray:
    """Shared branchless search: max h <= est with count(vals >= h) >= h.

    ``vals``: int32 with invalid lanes already set to -1, its neighbour
    entries along ``axis``; ``est``: the non-negative upper bound, shaped
    like ``vals`` reduced along ``axis`` with keepdims. The invariant is
    pred(lo) true / answer in [lo, hi]; pred(0) holds trivially, and the
    range halves every step, so ``n_iters = W.bit_length()`` pins the
    answer exactly.
    """
    lo = jnp.zeros_like(est)
    hi = jnp.minimum(est, vals.shape[axis])

    def body(_, lohi):
        lo, hi = lohi
        mid = jnp.right_shift(lo + hi + 1, 1)  # both halves are >= 0
        cnt = jnp.sum((vals >= mid).astype(jnp.int32), axis=axis,
                      keepdims=True)
        ok = cnt >= mid
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid - 1)

    lo, _ = jax.lax.fori_loop(0, n_iters, body, (lo, hi))
    return lo


def h_index_count(values: jnp.ndarray, valid: jnp.ndarray,
                  est: jnp.ndarray) -> jnp.ndarray:
    """``min(est, H(row))`` by counting — exact, sort-free, jit-friendly.

    values: (R, W) int; valid: (R, W) bool; est: (R,) int. Returns (R,) int32.
    """
    vals = jnp.where(valid, values.astype(jnp.int32), -1)
    est = jnp.maximum(est.astype(jnp.int32), 0)[:, None]
    n_iters = max(1, int(values.shape[-1]).bit_length())
    return _bisect_h(vals, est, n_iters, axis=-1)[:, 0]


def block_rows(R: int, W: int) -> int:
    """Rows per grid step for a ``(W, R)`` transposed block.

    A multiple of 128 lanes (or all of ``R`` when it is smaller) and at most
    :data:`MAX_BLOCK_R`, shrunk so one ``(W, rb)`` int32 block stays within
    :data:`BLOCK_BYTES` of VMEM.
    """
    if R <= 128:
        return R
    fit = BLOCK_BYTES // (4 * max(W, 1)) // 128 * 128
    return max(128, min(MAX_BLOCK_R, fit, -(-R // 128) * 128))


def _hindex_kernel(n_iters, vals_ref, est_ref, out_ref):
    # vals (W, RB) int32, invalid lanes = -1; est / out (1, RB) int32
    out_ref[...] = _bisect_h(vals_ref[...], est_ref[...], n_iters, axis=0)


@functools.partial(jax.jit, static_argnames=("block_r", "interpret"))
def h_index_pallas(vals_t, est, *, block_r: int, interpret: bool = False):
    """Blocked h-index search: out[i] = max h <= est[i] with cnt >= h.

    vals_t: (W, R) int32, row i's neighbour estimates in column i, invalid
    entries -1, W a multiple of 8; est: (1, R) int32 non-negative. R must
    divide into ``block_r`` blocks. Returns (1, R) int32.
    """
    W, R = vals_t.shape
    assert R % block_r == 0, f"rows {R} not divisible by block {block_r}"
    n_iters = max(1, int(W).bit_length())
    return pl.pallas_call(
        functools.partial(_hindex_kernel, n_iters),
        grid=(R // block_r,),
        in_specs=[
            pl.BlockSpec((W, block_r), lambda i: (0, i)),
            pl.BlockSpec((1, block_r), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_r), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, R), jnp.int32),
        interpret=interpret,
    )(vals_t, est)
