"""Blockwise score+reduce top-k Pallas kernel — the retrieval hot spot.

Nearest-neighbour retrieval over the serving store is one (Q, D) query block
against an (N, D) embedding table. Materialising the (Q, N) score matrix is
what kills scaling — at N in the millions it is gigabytes of HBM traffic per
batch. This kernel reuses the ``flash_decode`` streaming-tile idiom: the
table is streamed through VMEM in BN-row blocks, each block's scores are
reduced **on-chip** into a running per-query top-k accumulator (a (Q, K)
value/index pair in VMEM scratch), and nothing but the final (Q, K) result
is ever written back. HBM traffic is exactly one pass over the table.

Per grid step ``s`` (sequential over table blocks, like the decode kernel's
cache axis):

1. ``scores = q @ block.T + bias`` — one MXU matmul; ``bias`` carries row
   validity (0 for live rows, -inf for dead/padding rows), so masking costs
   an add, not a gather;
2. k rounds of extract-max / replace-worst tournament against the running
   accumulator. Each round pulls the block's best remaining candidate
   (the lowest column at the max, so ties break toward the lower index)
   and replaces the accumulator's worst entry when the candidate wins under
   the total order (score desc, index asc). A candidate that loses implies
   every remaining one loses too, so correctness needs no early exit.

The accumulator keeps at most k live lanes (lanes past k are pinned to +inf
so the worst-entry argmin never lands on them), and the output is *unsorted*
— the ``ops.top_k_scores`` wrapper does one (Q, K)-sized lexicographic sort
at the end, which is noise next to the streamed reduction.

Forward-only by design: retrieval needs no gradients.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_N = 512
NEG_INF = float("-inf")
IDX_PAD = jnp.iinfo(jnp.int32).max


def _topk_kernel(q_ref, t_ref, b_ref, ov_ref, oi_ref, vals_ref, idx_ref,
                 *, k, block_n):
    s = pl.program_id(0)
    n_s = pl.num_programs(0)
    Q = q_ref.shape[0]
    Kp = vals_ref.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (Q, Kp), 1)

    @pl.when(s == 0)
    def _():
        # lanes < k are live (start at -inf, any real candidate beats them);
        # lanes >= k are pinned to +inf so the worst-entry argmin below can
        # never select them
        vals_ref[...] = jnp.where(lane < k, NEG_INF, jnp.inf)
        idx_ref[...] = jnp.full((Q, Kp), IDX_PAD, jnp.int32)

    q = q_ref[...]  # (Q, D)
    t = t_ref[...]  # (BN, D)
    scores = jax.lax.dot_general(
        q, t, (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    ) + b_ref[...]  # (Q, BN); bias = -inf on dead/padding rows
    col = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)

    for _ in range(k):
        # block's best remaining candidate: the lowest column at the max, a
        # min over an int iota (Mosaic lowers no argmax over bool/int). On
        # ties the lower local, hence global, index wins (blocks stream in
        # ascending row order), matching the (score desc, index asc) order
        c_val = jnp.max(scores, axis=1, keepdims=True)  # (Q, 1)
        c_arg = jnp.min(jnp.where(scores == c_val, col, block_n), axis=1,
                        keepdims=True)  # (Q, 1)
        c_idx = s * block_n + c_arg
        scores = jnp.where(col == c_arg, NEG_INF, scores)

        # accumulator's worst entry: min value, ties -> largest index
        vals = vals_ref[...]
        idx = idx_ref[...]
        w_val = jnp.min(vals, axis=1, keepdims=True)  # (Q, 1)
        at_w = vals == w_val
        w_idx = jnp.max(jnp.where(at_w, idx, -1), axis=1, keepdims=True)
        w_pos = jnp.min(jnp.where(at_w & (idx == w_idx), lane, Kp), axis=1,
                        keepdims=True)  # (Q, 1)

        better = (c_val > w_val) | ((c_val == w_val) & (c_idx < w_idx))
        better = better & (c_val > NEG_INF)  # masked lanes never enter
        write = better & (lane == w_pos)
        vals_ref[...] = jnp.where(write, c_val, vals)
        idx_ref[...] = jnp.where(write, c_idx, idx)

    @pl.when(s == n_s - 1)
    def _():
        vals = vals_ref[...]
        filled = (lane < k) & (vals > NEG_INF)
        ov_ref[...] = jnp.where(filled, vals, NEG_INF)
        oi_ref[...] = jnp.where(filled, idx_ref[...], -1)


@functools.partial(
    jax.jit, static_argnames=("k", "block_n", "interpret")
)
def topk_pallas(q, table, bias, *, k, block_n=DEFAULT_BLOCK_N,
                interpret=False):
    """q: (Q, D); table: (N, D); bias: (N,) 0/-inf validity -> ((Q, Kp)
    float32 scores, (Q, Kp) int32 row indices), **unsorted**, -inf/-1 on
    unfilled lanes. Kp = k padded to the lane width; the caller sorts and
    slices. Q, D, N must already be padded (sublane/lane/block multiples).
    """
    Q, D = q.shape
    N = table.shape[0]
    bn = min(block_n, N)
    assert N % bn == 0, f"table rows {N} not divisible by block {bn}"
    Kp = -(-max(k, 1) // 128) * 128

    kernel = functools.partial(_topk_kernel, k=k, block_n=bn)
    return pl.pallas_call(
        kernel,
        grid=(N // bn,),
        in_specs=[
            pl.BlockSpec((Q, D), lambda s: (0, 0)),
            pl.BlockSpec((bn, D), lambda s: (s, 0)),
            pl.BlockSpec((1, bn), lambda s: (0, s)),
        ],
        out_specs=[
            pl.BlockSpec((Q, Kp), lambda s: (0, 0)),
            pl.BlockSpec((Q, Kp), lambda s: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Q, Kp), jnp.float32),
            jax.ShapeDtypeStruct((Q, Kp), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((Q, Kp), jnp.float32),
            pltpu.VMEM((Q, Kp), jnp.int32),
        ],
        interpret=interpret,
    )(q.astype(jnp.float32), table.astype(jnp.float32),
      bias.astype(jnp.float32).reshape(1, N))
