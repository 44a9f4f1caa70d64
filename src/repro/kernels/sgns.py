"""Fused SGNS loss/grad Pallas kernels — the paper's compute hot spot.

The SkipGram-negative-sampling inner loop is, per example, one positive dot
product, K negative dot products, K+1 sigmoids, and rank-1 gradient updates.
Done naively (gather -> einsum -> sigmoid -> three einsums) XLA materialises
the (B, K) logits and (B, K, D) gradient tensors in HBM several times. The
kernels here keep the whole per-block working set — center/context blocks
(BB, D), negatives (BB, K, D), logits (BB, K) — resident in VMEM and emit
loss (fwd) or all three gradients (bwd) in a single pass.

TPU adaptation notes (vs the paper's gensim/CPU hogwild):
  * D is padded to a multiple of 128 (lane width) by the ops.py wrapper.
  * Logits accumulate in fp32; inputs may be bf16 (MXU-friendly).
  * The batch is blocked at BB=256 rows by default — working set at
    K=5, D=256, bf16 is ~(2+5)*256*256*2B + logits = ~1 MB, far under VMEM.
  * Every block is 2-D, as the TPU's tiled layouts require: negatives enter
    as ``(B, K*D)`` (a free reshape) and the kernels take negative ``k`` as
    the lane-aligned column slice ``[k*D, (k+1)*D)``; the per-example loss
    and its cotangent travel as ``(B, 1)`` columns. The K negative logits
    are K row-wise dot products on the VPU — no batched ``dot_general``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_B = 256


def _neg_logits(c, neg_ref, K, D):
    """K per-row dot products: [(BB, 1)] * K, negative k = lanes [kD, kD+D)."""
    return [
        jnp.sum(neg_ref[:, k * D:(k + 1) * D].astype(jnp.float32) * c,
                axis=-1, keepdims=True)
        for k in range(K)
    ]


def _fwd_kernel(K, center_ref, ctx_ref, neg_ref, loss_ref):
    D = center_ref.shape[1]
    c = center_ref[...].astype(jnp.float32)  # (BB, D)
    x = ctx_ref[...].astype(jnp.float32)  # (BB, D)
    loss = jax.nn.softplus(-jnp.sum(c * x, axis=-1, keepdims=True))  # (BB, 1)
    for negl in _neg_logits(c, neg_ref, K, D):
        loss = loss + jax.nn.softplus(negl)
    loss_ref[...] = loss.astype(loss_ref.dtype)


def _bwd_kernel(K, center_ref, ctx_ref, neg_ref, dout_ref, dc_ref, dx_ref,
                dn_ref):
    D = center_ref.shape[1]
    c = center_ref[...].astype(jnp.float32)
    x = ctx_ref[...].astype(jnp.float32)
    d = dout_ref[...].astype(jnp.float32)  # (BB, 1)
    pos = jnp.sum(c * x, axis=-1, keepdims=True)
    dpos = (jax.nn.sigmoid(pos) - 1.0) * d  # (BB, 1)
    # dcenter = dpos * ctx + sum_k dneg_k * neg_k;  dneg_k-row = dneg_k * c
    dc = dpos * x
    for k, negl in enumerate(_neg_logits(c, neg_ref, K, D)):
        dneg = jax.nn.sigmoid(negl) * d  # (BB, 1)
        dc = dc + dneg * neg_ref[:, k * D:(k + 1) * D].astype(jnp.float32)
        dn_ref[:, k * D:(k + 1) * D] = (dneg * c).astype(dn_ref.dtype)
    dc_ref[...] = dc.astype(dc_ref.dtype)
    dx_ref[...] = (dpos * c).astype(dx_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def sgns_loss_fwd_pallas(center, ctx, neg, *, block_b=DEFAULT_BLOCK_B, interpret=False):
    """center, ctx: (B, D); neg: (B, K, D); D a lane multiple -> (B,) f32."""
    B, D = center.shape
    K = neg.shape[1]
    bb = min(block_b, B)
    assert B % bb == 0, f"batch {B} not divisible by block {bb}"
    loss = pl.pallas_call(
        functools.partial(_fwd_kernel, K),
        grid=(B // bb,),
        in_specs=[
            pl.BlockSpec((bb, D), lambda i: (i, 0)),
            pl.BlockSpec((bb, D), lambda i: (i, 0)),
            pl.BlockSpec((bb, K * D), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bb, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 1), jnp.float32),
        interpret=interpret,
    )(center, ctx, neg.reshape(B, K * D))
    return loss[:, 0]


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def sgns_loss_bwd_pallas(center, ctx, neg, dout, *, block_b=DEFAULT_BLOCK_B, interpret=False):
    """Gradients of ``sum(dout * loss)`` wrt (center, ctx, neg)."""
    B, D = center.shape
    K = neg.shape[1]
    bb = min(block_b, B)
    assert B % bb == 0
    dc, dx, dn = pl.pallas_call(
        functools.partial(_bwd_kernel, K),
        grid=(B // bb,),
        in_specs=[
            pl.BlockSpec((bb, D), lambda i: (i, 0)),
            pl.BlockSpec((bb, D), lambda i: (i, 0)),
            pl.BlockSpec((bb, K * D), lambda i: (i, 0)),
            pl.BlockSpec((bb, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bb, D), lambda i: (i, 0)),
            pl.BlockSpec((bb, D), lambda i: (i, 0)),
            pl.BlockSpec((bb, K * D), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, D), center.dtype),
            jax.ShapeDtypeStruct((B, D), ctx.dtype),
            jax.ShapeDtypeStruct((B, K * D), neg.dtype),
        ],
        interpret=interpret,
    )(center, ctx, neg.reshape(B, K * D), dout.reshape(B, 1))
    return dc, dx, dn.reshape(B, K, D)
