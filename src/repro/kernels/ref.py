"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantics of record: kernels must match them (allclose) across
shape/dtype sweeps in interpret mode, and they double as the CPU execution
path (interpret-mode Pallas is a Python loop — fine for validation, wrong for
CPU benchmarking).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "sgns_loss_ref",
    "sgns_grads_ref",
    "ell_mean_ref",
    "h_index_ref",
    "decode_attention_ref",
    "topk_ref",
]


def _log_sigmoid(x):
    # stable: -softplus(-x)
    return -jax.nn.softplus(-x)


def sgns_loss_ref(center: jnp.ndarray, ctx: jnp.ndarray, neg: jnp.ndarray) -> jnp.ndarray:
    """SkipGram negative-sampling loss per example.

    center, ctx: (B, D); neg: (B, K, D). Returns (B,) float32.
    Logits accumulate in float32 regardless of input dtype.
    """
    c = center.astype(jnp.float32)
    x = ctx.astype(jnp.float32)
    n = neg.astype(jnp.float32)
    pos = jnp.sum(c * x, axis=-1)
    negl = jnp.einsum("bkd,bd->bk", n, c)
    return -(_log_sigmoid(pos) + jnp.sum(_log_sigmoid(-negl), axis=-1))


def sgns_grads_ref(center, ctx, neg, dout):
    """Analytic gradients of sum(sgns_loss * dout) wrt (center, ctx, neg)."""
    c = center.astype(jnp.float32)
    x = ctx.astype(jnp.float32)
    n = neg.astype(jnp.float32)
    d = dout.astype(jnp.float32)
    pos = jnp.sum(c * x, axis=-1)
    negl = jnp.einsum("bkd,bd->bk", n, c)
    dpos = (jax.nn.sigmoid(pos) - 1.0) * d  # (B,)
    dneg = jax.nn.sigmoid(negl) * d[:, None]  # (B, K)
    dcenter = dpos[:, None] * x + jnp.einsum("bk,bkd->bd", dneg, n)
    dctx = dpos[:, None] * c
    dnegs = dneg[:, :, None] * c[:, None, :]
    return (
        dcenter.astype(center.dtype),
        dctx.astype(ctx.dtype),
        dnegs.astype(neg.dtype),
    )


def ell_mean_ref(idx: jnp.ndarray, valid: jnp.ndarray, emb: jnp.ndarray) -> jnp.ndarray:
    """Masked neighbour mean over an ELL table.

    idx: (N, L) int32 rows into emb; valid: (N, L) bool; emb: (M, D).
    Rows with no valid neighbour return zeros.
    """
    gathered = emb[idx].astype(jnp.float32)  # (N, L, D)
    m = valid.astype(jnp.float32)[..., None]
    s = jnp.sum(gathered * m, axis=1)
    cnt = jnp.sum(m, axis=1)
    return (s / jnp.maximum(cnt, 1.0)).astype(emb.dtype)


def h_index_ref(values: jnp.ndarray, valid: jnp.ndarray,
                est: jnp.ndarray) -> jnp.ndarray:
    """Row-masked h-index repair sweep: ``min(est, H(row))``, by sorting.

    values: (R, W) neighbour estimates; valid: (R, W) bool; est: (R,) current
    row estimates. H = max h such that at least h valid entries are >= h.
    The sort-based formulation is the semantics of record; the Pallas kernel
    (``kernels.hindex``) computes the same quantity by binary-searched
    threshold counting and must match it exactly.
    """
    vals = jnp.where(valid, values.astype(jnp.int32), -1)
    svals = -jnp.sort(-vals, axis=-1)  # descending
    ranks = jnp.arange(1, vals.shape[-1] + 1, dtype=svals.dtype)
    ok = svals >= ranks
    h = jnp.max(jnp.where(ok, ranks, 0), axis=-1)
    return jnp.minimum(est.astype(jnp.int32), h)


def topk_ref(q: jnp.ndarray, table: jnp.ndarray, k: int,
             valid: jnp.ndarray = None, block_n: int = 512) -> tuple:
    """Dense top-k by dot-product score — the semantics of record.

    q: (Q, D); table: (N, D); valid: optional (N,) bool row mask. Returns
    ``(vals (Q, k) float32, idx (Q, k) int32)`` ordered by the total order
    (score desc, index asc) — ties always break toward the lower row index,
    which is what makes results exactly comparable across block sizes and
    shard counts. Missing candidates (k > #valid rows) pad with -inf / -1.

    Materialises the full (Q, N) score matrix; the Pallas kernel
    (``kernels.topk``) streams it blockwise and must match this exactly.
    Like the kernel, it scores ``block_n``-row tiles that all have one
    shape, so a row's score depends on its values alone and never on where
    the table is cut (its offset, a shard boundary).
    """
    q = q.astype(jnp.float32)
    N, D = table.shape
    n_blk = -(-N // block_n)
    tiles = jnp.pad(table.astype(jnp.float32), ((0, n_blk * block_n - N),
                                                (0, 0)))
    scores = jax.lax.map(
        lambda t: jnp.dot(q, t.T, precision=jax.lax.Precision.HIGHEST),
        tiles.reshape(n_blk, block_n, D),
    )  # (n_blk, Q, block_n)
    scores = jnp.moveaxis(scores, 0, 1).reshape(q.shape[0], -1)[:, :N]
    if valid is not None:
        scores = jnp.where(valid[None, :], scores, -jnp.inf)
    Q, N = scores.shape
    idx = jnp.broadcast_to(jnp.arange(N, dtype=jnp.int32)[None, :], (Q, N))
    neg, sidx = jax.lax.sort((-scores, idx), dimension=1, num_keys=2)
    kk = min(k, N)
    vals = -neg[:, :kk]
    sidx = jnp.where(vals > -jnp.inf, sidx[:, :kk], -1)
    if kk < k:
        vals = jnp.pad(vals, ((0, 0), (0, k - kk)),
                       constant_values=-jnp.inf)
        sidx = jnp.pad(sidx, ((0, 0), (0, k - kk)), constant_values=-1)
    return vals, sidx


def decode_attention_ref(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    cache_len: jnp.ndarray,
    *,
    softcap: float = 0.0,
    window: int = 0,
    k_scale=None,
    v_scale=None,
) -> jnp.ndarray:
    """Single-token GQA decode attention.

    q: (B, H, Dh) for the new token; k, v: (B, S, Hkv, Dh) cache (padded to S);
    cache_len: (B,) valid lengths. H = G * Hkv. Sliding ``window`` > 0 keeps
    only the last ``window`` positions; it may be a traced scalar (0 disables).
    Returns (B, H, Dh).
    """
    B, H, Dh = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    qf = q.astype(jnp.float32).reshape(B, Hkv, G, Dh)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    if k_scale is not None:  # int8 cache: dequantise with (B, S, Hkv) scales
        kf = kf * k_scale[..., None].astype(jnp.float32)
    if v_scale is not None:
        vf = vf * v_scale[..., None].astype(jnp.float32)
    logits = jnp.einsum("bhgd,bshd->bhgs", qf, kf) / jnp.sqrt(Dh).astype(jnp.float32)
    if softcap > 0.0:
        logits = softcap * jnp.tanh(logits / softcap)
    pos = jnp.arange(S)[None, :]
    mask = pos < cache_len[:, None]
    window = jnp.asarray(window)
    win_lo = jnp.where(window > 0, cache_len[:, None] - window, 0)
    mask = mask & (pos >= win_lo)
    logits = jnp.where(mask[:, None, None, :], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p, vf)
    return out.reshape(B, H, Dh).astype(q.dtype)
