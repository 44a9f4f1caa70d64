"""Public jit'd wrappers around the Pallas kernels.

Dispatch contract: ``impl="auto"`` runs the Pallas kernel on TPU and the pure
jnp reference elsewhere (interpret-mode Pallas is a correctness tool, not a
CPU execution engine). Tests force ``impl="pallas_interpret"`` to validate the
kernels on this CPU-only container.

``sgns_loss`` carries a custom_vjp whose forward/backward are both single
fused kernels (recompute-in-backward: residuals are just the inputs).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import flash_decode as _fd
from . import hindex as _hx
from . import ref as _ref
from . import sgns as _sgns
from . import topk as _tk
from .ellmean import ell_mean_pallas

__all__ = [
    "sgns_loss",
    "ell_mean",
    "h_index_sweep",
    "decode_attention",
    "top_k_scores",
    "normalize_rows",
    "pad_dim",
]


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def pad_dim(x: jnp.ndarray, axis: int, multiple: int) -> jnp.ndarray:
    size = x.shape[axis]
    rem = size % multiple
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, multiple - rem)
    return jnp.pad(x, pad)


# ---------------------------------------------------------------- SGNS ----


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _sgns_loss_inner(center, ctx, neg, block_b, interpret):
    return _sgns.sgns_loss_fwd_pallas(
        center, ctx, neg, block_b=block_b, interpret=interpret
    )


def _sgns_fwd(center, ctx, neg, block_b, interpret):
    loss = _sgns.sgns_loss_fwd_pallas(
        center, ctx, neg, block_b=block_b, interpret=interpret
    )
    return loss, (center, ctx, neg)


def _sgns_bwd(block_b, interpret, res, dout):
    center, ctx, neg = res
    dc, dx, dn = _sgns.sgns_loss_bwd_pallas(
        center, ctx, neg, dout, block_b=block_b, interpret=interpret
    )
    return dc, dx, dn


_sgns_loss_inner.defvjp(_sgns_fwd, _sgns_bwd)


def sgns_loss(center, ctx, neg, *, impl: str = "auto", block_b: int = 256):
    """Per-example SGNS loss, differentiable wrt all three inputs.

    center, ctx: (B, D); neg: (B, K, D) -> (B,) float32.
    """
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "ref"
    if impl == "ref":
        return _ref.sgns_loss_ref(center, ctx, neg)
    interpret = impl == "pallas_interpret"
    B, D = center.shape
    # pad D to the lane width and B to the block size
    cp = pad_dim(center, 1, 128)
    xp = pad_dim(ctx, 1, 128)
    np_ = pad_dim(neg, 2, 128)
    bb = min(block_b, B) if B % min(block_b, B) == 0 else B
    while B % bb:
        bb //= 2
    return _sgns_loss_inner(cp, xp, np_, bb, interpret)


# ------------------------------------------------------------- ELL mean ----


def _left_pack(idx, valid, sentinel):
    """Stable-sort each row so valid entries come first; returns (idx, cnt)."""
    order = jnp.argsort(~valid, axis=1, stable=True)
    packed = jnp.take_along_axis(idx, order, axis=1)
    cnt = valid.sum(axis=1).astype(jnp.int32)
    packed = jnp.where(
        jnp.arange(idx.shape[1])[None, :] < cnt[:, None], packed, sentinel
    )
    return packed, cnt


def ell_mean(idx, valid, emb, *, impl: str = "auto"):
    """Masked neighbour mean: out[i] = mean over valid j of emb[idx[i, j]].

    idx: (N, L) int32; valid: (N, L) bool; emb: (M, D) -> (N, D).
    """
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "ref"
    if impl == "ref":
        return _ref.ell_mean_ref(idx, valid, emb)
    interpret = impl == "pallas_interpret"
    packed, cnt = _left_pack(idx, valid, emb.shape[0] - 1)
    embp = pad_dim(emb, 1, 128)
    out = ell_mean_pallas(packed, cnt, embp, interpret=interpret)
    return out[:, : emb.shape[1]]


# -------------------------------------------------------------- h-index ----


def h_index_sweep(values, valid, est, *, impl: str = "auto"):
    """One row-masked h-index repair sweep: ``min(est, H(row))``.

    values: (R, W) neighbour core estimates; valid: (R, W) bool; est: (R,)
    current row estimates -> (R,) int32. The shared operator of the offline
    core fixpoint (``core.kcore``) and the online block repair
    (``serve.kcore_inc``). ``impl``: "ref" (sort-based semantics of record),
    "count" (sort-free counting, the non-TPU default), "pallas" /
    "pallas_interpret" (the ``kernels.hindex`` kernel).
    """
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "count"
    if impl == "ref":
        return _ref.h_index_ref(values, valid, est)
    if impl == "count":
        return _hx.h_index_count(values, valid, est)
    interpret = impl == "pallas_interpret"
    R, W = values.shape
    # the kernel takes rows on lanes: (W, R), W padded to a sublane multiple
    # and R to the row block, both with -1 (never counted by any threshold)
    vals_t = jnp.where(valid, values.astype(jnp.int32), -1).T
    rb = _hx.block_rows(R, W)
    r_pad = -(-R // rb) * rb
    vals_t = jnp.pad(vals_t, ((0, -W % 8), (0, r_pad - R)), constant_values=-1)
    est_p = jnp.pad(jnp.maximum(est.astype(jnp.int32), 0), (0, r_pad - R))
    out = _hx.h_index_pallas(
        vals_t, est_p[None, :], block_r=rb, interpret=interpret
    )
    return out[0, :R]


# ----------------------------------------------------------------- top-k ----


def normalize_rows(x, *, eps: float = 1e-9):
    """L2-normalize rows in float32 (the cosine prep of the top-k scoring
    tile — ``link_scores`` and ``top_k_neighbors`` share this exact helper
    so service scores and kernel scores are the same numbers)."""
    x = x.astype(jnp.float32)
    n = jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True))
    return x / jnp.maximum(n, eps)


def _order_topk(vals, idx, k):
    """Sort candidate lanes by (score desc, index asc) and slice to k."""
    key = jnp.where(idx < 0, jnp.iinfo(jnp.int32).max, idx)
    neg, _, sidx = jax.lax.sort((-vals, key, idx), dimension=1, num_keys=2)
    return -neg[:, :k], sidx[:, :k]


def top_k_scores(q, table, k, *, valid=None, impl: str = "auto",
                 block_n: int = 512):
    """Per-query top-k candidate rows by dot-product score.

    q: (Q, D); table: (N, D); valid: optional (N,) bool row mask. Returns
    ``(vals (Q, k) float32, idx (Q, k) int32)`` ordered by (score desc,
    index asc); -inf / -1 pad when fewer than k valid candidates exist.
    Cosine retrieval = pass both sides through :func:`normalize_rows` first.

    The Pallas path streams the table in ``block_n``-row tiles with an
    on-chip running top-k (``kernels.topk``) — the (Q, N) score matrix is
    never materialised. k is a compile-time constant (the reduce unrolls k
    tournament rounds); keep it <= ~128.
    """
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "ref"
    if impl == "ref":
        return _ref.topk_ref(q, table, k, valid=valid)
    interpret = impl == "pallas_interpret"
    Q, D = q.shape
    N = table.shape[0]
    qp = pad_dim(pad_dim(q.astype(jnp.float32), 1, 128), 0, 8)
    tp = pad_dim(table.astype(jnp.float32), 1, 128)
    bias = (
        jnp.where(valid, 0.0, -jnp.inf)
        if valid is not None
        else jnp.zeros(N, jnp.float32)
    )
    # pad rows to the block multiple; padding rows are masked via the bias
    tp = pad_dim(tp, 0, 128)
    bn = min(block_n, tp.shape[0])
    tp = pad_dim(tp, 0, bn)
    bias = jnp.pad(bias, (0, tp.shape[0] - N), constant_values=-jnp.inf)
    vals, idx = _tk.topk_pallas(
        qp, tp, bias, k=int(k), block_n=bn, interpret=interpret
    )
    vals, idx = _order_topk(vals[:Q], idx[:Q], int(k))
    if k > vals.shape[1]:  # k exceeds the padded lane count: pad out
        vals = jnp.pad(vals, ((0, 0), (0, k - vals.shape[1])),
                       constant_values=-jnp.inf)
        idx = jnp.pad(idx, ((0, 0), (0, k - idx.shape[1])),
                      constant_values=-1)
    return vals, idx


# ------------------------------------------------------ decode attention ----


def decode_attention(
    q, k, v, cache_len, *, softcap: float = 0.0, window=0, impl: str = "auto",
    block_s: int = 512, k_scale=None, v_scale=None,
):
    """Single-token GQA decode attention over a padded KV cache.

    q: (B, H, Dh); k, v: (B, S, Hkv, Dh); cache_len: (B,) -> (B, H, Dh).
    ``window`` may be a python int or a traced scalar (0 = full attention) —
    the sliding bound reaches the kernel as data, so scanned per-layer windows
    (gemma2 local/global) share one compilation.
    """
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "ref"
    if impl == "ref":
        return _ref.decode_attention_ref(
            q, k, v, cache_len, softcap=softcap, window=window,
            k_scale=k_scale, v_scale=v_scale,
        )
    interpret = impl == "pallas_interpret"
    S = k.shape[1]
    bs = min(block_s, S)
    while S % bs:
        bs //= 2
    window = jnp.asarray(window)
    win_lo = jnp.where(window > 0, jnp.maximum(cache_len - window, 0), 0)
    win_lo = jnp.broadcast_to(win_lo, cache_len.shape).astype(jnp.int32)
    return _fd.decode_attention_pallas(
        q, k, v, cache_len, win_lo, softcap=softcap, block_s=bs,
        interpret=interpret, k_scale=k_scale, v_scale=v_scale,
    )
