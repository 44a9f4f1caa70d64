"""Neighbour-mean propagation kernel over ELL adjacency (paper §2.2).

One Jacobi sweep of the mean-embedding propagation is, per node, a gather of
its neighbours' embedding rows followed by a masked mean. The GPU/CPU-natural
formulation (materialise emb[idx] as an (N, L, D) tensor, then reduce) writes
the gathered tensor to HBM. The TPU-native formulation implemented here never
materialises it: neighbour indices are scalar-prefetched into SMEM, and the
kernel issues per-row HBM->VMEM DMAs (double-buffered) accumulating the mean
in a VMEM register block — the gather lives entirely in the memory hierarchy
(HBM -> VMEM -> VREG), which is exactly the adaptation DESIGN.md §3 calls out.

Grid: one program per block of ``ROWS`` (8) destination rows, the f32
sublane tile, so every output block is ``(8, D)`` as the TPU's tiling
requires. Within a program the rows run one after another, each with a
2-deep DMA pipeline across its neighbour loop.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROWS = 8


def _ell_mean_kernel(idx_ref, cnt_ref, emb_ref, out_ref, buf_ref, sem_ref):
    i = pl.program_id(0)
    D = out_ref.shape[1]
    for r in range(ROWS):
        row = i * ROWS + r
        cnt = cnt_ref[row]

        def dma(slot, j, row=row):
            return pltpu.make_async_copy(
                emb_ref.at[pl.ds(idx_ref[row, j], 1)], buf_ref.at[slot],
                sem_ref.at[slot],
            )

        # warm-up: start DMA for neighbour 0 into slot 0
        @pl.when(cnt > 0)
        def _():
            dma(0, 0).start()

        def body(j, acc, cnt=cnt, dma=dma):
            slot = jax.lax.rem(j, 2)
            nxt = jax.lax.rem(j + 1, 2)

            @pl.when(j + 1 < cnt)
            def _():
                dma(nxt, j + 1).start()

            dma(slot, j).wait()
            return acc + buf_ref[slot].astype(jnp.float32)

        acc = jax.lax.fori_loop(0, cnt, body, jnp.zeros((1, D), jnp.float32))
        denom = jnp.maximum(cnt.astype(jnp.float32), 1.0)
        out_ref[pl.ds(r, 1), :] = (acc / denom).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ell_mean_pallas(idx, cnt, emb, *, interpret=False):
    """Masked neighbour mean: out[i] = mean(emb[idx[i, :cnt[i]]]).

    idx: (N, L) int32 — valid entries must be left-packed (first cnt[i] slots);
    cnt: (N,) int32; emb: (M, D). Returns (N, D) in emb.dtype. N is padded
    to a multiple of ``ROWS`` here (padding rows have cnt 0).
    """
    N, L = idx.shape
    M, D = emb.shape
    n_pad = -N % ROWS
    idx = jnp.pad(idx, ((0, n_pad), (0, 0)))
    cnt = jnp.pad(cnt, (0, n_pad))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # idx, cnt
        grid=((N + n_pad) // ROWS,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],  # emb stays in HBM
        out_specs=pl.BlockSpec((ROWS, D), lambda i, *_: (i, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, 1, D), emb.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        _ell_mean_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N + n_pad, D), emb.dtype),
        interpret=interpret,
    )(idx, cnt, emb)[:N]
