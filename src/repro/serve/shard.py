"""Row-sharding plan for the serving stack's node-indexed device state.

The paper's offline side already row-shards its embedding tables for the
web-scale configs (the ``deepwalk-web1b`` recipe: 2D tables split over the
``data`` mesh axis). ``ShardPlan`` brings the same placement to the online
stack: every *node-indexed* device array — the ``EmbeddingStore`` table, the
``DynamicGraph`` ELL mirror, and the candidate matrices of the fused h-index
descent — is laid out row-sharded over a 1D ``data`` mesh.

Sharding here is strictly a **placement** concern, never a semantics one:
the host-side state machines (slot assignment, LRU clocks, spill dicts,
core-repair control flow) are byte-identical across shard counts, and the
device programs are the same integer/float math partitioned by GSPMD. That
is what the multi-device parity suite (``tests/multidevice/``) proves:
``--shards N`` equals ``--shards 1`` bit-for-bit on every serve operation —
embeddings, core numbers, staleness, eviction counts.

A disabled plan (``n_shards == 1``) is inert: callers skip every plan hook
and run today's exact single-device code path.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.kernels import ops
from repro.launch.mesh import make_mesh
from repro.obs import metrics

__all__ = ["ShardPlan"]


@dataclasses.dataclass(frozen=True)
class ShardPlan:
    """Row-sharding of the node axis across a 1D mesh.

    Rows ``[0, n_rows)`` are split into ``n_shards`` contiguous chunks;
    shard ``s`` owns rows ``[s * chunk, (s + 1) * chunk)`` where
    ``chunk = n_rows / n_shards`` (callers pad row counts with
    ``pad_rows`` so the split is exact).
    """

    n_shards: int = 1
    axis: str = "data"
    mesh: Optional[Mesh] = None

    @staticmethod
    def build(n_shards: int = 1, axis: str = "data") -> "ShardPlan":
        """Build a plan over ``n_shards`` devices (1 = disabled, no mesh).

        Shard counts must be powers of two: the serve stack pads its row
        dimensions to powers of two (``pow2``), and a non-power-of-two split
        would force uneven shards XLA cannot place.

        Plans are cached per ``(n_shards, axis)``: every store/graph built
        for the same shard count shares one mesh and one compilation of
        each jit program below.
        """
        return _build_cached(int(n_shards), axis)

    # ----------------------------------------------------------- predicates

    @property
    def enabled(self) -> bool:
        return self.n_shards > 1 and self.mesh is not None

    # ------------------------------------------------------------ placement

    def pad_rows(self, n_rows: int) -> int:
        """Smallest row count >= ``n_rows`` divisible by ``n_shards``."""
        if not self.enabled:
            return int(n_rows)
        return -(-int(n_rows) // self.n_shards) * self.n_shards

    def row_sharding(self, ndim: int = 1) -> NamedSharding:
        """NamedSharding splitting axis 0, replicating the rest."""
        return NamedSharding(
            self.mesh, P(self.axis, *([None] * (max(ndim, 1) - 1)))
        )

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def place_rows(self, x) -> jnp.ndarray:
        """Upload ``x`` with axis 0 split across the mesh.

        Falls back to replicated placement when axis 0 does not divide (the
        caller forgot ``pad_rows``) — placement must never change results,
        but the memory win silently disappears, so the fallback warns.
        """
        x = jnp.asarray(x)
        if x.shape[0] % self.n_shards:
            warnings.warn(
                f"ShardPlan.place_rows: axis 0 ({x.shape[0]} rows) is not "
                f"divisible by n_shards={self.n_shards}; replicating instead "
                "of sharding (pad the row count with plan.pad_rows first)",
                stacklevel=2,
            )
            metrics().counter("shard_replicated_fallbacks_total").inc()
            return jax.device_put(x, self.replicated())
        metrics().counter("shard_row_placements_total").inc()
        return jax.device_put(x, self.row_sharding(x.ndim))

    def replicate(self, x) -> jnp.ndarray:
        return jax.device_put(jnp.asarray(x), self.replicated())

    # ----------------------------------------------------------- accounting

    def shard_of_rows(self, rows, n_rows: int) -> np.ndarray:
        """Owning shard of each row id under a ``n_rows``-row layout."""
        rows = np.asarray(rows, np.int64)
        if not self.enabled:
            return np.zeros(rows.shape, np.int64)
        chunk = max(self.pad_rows(n_rows) // self.n_shards, 1)
        return np.minimum(rows // chunk, self.n_shards - 1)

    def balance_of(self, rows, n_rows: int) -> np.ndarray:
        """(n_shards,) count of ``rows`` owned by each shard."""
        return np.bincount(
            self.shard_of_rows(rows, n_rows), minlength=max(self.n_shards, 1)
        )

    # ------------------------------------------------------- jit programs
    # cached per plan (not per store/graph instance) so twin stacks and
    # benchmark services share one XLA compilation of each program

    @functools.cached_property
    def gather_rows_fn(self):
        """jit: (row-sharded table, row ids) -> replicated gathered rows."""
        return jax.jit(lambda t, s: t[s], out_shardings=self.replicated())

    @functools.cached_property
    def set_rows_fn(self):
        """jit: scatter whole rows into a row-sharded rank-2 table."""
        return jax.jit(
            lambda t, s, v: t.at[s].set(v),
            out_shardings=self.row_sharding(2),
        )

    @functools.cached_property
    def set_cells_fn(self):
        """jit: scatter (row, col) cells into a row-sharded rank-2 table."""
        return jax.jit(
            lambda t, r, s, v: t.at[r, s].set(v),
            out_shardings=self.row_sharding(2),
        )

    @functools.cached_property
    def set_rows1_fn(self):
        """jit: scatter entries into a row-sharded rank-1 array."""
        return jax.jit(
            lambda t, r, v: t.at[r].set(v),
            out_shardings=self.row_sharding(1),
        )

    def ell_mean(self, idx, valid, table, *, impl: str):
        """``ops.ell_mean`` over a row-sharded ``table``, traced inside jit.

        GSPMD cannot partition a Pallas kernel. So the (B, L) neighbour rows
        are gathered first (a partitioned gather with a replicated result)
        and every device runs the kernel over that small block: the same
        rows in the same order as on one device, hence the same sums.
        """
        B, L = idx.shape
        rows = jax.lax.with_sharding_constraint(
            table[idx.reshape(-1)], self.replicated()
        )
        local = jnp.arange(B * L, dtype=jnp.int32).reshape(B, L)
        return jax.shard_map(
            functools.partial(ops.ell_mean, impl=impl), mesh=self.mesh,
            in_specs=(P(), P(), P()), out_specs=P(), check_vma=False,
        )(local, valid, rows)

    @functools.cached_property
    def partial_topk_fn(self):
        """jit: shard-local partial top-k over a row-sharded score table.

        ``(q (Q, D) replicated, table (rows, D) row-sharded, valid (rows,)
        row mask) -> ((S, Q, kl) values, (S, Q, kl) global row indices)``,
        kl = min(k, rows per shard). Each shard runs the single-device
        scorer (``ops.top_k_scores``: the Pallas kernel on TPU) on its own
        row chunk under the (score desc, index asc) total order, so every
        row is scored exactly as on one device — the (Q, rows) score matrix
        never crosses shards; only the (S, Q, kl) candidate lists do, and
        :meth:`merge_topk` stitches them on the host. Any global top-k row
        is necessarily in its owner's local top-k, so the stitch is exact.
        """
        def local(q, t, valid, *, k, impl):
            kl = min(k, t.shape[0])
            vals, idx = ops.top_k_scores(q, t, kl, valid=valid, impl=impl)
            off = jax.lax.axis_index(self.axis) * t.shape[0]
            return vals[None], jnp.where(idx >= 0, idx + off, -1)[None]

        def fn(q, table, valid, k, impl):
            return jax.shard_map(
                functools.partial(local, k=int(k), impl=impl),
                mesh=self.mesh,
                in_specs=(P(), P(self.axis, None), P(self.axis)),
                out_specs=(P(self.axis), P(self.axis)),
                check_vma=False,  # a Pallas kernel's outputs carry no vma
            )(q, table, valid)

        return jax.jit(
            fn, static_argnames=("k", "impl"),
            out_shardings=(self.replicated(), self.replicated()),
        )

    @staticmethod
    def merge_topk(vals, idx, k: int):
        """Host-side stitch of per-shard partial top-k candidate lists.

        vals, idx: (S, Q, kl) shard-local candidates (global row indices,
        -inf/-1 padded) -> ``((Q, k) float32, (Q, k) int64)`` under the
        global (score desc, index asc) order, -inf/-1 padded when fewer
        than k live candidates exist in total.
        """
        vals = np.asarray(vals, np.float32)
        idx = np.asarray(idx, np.int64)
        S, Q, kl = vals.shape
        v = np.swapaxes(vals, 0, 1).reshape(Q, S * kl)
        i = np.swapaxes(idx, 0, 1).reshape(Q, S * kl)
        ikey = np.where(i < 0, np.iinfo(np.int64).max, i)
        order = np.lexsort((ikey, -v), axis=-1)
        kk = min(k, S * kl)
        out_v = np.full((Q, k), -np.inf, np.float32)
        out_i = np.full((Q, k), -1, np.int64)
        out_v[:, :kk] = np.take_along_axis(v, order, 1)[:, :kk]
        out_i[:, :kk] = np.take_along_axis(i, order, 1)[:, :kk]
        out_i[~np.isfinite(out_v)] = -1
        return out_v, out_i


@functools.lru_cache(maxsize=None)
def _build_cached(n_shards: int, axis: str) -> ShardPlan:
    if n_shards <= 1:
        return ShardPlan()
    if n_shards & (n_shards - 1):
        raise ValueError(f"n_shards must be a power of two, got {n_shards}")
    avail = jax.device_count()
    if avail < n_shards:
        raise ValueError(
            f"ShardPlan needs {n_shards} devices but only {avail} are "
            "visible; on CPU set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n_shards}"
        )
    return ShardPlan(n_shards, axis, make_mesh((n_shards,), (axis,)))
