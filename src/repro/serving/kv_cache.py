"""Device-side paged KV cache in the FlowKV block-major layout.

The pool is ONE array ``(num_blocks, L, 2, KV, block_size, head_dim)``
(paper Eq. 5, pages stored kv-head-major as the kernels read them; see
``core/layout.py``) so a request's KV for all layers lives in its blocks
contiguously — the transfer engine moves whole block ranges with single
calls. The control plane (which blocks belong to whom) is
``core.block_manager.BlockManager``.

``write_prefill`` / ``gather_dense`` / ``append_token`` bridge between the
model's dense cache format (L, S, KV, hd) and pages. At serving time the
decode plane does NOT use the bridge: ``models/transformer.decode_step_paged``
reads pages in place through ``kernels/paged_attention`` and appends the
batch's new K/V with one fused in-place update (``export_block_tables`` /
``append_tokens`` are its host-side ports). The dense bridge here is the
reference data path — the oracle the paged step is tested against.

``num_pool_dispatches`` counts host-issued device ops against the pool
(dense bridge calls + fused imports/appends); the decode benchmark reads it
to show the O(batch) -> O(1) collapse.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.block_manager import BlockManager
from repro.core.layout import KVCacheSpec, KVLayout, alloc_cache
from repro.models.common import ModelConfig


def spec_for_model(cfg: ModelConfig, num_blocks: int,
                   layout: KVLayout = KVLayout.FLOWKV) -> KVCacheSpec:
    return KVCacheSpec(
        num_layers=cfg.num_attention_layers() or cfg.num_layers,
        num_blocks=num_blocks,
        block_size=cfg.block_size,
        num_kv_heads=cfg.num_kv_heads,
        head_dim=cfg.head_dim,
        dtype=cfg.dtype,
        layout=layout,
    )


class PagedKVCache:
    """One node's paged pool + block manager.

    ``bm`` shares an existing BlockManager instead of owning one: the
    sharded cache below keeps ONE control plane (global page ids) over
    ``tp`` per-shard pools, so every shard's PagedKVCache is built around
    the same manager.
    """

    def __init__(self, spec: KVCacheSpec, allocator: str = "flowkv",
                 bm: Optional[BlockManager] = None):
        self.spec = spec
        self.pool = alloc_cache(spec)
        self.bm = bm if bm is not None else BlockManager(
            spec.num_blocks, spec.block_size, allocator)
        self.num_pool_dispatches = 0     # host-issued device ops on the pool

    # -- write path -------------------------------------------------------------
    def write_prefill(self, request_id: int, k: jax.Array, v: jax.Array,
                      length: int, start: int = 0) -> List[int]:
        """Store a request's prefill KV. k/v: (L, S, KV, hd), S >= length.

        Blocks must already be allocated (scheduler does it at admission).
        K and V land in ONE pool update (whole blocks, all layers), not one
        per cache half.

        ``start`` (block-aligned) writes a SUFFIX: k/v cover tokens
        ``start..start+length`` and land in the table's blocks after the
        shared prefix — a prefix-cache hit writes only the tokens it
        actually computed, never touching the shared (read-only) blocks.
        """
        spec = self.spec
        assert start % spec.block_size == 0, "suffix writes are block-aligned"
        first = start // spec.block_size
        blocks = self.bm.get(request_id)[first:]
        nb = spec.blocks_for_tokens(length)
        assert nb <= len(blocks), (nb, len(blocks))
        pad = nb * spec.block_size - length
        k = k[:, :length]
        v = v[:, :length]
        if pad:
            k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        # (L, S, KV, hd) x2 -> (nb, L, 2, KV, bs, hd)
        kv = jnp.stack([k, v], axis=1).reshape(
            spec.num_layers, 2, nb, spec.block_size, spec.num_kv_heads,
            spec.head_dim).transpose(2, 0, 1, 4, 3, 5).astype(spec.dtype)
        idx = jnp.asarray(blocks[:nb], jnp.int32)
        self.pool = self.pool.at[idx].set(kv)
        self.num_pool_dispatches += 1
        return blocks[:nb]

    def append_token(self, request_id: int, k_new: jax.Array, v_new: jax.Array,
                     position: int) -> None:
        """Write one token's K/V (L, KV, hd) at absolute position.

        Reference path only — one pool rewrite PER REQUEST per step. The
        serving decode plane appends the whole batch in one fused dispatch
        (:meth:`append_tokens` / ``kv_append_tokens``).
        """
        spec = self.spec
        blocks = self.bm.get(request_id)
        block = blocks[position // spec.block_size]
        slot = position % spec.block_size
        kv = jnp.stack([k_new, v_new], axis=1).astype(spec.dtype)  # (L, 2, KV, hd)
        self.pool = self.pool.at[block, :, :, :, slot].set(kv)
        self.num_pool_dispatches += 1

    def append_tokens(self, request_ids: Sequence[int], k_new: jax.Array,
                      v_new: jax.Array, positions: Sequence[int]) -> None:
        """Fused batch append: every request's token in ONE dispatch.

        k_new / v_new (L, B, KV, hd); positions are absolute token indices.
        """
        from repro.kernels.kv_gather import kv_append_tokens

        tables = self.export_block_tables(request_ids)
        pos = jnp.asarray(list(positions), jnp.int32)
        self.pool = kv_append_tokens(self.pool, jnp.asarray(tables), pos,
                                     k_new, v_new)
        self.num_pool_dispatches += 1

    # -- read path ---------------------------------------------------------------
    def export_block_tables(self, request_ids: Sequence[int]) -> np.ndarray:
        """Padded (B, W) int32 block table for a batch of requests, W = the
        longest table. Rows shorter than W are zero-padded; the paged kernel
        masks them by length, and the fused append never addresses them.
        """
        tables = [self.bm.get(rid) for rid in request_ids]
        w = max((len(t) for t in tables), default=1)
        out = np.zeros((len(tables), max(1, w)), np.int32)
        for i, t in enumerate(tables):
            out[i, :len(t)] = t
        return out

    def gather_prefix(self, request_id: int, length: int
                      ) -> Tuple[jax.Array, jax.Array]:
        """Dense K/V of a request's first ``length`` tokens — reads ONLY the
        blocks holding them (the shared prefix of a cache hit), so fresh
        suffix blocks full of garbage are never touched."""
        nb = self.spec.blocks_for_tokens(length)
        with jax.named_scope("prefix_gather"):
            return self.gather_dense(request_id, length, num_blocks=nb)

    def gather_dense(self, request_id: int, max_len: int,
                     num_blocks: Optional[int] = None
                     ) -> Tuple[jax.Array, jax.Array]:
        """Rebuild (L, max_len, KV, hd) dense K/V from pages (reference path)."""
        spec = self.spec
        blocks = self.bm.get(request_id)
        if num_blocks is not None:
            blocks = blocks[:num_blocks]
        idx = jnp.asarray(blocks, jnp.int32)
        pages = jnp.take(self.pool, idx, axis=0)          # (nb, L, 2, KV, bs, hd)
        self.num_pool_dispatches += 1
        shape = (spec.num_layers, -1, spec.num_kv_heads, spec.head_dim)
        k = pages[:, :, 0].transpose(1, 0, 3, 2, 4).reshape(shape)
        v = pages[:, :, 1].transpose(1, 0, 3, 2, 4).reshape(shape)
        cur = k.shape[1]
        if cur < max_len:
            k = jnp.pad(k, ((0, 0), (0, max_len - cur), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, max_len - cur), (0, 0), (0, 0)))
        return k[:, :max_len], v[:, :max_len]

    # -- transfer path -----------------------------------------------------------
    def import_plan(self, engine, plan, src_pool: jax.Array) -> None:
        """Land one transfer plan in this pool as ONE fused dispatch.

        Replaces per-page copies: the engine lowers the plan to its descriptor
        table and the whole table executes in a single jitted Pallas call,
        updating the pool in place (donated where the backend allows).
        """
        self.pool = engine.execute(plan, src_pool, self.pool)
        self.num_pool_dispatches += 1

    # -- capacity / bookkeeping -----------------------------------------------------
    @property
    def utilization(self) -> float:
        return self.bm.utilization

    def free(self, request_id: int) -> None:
        self.bm.free(request_id)

    def check_invariants(self) -> None:
        self.bm.check_invariants()


class ShardedKVCache:
    """``tp`` per-shard pools over ONE block manager (mesh-parallel pool).

    Shard ``s`` holds the FLOWKV pool for its contiguous kv-head slice —
    same ``(num_blocks, L, 2, ·, block_size, head_dim)`` geometry with
    ``num_kv_heads/tp`` heads on axis 3. Page ids are GLOBAL: one BlockManager
    allocates for all shards (a request's block i is block i in every
    shard's pool), which is what lets a cross-degree transfer plan address
    both sides with one descriptor table (core/transfer.ShardedTransferEngine)
    and keeps the leak/invariant audit a single-control-plane problem.

    The dense bridge (write/gather) presents FULL-width K/V to callers and
    slices/concats on the kv-head axis at the boundary, so the engine's
    prefill, spill and prefix-reuse paths are shard-agnostic.

    ``num_pool_dispatches`` counts host-issued device ops, matching
    PagedKVCache semantics per ROLE not per shard (one fused decode step is
    one dispatch from the host even though it touches ``tp`` pools — on a
    real mesh those are the same launch). ``shard_dispatches`` counts the
    per-(src_shard, dst_shard)-pair fused transfer dispatches landed here.
    """

    def __init__(self, spec: KVCacheSpec, tp: int, allocator: str = "flowkv"):
        from repro.core.transfer import ShardSpec, shard_slice_spec

        self.spec = spec                       # FULL-width spec
        self.tp = tp
        self.shard_spec = ShardSpec(tp, spec.num_kv_heads)
        self.bm = BlockManager(spec.num_blocks, spec.block_size, allocator)
        self.shards = [
            PagedKVCache(shard_slice_spec(spec, self.shard_spec), allocator,
                         bm=self.bm)
            for _ in range(tp)]
        self.num_pool_dispatches = 0
        self.shard_dispatches = 0              # per-shard-pair transfer lands

    @property
    def pools(self) -> List[jax.Array]:
        return [s.pool for s in self.shards]

    def _head_slices(self, arr: jax.Array, axis: int) -> List[jax.Array]:
        width = arr.shape[axis] // self.tp
        return [jax.lax.slice_in_dim(arr, s * width, (s + 1) * width,
                                     axis=axis)
                for s in range(self.tp)]

    # -- write path -------------------------------------------------------------
    def write_prefill(self, request_id: int, k: jax.Array, v: jax.Array,
                      length: int, start: int = 0) -> List[int]:
        """Full-width (L, S, KV, hd) K/V: each shard writes its head slice."""
        ks, vs = self._head_slices(k, 2), self._head_slices(v, 2)
        blocks: List[int] = []
        for shard, k_s, v_s in zip(self.shards, ks, vs):
            blocks = shard.write_prefill(request_id, k_s, v_s, length,
                                         start=start)
        self.num_pool_dispatches += 1
        return blocks

    def append_token(self, request_id: int, k_new: jax.Array,
                     v_new: jax.Array, position: int) -> None:
        for shard, k_s, v_s in zip(self.shards,
                                   self._head_slices(k_new, 1),
                                   self._head_slices(v_new, 1)):
            shard.append_token(request_id, k_s, v_s, position)
        self.num_pool_dispatches += 1

    def append_tokens(self, request_ids: Sequence[int], k_new: jax.Array,
                      v_new: jax.Array, positions: Sequence[int]) -> None:
        for shard, k_s, v_s in zip(self.shards,
                                   self._head_slices(k_new, 2),
                                   self._head_slices(v_new, 2)):
            shard.append_tokens(request_ids, k_s, v_s, positions)
        self.num_pool_dispatches += 1

    # -- read path ---------------------------------------------------------------
    def export_block_tables(self, request_ids: Sequence[int]) -> np.ndarray:
        return self.shards[0].export_block_tables(request_ids)

    def gather_prefix(self, request_id: int, length: int
                      ) -> Tuple[jax.Array, jax.Array]:
        nb = self.spec.blocks_for_tokens(length)
        with jax.named_scope("prefix_gather"):
            return self.gather_dense(request_id, length, num_blocks=nb)

    def gather_dense(self, request_id: int, max_len: int,
                     num_blocks: Optional[int] = None
                     ) -> Tuple[jax.Array, jax.Array]:
        parts = [s.gather_dense(request_id, max_len, num_blocks=num_blocks)
                 for s in self.shards]
        self.num_pool_dispatches += 1
        return (jnp.concatenate([k for k, _ in parts], axis=2),
                jnp.concatenate([v for _, v in parts], axis=2))

    # -- transfer path -----------------------------------------------------------
    def import_plan(self, engine, plan, src_pools: Sequence[jax.Array]) -> None:
        """Land a sharded transfer plan: one fused dispatch per shard pair.

        ``engine`` is a :class:`~repro.core.transfer.ShardedTransferEngine`;
        ``src_pools`` are the source node's per-shard pools (any tp degree).
        """
        before = engine.num_dispatches
        new_pools = engine.execute(plan, list(src_pools), self.pools)
        for shard, pool in zip(self.shards, new_pools):
            shard.pool = pool
        landed = engine.num_dispatches - before
        self.shard_dispatches += landed
        self.num_pool_dispatches += landed

    # -- capacity / bookkeeping -----------------------------------------------------
    @property
    def utilization(self) -> float:
        return self.bm.utilization

    def free(self, request_id: int) -> None:
        self.bm.free(request_id)

    def check_invariants(self) -> None:
        self.bm.check_invariants()
