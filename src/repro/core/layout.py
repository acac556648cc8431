"""KV-cache layouts and the FlowKV layout transform (paper Eq. 5).

The baseline (vLLM/PagedAttention) keys the cache by layer::

    VLLM layout:   K,V : (L, 2, B, H)

so the unit of contiguity is *one layer's half (K or V) of one block* — a
request spanning ``n`` blocks needs ``L * 2 * n`` contiguous-range transfers.

FlowKV transposes block to the major axis::

    FLOWKV layout: K,V : (B, L, 2, H)

making *one block* carry K and V for *all* layers contiguously, so the same
request needs only ``n`` transfers before alignment (and ideally 1 after).

``H`` is one (block, layer, k/v) *page*: ``block_size * num_kv_heads *
head_dim`` elements (``payload``). The pool stores each page unflattened and
kv-head-major, ``(num_kv_heads, block_size, head_dim)``, so the stored arrays
are::

    FLOWKV: (B, L, 2, KV, bs, hd)        VLLM: (L, 2, B, KV, bs, hd)

That is the shape every kernel reads: paged attention takes one page at
``(block, layer)`` straight from the pool, the decode append rewrites pages
in place, and the transfer views pages as ``(pages, KV * bs, hd)`` by
merging leading dims only. On a TPU the minor ``(bs, hd)`` dims are whole
tiles for the served models (32 x 128 in bf16), so none of these views
copies the pool.

Everything in this module is data-plane: the arrays are real ``jnp`` arrays
(tiny in tests, ShapeDtypeStructs in the dry-run).
"""
from __future__ import annotations

import dataclasses
import enum
import math
from typing import Tuple

import jax
import jax.numpy as jnp


class KVLayout(enum.Enum):
    VLLM = "vllm"        # (L, 2, B, KV, bs, hd)  — layer-major baseline
    FLOWKV = "flowkv"    # (B, L, 2, KV, bs, hd)  — block-major, paper Eq. 5

    @property
    def block_axis(self) -> int:
        return 2 if self is KVLayout.VLLM else 0


@dataclasses.dataclass(frozen=True)
class KVCacheSpec:
    """Static description of one node's paged KV pool."""

    num_layers: int
    num_blocks: int
    block_size: int          # tokens per block
    num_kv_heads: int
    head_dim: int
    dtype: jnp.dtype = jnp.bfloat16
    layout: KVLayout = KVLayout.FLOWKV

    @property
    def payload(self) -> int:
        """H — elements per (layer, k/v, block)."""
        return self.block_size * self.num_kv_heads * self.head_dim

    @property
    def page_shape(self) -> Tuple[int, int, int]:
        """One stored page, kv-head-major: (num_kv_heads, block_size, head_dim)."""
        return (self.num_kv_heads, self.block_size, self.head_dim)

    @property
    def shape(self) -> Tuple[int, ...]:
        if self.layout is KVLayout.VLLM:
            return (self.num_layers, 2, self.num_blocks, *self.page_shape)
        return (self.num_blocks, self.num_layers, 2, *self.page_shape)

    @property
    def bytes_per_block(self) -> int:
        """Bytes moved when one block (all layers, K+V) is transferred."""
        return self.num_layers * 2 * self.payload * jnp.dtype(self.dtype).itemsize

    @property
    def bytes_per_token(self) -> int:
        return self.bytes_per_block // self.block_size

    @property
    def total_bytes(self) -> int:
        return self.num_blocks * self.bytes_per_block

    def blocks_for_tokens(self, num_tokens: int) -> int:
        return max(1, math.ceil(num_tokens / self.block_size))

    def with_layout(self, layout: KVLayout) -> "KVCacheSpec":
        return dataclasses.replace(self, layout=layout)

    def transfer_calls_per_block(self) -> int:
        """Contiguous-range transfer calls needed to move ONE block.

        This is the paper's core observation: the vLLM layout pays L*2 calls
        per block; FlowKV pays 1.
        """
        return self.num_layers * 2 if self.layout is KVLayout.VLLM else 1


def alloc_cache(spec: KVCacheSpec) -> jax.Array:
    return jnp.zeros(spec.shape, dtype=spec.dtype)


def vllm_to_flowkv(cache: jax.Array) -> jax.Array:
    """(L, 2, B, KV, bs, hd) -> (B, L, 2, KV, bs, hd)."""
    return jnp.transpose(cache, (2, 0, 1, 3, 4, 5))


def flowkv_to_vllm(cache: jax.Array) -> jax.Array:
    """(B, L, 2, KV, bs, hd) -> (L, 2, B, KV, bs, hd)."""
    return jnp.transpose(cache, (1, 2, 0, 3, 4, 5))


def convert(cache: jax.Array, src: KVLayout, dst: KVLayout) -> jax.Array:
    if src is dst:
        return cache
    if src is KVLayout.VLLM and dst is KVLayout.FLOWKV:
        return vllm_to_flowkv(cache)
    return flowkv_to_vllm(cache)


def write_block(cache: jax.Array, spec: KVCacheSpec, block_id, layer: int,
                k_page: jax.Array, v_page: jax.Array) -> jax.Array:
    """Write one (layer, block) K/V page given as (block_size, kv_heads, head_dim)."""
    k = jnp.swapaxes(k_page, 0, 1).astype(spec.dtype)    # stored kv-head-major
    v = jnp.swapaxes(v_page, 0, 1).astype(spec.dtype)
    if spec.layout is KVLayout.FLOWKV:
        cache = cache.at[block_id, layer, 0].set(k)
        cache = cache.at[block_id, layer, 1].set(v)
    else:
        cache = cache.at[layer, 0, block_id].set(k)
        cache = cache.at[layer, 1, block_id].set(v)
    return cache


def read_block(cache: jax.Array, spec: KVCacheSpec, block_id, layer: int) -> Tuple[jax.Array, jax.Array]:
    """Read one (layer, block) K/V page back as (block_size, kv_heads, head_dim)."""
    if spec.layout is KVLayout.FLOWKV:
        k = cache[block_id, layer, 0]
        v = cache[block_id, layer, 1]
    else:
        k = cache[layer, 0, block_id]
        v = cache[layer, 1, block_id]
    return jnp.swapaxes(k, 0, 1), jnp.swapaxes(v, 0, 1)


def gather_blocks(cache: jax.Array, spec: KVCacheSpec, block_ids) -> jax.Array:
    """Gather whole blocks (all layers, K+V) — the unit FlowKV transfers.

    Returns (n, L, 2, KV, bs, hd) regardless of source layout.
    """
    idx = jnp.asarray(block_ids, dtype=jnp.int32)
    if spec.layout is KVLayout.FLOWKV:
        return jnp.take(cache, idx, axis=0)
    return vllm_to_flowkv(jnp.take(cache, idx, axis=2))


def scatter_blocks(cache: jax.Array, spec: KVCacheSpec, block_ids, blocks: jax.Array) -> jax.Array:
    """Scatter (n, L, 2, KV, bs, hd) blocks into the destination pool."""
    idx = jnp.asarray(block_ids, dtype=jnp.int32)
    if spec.layout is KVLayout.FLOWKV:
        return cache.at[idx].set(blocks.astype(cache.dtype))
    return cache.at[:, :, idx].set(flowkv_to_vllm(blocks).astype(cache.dtype))
