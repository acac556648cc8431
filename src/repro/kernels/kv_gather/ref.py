"""Pure-jnp oracles for the KV staging/transfer kernels."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def kv_gather_ref(pool: jax.Array, block_ids: jax.Array) -> jax.Array:
    """pool (nb, L, 2, KV, bs, hd); block_ids (n,) -> staging (n, L, 2, KV, bs, hd)."""
    return jnp.take(pool, block_ids, axis=0)


def kv_scatter_ref(pool: jax.Array, block_ids: jax.Array,
                   staging: jax.Array) -> jax.Array:
    """Inverse of :func:`kv_gather_ref`: place staged blocks into the pool."""
    return pool.at[block_ids].set(staging.astype(pool.dtype))


def kv_transfer_ref(src_pool: jax.Array, dst_pool: jax.Array,
                    src_pages: jax.Array, dst_pages: jax.Array) -> jax.Array:
    """Descriptor-table oracle over flat ``(num_pages, KV*bs*hd)`` page views."""
    page = math.prod(src_pool.shape[-3:])
    src_flat = src_pool.reshape(-1, page)
    dst_flat = dst_pool.reshape(-1, page)
    out = dst_flat.at[dst_pages].set(
        jnp.take(src_flat, src_pages, axis=0).astype(dst_flat.dtype))
    return out.reshape(dst_pool.shape)


def kv_append_ref(pool: jax.Array, block_tables: jax.Array,
                  positions: jax.Array, k_new: jax.Array,
                  v_new: jax.Array) -> jax.Array:
    """Batched token-append oracle: per-request slot writes, plain indexing.

    pool (nb, L, 2, KV, bs, hd); block_tables (B, W); positions (B,);
    k_new / v_new (L, B, KV, hd).
    """
    bs = pool.shape[-2]
    for b in range(int(positions.shape[0])):
        blk = int(block_tables[b, int(positions[b]) // bs])
        slot = int(positions[b]) % bs
        pool = pool.at[blk, :, 0, :, slot].set(k_new[:, b].astype(pool.dtype))
        pool = pool.at[blk, :, 1, :, slot].set(v_new[:, b].astype(pool.dtype))
    return pool
