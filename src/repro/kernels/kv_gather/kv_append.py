"""Fused batched token append: one descriptor-table dispatch per decode step.

The zero-gather decode step produces one new token's K/V per request per
layer — ``2 * L * B`` token-sized pages. Instead of ``B`` per-request pool
rewrites (the old ``PagedKVCache.append_token`` loop), the whole batch lands
in ONE ``kv_transfer`` dispatch by viewing the pool at *token-slot*
granularity: a FlowKV page ``(block, layer, k/v)`` is ``block_size`` slots of
``num_kv_heads * head_dim`` elements, so the flat slot table is
``(nb * L * 2 * block_size, KV*hd)`` and a token append is a descriptor row
``staging[i] -> slots[ids[i]]``.

Padded batch lanes must replicate a REAL lane (token/length/block-table row),
not carry zeros: duplicate descriptors then write identical bytes to
identical slots, which is order-independent, whereas a zero lane would aim
its write at block 0. The engine's bucketing does exactly that.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.kv_gather.kv_transfer import kv_transfer


def append_slot_ids(block_tables: jax.Array, positions: jax.Array,
                    num_layers: int, block_size: int) -> jax.Array:
    """Flat token-slot ids for one token per request, all layers and K/V.

    block_tables (B, W) int32; positions (B,) int32 absolute token index.
    Returns (B * L * 2,) int32, row-major over (request, layer, k/v) — the
    same order ``stage_tokens`` emits.
    """
    blk = jnp.take_along_axis(block_tables,
                              (positions // block_size)[:, None], axis=1)[:, 0]
    slot = positions % block_size
    layer = jnp.arange(num_layers, dtype=jnp.int32)[None, :, None]
    kv = jnp.arange(2, dtype=jnp.int32)[None, None, :]
    page = (blk[:, None, None].astype(jnp.int32) * num_layers + layer) * 2 + kv
    ids = page * block_size + slot[:, None, None].astype(jnp.int32)
    return ids.reshape(-1)


def stage_tokens(k_new: jax.Array, v_new: jax.Array) -> jax.Array:
    """k/v (L, B, KV, hd) -> staging (B * L * 2, KV*hd), descriptor order."""
    L, B = k_new.shape[0], k_new.shape[1]
    stage = jnp.stack([k_new, v_new], axis=2)          # (L, B, 2, KV, hd)
    return stage.transpose(1, 0, 2, 3, 4).reshape(B * L * 2, -1)


def kv_append_tokens(pool: jax.Array, block_tables: jax.Array,
                     positions: jax.Array, k_new: jax.Array, v_new: jax.Array,
                     *, block_size: int,
                     interpret: Optional[bool] = None) -> jax.Array:
    """Append the batch's new-token K/V to the pool in ONE fused dispatch.

    pool (nb, L, 2, payload) FlowKV layout; block_tables (B, W) int32;
    positions (B,) int32 — the slot each request's token occupies;
    k_new / v_new (L, B, KV, hd). Returns the updated pool (aliased/donated
    through ``kv_transfer``; untouched slots keep their contents).
    ``interpret=None`` resolves by backend (``repro.kernels.interpret_mode``).
    """
    nb, L, two, payload = pool.shape
    tok_payload = payload // block_size                # KV * hd
    staging = stage_tokens(k_new, v_new).astype(pool.dtype)
    ids = append_slot_ids(block_tables, positions, L, block_size)
    src = jnp.arange(staging.shape[0], dtype=jnp.int32)
    pool_view = pool.reshape(nb, L, 2, block_size, tok_payload)
    out = kv_transfer(staging, pool_view, src, ids, interpret=interpret,
                      name="kv_append")
    return out.reshape(pool.shape)
