"""Pure-jnp oracle for the FlowKV-layout paged decode attention kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _dense_kv(pool: jax.Array, layer, block_tables: jax.Array):
    """Layer ``layer``'s K and V of each sequence, (B, maxb*bs, KV, hd)."""
    b, maxb = block_tables.shape
    _, _, _, kv, bs, hd = pool.shape
    pages = jnp.take(pool[:, layer], block_tables.reshape(-1), axis=0)
    pages = pages.reshape(b, maxb, 2, kv, bs, hd).transpose(2, 0, 1, 4, 3, 5)
    k = pages[0].reshape(b, maxb * bs, kv, hd)
    v = pages[1].reshape(b, maxb * bs, kv, hd)
    return k, v


def paged_decode_attention_ref(q: jax.Array, pool: jax.Array, layer,
                               block_tables: jax.Array,
                               lengths: jax.Array) -> jax.Array:
    """Reference paged decode attention.

    q:            (B, H, hd)        — one query token per sequence
    pool:         (nb, L, 2, KV, bs, hd) — the FlowKV pool
    layer:        int               — the layer whose pages are read
    block_tables: (B, maxb) int32   — physical block ids per sequence
    lengths:      (B,) int32        — valid tokens per sequence
    returns:      (B, H, hd)
    """
    b, h, hd = q.shape
    k, v = _dense_kv(pool, layer, block_tables)
    kv = k.shape[2]
    g = h // kv

    qg = q.reshape(b, kv, g, hd)
    scores = jnp.einsum("bkgd,btkd->bkgt", qg, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(hd)
    t = k.shape[1]
    valid = jnp.arange(t)[None, :] < lengths[:, None]
    scores = jnp.where(valid[:, None, None, :], scores, jnp.finfo(jnp.float32).min)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgt,btkd->bkgd", w.astype(v.dtype), v)
    return out.reshape(b, h, hd)


def paged_decode_attention_stats_ref(q: jax.Array, pool: jax.Array, layer,
                                     block_tables: jax.Array,
                                     lengths: jax.Array):
    """Oracle for ``return_stats=True``: (out, m, l) with fp32 softmax state.

    ``m`` is the running max score, ``l`` the normalizer, per (B, KV, G) —
    the same quantities the kernel keeps in VMEM scratch.
    """
    b, h, hd = q.shape
    k, v = _dense_kv(pool, layer, block_tables)
    kv = k.shape[2]
    g = h // kv

    qg = q.reshape(b, kv, g, hd)
    scores = jnp.einsum("bkgd,btkd->bkgt", qg, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(hd)
    t = k.shape[1]
    valid = jnp.arange(t)[None, :] < lengths[:, None]
    neg = jnp.finfo(jnp.float32).min
    scores = jnp.where(valid[:, None, None, :], scores, neg)
    m = jnp.max(scores, axis=-1)
    p = jnp.where(valid[:, None, None, :], jnp.exp(scores - m[..., None]), 0.0)
    l = p.sum(axis=-1)
    # masked weights (not a raw softmax) so a fully-masked row (length 0)
    # yields out = 0, matching the kernel's init state
    out = jnp.einsum("bkgt,btkd->bkgd", p, v.astype(jnp.float32))
    out = out / jnp.maximum(l, 1e-30)[..., None]
    m = jnp.where(lengths[:, None, None] > 0, m, neg)
    return out.reshape(b, h, hd).astype(q.dtype), m, l
