"""Attention blocks: GQA/MQA with optional qk-norm, full-causal or
local-window masks, cross-attention, and dense-cache decode.

These are the *reference* (pure-jnp) paths used by training, the dry-run
step functions, and as oracles for the Pallas kernels in ``repro.kernels``.
Serving-time paged decode goes through ``kernels/paged_attention`` (FlowKV
block-major layout).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import ModelConfig, apply_rope, rms_norm


# ---------------------------------------------------------------------------
# Parameter init — per layer (caller stacks over layers)
# ---------------------------------------------------------------------------
def attn_param_shapes(cfg: ModelConfig) -> Dict[str, Tuple[int, ...]]:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    shapes = {
        "wq": (d, h, hd),
        "wk": (d, kv, hd),
        "wv": (d, kv, hd),
        "wo": (h, hd, d),
    }
    if cfg.qk_norm:
        shapes["q_norm"] = (hd,)
        shapes["k_norm"] = (hd,)
    return shapes


def attn_param_axes(cfg: ModelConfig) -> Dict[str, Tuple[Optional[str], ...]]:
    axes = {
        "wq": ("embed", "heads", "head_dim"),
        "wk": ("embed", "kv_heads", "head_dim"),
        "wv": ("embed", "kv_heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
    }
    if cfg.qk_norm:
        axes["q_norm"] = ("head_dim",)
        axes["k_norm"] = ("head_dim",)
    return axes


# ---------------------------------------------------------------------------
# Projections
# ---------------------------------------------------------------------------
def qkv_project(p: Dict[str, jax.Array], x: jax.Array, cfg: ModelConfig,
                positions: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x (B, S, D) -> q (B, S, H, hd), k/v (B, S, KV, hd), with RoPE + qk-norm."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def out_project(p: Dict[str, jax.Array], attn: jax.Array) -> jax.Array:
    return jnp.einsum("bshk,hkd->bsd", attn, p["wo"])


# ---------------------------------------------------------------------------
# Core attention math (GQA-aware)
# ---------------------------------------------------------------------------
def _gqa_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """q (B,S,H,hd), k (B,T,KV,hd) -> scores (B,KV,G,S,T) with H = KV*G."""
    b, s, h, hd = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qg = q.reshape(b, s, kvh, g, hd)
    return jnp.einsum("bskgd,btkd->bkgst", qg, k) / jnp.sqrt(hd).astype(q.dtype)


def _gqa_combine(weights: jax.Array, v: jax.Array) -> jax.Array:
    """weights (B,KV,G,S,T), v (B,T,KV,hd) -> (B,S,H,hd)."""
    b, kvh, g, s, t = weights.shape
    out = jnp.einsum("bkgst,btkd->bskgd", weights, v)
    return out.reshape(b, s, kvh * g, v.shape[-1])


def causal_mask(s: int, t: int, offset: int = 0, window: int = 0) -> jax.Array:
    """(s, t) boolean mask; query i (global pos offset+i) sees key j iff
    j <= offset+i and (window == 0 or j > offset+i-window)."""
    qpos = jnp.arange(s)[:, None] + offset
    kpos = jnp.arange(t)[None, :]
    m = kpos <= qpos
    if window > 0:
        m &= kpos > qpos - window
    return m


def attend(q: jax.Array, k: jax.Array, v: jax.Array,
           mask: Optional[jax.Array]) -> jax.Array:
    """Full-precision softmax attention. mask broadcastable to (B,KV,G,S,T)."""
    scores = _gqa_scores(q, k).astype(jnp.float32)
    if mask is not None:
        scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)
    weights = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return _gqa_combine(weights, v)


# ---------------------------------------------------------------------------
# Layer-level entry points
# ---------------------------------------------------------------------------
def self_attention_heads(p: Dict[str, jax.Array], x: jax.Array, cfg: ModelConfig,
                         positions: jax.Array, window: int = 0
                         ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """:func:`self_attention` minus the output projection.

    Returns (heads (B,S,H,hd), (k, v)). Every step is per-kv-head
    independent, so a tensor-parallel shard can run this on its contiguous
    head slice of wq/wk/wv and the concatenated shard outputs equal the
    full-width result exactly (``distributed/tp.py``).
    """
    from repro.models.flash import flash_attention  # local import: avoid cycle

    q, k, v = qkv_project(p, x, cfg, positions)
    s = x.shape[1]
    if window > 0 or s > cfg.flash_threshold:
        out = flash_attention(q, k, v, causal=True, window=window,
                              q_chunk=cfg.attn_q_chunk, kv_chunk=cfg.attn_kv_chunk,
                              wedge=cfg.attn_wedge)
    else:
        mask = causal_mask(s, s, 0, window)[None, None, None]
        out = attend(q, k, v, mask)
    return out, (k, v)


def self_attention(p: Dict[str, jax.Array], x: jax.Array, cfg: ModelConfig,
                   positions: jax.Array, window: int = 0) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Training/prefill: full-sequence causal (or windowed) self-attention.

    Returns (output (B,S,D), (k, v)) — k/v returned for cache capture.
    Long sequences (or any windowed attention) route through the chunked
    flash path so (S, T) scores never materialize.
    """
    out, (k, v) = self_attention_heads(p, x, cfg, positions, window)
    return out_project(p, out), (k, v)


def suffix_attention(p: Dict[str, jax.Array], x: jax.Array, cfg: ModelConfig,
                     positions: jax.Array, prefix_k: jax.Array,
                     prefix_v: jax.Array, window: int = 0
                     ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Suffix-only prefill attention over a resident prefix (prefix-cache hit).

    x (B, S_suf, D) are the UNCACHED prompt tokens; prefix_k/v (B, C, KV, hd)
    are the matched prefix's cached K/V (already RoPE'd at positions 0..C);
    ``positions`` must be the suffix's global positions (C + arange(S_suf)).
    Computes exactly the rows C..C+S_suf of full-prompt attention — same
    flash/dense dispatch policy as :func:`self_attention` keyed on the TOTAL
    length, so warm and cold prefill take the same numeric path and outputs
    stay bit-identical. Returns (out (B,S_suf,D), (k, v)) with k/v covering
    ONLY the suffix (the caller writes just those tokens' pages).
    """
    from repro.models.flash import flash_attention  # local import: avoid cycle

    out, (k, v) = suffix_attention_heads(p, x, cfg, positions, prefix_k,
                                         prefix_v, window)
    return out_project(p, out), (k, v)


def suffix_attention_heads(p: Dict[str, jax.Array], x: jax.Array,
                           cfg: ModelConfig, positions: jax.Array,
                           prefix_k: jax.Array, prefix_v: jax.Array,
                           window: int = 0
                           ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """:func:`suffix_attention` minus the output projection (TP shard body)."""
    from repro.models.flash import flash_attention  # local import: avoid cycle

    q, k, v = qkv_project(p, x, cfg, positions)
    k_full = jnp.concatenate([prefix_k.astype(k.dtype), k], axis=1)
    v_full = jnp.concatenate([prefix_v.astype(v.dtype), v], axis=1)
    s, t = x.shape[1], k_full.shape[1]
    offset = prefix_k.shape[1]
    if window > 0 or t > cfg.flash_threshold:
        out = flash_attention(q, k_full, v_full, causal=True, window=window,
                              q_chunk=cfg.attn_q_chunk, kv_chunk=cfg.attn_kv_chunk,
                              q_offset=offset, wedge=cfg.attn_wedge)
    else:
        mask = causal_mask(s, t, offset, window)[None, None, None]
        out = attend(q, k_full, v_full, mask)
    return out, (k, v)


def decode_self_attention(p: Dict[str, jax.Array], x: jax.Array, cfg: ModelConfig,
                          cache_k: jax.Array, cache_v: jax.Array,
                          position: jax.Array, window: int = 0
                          ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Single-token decode against a dense cache.

    x (B, 1, D); cache_k/v (B, T, KV, hd) — position is the write index
    (B,) or scalar. Returns (out (B,1,D), updated cache).
    """
    pos = jnp.broadcast_to(jnp.asarray(position), (x.shape[0],))
    q, k_new, v_new = qkv_project(p, x, cfg, pos[:, None])
    # write the new token's K/V at `pos`
    b_idx = jnp.arange(x.shape[0])
    cache_k = cache_k.at[b_idx, pos].set(k_new[:, 0])
    cache_v = cache_v.at[b_idx, pos].set(v_new[:, 0])
    t = cache_k.shape[1]
    kpos = jnp.arange(t)[None, :]
    valid = kpos <= pos[:, None]
    if window > 0:
        valid &= kpos > (pos[:, None] - window)
    mask = valid[:, None, None, None, :]          # (B,1,1,1,T)
    out = attend(q, cache_k, cache_v, mask)
    return out_project(p, out), (cache_k, cache_v)


def decode_paged_self_attention(p: Dict[str, jax.Array], x: jax.Array,
                                cfg: ModelConfig, pool: jax.Array, layer,
                                block_tables: jax.Array, position: jax.Array,
                                *, interpret: Optional[bool] = None
                                ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Single-token decode directly against one layer's pages of the pool.

    x (B, 1, D); pool (nb, L, 2, KV, bs, hd) FlowKV layout; layer int
    scalar; block_tables (B, W) int32; position (B,) int32 = tokens already
    cached (the in-flight token's absolute index). The cached keys of layer
    ``layer`` are read IN PLACE from the whole pool by the paged
    kernel; the in-flight token — whose K/V is not in the pool yet — is
    folded in exactly via the kernel's online-softmax state (m, l), so no
    dense (B, T) cache is ever materialized. Returns
    (out (B, 1, D), (k_new (B, KV, hd), v_new (B, KV, hd))); the caller
    appends the new K/V for the whole layer stack in one fused in-place
    append.
    """
    out, kv = decode_paged_attention_heads(p, x, cfg, pool, layer,
                                           block_tables, position,
                                           interpret=interpret)
    return out_project(p, out), kv


def decode_paged_attention_heads(p: Dict[str, jax.Array], x: jax.Array,
                                 cfg: ModelConfig, pool: jax.Array, layer,
                                 block_tables: jax.Array, position: jax.Array,
                                 *, interpret: Optional[bool] = None
                                 ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """:func:`decode_paged_self_attention` minus the output projection.

    The paged read, the online-softmax merge of the in-flight token, and the
    normalization are all per-kv-head independent, so a TP shard runs this
    against its own head-sliced pool (``distributed/tp.py``).
    """
    from repro.kernels.paged_attention import paged_decode_attention

    pos = jnp.broadcast_to(jnp.asarray(position), (x.shape[0],))
    q, k_new, v_new = qkv_project(p, x, cfg, pos[:, None])
    q1, k1, v1 = q[:, 0], k_new[:, 0], v_new[:, 0]
    out_old, m_old, l_old = paged_decode_attention(
        q1, pool, layer, block_tables, pos, interpret=interpret,
        return_stats=True)
    out = merge_inflight_token(q1, k1, v1, out_old, m_old, l_old, x.dtype)
    return out, (k1, v1)


def merge_inflight_token(q1: jax.Array, k1: jax.Array, v1: jax.Array,
                         out_old: jax.Array, m_old: jax.Array,
                         l_old: jax.Array, out_dtype) -> jax.Array:
    """Fold the in-flight token into paged-kernel output as one extra key.

    q1 (B,H,hd), k1/v1 (B,KV,hd); out_old (B,H,hd) + m_old/l_old (B,KV,G)
    are the kernel's online-softmax state. Exact online-softmax step;
    returns (B,1,H,hd). The TP emulation calls this ONCE on the full-width
    concat of per-shard kernel outputs: the einsum lowerings here are not
    bit-stable across kv-head extents, so merging at per-shard width would
    drift from the single-device result by an ulp (distributed/tp.py).
    """
    b, h, hd = q1.shape
    kvh = k1.shape[1]
    g = h // kvh
    qg = q1.reshape(b, kvh, g, hd).astype(jnp.float32)
    s_self = jnp.einsum("bkgd,bkd->bkg", qg, k1.astype(jnp.float32))
    s_self = s_self / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    m_new = jnp.maximum(m_old, s_self)
    alpha = jnp.exp(m_old - m_new)
    p_self = jnp.exp(s_self - m_new)
    l_new = l_old * alpha + p_self
    acc = (out_old.reshape(b, kvh, g, hd).astype(jnp.float32)
           * (l_old * alpha)[..., None]
           + p_self[..., None] * v1.astype(jnp.float32)[:, :, None, :])
    out = acc / jnp.maximum(l_new, 1e-30)[..., None]
    return out.reshape(b, 1, h, hd).astype(out_dtype)


# ---------------------------------------------------------------------------
# Cross-attention (encoder-decoder)
# ---------------------------------------------------------------------------
def cross_param_shapes(cfg: ModelConfig) -> Dict[str, Tuple[int, ...]]:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    return {"wq": (d, h, hd), "wk": (d, kv, hd), "wv": (d, kv, hd), "wo": (h, hd, d)}


def cross_attention(p: Dict[str, jax.Array], x: jax.Array, memory_kv: Tuple[jax.Array, jax.Array],
                    cfg: ModelConfig, memory_mask: Optional[jax.Array] = None) -> jax.Array:
    """x (B,S,D) attends over precomputed encoder K/V (B,T,KV,hd)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k, v = memory_kv
    mask = None if memory_mask is None else memory_mask[:, None, None, None, :]
    out = attend(q, k, v, mask)
    return out_project(p, out)


def encode_memory(p: Dict[str, jax.Array], memory: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Project encoder output once into cross-attn K/V (cached per request)."""
    k = jnp.einsum("btd,dhk->bthk", memory, p["wk"])
    v = jnp.einsum("btd,dhk->bthk", memory, p["wv"])
    return k, v
