"""Decoder-only transformer LM covering the dense, MoE and VLM/backbone
configs (granite-moe, llama4-scout, minitron, gemma, stablelm, qwen3,
llava-next, and the paper's llama-3.1 models).

Layer parameters are stacked on a leading ``layers`` axis; forward passes
``jax.lax.scan`` over them so the lowered HLO is one layer body regardless
of depth. Pre-norm residual blocks::

    x = x + Attn(RMSNorm(x));  x = x + FFN(RMSNorm(x))

Three entry points per model:
  * ``forward_train``  — full-sequence causal logits (training).
  * ``prefill``        — full-sequence forward that also returns the dense
                         KV cache (the tensors FlowKV ships P -> D).
  * ``decode_step``    — one token against a dense cache.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as A
from repro.models import mlp as M
from repro.models import moe as MOE
from repro.models.common import (ModelConfig, dense_init, embed, maybe_remat,
                                 rms_norm, softmax_cross_entropy, unembed)

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------
def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    keys = jax.random.split(key, 16)
    L = cfg.num_layers
    d = cfg.d_model

    def stack(k, shape, scale=None):
        return dense_init(k, (L, *shape), cfg.dtype, scale)

    attn_shapes = A.attn_param_shapes(cfg)
    layer: Dict[str, jax.Array] = {
        name: stack(k, shape)
        for (name, shape), k in zip(attn_shapes.items(), jax.random.split(keys[0], len(attn_shapes)))
    }
    if cfg.qk_norm:
        layer["q_norm"] = jnp.zeros((L, cfg.head_dim), cfg.dtype)
        layer["k_norm"] = jnp.zeros((L, cfg.head_dim), cfg.dtype)
    layer["norm_attn"] = jnp.zeros((L, d), cfg.dtype)
    layer["norm_mlp"] = jnp.zeros((L, d), cfg.dtype)
    if cfg.family == "moe":
        moe_shapes = MOE.moe_param_shapes(cfg)
        for (name, shape), k in zip(moe_shapes.items(), jax.random.split(keys[1], len(moe_shapes))):
            layer[f"moe_{name}"] = stack(k, shape)
    else:
        mlp_shapes = M.mlp_param_shapes(cfg)
        for (name, shape), k in zip(mlp_shapes.items(), jax.random.split(keys[2], len(mlp_shapes))):
            layer[name] = stack(k, shape)

    params: Params = {
        "embed": dense_init(keys[3], (cfg.vocab_size, d), cfg.dtype, scale=0.02),
        "final_norm": jnp.zeros((d,), cfg.dtype),
        "layers": layer,
    }
    if not cfg.tie_embeddings:
        params["unembed"] = dense_init(keys[4], (cfg.vocab_size, d), cfg.dtype, scale=0.02)
    return params


def param_axes(cfg: ModelConfig) -> Params:
    layer_axes: Dict[str, Tuple[Optional[str], ...]] = {
        name: ("layers", *ax) for name, ax in A.attn_param_axes(cfg).items()
    }
    layer_axes["norm_attn"] = ("layers", "embed")
    layer_axes["norm_mlp"] = ("layers", "embed")
    if cfg.family == "moe":
        for name, ax in MOE.moe_param_axes().items():
            layer_axes[f"moe_{name}"] = ("layers", *ax)
    else:
        for name, ax in M.mlp_param_axes().items():
            layer_axes[name] = ("layers", *ax)
    axes: Params = {
        "embed": ("vocab", "embed"),
        "final_norm": ("embed",),
        "layers": layer_axes,
    }
    if not cfg.tie_embeddings:
        axes["unembed"] = ("vocab", "embed")
    return axes


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------
def _ffn(lp: Params, x: jax.Array, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    if cfg.family == "moe":
        moe_p = {k[len("moe_"):]: v for k, v in lp.items() if k.startswith("moe_")}
        if cfg.top_k == 1 and cfg.moe_sparse_dispatch:
            return MOE.moe_ffn_topk_sparse(moe_p, x, cfg)
        if cfg.moe_dispatch == "gshard":
            return MOE.moe_ffn_gshard(moe_p, x, cfg, cfg.moe_capacity_factor)
        if cfg.moe_dispatch == "gshard_einsum":
            return MOE.moe_ffn_gshard_einsum(moe_p, x, cfg, cfg.moe_capacity_factor)
        return MOE.moe_ffn(moe_p, x, cfg)
    return M.gated_mlp(lp, x, cfg.activation), jnp.zeros((), jnp.float32)


def _layer_train(cfg: ModelConfig, x: jax.Array, lp: Params,
                 positions: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    h = rms_norm(x, lp["norm_attn"], cfg.norm_eps)
    attn_out, (k, v) = A.self_attention(lp, h, cfg, positions, cfg.attn_window)
    x = x + attn_out
    h = rms_norm(x, lp["norm_mlp"], cfg.norm_eps)
    ffn_out, aux = _ffn(lp, h, cfg)
    return x + ffn_out, aux, k, v


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------
def _input_embeds(params: Params, cfg: ModelConfig, tokens: jax.Array,
                  frontend_embeds: Optional[jax.Array]) -> jax.Array:
    x = embed(tokens, params["embed"], scale=cfg.embed_scale)
    if frontend_embeds is not None:
        # VLM/audio backbone: splice precomputed patch/frame embeddings in
        # front of the text embeddings (stub frontend per spec).
        x = jnp.concatenate([frontend_embeds.astype(x.dtype), x], axis=1)
    return x


def forward_train(params: Params, cfg: ModelConfig, tokens: jax.Array,
                  frontend_embeds: Optional[jax.Array] = None) -> Tuple[jax.Array, jax.Array]:
    """tokens (B, S_text) -> (logits (B, S_total, V) fp32, aux_loss)."""
    x = _input_embeds(params, cfg, tokens, frontend_embeds)
    positions = jnp.arange(x.shape[1])[None, :]

    def body(carry, lp):
        h, aux = carry
        h, aux_i, _, _ = _layer_train(cfg, h, lp, positions)
        return (h, aux + aux_i), None

    (x, aux), _ = jax.lax.scan(maybe_remat(body, cfg),
                               (x, jnp.zeros((), jnp.float32)), params["layers"])
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(x, params.get("unembed", params["embed"]))
    return logits, aux


def loss_fn(params: Params, cfg: ModelConfig, batch: Dict[str, jax.Array]) -> jax.Array:
    logits, aux = forward_train(params, cfg, batch["tokens"],
                                batch.get("frontend_embeds"))
    labels = batch["labels"]
    mask = batch.get("loss_mask")
    if logits.shape[1] != labels.shape[1]:       # frontend positions carry no labels
        n_front = logits.shape[1] - labels.shape[1]
        logits = logits[:, n_front:]
    loss = softmax_cross_entropy(logits[:, :-1], labels[:, 1:],
                                 None if mask is None else mask[:, 1:])
    return loss + 0.01 * aux


def prefill(params: Params, cfg: ModelConfig, tokens: jax.Array,
            frontend_embeds: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Full-sequence forward; returns last-position logits + dense KV cache.

    Cache: k/v (L, B, S_total, KV, head_dim) — the tensors FlowKV pages and
    ships to the decode node.
    """
    x = _input_embeds(params, cfg, tokens, frontend_embeds)
    positions = jnp.arange(x.shape[1])[None, :]

    def body(carry, lp):
        h, aux = carry
        h, aux_i, k, v = _layer_train(cfg, h, lp, positions)
        return (h, aux + aux_i), (k, v)

    with jax.named_scope("prefill_layers"):
        (x, _), (ks, vs) = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                        params["layers"])
    x = rms_norm(x[:, -1:], params["final_norm"], cfg.norm_eps)
    logits = unembed(x, params.get("unembed", params["embed"]))[:, 0]
    length = jnp.full((tokens.shape[0],), ks.shape[2], jnp.int32)
    return logits, {"k": ks, "v": vs, "length": length}


def prefill_suffix(params: Params, cfg: ModelConfig, tokens: jax.Array,
                   prefix_k: jax.Array, prefix_v: jax.Array
                   ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Suffix-only prefill over a resident prefix (prefix-cache hit).

    tokens (B, S_suf) are the prompt tokens AFTER the cached prefix;
    prefix_k/v (L, B, C, KV, hd) are the prefix's cached K/V exactly as a
    cold :func:`prefill` would have produced them (read back from the paged
    pool). Computes rows C..C+S_suf of the full forward — attention per
    layer runs over [prefix KV ++ suffix KV] with the suffix positions
    offset by C — so last-position logits and the returned suffix cache are
    bit-identical to the cold path's, at ``S_suf/S_total`` of the compute.

    Returns (logits (B, V) fp32, cache with k/v covering ONLY the suffix).
    """
    x = _input_embeds(params, cfg, tokens, None)
    c = prefix_k.shape[2]
    positions = c + jnp.arange(x.shape[1])[None, :]

    def body(carry, inputs):
        h, aux = carry
        lp, pk, pv = inputs
        hn = rms_norm(h, lp["norm_attn"], cfg.norm_eps)
        attn_out, (k, v) = A.suffix_attention(lp, hn, cfg, positions, pk, pv,
                                              cfg.attn_window)
        h = h + attn_out
        hn = rms_norm(h, lp["norm_mlp"], cfg.norm_eps)
        ffn_out, aux_i = _ffn(lp, hn, cfg)
        return (h + ffn_out, aux + aux_i), (k, v)

    with jax.named_scope("prefill_layers"):
        (x, _), (ks, vs) = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)),
            (params["layers"], prefix_k, prefix_v))
    x = rms_norm(x[:, -1:], params["final_norm"], cfg.norm_eps)
    logits = unembed(x, params.get("unembed", params["embed"]))[:, 0]
    length = jnp.full((tokens.shape[0],), c + ks.shape[2], jnp.int32)
    return logits, {"k": ks, "v": vs, "length": length}


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None) -> Dict[str, jax.Array]:
    dtype = dtype or cfg.dtype
    shape = (cfg.num_layers, batch, max_len, cfg.num_kv_heads, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "length": jnp.zeros((batch,), jnp.int32),
    }


def cache_axes() -> Dict[str, Tuple[Optional[str], ...]]:
    return {
        "k": ("layers", "batch", "kv_seq", "kv_heads", "head_dim"),
        "v": ("layers", "batch", "kv_seq", "kv_heads", "head_dim"),
        "length": ("batch",),
    }


def decode_step(params: Params, cfg: ModelConfig, token: jax.Array,
                cache: Dict[str, jax.Array]) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """token (B,) int32; cache k/v (L, B, T, KV, hd) + length (B,).

    Returns (logits (B, V) fp32, updated cache).
    """
    x = embed(token[:, None], params["embed"], scale=cfg.embed_scale)
    position = cache["length"]

    def body(carry, inputs):
        h = carry
        lp, ck, cv = inputs
        hn = rms_norm(h, lp["norm_attn"], cfg.norm_eps)
        attn_out, (ck, cv) = A.decode_self_attention(
            lp, hn, cfg, ck, cv, position, cfg.attn_window)
        h = h + attn_out
        hn = rms_norm(h, lp["norm_mlp"], cfg.norm_eps)
        ffn_out, _ = _ffn(lp, hn, cfg)
        return h + ffn_out, (ck, cv)

    x, (ks, vs) = jax.lax.scan(body, x, (params["layers"], cache["k"], cache["v"]))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(x, params.get("unembed", params["embed"]))[:, 0]
    new_cache = {"k": ks, "v": vs, "length": cache["length"] + 1}
    return logits, new_cache


def decode_step_paged(params: Params, cfg: ModelConfig, token: jax.Array,
                      pool: jax.Array, block_tables: jax.Array,
                      lengths: jax.Array, *, interpret: Optional[bool] = None
                      ) -> Tuple[jax.Array, jax.Array]:
    """One batched decode step directly on the FlowKV pool (zero-gather).

    token (B,) int32; pool (nb, L, 2, KV, bs, hd); block_tables (B, W)
    int32; lengths (B,) int32 = tokens already cached per request — the new
    token's write position. Returns (logits (B, V) fp32, updated pool).

    Unlike :func:`decode_step`, no dense (L, B, T, KV, hd) cache is ever
    built, and the pool is never sliced, reshaped or copied: every layer's
    attention hands the whole pool and its layer index to the Pallas paged
    kernel, which reads that layer's pages in place (the in-flight token is
    merged via the kernel's softmax state), and the batch's new K/V for ALL
    layers is written in place by one fused append after the layer stack.
    Under ``jax.jit`` with the pool donated this is one device dispatch per
    decode cycle, independent of batch size and context length.
    """
    from repro.kernels.kv_gather import kv_append_tokens

    x = embed(token[:, None], params["embed"], scale=cfg.embed_scale)
    position = lengths
    L = pool.shape[1]

    def body(h, inputs):
        lp, layer = inputs
        hn = rms_norm(h, lp["norm_attn"], cfg.norm_eps)
        with jax.named_scope("paged_attention"):
            attn_out, (k_new, v_new) = A.decode_paged_self_attention(
                lp, hn, cfg, pool, layer, block_tables, position,
                interpret=interpret)
        h = h + attn_out
        hn = rms_norm(h, lp["norm_mlp"], cfg.norm_eps)
        ffn_out, _ = _ffn(lp, hn, cfg)
        return h + ffn_out, (k_new, v_new)

    x, (ks, vs) = jax.lax.scan(
        body, x, (params["layers"], jnp.arange(L, dtype=jnp.int32)))
    with jax.named_scope("kv_append"):
        pool = kv_append_tokens(pool, block_tables, position, ks, vs,
                                interpret=interpret)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(x, params.get("unembed", params["embed"]))[:, 0]
    return logits, pool


# ---------------------------------------------------------------------------
# Convenience
# ---------------------------------------------------------------------------
def greedy_generate(params: Params, cfg: ModelConfig, prompt: jax.Array,
                    max_new_tokens: int, max_len: Optional[int] = None) -> jax.Array:
    """Reference autoregressive generation (used by tests/examples)."""
    b, s = prompt.shape
    max_len = max_len or (s + max_new_tokens)
    logits, pre = prefill(params, cfg, prompt)
    cache = init_cache(cfg, b, max_len)
    cache["k"] = cache["k"].at[:, :, :s].set(pre["k"])
    cache["v"] = cache["v"].at[:, :, :s].set(pre["v"])
    cache["length"] = jnp.full((b,), s, jnp.int32)
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    out = [tok]
    for _ in range(max_new_tokens - 1):
        logits, cache = decode_step(params, cfg, tok, cache)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out.append(tok)
    return jnp.stack(out, axis=1)
