"""Float32 reference of the dense decoder (Qwen3-style), in plain jax.numpy.

Pre-norm residual blocks, ``h += Attn(RMSNorm(h)); h += FFN(RMSNorm(h))``:
grouped-query attention with per-head RMSNorm on q and k (where the
configuration has it), rotary embeddings of the half-split form at
``rope_theta``, causal softmax scaled by ``1/sqrt(head_dim)``, a SwiGLU FFN,
a final RMSNorm and logits against the tied embedding. An RMSNorm's weight is
``1 + w`` of the stored ``w`` (see ``harness/weights.py``).

Every matrix product runs in float32 at ``Precision.HIGHEST``; weights stay
in their served type in memory and are widened one layer at a time. The
sequence is padded to a multiple of ``PAD`` (causal attention leaves the
real positions untouched) and attention runs in blocks of ``Q_BLOCK`` query
rows, so a 17k-token sequence fits beside the weights.

``fp8=True`` computes the same model with both operands of every weight
product rounded to float8 e4m3 (absmax scale per output channel for weights,
per token for activations): the control, one precision below the bfloat16
the configurations state.

The module is also everything else the benchmark knows of the family (see
``harness/spec.py``): the sizes it reads from a configuration file
(``shape``), the program's ``ModelConfig`` (``program_config``), the weight
tree (``weight_shapes``), the FLOPs of a prefill chunk and of a decode token
(``prefill_flops``, ``decode_flops``) and the paged-attention calls of a
decode step (``attention_calls``).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Tree = Dict[str, Any]

HI = jax.lax.Precision.HIGHEST
PAD = 1024
ROWS = 256
Q_BLOCK = 512
F32 = jnp.float32


def q8(x: jax.Array, axis) -> jax.Array:
    """Round to float8 e4m3 with an absmax scale taken over ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def mm(x: jax.Array, w: jax.Array, fp8: bool) -> jax.Array:
    """x (..., K) @ w (K, N)."""
    if fp8:
        x, w = q8(x, -1), q8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def rms(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """x (S, heads, hd) rotated by position, first half against second."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = pos[:, None].astype(F32) * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """Causal GQA. q (S, H, hd), k/v (S, KV, hd) -> (S, H*hd)."""
    S, H, hd = q.shape
    KV = k.shape[1]
    qg = q.reshape(S // Q_BLOCK, Q_BLOCK, KV, H // KV, hd)
    kpos = jnp.arange(S)

    def block(args):
        i, qb = args
        s = jnp.einsum("bkgd,tkd->kgbt", qb, k, precision=HI) / jnp.sqrt(F32(hd))
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where((kpos[None, :] <= qpos[:, None])[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgbt,tkd->bkgd", p, v, precision=HI).reshape(Q_BLOCK, H * hd)

    out = jax.lax.map(block, (jnp.arange(S // Q_BLOCK), qg))
    return out.reshape(S, H * hd)


def swiglu(x: jax.Array, lp: Dict[str, jax.Array], s: Dict[str, Any], fp8: bool) -> jax.Array:
    gate = jax.nn.silu(mm(x, lp["w_gate"], fp8))
    return mm(gate * mm(x, lp["w_up"], fp8), lp["w_down"], fp8)


def block_fn(h, lp, s, pos, fp8, ffn):
    d, H, KV, hd, eps = s["d_model"], s["heads"], s["kv_heads"], s["head_dim"], s["norm_eps"]
    S = h.shape[0]
    x = rms(h, lp["norm_attn"], eps)
    q = mm(x, lp["wq"].reshape(d, H * hd), fp8).reshape(S, H, hd)
    k = mm(x, lp["wk"].reshape(d, KV * hd), fp8).reshape(S, KV, hd)
    v = mm(x, lp["wv"].reshape(d, KV * hd), fp8).reshape(S, KV, hd)
    if s["qk_norm"]:
        q, k = rms(q, lp["q_norm"], eps), rms(k, lp["k_norm"], eps)
    q, k = rope(q, pos, s["rope_theta"]), rope(k, pos, s["rope_theta"])
    h = h + mm(attention(q, k, v), lp["wo"].reshape(H * hd, d), fp8)
    return h + ffn(rms(h, lp["norm_mlp"], eps), lp, s, fp8)


@functools.partial(jax.jit, static_argnames=("items", "rows", "fp8", "ffn"))
def _forward(weights, tokens, first, *, items, rows, fp8, ffn):
    s = dict(items)
    h = jnp.take(weights["embed"], tokens, axis=0).astype(F32)
    pos = jnp.arange(tokens.shape[0])

    def body(h, lp):
        lp = jax.tree.map(lambda a: a.astype(F32), lp)
        return block_fn(h, lp, s, pos, fp8, ffn), None

    h, _ = jax.lax.scan(body, h, weights["layers"])
    x = rms(jax.lax.dynamic_slice_in_dim(h, first, rows, 0),
            weights["final_norm"].astype(F32), s["norm_eps"])
    table = weights.get("unembed", weights["embed"]).astype(F32)
    return mm(x, table.T, fp8)


def run(weights, shape: Dict[str, Any], tokens: np.ndarray, first: int,
        fp8: bool, ffn: Callable) -> np.ndarray:
    """Logits (n, vocab) of positions ``first .. len(tokens)-1``."""
    n = len(tokens) - first
    rows = -(-n // ROWS) * ROWS
    S = -(-max(len(tokens), first + rows) // PAD) * PAD
    padded = np.zeros(S, np.int32)
    padded[:len(tokens)] = tokens
    items = tuple(sorted((k, v) for k, v in shape.items()))
    out = _forward(weights, jnp.asarray(padded), jnp.int32(first),
                   items=items, rows=rows, fp8=fp8, ffn=ffn)
    return np.asarray(out[:n])


def logits(weights, shape: Dict[str, Any], tokens: np.ndarray, first: int,
           fp8: bool = False) -> np.ndarray:
    return run(weights, shape, tokens, first, fp8, swiglu)


def shape(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes every consumer (weights, reference, FLOP counts) reads,
    under one set of names, from a ``configs/<config>.json``."""
    serving = config["serving"]
    return {
        "family": serving["family"],
        "layers": config["num_hidden_layers"],
        "d_model": config["hidden_size"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config.get("head_dim") or serving["head_dim"],
        "d_ff": config["intermediate_size"],
        "experts": 0,
        "top_k": 0,
        "vocab": config["vocab_size"],
        "qk_norm": bool(serving.get("qk_norm", False)),
        "rope_theta": float(config["rope_theta"]),
        "norm_eps": float(config["rms_norm_eps"]),
        "tied": bool(config["tie_word_embeddings"]),
        "dtype": config["torch_dtype"],
        "block_size": int(serving["block_size"]),
    }


def program_config(config: Dict[str, Any], name: str):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.models.common import ModelConfig

    s = shape(config)
    return ModelConfig(
        name=name, family=s["family"], num_layers=s["layers"],
        d_model=s["d_model"], num_heads=s["heads"], num_kv_heads=s["kv_heads"],
        head_dim=s["head_dim"], d_ff=s["d_ff"], qk_norm=s["qk_norm"],
        vocab_size=s["vocab"], rope_theta=s["rope_theta"], norm_eps=s["norm_eps"],
        tie_embeddings=s["tied"], dtype=getattr(jnp, s["dtype"]),
        block_size=s["block_size"])


def weight_tree(s: Dict[str, Any], ffn: Tree) -> Tree:
    """{path: (shape, init)} of the program's stacked-layer transformer with
    the FFN leaves ``ffn``: ``embed``, ``final_norm`` and ``layers`` with
    ``wq (L, d, H, hd)``, ``wk``/``wv (L, d, KV, hd)``, ``wo (L, H, hd, d)``,
    ``q_norm``/``k_norm (L, hd)`` where the configuration has qk-norm, and
    ``norm_attn``/``norm_mlp (L, d)``; ``unembed`` where the embedding is not
    tied. ``init`` is a projection's fan-in, or a name in
    ``harness/weights.py``'s ``INITS`` (``None`` for a norm)."""
    L, d, H, KV, hd = s["layers"], s["d_model"], s["heads"], s["kv_heads"], s["head_dim"]
    layer: Tree = {
        "wq": ((L, d, H, hd), d), "wk": ((L, d, KV, hd), d),
        "wv": ((L, d, KV, hd), d), "wo": ((L, H, hd, d), H * hd),
        "norm_attn": ((L, d), None), "norm_mlp": ((L, d), None),
    }
    if s["qk_norm"]:
        layer["q_norm"] = ((L, hd), None)
        layer["k_norm"] = ((L, hd), None)
    layer.update(ffn)
    tree = {"embed": ((s["vocab"], d), "embed"), "final_norm": ((d,), None),
            "layers": layer}
    if not s["tied"]:
        tree["unembed"] = ((s["vocab"], d), "embed")
    return tree


def weight_shapes(s: Dict[str, Any]) -> Tree:
    """The weight tree with a SwiGLU FFN: ``w_gate``/``w_up (L, d, f)``,
    ``w_down (L, f, d)``."""
    L, d, f = s["layers"], s["d_model"], s["d_ff"]
    return weight_tree(s, {"w_gate": ((L, d, f), d), "w_up": ((L, d, f), d),
                           "w_down": ((L, f, d), f)})


# FLOPs: two per multiply-add of the weights a token passes through; causal
# attention over ``n`` keys costs ``4 * heads * head_dim * n`` per layer
# (scores and weighted sum); the logits head counts once per prefill call's
# last position and once per decode token.

def attn_params(s: Dict[str, Any]) -> int:
    """Attention weights one token multiplies through in one layer."""
    d, H, KV, hd = s["d_model"], s["heads"], s["kv_heads"], s["head_dim"]
    return d * H * hd + 2 * d * KV * hd + H * hd * d


def layer_params(s: Dict[str, Any]) -> int:
    """Weights one token multiplies through in one layer."""
    return attn_params(s) + 3 * s["d_model"] * s["d_ff"]


def attn_pairs(offset: int, chunk: int) -> int:
    """(query, key) pairs of a causal chunk of ``chunk`` queries at ``offset``."""
    return chunk * offset + chunk * (chunk + 1) // 2


def stack_flops(s: Dict[str, Any], params: int, tokens: int, pairs: int) -> int:
    """``tokens`` tokens through every layer, ``params`` weights a layer and
    ``pairs`` attended (query, key) pairs, then one row of logits."""
    L, H, hd = s["layers"], s["heads"], s["head_dim"]
    return (L * (2 * params * tokens + 4 * H * hd * pairs)
            + 2 * s["vocab"] * s["d_model"])


def prefill_flops(s: Dict[str, Any], offset: int, chunk: int) -> int:
    """A causal chunk of ``chunk`` tokens after ``offset`` cached ones."""
    return stack_flops(s, layer_params(s), chunk, attn_pairs(offset, chunk))


def decode_flops(s: Dict[str, Any], cached: int) -> int:
    """One token with ``cached`` tokens already in the cache (it attends to
    those and to itself)."""
    return stack_flops(s, layer_params(s), 1, cached + 1)


def attention_calls(s: Dict[str, Any]) -> List[Tuple[int, int, int, int]]:
    """(KV heads, query group, head_dim, window) of each paged-attention call
    of a decode step: one full-attention call a layer (window 0)."""
    return [(s["kv_heads"], s["heads"] // s["kv_heads"], s["head_dim"], 0)] * s["layers"]
