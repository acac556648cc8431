"""Float32 reference of the mixture-of-experts decoder, in plain jax.numpy.

The dense family's blocks (``reference/dense.py``: pre-norm, GQA with
optional qk-norm, half-split RoPE, tied or untied logits) with the FFN
replaced by experts, as the program's ``models/moe.py`` ``moe_ffn`` computes
them: a softmax router over every expert held, the ``top_k`` largest
probabilities renormalised to sum to 1, and each chosen expert a SwiGLU FFN
whose output is weighted by its probability. Every expert runs over every
token, one expert at a time, and tokens that did not choose it weigh it 0.
Every product runs in float32 at ``Precision.HIGHEST``; ``fp8=True`` rounds
both operands of every weight product, the router's included, to float8
e4m3 (the control).

The rest of what the benchmark knows of the family: its configuration keys
(Mixtral/Granite names, ``num_local_experts``), the weight tree
(``moe_router (L, d, E)``, ``moe_w_gate``/``moe_w_up (L, E, d, f)``,
``moe_w_down (L, E, f, d)``, ``E`` the experts held), and FLOPs at the
``top_k`` chosen experts and the router, not the all-expert product that
dense dispatch computes. Attention is the dense family's.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from reference import dense as D

F32 = D.F32
attention_calls = D.attention_calls


def shape(config: Dict[str, Any]) -> Dict[str, Any]:
    """The dense family's sizes, ``d_ff`` the width of one expert, with the
    experts held and the experts chosen per token."""
    return dict(D.shape(config), experts=config["num_local_experts"],
                top_k=config["num_experts_per_tok"])


def program_config(config: Dict[str, Any], name: str):
    s = shape(config)
    return dataclasses.replace(D.program_config(config, name), d_ff=0, moe_d_ff=s["d_ff"],
                               num_experts=s["experts"], top_k=s["top_k"])


def weight_shapes(s: Dict[str, Any]) -> D.Tree:
    L, d, E, f = s["layers"], s["d_model"], s["experts"], s["d_ff"]
    return D.weight_tree(s, {"moe_router": ((L, d, E), d),
                             "moe_w_gate": ((L, E, d, f), d), "moe_w_up": ((L, E, d, f), d),
                             "moe_w_down": ((L, E, f, d), f)})


def layer_params(s: Dict[str, Any]) -> int:
    """Weights one token multiplies through in one layer: attention, the
    router over every expert held, and ``top_k`` experts."""
    d = s["d_model"]
    return D.attn_params(s) + d * s["experts"] + s["top_k"] * 3 * d * s["d_ff"]


def prefill_flops(s: Dict[str, Any], offset: int, chunk: int) -> int:
    return D.stack_flops(s, layer_params(s), chunk, D.attn_pairs(offset, chunk))


def decode_flops(s: Dict[str, Any], cached: int) -> int:
    return D.stack_flops(s, layer_params(s), 1, cached + 1)


def route(x: jax.Array, router: jax.Array, top_k: int, fp8: bool) -> jax.Array:
    """(S, E) weight of each expert for each token: its renormalised softmax
    probability where it is among the token's ``top_k``, else 0."""
    probs = jax.nn.softmax(D.mm(x, router, fp8), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, top_k)
    top_p = top_p / top_p.sum(-1, keepdims=True)
    chosen = jax.nn.one_hot(top_i, probs.shape[-1], dtype=F32)       # (S, k, E)
    return jnp.einsum("ske,sk->se", chosen, top_p, precision=D.HI)


def experts(x: jax.Array, lp: Dict[str, jax.Array], s: Dict[str, Any], fp8: bool) -> jax.Array:
    """The FFN of one layer: the routed sum of SwiGLU experts."""
    weight = route(x, lp["moe_router"], s["top_k"], fp8)

    def one(out, e):
        w_gate, w_up, w_down, w = e
        y = D.mm(jax.nn.silu(D.mm(x, w_gate, fp8)) * D.mm(x, w_up, fp8), w_down, fp8)
        return out + w[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x),
                          (lp["moe_w_gate"], lp["moe_w_up"], lp["moe_w_down"], weight.T))
    return out


def logits(weights, shape: Dict[str, Any], tokens: np.ndarray, first: int,
           fp8: bool = False) -> np.ndarray:
    return D.run(weights, shape, tokens, first, fp8, experts)
