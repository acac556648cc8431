"""Jitted wrapper for the prefill flash-attention kernel (pads S to tile
multiples, strips padding after).

Suffix mode (prefix-cache reuse): pass keys/values covering prefix+suffix
and ``q_offset = T - S`` — queries are just the uncached suffix rows and the
kernel computes exactly rows ``T-S..T`` of the full-sequence result. Both
sides pad at the END; padded key rows sit beyond every real query's causal
frontier, so they never contribute.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_prefill.flash_prefill import flash_prefill


@functools.partial(jax.jit, static_argnames=("causal", "q_blk", "k_blk",
                                             "q_offset", "interpret"))
def flash_prefill_op(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     causal: bool = True, q_blk: int = 128, k_blk: int = 128,
                     q_offset: int = 0, interpret: Optional[bool] = None) -> jax.Array:
    b, s, h, hd = q.shape
    t = k.shape[1]
    assert t == s + q_offset, "keys must cover prefix (q_offset) + queries"
    blk = max(min(q_blk, s), min(k_blk, t))
    pad_q = (-s) % blk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    # keys must reach at least the last PADDED query row's position
    # (q_offset + s + pad_q - 1) and land on a tile boundary
    pad_k = (-t) % blk
    while t + pad_k < q.shape[1] + q_offset:
        pad_k += blk
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    # both sides were padded to multiples of `blk`, so tile with exactly
    # `blk` — re-deriving from the padded lengths could pick a tile that
    # does not divide them (e.g. C=64, S=8: k pads to 144, min(128,144)=128)
    out = flash_prefill(q, k, v, causal=causal, q_blk=blk, k_blk=blk,
                        q_offset=q_offset, interpret=interpret)
    return out[:, :s]
