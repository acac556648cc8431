"""Jitted wrapper for the paged decode attention kernel.

``paged_decode_attention_op`` takes the full FlowKV pool and a layer index
and runs the kernel, which reads that layer's pages in place. On TPU the
call compiles to a Mosaic kernel; on a CPU backend the Pallas interpreter
executes the same kernel body for correctness (tests sweep shapes/dtypes
against ``ref.py``).

The batched zero-gather decode step (``models/transformer.decode_step_paged``)
calls the unjitted kernel directly inside its own jit — one compiled artifact
covers the whole layer stack plus the fused KV append.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels.paged_attention.paged_attention import paged_decode_attention


@functools.partial(jax.jit, static_argnames=("interpret", "return_stats"))
def paged_decode_attention_op(q: jax.Array, pool: jax.Array, layer,
                              block_tables: jax.Array, lengths: jax.Array,
                              *, interpret: Optional[bool] = None,
                              return_stats: bool = False):
    """q (B,H,hd); pool (nb, L, 2, KV, bs, hd) FlowKV layout; layer scalar."""
    return paged_decode_attention(q, pool, layer, block_tables, lengths,
                                  interpret=interpret,
                                  return_stats=return_stats)
