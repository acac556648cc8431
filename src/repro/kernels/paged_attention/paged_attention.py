"""Pallas TPU kernel: decode attention over FlowKV block-major pages.

This is the paper's "targeted optimizations ... for the PagedAttention
kernel" (§3.3) adapted to TPU. The pool is stored block-major with each
page kv-head-major, ``(nb, L, 2, KV, block_size, hd)`` (Eq. 5, see
``core/layout.py``), and the kernel reads it IN PLACE: it takes the whole
pool plus the layer index, and its page BlockSpec ``(None, None, 2, KV,
block_size, hd)`` at ``(block_tables[b, i], layer, 0, 0, 0, 0)`` makes *one
DMA per page* stage a block's K AND V for this layer into VMEM, straight
from the stored pool, with no per-(layer, k/v) descriptors, mirroring the
transfer-path win.

``q`` is presented as ``(B, KV, G, hd)``, so every block's last two dims
are whole array dims (Mosaic's tiling rule) and both contractions batch
over the leading kv-head axis. No reshape happens inside the kernel.

Grid: ``(B, max_blocks)`` — the page dim iterates sequentially (TPU minor
grid dim), maintaining an online-softmax accumulator in VMEM scratch per
sequence. Page indirection uses scalar-prefetched block tables (and the
layer index) in the BlockSpec index_map, so the pipeline prefetches page
``i+1`` while page ``i`` is being processed (the TPU analogue of
overlapping transfer kernels with compute).

Tiling: one page block is ``(2, KV, block_size, hd)``. With the default
32-token blocks and a 128-wide head_dim every MXU operand is lane-aligned.

``return_stats=True`` additionally emits the per-(kv-head, group) online
softmax state ``(m, l)`` so callers can merge EXTRA keys exactly — the
zero-gather decode step uses this to fold in the in-flight token (whose K/V
is not in the pool yet) without densifying any cached page.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

NEG_INF = float(jnp.finfo(jnp.float32).min)


def _kernel(block_tables_ref, lengths_ref, layer_ref,   # scalar prefetch
            q_ref, pages_ref,                  # VMEM inputs
            *refs,                             # VMEM outputs + scratch
            block_size: int, head_dim: int, return_stats: bool):
    if return_stats:
        o_ref, m_out_ref, l_out_ref, m_ref, l_ref, acc_ref = refs
    else:
        o_ref, m_ref, l_ref, acc_ref = refs
    b = pl.program_id(0)
    i = pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = lengths_ref[b]
    start = i * block_size

    @pl.when(start < length)
    def _process():
        qg = q_ref[...].astype(jnp.float32)            # (KV, G, hd)
        k = pages_ref[0].astype(jnp.float32)           # (KV, bs, hd)
        v = pages_ref[1].astype(jnp.float32)
        s = jax.lax.dot_general(
            qg, k, (((2,), (2,)), ((0,), (0,))),
        )                                              # (KV, G, bs)
        s = s / jnp.sqrt(jnp.asarray(head_dim, jnp.float32))
        pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(pos < length, s, NEG_INF)

        m_prev = m_ref[...]                            # (KV, G)
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(pos < length, p, 0.0)
        scale = jnp.exp(m_prev - m_new)
        l_new = l_prev * scale + p.sum(axis=-1)
        pv = jax.lax.dot_general(
            p, v, (((2,), (1,)), ((0,), (0,))),
        )                                              # (KV, G, hd)
        acc_ref[...] = acc_ref[...] * scale[..., None] + pv
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(i == nb - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)[..., None]
        o_ref[...] = (acc_ref[...] / denom).astype(o_ref.dtype)
        if return_stats:
            m_out_ref[...] = m_ref[...]
            l_out_ref[...] = l_ref[...]


def paged_decode_attention(q: jax.Array, pool: jax.Array, layer,
                           block_tables: jax.Array, lengths: jax.Array,
                           *, interpret: Optional[bool] = None,
                           return_stats: bool = False):
    """q (B,H,hd); pool (nb,L,2,KV,bs,hd); layer int scalar;
    block_tables (B,maxb); lengths (B,).

    Reads layer ``layer``'s pages of the blocks in ``block_tables`` straight
    from the pool. Returns ``out (B,H,hd)``; with ``return_stats=True``
    returns ``(out, m, l)`` where ``m``/``l`` are the fp32 online-softmax
    max and normalizer per (B, KV, G) — ``out * l`` recovers the
    unnormalized accumulator for exact merging with additional keys.
    """
    b, h, hd = q.shape
    maxb = block_tables.shape[1]
    _, _, two, num_kv, block_size, _ = pool.shape
    g = h // num_kv
    qg = q.reshape(b, num_kv, g, hd)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    per_seq = lambda bb, i, bt, ln, ly: (bb, 0, 0, 0)
    out_specs = [pl.BlockSpec((None, num_kv, g, hd), per_seq)]
    out_shapes = [jax.ShapeDtypeStruct((b, num_kv, g, hd), q.dtype)]
    if return_stats:
        out_specs += [pl.BlockSpec((None, num_kv, g),
                                   lambda bb, i, bt, ln, ly: (bb, 0, 0))] * 2
        out_shapes += [jax.ShapeDtypeStruct((b, num_kv, g), jnp.float32)] * 2

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, maxb),
        in_specs=[
            pl.BlockSpec((None, num_kv, g, hd), per_seq),
            pl.BlockSpec((None, None, two, num_kv, block_size, hd),
                         lambda bb, i, bt, ln, ly: (bt[bb, i], ly[0],
                                                    0, 0, 0, 0)),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((num_kv, g), jnp.float32),
            pltpu.VMEM((num_kv, g), jnp.float32),
            pltpu.VMEM((num_kv, g, hd), jnp.float32),
        ],
    )
    kernel = functools.partial(_kernel, block_size=block_size, head_dim=hd,
                               return_stats=return_stats)
    outs = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shapes,
        interpret=interpret_mode(interpret),
        name="paged_attention",
    )(block_tables, lengths, layer, qg, pool)
    out = outs[0].reshape(b, h, hd)
    if return_stats:
        return out, outs[1], outs[2]
    return out
