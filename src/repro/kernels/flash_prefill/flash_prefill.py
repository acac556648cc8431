"""Pallas TPU kernel: causal flash attention for chunked prefill.

Why a kernel when ``models/flash.py`` already exists: the jnp flash path
carries its fp32 online-softmax accumulators through XLA while-loop state,
which round-trips them through HBM every kv-chunk iteration — the dry-run
roofline shows prefill cells memory-bound largely because of that. Here the
accumulators live in VMEM scratch for the whole kv sweep, so HBM traffic
drops to ~(Q + K + V + O) once, moving prefill back toward the compute
roofline (the §Perf "kernel-adjusted" rows).

Grid ``(B, KV, nq, nk)``: nk iterates minor (sequential) so scratch carries
the accumulator across kv chunks; causal skip via ``pl.when`` — kv chunks
entirely above the diagonal are never loaded (exact-causal FLOPs, the wedge
optimization for free).

Tiles: q (q_blk, G, hd), k/v (k_blk, hd) with q_blk/k_blk multiples of 128
in production; hd is the MXU lane dim.
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


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, q_blk: int, k_blk: int, causal: bool, q_offset: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # q_offset > 0 = suffix mode (prefix-cache reuse): query row i sits at
    # global position q_offset + i while keys cover the whole [0, T) range,
    # so the causal frontier — and the chunk-skip test — shift by q_offset.
    q_start = qi * q_blk + q_offset
    k_start = ki * k_blk
    run = (k_start <= q_start + q_blk - 1) if causal else (ki >= 0)

    @pl.when(run)
    def _process():
        q = q_ref[0, 0].astype(jnp.float32)               # (q_blk, G, hd)
        k = k_ref[0, 0].astype(jnp.float32)               # (k_blk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        hd = q.shape[-1]
        s = jax.lax.dot_general(q, k, (((2,), (1,)), ((), ())))  # (q_blk,G,k_blk)
        s = s / jnp.sqrt(jnp.asarray(hd, jnp.float32))
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_ref[...]                               # (q_blk, G)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        if causal:
            p = jnp.where(kpos <= qpos, p, 0.0)
        scale = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
        l_ref[...] = l_ref[...] * scale + p.sum(axis=-1)
        pv = jax.lax.dot_general(p, v, (((2,), (0,)), ((), ())))  # (q_blk,G,hd)
        acc_ref[...] = acc_ref[...] * scale[..., None] + pv
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)[..., None]
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_prefill(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = True, q_blk: int = 128, k_blk: int = 128,
                  q_offset: int = 0, interpret: Optional[bool] = None) -> jax.Array:
    """q (B,S,H,hd); k/v (B,T,KV,hd) -> (B,S,H,hd). S/T divisible by blocks.

    T == S with ``q_offset=0`` is ordinary causal prefill. T > S with
    ``q_offset = T - S`` is SUFFIX prefill (prefix-cache reuse): the first
    ``q_offset`` keys are a resident cached prefix and queries are the
    uncached tail — the kernel computes exactly rows ``q_offset..T`` of the
    full-sequence result, skipping the prefix rows' compute entirely.
    """
    b, s, h, hd = q.shape
    t = k.shape[1]
    kvh = k.shape[2]
    g = h // kvh
    if causal:
        # keys may extend past the query horizon (tile padding): causality
        # masks them for every real row
        assert t >= s + q_offset, "keys must cover prefix (q_offset) + queries"
    else:
        assert t == s and q_offset == 0, "q_offset is causal-only"
    q_blk = min(q_blk, s)
    k_blk = min(k_blk, t)
    assert s % q_blk == 0 and t % k_blk == 0, "pad S/T to block multiples"
    nq, nk = s // q_blk, t // k_blk
    # layout: (B, KV, S, G, hd) for q/o; (B, KV, S, hd) for k/v
    qr = jnp.transpose(q.reshape(b, s, kvh, g, hd), (0, 2, 1, 3, 4))
    kr = jnp.transpose(k, (0, 2, 1, 3))
    vr = jnp.transpose(v, (0, 2, 1, 3))

    kernel = functools.partial(_kernel, q_blk=q_blk, k_blk=k_blk, causal=causal,
                               q_offset=q_offset)
    out = pl.pallas_call(
        kernel,
        grid=(b, kvh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, q_blk, g, hd), lambda bb, kk, qi, ki: (bb, kk, qi, 0, 0)),
            pl.BlockSpec((1, 1, k_blk, hd), lambda bb, kk, qi, ki: (bb, kk, ki, 0)),
            pl.BlockSpec((1, 1, k_blk, hd), lambda bb, kk, qi, ki: (bb, kk, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q_blk, g, hd),
                               lambda bb, kk, qi, ki: (bb, kk, qi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kvh, s, g, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_blk, g), jnp.float32),
            pltpu.VMEM((q_blk, g), jnp.float32),
            pltpu.VMEM((q_blk, g, hd), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
        name="flash_prefill",
    )(qr, kr, vr)
    return jnp.transpose(out, (0, 2, 1, 3, 4)).reshape(b, s, h, hd)
