"""Fused batched token append: one in-place pool update per decode step.

The zero-gather decode step produces one new token's K/V per request per
layer. The pool stores each (block, layer, k/v) page as it is read,
``(KV, block_size, head_dim)`` (``core/layout.py``), so a token is row
``slot`` of each head's plane in ``B * L * 2`` pages. The kernel rewrites
exactly those pages IN PLACE: grid ``(B, L, 2)``, the page at
``(block_tables[b, pos_b // bs], layer, k/v)`` is read, its row ``slot_b``
replaced by the staged token, and written back, with the pool aliased to
the output (donated under ``jax.jit``). Untouched pages keep their contents,
and the pool is never reshaped. The kernel is named ``kv_append`` in the
compiled program and the device trace.

Padded batch lanes must replicate a REAL lane (token/length/block-table row),
not carry zeros: a duplicate lane then rewrites the same page with identical
bytes, which is order-independent, whereas a zero lane would aim its write
at block 0. The engine's bucketing does exactly that.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode


def _kernel(blocks_ref, slots_ref, new_ref, page_ref, out_ref):
    # one grid step == one page: HBM(pool[blk, l, kv]) -> VMEM, one row
    # replaced, -> HBM(pool[blk, l, kv])
    slot = slots_ref[pl.program_id(0)]
    rows = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
    out_ref[...] = jnp.where(rows == slot, new_ref[...], page_ref[...])


def kv_append_tokens(pool: jax.Array, block_tables: jax.Array,
                     positions: jax.Array, k_new: jax.Array, v_new: jax.Array,
                     *, interpret: Optional[bool] = None) -> jax.Array:
    """Append the batch's new-token K/V to the pool in ONE fused dispatch.

    pool (nb, L, 2, KV, bs, hd) FlowKV layout; block_tables (B, W) int32;
    positions (B,) int32 — the slot each request's token occupies;
    k_new / v_new (L, B, KV, hd). Returns the updated pool (aliased to the
    input; untouched pages keep their contents).
    ``interpret=None`` resolves by backend (``repro.kernels.interpret_mode``).
    """
    _, num_layers, two, kv, bs, hd = pool.shape
    b = positions.shape[0]
    blocks = jnp.take_along_axis(block_tables, (positions // bs)[:, None],
                                 axis=1)[:, 0].astype(jnp.int32)
    slots = (positions % bs).astype(jnp.int32)
    # (L, B, KV, hd) x2 -> (B, L, 2, KV, 1, hd): one staged row per page
    new = jnp.stack([k_new, v_new], axis=2).transpose(1, 0, 2, 3, 4)
    new = new[..., None, :].astype(pool.dtype)
    page = (None, None, None, kv, bs, hd)
    at_page = lambda i, l, k, blk, slt: (blk[i], l, k, 0, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, num_layers, two),
        in_specs=[
            pl.BlockSpec((None, None, None, kv, 1, hd),
                         lambda i, l, k, blk, slt: (i, l, k, 0, 0, 0)),
            pl.BlockSpec(page, at_page),
        ],
        out_specs=pl.BlockSpec(page, at_page),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        # operand indices include the two scalar-prefetch tables: the pool is
        # operand 3 and aliases output 0 (in-place update / donation).
        input_output_aliases={3: 0},
        interpret=interpret_mode(interpret),
        name="kv_append",
    )(blocks, slots, new, pool)
