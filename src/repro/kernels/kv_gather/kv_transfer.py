"""Pallas TPU kernel: fused descriptor-table KV transfer (gather–scatter).

This is THE transfer data plane. A :class:`~repro.core.transfer.TransferPlan`
lowers to a *descriptor table* — int32 arrays of flattened source/destination
page ids — and the whole plan executes as ONE kernel dispatch, regardless of
schedule (layerwise / blockwise / flowkv). Schedules differ only in how many
*transport calls* the cost model prices, never in Python loop structure.

A pool's trailing three dims are one page, as it is stored: one (block,
layer, k/v) slice ``(KV, block_size, head_dim)``, the finest unit any
schedule moves (``core/layout.py``). Both pools are viewed as page tables
``(num_pages, KV * block_size, head_dim)``, which merges leading dims only:
on a TPU the minor ``(block_size, head_dim)`` dims are whole tiles, so the
view is a bitcast of the stored pool, not a copy. Each grid step moves one
whole page, so the block's last two dims equal the array's, which is what
Mosaic's (8, 128) tiling rule asks of a block. The two page-id tables are
scalar-prefetched so the grid's index maps can compute each page DMA's
source and destination before the body runs: the compiled artifact *is*
the descriptor table. The destination pool is aliased to the output
(donated under ``jax.jit``), so pages not named by the table keep their
previous contents and no second pool allocation is made. The kernel is
named ``kv_transfer`` in the compiled program and the device trace.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode


def _page_view(pool: jax.Array) -> jax.Array:
    """``pool`` as ``(num_pages, rows, head_dim)``: its trailing three dims,
    one page, with the kv-head and slot dims merged."""
    kv, slots, hd = pool.shape[-3:]
    return pool.reshape(-1, kv * slots, hd)


def _kernel(src_pages_ref, dst_pages_ref, src_ref, dst_ref, out_ref):
    # one grid step == one page DMA: HBM(src[src_pages[i]]) -> HBM(dst[dst_pages[i]])
    out_ref[...] = src_ref[...].astype(out_ref.dtype)


def kv_transfer(src_pool: jax.Array, dst_pool: jax.Array,
                src_pages: jax.Array, dst_pages: jax.Array, *,
                interpret: Optional[bool] = None) -> jax.Array:
    """Execute one descriptor table in one dispatch.

    ``src_pool`` / ``dst_pool`` are paged KV pools in either layout, FLOWKV
    ``(B, L, 2, KV, bs, hd)`` or VLLM ``(L, 2, B, KV, bs, hd)``, on either
    side; page ``p`` is the ``p``-th page in row-major order of the leading
    dims. ``src_pages`` / ``dst_pages`` are equal-length int32 page-id
    tables. Returns the updated destination pool (dst is aliased to the
    output).
    """
    if src_pool.shape[-3:] != dst_pool.shape[-3:]:
        raise ValueError(f"src/dst pages differ: {src_pool.shape[-3:]} "
                         f"vs {dst_pool.shape[-3:]}")
    src_flat = _page_view(src_pool)
    dst_flat = _page_view(dst_pool)
    page = (None, *dst_flat.shape[1:])
    n = src_pages.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n,),
        in_specs=[
            pl.BlockSpec(page, lambda i, sp, dp: (sp[i], 0, 0)),
            pl.BlockSpec(page, lambda i, sp, dp: (dp[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec(page, lambda i, sp, dp: (dp[i], 0, 0)),
    )
    out_flat = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(dst_flat.shape, dst_flat.dtype),
        # operand indices include the two scalar-prefetch tables: dst_flat is
        # operand 3 and aliases output 0 (in-place pool update / donation).
        input_output_aliases={3: 0},
        interpret=interpret_mode(interpret),
        name="kv_transfer",
    )(src_pages.astype(jnp.int32), dst_pages.astype(jnp.int32),
      src_flat, dst_flat)
    return out_flat.reshape(dst_pool.shape)
