"""Pallas TPU kernel: gather scattered FlowKV pages into a contiguous
staging buffer.

This is the transfer-path FALLBACK: when bidirectional segment alignment
finds no mergeable runs (hostile fragmentation), the sender stages the
request's pages into one contiguous buffer — one DMA per page — and ships
the buffer with a single descriptor. The kernel makes the cost model's
"per-call overhead x n_pages" term concrete: the grid has exactly one step
per page, and the scalar-prefetched block table drives the source index of
each page DMA, so the compiled artifact *is* the descriptor list.

Block-major pool layout (paper Eq. 5) means one grid step moves a block's
K+V for ALL layers — under the vLLM (L, 2, B, ...) layout the same staging
would need L x 2 grid steps per block.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode


def _kernel(ids_ref, pool_ref, out_ref):
    # one grid step == one page DMA: HBM(pool[ids[i]]) -> HBM(out[i])
    out_ref[...] = pool_ref[...]


def kv_gather(pool: jax.Array, block_ids: jax.Array, *,
              interpret: Optional[bool] = None) -> jax.Array:
    """pool (nb, L, 2, KV, bs, hd); block_ids (n,) int32 -> (n, L, 2, KV, bs, hd)."""
    block = pool.shape[1:]
    rest = (0,) * len(block)
    n = block_ids.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[pl.BlockSpec((1, *block), lambda i, ids: (ids[i], *rest))],
        out_specs=pl.BlockSpec((1, *block), lambda i, ids: (i, *rest)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, *block), pool.dtype),
        interpret=interpret_mode(interpret),
    )(block_ids, pool)
