"""Pallas TPU kernel: scatter a contiguous staging buffer into FlowKV pages.

The receiver-side inverse of ``kv_gather``: after a staged transfer lands as
one contiguous buffer ``(n, L, 2, KV, bs, hd)``, each grid step DMAs one staged
block into its local pool slot, driven by the scalar-prefetched block table.
The pool is aliased to the output, so untouched blocks keep their contents
without a second pool allocation.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode


def _kernel(ids_ref, staging_ref, pool_ref, out_ref):
    # one grid step == one block DMA: HBM(staging[i]) -> HBM(pool[ids[i]])
    out_ref[...] = staging_ref[...].astype(out_ref.dtype)


def kv_scatter(pool: jax.Array, block_ids: jax.Array, staging: jax.Array, *,
               interpret: Optional[bool] = None) -> jax.Array:
    """pool (nb, L, 2, KV, bs, hd); block_ids (n,) int32; staging (n, L, 2, KV, bs, hd)."""
    block = (1, *pool.shape[1:])
    rest = (0,) * (len(block) - 1)
    n = block_ids.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[
            pl.BlockSpec(block, lambda i, ids: (i, *rest)),
            pl.BlockSpec(block, lambda i, ids: (ids[i], *rest)),
        ],
        out_specs=pl.BlockSpec(block, lambda i, ids: (ids[i], *rest)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        # operand indices include the scalar-prefetch table: pool is operand 2
        # and aliases output 0 (in-place pool update / donation).
        input_output_aliases={2: 0},
        interpret=interpret_mode(interpret),
    )(block_ids.astype(jnp.int32), staging, pool)
