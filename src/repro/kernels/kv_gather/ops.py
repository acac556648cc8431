"""Jitted wrappers for the KV gather / scatter / fused-transfer kernels."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.kv_gather.kv_gather import kv_gather
from repro.kernels.kv_gather.kv_scatter import kv_scatter
from repro.kernels.kv_gather.kv_transfer import kv_transfer


@functools.partial(jax.jit, static_argnames=("interpret",))
def kv_gather_op(pool: jax.Array, block_ids: jax.Array, *,
                 interpret: Optional[bool] = None) -> jax.Array:
    return kv_gather(pool, block_ids.astype(jnp.int32),
                     interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def kv_scatter_op(pool: jax.Array, block_ids: jax.Array, staging: jax.Array, *,
                  interpret: Optional[bool] = None) -> jax.Array:
    """Receiver side: place staged pages into local blocks (one dispatch)."""
    return kv_scatter(pool, block_ids.astype(jnp.int32), staging,
                      interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def kv_transfer_op(src_pool: jax.Array, dst_pool: jax.Array,
                   src_pages: jax.Array, dst_pages: jax.Array, *,
                   interpret: Optional[bool] = None) -> jax.Array:
    """One fused descriptor-table dispatch (see ``kv_transfer``)."""
    return kv_transfer(src_pool, dst_pool, src_pages.astype(jnp.int32),
                       dst_pages.astype(jnp.int32), interpret=interpret)
