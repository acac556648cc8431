from repro.kernels.kv_gather.kv_append import kv_append_tokens
from repro.kernels.kv_gather.kv_gather import kv_gather
from repro.kernels.kv_gather.kv_scatter import kv_scatter
from repro.kernels.kv_gather.kv_transfer import kv_transfer
from repro.kernels.kv_gather.ops import kv_gather_op, kv_scatter_op, kv_transfer_op
from repro.kernels.kv_gather.ref import (kv_append_ref, kv_gather_ref,
                                         kv_scatter_ref, kv_transfer_ref)

__all__ = [
    "kv_gather", "kv_scatter", "kv_transfer", "kv_append_tokens",
    "kv_gather_op", "kv_scatter_op", "kv_transfer_op",
    "kv_gather_ref", "kv_scatter_ref", "kv_transfer_ref", "kv_append_ref",
]
