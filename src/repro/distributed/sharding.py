"""Logical-axis -> mesh sharding rules with divisibility fallbacks.

Models annotate every parameter / cache / input dim with a *logical* axis
name; this module maps those onto the production mesh:

    batch    -> ("pod", "data")   (multi-pod) or ("data",)
    heads / kv_heads / mlp / experts / vocab / inner / lru -> "model"
    kv_seq   -> "model"           (decode caches; wins when kv_heads
                                   can't divide the model axis)
    everything else replicated

Assignment walks a tensor's dims in order; a mesh axis is used at most once
per tensor, and a candidate is skipped when the dim size isn't divisible by
the mesh-axis size (e.g. gemma's 8 query heads on a 16-way model axis fall
back to replication — see DESIGN.md and the llava hillclimb in
EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis -> ordered mesh-axis candidates. Each candidate is a tuple of
# mesh axes to use JOINTLY for that dim (e.g. batch over pod x data).
DEFAULT_RULES: Dict[str, Tuple[Tuple[str, ...], ...]] = {
    "batch": (("pod", "data"), ("data",)),
    "heads": (("model",),),
    "kv_heads": (("model",),),
    "mlp": (("model",),),
    "experts": (("model",),),
    "vocab": (("model",),),
    "inner": (("model",),),     # mamba2 d_inner channels
    "lru": (("model",),),       # griffin RG-LRU width
    "kv_seq": (("model",),),    # decode-cache length dim (fallback TP target)
    # "kv_pages" — a paged pool's block/page dim — is EXPLICITLY pinned to
    # replication: page ids are global names shared by every shard's block
    # manager and transfer descriptor table, so sharding the page dim would
    # silently split the address space the descriptor plane indexes into.
    # A paged pool shards only on its kv-head axis (see
    # serving/kv_cache.ShardedKVCache), never across pages. Declared as an
    # empty candidate list (not just left out of the dict) so the intent
    # survives anyone extending the kv_seq fallback chain.
    "kv_pages": (),
    # replicated: embed, head_dim, seq, layers, groups, conv, state, lru_in
}

# Canonical logical axes of a FLOWKV paged pool (num_blocks, L, 2, KV, bs, hd).
# The page dim must use "kv_pages" (never "kv_seq": the decode-cache length
# fallback would shard page tables when num_blocks happens to divide the
# model axis — see tests/test_sharding.py::test_paged_pool_never_shards_pages).
PAGED_POOL_AXES: Tuple[Optional[str], ...] = (
    "kv_pages", "layers", None, "kv_heads", None, "head_dim")


class AbstractMesh:
    """Mesh stand-in for planning shardings without physical devices.

    ``spec_for`` / ``tree_specs`` only consult ``axis_names`` and
    ``devices.shape``, so parameter-slicing decisions for a tp-degree that
    exceeds the local device count (the single-controller TP emulation in
    ``distributed/tp.py``, unit tests on 1-CPU hosts) can reuse the exact
    production rule walk. Not usable with ``NamedSharding``/``jax.jit``.
    """

    def __init__(self, **sizes: int):
        self.axis_names = tuple(sizes)
        self.devices = np.empty(tuple(sizes.values()), dtype=object)


def mesh_axis_sizes(mesh: Mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def spec_for(shape: Sequence[int], logical: Sequence[Optional[str]], mesh: Mesh,
             rules: Optional[Dict] = None) -> P:
    """Build a PartitionSpec for one tensor, with divisibility fallbacks."""
    rules = rules or DEFAULT_RULES
    sizes = mesh_axis_sizes(mesh)
    used: set = set()
    parts = []
    assert len(shape) == len(logical), (shape, logical)
    for dim, name in zip(shape, logical):
        assigned = None
        for cand in rules.get(name or "", ()):
            cand = tuple(ax for ax in cand if ax in sizes)
            if not cand or any(ax in used for ax in cand):
                continue
            total = math.prod(sizes[ax] for ax in cand)
            if dim % total != 0:
                continue
            assigned = cand
            used.update(cand)
            break
        if assigned is None:
            parts.append(None)
        elif len(assigned) == 1:
            parts.append(assigned[0])
        else:
            parts.append(assigned)
    # trim trailing Nones (canonical form)
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def tree_specs(shapes_tree: Any, axes_tree: Any, mesh: Mesh,
               rules: Optional[Dict] = None) -> Any:
    """Map spec_for over parallel (shapes, logical axes) pytrees.

    ``shapes_tree`` leaves: arrays or ShapeDtypeStructs. ``axes_tree``
    leaves: tuples of logical axis names (a tuple IS a pytree, so we walk
    the shapes tree and look the axes up by path).
    """
    flat, treedef = jax.tree.flatten(shapes_tree)
    axes_flat = treedef.flatten_up_to(axes_tree)
    specs = [spec_for(x.shape, ax, mesh, rules) for x, ax in zip(flat, axes_flat)]
    return jax.tree.unflatten(treedef, specs)


def tree_shardings(shapes_tree: Any, axes_tree: Any, mesh: Mesh,
                   rules: Optional[Dict] = None) -> Any:
    specs = tree_specs(shapes_tree, axes_tree, mesh, rules)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def scalar_spec() -> P:
    return P()


def bytes_per_device(shapes_tree: Any, specs_tree: Any, mesh: Mesh) -> int:
    """Estimate per-device bytes for a (shapes, specs) pair."""
    sizes = mesh_axis_sizes(mesh)
    total = 0
    flat, treedef = jax.tree.flatten(shapes_tree)
    specs = treedef.flatten_up_to(specs_tree)
    for x, spec in zip(flat, specs):
        shard = 1
        for entry in spec:
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            shard *= math.prod(sizes[a] for a in axes)
        total += int(np.prod(x.shape)) * x.dtype.itemsize // max(1, shard)
    return total
