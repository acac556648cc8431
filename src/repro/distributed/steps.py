"""Jitted distributed step functions: train_step / prefill_step / decode_step
plus the FlowKV cross-pod KV-transfer program.

Every step is built as (fn, in_shardings, out_shardings) against a concrete
mesh, ready for ``jax.jit(...).lower(**specs).compile()`` — the multi-pod
dry-run path — or for real execution on the CPU-scale meshes in tests.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed import sharding as SH
from repro.models.api import Model, input_specs
from repro.models.common import ModelConfig
from repro.training import optimizer as OPT


# ---------------------------------------------------------------------------
# Train
# ---------------------------------------------------------------------------
def zero1_specs(params_shapes, p_spec, mesh: Mesh):
    """Extend TP param specs with data(-and-pod)-axis sharding for the
    optimizer state (ZeRO-1): the first unsharded dim divisible by the
    data-axis size additionally shards over ("data",) (+"pod" if present).

    Under SPMD this makes XLA reduce-scatter gradients into the optimizer
    shards and all-gather updated params once per step — exactly the ZeRO-1
    communication pattern.
    """
    sizes = SH.mesh_axis_sizes(mesh)
    dp_axes = tuple(a for a in ("pod", "data") if a in sizes)
    dp = 1
    for a in dp_axes:
        dp *= sizes[a]

    def extend(x, spec):
        parts = list(spec) + [None] * (len(x.shape) - len(spec))
        for i, (dim, cur) in enumerate(zip(x.shape, parts)):
            if cur is None and dim % dp == 0 and dim >= dp:
                parts[i] = dp_axes if len(dp_axes) > 1 else dp_axes[0]
                break
        while parts and parts[-1] is None:
            parts.pop()
        return P(*parts)

    return jax.tree.map(extend, params_shapes, p_spec,
                        is_leaf=lambda s: isinstance(s, P))


def make_train_step(model: Model, mesh: Mesh, params_shapes,
                    opt_cfg: Optional[OPT.AdamWConfig] = None,
                    compress_pod_grads: bool = False, zero1: bool = True):
    """Returns (train_step, state_spec).

    ``train_step(state, batch) -> (state, metrics)``. Compute params stay
    TP-sharded (logical rules); master/m/v are additionally ZeRO-1 sharded
    over the data(+pod) axes. The bf16 compute cast is constrained back to
    the TP spec so the ZeRO all-gather happens once per step, not per layer.

    ``compress_pod_grads``: int8-compress gradients (with error feedback)
    before the optimizer — the DCN gradient-compression path.
    """
    opt_cfg = opt_cfg or OPT.AdamWConfig()
    cfg = model.cfg
    axes = model.param_axes()
    p_spec = SH.tree_specs(params_shapes, axes, mesh)
    z_spec = zero1_specs(params_shapes, p_spec, mesh) if zero1 else p_spec
    p_shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), p_spec,
                               is_leaf=lambda s: isinstance(s, P))

    def train_step(state, batch):
        def loss_with_compute_dtype(master):
            compute = jax.tree.map(
                lambda w, sh: jax.lax.with_sharding_constraint(w.astype(cfg.dtype), sh),
                master, p_shardings)
            return model.loss(compute, batch)

        loss, grads = jax.value_and_grad(loss_with_compute_dtype)(state["master"])
        if compress_pod_grads:
            q, scales, residual = OPT.compress_grads(grads, state["ef"])
            grads = OPT.decompress_grads(q, scales)
            new_state, metrics = OPT.apply_updates(
                {k: v for k, v in state.items() if k != "ef"}, grads, opt_cfg,
                compute_dtype=cfg.dtype)
            new_state["ef"] = residual
        else:
            new_state, metrics = OPT.apply_updates(state, grads, opt_cfg,
                                                   compute_dtype=cfg.dtype)
        metrics["loss"] = loss
        return new_state, metrics

    state_spec = {"params": p_spec, "master": z_spec, "m": z_spec,
                  "v": z_spec, "step": P()}
    if compress_pod_grads:
        state_spec["ef"] = z_spec
    return train_step, state_spec


def abstract_train_state(model: Model, with_ef: bool = False):
    """eval_shape the full train state without allocating."""
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    f32 = lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32)
    state = {
        "params": params,
        "master": jax.tree.map(f32, params),
        "m": jax.tree.map(f32, params),
        "v": jax.tree.map(f32, params),
        "step": jax.ShapeDtypeStruct((), jnp.int32),
    }
    if with_ef:
        state["ef"] = jax.tree.map(f32, params)
    return state


# ---------------------------------------------------------------------------
# Serve: prefill / decode
# ---------------------------------------------------------------------------
def make_prefill_step(model: Model, mesh: Mesh):
    """prefill_step(params, batch) -> (logits, cache)."""
    def prefill_step(params, batch):
        return model.prefill(params, batch)

    return prefill_step


def make_decode_step(model: Model, mesh: Mesh):
    """decode_step(params, token, cache) -> (logits, cache). Cache donated."""
    def decode_step(params, token, cache):
        return model.decode(params, token, cache)

    return decode_step


# ---------------------------------------------------------------------------
# FlowKV cross-pod KV transfer (the paper-representative collective program)
# ---------------------------------------------------------------------------
# The old make_kv_transfer_step ring-shift (ppermute over "pod") is gone: the
# serving data plane moves KV through descriptor-table plans
# (core/transfer.py — ShardedTransferEngine for mesh-parallel pools), which
# subsumes the whole-pool shift with per-page addressing. Only the
# shape/sharding specs below survive for the dry-run compile path.
def kv_transfer_specs(cfg: ModelConfig, mesh: Mesh, seq: int, batch: int):
    """ShapeDtypeStructs for the transfer program: the paged FlowKV pool.

    Pool shape (num_blocks, L, 2, KV, bs, hd): block-major (paper Eq. 5), block
    dim sharded (pod, data) so each pod/replica owns its page slab.
    """
    from repro.core.layout import KVCacheSpec

    n_attn = cfg.num_attention_layers()
    if n_attn == 0:   # ssm: transfer the state tensor instead
        spec = jax.ShapeDtypeStruct(
            (batch, cfg.num_layers, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
            cfg.dtype)
        return spec, P("pod")
    kv_spec = KVCacheSpec(
        num_layers=n_attn,
        num_blocks=batch * -(-seq // cfg.block_size),
        block_size=cfg.block_size,
        num_kv_heads=cfg.num_kv_heads,
        head_dim=cfg.head_dim,
        dtype=cfg.dtype,
    )
    spec = jax.ShapeDtypeStruct(kv_spec.shape, cfg.dtype)
    return spec, P("pod")
