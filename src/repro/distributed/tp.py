"""Single-controller tensor/expert-parallel execution of the serving paths.

A ``NodeEngine`` with ``tp_degree > 1`` runs every layer's attention and FFN
as ``tp`` independent shard computations over parameter slices chosen by the
SAME logical-axis rule walk production meshes use (``spec_for`` over
``transformer.param_axes`` with an :class:`~repro.distributed.sharding.
AbstractMesh` whose ``model`` axis has size ``tp``):

* attention — wq/wk/wv column-sliced on (kv_)heads, per-shard
  :func:`~repro.models.attention.self_attention_heads` /
  :func:`~repro.models.attention.decode_paged_attention_heads`;
* dense MLP — w_gate/w_up column-sliced on the mlp dim;
* MoE — router columns + expert slices (expert parallelism; dense-dispatch
  combine only).

Bit-identity with the single-device engine is by construction, not by
tolerance: every sliced computation is per-output-column (or per-kv-head /
per-expert) independent, so the concatenation of shard outputs reproduces
the full-width intermediate exactly, and every COMBINE contraction
(``out_project``'s reduce over heads, ``w_down``'s reduce over the mlp dim,
the MoE combine's reduce over experts) runs ONCE over the concatenated
operands — never as per-shard partial sums, whose float addition order
would differ from the unsharded einsum. On a real mesh the concatenations
are the all-gathers the logical-axis rules imply; here they are
``jnp.concatenate`` on one controller, which keeps the data path testable
on 1-CPU hosts (``make_local_mesh`` cannot build a model>1 mesh there).

Embedding and unembedding stay replicated: the rule table maps ``vocab`` to
the model axis, but a vocab-sharded gather/projection needs masked
all-reduce plumbing that buys nothing for the serving data path reproduced
here.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import DEFAULT_RULES, AbstractMesh, spec_for
from repro.models import attention as A
from repro.models import mlp as M
from repro.models import moe as MOE
from repro.models import transformer as TF
from repro.models.common import ModelConfig, embed, rms_norm, unembed

Params = Dict[str, Any]

TP_FAMILIES = ("dense", "moe")


def ep_degree(cfg: ModelConfig, tp: int) -> int:
    """Expert-parallel degree implied by a tp degree: MoE configs run their
    experts over the same model axis, everything else has no expert axis."""
    return tp if cfg.family == "moe" else 1


def validate_tp(cfg: ModelConfig, tp: int) -> None:
    """Reject configs the sharded data path cannot run exactly."""
    if tp <= 1:
        return
    if cfg.family not in TP_FAMILIES:
        raise ValueError(f"tensor parallelism supports families {TP_FAMILIES}, "
                         f"got {cfg.family!r}")
    if cfg.num_heads % tp or cfg.num_kv_heads % tp:
        raise ValueError(f"heads ({cfg.num_heads}/{cfg.num_kv_heads}) must "
                         f"divide tp={tp}")
    if cfg.family == "moe":
        if cfg.num_experts % tp:
            raise ValueError(f"experts ({cfg.num_experts}) must divide tp={tp}")
        if cfg.moe_dispatch != "dense" or (cfg.top_k == 1 and
                                           cfg.moe_sparse_dispatch):
            raise ValueError("expert-parallel serving supports dense dispatch "
                             "only (capacity/sparse dispatch reorders tokens "
                             "per shard)")
    elif cfg.d_ff % tp:
        raise ValueError(f"d_ff ({cfg.d_ff}) must divide tp={tp}")


def shard_params(params: Params, cfg: ModelConfig, tp: int) -> List[Params]:
    """Slice a full parameter tree into ``tp`` shard trees.

    Which dim of each tensor is sliced is decided by ``spec_for`` over
    ``param_axes`` — the exact walk a production mesh's shardings use — so
    the emulation and a real ``model``-axis mesh partition identically.
    Replicated tensors are shared by reference, not copied.
    """
    validate_tp(cfg, tp)
    if tp == 1:
        return [params]
    mesh = AbstractMesh(model=tp)
    axes = dict(TF.param_axes(cfg))
    axes["embed"] = (None, None)        # replicated (see module docstring)
    if "unembed" in axes:
        axes["unembed"] = (None, None)
    flat, treedef = jax.tree.flatten(params)
    axes_flat = treedef.flatten_up_to(axes)
    shards: List[Params] = []
    for s in range(tp):
        leaves = []
        for x, ax in zip(flat, axes_flat):
            spec = spec_for(x.shape, ax, mesh, DEFAULT_RULES)
            dim = next((i for i, part in enumerate(spec) if part == "model"),
                       None)
            if dim is None:
                leaves.append(x)
            else:
                width = x.shape[dim] // tp
                leaves.append(jax.lax.slice_in_dim(
                    x, s * width, (s + 1) * width, axis=dim))
        shards.append(jax.tree.unflatten(treedef, leaves))
    return shards


# ---------------------------------------------------------------------------
# Shard-and-merge layer bodies
# ---------------------------------------------------------------------------
def _merged_out_project(lps: Sequence[Params], outs: Sequence[jax.Array]
                        ) -> jax.Array:
    """Concat shard head-outputs + shard wo slices, ONE combine einsum."""
    out = jnp.concatenate(list(outs), axis=2)
    wo = jnp.concatenate([lp["wo"] for lp in lps], axis=0)
    return A.out_project({"wo": wo}, out)


def _sharded_mlp(lps: Sequence[Params], x: jax.Array, cfg: ModelConfig
                 ) -> Tuple[jax.Array, jax.Array]:
    """Column-parallel SwiGLU: per-shard gate/up, one full-width down."""
    hidden = jnp.concatenate(
        [M._act(jnp.einsum("bsd,df->bsf", x, lp["w_gate"]), cfg.activation)
         * jnp.einsum("bsd,df->bsf", x, lp["w_up"]) for lp in lps], axis=-1)
    w_down = jnp.concatenate([lp["w_down"] for lp in lps], axis=0)
    return jnp.einsum("bsf,fd->bsd", hidden, w_down), jnp.zeros((), jnp.float32)


def _sharded_moe(moe_ps: Sequence[Params], x: jax.Array, cfg: ModelConfig
                 ) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel dense-dispatch MoE (mirrors ``moe.moe_ffn``).

    Router logits are per-expert-column independent, so shard columns concat
    to the full logits exactly; routing (softmax / top-k / normalize) then
    runs replicated on the full tensor, expert matmuls run per shard on the
    expert slices, and the token combine reduces ONCE over the concatenated
    (B, S, E, D) expert outputs.
    """
    x32 = x.astype(jnp.float32)
    logits = jnp.concatenate(
        [jnp.einsum("bsd,de->bse", x32, p["router"].astype(jnp.float32))
         for p in moe_ps], axis=-1)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_idx = jax.lax.top_k(probs, cfg.top_k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    combine = jnp.zeros_like(probs).at[
        jnp.arange(probs.shape[0])[:, None, None],
        jnp.arange(probs.shape[1])[None, :, None],
        top_idx,
    ].set(top_p)
    combine = combine.astype(x.dtype)
    expert_out = jnp.concatenate(
        [jnp.einsum("bsef,efd->bsed",
                    MOE._act(jnp.einsum("bsd,edf->bsef", x, p["w_gate"]),
                             cfg.activation)
                    * jnp.einsum("bsd,edf->bsef", x, p["w_up"]),
                    p["w_down"]) for p in moe_ps], axis=2)
    out = jnp.einsum("bsed,bse->bsd", expert_out, combine)
    density = combine.astype(jnp.float32).mean(axis=(0, 1))
    router_prob = probs.mean(axis=(0, 1))
    aux = cfg.num_experts * jnp.sum(density * router_prob)
    return out, aux


def _sharded_ffn(lps: Sequence[Params], x: jax.Array, cfg: ModelConfig
                 ) -> Tuple[jax.Array, jax.Array]:
    if cfg.family == "moe":
        moe_ps = [{k[len("moe_"):]: v for k, v in lp.items()
                   if k.startswith("moe_")} for lp in lps]
        return _sharded_moe(moe_ps, x, cfg)
    return _sharded_mlp(lps, x, cfg)


def _kv_head_slices(arr: jax.Array, tp: int, axis: int) -> List[jax.Array]:
    """Contiguous kv-head slices of a full-width cache tensor."""
    width = arr.shape[axis] // tp
    return [jax.lax.slice_in_dim(arr, s * width, (s + 1) * width, axis=axis)
            for s in range(tp)]


# ---------------------------------------------------------------------------
# Entry points (mirror transformer.prefill / prefill_suffix /
# decode_step_paged with sharded layer bodies)
# ---------------------------------------------------------------------------
def sharded_prefill(shards: Sequence[Params], cfg: ModelConfig,
                    tokens: jax.Array
                    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Sharded twin of ``transformer.prefill``; returns the FULL-width cache
    (k/v (L, B, S, KV, hd)) so callers slice per shard when writing pools."""
    x = embed(tokens, shards[0]["embed"], scale=cfg.embed_scale)
    positions = jnp.arange(x.shape[1])[None, :]

    def body(carry, lps):
        h, aux = carry
        hn = rms_norm(h, lps[0]["norm_attn"], cfg.norm_eps)
        outs, ks, vs = [], [], []
        for lp in lps:
            o, (k, v) = A.self_attention_heads(lp, hn, cfg, positions,
                                               cfg.attn_window)
            outs.append(o), ks.append(k), vs.append(v)
        h = h + _merged_out_project(lps, outs)
        hn = rms_norm(h, lps[0]["norm_mlp"], cfg.norm_eps)
        ffn_out, aux_i = _sharded_ffn(lps, hn, cfg)
        return (h + ffn_out, aux + aux_i), (jnp.concatenate(ks, axis=2),
                                            jnp.concatenate(vs, axis=2))

    (x, _), (ks, vs) = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)),
        tuple(sp["layers"] for sp in shards))
    x = rms_norm(x[:, -1:], shards[0]["final_norm"], cfg.norm_eps)
    logits = unembed(x, shards[0].get("unembed", shards[0]["embed"]))[:, 0]
    length = jnp.full((tokens.shape[0],), ks.shape[2], jnp.int32)
    return logits, {"k": ks, "v": vs, "length": length}


def sharded_prefill_suffix(shards: Sequence[Params], cfg: ModelConfig,
                           tokens: jax.Array, prefix_k: jax.Array,
                           prefix_v: jax.Array
                           ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Sharded twin of ``transformer.prefill_suffix`` (chunked prefill /
    prefix-cache hits). prefix_k/v are FULL-width (L, B, C, KV, hd)."""
    tp = len(shards)
    x = embed(tokens, shards[0]["embed"], scale=cfg.embed_scale)
    c = prefix_k.shape[2]
    positions = c + jnp.arange(x.shape[1])[None, :]

    def body(carry, inputs):
        h, aux = carry
        lps, pk, pv = inputs
        hn = rms_norm(h, lps[0]["norm_attn"], cfg.norm_eps)
        outs, ks, vs = [], [], []
        for lp, pk_s, pv_s in zip(lps, _kv_head_slices(pk, tp, 2),
                                  _kv_head_slices(pv, tp, 2)):
            o, (k, v) = A.suffix_attention_heads(lp, hn, cfg, positions,
                                                 pk_s, pv_s, cfg.attn_window)
            outs.append(o), ks.append(k), vs.append(v)
        h = h + _merged_out_project(lps, outs)
        hn = rms_norm(h, lps[0]["norm_mlp"], cfg.norm_eps)
        ffn_out, aux_i = _sharded_ffn(lps, hn, cfg)
        return (h + ffn_out, aux + aux_i), (jnp.concatenate(ks, axis=2),
                                            jnp.concatenate(vs, axis=2))

    (x, _), (ks, vs) = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)),
        (tuple(sp["layers"] for sp in shards), prefix_k, prefix_v))
    x = rms_norm(x[:, -1:], shards[0]["final_norm"], cfg.norm_eps)
    logits = unembed(x, shards[0].get("unembed", shards[0]["embed"]))[:, 0]
    length = jnp.full((tokens.shape[0],), c + ks.shape[2], jnp.int32)
    return logits, {"k": ks, "v": vs, "length": length}


def sharded_decode_step_paged(shards: Sequence[Params], cfg: ModelConfig,
                              token: jax.Array,
                              pools: Sequence[jax.Array],
                              block_tables: jax.Array, lengths: jax.Array,
                              *, interpret: Optional[bool] = None
                              ) -> Tuple[jax.Array, Tuple[jax.Array, ...]]:
    """Sharded twin of ``transformer.decode_step_paged``.

    ``pools[s]`` is shard s's FLOWKV pool (same blocks/layers, its kv-head
    slice on axis 3). Each shard's pool is read in place by the paged kernel
    and takes its slice of the batch's new K/V through its own fused
    append — on a real mesh that is one dispatch per device, here
    ``tp`` calls inside one jitted step. The in-flight-token online-softmax
    merge runs ONCE on the concatenated kernel stats (the post-gather merge):
    its einsums are not bit-stable across kv-head extents, so a per-shard
    merge would drift from the single-device logits by an ulp.
    """
    from repro.kernels.kv_gather import kv_append_tokens
    from repro.kernels.paged_attention import paged_decode_attention

    x = embed(token[:, None], shards[0]["embed"], scale=cfg.embed_scale)
    position = lengths
    num_layers = pools[0].shape[1]

    def body(h, inputs):
        lps, layer = inputs
        hn = rms_norm(h, lps[0]["norm_attn"], cfg.norm_eps)
        pos = jnp.broadcast_to(jnp.asarray(position), (hn.shape[0],))
        q1s, k1s, v1s, outs, ms, ls = [], [], [], [], [], []
        for lp, pool in zip(lps, pools):
            q, k_new, v_new = A.qkv_project(lp, hn, cfg, pos[:, None])
            q1s.append(q[:, 0]), k1s.append(k_new[:, 0]), v1s.append(v_new[:, 0])
            o, m, l = paged_decode_attention(
                q[:, 0], pool, layer, block_tables, pos, interpret=interpret,
                return_stats=True)
            outs.append(o), ms.append(m), ls.append(l)
        kns, vns = k1s, v1s
        merged = A.merge_inflight_token(
            jnp.concatenate(q1s, axis=1), jnp.concatenate(k1s, axis=1),
            jnp.concatenate(v1s, axis=1), jnp.concatenate(outs, axis=1),
            jnp.concatenate(ms, axis=1), jnp.concatenate(ls, axis=1), hn.dtype)
        h = h + _merged_out_project(lps, [merged])
        hn = rms_norm(h, lps[0]["norm_mlp"], cfg.norm_eps)
        ffn_out, _ = _sharded_ffn(lps, hn, cfg)
        return h + ffn_out, (tuple(kns), tuple(vns))

    x, (ks, vs) = jax.lax.scan(
        body, x, (tuple(sp["layers"] for sp in shards),
                  jnp.arange(num_layers, dtype=jnp.int32)))
    new_pools = tuple(
        kv_append_tokens(pool, block_tables, position, ks[s], vs[s],
                         interpret=interpret)
        for s, pool in enumerate(pools))
    x = rms_norm(x, shards[0]["final_norm"], cfg.norm_eps)
    logits = unembed(x, shards[0].get("unembed", shards[0]["embed"]))[:, 0]
    return logits, new_pools
