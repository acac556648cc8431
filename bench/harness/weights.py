"""Random weights made on the device from ``--seed``, in the type served.

The benchmark, not the program, makes the weights, so that the float32
reference can use the very same numbers without taking anything the program
made. The tree has the program's parameter layout (``repro.models``'
stacked-layer transformer): ``embed``, ``final_norm`` and ``layers`` with
``wq (L, d, H, hd)``, ``wk``/``wv (L, d, KV, hd)``, ``wo (L, H, hd, d)``,
``q_norm``/``k_norm (L, hd)``, ``norm_attn``/``norm_mlp (L, d)`` and either
``w_gate``/``w_up (L, d, f)``, ``w_down (L, f, d)`` or, for experts,
``moe_router (L, d, E)``, ``moe_w_gate``/``moe_w_up (L, E, d, f)``,
``moe_w_down (L, E, f, d)``. Norm weights are stored as offsets from 1: the
published weight of an RMSNorm is ``1 + norm``.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

NORM_SCALE = 0.1      # spread of the norm weights around 1
EMBED_SCALE = 0.02    # the published models' initializer range


def shapes(s: Dict[str, Any]) -> Dict[str, Any]:
    """{path: (shape, fan_in or None for norms)} of the weight tree."""
    L, d, H, KV, hd, f = (s["layers"], s["d_model"], s["heads"], s["kv_heads"],
                          s["head_dim"], s["d_ff"])
    layer: Dict[str, Tuple[Tuple[int, ...], Any]] = {
        "wq": ((L, d, H, hd), d), "wk": ((L, d, KV, hd), d),
        "wv": ((L, d, KV, hd), d), "wo": ((L, H, hd, d), H * hd),
        "norm_attn": ((L, d), None), "norm_mlp": ((L, d), None),
    }
    if s["qk_norm"]:
        layer["q_norm"] = ((L, hd), None)
        layer["k_norm"] = ((L, hd), None)
    if s["family"] == "moe":
        E = s["experts"]
        layer.update({"moe_router": ((L, d, E), d),
                      "moe_w_gate": ((L, E, d, f), d), "moe_w_up": ((L, E, d, f), d),
                      "moe_w_down": ((L, E, f, d), f)})
    else:
        layer.update({"w_gate": ((L, d, f), d), "w_up": ((L, d, f), d),
                      "w_down": ((L, f, d), f)})
    tree = {"embed": ((s["vocab"], d), "embed"), "final_norm": ((d,), None),
            "layers": layer}
    if not s["tied"]:
        tree["unembed"] = ((s["vocab"], d), "embed")
    return tree


def make(s: Dict[str, Any], seed: int):
    """The weight tree of shape ``s`` from ``seed``, made in one jitted call."""
    dtype = getattr(jnp, s["dtype"])
    spec = shapes(s)
    leaves, treedef = jax.tree.flatten(spec, is_leaf=lambda x: isinstance(x, tuple)
                                       and len(x) == 2 and isinstance(x[0], tuple))

    def init(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, (shape, fan_in) in zip(keys, leaves):
            if fan_in is None:
                scale = NORM_SCALE
            elif fan_in == "embed":
                scale = EMBED_SCALE
            else:
                scale = fan_in ** -0.5
            out.append((jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(init)(seed_key(seed))


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number, 64-bit seeds included."""
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)
