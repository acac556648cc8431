"""Random weights made on the device from ``--seed``, in the type served.

The benchmark, not the program, makes the weights, so that the float32
reference can use the very same numbers without taking anything the program
made. The tree, in the program's parameter layout, is the family's
(``reference/<family>.py`` ``weight_shapes``): ``{path: (shape, init)}``,
where ``init`` is a projection's fan-in (``N(0, 1/fan_in)``) or a name in
``INITS``. Norm weights are stored as offsets from 1: the published weight
of an RMSNorm is ``1 + norm``.
"""
from __future__ import annotations

from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

from harness import spec

NORM_SCALE = 0.1      # spread of the norm weights around 1
EMBED_SCALE = 0.02    # the published models' initializer range


def normal(scale: float) -> Callable:
    return lambda key, shape: jax.random.normal(key, shape, jnp.float32) * scale


# The inits a family may name besides a fan-in.
INITS: Dict[Any, Callable] = {
    None: normal(NORM_SCALE),                                  # a norm's offset from 1
    "embed": normal(EMBED_SCALE),
    "zeros": lambda key, shape: jnp.zeros(shape, jnp.float32),  # e.g. a routing bias
}


def initializer(init: Any) -> Callable:
    return normal(init ** -0.5) if isinstance(init, int) else INITS[init]


def make(s: Dict[str, Any], seed: int):
    """The weight tree of shape ``s`` from ``seed``, made in one jitted call."""
    dtype = getattr(jnp, s["dtype"])
    tree = spec.family(s["family"]).weight_shapes(s)
    leaves, treedef = jax.tree.flatten(tree, is_leaf=lambda x: isinstance(x, tuple)
                                       and len(x) == 2 and isinstance(x[0], tuple))

    def init(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(treedef, [initializer(kind)(k, shape).astype(dtype)
                                            for k, (shape, kind) in zip(keys, leaves)])

    return jax.jit(init)(seed_key(seed))


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number, 64-bit seeds included."""
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)
