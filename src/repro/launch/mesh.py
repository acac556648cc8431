"""Production mesh builders.

Kept as FUNCTIONS so importing this module never touches jax device state
(the dry-run must set XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    # Auto axes: the sharding rules constrain with with_sharding_constraint,
    # which refuses the Explicit axes jax.make_mesh builds by default
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod.

    Single-pod axes: ("data", "model"). Multi-pod adds a leading "pod" axis —
    in training it is extra data parallelism over DCN; in FlowKV serving it
    is the P/D boundary (pod 0 = prefill cluster, pod 1 = decode cluster).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1, pod: int = 0):
    """CPU-scale mesh for tests/examples (requires devices to exist)."""
    if pod:
        return _mesh((pod, data, model), ("pod", "data", "model"))
    return _mesh((data, model), ("data", "model"))
