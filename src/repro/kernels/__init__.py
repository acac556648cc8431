"""Pallas kernels of the serving path, and the one rule for how they run."""
from __future__ import annotations

from typing import Optional

import jax


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """Whether a Pallas kernel runs in interpret mode.

    ``None`` resolves by the default backend: compiled Mosaic on a TPU, the
    Pallas interpreter everywhere else (the CPU test suite). An explicit
    bool wins, so a test that compiles for a described TPU from a CPU
    process passes ``interpret=False``.
    """
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
