"""Shared helpers for the Pallas kernels."""
from __future__ import annotations

import jax


def default_interpret(interpret: bool | None) -> bool:
    """Resolve the interpret flag: None = interpret mode off-TPU (kernel
    bodies execute in Python for correctness validation), compiled on TPU."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
