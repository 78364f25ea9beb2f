"""Shared kernel utilities."""
from __future__ import annotations

import functools

import jax


@functools.lru_cache(None)
def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def interpret_default() -> bool:
    """Pallas interpret mode: True off-TPU (CPU correctness runs)."""
    return not on_tpu()


def cdiv(a: int, b: int) -> int:
    return -(-a // b)
