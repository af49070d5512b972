"""Jit'd wrapper for the RG-LRU scan kernel."""

from __future__ import annotations

import functools

import jax

from .kernel import rglru_scan_pallas
from .ref import rglru_scan_ref


@functools.partial(jax.jit, static_argnames=("impl", "block_t", "block_d"))
def rglru_scan(
    a: jax.Array,  # [B, T, D]
    b: jax.Array,
    h0: jax.Array,  # [B, D]
    *,
    impl: str,
    block_t: int = 256,
    block_d: int = 512,
) -> jax.Array:
    if impl == "ref":
        return rglru_scan_ref(a, b, h0)
    return rglru_scan_pallas(a, b, h0, block_t=block_t, block_d=block_d, interpret=(impl == "interpret"))
