"""Jit'd public wrapper: GQA expansion + dispatch (pallas | interpret | ref)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import flash_attention_pallas
from .ref import flash_attention_ref


def _expand_kv(k: jax.Array, n_heads: int) -> jax.Array:
    n_kv = k.shape[2]
    if n_kv == n_heads:
        return k
    return jnp.repeat(k, n_heads // n_kv, axis=2)


@functools.partial(jax.jit, static_argnames=("window", "softcap", "causal", "impl", "block_q", "block_k"))
def flash_attention(
    q: jax.Array,  # [B, T, H, hd]
    k: jax.Array,  # [B, T, Hkv, hd]
    v: jax.Array,
    *,
    window: int = 1 << 30,
    softcap: float = 0.0,
    causal: bool = True,
    impl: str,  # 'pallas' (TPU) | 'interpret' (CPU check) | 'ref'
    block_q: int = 128,
    block_k: int = 128,
) -> jax.Array:
    H = q.shape[2]
    k = _expand_kv(k, H)
    v = _expand_kv(v, H)
    if impl == "ref":
        return flash_attention_ref(q, k, v, window=window, softcap=softcap, causal=causal)
    return flash_attention_pallas(
        q, k, v, window=window, softcap=softcap, causal=causal,
        block_q=block_q, block_k=block_k, interpret=(impl == "interpret"),
    )
