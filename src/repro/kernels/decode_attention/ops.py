"""Jit'd wrappers for decode attention (GQA expansion + impl dispatch).

Two entries share one dispatch convention (``impl``: ``'ref'`` pure-JAX
oracle, ``'interpret'`` Pallas interpret mode for CPU, ``'pallas'`` compiled
TPU):

* ``decode_attention`` — flat contiguous cache ``[B, S, Hkv, hd]``;
* ``paged_decode_attention`` — global page pool ``[P, bs, Hkv, hd]`` +
  per-lane block tables (``models/paged_kv.py``), the serving layout where
  sessions share prefix pages copy-on-write.  Ragged python block tables are
  padded through ``kernels.spec_verify.pad_block_tables`` (the same pow2
  bucketing as the batched NAV entries, pad id 0 = valid page, masked by
  ``lengths``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..spec_verify.ops import pad_block_tables
from .kernel import (
    decode_attention_pallas,
    paged_decode_attention_pallas,
    paged_decode_attention_q8_pallas,
)
from .ref import (
    decode_attention_ref,
    paged_decode_attention_q8_ref,
    paged_decode_attention_ref,
)


@functools.partial(jax.jit, static_argnames=("window", "impl", "block_k"))
def decode_attention(
    q: jax.Array,  # [B, H, hd]
    k_cache: jax.Array,  # [B, S, Hkv, hd]
    v_cache: jax.Array,
    lengths: jax.Array,  # [B]
    *,
    window: int = 1 << 30,
    impl: str,
    block_k: int = 512,
) -> jax.Array:
    """Single-position decode attention over a flat contiguous KV cache."""
    H = q.shape[1]
    n_kv = k_cache.shape[2]
    if n_kv != H:
        k_cache = jnp.repeat(k_cache, H // n_kv, axis=2)
        v_cache = jnp.repeat(v_cache, H // n_kv, axis=2)
    if impl == "ref":
        return decode_attention_ref(q, k_cache, v_cache, lengths, window=window)
    return decode_attention_pallas(
        q, k_cache, v_cache, lengths, window=window, block_k=block_k, interpret=(impl == "interpret")
    )


@functools.partial(jax.jit, static_argnames=("window", "impl"))
def _paged_dispatch(q, k_pages, v_pages, block_tables, lengths, *, window, impl):
    H = q.shape[1]
    n_kv = k_pages.shape[2]
    if n_kv != H:
        k_pages = jnp.repeat(k_pages, H // n_kv, axis=2)
        v_pages = jnp.repeat(v_pages, H // n_kv, axis=2)
    if impl == "ref":
        return paged_decode_attention_ref(q, k_pages, v_pages, block_tables, lengths, window=window)
    return paged_decode_attention_pallas(
        q, k_pages, v_pages, block_tables, lengths, window=window, interpret=(impl == "interpret")
    )


@functools.partial(jax.jit, static_argnames=("window", "impl"))
def _paged_q8_dispatch(q, k_pages, v_pages, quant, block_tables, lengths, *, window, impl):
    H = q.shape[1]
    n_kv = k_pages.shape[2]
    if n_kv != H:
        k_pages = jnp.repeat(k_pages, H // n_kv, axis=2)
        v_pages = jnp.repeat(v_pages, H // n_kv, axis=2)
        quant = tuple(jnp.repeat(p, H // n_kv, axis=2) for p in quant)
    ks, kz, vs, vz = quant
    if impl == "ref":
        return paged_decode_attention_q8_ref(
            q, k_pages, v_pages, ks, kz, vs, vz, block_tables, lengths, window=window
        )
    return paged_decode_attention_q8_pallas(
        q, k_pages, v_pages, ks, kz, vs, vz, block_tables, lengths,
        window=window, interpret=(impl == "interpret"),
    )


def paged_decode_attention(
    q: jax.Array,  # [B, H, hd]
    k_pages: jax.Array,  # [P, bs, Hkv, hd]  (int8 payload when quantized)
    v_pages: jax.Array,
    block_tables,  # [B, G] int32 array, or B ragged python page-id lists
    lengths: jax.Array,  # [B]
    *,
    window: int = 1 << 30,
    impl: str,
    bucket: bool = True,
    quant=None,  # (k_scale, k_zero, v_scale, v_zero), each [P, bs, Hkv] f32
    pad_page_id: int = 0,
) -> jax.Array:
    """Single-position decode attention gathered through KV block tables.

    ``block_tables`` may be a rectangular ``[B, G]`` int32 array (e.g. from
    ``PagedKVPool.table(sid, pad_to=G)``) or ragged per-lane page-id lists,
    which are padded here with the serving bucketing (``pad_block_tables``)
    using ``pad_page_id`` — pass the pool's ``sentinel_page`` so padded
    lanes never DMA another session's pages.  Bit-exact vs the flat entry on
    the same logical cache: ``impl='ref'`` by construction (page gather +
    flat oracle), Pallas impls by streaming pages in the flat kernel's
    block order (``tests/test_paged_attention.py``).

    With ``quant`` (the pool's four affine-parameter planes), pages are
    int8 and dequantized in-kernel; output error vs the fp32 cache is
    bounded per ``docs/kernels.md`` §7.
    """
    if isinstance(block_tables, (list, tuple)):
        block_tables = pad_block_tables(
            block_tables, batch_pad=len(block_tables), bucket=bucket, pad_id=pad_page_id
        )
    block_tables = jnp.asarray(block_tables, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    if quant is not None:
        return _paged_q8_dispatch(
            q, k_pages, v_pages, tuple(quant), block_tables, lengths,
            window=window, impl=impl,
        )
    return _paged_dispatch(
        q, k_pages, v_pages, block_tables, lengths, window=window, impl=impl
    )
