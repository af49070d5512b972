"""Pure-jnp oracles for single-position decode attention (flat and paged).

The paged oracle gathers physical pages through the block table into the
flat layout and reuses the flat oracle verbatim, so flat-vs-paged parity is
bit-exact *by construction*: identical values flow through identical
arithmetic (`tests/test_paged_attention.py` pins this with
``np.testing.assert_array_equal``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..tiles import HIGHEST


def decode_attention_ref(
    q: jax.Array,  # [B, H, hd]
    k_cache: jax.Array,  # [B, S, H, hd]
    v_cache: jax.Array,
    lengths: jax.Array,  # [B]
    *,
    window: int = 1 << 30,
) -> jax.Array:
    B, S, H, hd = k_cache.shape
    s = jnp.einsum(
        "bhd,bkhd->bhk", q.astype(jnp.float32), k_cache.astype(jnp.float32), precision=HIGHEST
    )
    s = s / math.sqrt(hd)
    k_pos = jnp.arange(S)[None, :]
    valid = jnp.logical_and(k_pos < lengths[:, None], k_pos >= lengths[:, None] - window)
    s = jnp.where(valid[:, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhk,bkhd->bhd", p, v_cache.astype(jnp.float32), precision=HIGHEST)
    return o.astype(q.dtype)


def paged_gather(pages: jax.Array, block_tables: jax.Array) -> jax.Array:
    """Assemble the flat cache view from physical pages.

    ``pages: [P, bs, H, hd]`` and ``block_tables: [B, G]`` (int32 physical
    page ids; logical position ``p`` of lane ``b`` lives in page
    ``block_tables[b, p // bs]`` at slot ``p % bs``) gather to
    ``[B, G*bs, H, hd]``.  Pad table entries may hold any *valid* page id
    (the pool pads with 0): their positions sit past ``lengths`` and are
    masked by the attention oracle/kernel.
    """
    B, G = block_tables.shape
    P, bs, H, hd = pages.shape
    flat = jnp.take(pages, block_tables.reshape(-1), axis=0)  # [B*G, bs, H, hd]
    return flat.reshape(B, G * bs, H, hd)


def dequantize_pages(pages: jax.Array, scale: jax.Array, zero: jax.Array) -> jax.Array:
    """Affine-dequantize int8 pages to float32.

    ``pages: [P, bs, H, hd]`` int8, ``scale/zero: [P, bs, H]`` f32 →
    ``x_hat = (q + 128) * scale + zero``, the exact inverse the pool's
    ``write`` quantizer targets (``models/paged_kv.py``) and the arithmetic
    the q8 kernel performs in VMEM — so kernel-vs-ref parity on int8 pages
    is bit-exact, while int8-vs-fp32 parity is bounded by ``scale / 2`` per
    element.
    """
    return (pages.astype(jnp.float32) + 128.0) * scale[..., None] + zero[..., None]


def paged_decode_attention_q8_ref(
    q: jax.Array,  # [B, H, hd]
    k_pages: jax.Array,  # [P, bs, H, hd] int8
    v_pages: jax.Array,
    k_scale: jax.Array,  # [P, bs, H] f32
    k_zero: jax.Array,
    v_scale: jax.Array,
    v_zero: jax.Array,
    block_tables: jax.Array,  # [B, G]
    lengths: jax.Array,  # [B]
    *,
    window: int = 1 << 30,
) -> jax.Array:
    """Int8 paged oracle: dequantize pages, then the fp32 paged oracle."""
    k = dequantize_pages(k_pages, k_scale, k_zero)
    v = dequantize_pages(v_pages, v_scale, v_zero)
    return paged_decode_attention_ref(q, k, v, block_tables, lengths, window=window)


def paged_decode_attention_ref(
    q: jax.Array,  # [B, H, hd]
    k_pages: jax.Array,  # [P, bs, H, hd]
    v_pages: jax.Array,
    block_tables: jax.Array,  # [B, G] int32 physical page ids
    lengths: jax.Array,  # [B]
    *,
    window: int = 1 << 30,
) -> jax.Array:
    """Paged oracle: page gather + the flat oracle — bit-exact vs flat."""
    k = paged_gather(k_pages, block_tables)
    v = paged_gather(v_pages, block_tables)
    return decode_attention_ref(q, k, v, lengths, window=window)
