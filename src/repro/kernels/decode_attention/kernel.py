"""Pallas TPU decode attention (flash-decode): one query vs a long KV cache.

The serving hot path for ``decode_32k`` / ``long_500k``: a single new token's
query attends over S cached keys.  Grid: (batch, num_kv_blocks) with the kv
dimension "arbitrary" so online-softmax state (m, l, acc — per head) lives in
VMEM scratch across kv blocks.  KV blocks of [BK, hd] per head stream through
VMEM; per-lane valid lengths mask dead slots, and a sliding window bounds the
live region for local-attention layers.

Working set per step: H·hd (q) + 2·BK·H·hd (k,v) + H·BK (scores) floats —
BK=512, H≤64, hd≤256 stays well under VMEM.  The step itself is
``tiles.attend_page``, which the fused verify kernel shares; lengths (and
the paged entries' block tables) are scalar-prefetched.

**Paged variant** (``paged_decode_attention_pallas``): the KV cache lives in
a global page pool (``models/paged_kv.py``) instead of one contiguous buffer
per lane.  The grid stays (batch, pages-per-sequence), but the kv BlockSpec's
index map reads the *block table* — scalar-prefetched via
``pltpu.PrefetchScalarGridSpec`` so page ids are known before the kernel body
runs — to DMA physical page ``table[b, g]`` where the flat kernel would load
contiguous block ``g``.  With the page size matching the flat kernel's
``block_k``, the two kernels stream identical values in identical order, so
their outputs are bit-exact (pinned by ``tests/test_paged_attention.py``).
Pad table entries must hold valid page ids (the pool pads with its
zero-filled sentinel page); their positions sit past ``lengths`` and are
masked like any dead slot.

**Int8 variant** (``paged_decode_attention_q8_pallas``): pages carry int8
payload plus per-(slot, head) float32 ``scale``/``zero`` (affine over
``head_dim``; ``models/paged_kv.py``).  The scale/zero pages ride the same
block-table index map as the payload, and the kernel dequantizes in VMEM —
``x_hat = (q + 128) * scale + zero`` — before the identical online-softmax
math, so HBM traffic drops to ~1/4 + params while the arithmetic matches
the fp32 kernel on the dequantized values bit-for-bit.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..tiles import NEG_INF, attend_page

DEFAULT_BK = 512


def _attend_kernel(
    len_ref,  # [B] i32 scalar-prefetch — valid KV length per lane
    *rest,  # [paged: bt [B, G] prefetch] q, k, v, [quant: ks/kz/vs/vz], o, m/l/acc
    sm_scale: float,
    window: int,
    bk: int,
    nk: int,
    paged: bool,
    quantized: bool,
):
    """Flash-decode over kv blocks; the flat and both paged entries share it.

    ``q [1, H, hd]``; ``k, v [1, bk, H, hd]`` — kv block j (flat) or physical
    page ``bt[b, j]`` (paged, the table rides the index map).  In-VMEM
    affine dequant for int8 pages: ``x_hat = (q + 128) * scale + zero``,
    params broadcast over head_dim — ``PagedKVPool.dequantize_kv``.
    """
    q_ref, k_ref, v_ref, *rest = rest[1:] if paged else rest
    if quantized:
        ks_ref, kz_ref, vs_ref, vz_ref = rest[:4]
        rest = rest[4:]
    o_ref, m_scr, l_scr, acc_scr = rest
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    if quantized:
        k = (k_ref[0].astype(jnp.float32) + 128.0) * ks_ref[0][..., None] + kz_ref[0][..., None]
        v = (v_ref[0].astype(jnp.float32) + 128.0) * vs_ref[0][..., None] + vz_ref[0][..., None]
    else:
        k = k_ref[0].astype(jnp.float32)  # [bk, H, hd]
        v = v_ref[0].astype(jnp.float32)
    # Logical positions: block j covers [j*bk, (j+1)*bk) regardless of which
    # physical page backs it — the table indirection is purely in the DMA.
    k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1, 1), 0)
    m_scr[...], l_scr[...], acc_scr[...] = attend_page(
        q_ref[0].astype(jnp.float32), k, v, k_pos, len_ref[b],
        m_scr[...], l_scr[...], acc_scr[...], sm_scale=sm_scale, window=window,
    )

    @pl.when(j == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def _launch(q, kv, quant, lengths, block_tables, *, bk, nk, window, interpret):
    """One ``(B, nk)`` flash-decode launch; paged when ``block_tables`` is given."""
    B, H, hd = q.shape
    kernel = functools.partial(
        _attend_kernel, sm_scale=1.0 / math.sqrt(hd), window=int(window), bk=bk, nk=nk,
        paged=block_tables is not None, quantized=quant is not None,
    )
    if block_tables is None:
        prefetch = (lengths.astype(jnp.int32),)
        kv_ix = lambda b, j, ln: (b, j, 0, 0)  # noqa: E731
        param_ix = None
        q_ix = lambda b, j, ln: (b, 0, 0)  # noqa: E731
    else:
        prefetch = (lengths.astype(jnp.int32), block_tables.astype(jnp.int32))
        kv_ix = lambda b, j, ln, bt: (bt[b, j], 0, 0, 0)  # noqa: E731
        param_ix = lambda b, j, ln, bt: (bt[b, j], 0, 0)  # noqa: E731
        q_ix = lambda b, j, ln, bt: (b, 0, 0)  # noqa: E731
    in_specs = [pl.BlockSpec((1, H, hd), q_ix)] + [pl.BlockSpec((1, bk, H, hd), kv_ix)] * 2
    operands = [q, *kv]
    if quant is not None:
        in_specs += [pl.BlockSpec((1, bk, H), param_ix)] * 4
        operands += [p.astype(jnp.float32) for p in quant]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(B, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, H, hd), q_ix),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*prefetch, *operands)


def decode_attention_pallas(
    q: jax.Array,  # [B, H, hd] — single-position queries
    k_cache: jax.Array,  # [B, S, H, hd]  (GQA-expanded by the wrapper)
    v_cache: jax.Array,
    lengths: jax.Array,  # [B] i32 valid prefix per lane
    *,
    window: int = 1 << 30,
    block_k: int = DEFAULT_BK,
    interpret: bool = False,
) -> jax.Array:
    B, S, H, hd = k_cache.shape
    bk = min(block_k, S)
    if S % bk:
        raise ValueError(f"S={S} must be divisible by block_k={bk}")
    return _launch(
        q, (k_cache, v_cache), None, lengths, None,
        bk=bk, nk=S // bk, window=window, interpret=interpret,
    )


def _check_pages(q, k_pages):
    if k_pages.shape[2] != q.shape[1]:
        raise ValueError(f"pages must be GQA-expanded: {k_pages.shape[2]} heads vs {q.shape[1]} queries")


def paged_decode_attention_pallas(
    q: jax.Array,  # [B, H, hd] — single-position queries
    k_pages: jax.Array,  # [P, bs, H, hd]  (GQA-expanded by the wrapper)
    v_pages: jax.Array,
    block_tables: jax.Array,  # [B, G] i32 physical page ids (pads = any valid id)
    lengths: jax.Array,  # [B] i32 valid prefix per lane
    *,
    window: int = 1 << 30,
    interpret: bool = False,
) -> jax.Array:
    """Flash-decode over a paged KV pool: block-table gather via scalar prefetch.

    Grid (B, G); kv page ``g`` of lane ``b`` streams from physical page
    ``block_tables[b, g]`` — the BlockSpec index map reads the prefetched
    table, so the DMA engine chases the indirection, not the kernel body.
    """
    _check_pages(q, k_pages)
    return _launch(
        q, (k_pages, v_pages), None, lengths, block_tables,
        bk=k_pages.shape[1], nk=block_tables.shape[1], window=window, interpret=interpret,
    )


def paged_decode_attention_q8_pallas(
    q: jax.Array,  # [B, H, hd]
    k_pages: jax.Array,  # [P, bs, H, hd] int8  (GQA-expanded by the wrapper)
    v_pages: jax.Array,
    k_scale: jax.Array,  # [P, bs, H] f32 — affine params over head_dim
    k_zero: jax.Array,
    v_scale: jax.Array,
    v_zero: jax.Array,
    block_tables: jax.Array,  # [B, G] i32 physical page ids
    lengths: jax.Array,  # [B] i32
    *,
    window: int = 1 << 30,
    interpret: bool = False,
) -> jax.Array:
    """Paged flash-decode over int8 pages with in-kernel affine dequant.

    Same grid and DMA indirection as ``paged_decode_attention_pallas``; the
    four quant-param planes ride the identical ``bt[b, g]`` index map so a
    page's payload and parameters always arrive together.
    """
    _check_pages(q, k_pages)
    if k_pages.dtype != jnp.int8:
        raise TypeError(f"q8 entry needs int8 pages, got {k_pages.dtype}")
    return _launch(
        q, (k_pages, v_pages), (k_scale, k_zero, v_scale, v_zero), lengths, block_tables,
        bk=k_pages.shape[1], nk=block_tables.shape[1], window=window, interpret=interpret,
    )
