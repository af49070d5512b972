"""Pallas TPU flash attention (prefill): causal, GQA, sliding-window, softcap.

Grid layout: (batch·q_heads, num_q_blocks, num_k_blocks) with dimension
semantics ("parallel", "parallel", "arbitrary") — the k dimension iterates
sequentially per (bh, q-block) so the online-softmax running state (m, l,
acc) lives in VMEM scratch across k iterations and is finalized on the last
k block.

BlockSpecs tile Q/K/V into VMEM: q [1, BQ, hd], k/v [1, BK, hd]; the working
set per step is BQ·hd + 2·BK·hd + BQ·BK floats — with BQ=BK=128 and
hd≤256 this is ≤ ~0.4 MB, far under the ~16 MB v5e VMEM budget, and all
matmul dims are 128-aligned for the MXU.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BQ = 128
DEFAULT_BK = 128
NEG_INF = -1e30


def _flash_kernel(
    q_ref,  # [1, BQ, hd]
    k_ref,  # [1, BK, hd]
    v_ref,  # [1, BK, hd]
    o_ref,  # [1, BQ, hd]
    m_scr,  # [BQ] f32 scratch — running max
    l_scr,  # [BQ] f32 scratch — running denom
    acc_scr,  # [BQ, hd] f32 scratch — running numerator
    *,
    sm_scale: float,
    window: int,
    softcap: float,
    bq: int,
    bk: int,
    nk: int,
    causal: bool,
):
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)  # [BQ, hd]
    k = k_ref[0].astype(jnp.float32)  # [BK, hd]
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * sm_scale  # [BQ, BK]
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    q_pos = pl.program_id(1) * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    dist = q_pos - k_pos
    mask = dist < window
    if causal:
        mask = jnp.logical_and(mask, dist >= 0)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_scr[...] + jnp.sum(p, axis=-1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot(p, v)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(kb == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)[:, None]
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,  # [B, T, H, hd]
    k: jax.Array,  # [B, T, H, hd]  (GQA-expanded by the wrapper)
    v: jax.Array,
    *,
    window: int = 1 << 30,
    softcap: float = 0.0,
    causal: bool = True,
    block_q: int = DEFAULT_BQ,
    block_k: int = DEFAULT_BK,
    interpret: bool = False,
) -> jax.Array:
    B, T, H, hd = q.shape
    bq = min(block_q, T)
    bk = min(block_k, T)
    if T % bq or T % bk:
        raise ValueError(f"T={T} must be divisible by block sizes ({bq},{bk})")
    nq, nk = T // bq, T // bk
    sm_scale = 1.0 / math.sqrt(hd)
    # Layout: fold (B, H) into one grid axis; heads vary fastest.
    qr = q.transpose(0, 2, 1, 3).reshape(B * H, T, hd)
    kr = k.transpose(0, 2, 1, 3).reshape(B * H, T, hd)
    vr = v.transpose(0, 2, 1, 3).reshape(B * H, T, hd)
    kernel = functools.partial(
        _flash_kernel,
        sm_scale=sm_scale,
        window=int(window),
        softcap=float(softcap),
        bq=bq,
        bk=bk,
        nk=nk,
        causal=causal,
    )
    out = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, T, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(B, H, T, hd).transpose(0, 2, 1, 3)
