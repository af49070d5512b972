"""Pallas TPU chunked RG-LRU linear-recurrence scan.

Computes h_t = a_t ⊙ h_{t-1} + b_t over long sequences.  Grid:
(batch, channel_blocks, time_blocks) with the time dimension "arbitrary":
the hidden state (one [BD] vector) persists in VMEM scratch across time
blocks, and each block runs a short sequential ``fori_loop`` over its BT
steps entirely in VMEM — HBM traffic is exactly one read of (a, b) and one
write of h (the memory-bound optimum for this op).

This is the TPU adaptation of the paper-family's CUDA linear-scan kernels:
instead of warp-level scans, VMEM residency + the 8×128 VPU lanes do the
work; the sequential dependency only crosses time *blocks*, not HBM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BT = 256
DEFAULT_BD = 512


def _rglru_kernel(
    a_ref,  # [1, BT, BD]
    b_ref,  # [1, BT, BD]
    h0_ref,  # [1, BD]
    o_ref,  # [1, BT, BD]
    h_scr,  # [BD] f32 carried hidden state
    *,
    bt: int,
):
    tb = pl.program_id(2)

    @pl.when(tb == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(jnp.float32)

    a = a_ref[0].astype(jnp.float32)
    b = b_ref[0].astype(jnp.float32)

    def step(t, h):
        h = a[t] * h + b[t]
        o_ref[0, t, :] = h.astype(o_ref.dtype)
        return h

    h_scr[...] = jax.lax.fori_loop(0, bt, step, h_scr[...])


def rglru_scan_pallas(
    a: jax.Array,  # [B, T, D]
    b: jax.Array,  # [B, T, D]
    h0: jax.Array,  # [B, D]
    *,
    block_t: int = DEFAULT_BT,
    block_d: int = DEFAULT_BD,
    interpret: bool = False,
) -> jax.Array:
    B, T, D = a.shape
    bt = min(block_t, T)
    bd = min(block_d, D)
    if T % bt or D % bd:
        raise ValueError(f"(T={T}, D={D}) must divide into blocks ({bt},{bd})")
    nt, nd = T // bt, D // bd
    kernel = functools.partial(_rglru_kernel, bt=bt)
    return pl.pallas_call(
        kernel,
        grid=(B, nd, nt),
        in_specs=[
            pl.BlockSpec((1, bt, bd), lambda i, d, t: (i, t, d)),
            pl.BlockSpec((1, bt, bd), lambda i, d, t: (i, t, d)),
            pl.BlockSpec((1, bd), lambda i, d, t: (i, d)),
        ],
        out_specs=pl.BlockSpec((1, bt, bd), lambda i, d, t: (i, t, d)),
        out_shape=jax.ShapeDtypeStruct((B, T, D), a.dtype),
        scratch_shapes=[pltpu.VMEM((bd,), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(a, b, h0)
