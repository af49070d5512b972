"""Tensor-parallel spec-verify: the fused target forward as ONE sharded launch.

The unsharded fused verify (``kernels.spec_verify.spec_verify_fused``) runs
paged target attention + blocked LM-head projection + the NAV scan in one
launch.  This module shards that SAME launch across a 1-D ``("model",)``
device mesh via ``shard_map`` while keeping the entry signature — the
dispatcher and router never learn the shard count:

* **Attention — head-parallel.**  Queries and the (GQA-expanded) KV pages
  split on the head axis; each shard runs the paged-attention oracle over
  its local heads only.  Per-head attention is independent, so a head slice
  is bitwise identical to the same heads of the full computation, and the
  ``all_gather`` that reassembles ``[B*K1, H, hd]`` is pure concatenation.
  Head counts that don't divide the mesh (GQA ratios, odd H) are zero-padded
  to the next multiple of ``shards``; padded head lanes compute finite
  garbage that is sliced off right after the gather.
* **LM head — vocab-parallel (Megatron column style).**  Each shard holds a
  ``[F, Vs]`` column slice of the LM head (``Vs`` a ``block_v`` multiple)
  and issues the SAME per-head ``lm_head_tile`` products as
  ``fused_target_logits`` — full contraction dim, local vocab tiles — so
  every logit is produced by identical arithmetic on one shard.  Padded
  vocab ids are masked to ``-1e30`` with GLOBAL ids before the vocab
  ``all_gather``, preserving the unsharded masking contract.
* **NAV scan — replicated.**  After the gather every shard holds the full
  ``[B, K1, Vp]`` logits and runs ``spec_verify_ref`` redundantly; outputs
  are replicated (``check_vma=False`` + fully-replicated out specs).
* **int8 pages.**  Quantized pools shard the affine ``scale``/``zero``
  planes WITH their KV on the head axis; dequantization is per-element, so
  local dequant of a head slice is bitwise identical to slicing a global
  dequant.
* **Per-device block tables.**  Block tables, lengths, tokens and
  ``n_drafted`` are replicated — every device holds the full table, and the
  sentinel-page padding contract (``pad_block_tables``) holds per shard
  because each shard's page buffer keeps the zero-filled sentinel page in
  its local head slice.

Bit-exactness (``tests/test_sharded_verify.py``): the jitted sharded launch
is ``assert_array_equal``-exact against the jitted unsharded oracle — the
comparison that matters, since XLA's eager-vs-jit fusion already perturbs
attention by ~1 ulp while two jitted programs agree bitwise on a host mesh.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels.decode_attention.ref import dequantize_pages, paged_decode_attention_ref
from repro.kernels.spec_verify.ops import fused_operands, vocab_tiles
from repro.kernels.spec_verify.ref import lm_head_logits, spec_verify_ref

__all__ = [
    "MODEL_AXIS",
    "ShardPlan",
    "plan_shards",
    "spec_verify_sharded",
]

MODEL_AXIS = "model"


# --------------------------------------------------------------------------- #
# Shard planning (padding geometry + divisibility metadata)
# --------------------------------------------------------------------------- #


@dataclass(frozen=True)
class ShardPlan:
    """Padding geometry for one sharded verify launch.

    ``heads`` is the QUERY head count (KV is GQA-expanded to it before the
    head split); ``padded_heads`` is the zero-padded head count actually
    split over the mesh.  ``vocab_per_shard`` is each shard's LM-head column
    width — a ``block_v`` multiple, so the per-shard projection issues the
    same vocab tiles as the unsharded blocked LM head.
    """

    shards: int
    heads: int  # H (query heads; KV expands to this)
    kv_heads: int  # Hkv as stored in the pool
    head_dim: int
    padded_heads: int  # Hp = ceil(H / shards) * shards
    vocab: int  # true vocab V
    padded_vocab: int  # Vp = ceil(V / block_v) * block_v (unsharded padding)
    vocab_per_shard: int  # Vs, a block_v multiple
    block_v: int

    @property
    def heads_per_shard(self) -> int:
        return self.padded_heads // self.shards

    @property
    def launch_vocab(self) -> int:
        """Total LM-head columns in the sharded launch (``shards * Vs``)."""
        return self.shards * self.vocab_per_shard

    @property
    def even_heads(self) -> bool:
        """True iff the query heads split without zero-padded lanes."""
        return self.heads % self.shards == 0

    @property
    def even_kv_heads(self) -> bool:
        """True iff the pool's KV head axis splits without replication."""
        return self.kv_heads % self.shards == 0


def plan_shards(
    *,
    shards: int,
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    vocab: int,
    block_v: int = 2048,
) -> ShardPlan:
    """Compute the padding geometry for a ``shards``-way verify launch."""
    if shards < 1:
        raise ValueError(f"shards must be >= 1, got {shards}")
    if n_heads % max(n_kv_heads, 1):
        raise ValueError(f"n_heads={n_heads} not a multiple of n_kv_heads={n_kv_heads}")
    bv, vp = vocab_tiles(vocab, block_v)
    vs = -(-vp // (shards * bv)) * bv
    hp = -(-n_heads // shards) * shards
    return ShardPlan(
        shards=shards,
        heads=n_heads,
        kv_heads=n_kv_heads,
        head_dim=head_dim,
        padded_heads=hp,
        vocab=vocab,
        padded_vocab=vp,
        vocab_per_shard=vs,
        block_v=bv,
    )


# --------------------------------------------------------------------------- #
# The one sharded launch
# --------------------------------------------------------------------------- #


def _pad_axis(x: jax.Array, axis: int, to: int) -> jax.Array:
    if x.shape[axis] == to:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, to - x.shape[axis])
    return jnp.pad(x, widths)


@functools.lru_cache(maxsize=None)
def _build_launch(
    mesh: Mesh,
    *,
    heads: int,
    head_dim: int,
    v_true: int,
    padded_vocab: int,
    vocab_per_shard: int,
    block_v: int,
    window: int,
    quantized: bool,
):
    """Jitted shard_map launch, cached per (mesh, static geometry).

    The body mirrors ``spec_verify_fused_ref`` stage for stage: per-shard
    paged attention on the local head slice, head ``all_gather`` + slice to
    the true head count, per-shard ``block_v`` vocab tiles with the FULL
    contraction dim, global-id masking, vocab ``all_gather``, then the
    replicated NAV scan.
    """
    H, hd, Vp, Vs, bv = heads, head_dim, padded_vocab, vocab_per_shard, block_v

    def body(q, kp, vp, w, tables, lengths, tokens, nd, *quant):
        B, K1 = q.shape[0], q.shape[1]
        if quantized:
            ks, kz, vs_, vz = quant
            kp = dequantize_pages(kp, ks, kz)
            vp = dequantize_pages(vp, vs_, vz)
        qf = q.reshape(B * K1, q.shape[2], hd)
        tf = jnp.repeat(tables, K1, axis=0)
        lf = lengths.reshape(-1)
        o = paged_decode_attention_ref(qf, kp, vp, tf, lf, window=window)
        o = jax.lax.all_gather(o, MODEL_AXIS, axis=1, tiled=True)
        o = o[:, :H].reshape(B, K1, H, hd).astype(jnp.float32)
        # Same vocab tiles as fused_target_logits, restricted to this
        # shard's LM-head columns — identical per-logit arithmetic.
        logits = lm_head_logits(o, w, block_v=bv)  # [B, K1, Vs]
        shard = jax.lax.axis_index(MODEL_AXIS)
        ids = shard * Vs + jnp.arange(Vs)[None, None, :]
        logits = jnp.where(ids >= v_true, -1e30, logits)
        logits = jax.lax.all_gather(logits, MODEL_AXIS, axis=2, tiled=True)
        return spec_verify_ref(logits[:, :, :Vp], tokens, nd)

    head4 = P(None, None, MODEL_AXIS, None)  # [*, *, heads, hd]
    quant_specs = (P(None, None, MODEL_AXIS),) * 4 if quantized else ()
    in_specs = (
        head4,  # q [B, K1, Hp, hd]
        head4,  # k_pages [P, bs, Hp, hd]
        head4,  # v_pages
        P(None, MODEL_AXIS),  # w [F, shards * Vs]
        P(None, None),  # tables (replicated per device)
        P(None, None),  # lengths
        P(None, None),  # tokens
        P(None),  # n_drafted
    ) + quant_specs
    out_specs = (P(None, None), P(None, None), P(None, None))
    return jax.jit(
        jax.shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False)
    )


def _prepare(q, draft_tokens, k_pages, v_pages, w, mesh: Mesh, *, v_true, block_v: int, quant):
    """The fused operands (``fused_operands``), head-padded and vocab-padded for the mesh."""
    V = w.shape[1]
    plan = plan_shards(
        shards=int(np.prod(list(mesh.shape.values()))), n_heads=q.shape[2],
        n_kv_heads=k_pages.shape[2], head_dim=q.shape[3], vocab=V, block_v=block_v,
    )
    q, k_pages, v_pages, quant = fused_operands(q, draft_tokens, k_pages, v_pages, quant)
    q = _pad_axis(q, 2, plan.padded_heads)
    k_pages = _pad_axis(k_pages, 2, plan.padded_heads)
    v_pages = _pad_axis(v_pages, 2, plan.padded_heads)
    if quant is not None:
        # Zero scale/zero planes dequantize padded head lanes to 0.0 — finite
        # garbage sliced off after the head gather, like the fp32 zero pad.
        quant = tuple(_pad_axis(p, 2, plan.padded_heads) for p in quant)
    w = _pad_axis(
        _pad_axis(w.astype(jnp.float32), 1, plan.padded_vocab), 1, plan.launch_vocab
    )
    return q, k_pages, v_pages, w, quant, plan, int(V if v_true is None else v_true)


def spec_verify_sharded(
    q: jax.Array,  # [R, P, H, hd] per-position queries; reads q[:B, :K+1]
    k_pages: jax.Array,  # [P, bs, Hkv, hd] (int8 payload when quant is given)
    v_pages: jax.Array,
    w: jax.Array,  # [H*hd, V] LM head
    block_tables: jax.Array,  # [B, G] i32 physical page ids
    lengths: jax.Array,  # [B, K+1] i32 valid KV length per query position
    draft_tokens: jax.Array,  # [B, K] i32
    n_drafted: jax.Array,  # [B] i32
    *,
    mesh: Mesh,
    v_true: Optional[int] = None,
    block_v: int = 2048,
    window: int = 1 << 30,
    quant=None,  # (k_scale, k_zero, v_scale, v_zero), each [P, bs, Hkv] f32
):
    """Sharded twin of ``spec_verify_fused``: ONE launch across the mesh.

    Same signature and return contract as the unsharded fused entry
    (``(n_accepted [B,1], correction [B,1], logp [B,K])``), plus the mesh.
    Bit-exact against the jitted unsharded oracle for any shard count,
    including head counts that don't divide the mesh and int8 pools.
    """
    q, k_pages, v_pages, w, quant, plan, v_true = _prepare(
        q, draft_tokens, k_pages, v_pages, w, mesh, v_true=v_true, block_v=block_v, quant=quant
    )
    fn = _build_launch(
        mesh,
        heads=plan.heads,
        head_dim=plan.head_dim,
        v_true=v_true,
        padded_vocab=plan.padded_vocab,
        vocab_per_shard=plan.vocab_per_shard,
        block_v=plan.block_v,
        window=window,
        quantized=quant is not None,
    )
    args = (q, k_pages, v_pages, w,
            jnp.asarray(block_tables, jnp.int32), jnp.asarray(lengths, jnp.int32),
            jnp.asarray(draft_tokens, jnp.int32), jnp.asarray(n_drafted, jnp.int32))
    if quant is not None:
        args += tuple(quant)
    return fn(*args)
