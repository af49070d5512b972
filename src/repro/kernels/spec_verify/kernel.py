"""Pallas TPU fused speculative-verification (greedy NAV) kernel.

The NAV step's post-processing is memory-bound on the target logits
[B, K+1, V] (V up to 262k padded): XLA's naive lowering reads the logits
once for argmax, once for log-softmax, and once for the draft-token gather.
This kernel fuses all three into ONE pass over the vocabulary:

    per (lane, vocab-block): running (max, argmax, logsumexp) per position
    + gather of each draft token's logit when its id falls in the block;
    final block → n_accepted, correction token, draft-token log-probs.

Grid: (B, num_vocab_blocks), vocab dimension "arbitrary" (sequential) with
running state in VMEM scratch, one ``[K+1, 1]`` column per quantity.  Draft
tokens arrive as a ``[B, K+1, 1]`` column and ``n_drafted`` by scalar
prefetch; results leave lane-packed (``[B, 1, 128]`` int32: n_accepted,
correction) plus a ``[B, K+1, 1]`` log-prob column — block shapes the TPU
compiler accepts (see ``docs/kernels.md``, "TPU layout rules").

Padding invariants (relied on by ``ops.spec_verify_batched``, which packs
ragged multi-session requests into one rectangular launch):

* rows with ``n_drafted = 0`` produce ``n_accepted = 0`` and touch nothing
  else — whole padding rows (zero logits, zero tokens) are inert;
* positions ``>= n_drafted`` never accept (the match is masked by
  ``pos < n_drafted``), and the correction index ``min(n_accepted, K)``
  never exceeds ``n_drafted``, so per-row padding columns beyond a
  session's real draft length cannot leak into its outputs;
* ``logp`` lanes at padded positions carry garbage by design — callers
  slice ``logp[:K_i]``.

``_fused_verify_kernel`` goes one step further than fusing the logits
post-processing: it fuses the TARGET FORWARD itself — paged flash-decode
attention over the session's KV block tables (the
``kernels/decode_attention`` PrefetchScalarGridSpec machinery) plus the
LM-head projection plus the accept/reject scan — so a K-token chain verify
is ONE kernel launch instead of attention-launch-then-verify-launch.  Grid
``(B, G + NV)``: steps ``t < G`` stream physical page ``bt[b, t]`` and
advance K+1 online-softmax states (one per query position, causal
per-position lengths), step ``t == G-1`` finalizes attention into a
``[K1, H, hd]`` VMEM tile, and steps ``t >= G`` stream LM-head tiles
``W[:, :, (t-G)*bv : ...]`` (the head viewed as ``[H, hd, Vp]``), form the
logits tile in-VMEM (masking padded vocab ids to ``NEG_INF``), and run the
same ``_scan_update`` as ``_verify_kernel`` on it.  Because every op/shape
matches the unfused kernels exactly — the same ``tiles.attend_page`` step,
same output-dtype round-trip, same ``tiles.lm_head_tile`` products, same
scan — the fused launch is bit-exact vs the
``paged_decode_attention`` → projection → ``spec_verify`` composition
(``tests/test_spec_verify_fused.py``).  The int8 variant dequantizes pages
in-VMEM exactly like ``paged_decode_attention_q8_pallas``.

``_tree_verify_kernel`` is the tree-NAV generalization: N packed tree nodes
verified against N+1 logits rows (row 0 = anchor, row 1+i = node i), where
node i is scored by its PARENT's row (``prow = parents + 1``) and acceptance
propagates along the packed ancestor mask ``anc[i, j]`` — accepted(i) =
∀j on root→i path: match(j).  The finalize step reduces to the deepest
accepted node (ties → smallest packed index), its depth, and the correction
token from that node's own row.  The same padding invariants hold with
``n_drafted`` replaced by ``n_nodes``: pad nodes never match, and real
nodes' ancestor sets contain only real nodes, so pad nodes cannot veto an
acceptance.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..tiles import HIGHEST, NEG_INF, attend_page, lm_head_tile

DEFAULT_BV = 512
_BIG = 2**30
_SEMANTICS = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))
VMEM_DEFAULT = 16 << 20  # the scoped VMEM a v5e kernel gets unless it asks for more
# The TPU compiler's own work space: about 4.5 ``[K1, bv]`` f32 tiles per
# head while it forms the LM-head products, counted as 5, and the attention
# step's two ``[bs, H, hd]`` f32 products (fitted to the smallest limit each
# launch compiles under, for a described v5e over H 16-48, hd 64 and 128,
# K+1 2-9, block_v 256 and 512; the count is at or above every one).
_HEAD_WORK_TILES = 5


def _vmem_bytes(shape, dtype) -> int:
    """One VMEM buffer of ``shape``: its last two dims padded to the dtype's
    tile, (8, 128) for 32-bit values and (32, 128) for int8."""
    itemsize = jnp.dtype(dtype).itemsize
    *lead, rows, cols = shape
    sub = 8 * (4 // itemsize)
    return math.prod(lead) * (-(-rows // sub) * sub) * (-(-cols // 128) * 128) * itemsize


def fused_vmem_bytes(k1: int, H: int, hd: int, bs: int, bv: int, page_dtype, quantized: bool) -> int:
    """What one fused chain launch needs of VMEM, from its block shapes.

    Every block is double-buffered: the queries, the K and V page tiles (and
    the int8 planes' scale/zero tiles), the ``[H, hd, bv]`` LM-head tile, the
    token column and the outputs.  Scratch is held once: ``[K1, H, 1]`` and
    ``[K1, H, hd]`` twice each, four ``[K1, 1]`` columns.  On top comes the
    compiler's own work space for the per-head LM-head products and the
    attention step.
    """
    f32, i32 = jnp.float32, jnp.int32
    blocks = [((1, k1, H, hd), f32), ((1, bs, H, hd), page_dtype), ((1, bs, H, hd), page_dtype)]
    if quantized:
        blocks += [((1, bs, H), f32)] * 4
    blocks += [((H, hd, bv), f32), ((1, k1, 1), i32), ((1, 1, 128), i32), ((1, k1, 1), f32)]
    scratch = [((k1, H, 1), f32)] * 2 + [((k1, H, hd), f32)] * 2 + [((k1, 1), f32)] * 4
    work = _HEAD_WORK_TILES * H * _vmem_bytes((k1, bv), f32) + 2 * _vmem_bytes((bs, H, hd), f32)
    return 2 * sum(_vmem_bytes(*b) for b in blocks) + sum(_vmem_bytes(*b) for b in scratch) + work


def fused_vmem_limit(k1: int, H: int, hd: int, bs: int, bv: int, page_dtype, quantized: bool):
    """The scoped VMEM limit a fused launch asks for: ``None`` (the default)
    while its need fits the default, else the need and a quarter more,
    rounded up to a whole MiB."""
    need = fused_vmem_bytes(k1, H, hd, bs, bv, page_dtype, quantized)
    if need <= VMEM_DEFAULT:
        return None
    return -(-(need * 5 // 4) // (1 << 20)) << 20


def _scan_init(m_scr, arg_scr, lse_scr, tok_scr):
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    arg_scr[...] = jnp.zeros_like(arg_scr)
    lse_scr[...] = jnp.zeros_like(lse_scr)
    tok_scr[...] = jnp.full_like(tok_scr, NEG_INF)


def _scan_rows(s, ids, m_scr, arg_scr, lse_scr):
    """Fold one ``[R, bv]`` logits tile into the running (max, argmax, sum exp).

    Every array is 2-D with rows on sublanes; reductions keep the lane axis.
    """
    blk_max = jnp.max(s, axis=-1, keepdims=True)  # [R, 1]
    blk_arg = jnp.min(jnp.where(s == blk_max, ids, _BIG), axis=-1, keepdims=True)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, blk_max)
    lse_scr[...] = lse_scr[...] * jnp.exp(m_prev - m_new) + jnp.sum(
        jnp.exp(s - m_new), axis=-1, keepdims=True
    )
    arg_scr[...] = jnp.where(blk_max > m_prev, blk_arg, arg_scr[...])
    m_scr[...] = m_new


def _gather_tokens(s, ids, tok, tok_scr):
    """Keep each row's logit of token ``tok [R, 1]`` when this tile holds it."""
    hit = ids == tok  # [R, bv]
    gathered = jnp.sum(jnp.where(hit, s, 0.0), axis=-1, keepdims=True)
    found = jnp.max(hit.astype(jnp.int32), axis=-1, keepdims=True) > 0
    tok_scr[...] = jnp.where(found, gathered, tok_scr[...])


def _scan_update(s, ids, tok, m_scr, arg_scr, lse_scr, tok_scr):
    """The chain scan's per-tile step: running row state + draft-token logits."""
    _scan_rows(s, ids, m_scr, arg_scr, lse_scr)
    _gather_tokens(s, ids, tok, tok_scr)


def _lanes(*values):
    """Pack ``(1, 1)`` int32 values into lanes 0.. of a ``(1, 128)`` row."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
    out = jnp.zeros((1, 128), jnp.int32)
    for i, v in enumerate(values):
        out = jnp.where(lane == i, v, out)
    return out


def _chain_finalize(n_d, tok, m_scr, arg_scr, lse_scr, tok_scr, res_ref, logp_ref, *, k1):
    """Greedy chain NAV from the scanned state: accept, correct, log-probs.

    ``n_accepted`` is the first position ``< K`` whose greedy token misses
    its draft (or K); the correction is the greedy token there.  ``res`` gets
    ``[n_accepted, correction]`` in lanes 0-1, ``logp`` every row (rows past
    ``n_drafted`` are garbage by design).
    """
    K = k1 - 1
    greedy = arg_scr[...]  # [K1, 1]
    lse = m_scr[...] + jnp.log(jnp.maximum(lse_scr[...], 1e-30))
    pos = jax.lax.broadcasted_iota(jnp.int32, (k1, 1), 0)
    match = (greedy == tok) & (pos < n_d) & (pos < K)
    n_acc = jnp.min(jnp.where(match, K, pos), axis=0, keepdims=True)  # (1, 1)
    corr = jnp.sum(jnp.where(pos == n_acc, greedy, 0), axis=0, keepdims=True)
    res_ref[0] = _lanes(n_acc, corr)
    logp_ref[0] = tok_scr[...] - lse


def _chain_outputs(B, k1):
    return [
        jax.ShapeDtypeStruct((B, 1, 128), jnp.int32),  # lanes: n_accepted, correction
        jax.ShapeDtypeStruct((B, k1, 1), jnp.float32),  # logp per row
    ]


def _chain_result(res, logp, K):
    """``(n_accepted [B,1], correction [B,1], logp [B,K])`` from the packed outputs."""
    return res[:, 0, 0:1], res[:, 0, 1:2], logp[:, :K, 0]


def _token_column(draft_tokens, k1):
    """``[B, K] -> [B, K1, 1]`` int32 with -1 (no token) in the bonus row."""
    B = draft_tokens.shape[0]
    col = jnp.full((B, k1), -1, jnp.int32).at[:, : k1 - 1].set(draft_tokens.astype(jnp.int32))
    return col[:, :, None]


def _verify_kernel(
    nd_ref,  # [B] i32 scalar-prefetch — n_drafted
    logits_ref,  # [1, K1, BV] f32/bf16 target logits block
    tok_ref,  # [1, K1, 1] i32 draft token per row (-1 in the bonus row)
    res_ref,  # [1, 1, 128] i32 out — lanes: n_accepted, correction
    logp_ref,  # [1, K1, 1] f32 out — log P_target(draft token)
    m_scr,  # [K1, 1] f32 running max
    arg_scr,  # [K1, 1] i32 running argmax
    lse_scr,  # [K1, 1] f32 running sum exp (shifted by m)
    tok_scr,  # [K1, 1] f32 draft-token logits (row i holds logit of draft i)
    *,
    bv: int,
    nv: int,
    k1: int,
):
    b, vb = pl.program_id(0), pl.program_id(1)

    @pl.when(vb == 0)
    def _init():
        _scan_init(m_scr, arg_scr, lse_scr, tok_scr)

    s = logits_ref[0].astype(jnp.float32)  # [K1, BV]
    ids = vb * bv + jax.lax.broadcasted_iota(jnp.int32, (k1, bv), 1)
    _scan_update(s, ids, tok_ref[0], m_scr, arg_scr, lse_scr, tok_scr)

    @pl.when(vb == nv - 1)
    def _finalize():
        _chain_finalize(
            nd_ref[b], tok_ref[0], m_scr, arg_scr, lse_scr, tok_scr, res_ref, logp_ref, k1=k1
        )


def _fused_verify_kernel(
    bt_ref,  # [B, G] i32 scalar-prefetch — physical page id per logical page
    len_ref,  # [B, K1] i32 scalar-prefetch — valid KV length per query position
    nd_ref,  # [B] i32 scalar-prefetch — n_drafted
    q_ref,  # [1, K1, H, hd] — query per draft position (row K = bonus)
    k_ref,  # [1, bs, H, hd] — physical page bt[b, min(t, G-1)]
    v_ref,  # [1, bs, H, hd]
    *rest,  # [quant: ks/kz/vs/vz [1, bs, H]] w [H, hd, bv], tok, outs, scratch
    sm_scale: float,
    window: int,
    bs: int,
    ng: int,
    bv: int,
    nv: int,
    k1: int,
    v_true: int,
    quantized: bool,
):
    if quantized:
        ks_ref, kz_ref, vs_ref, vz_ref = rest[:4]
        rest = rest[4:]
    (
        w_ref,  # [H, hd, bv] f32 LM-head tile (t - ng)
        tok_ref,  # [1, K1, 1] i32
        res_ref,  # [1, 1, 128] i32 out
        logp_ref,  # [1, K1, 1] f32 out
        m_att,  # [K1, H, 1] f32 — attention running max per position
        l_att,  # [K1, H, 1] f32
        acc_att,  # [K1, H, hd] f32
        o_scr,  # [K1, H, hd] f32 — finalized attention outputs
        m_scr,  # [K1, 1] f32 — verify running max
        arg_scr,  # [K1, 1] i32
        lse_scr,  # [K1, 1] f32
        tok_scr,  # [K1, 1] f32
    ) = rest
    b, t = pl.program_id(0), pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        m_att[...] = jnp.full_like(m_att, NEG_INF)
        l_att[...] = jnp.zeros_like(l_att)
        acc_att[...] = jnp.zeros_like(acc_att)
        _scan_init(m_scr, arg_scr, lse_scr, tok_scr)

    # ---- Phase 1 (t < ng): paged flash-decode for K1 query positions. ----
    # Per position this is the unfused paged kernel's step (``attend_page``
    # on the same [H, hd] x [bs, H, hd] shapes), so phase-1 state is bitwise
    # what that kernel would hold for the same (lane, page).
    @pl.when(t < ng)
    def _attend():
        if quantized:
            k = (k_ref[0].astype(jnp.float32) + 128.0) * ks_ref[0][..., None] + kz_ref[0][..., None]
            v = (v_ref[0].astype(jnp.float32) + 128.0) * vs_ref[0][..., None] + vz_ref[0][..., None]
        else:
            k = k_ref[0].astype(jnp.float32)  # [bs, H, hd]
            v = v_ref[0].astype(jnp.float32)
        k_pos = t * bs + jax.lax.broadcasted_iota(jnp.int32, (bs, 1, 1), 0)
        for i in range(k1):
            m_att[i], l_att[i], acc_att[i] = attend_page(
                q_ref[0, i].astype(jnp.float32), k, v, k_pos, len_ref[b, i],
                m_att[i], l_att[i], acc_att[i], sm_scale=sm_scale, window=window,
            )

    @pl.when(t == ng - 1)
    def _finalize_attention():
        # Round-trip through the query dtype exactly like the unfused
        # kernel's o_ref cast, so downstream logits see identical values.
        o = (acc_att[...] / jnp.maximum(l_att[...], 1e-30)).astype(q_ref.dtype)
        o_scr[...] = o.astype(jnp.float32)

    # ---- Phase 2 (t >= ng): LM-head tile + the _verify_kernel update. ----
    @pl.when(t >= ng)
    def _verify():
        vb = t - ng
        s = lm_head_tile(o_scr, w_ref)  # [K1, bv] f32
        ids = vb * bv + jax.lax.broadcasted_iota(jnp.int32, (k1, bv), 1)
        s = jnp.where(ids >= v_true, NEG_INF, s)  # vocab pad lanes are inert
        _scan_update(s, ids, tok_ref[0], m_scr, arg_scr, lse_scr, tok_scr)

    @pl.when(t == ng + nv - 1)
    def _finalize():
        _chain_finalize(
            nd_ref[b], tok_ref[0], m_scr, arg_scr, lse_scr, tok_scr, res_ref, logp_ref, k1=k1
        )


def spec_verify_fused_pallas(
    q: jax.Array,  # [B, K+1, H, hd] — per-position queries (GQA-expanded pages)
    k_pages: jax.Array,  # [P, bs, H, hd] (int8 when quant is given)
    v_pages: jax.Array,
    w: jax.Array,  # [H*hd, Vp] f32 LM head, Vp % block_v == 0 (zero-padded)
    block_tables: jax.Array,  # [B, G] i32 physical page ids
    lengths: jax.Array,  # [B, K+1] i32 valid KV length per query position
    draft_tokens: jax.Array,  # [B, K] i32
    n_drafted: jax.Array,  # [B] i32
    *,
    v_true: int,
    window: int = 1 << 30,
    block_v: int = DEFAULT_BV,
    quant=None,  # (k_scale, k_zero, v_scale, v_zero), each [P, bs, H] f32
    interpret: bool = False,
):
    """One-launch chain verify: paged attention + LM head + NAV scan fused.

    Returns ``(n_accepted [B,1], correction [B,1], logp [B,K])`` — the same
    contract as ``spec_verify_pallas`` — from queries + paged KV + LM head
    instead of precomputed logits.  Bit-exact vs the unfused composition by
    construction (see module docstring).
    """
    B, K1, H, hd = q.shape
    P, bs, Hk, _ = k_pages.shape
    if Hk != H:
        raise ValueError(f"pages must be GQA-expanded: {Hk} heads vs {H} queries")
    if K1 > 128:
        raise ValueError(f"K+1={K1} exceeds the [K1] VMEM scratch budget (max 128)")
    F, Vp = w.shape
    if F != H * hd:
        raise ValueError(f"LM head rows {F} != H*hd = {H * hd}")
    bv = min(block_v, Vp)
    if Vp % bv:
        raise ValueError(f"Vp={Vp} must be divisible by block_v={bv}")
    nv = Vp // bv
    G = block_tables.shape[1]
    limit = fused_vmem_limit(K1, H, hd, bs, bv, k_pages.dtype, quant is not None)
    params = _SEMANTICS if limit is None else dataclasses.replace(_SEMANTICS, vmem_limit_bytes=limit)
    kernel = functools.partial(
        _fused_verify_kernel,
        sm_scale=1.0 / math.sqrt(hd),
        window=int(window),
        bs=bs,
        ng=G,
        bv=bv,
        nv=nv,
        k1=K1,
        v_true=int(v_true),
        quantized=quant is not None,
    )
    page_ix = lambda b, t, bt, ln, nd: (bt[b, jnp.minimum(t, G - 1)], 0, 0, 0)  # noqa: E731
    param_ix = lambda b, t, bt, ln, nd: (bt[b, jnp.minimum(t, G - 1)], 0, 0)  # noqa: E731
    row_ix = lambda b, t, bt, ln, nd: (b, 0, 0)  # noqa: E731
    in_specs = [
        pl.BlockSpec((1, K1, H, hd), lambda b, t, bt, ln, nd: (b, 0, 0, 0)),
        pl.BlockSpec((1, bs, H, hd), page_ix),
        pl.BlockSpec((1, bs, H, hd), page_ix),
    ]
    operands = [q, k_pages, v_pages]
    if quant is not None:
        in_specs += [pl.BlockSpec((1, bs, H), param_ix)] * 4
        operands += [p.astype(jnp.float32) for p in quant]
    in_specs += [
        pl.BlockSpec((H, hd, bv), lambda b, t, bt, ln, nd: (0, 0, jnp.maximum(t - G, 0))),
        pl.BlockSpec((1, K1, 1), row_ix),
    ]
    operands += [w.astype(jnp.float32).reshape(H, hd, Vp), _token_column(draft_tokens, K1)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # block_tables, per-position lengths, n_drafted
        grid=(B, G + nv),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((1, 1, 128), row_ix), pl.BlockSpec((1, K1, 1), row_ix)],
        scratch_shapes=[
            pltpu.VMEM((K1, H, 1), jnp.float32),
            pltpu.VMEM((K1, H, 1), jnp.float32),
            pltpu.VMEM((K1, H, hd), jnp.float32),
            pltpu.VMEM((K1, H, hd), jnp.float32),
            pltpu.VMEM((K1, 1), jnp.float32),
            pltpu.VMEM((K1, 1), jnp.int32),
            pltpu.VMEM((K1, 1), jnp.float32),
            pltpu.VMEM((K1, 1), jnp.float32),
        ],
    )
    res, logp = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=_chain_outputs(B, K1),
        compiler_params=params,
        interpret=interpret,
        name="_fused_verify_kernel",  # names the compiled op, and so the profiler's event
    )(
        block_tables.astype(jnp.int32),
        lengths.astype(jnp.int32),
        n_drafted.astype(jnp.int32),
        *operands,
    )
    return _chain_result(res, logp, K1 - 1)


def _tree_verify_kernel(
    nn_ref,  # [B] i32 scalar-prefetch — n_nodes
    logits_ref,  # [1, N1, BV] f32/bf16 target logits block (row 0 = anchor)
    tok_ref,  # [1, N, 1] i32 packed node tokens
    prow_ref,  # [1, N, 1] i32 verify row per node = parents + 1
    depth_ref,  # [1, N, 1] i32 1-based node depth
    anc_ref,  # [1, N, N] i32 packed ancestor mask (anc[i,j]=1: j on root→i path)
    res_ref,  # [1, 1, 128] i32 out — lanes: n_accepted (depth), best node, correction
    logp_ref,  # [1, N, 1] f32 out — log P_target(node token) at its verify row
    m_scr,  # [N1, 1] f32 running max
    arg_scr,  # [N1, 1] i32 running argmax
    lse_scr,  # [N1, 1] f32 running sum exp (shifted by m)
    tok_scr,  # [N, 1] f32 node-token logits gathered at each node's verify row
    *,
    bv: int,
    nv: int,
    n1: int,
):
    b, vb = pl.program_id(0), pl.program_id(1)
    N = n1 - 1

    @pl.when(vb == 0)
    def _init():
        _scan_init(m_scr, arg_scr, lse_scr, tok_scr)

    s = logits_ref[0].astype(jnp.float32)  # [N1, BV]
    _scan_rows(s, vb * bv + jax.lax.broadcasted_iota(jnp.int32, (n1, bv), 1), m_scr, arg_scr, lse_scr)
    # Gather each node's token logit from its VERIFY row (unlike the chain
    # kernel, node i is scored by row prow[i], not row i): a one-hot matmul
    # re-indexes the [N1, BV] tile to [N, BV] before the in-block id match.
    tok = tok_ref[0]  # [N, 1]
    row_ids = jax.lax.broadcasted_iota(jnp.int32, (N, n1), 1)
    onehot = (row_ids == prow_ref[0]).astype(jnp.float32)  # [N, N1]
    s_at = jnp.dot(onehot, s, precision=HIGHEST, preferred_element_type=jnp.float32)  # [N, BV]
    _gather_tokens(s_at, vb * bv + jax.lax.broadcasted_iota(jnp.int32, (N, bv), 1), tok, tok_scr)

    @pl.when(vb == nv - 1)
    def _finalize():
        lse = m_scr[...] + jnp.log(jnp.maximum(lse_scr[...], 1e-30))  # [N1, 1]
        # Row lookups as one-hot matmuls (column vectors stay columns):
        # greedy ids are < 2**24, so float32 carries them exactly.
        g_at = jnp.dot(onehot, arg_scr[...].astype(jnp.float32), precision=HIGHEST)
        lse_at = jnp.dot(onehot, lse, precision=HIGHEST)  # [N, 1]
        pos = jax.lax.broadcasted_iota(jnp.int32, (N, 1), 0)
        valid = pos < nn_ref[b]
        match = jnp.logical_and(g_at.astype(jnp.int32) == tok, valid)
        # accepted[i] = no node on the root→i path misses (anc[i,i] covers i).
        misses = jnp.dot(
            anc_ref[0].astype(jnp.float32), 1.0 - match.astype(jnp.float32), precision=HIGHEST
        )
        accepted = jnp.logical_and(misses == 0.0, valid)
        acc_depth = jnp.where(accepted, depth_ref[0], 0)
        n_acc = jnp.max(acc_depth, axis=0, keepdims=True)  # (1, 1)
        best = jnp.min(
            jnp.where(jnp.logical_and(accepted, acc_depth == n_acc), pos, _BIG),
            axis=0, keepdims=True,
        )
        best = jnp.where(n_acc > 0, best, -1)
        best_row = jnp.where(n_acc > 0, best + 1, 0)
        ids_n1 = jax.lax.broadcasted_iota(jnp.int32, (n1, 1), 0)
        corr = jnp.sum(jnp.where(ids_n1 == best_row, arg_scr[...], 0), axis=0, keepdims=True)
        res_ref[0] = _lanes(n_acc, best, corr)
        logp_ref[0] = tok_scr[...] - lse_at


def spec_verify_tree_pallas(
    target_logits: jax.Array,  # [B, N+1, V] — row 0 anchor, row 1+i = node i
    tokens: jax.Array,  # [B, N] i32
    prow: jax.Array,  # [B, N] i32 (parents + 1)
    depth: jax.Array,  # [B, N] i32 (1-based)
    anc: jax.Array,  # [B, N, N] i32/bool packed ancestor mask
    n_nodes: jax.Array,  # [B] i32
    *,
    block_v: int = DEFAULT_BV,
    interpret: bool = False,
):
    B, N1, V = target_logits.shape
    N = N1 - 1
    if N < 1:
        raise ValueError("tree verification needs at least one node")
    if N1 > 128:
        raise ValueError(f"N+1={N1} exceeds the [N1] VMEM scratch budget (max 128)")
    bv = min(block_v, V)
    if V % bv:
        raise ValueError(f"V={V} must be divisible by block_v={bv}")
    nv = V // bv
    kernel = functools.partial(_tree_verify_kernel, bv=bv, nv=nv, n1=N1)
    row_ix = lambda b, j, nn: (b, 0, 0)  # noqa: E731
    col = pl.BlockSpec((1, N, 1), row_ix)
    res, logp = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,  # n_nodes
            grid=(B, nv),
            in_specs=[
                pl.BlockSpec((1, N1, bv), lambda b, j, nn: (b, 0, j)),
                col,
                col,
                col,
                pl.BlockSpec((1, N, N), row_ix),
            ],
            out_specs=[pl.BlockSpec((1, 1, 128), row_ix), col],
            scratch_shapes=[
                pltpu.VMEM((N1, 1), jnp.float32),
                pltpu.VMEM((N1, 1), jnp.int32),
                pltpu.VMEM((N1, 1), jnp.float32),
                pltpu.VMEM((N, 1), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, 1, 128), jnp.int32),
            jax.ShapeDtypeStruct((B, N, 1), jnp.float32),
        ],
        compiler_params=_SEMANTICS,
        interpret=interpret,
        name="_tree_verify_kernel",
    )(
        n_nodes.astype(jnp.int32),
        target_logits,
        tokens.astype(jnp.int32)[:, :, None],
        prow.astype(jnp.int32)[:, :, None],
        depth.astype(jnp.int32)[:, :, None],
        anc.astype(jnp.int32),
    )
    return res[:, 0, 0:1], res[:, 0, 1:2], res[:, 0, 2:3], logp[:, :, 0]


def spec_verify_pallas(
    target_logits: jax.Array,  # [B, K+1, V]
    draft_tokens: jax.Array,  # [B, K] i32
    n_drafted: jax.Array,  # [B] i32
    *,
    block_v: int = DEFAULT_BV,
    interpret: bool = False,
):
    B, K1, V = target_logits.shape
    if K1 > 128:
        raise ValueError(f"K+1={K1} exceeds the [K1] VMEM scratch budget (max 128)")
    bv = min(block_v, V)
    if V % bv:
        raise ValueError(f"V={V} must be divisible by block_v={bv}")
    nv = V // bv
    kernel = functools.partial(_verify_kernel, bv=bv, nv=nv, k1=K1)
    row_ix = lambda b, j, nd: (b, 0, 0)  # noqa: E731
    res, logp = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,  # n_drafted
            grid=(B, nv),
            in_specs=[
                pl.BlockSpec((1, K1, bv), lambda b, j, nd: (b, 0, j)),
                pl.BlockSpec((1, K1, 1), row_ix),
            ],
            out_specs=[pl.BlockSpec((1, 1, 128), row_ix), pl.BlockSpec((1, K1, 1), row_ix)],
            scratch_shapes=[
                pltpu.VMEM((K1, 1), jnp.float32),
                pltpu.VMEM((K1, 1), jnp.int32),
                pltpu.VMEM((K1, 1), jnp.float32),
                pltpu.VMEM((K1, 1), jnp.float32),
            ],
        ),
        out_shape=_chain_outputs(B, K1),
        compiler_params=_SEMANTICS,
        interpret=interpret,
        name="_verify_kernel",
    )(n_drafted.astype(jnp.int32), target_logits, _token_column(draft_tokens, K1))
    return _chain_result(res, logp, K1 - 1)
