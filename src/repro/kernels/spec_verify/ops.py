"""Jit'd wrappers for the NAV verify kernels.

``spec_verify`` is the rectangular entry ([B, K+1, V] with per-row
``n_drafted``); ``spec_verify_batched`` takes **ragged** per-session logits
(different draft lengths K_i), pads them into one [B', Kmax+1, V] launch,
and unpacks per-session results.  ``spec_verify_fused`` folds the paged
target attention and the LM head into the launch; the served backend
(runtime/server.py) packs its ragged rounds with ``ragged_launch_args`` and
unpacks them with ``ragged_results``.  Shapes round up to powers of two so
a serving process compiles a handful of variants instead of one per
(B, Kmax) pair.

Padded rows/positions are provably inert (see kernel.py "padding
invariants"): acceptance is masked by ``pos < n_drafted``, the correction
index never exceeds ``n_drafted``, and padded log-prob lanes are sliced off
before returning.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .kernel import DEFAULT_BV, spec_verify_fused_pallas, spec_verify_pallas, spec_verify_tree_pallas
from .ref import spec_verify_fused_ref, spec_verify_ref, spec_verify_tree_ref, tree_topology


@functools.partial(jax.jit, static_argnames=("impl", "block_v"))
def spec_verify(
    target_logits: jax.Array,  # [B, K+1, V]
    draft_tokens: jax.Array,  # [B, K]
    n_drafted: jax.Array,  # [B]
    *,
    impl: str,
    block_v: int = 2048,
):
    if impl == "ref":
        return spec_verify_ref(target_logits, draft_tokens, n_drafted)
    return spec_verify_pallas(target_logits, draft_tokens, n_drafted, block_v=block_v, interpret=(impl == "interpret"))


def _next_pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def batch_rows(n: int) -> int:
    """Rows a batched launch gives ``n`` sessions, pad rows included."""
    return _next_pow2(n)


def padded_dims(ks: Sequence[int]) -> Tuple[int, int]:
    """``(Bp, Kp)`` of the launch that carries sessions drafting ``ks`` tokens.

    Pad rows and draft positions round up to powers of two, so a serving
    process compiles a handful of launch shapes; a verify backend draws its
    queries at least this wide.
    """
    kmax = max(max(ks, default=0), 1)
    return batch_rows(len(ks)), _next_pow2(kmax)


def vocab_tiles(V: int, block_v: int) -> Tuple[int, int]:
    """``(bv, Vp)``: the vocab tile of a ``block_v`` launch over ``V`` ids,
    and ``V`` padded up to a whole number of those tiles."""
    bv = min(block_v, _next_pow2(V))
    return bv, -(-V // bv) * bv


def fused_operands(q, draft_tokens, k_pages, v_pages, quant=None):
    """The fused verify's query and page operands for ``draft_tokens [B, K]``.

    Raises unless ``q`` is ``[R, P, H, hd]`` with ``R >= B`` and ``P >=
    K+1``, slices it to the round's ``[B, K+1]``, and repeats the KV heads
    of the pages (and of the int8 planes in ``quant``) up to the query heads
    (GQA).  Shared by ``spec_verify_fused`` and the sharded launch.
    """
    B, K = draft_tokens.shape
    if q.ndim != 4 or q.shape[0] < B or q.shape[1] < K + 1:
        raise ValueError(f"queries must be [R>=B, P>=K+1, H, hd] with B={B}, K={K}; got {q.shape}")
    q = q[:B, : K + 1]
    H = q.shape[2]
    n_kv = k_pages.shape[2]
    if n_kv != H:
        k_pages = jnp.repeat(k_pages, H // n_kv, axis=2)
        v_pages = jnp.repeat(v_pages, H // n_kv, axis=2)
        if quant is not None:
            quant = tuple(jnp.repeat(p, H // n_kv, axis=2) for p in quant)
    return q, k_pages, v_pages, quant


@functools.partial(
    jax.jit, static_argnames=("v_true", "impl", "block_v", "window")
)
def spec_verify_fused(
    q: jax.Array,  # [R, P, H, hd] per-position queries; reads q[:B, :K+1]
    k_pages: jax.Array,  # [P, bs, Hkv, hd] (int8 payload when quant is given)
    v_pages: jax.Array,
    w: jax.Array,  # [H*hd, V] LM head (padded to a block_v multiple here)
    block_tables: jax.Array,  # [B, G] i32 physical page ids
    lengths: jax.Array,  # [B, K+1] i32 valid KV length per query position
    draft_tokens: jax.Array,  # [B, K] i32
    n_drafted: jax.Array,  # [B] i32
    *,
    v_true: Optional[int] = None,
    impl: str,
    block_v: int = DEFAULT_BV,
    window: int = 1 << 30,
    quant=None,  # (k_scale, k_zero, v_scale, v_zero), each [P, bs, Hkv] f32
):
    """ONE-launch chain verify: paged target attention + LM head + NAV scan.

    The rectangular fused entry: instead of precomputed ``[B, K+1, V]``
    logits it takes the target's per-position queries, the paged KV pool
    slices, the LM head, and the sessions' block tables, and returns the
    ``spec_verify`` contract ``(n_accepted [B,1], correction [B,1],
    logp [B,K])`` from a single Pallas launch (vs attention-launch +
    verify-launch unfused).  ``lengths[b, i]`` is the valid KV length seen
    by query position ``i`` (causal: the serving entry passes
    ``base + i``).  With ``quant`` the pages are int8 and dequantized
    in-kernel (``models/paged_kv.py`` affine layout).  ``q`` may hold
    more rows and positions than the round (a serving backend draws at a
    fixed least shape): the launch reads ``q[:B, :K+1]``.  Bit-exact vs the
    unfused composition per ``tests/test_spec_verify_fused.py``.
    """
    q, k_pages, v_pages, quant = fused_operands(q, draft_tokens, k_pages, v_pages, quant)
    V = w.shape[1]
    if v_true is None:
        v_true = V
    bv, Vp = vocab_tiles(V, block_v)
    if Vp > V:  # zero columns; the kernels mask ids >= v_true to -1e30
        w = jnp.pad(w, ((0, 0), (0, Vp - V)))
    if impl == "ref":
        if quant is not None:
            # Local import: decode_attention.ops imports pad_block_tables
            # from this module, so a top-level import would be circular.
            from ..decode_attention.ref import dequantize_pages

            ks, kz, vs, vz = quant
            k_pages = dequantize_pages(k_pages, ks, kz)
            v_pages = dequantize_pages(v_pages, vs, vz)
        return spec_verify_fused_ref(
            q, k_pages, v_pages, w, block_tables, lengths, draft_tokens, n_drafted,
            v_true=v_true, block_v=bv, window=window,
        )
    return spec_verify_fused_pallas(
        q, k_pages, v_pages, w, block_tables, lengths, draft_tokens, n_drafted,
        v_true=v_true, block_v=bv, window=window, quant=quant,
        interpret=(impl == "interpret"),
    )


def _pack_drafts(tokens_seq: Sequence) -> Tuple[List[int], np.ndarray, np.ndarray]:
    """``(ks, tokens [Bp, Kp], n_drafted [Bp])`` of a ragged round.

    Session ``i`` fills row ``i``; pad rows and positions hold 0 and pad
    rows draft nothing.
    """
    ks = [len(t) for t in tokens_seq]
    Bp, Kp = padded_dims(ks)
    tokens = np.zeros((Bp, Kp), np.int32)
    for i, (tk, k) in enumerate(zip(tokens_seq, ks)):
        tokens[i, :k] = np.asarray(tk, np.int32)
    nd = np.zeros((Bp,), np.int32)
    nd[: len(ks)] = ks
    return ks, tokens, nd


def ragged_launch_args(
    tokens_seq: Sequence,
    block_tables_seq: Sequence,
    base_lengths: Sequence,
    *,
    pad_page_id: int = 0,
):
    """Pad a ragged round's host-side arguments for one fused launch.

    Returns ``(ks, tables [Bp, Gp], lengths [Bp, Kp+1], tokens [Bp, Kp],
    n_drafted [Bp])``, the four arrays on the device in the order the
    launches take them.  Position ``i`` of session ``s`` sees
    ``base_lengths[s] + i``; pad rows and positions see length 0 and draft
    nothing, which makes them inert.  Tables pad with ``pad_page_id``: pass
    the pool's ``sentinel_page``.  ``ragged_results`` unpacks the launch.
    """
    if not (len(tokens_seq) == len(block_tables_seq) == len(base_lengths)):
        raise ValueError("need one (tokens, table, base_length) per session")
    if not len(tokens_seq):
        raise ValueError("need at least one session")
    ks, tokens, nd = _pack_drafts(tokens_seq)
    Bp, Kp = tokens.shape
    lengths = np.zeros((Bp, Kp + 1), np.int32)
    for i, (k, base) in enumerate(zip(ks, base_lengths)):
        lengths[i, : k + 1] = int(base) + np.arange(k + 1)
    tables = pad_block_tables(block_tables_seq, batch_pad=Bp, pad_id=pad_page_id)
    return (ks, *(jnp.asarray(x) for x in (tables, lengths, tokens, nd)))


def ragged_results(ks: Sequence[int], n_accepted, correction, logp) -> List[Tuple[int, int, np.ndarray]]:
    """One padded launch's results as ``(n_accepted, correction, logp[:K_i])``
    per session, in input order; pad rows are dropped."""
    na, corr, logp = np.asarray(n_accepted), np.asarray(correction), np.asarray(logp)
    return [(int(na[i, 0]), int(corr[i, 0]), logp[i, :k]) for i, k in enumerate(ks)]


def spec_verify_fused_batched(
    q: jax.Array,  # [R, P, H, hd] padded queries, R >= Bp, P >= Kp+1 (see padded_dims)
    tokens_seq: Sequence,  # B entries of length-K_i int sequences
    block_tables_seq: Sequence,  # B ragged KV block tables
    base_lengths: Sequence,  # B ints — KV length visible to query position 0
    k_pages: jax.Array,
    v_pages: jax.Array,
    w: jax.Array,
    *,
    impl: str,
    block_v: int = DEFAULT_BV,
    window: int = 1 << 30,
    pad_page_id: int = 0,
    quant=None,
) -> List[Tuple[int, int, np.ndarray]]:
    """Ragged entry for the fused verify — one launch for B sessions.

    The queries come padded already, at least to the ``padded_dims`` of the
    round: ``[R, P, H, hd]`` with ``R >= Bp`` and ``P >= Kp+1`` (session
    ``s`` in row ``s``, its ``K_s+1`` positions first; pad rows and
    positions zero; the launch reads ``[:Bp, :Kp+1]``).  The entry pads the
    rest (``ragged_launch_args``; ``pad_page_id`` — pass the pool's
    ``sentinel_page``), launches ``spec_verify_fused`` once, and unpacks
    ``(n_accepted, correction, logp[K_i])`` per session in input order.
    """
    ks, *packed = ragged_launch_args(
        tokens_seq, block_tables_seq, base_lengths, pad_page_id=pad_page_id
    )
    out = spec_verify_fused(
        q, k_pages, v_pages, w, *packed,
        impl=impl, block_v=block_v, window=window, quant=quant,
    )
    return ragged_results(ks, *out)


def pad_block_tables(tables_seq: Sequence, *, batch_pad: int, pad_id: int = 0) -> np.ndarray:
    """Pad ragged per-session KV block tables into one ``[Bp, Gp]`` int32 array.

    The serving-side companion of the batched verify entries: a paged target
    forward (``kernels.decode_attention`` paged path) consumes one block
    table per admitted session, and those tables are ragged exactly like the
    draft lengths.  ``Gp`` is the longest table rounded up to a power of two
    and ``batch_pad`` rows (the launch's ``Bp``) are filled, so a serving
    process compiles one shape family.  Pad entries carry ``pad_id``; pass
    the pool's zero-filled ``sentinel_page`` (as the serving backend does)
    so padded lanes can only ever DMA the sentinel — never a page owned by
    another session.  The default 0 is a *live* page id and is only safe
    because attention masks pad positions by ``lengths``; see
    ``docs/kernels.md``.
    """
    gmax = max((len(t) for t in tables_seq), default=0)
    Gp = max(_next_pow2(gmax), 1)
    out = np.full((batch_pad, Gp), pad_id, np.int32)
    for i, t in enumerate(tables_seq):
        if len(t):
            out[i, : len(t)] = np.asarray(t, np.int32)
    return out


def _pack_logits(logits_seq: Sequence, ns: Sequence[int], rows: int, width: int, block_v: int):
    """Per-session ``[n_i+1, V]`` logits in one ``[rows, width+1, Vp]`` array.

    Returns it with the vocab tile ``bv``.  Pallas needs ``Vp % bv == 0``,
    so the vocab pads with -1e30 lanes: inert, since they never win the
    argmax, add 0 to the logsumexp, and no draft id can address them.
    """
    if len(logits_seq) != len(ns) or not len(ns):
        raise ValueError("need equal, non-empty logits/tokens sequences")
    for lg, n in zip(logits_seq, ns):
        if lg.ndim != 2 or lg.shape[0] != n + 1:
            raise ValueError(f"logits must be [K_i+1, V]; got {lg.shape} for K_i={n}")
    V = logits_seq[0].shape[-1]
    if any(lg.shape[-1] != V for lg in logits_seq):
        raise ValueError("all sessions must share one (padded) vocab size")
    bv, Vp = vocab_tiles(V, block_v)
    logits = np.zeros((rows, width + 1, Vp), np.float32)
    logits[:, :, V:] = -1e30
    for i, (lg, n) in enumerate(zip(logits_seq, ns)):
        logits[i, : n + 1, :V] = np.asarray(lg, np.float32)
    return logits, bv


def spec_verify_batched(
    logits_seq: Sequence,  # B entries of [K_i+1, V]
    tokens_seq: Sequence,  # B entries of length-K_i int sequences
    *,
    impl: str,
    block_v: int = 2048,
) -> List[Tuple[int, int, np.ndarray]]:
    """Verify B sessions with ragged draft lengths in ONE launch.

    Returns a list of ``(n_accepted, correction_token, logp[K_i])`` in input
    order.  The batch and draft dimensions are padded to the next power of
    two (padding rows carry ``n_drafted = 0`` and are discarded), bounding
    the number of compiled shapes under serving load.
    """
    ks, tokens, nd = _pack_drafts(tokens_seq)
    logits, bv = _pack_logits(logits_seq, ks, *tokens.shape, block_v)
    out = spec_verify(
        jnp.asarray(logits), jnp.asarray(tokens), jnp.asarray(nd), impl=impl, block_v=bv
    )
    return ragged_results(ks, *out)


# --------------------------------------------------------------------------- #
# Tree-NAV entries
# --------------------------------------------------------------------------- #


def tree_path(parents: Sequence[int], node: int) -> List[int]:
    """Packed node indices along the root→``node`` path (inclusive, in order).

    Returns [] for ``node < 0`` (the no-acceptance sentinel), so callers can
    feed ``best_node`` from the verifier straight through.
    """
    path: List[int] = []
    i = int(node)
    while i >= 0:
        path.append(i)
        i = int(parents[i])
    path.reverse()
    return path


@functools.partial(jax.jit, static_argnames=("impl", "block_v"))
def spec_verify_tree(
    target_logits: jax.Array,  # [B, N+1, V] — row 0 anchor, row 1+i = node i
    tokens: jax.Array,  # [B, N]
    parents: jax.Array,  # [B, N] int32, -1 = root level, parents[i] < i
    n_nodes: jax.Array,  # [B]
    *,
    impl: str,
    block_v: int = 2048,
):
    """Greedy tree-NAV: (n_accepted [B,1], best_node [B,1], corr [B,1], logp [B,N])."""
    if impl == "ref":
        return spec_verify_tree_ref(target_logits, tokens, parents, n_nodes)
    prow, depth, anc = tree_topology(jnp.asarray(parents, jnp.int32))
    return spec_verify_tree_pallas(
        target_logits,
        tokens,
        prow,
        depth,
        anc,
        n_nodes,
        block_v=block_v,
        interpret=(impl == "interpret"),
    )


def spec_verify_tree_batched(
    logits_seq: Sequence,  # B entries of [N_i+1, V]
    tokens_seq: Sequence,  # B entries of length-N_i int sequences
    parents_seq: Sequence,  # B entries of length-N_i int sequences
    *,
    impl: str,
    block_v: int = 2048,
) -> List[Tuple[int, List[int], int, np.ndarray]]:
    """Verify B sessions' ragged token TREES in ONE padded launch.

    Returns, per session in input order, ``(n_accepted, path, correction,
    logp[N_i])`` where ``path`` is the accepted root→leaf node-index list
    (length ``n_accepted``).  Trees are padded by NODE count with the same
    pow2 rounding as the chain entry; pad nodes carry ``parents = -1`` and
    pad rows ``n_nodes = 0``, both provably inert (kernel.py invariants).
    """
    if len(tokens_seq) != len(parents_seq) or not len(tokens_seq):
        raise ValueError("need equal, non-empty tokens/parents sequences")
    ns, tokens, nn = _pack_drafts(tokens_seq)
    parents = np.full(tokens.shape, -1, np.int32)
    for i, (pr, n) in enumerate(zip(parents_seq, ns)):
        if len(pr) != n:
            raise ValueError(f"parents length {len(pr)} != node count {n}")
        for j, p in enumerate(pr):
            if not (-1 <= int(p) < j):
                raise ValueError(f"parents must be topologically packed; parents[{j}]={p}")
        parents[i, :n] = np.asarray(pr, np.int32)
    logits, bv = _pack_logits(logits_seq, ns, *tokens.shape, block_v)
    na, best, corr, logp = spec_verify_tree(
        jnp.asarray(logits), jnp.asarray(tokens), jnp.asarray(parents), jnp.asarray(nn),
        impl=impl, block_v=bv,
    )
    na, best, corr, logp = (np.asarray(x) for x in (na, best, corr, logp))
    return [
        (int(na[i, 0]), tree_path(parents[i], int(best[i, 0])), int(corr[i, 0]), logp[i, :n])
        for i, n in enumerate(ns)
    ]
