"""Jit'd wrappers for the fused NAV verify kernel.

``spec_verify`` is the rectangular entry ([B, K+1, V] with per-row
``n_drafted``).  ``spec_verify_batched`` is the serving entry used by the
continuous-batching cloud verifier (runtime/server.py): it takes **ragged**
per-session requests (different draft lengths K_i), pads them into one
[B', Kmax+1, V] launch, and unpacks per-session results.  Shapes are
bucketed to powers of two so a serving process compiles a handful of
variants instead of one per (B, Kmax) pair.

Padded rows/positions are provably inert (see kernel.py "padding
invariants"): acceptance is masked by ``pos < n_drafted``, the correction
index never exceeds ``n_drafted``, and padded log-prob lanes are sliced off
before returning.
"""

from __future__ import annotations

import functools
from typing import Callable, List, Optional, Sequence, Tuple

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


def batch_rows(n: int, bucket: bool = True) -> int:
    """Rows a batched launch gives ``n`` sessions, pad rows included."""
    return _next_pow2(n) if bucket else int(n)


@functools.partial(
    jax.jit, static_argnames=("v_true", "impl", "block_v", "window")
)
def spec_verify_fused(
    q: jax.Array,  # [B, K+1, H, hd] — per-position queries
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
    in-kernel (``models/paged_kv.py`` affine layout).  Bit-exact vs the
    unfused composition per ``tests/test_spec_verify_fused.py``.
    """
    H = q.shape[2]
    n_kv = k_pages.shape[2]
    if n_kv != H:
        k_pages = jnp.repeat(k_pages, H // n_kv, axis=2)
        v_pages = jnp.repeat(v_pages, H // n_kv, axis=2)
        if quant is not None:
            quant = tuple(jnp.repeat(p, H // n_kv, axis=2) for p in quant)
    V = w.shape[1]
    if v_true is None:
        v_true = V
    bv = min(block_v, _next_pow2(V))
    Vp = -(-V // bv) * bv
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


def spec_verify_fused_batched(
    q_seq: Sequence,  # B entries of [K_i+1, H, hd] per-position queries
    tokens_seq: Sequence,  # B entries of length-K_i int sequences
    block_tables_seq: Sequence,  # B ragged KV block tables
    base_lengths: Sequence,  # B ints — KV length visible to query position 0
    k_pages: jax.Array,
    v_pages: jax.Array,
    w: jax.Array,
    *,
    impl: str,
    block_v: int = DEFAULT_BV,
    bucket: bool = True,
    window: int = 1 << 30,
    pad_page_id: int = 0,
    quant=None,
) -> List[Tuple[int, int, np.ndarray]]:
    """Ragged serving entry for the fused verify — one launch for B sessions.

    The fused twin of ``spec_verify_batched``'s ``batched_logits_fn`` path,
    with the forward folded INTO the verify launch: pads queries, tokens,
    block tables (``pad_page_id`` — pass the pool's ``sentinel_page``), and
    per-position lengths (position ``i`` of session ``s`` sees
    ``base_lengths[s] + i``; pad rows/positions see 0, making them inert)
    under the same pow2 bucketing, launches once, and unpacks
    ``(n_accepted, correction, logp[K_i])`` per session in input order.
    """
    if not (len(q_seq) == len(tokens_seq) == len(block_tables_seq) == len(base_lengths)):
        raise ValueError("need one (queries, tokens, table, base_length) per session")
    if not len(tokens_seq):
        raise ValueError("need at least one session")
    ks = [len(t) for t in tokens_seq]
    for qi, k in zip(q_seq, ks):
        if qi.shape[0] != k + 1:
            raise ValueError(f"queries must be [K_i+1, H, hd]; got {qi.shape} for K_i={k}")
    B, kmax = len(ks), max(max(ks, default=0), 1)
    Bp = batch_rows(B, bucket)
    Kp = _next_pow2(kmax) if bucket else kmax
    H, hd = q_seq[0].shape[1], q_seq[0].shape[2]
    qpad = np.zeros((Bp, Kp + 1, H, hd), np.float32)
    tokens = np.zeros((Bp, Kp), np.int32)
    nd = np.zeros((Bp,), np.int32)
    lengths = np.zeros((Bp, Kp + 1), np.int32)
    for i, (qi, tk, k, base) in enumerate(zip(q_seq, tokens_seq, ks, base_lengths)):
        qpad[i, : k + 1] = np.asarray(qi, np.float32)
        tokens[i, :k] = np.asarray(tk, np.int32)
        nd[i] = k
        lengths[i, : k + 1] = int(base) + np.arange(k + 1)
    tables = pad_block_tables(
        block_tables_seq, batch_pad=Bp, bucket=bucket, pad_id=pad_page_id
    )
    na, corr, logp = spec_verify_fused(
        jnp.asarray(qpad),
        k_pages,
        v_pages,
        w,
        jnp.asarray(tables),
        jnp.asarray(lengths),
        jnp.asarray(tokens),
        jnp.asarray(nd),
        impl=impl,
        block_v=block_v,
        window=window,
        quant=quant,
    )
    na, corr, logp = np.asarray(na), np.asarray(corr), np.asarray(logp)
    return [(int(na[i, 0]), int(corr[i, 0]), logp[i, : ks[i]]) for i in range(B)]


def pad_block_tables(
    tables_seq: Sequence, *, batch_pad: int, bucket: bool = True, pad_id: int = 0
) -> np.ndarray:
    """Pad ragged per-session KV block tables into one ``[Bp, Gp]`` int32 array.

    The serving-side companion of the batched verify entries: a paged target
    forward (``kernels.decode_attention`` paged path) consumes one block
    table per admitted session, and those tables are ragged exactly like the
    draft lengths.  They are padded with the SAME pow2 bucketing as the
    logits batch (``batch_pad`` = the entry's ``Bp``) so a serving process
    compiles one shape family for the fused forward+verify dispatch.  Pad
    entries carry ``pad_id``; pass the pool's zero-filled ``sentinel_page``
    (as the serving backend does) so padded lanes can only ever DMA the
    sentinel — never a page owned by another session.  The legacy default 0
    is a *live* page id and is only safe because attention masks pad
    positions by ``lengths``; see ``docs/kernels.md``.
    """
    gmax = max((len(t) for t in tables_seq), default=0)
    Gp = max(_next_pow2(gmax) if bucket else gmax, 1)
    out = np.full((batch_pad, Gp), pad_id, np.int32)
    for i, t in enumerate(tables_seq):
        if len(t):
            out[i, : len(t)] = np.asarray(t, np.int32)
    return out


def spec_verify_batched(
    logits_seq: Optional[Sequence],  # B entries of [K_i+1, V]; None with batched_logits_fn
    tokens_seq: Sequence,  # B entries of length-K_i int sequences
    *,
    impl: str,
    block_v: int = 2048,
    bucket: bool = True,
    block_tables_seq: Optional[Sequence] = None,  # B ragged KV block tables
    batched_logits_fn: Optional[Callable] = None,
    pad_page_id: int = 0,
) -> List[Tuple[int, int, np.ndarray]]:
    """Verify B sessions with ragged draft lengths in ONE launch.

    Returns a list of ``(n_accepted, correction_token, logp[K_i])`` in input
    order.  With ``bucket=True`` the batch and draft dimensions are padded to
    the next power of two (padding rows carry ``n_drafted = 0`` and are
    discarded), bounding the number of compiled shapes under serving load.

    **Paged target forward.**  With ``batched_logits_fn`` the entry owns the
    whole fused dispatch: it pads tokens, per-session ``n_drafted``, and the
    sessions' KV ``block_tables_seq`` (same ``Bp`` bucketing, via
    ``pad_block_tables``), then calls
    ``batched_logits_fn(tokens[Bp, Kp], n_drafted[Bp], tables[Bp, Gp]|None)``
    for one batched ``[Bp, Kp+1, V]`` target forward (paged attention over
    the block tables in a real deployment) before the NAV reduction —
    instead of accepting per-session precomputed ``logits_seq``.
    """
    if batched_logits_fn is None:
        if logits_seq is None or len(logits_seq) != len(tokens_seq) or not len(tokens_seq):
            raise ValueError("need equal, non-empty logits/tokens sequences")
    elif logits_seq is not None:
        raise ValueError("pass logits_seq OR batched_logits_fn, not both")
    if block_tables_seq is not None and len(block_tables_seq) != len(tokens_seq):
        raise ValueError("need one block table per session")
    ks = [len(t) for t in tokens_seq]
    B, kmax = len(ks), max(max(ks, default=0), 1)
    Bp = batch_rows(B, bucket)
    Kp = _next_pow2(kmax) if bucket else kmax
    tokens = np.zeros((Bp, Kp), np.int32)
    nd = np.zeros((Bp,), np.int32)
    for i, (tk, k) in enumerate(zip(tokens_seq, ks)):
        tokens[i, :k] = np.asarray(tk, np.int32)
        nd[i] = k

    if batched_logits_fn is not None:
        tables = (
            pad_block_tables(block_tables_seq, batch_pad=Bp, bucket=bucket, pad_id=pad_page_id)
            if block_tables_seq is not None
            else None
        )
        full = np.asarray(batched_logits_fn(tokens, nd, tables), np.float32)
        if full.shape[:2] != (Bp, Kp + 1):
            raise ValueError(f"batched_logits_fn must return [Bp, Kp+1, V]; got {full.shape}")
        logits_rows = full
        V = full.shape[-1]
    else:
        for lg, k in zip(logits_seq, ks):
            if lg.ndim != 2 or lg.shape[0] != k + 1:
                raise ValueError(f"logits must be [K_i+1, V]; got {lg.shape} for K_i={k}")
        V = logits_seq[0].shape[-1]
        if any(lg.shape[-1] != V for lg in logits_seq):
            raise ValueError("all sessions must share one (padded) vocab size")
        logits_rows = None

    # Pallas needs V % block_v == 0: pad the vocab with -inf lanes (inert —
    # they never win the argmax, add 0 to the logsumexp, and no draft token
    # id can address them), keeping the documented VMEM tile budget.
    bv = min(block_v, _next_pow2(V))
    Vp = -(-V // bv) * bv
    logits = np.zeros((Bp, Kp + 1, Vp), np.float32)
    if Vp > V:
        logits[:, :, V:] = -1e30  # only the pad lanes need the -inf sweep
    if logits_rows is not None:
        logits[:, :, :V] = logits_rows
    else:
        for i, (lg, k) in enumerate(zip(logits_seq, ks)):
            logits[i, : k + 1, :V] = np.asarray(lg, np.float32)

    na, corr, logp = spec_verify(
        jnp.asarray(logits), jnp.asarray(tokens), jnp.asarray(nd), impl=impl, block_v=bv
    )
    na, corr, logp = np.asarray(na), np.asarray(corr), np.asarray(logp)
    return [(int(na[i, 0]), int(corr[i, 0]), logp[i, : ks[i]]) for i in range(B)]


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
    logits_seq: Optional[Sequence],  # B entries of [N_i+1, V]; None with batched_logits_fn
    tokens_seq: Sequence,  # B entries of length-N_i int sequences
    parents_seq: Sequence,  # B entries of length-N_i int sequences
    *,
    impl: str,
    block_v: int = 2048,
    bucket: bool = True,
    block_tables_seq: Optional[Sequence] = None,  # B ragged KV block tables
    batched_logits_fn: Optional[Callable] = None,
    pad_page_id: int = 0,
) -> List[Tuple[int, List[int], int, np.ndarray]]:
    """Verify B sessions' ragged token TREES in ONE padded launch.

    Returns, per session in input order, ``(n_accepted, path, correction,
    logp[N_i])`` where ``path`` is the accepted root→leaf node-index list
    (length ``n_accepted``).  Trees are padded by NODE count with the same
    pow2 bucketing as the chain entry; pad nodes carry ``parents = -1`` and
    pad rows ``n_nodes = 0``, both provably inert (kernel.py invariants).

    Like the chain entry, ``batched_logits_fn`` replaces per-session
    precomputed logits with ONE batched target forward over the padded
    arrays: ``batched_logits_fn(tokens[Bp, Np], parents[Bp, Np],
    n_nodes[Bp], tables[Bp, Gp]|None) -> [Bp, Np+1, V]`` — an
    ancestor-masked paged-attention forward in a real deployment, with the
    sessions' KV ``block_tables_seq`` padded by ``pad_block_tables`` under
    the same ``Bp`` bucketing.
    """
    if not (len(tokens_seq) == len(parents_seq)) or not len(tokens_seq):
        raise ValueError("need equal, non-empty tokens/parents sequences")
    if batched_logits_fn is None:
        if logits_seq is None or len(logits_seq) != len(tokens_seq):
            raise ValueError("need equal, non-empty logits/tokens/parents sequences")
    elif logits_seq is not None:
        raise ValueError("pass logits_seq OR batched_logits_fn, not both")
    if block_tables_seq is not None and len(block_tables_seq) != len(tokens_seq):
        raise ValueError("need one block table per session")
    ns = [len(t) for t in tokens_seq]
    for pr, n in zip(parents_seq, ns):
        if len(pr) != n:
            raise ValueError(f"parents length {len(pr)} != node count {n}")
        for i, p in enumerate(pr):
            if not (-1 <= int(p) < i):
                raise ValueError(f"parents must be topologically packed; parents[{i}]={p}")
    B, nmax = len(ns), max(max(ns), 1)
    Bp = batch_rows(B, bucket)
    Np = _next_pow2(nmax) if bucket else nmax
    tokens = np.zeros((Bp, Np), np.int32)
    parents = np.full((Bp, Np), -1, np.int32)
    nn = np.zeros((Bp,), np.int32)
    for i, (tk, pr, n) in enumerate(zip(tokens_seq, parents_seq, ns)):
        tokens[i, :n] = np.asarray(tk, np.int32)
        parents[i, :n] = np.asarray(pr, np.int32)
        nn[i] = n

    if batched_logits_fn is not None:
        tables = (
            pad_block_tables(block_tables_seq, batch_pad=Bp, bucket=bucket, pad_id=pad_page_id)
            if block_tables_seq is not None
            else None
        )
        full = np.asarray(batched_logits_fn(tokens, parents, nn, tables), np.float32)
        if full.shape[:2] != (Bp, Np + 1):
            raise ValueError(f"batched_logits_fn must return [Bp, Np+1, V]; got {full.shape}")
        V = full.shape[-1]
    else:
        for lg, n in zip(logits_seq, ns):
            if lg.ndim != 2 or lg.shape[0] != n + 1:
                raise ValueError(f"logits must be [N_i+1, V]; got {lg.shape} for N_i={n}")
        V = logits_seq[0].shape[-1]
        if any(lg.shape[-1] != V for lg in logits_seq):
            raise ValueError("all sessions must share one (padded) vocab size")
        full = None

    bv = min(block_v, _next_pow2(V))
    Vp = -(-V // bv) * bv
    logits = np.zeros((Bp, Np + 1, Vp), np.float32)
    if Vp > V:
        logits[:, :, V:] = -1e30  # inert pad lanes (see chain entry)
    if full is not None:
        logits[:, :, :V] = full
    else:
        for i, (lg, n) in enumerate(zip(logits_seq, ns)):
            logits[i, : n + 1, :V] = np.asarray(lg, np.float32)

    na, best, corr, logp = spec_verify_tree(
        jnp.asarray(logits), jnp.asarray(tokens), jnp.asarray(parents), jnp.asarray(nn),
        impl=impl, block_v=bv,
    )
    na, best, corr, logp = (np.asarray(x) for x in (na, best, corr, logp))
    out: List[Tuple[int, List[int], int, np.ndarray]] = []
    for i in range(B):
        path = tree_path(parents[i], int(best[i, 0]))
        out.append((int(na[i, 0]), path, int(corr[i, 0]), logp[i, : ns[i]]))
    return out
