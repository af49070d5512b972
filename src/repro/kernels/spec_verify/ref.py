"""Pure-jnp oracles for fused greedy NAV verification.

``spec_verify_ref`` is the rectangular [B, K+1, V] oracle (also the CPU
fallback behind ``ops.spec_verify(impl='ref')``).  ``spec_verify_ragged_ref``
is the unbatched per-session oracle the batched serving path is tested
against: it loops sessions one at a time with no padding, so any cross-
session leakage or padding bug in ``ops.spec_verify_batched`` shows up as a
mismatch.

Tree verification (``spec_verify_tree_ref``) generalizes the chain oracle to
a *packed token tree*: N draft nodes in topological order (every parent
precedes its children), ``parents[i] ∈ {-1, 0..i-1}`` with -1 marking a
root-level node.  The target logits carry N+1 rows — row 0 is the *anchor*
(logits after the committed prefix, which verify the root-level nodes) and
row 1+i is the target's distribution after the root→i path (which verifies
node i's children, and supplies the bonus token when i ends the accepted
path).  Greedy tree-NAV accepts node i iff the target's greedy token at its
parent's row equals ``tokens[i]`` AND every ancestor was accepted; the result
is the deepest accepted node (ties break toward the smallest packed index,
i.e. the highest-ranked sibling) plus the correction token from that node's
own row.
"""

from __future__ import annotations

import math
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..tiles import lm_head_tile


def spec_verify_ref(target_logits: jax.Array, draft_tokens: jax.Array, n_drafted: jax.Array):
    """Returns (n_accepted [B,1], correction [B,1], draft_logp [B,K])."""
    B, K1, V = target_logits.shape
    K = K1 - 1
    s = target_logits.astype(jnp.float32)
    greedy = jnp.argmax(s, axis=-1).astype(jnp.int32)  # [B, K1]
    pos = jnp.arange(K)[None, :]
    match = jnp.logical_and(greedy[:, :K] == draft_tokens, pos < n_drafted[:, None])
    n_acc = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=-1), axis=-1).astype(jnp.int32)
    corr = jnp.take_along_axis(greedy, jnp.minimum(n_acc, K)[:, None], axis=-1)
    logp_all = jax.nn.log_softmax(s, axis=-1)
    logp = jnp.take_along_axis(logp_all[:, :K, :], draft_tokens[..., None], axis=-1)[..., 0]
    return n_acc[:, None], corr, logp


def lm_head_logits(o: jax.Array, w: jax.Array, *, block_v: int) -> jax.Array:
    """Blocked LM-head projection matching the fused kernel tile-for-tile.

    ``o [B, K1, H, hd]`` attention outputs times ``w [H*hd, Vp]`` (``Vp`` a
    ``block_v`` multiple) gives ``[B, K1, Vp]`` logits, one
    ``tiles.lm_head_tile`` — the EXACT per-head products and summation
    order the fused kernel issues — per (lane, vocab tile).  ``lax.map``
    keeps the traced program one tile long whatever B and Vp are.
    """
    B, K1, H, hd = o.shape
    Vp = w.shape[1]
    if Vp % block_v:
        raise ValueError(f"Vp={Vp} must be a multiple of block_v={block_v}")
    nt = Vp // block_v
    w_tiles = jnp.moveaxis(w.astype(jnp.float32).reshape(H, hd, nt, block_v), 2, 0)

    def lane(o_b):  # [K1, H, hd] -> [nt, K1, bv]
        return jax.lax.map(lambda w_t: lm_head_tile(o_b, w_t), w_tiles)

    tiles = jax.lax.map(lane, o.astype(jnp.float32))  # [B, nt, K1, bv]
    return jnp.moveaxis(tiles, 1, 2).reshape(B, K1, Vp)


def fused_target_logits(
    o: jax.Array,  # [B, K1, H, hd] f32 attention outputs
    w: jax.Array,  # [H*hd, Vp] f32 LM head, Vp a multiple of block_v
    *,
    block_v: int,
    v_true: int,
) -> jax.Array:
    """``lm_head_logits`` with padded vocab ids masked to ``-1e30``.

    Composing this with ``spec_verify`` reproduces the fused launch bitwise
    (same values through the same arithmetic).
    """
    logits = lm_head_logits(o, w, block_v=block_v)
    ids = jnp.arange(logits.shape[-1])[None, None, :]
    return jnp.where(ids >= v_true, -1e30, logits)


def spec_verify_fused_ref(
    q: jax.Array,  # [B, K+1, H, hd]
    k_pages: jax.Array,  # [P, bs, H, hd]
    v_pages: jax.Array,
    w: jax.Array,  # [F, Vp]
    block_tables: jax.Array,  # [B, G]
    lengths: jax.Array,  # [B, K+1] — valid KV length per query position
    draft_tokens: jax.Array,  # [B, K]
    n_drafted: jax.Array,  # [B]
    *,
    v_true: int,
    block_v: int,
    window: int = 1 << 30,
):
    """Fused-verify oracle: the unfused composition, stage by stage.

    Paged decode attention per query position (the ``decode_attention``
    oracle over position-flattened lanes), the blocked LM-head projection,
    then ``spec_verify_ref`` — the pure-JAX statement of what the one-launch
    kernel computes.
    """
    from ..decode_attention.ref import paged_decode_attention_ref

    B, K1, H, hd = q.shape
    qf = q.reshape(B * K1, H, hd)
    tf = jnp.repeat(jnp.asarray(block_tables, jnp.int32), K1, axis=0)
    lf = jnp.asarray(lengths, jnp.int32).reshape(B * K1)
    o = paged_decode_attention_ref(qf, k_pages, v_pages, tf, lf, window=window)
    o = o.reshape(B, K1, H, hd).astype(jnp.float32)
    logits = fused_target_logits(o, w, block_v=block_v, v_true=v_true)
    return spec_verify_ref(logits, draft_tokens, n_drafted)


def spec_verify_ragged_ref(
    logits_seq: Sequence,  # B entries of [K_i+1, V]
    tokens_seq: Sequence,  # B entries of length-K_i ints
) -> List[Tuple[int, int, np.ndarray]]:
    """Per-session oracle: one unpadded ``spec_verify_ref`` call per session."""
    out: List[Tuple[int, int, np.ndarray]] = []
    for lg, tk in zip(logits_seq, tokens_seq):
        k = len(tk)
        toks = jnp.asarray(tk, jnp.int32).reshape(1, k) if k else jnp.zeros((1, 0), jnp.int32)
        na, corr, lp = spec_verify_ref(
            jnp.asarray(lg)[None], toks, jnp.asarray([k], jnp.int32)
        )
        out.append((int(na[0, 0]), int(corr[0, 0]), np.asarray(lp[0])))
    return out


# --------------------------------------------------------------------------- #
# Tree-NAV (packed ancestor-mask) oracle
# --------------------------------------------------------------------------- #


def tree_topology(parents: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Derive (prow, depth, anc) from a packed parents array [B, N].

    prow[b, i]  — the target-logits row verifying node i: ``parents + 1``
                  (row 0 is the anchor, row 1+p is node p's own row).
    depth[b, i] — 1-based depth of node i (root-level nodes have depth 1).
    anc[b, i, j] — bool, node j lies on the root→i path (including j = i).

    Requires topological packing (``parents[i] < i``), which makes the parent
    one-hot strictly lower-triangular; the transitive closure then converges
    in ⌈log2 N⌉ boolean squarings.
    """
    B, N = parents.shape
    prow = (parents + 1).astype(jnp.int32)
    oh = parents[..., None] == jnp.arange(N, dtype=parents.dtype)[None, None, :]
    anc = jnp.eye(N, dtype=bool)[None] | oh  # self + direct parent
    for _ in range(max(int(math.ceil(math.log2(max(N, 2)))), 1)):
        anc = jnp.einsum("bij,bjk->bik", anc.astype(jnp.int32), anc.astype(jnp.int32)) > 0
    depth = jnp.sum(anc, axis=-1).astype(jnp.int32)
    return prow, depth, anc


def spec_verify_tree_ref(
    target_logits: jax.Array,  # [B, N+1, V] — row 0 anchor, row 1+i = node i
    tokens: jax.Array,  # [B, N] int32 packed node tokens
    parents: jax.Array,  # [B, N] int32, -1 = root level; parents[i] < i
    n_nodes: jax.Array,  # [B] int32 — valid node count (positions ≥ are pad)
):
    """Greedy tree-NAV oracle.

    Returns (n_accepted [B,1], best_node [B,1], correction [B,1], logp [B,N]):
    n_accepted is the depth of the deepest fully-accepted node (0 if no
    root-level node matches), best_node its packed index (-1 if none), and
    correction the target's greedy token at the accepted path's end (the
    anchor row when nothing is accepted).  ``logp[i]`` is the target log-prob
    of node i's token at its verify row (garbage at padded positions —
    callers slice ``logp[:n_nodes]``).
    """
    B, N1, V = target_logits.shape
    N = N1 - 1
    s = target_logits.astype(jnp.float32)
    greedy = jnp.argmax(s, axis=-1).astype(jnp.int32)  # [B, N1]
    prow, depth, anc = tree_topology(parents)
    g_at = jnp.take_along_axis(greedy, prow, axis=-1)  # [B, N]
    pos = jnp.arange(N)[None, :]
    valid = pos < n_nodes[:, None]
    match = jnp.logical_and(g_at == tokens, valid)
    # accepted[i] = every node on the root→i path matches (own match included
    # through anc[i, i]); pad nodes are masked out explicitly.
    accepted = jnp.all(match[:, None, :] | ~anc, axis=-1) & valid
    acc_depth = jnp.where(accepted, depth, 0)
    n_acc = jnp.max(acc_depth, axis=-1).astype(jnp.int32)  # [B]
    is_best = accepted & (acc_depth == n_acc[:, None]) & (n_acc[:, None] > 0)
    best = jnp.where(n_acc > 0, jnp.argmax(is_best, axis=-1).astype(jnp.int32), -1)
    best_row = jnp.where(n_acc > 0, best + 1, 0)
    corr = jnp.take_along_axis(greedy, best_row[:, None], axis=-1)
    logp_all = jax.nn.log_softmax(s, axis=-1)
    lp_rows = jnp.take_along_axis(logp_all, prow[:, :, None], axis=1)  # [B, N, V]
    logp = jnp.take_along_axis(lp_rows, tokens[..., None].astype(jnp.int32), axis=-1)[..., 0]
    return n_acc[:, None], best[:, None], corr, logp


def spec_verify_tree_ragged_ref(
    logits_seq: Sequence,  # B entries of [N_i+1, V]
    tokens_seq: Sequence,  # B entries of length-N_i ints
    parents_seq: Sequence,  # B entries of length-N_i ints
) -> List[Tuple[int, int, int, np.ndarray]]:
    """Per-session tree oracle: one unpadded ``spec_verify_tree_ref`` each."""
    out: List[Tuple[int, int, int, np.ndarray]] = []
    for lg, tk, pr in zip(logits_seq, tokens_seq, parents_seq):
        n = len(tk)
        na, best, corr, lp = spec_verify_tree_ref(
            jnp.asarray(lg)[None],
            jnp.asarray(tk, jnp.int32).reshape(1, n),
            jnp.asarray(pr, jnp.int32).reshape(1, n),
            jnp.asarray([n], jnp.int32),
        )
        out.append((int(na[0, 0]), int(best[0, 0]), int(corr[0, 0]), np.asarray(lp[0])))
    return out
