"""Plain float32 reference of the served synthetic target, for ``correct``.

The launcher (``launch/serve.py --backend spec``) serves a seeded synthetic
target: per-session queries, one attention layer over position-keyed KV, and
an LM head ``[H*hd, V]``.  This module rebuilds that target from the seed
with its own code and imports nothing of the program: no kernel, no pool, no
block table, no weights the program made.

* Queries: ``normal(fold_in(fold_in(PRNGKey(seed), 88), session*131 + K),
  (K+1, H, hd))``.
* KV of layer 0 at position ``p``: ``k = sin(p*0.37 + j*0.11)`` over the
  flattened ``(kv head, dim)`` index ``j``, ``v = roll(k, 1, dim) * 0.5``,
  read for positions ``[0, base + i)`` straight from this formula, never
  from the pool's pages.
* LM head: ``normal(fold_in(PRNGKey(seed), 77), (H*hd, V)) * 16/sqrt(H*hd)``.
* Row ``i`` of a round with ``K`` drafts sees ``base + i`` positions, where
  ``base = prefix + pos + 1`` and ``pos`` is the session's committed length
  as the edge sent it.  Query head ``h`` reads KV head ``h // (H/Hkv)``.

Everything is float32.  ``precision`` picks the matmul arithmetic:
``highest`` (float32, the configuration's own), ``high`` (three bfloat16
passes, the control one step below it) or ``bf16`` (one bfloat16 pass).
The two lower ones are written out as explicit bfloat16 splits, so they
compute the same on any backend.  The LM head is applied in vocabulary
blocks and rounds in blocks of rows, so the reference fits beside nothing.
"""

from __future__ import annotations

import math
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("highest", "high", "bf16")
K1_MAX = 9  # rows per round: the edge window (8 drafts) plus the bonus row
NEG = -1e30


def kv_layer0(n_pos: int, n_kv_heads: int, head_dim: int):
    """Layer-0 K and V for positions ``[0, n_pos)``: ``[n_pos, Hkv, hd]`` each."""
    pos = np.arange(n_pos, dtype=np.float32)
    phase = np.arange(n_kv_heads * head_dim, dtype=np.float32).reshape(1, n_kv_heads, head_dim)
    k = np.sin(pos[:, None, None] * 0.37 + phase * 0.11).astype(np.float32)
    return k, (np.roll(k, 1, axis=-1) * 0.5).astype(np.float32)


def lm_head(seed: int, n_heads: int, head_dim: int, vocab: int):
    """The LM head ``[H*hd, V]`` float32, on the default device."""
    F = n_heads * head_dim
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 77)
    return jax.jit(
        lambda k: jax.random.normal(k, (F, vocab)) * (16.0 / math.sqrt(F))
    )(key)


def _split(x):
    hi = x.astype(jnp.bfloat16)
    return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def _mm(spec: str, a, b, precision: str):
    """``einsum(spec, a, b)`` in float32 with the chosen matmul arithmetic."""
    if precision == "highest":
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)
    dot = lambda x, y: jnp.einsum(spec, x, y, preferred_element_type=jnp.float32)  # noqa: E731
    a_hi, a_lo = _split(a)
    b_hi, b_lo = _split(b)
    if precision == "bf16":
        return dot(a_hi, b_hi)
    if precision == "high":
        return dot(a_hi, b_hi) + dot(a_hi, b_lo) + dot(a_lo, b_hi)
    raise ValueError(f"precision must be one of {PRECISIONS}, got {precision!r}")


def _rows_fn(n_heads: int, n_kv_heads: int, head_dim: int, vocab: int, block_v: int, precision: str):
    """Jitted block step: queries + lengths + asked tokens -> row statistics."""
    g = n_heads // n_kv_heads
    scale = 1.0 / math.sqrt(head_dim)
    n_blocks = -(-vocab // block_v)
    bv = min(block_v, vocab)

    def fn(q, lengths, k, v, w, ask):
        # q [R, K1, H, hd]; lengths [R, K1]; k, v [P, Hkv, hd]; ask [R, K1, A]
        R, K1 = lengths.shape
        kk = jnp.repeat(k, g, axis=1)
        vv = jnp.repeat(v, g, axis=1)
        s = _mm("rihd,phd->rihp", q, kk, precision) * scale
        valid = jnp.arange(k.shape[0])[None, None, None, :] < lengths[:, :, None, None]
        s = jnp.where(valid, s, NEG)
        p = jax.nn.softmax(s, axis=-1)
        o = _mm("rihp,phd->rihd", p, vv, precision)
        x = o.reshape(R * K1, n_heads * head_dim)
        a = ask.reshape(R * K1, -1)

        def body(b, carry):
            m, arg, se, picked = carry
            start = jnp.minimum(b * bv, vocab - bv)
            w_b = jax.lax.dynamic_slice_in_dim(w, start, bv, axis=1)
            ids = start + jnp.arange(bv)
            logits = _mm("rf,fv->rv", x, w_b, precision)
            logits = jnp.where((ids >= b * bv)[None, :], logits, NEG)
            bm = jnp.max(logits, axis=1)
            barg = start + jnp.argmax(logits, axis=1)
            new_m = jnp.maximum(m, bm)
            se = se * jnp.exp(m - new_m) + jnp.sum(jnp.exp(logits - new_m[:, None]), axis=1)
            arg = jnp.where(bm > m, barg, arg)
            inside = (a >= b * bv) & (a >= start) & (a < start + bv)
            got = jnp.take_along_axis(logits, jnp.clip(a - start, 0, bv - 1), axis=1)
            picked = jnp.where(inside, got, picked)
            return new_m, arg, se, picked

        init = (
            jnp.full((R * K1,), NEG, jnp.float32),
            jnp.zeros((R * K1,), jnp.int32),
            jnp.zeros((R * K1,), jnp.float32),
            jnp.full(a.shape, jnp.nan, jnp.float32),
        )
        m, arg, se, picked = jax.lax.fori_loop(0, n_blocks, body, init)
        lse = m + jnp.log(se)
        return (m.reshape(R, K1), arg.reshape(R, K1), lse.reshape(R, K1),
                picked.reshape(R, K1, -1))

    return jax.jit(fn)


def _queries(key, sessions: np.ndarray, K: int, n_heads: int, head_dim: int):
    """Each session's queries for a round of ``K`` drafts: ``[n, K+1, H, hd]``."""
    k88 = jax.random.fold_in(key, 88)
    n = len(sessions)
    data = np.zeros(1 << max(n - 1, 0).bit_length(), np.uint32)  # pow2 rows: few shapes
    data[:n] = sessions * 131 + K
    return jax.vmap(
        lambda d: jax.random.normal(jax.random.fold_in(k88, d), (K + 1, n_heads, head_dim))
    )(jnp.asarray(data))[:n]


def row_stats(
    model: Dict,
    seed: int,
    rounds: Sequence[Dict],
    ask: np.ndarray,
    *,
    prefix: int,
    precision: str = "highest",
    w=None,
    rounds_per_block: int = 256,
    block_v: int = 8192,
) -> Dict[str, np.ndarray]:
    """Per-row statistics of the target for each round, at ``precision``.

    ``rounds`` carry ``session``, ``pos`` and ``tokens`` (the drafts, ``K =
    len(tokens)`` of them, ``K <= 8``).  ``ask[r, i, a]`` names vocabulary ids
    whose logits to return for row ``i`` of round ``r`` (``-1``: none).
    Returns ``max``, ``argmax`` and ``lse`` ``[n, 9]`` and ``picked`` ``[n, 9,
    A]`` (NaN where nothing was asked); rows past ``K`` hold garbage.
    """
    H, Hkv, hd, V = (model["num_attention_heads"], model["num_key_value_heads"],
                     model["head_dim"], model["vocab_size"])
    n = len(rounds)
    if w is None:
        w = lm_head(seed, H, hd, V)
    key = jax.random.PRNGKey(seed)
    ks = np.array([len(r["tokens"]) for r in rounds], np.int64)
    if n and (ks.max() >= K1_MAX or ks.min() < 1):
        raise ValueError(f"draft counts must lie in [1, {K1_MAX - 1}]")
    sessions = np.array([r["session"] for r in rounds], np.int64)
    base = prefix + np.array([r["pos"] for r in rounds], np.int64) + 1
    n_pos = int(-(-(int((base + ks).max()) if n else 1) // 128) * 128)
    k, v = kv_layer0(n_pos, Hkv, hd)
    k, v = jnp.asarray(k), jnp.asarray(v)
    q = np.zeros((n, K1_MAX, H, hd), np.float32)
    for K in np.unique(ks):
        idx = np.nonzero(ks == K)[0]
        q[idx, : K + 1] = np.asarray(_queries(key, sessions[idx], int(K), H, hd))
    lengths = np.zeros((n, K1_MAX), np.int32)
    for r in range(n):
        lengths[r, : ks[r] + 1] = base[r] + np.arange(ks[r] + 1)
    fn = _rows_fn(H, Hkv, hd, V, block_v, precision)
    out: Dict[str, List[np.ndarray]] = {"max": [], "argmax": [], "lse": [], "picked": []}
    R = rounds_per_block
    for s0 in range(0, n, R):
        qb, lb, ab = q[s0:s0 + R], lengths[s0:s0 + R], ask[s0:s0 + R]
        pad = R - len(qb)
        if pad:
            qb = np.concatenate([qb, np.zeros((pad,) + qb.shape[1:], np.float32)])
            lb = np.concatenate([lb, np.ones((pad, K1_MAX), np.int32)])
            ab = np.concatenate([ab, np.full((pad,) + ab.shape[1:], -1, ab.dtype)])
        res = fn(jnp.asarray(qb), jnp.asarray(lb), k, v, w, jnp.asarray(ab, jnp.int32))
        for name, val in zip(("max", "argmax", "lse", "picked"), res):
            out[name].append(np.asarray(val)[: R - pad])
    if not n:
        return {"max": np.zeros((0, K1_MAX)), "argmax": np.zeros((0, K1_MAX), np.int32),
                "lse": np.zeros((0, K1_MAX)), "picked": np.zeros((0, K1_MAX, ask.shape[-1]))}
    return {name: np.concatenate(vals) for name, vals in out.items()}
