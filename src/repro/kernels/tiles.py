"""Tile math shared by the Pallas kernels and their pure-JAX oracles.

Each helper is the arithmetic of ONE grid step, written in shapes the TPU
compiler lowers (2-D or wider, no batch dims on a non-leading axis, lane
reductions with ``keepdims``).  A kernel and the composition it must match
call the same helper on the same shapes, which is what makes them
bit-exact on the CPU interpreter:

* ``attend_page`` — one KV page of flash-decode for one query: the paged
  and flat decode kernels run it per lane, the fused verify kernel per
  draft position;
* ``lm_head_tile`` — one ``[K1, block_v]`` logits tile as a sum of per-head
  ``[K1, hd] @ [hd, block_v]`` products: the fused verify kernel runs it per
  vocab step, ``ref.lm_head_logits`` per (lane, tile).

Matmuls ask for ``Precision.HIGHEST`` so a TPU computes them in float32
rather than a single bfloat16 pass; the CPU ignores the flag.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30
HIGHEST = jax.lax.Precision.HIGHEST


def attend_page(q, k, v, k_pos, length, m_prev, l_prev, acc_prev, *, sm_scale, window):
    """Advance one query's online softmax over one KV page.

    ``q [H, hd]``; ``k, v [bs, H, hd]`` float32; ``k_pos [bs, 1, 1]`` the
    page's logical positions; ``length`` the query's valid KV length.  State
    ``m, l [H, 1]`` and ``acc [H, hd]``.  Scores are elementwise products
    reduced over ``hd`` (no per-head batched matmul, which the TPU compiler
    refuses on a non-leading batch axis).  Returns the new ``(m, l, acc)``.
    """
    s = jnp.sum(q[None] * k, axis=-1, keepdims=True) * sm_scale  # [bs, H, 1]
    valid = jnp.logical_and(k_pos < length, k_pos >= length - window)
    s = jnp.where(valid, s, NEG_INF)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))  # [H, 1]
    p = jnp.exp(s - m_new[None])
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=0)
    acc_new = acc_prev * alpha + jnp.sum(p * v, axis=0)  # [H, hd]
    return m_new, l_new, acc_new


def lm_head_tile(o, w):
    """One logits tile: ``sum_h o[:, h, :] @ w[h]`` accumulated in head order.

    ``o [K1, H, hd]`` and ``w [H, hd, bv]`` may be arrays or Pallas refs
    (both index the same way).  Returns ``[K1, bv]`` float32.
    """
    acc = jnp.dot(o[:, 0, :], w[0], precision=HIGHEST, preferred_element_type=jnp.float32)
    for h in range(1, w.shape[0]):
        acc = acc + jnp.dot(
            o[:, h, :], w[h], precision=HIGHEST, preferred_element_type=jnp.float32
        )
    return acc
