"""Fused verify == (paged attention ∘ LM head ∘ spec_verify), bit-exact.

The acceptance bar for the one-launch kernel: for every geometry, the fused
launch's integer outputs (n_accepted, correction) must be BIT-EXACT vs the
unfused composition — ``paged_decode_attention`` per query position, the
blocked ``fused_target_logits`` projection, then ``spec_verify`` — with the
same impl on both sides (interpret vs interpret, ref vs ref), and the
float log-probs bitwise equal too (identical values through identical
arithmetic).  The hypothesis sweep covers random ragged batches, tables,
GQA, non-pow2 lengths, and the all-accepted / all-rejected / B=1 edge
cases; the int8 suite pins fused-q8 == composed-q8 plus a bounded error vs
the fp32 pipeline.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings

from strategies import (
    KEY,
    assert_triples_match as _assert_fused_matches,
    composed_verify as _composed,
    make_rect_case as _make_case,
    rect_geometries,
)

from repro.kernels.decode_attention import paged_decode_attention
from repro.kernels.spec_verify import (
    spec_verify_fused,
    spec_verify_fused_batched,
)
from repro.models.paged_kv import PagedKVPool


@pytest.mark.parametrize("impl", ["ref", "interpret"])
@pytest.mark.parametrize(
    "B,K,H,Hkv,hd,bs,G,P,V",
    [
        (2, 3, 2, 2, 16, 8, 4, 16, 512),
        (1, 1, 2, 1, 16, 8, 2, 8, 256),  # B=1, GQA, single draft token
        (3, 4, 4, 2, 8, 4, 8, 32, 384),  # non-pow2 vocab -> padded lanes
    ],
)
def test_fused_bitexact_vs_composition(impl, B, K, H, Hkv, hd, bs, G, P, V):
    q, kp, vp, w, tables, lengths, tokens, nd = _make_case(B, K, H, Hkv, hd, bs, G, P, V)
    fused = spec_verify_fused(
        q, kp, vp, w, tables, lengths, tokens, nd, impl=impl, block_v=256
    )
    composed = _composed(
        q, kp, vp, w, tables, lengths, tokens, nd, impl=impl, block_v=256
    )
    _assert_fused_matches(fused, composed)


@pytest.mark.parametrize("forced", ["accept_all", "reject_all"])
def test_fused_forced_accept_reject_edges(forced):
    """All-accepted and all-rejected drafts round-trip through the fusion."""
    B, K, H, hd, bs, G, P, V = 2, 3, 2, 16, 8, 4, 16, 512
    q, kp, vp, w, tables, lengths, tokens, nd = _make_case(
        B, K, H, H, hd, bs, G, P, V, seed=5, sharp=True
    )
    # Compute the target's actual greedy chain via the composition, then
    # either copy it (all match) or corrupt every position (none match).
    na, corr, _ = _composed(q, kp, vp, w, tables, lengths, tokens, nd, impl="ref", block_v=256)
    o = paged_decode_attention(
        q.reshape(B * (K + 1), H, hd), kp, vp,
        jnp.repeat(tables, K + 1, axis=0), lengths.reshape(-1), impl="ref",
    ).reshape(B, K + 1, H * hd).astype(jnp.float32)
    greedy = np.asarray(jnp.argmax(jnp.dot(o, w.astype(jnp.float32)), axis=-1))
    if forced == "accept_all":
        tokens = jnp.asarray(greedy[:, :K], jnp.int32)
    else:
        tokens = jnp.asarray((greedy[:, :K] + 1) % V, jnp.int32)
    nd = jnp.full((B,), K, jnp.int32)
    fused = spec_verify_fused(q, kp, vp, w, tables, lengths, tokens, nd, impl="interpret", block_v=256)
    composed = _composed(q, kp, vp, w, tables, lengths, tokens, nd, impl="interpret", block_v=256)
    _assert_fused_matches(fused, composed)
    want = K if forced == "accept_all" else 0
    np.testing.assert_array_equal(np.asarray(fused[0]).ravel(), want)


def test_fused_bitexact_where_the_vmem_rule_raises_the_limit():
    """At minicpm-2b's head widths (36 MHA heads of 64) and the edge window's
    K+1 = 9 rows, ``block_v`` 512 needs more than the default scoped VMEM,
    so the launch asks for a limit of its own.  Its results are still the
    unfused composition's, bit for bit, with a vocabulary that is not a
    multiple of ``block_v`` and drafts that the target accepts."""
    from repro.kernels.spec_verify.kernel import DEFAULT_BV, fused_vmem_limit

    B, K, H, hd, bs, G, P, V = 2, 8, 36, 64, 16, 2, 6, 1000
    assert fused_vmem_limit(K + 1, H, hd, bs, DEFAULT_BV, jnp.float32, False) is not None
    q, kp, vp, w, tables, lengths, tokens, nd = _make_case(
        B, K, H, H, hd, bs, G, P, V, seed=11, sharp=True
    )
    _, greedy, _ = _composed(
        q, kp, vp, w, tables, lengths, jnp.full((B, K), -1, jnp.int32),
        jnp.ones((B,), jnp.int32), impl="ref", block_v=DEFAULT_BV,
    )
    tokens = tokens.at[:, 0].set(jnp.asarray(greedy)[:, 0])  # row 0's greedy token
    fused = spec_verify_fused(
        q, kp, vp, w, tables, lengths, tokens, nd, impl="interpret", block_v=DEFAULT_BV
    )
    composed = _composed(
        q, kp, vp, w, tables, lengths, tokens, nd, impl="interpret", block_v=DEFAULT_BV
    )
    _assert_fused_matches(fused, composed)
    assert (np.asarray(fused[0]) >= 1).all()


@settings(max_examples=10, deadline=None)
@given(geom=rect_geometries())
def test_property_fused_bitexact(geom):
    """Random geometry sweep: fused == composition bitwise, both impls."""
    B, K, Hkv, gqa, bs, G, seed = (
        geom["B"], geom["K"], geom["Hkv"], geom["gqa"], geom["bs"], geom["G"], geom["seed"]
    )
    H = Hkv * gqa
    hd = 8
    P = max(2 * G, B * G)
    V = 256
    q, kp, vp, w, tables, lengths, tokens, nd = _make_case(
        B, K, H, Hkv, hd, bs, G, P, V, seed=seed
    )
    for impl in ("ref", "interpret"):
        fused = spec_verify_fused(q, kp, vp, w, tables, lengths, tokens, nd, impl=impl, block_v=128)
        composed = _composed(q, kp, vp, w, tables, lengths, tokens, nd, impl=impl, block_v=128)
        _assert_fused_matches(fused, composed)


def test_fused_batched_ragged_from_pool():
    """Serving entry: ragged sessions through a real pool, sentinel padding,
    matching per-session composition results."""
    rng = np.random.default_rng(9)
    H, hd, bs, V = 2, 16, 4, 512
    pool = PagedKVPool(num_blocks=16, block_size=bs, n_layers=1, n_kv_heads=H, head_dim=hd)
    ks = [3, 1, 4]
    q_seq, tok_seq, tables_seq, base = [], [], [], []
    keys = jax.random.split(KEY, 16)
    for s, k in enumerate(ks):
        pool.create(s)
        T = int(rng.integers(k + 2, 12))
        kv = jax.random.normal(keys[2 * s], (1, T, H, hd))
        pool.write(s, kv, kv + 0.5)
        q_seq.append(jax.random.normal(keys[2 * s + 1], (k + 1, H, hd)))
        tok_seq.append(rng.integers(0, V, size=k).tolist())
        tables_seq.append(list(pool.table(s)))
        base.append(T - k)
    w = jax.random.normal(keys[-1], (H * hd, V))
    out = spec_verify_fused_batched(
        q_seq, tok_seq, tables_seq, base,
        pool.k_pages[0], pool.v_pages[0], w,
        impl="interpret", block_v=256, pad_page_id=pool.sentinel_page,
    )
    # Oracle: per-session rectangular fused entry (B=1, no padding).
    for s, k in enumerate(ks):
        lengths = jnp.asarray([[base[s] + i for i in range(k + 1)]], jnp.int32)
        tab = jnp.asarray([tables_seq[s]], jnp.int32)
        na, corr, logp = spec_verify_fused(
            q_seq[s][None], pool.k_pages[0], pool.v_pages[0], w, tab, lengths,
            jnp.asarray([tok_seq[s]], jnp.int32), jnp.asarray([k], jnp.int32),
            impl="interpret", block_v=256,
        )
        assert out[s][0] == int(np.asarray(na)[0, 0])
        assert out[s][1] == int(np.asarray(corr)[0, 0])
        np.testing.assert_allclose(out[s][2], np.asarray(logp)[0, :k], atol=1e-5)


def test_fused_padded_lanes_only_touch_sentinel():
    """A bucketed fused launch must never DMA a page the padded lane does
    not own: poisoning every page NOT in the real sessions' tables (plus
    the sentinel) with NaN leaves the results unchanged."""
    rng = np.random.default_rng(4)
    H, hd, bs, V = 2, 8, 4, 256
    pool = PagedKVPool(num_blocks=8, block_size=bs, n_layers=1, n_kv_heads=H, head_dim=hd)
    keys = jax.random.split(KEY, 4)
    pool.create(0)
    kv = jax.random.normal(keys[0], (1, 6, H, hd))
    pool.write(0, kv, kv)
    # A second, "foreign" session whose pages must never be read.
    pool.create(1)
    foreign = jax.random.normal(keys[1], (1, 8, H, hd))
    pool.write(1, foreign, foreign)
    q_seq = [jax.random.normal(keys[2], (3, H, hd))]
    tok_seq = [rng.integers(0, V, size=2).tolist()]
    tables_seq = [list(pool.table(0))]
    w = jax.random.normal(keys[3], (H * hd, V))
    clean = spec_verify_fused_batched(
        q_seq, tok_seq, tables_seq, [4], pool.k_pages[0], pool.v_pages[0], w,
        impl="interpret", block_v=256, pad_page_id=pool.sentinel_page,
    )
    owned = set(tables_seq[0]) | {pool.sentinel_page}
    kp = np.array(pool.k_pages[0])
    vp = np.array(pool.v_pages[0])
    for p in range(kp.shape[0]):
        if p not in owned:
            kp[p] = np.nan
            vp[p] = np.nan
    poisoned = spec_verify_fused_batched(
        q_seq, tok_seq, tables_seq, [4], jnp.asarray(kp), jnp.asarray(vp), w,
        impl="interpret", block_v=256, pad_page_id=pool.sentinel_page,
    )
    assert clean[0][0] == poisoned[0][0] and clean[0][1] == poisoned[0][1]
    np.testing.assert_array_equal(clean[0][2], poisoned[0][2])
    assert np.all(np.isfinite(poisoned[0][2]))


def test_fused_q8_bitexact_vs_q8_composition_and_bounded_vs_fp32():
    """Int8 fused == int8 composition bitwise; both near the fp32 result."""
    B, K, H, hd, bs, G, P, V = 2, 3, 2, 16, 8, 4, 16, 512
    q, kp, vp, w, tables, lengths, tokens, nd = _make_case(
        B, K, H, H, hd, bs, G, P, V, seed=11, sharp=True
    )
    kq, ksc, kz = PagedKVPool.quantize_kv(kp)
    vq, vsc, vz = PagedKVPool.quantize_kv(vp)
    quant = (ksc, kz, vsc, vz)
    fused = spec_verify_fused(
        q, kq, vq, w, tables, lengths, tokens, nd,
        impl="interpret", block_v=256, quant=quant,
    )
    composed = _composed(
        q, kq, vq, w, tables, lengths, tokens, nd,
        impl="interpret", block_v=256, quant=quant,
    )
    _assert_fused_matches(fused, composed)
    # Sharp LM head => quantization noise cannot flip the greedy argmax, so
    # the integer outputs match the fp32 pipeline; logp drift stays small.
    fp32 = _composed(q, kp, vp, w, tables, lengths, tokens, nd, impl="interpret", block_v=256)
    np.testing.assert_array_equal(np.asarray(fused[0]), np.asarray(fp32[0]))
    np.testing.assert_array_equal(np.asarray(fused[1]), np.asarray(fp32[1]))
