"""Shared verify-batch strategies and the cross-path differential harness.

Every spec-verify test family (chain, tree, fused, batched, sharded) draws
its random cases from here so all paths are exercised on the SAME
distribution of shapes: ragged draft lengths, GQA head ratios, non-pow2
vocabularies, ragged block tables, and mixed accept/reject patterns.

Two case shapes exist:

* ``make_rect_case`` — a rectangular [B, K+1] fused-verify geometry (the
  kernel-level contract; ported from the ad-hoc builder that used to live
  in ``test_spec_verify_fused.py``).
* ``make_ragged_case`` — B ragged sessions with per-session draft lengths
  and block tables, materialized over one shared page arena (the serving
  contract of the ``*_batched`` entries).

``assert_paths_agree`` is the differential harness: given one ragged case
it runs every requested verify path — per-session chain composition,
chain-topology tree, per-session fused, one-launch fused-batched, and the
sharded launch at each shard count — and asserts they agree.  Paths that
share a launch geometry must agree BIT-FOR-BIT (``assert_array_equal`` on
the log-probs); integer verdicts (n_accepted, correction) must be equal
across every path unconditionally.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import strategies as st

from repro.kernels.decode_attention import paged_decode_attention
from repro.kernels.spec_verify import (
    fused_target_logits,
    spec_verify,
    spec_verify_batched,
    spec_verify_fused,
    spec_verify_fused_batched,
    spec_verify_tree_batched,
)
from repro.kernels.spec_verify.ops import padded_dims, ragged_launch_args, ragged_results

KEY = jax.random.PRNGKey(23)

# --------------------------------------------------------------------------- #
# Hypothesis strategies
# --------------------------------------------------------------------------- #
# Non-pow2 vocabularies on purpose: padded lanes must stay inert everywhere.
VOCABS = (96, 256, 384)
GQA_RATIOS = (1, 2, 3)


def rect_geometries(max_B: int = 3, max_K: int = 4):
    """Rectangular fused-verify geometries (kwargs for ``make_rect_case``).

    ``H = Hkv * gqa`` and ``P/V`` are derived by the consumer so every drawn
    dict is valid by construction (GQA divides, enough pages for the tables).
    """
    return st.fixed_dictionaries(
        dict(
            B=st.integers(1, max_B),
            K=st.integers(1, max_K),
            Hkv=st.sampled_from([1, 2]),
            gqa=st.sampled_from(list(GQA_RATIOS)),
            bs=st.sampled_from([4, 8]),
            G=st.integers(2, 4),
            seed=st.integers(0, 10_000),
        )
    )


def ragged_geometries(max_sessions: int = 4, max_k: int = 6):
    """Ragged serving-batch geometries (kwargs for ``make_ragged_case``)."""
    return st.fixed_dictionaries(
        dict(
            ks=st.lists(st.integers(1, max_k), min_size=1, max_size=max_sessions),
            Hkv=st.sampled_from([1, 2]),
            gqa=st.sampled_from(list(GQA_RATIOS)),
            bs=st.sampled_from([4, 8]),
            V=st.sampled_from(list(VOCABS)),
            seed=st.integers(0, 10_000),
            accept_bias=st.sampled_from([None, 0.0, 0.7, 1.0]),
        )
    )


# --------------------------------------------------------------------------- #
# Case builders
# --------------------------------------------------------------------------- #
def make_rect_case(B, K, H, Hkv, hd, bs, G, P, V, seed=0, sharp=False):
    """Random queries/pages/LM-head/tables + causal per-position lengths."""
    rng = np.random.default_rng(seed)
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (B, K + 1, H, hd))
    k_pages = jax.random.normal(ks[1], (P, bs, Hkv, hd))
    v_pages = jax.random.normal(ks[2], (P, bs, Hkv, hd))
    scale = 8.0 if sharp else 1.0  # sharp => near-deterministic greedy
    w = jax.random.normal(ks[3], (H * hd, V)) * scale
    tables = np.stack([rng.choice(P, G, replace=False) for _ in range(B)]).astype(np.int32)
    S = G * bs
    # lengths[b, i] = KV visible to position i; last position sees base+K.
    base = rng.integers(1, S - K, size=B)
    lengths = (base[:, None] + np.arange(K + 1)[None, :]).astype(np.int32)
    tokens = rng.integers(0, V, size=(B, K)).astype(np.int32)
    nd = rng.integers(0, K + 1, size=B).astype(np.int32)
    nd[0] = K  # always exercise a full-length row
    return q, k_pages, v_pages, w, jnp.asarray(tables), jnp.asarray(lengths), jnp.asarray(tokens), jnp.asarray(nd)


@dataclasses.dataclass(frozen=True)
class RaggedCase:
    """B ragged sessions over one shared page arena (the serving shape)."""

    q_seq: List[np.ndarray]  # per session [K_i+1, H, hd]
    tok_seq: List[List[int]]
    tables_seq: List[List[int]]
    base: List[int]  # committed KV length per session
    k_pages: jnp.ndarray  # [P, bs, Hkv, hd] (or int8 when quantized)
    v_pages: jnp.ndarray
    w: jnp.ndarray  # [H*hd, V]
    v_true: int
    sentinel_page: int
    quant: Optional[Tuple] = None  # (k_scale, k_zero, v_scale, v_zero)

    @property
    def ks(self) -> List[int]:
        return [len(t) for t in self.tok_seq]

    @property
    def q(self) -> np.ndarray:
        """The sessions' queries padded for the ragged entries' launch."""
        return pad_queries(self.q_seq)


def pad_queries(q_seq: Sequence) -> np.ndarray:
    """Per-session ``[K_i+1, H, hd]`` queries -> the padded ``[Bp, Kp+1, H, hd]``.

    The host-side form of what a verify backend draws on the device: row
    ``i`` holds session ``i``'s positions first, zeros after; pad rows zero.
    """
    Bp, Kp = padded_dims([len(q) - 1 for q in q_seq])
    H, hd = q_seq[0].shape[1:]
    out = np.zeros((Bp, Kp + 1, H, hd), np.float32)
    for i, q in enumerate(q_seq):
        out[i, : len(q)] = np.asarray(q, np.float32)
    return out


def make_ragged_case(
    ks: Sequence[int],
    *,
    Hkv: int = 2,
    gqa: int = 1,
    hd: int = 8,
    bs: int = 4,
    V: int = 256,
    seed: int = 0,
    sharp: bool = False,
    accept_bias: Optional[float] = None,
    quantize: Optional[str] = None,
) -> RaggedCase:
    """Materialize B ragged sessions with disjoint tables over one arena.

    ``accept_bias`` controls the accept/reject pattern: ``None`` draws
    uniform tokens, a float p replaces each draft with the target's greedy
    token with probability p (1.0 = all-accepted rounds, 0.0 = guaranteed
    first-token rejection under a sharp LM head).
    """
    H = Hkv * gqa
    rng = np.random.default_rng(seed)
    keys = jax.random.split(jax.random.fold_in(KEY, seed), 2 * len(ks) + 3)
    # Upper bound on pages any draw can need; page 0 reserved as sentinel.
    P = sum((k + 9 + bs - 1) // bs for k in ks) + 2
    k_pages = jax.random.normal(keys[-1], (P, bs, Hkv, hd))
    v_pages = jax.random.normal(keys[-2], (P, bs, Hkv, hd))
    scale = 8.0 if sharp else 1.0
    w = jax.random.normal(keys[-3], (H * hd, V)) * scale
    q_seq, tok_seq, tables_seq, base = [], [], [], []
    free = list(range(1, P))
    rng.shuffle(free)
    for s, k in enumerate(ks):
        T = int(rng.integers(k + 2, k + 10))
        G = (T + bs - 1) // bs
        tables_seq.append([free.pop() for _ in range(G)])
        q_seq.append(np.asarray(jax.random.normal(keys[2 * s], (k + 1, H, hd)), np.float32))
        base.append(T - k)
        tok_seq.append(rng.integers(0, V, size=k).tolist())
    quant = None
    if quantize == "int8":
        from repro.models.paged_kv import PagedKVPool

        kq, ksc, kz = PagedKVPool.quantize_kv(k_pages)
        vq, vsc, vz = PagedKVPool.quantize_kv(v_pages)
        k_pages, v_pages, quant = kq, vq, (ksc, kz, vsc, vz)
    case = RaggedCase(q_seq, tok_seq, tables_seq, base, k_pages, v_pages, w, V, 0, quant)
    if accept_bias is not None:
        greedy = [np.argmax(lg, axis=-1) for lg in session_logits(case)]
        mix = rng.random(sum(ks)) < accept_bias
        it = iter(mix)
        case = dataclasses.replace(
            case,
            tok_seq=[
                [int(g[i]) if next(it) else int((g[i] + 1) % V) for i in range(k)]
                for g, k in zip(greedy, ks)
            ],
        )
    return case


def pool_backed_case(case: RaggedCase, num_blocks: int = 64):
    """Rebuild a RaggedCase inside a real ``PagedKVPool`` (same values).

    Returns ``(pool, case2)`` where ``case2`` reads pages from the pool's
    arena: tables are pool-assigned, the sentinel contract is the pool's.
    """
    from repro.kernels.decode_attention.ref import dequantize_pages
    from repro.models.paged_kv import PagedKVPool

    _, bs, Hkv, hd = case.k_pages.shape
    pool = PagedKVPool(
        num_blocks=num_blocks, block_size=int(bs), n_layers=1,
        n_kv_heads=int(Hkv), head_dim=int(hd),
        quantize="int8" if case.quant is not None else None,
    )
    kp, vp = jnp.asarray(case.k_pages), jnp.asarray(case.v_pages)
    if case.quant is not None:
        ksc, kz, vsc, vz = case.quant
        kp = dequantize_pages(kp, ksc, kz)
        vp = dequantize_pages(vp, vsc, vz)
    kp, vp = np.asarray(kp), np.asarray(vp)
    tables_seq = []
    for s, (k, tab) in enumerate(zip(case.ks, case.tables_seq)):
        T = case.base[s] + k
        k_rows = kp[tab].reshape(-1, Hkv, hd)[:T]
        v_rows = vp[tab].reshape(-1, Hkv, hd)[:T]
        pool.create(s)
        pool.write(s, jnp.asarray(k_rows[None]), jnp.asarray(v_rows[None]))
        tables_seq.append(list(pool.table(s)))
    case2 = dataclasses.replace(
        case,
        tables_seq=tables_seq,
        k_pages=pool.k_pages[0],
        v_pages=pool.v_pages[0],
        sentinel_page=pool.sentinel_page,
        quant=(pool.k_scale[0], pool.k_zero[0], pool.v_scale[0], pool.v_zero[0])
        if case.quant is not None
        else None,
    )
    return pool, case2


def ragged_logits_requests(ks, V, seed=0):
    """Per-session logits [K_i+1, V] + drafts with a mix of greedy/random.

    The logits-level (no KV pages) ragged batch for the chain/tree scan
    entries; ported from the ad-hoc builder in ``test_spec_verify_batched``.
    """
    logits_seq, tokens_seq = [], []
    for i, k in enumerate(ks):
        keys = jax.random.split(jax.random.fold_in(KEY, seed * 101 + i), 3)
        lg = jax.random.normal(keys[0], (k + 1, V)) * 3
        greedy = jnp.argmax(lg, -1)[:k]
        rnd = jax.random.randint(keys[1], (k,), 0, V)
        mix = jax.random.bernoulli(keys[2], 0.7, (k,))
        tokens_seq.append(np.asarray(jnp.where(mix, greedy, rnd), np.int32))
        logits_seq.append(np.asarray(lg, np.float32))
    return logits_seq, tokens_seq


def fused_backend(quantize=None, impl="ref", num_blocks=16, shards=None):
    """The served backend over a real pool; over a ``shards``-device mesh when given.

    One fixed tiny geometry (H=2, hd=8, bs=4, V=256) with seeded LM head and
    queries, so unsharded and sharded backends built here are comparable
    request-for-request.  Returns ``(backend, pool, w, V)``.
    """
    from repro.models.paged_kv import PagedKVPool
    from repro.runtime import SpecVerifyBackend
    from repro.sharding import host_mesh

    H, hd, bs, V = 2, 8, 4, 256
    pool = PagedKVPool(
        num_blocks=num_blocks, block_size=bs, n_layers=1, n_kv_heads=H, head_dim=hd,
        quantize=quantize,
    )
    w = np.asarray(jax.random.normal(jax.random.fold_in(KEY, 77), (H * hd, V)) * 4, np.float32)

    def query_fn(session, tokens, k=None):
        n = len(tokens) if k is None else k
        draw = jax.random.fold_in(jax.random.fold_in(KEY, 88), session * 131 + n)
        return jax.random.normal(draw, (len(tokens) + 1, H, hd))

    backend = SpecVerifyBackend(
        kv_pool=pool, query_fn=query_fn, lm_head=w, impl=impl, block_v=256,
        mesh=None if shards is None else host_mesh(shards),
    )
    return backend, pool, w, V


# --------------------------------------------------------------------------- #
# Reference compositions
# --------------------------------------------------------------------------- #
def composed_verify(q, k_pages, v_pages, w, tables, lengths, tokens, nd, *, impl, block_v, quant=None):
    """The unfused two-launch path the fused kernel must reproduce bitwise."""
    logits = composed_logits(
        q, k_pages, v_pages, w, tables, lengths, impl=impl, block_v=block_v, quant=quant
    )
    bv = min(block_v, int(w.shape[1]))
    return spec_verify(logits, tokens, nd, impl=impl, block_v=bv)


def composed_logits(q, k_pages, v_pages, w, tables, lengths, *, impl, block_v, quant=None):
    """Paged attention + blocked LM head: target logits [B, K+1, Vp]."""
    B, K1, H, hd = q.shape
    o = paged_decode_attention(
        q.reshape(B * K1, H, hd),
        k_pages,
        v_pages,
        jnp.repeat(tables, K1, axis=0),
        lengths.reshape(-1),
        impl=impl,
        quant=quant,
    )
    o = o.reshape(B, K1, H, hd).astype(jnp.float32)
    V = w.shape[1]
    bv = min(block_v, V)
    Vp = -(-V // bv) * bv
    wp = jnp.pad(w.astype(jnp.float32), ((0, 0), (0, Vp - V)))
    return fused_target_logits(o, wp, block_v=bv, v_true=V)


def session_logits(case: RaggedCase, *, impl: str = "ref", block_v: int = 256):
    """Per-session target logits [K_i+1, Vp] through the composition."""
    out = []
    for s, k in enumerate(case.ks):
        lengths = jnp.asarray([[case.base[s] + i for i in range(k + 1)]], jnp.int32)
        tab = jnp.asarray([case.tables_seq[s]], jnp.int32)
        lg = composed_logits(
            jnp.asarray(case.q_seq[s])[None], case.k_pages, case.v_pages, case.w,
            tab, lengths, impl=impl, block_v=block_v, quant=case.quant,
        )
        out.append(np.asarray(lg)[0])
    return out


def sharded_round(case: RaggedCase, shards: int, *, block_v: int = 256):
    """The ragged round through the sharded launch over ``shards`` devices,
    packed and unpacked as the served backend does."""
    from repro.sharding import host_mesh, spec_verify_sharded

    ks, *packed = ragged_launch_args(
        case.tok_seq, case.tables_seq, case.base, pad_page_id=case.sentinel_page
    )
    out = spec_verify_sharded(
        case.q, case.k_pages, case.v_pages, case.w, *packed,
        mesh=host_mesh(shards), block_v=block_v, quant=case.quant,
    )
    return ragged_results(ks, *out)


def session_fused(case: RaggedCase, *, impl: str = "ref", block_v: int = 256):
    """Per-session rectangular fused verify (B=1, no batch padding)."""
    out = []
    for s, k in enumerate(case.ks):
        lengths = jnp.asarray([[case.base[s] + i for i in range(k + 1)]], jnp.int32)
        tab = jnp.asarray([case.tables_seq[s]], jnp.int32)
        na, corr, logp = spec_verify_fused(
            jnp.asarray(case.q_seq[s])[None], case.k_pages, case.v_pages, case.w,
            tab, lengths, jnp.asarray([case.tok_seq[s]], jnp.int32),
            jnp.asarray([k], jnp.int32), impl=impl, block_v=block_v, quant=case.quant,
        )
        out.append((int(np.asarray(na)[0, 0]), int(np.asarray(corr)[0, 0]), np.asarray(logp)[0, :k]))
    return out


# --------------------------------------------------------------------------- #
# Assertions
# --------------------------------------------------------------------------- #
def assert_triples_match(got, want, ks=None):
    """Rectangular results bit-for-bit (ragged: only real draft lanes)."""
    na_f, corr_f, logp_f = (np.asarray(x) for x in got)
    na_c, corr_c, logp_c = (np.asarray(x) for x in want)
    np.testing.assert_array_equal(na_f, na_c)
    np.testing.assert_array_equal(corr_f, corr_c)
    if ks is None:
        np.testing.assert_array_equal(logp_f, logp_c)
    else:  # ragged: only real draft lanes are defined
        for i, k in enumerate(ks):
            np.testing.assert_array_equal(logp_f[i, :k], logp_c[i, :k])


def assert_ragged_match(got, want, *, exact_logp=True, label=""):
    """Per-session (na, corr, logp) lists agree; logp bitwise when asked."""
    assert len(got) == len(want), label
    for i, ((na1, c1, lp1), (na2, c2, lp2)) in enumerate(zip(got, want)):
        assert (int(na1), int(c1)) == (int(na2), int(c2)), f"{label} session {i}"
        if exact_logp:
            np.testing.assert_array_equal(np.asarray(lp1), np.asarray(lp2), err_msg=f"{label} session {i}")
        else:
            np.testing.assert_allclose(np.asarray(lp1), np.asarray(lp2), atol=1e-5, err_msg=f"{label} session {i}")


def assert_paths_agree(
    case: RaggedCase,
    *,
    impl: str = "ref",
    block_v: int = 256,
    shards: Sequence[int] = (),
    paths: Sequence[str] = ("chain", "tree", "fused", "batched"),
):
    """The differential harness: every verify path agrees on ``case``.

    The one-launch ``spec_verify_fused_batched`` result is the pivot.  The
    sharded launch (every count in ``shards``) must match it BIT-FOR-BIT —
    identical padding, identical arithmetic.  The per-session fused path and
    the chain/tree scans over composed logits share that launch's values but
    not its padded shapes, so their integer verdicts must be equal and their
    log-probs compared per real lane.

    Returns the pivot (the batched result) so callers can chain asserts.
    """
    ks = case.ks
    pivot = spec_verify_fused_batched(
        case.q, case.tok_seq, case.tables_seq, case.base,
        case.k_pages, case.v_pages, case.w,
        impl=impl, block_v=block_v, pad_page_id=case.sentinel_page, quant=case.quant,
    )
    if "fused" in paths:
        solo = session_fused(case, impl=impl, block_v=block_v)
        assert_ragged_match(pivot, solo, exact_logp=False, label="fused-batched vs per-session fused")
    logits = None
    if "chain" in paths or "tree" in paths:
        logits = session_logits(case, impl=impl, block_v=block_v)
    if "chain" in paths:
        # Per-session composition (B=1): the two-launch chain oracle.  It is
        # bit-exact vs the per-session fused entry by the kernel contract.
        comp = []
        for s, k in enumerate(ks):
            lengths = jnp.asarray([[case.base[s] + i for i in range(k + 1)]], jnp.int32)
            tab = jnp.asarray([case.tables_seq[s]], jnp.int32)
            na, corr, lp = composed_verify(
                jnp.asarray(case.q_seq[s])[None], case.k_pages, case.v_pages, case.w,
                tab, lengths, jnp.asarray([case.tok_seq[s]], jnp.int32),
                jnp.asarray([k], jnp.int32), impl=impl, block_v=block_v, quant=case.quant,
            )
            comp.append((int(np.asarray(na)[0, 0]), int(np.asarray(corr)[0, 0]), np.asarray(lp)[0, :k]))
        if "fused" in paths:
            assert_ragged_match(session_fused(case, impl=impl, block_v=block_v), comp,
                                exact_logp=True, label="per-session fused vs chain composition")
        # One-launch chain scan over the SAME composed logits.
        bv = min(block_v, case.v_true)
        scan = spec_verify_batched(logits, case.tok_seq, impl=impl, block_v=bv)
        assert_ragged_match(scan, comp, exact_logp=False, label="batched chain scan vs composition")
    if "tree" in paths:
        # A chain-topology tree must reduce to chain verify: same verdicts,
        # accepted tokens are exactly the accepted draft prefix.
        parents_seq = [list(range(-1, k - 1)) for k in ks]
        bv = min(block_v, case.v_true)
        tree = spec_verify_tree_batched(logits, case.tok_seq, parents_seq, impl=impl, block_v=bv)
        for s, ((na_t, path_t, corr_t, _lp), (na_p, corr_p, _)) in enumerate(zip(tree, pivot)):
            assert int(na_t) == int(na_p), f"tree vs fused-batched session {s}"
            assert int(corr_t) == int(corr_p), f"tree vs fused-batched session {s}"
            # Chain topology: the accepted root->leaf path is node 0..na-1.
            assert list(path_t) == list(range(int(na_t))), f"tree path session {s}"
    for n in shards:
        sharded = sharded_round(case, n, block_v=block_v)
        assert_ragged_match(sharded, pivot, exact_logp=True, label=f"sharded@{n} vs fused-batched")
    return pivot
