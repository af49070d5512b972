"""Batched ragged NAV verification: parity with the per-session path.

The continuous-batching server pads B ragged sessions into one launch
(``spec_verify_batched``); these tests pin down that the padded batched
results are identical to verifying each session alone — i.e. padding rows
and padded positions are inert and nothing leaks across sessions.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from strategies import fused_backend, ragged_logits_requests as _ragged_requests

from repro.kernels.spec_verify import (
    spec_verify,
    spec_verify_batched,
    spec_verify_ragged_ref,
)

KEY = jax.random.PRNGKey(11)


@pytest.mark.parametrize("impl", ["ref", "interpret"])
@pytest.mark.parametrize("ks", [[3], [5, 2], [1, 8, 4, 6, 2]])
def test_batched_matches_per_session(impl, ks):
    V = 2048
    logits_seq, tokens_seq = _ragged_requests(ks, V)
    batched = spec_verify_batched(logits_seq, tokens_seq, impl=impl, block_v=1024)
    # Oracle 1: per-session ragged ref (no padding at all).
    oracle = spec_verify_ragged_ref(logits_seq, tokens_seq)
    # Oracle 2: one unbatched spec_verify call per session through `impl`.
    for i, (lg, tk, k) in enumerate(zip(logits_seq, tokens_seq, ks)):
        na1, corr1, lp1 = batched[i]
        na2, corr2, lp2 = oracle[i]
        assert (na1, corr1) == (na2, corr2), f"session {i}"
        np.testing.assert_allclose(lp1, lp2, atol=1e-4)
        na3, corr3, lp3 = spec_verify(
            jnp.asarray(lg)[None],
            jnp.asarray(tk)[None],
            jnp.asarray([k], jnp.int32),
            impl=impl,
            block_v=1024,
        )
        assert na1 == int(na3[0, 0]) and corr1 == int(corr3[0, 0]), f"session {i}"
        np.testing.assert_allclose(lp1, np.asarray(lp3)[0, :k], atol=1e-4)


def test_batched_ref_is_bit_identical_across_batch_shapes():
    """Padding rows must not perturb a session's outputs at all (ref path)."""
    V = 1024
    logits_seq, tokens_seq = _ragged_requests([4, 7, 2], V, seed=3)
    alone = [
        spec_verify_batched([lg], [tk], impl="ref")[0]
        for lg, tk in zip(logits_seq, tokens_seq)
    ]
    together = spec_verify_batched(logits_seq, tokens_seq, impl="ref")
    for (na1, c1, lp1), (na2, c2, lp2) in zip(alone, together):
        assert (na1, c1) == (na2, c2)
        np.testing.assert_array_equal(lp1, lp2)  # bit-identical


@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_batched_pads_non_divisible_vocab(impl):
    """V not divisible by block_v: padded -inf lanes must be inert."""
    V = 1500  # not a multiple of any pow2 block
    logits_seq, tokens_seq = _ragged_requests([4, 2], V, seed=5)
    batched = spec_verify_batched(logits_seq, tokens_seq, impl=impl, block_v=1024)
    oracle = spec_verify_ragged_ref(logits_seq, tokens_seq)
    for i, ((na1, c1, lp1), (na2, c2, lp2)) in enumerate(zip(batched, oracle)):
        assert (na1, c1) == (na2, c2), f"session {i}"
        np.testing.assert_allclose(lp1, lp2, atol=1e-4)


@pytest.mark.parametrize("impl", ["ref", "interpret"])
@pytest.mark.parametrize("V", [96, 130, 1500, 3000])
def test_batched_non_pow2_vocabs(impl, V):
    """Vocab padding must stay inert across block-split shapes: V smaller
    than one block, barely over a block, and multi-block with a remainder."""
    logits_seq, tokens_seq = _ragged_requests([3, 5], V, seed=V)
    batched = spec_verify_batched(logits_seq, tokens_seq, impl=impl, block_v=128)
    oracle = spec_verify_ragged_ref(logits_seq, tokens_seq)
    for i, ((na1, c1, lp1), (na2, c2, lp2)) in enumerate(zip(batched, oracle)):
        assert (na1, c1) == (na2, c2), f"V={V} session {i}"
        np.testing.assert_allclose(lp1, lp2, atol=1e-4)


@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_batched_single_session(impl):
    """B=1: bucketing still pads the batch row dim — the pad row (zero
    logits, n_drafted=0) must not perturb the one real session."""
    logits_seq, tokens_seq = _ragged_requests([5], 512, seed=9)
    (na, corr, lp), = spec_verify_batched(logits_seq, tokens_seq, impl=impl, block_v=256)
    (na2, corr2, lp2), = spec_verify_ragged_ref(logits_seq, tokens_seq)
    assert (na, corr) == (na2, corr2)
    np.testing.assert_allclose(lp, lp2, atol=1e-4)


@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_batched_all_rejected_round(impl):
    """Every draft wrong: n_accepted = 0 and the correction is the target's
    greedy token at position 0 for every session."""
    V = 256
    logits_seq, tokens_seq = [], []
    for i, k in enumerate([4, 1, 7]):
        lg = np.asarray(jax.random.normal(jax.random.fold_in(KEY, 50 + i), (k + 1, V)) * 3, np.float32)
        greedy = np.argmax(lg, -1)
        tokens_seq.append(np.asarray([(g + 1) % V for g in greedy[:k]], np.int32))  # never match
        logits_seq.append(lg)
    out = spec_verify_batched(logits_seq, tokens_seq, impl=impl, block_v=128)
    for (na, corr, lp), lg in zip(out, logits_seq):
        assert na == 0
        assert corr == int(np.argmax(lg[0]))


@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_batched_all_accepted_round(impl):
    """Every draft matches the target's greedy choice: n_accepted = K_i and
    the correction is the BONUS token (greedy of the extra row)."""
    V = 256
    ks = [2, 6, 3]
    logits_seq, tokens_seq = [], []
    for i, k in enumerate(ks):
        lg = np.asarray(jax.random.normal(jax.random.fold_in(KEY, 80 + i), (k + 1, V)) * 3, np.float32)
        logits_seq.append(lg)
        tokens_seq.append(np.argmax(lg, -1)[:k].astype(np.int32))
    out = spec_verify_batched(logits_seq, tokens_seq, impl=impl, block_v=128)
    for (na, corr, lp), lg, k in zip(out, logits_seq, ks):
        assert na == k
        assert corr == int(np.argmax(lg[k]))


def test_batched_rejects_bad_inputs():
    lg = np.zeros((4, 64), np.float32)
    with pytest.raises(ValueError):
        spec_verify_batched([], [], impl="ref")
    with pytest.raises(ValueError):
        spec_verify_batched([lg], [[1, 2]], impl="ref")  # K_i mismatch: 3+1 rows needed
    with pytest.raises(ValueError):
        spec_verify_batched([lg, np.zeros((4, 128), np.float32)], [[1, 2, 3], [1, 2, 3]], impl="ref")


def test_spec_verify_backend_no_cross_session_leakage():
    """The server's kernel-backed backend: batched call == per-session calls."""
    from repro.runtime import SpecVerifyBackend

    V = 512

    def logits_fn(session, tokens):
        rng = np.random.default_rng(1000 + session)
        return rng.standard_normal((len(tokens) + 1, V)).astype(np.float32) * 2

    backend = SpecVerifyBackend(logits_fn, impl="ref")
    reqs = [
        (0, [3, 99, 7], [0.9] * 3),
        (1, [5], [0.9]),
        (2, [1, 2, 3, 4, 5, 6], [0.9] * 6),
    ]
    batched = backend.verify_batch(reqs)
    solo = [backend.verify(s, t, c) for (s, t, c) in reqs]
    assert batched == solo


@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_batched_paged_target_forward_parity(impl):
    """``batched_logits_fn`` + block tables == precomputed per-session logits.

    The paged dispatch hands the entry ONE padded batch (tokens, n_drafted,
    pow2-bucketed block tables) and gets logits back from a single target
    forward; results must match feeding the same logits per session.
    """
    ks = [3, 5, 1]
    V = 512
    logits_seq, tokens_seq = _ragged_requests(ks, V, seed=7)
    tables_seq = [[4, 9], [2], [7, 1, 3]]  # ragged KV block tables
    seen = {}

    def batched_logits_fn(tokens, nd, tables):
        # Padded shapes carry the same pow2 bucketing as the logits batch.
        assert tokens.shape == (4, 8) and nd.shape == (4,)
        assert tables.shape == (4, 4) and tables.dtype == np.int32
        np.testing.assert_array_equal(tables[0, :2], [4, 9])
        np.testing.assert_array_equal(tables[2], [7, 1, 3, 0])  # pad id 0
        np.testing.assert_array_equal(tables[3], 0)  # pad row
        seen["called"] = True
        out = np.zeros((tokens.shape[0], tokens.shape[1] + 1, V), np.float32)
        for i, k in enumerate(ks):
            out[i, : k + 1] = logits_seq[i]
        return out

    paged = spec_verify_batched(
        None,
        tokens_seq,
        impl=impl,
        block_v=256,
        block_tables_seq=tables_seq,
        batched_logits_fn=batched_logits_fn,
    )
    assert seen.get("called")
    plain = spec_verify_batched(logits_seq, tokens_seq, impl=impl, block_v=256)
    for i in range(len(ks)):
        assert paged[i][0] == plain[i][0] and paged[i][1] == plain[i][1]
        np.testing.assert_allclose(paged[i][2], plain[i][2], atol=1e-4)
    with pytest.raises(ValueError):
        spec_verify_batched(logits_seq, tokens_seq, batched_logits_fn=batched_logits_fn, impl="ref")


def test_spec_verify_backend_paged_batched_forward():
    """SpecVerifyBackend with a kv_pool threads block tables into ONE
    batched forward and matches the per-session logits path."""
    from repro.models.paged_kv import PagedKVPool
    from repro.runtime import SpecVerifyBackend

    V = 256
    rngs = {s: np.random.default_rng(500 + s) for s in range(3)}
    cache = {}

    def logits_for(session, n):
        # Deterministic per (session, draft length): both paths agree.
        key = (session, n)
        if key not in cache:
            cache[key] = rngs[session].standard_normal((n + 1, V)).astype(np.float32) * 2
        return cache[key]

    pool = PagedKVPool(num_blocks=16, block_size=4)
    reqs = [(0, [3, 9, 7], [0.9] * 3), (1, [5], [0.9]), (2, [1, 2, 3, 4], [0.9] * 4)]
    for s, toks, _ in reqs:
        pool.create(s)
        pool.append(s, 5 + s)  # distinct table sizes

    def batched_logits_fn(tokens, nd, tables):
        assert tables is not None and tables.shape[0] == tokens.shape[0]
        out = np.zeros((tokens.shape[0], tokens.shape[1] + 1, V), np.float32)
        for i, (s, toks, _) in enumerate(reqs):
            out[i, : len(toks) + 1] = logits_for(s, len(toks))
        return out

    paged_backend = SpecVerifyBackend(
        kv_pool=pool, batched_logits_fn=batched_logits_fn, impl="ref"
    )
    plain_backend = SpecVerifyBackend(lambda s, t: logits_for(s, len(t)), impl="ref")
    assert paged_backend.verify_batch(reqs) == plain_backend.verify_batch(reqs)


def test_tree_batched_paged_target_forward_parity():
    """Tree entry: batched paged forward == precomputed per-session logits."""
    from repro.kernels.spec_verify import spec_verify_tree_batched

    V = 256
    tokens_seq = [[3, 9, 7], [5, 1]]
    parents_seq = [[-1, 0, 0], [-1, -1]]
    logits_seq = [
        np.asarray(jax.random.normal(jax.random.fold_in(KEY, 33 + i), (len(t) + 1, V)) * 3, np.float32)
        for i, t in enumerate(tokens_seq)
    ]
    tables_seq = [[2, 8], [5]]

    def batched_logits_fn(tokens, parents, nn, tables):
        assert tokens.shape == parents.shape == (2, 4) and tables.shape == (2, 2)
        assert parents[0, 3] == -1  # pad nodes carry -1
        out = np.zeros((tokens.shape[0], tokens.shape[1] + 1, V), np.float32)
        for i, t in enumerate(tokens_seq):
            out[i, : len(t) + 1] = logits_seq[i]
        return out

    paged = spec_verify_tree_batched(
        None, tokens_seq, parents_seq,
        impl="ref", block_tables_seq=tables_seq, batched_logits_fn=batched_logits_fn,
    )
    plain = spec_verify_tree_batched(logits_seq, tokens_seq, parents_seq, impl="ref")
    for p, q in zip(paged, plain):
        assert p[0] == q[0] and p[1] == q[1] and p[2] == q[2]
        np.testing.assert_allclose(p[3], q[3], atol=1e-4)


def test_spec_verify_backend_paged_tree_forward():
    """A paged-forward-only backend must serve tree requests through
    batched_tree_logits_fn (and raise clearly when it lacks one)."""
    from repro.models.paged_kv import PagedKVPool
    from repro.runtime import SpecVerifyBackend

    V = 128
    tokens, parents = [7, 9, 3], [-1, 0, 0]
    lg = np.asarray(jax.random.normal(jax.random.fold_in(KEY, 55), (4, V)) * 3, np.float32)

    def batched_tree_logits_fn(toks, pars, nn, tables):
        assert tables is not None
        out = np.zeros((toks.shape[0], toks.shape[1] + 1, V), np.float32)
        out[0, :4] = lg
        return out

    pool = PagedKVPool(num_blocks=8, block_size=4)
    pool.create(0)
    pool.append(0, 6)
    backend = SpecVerifyBackend(
        kv_pool=pool,
        batched_logits_fn=lambda t, n, b: np.zeros((t.shape[0], t.shape[1] + 1, V), np.float32),
        batched_tree_logits_fn=batched_tree_logits_fn,
        impl="ref",
    )
    got = backend.verify_tree_batch([(0, tokens, [0.9] * 3, parents)])
    from repro.kernels.spec_verify import spec_verify_tree_batched

    (want,) = spec_verify_tree_batched([lg], [tokens], [parents], impl="ref")
    assert got[0] == (int(want[0]), int(want[2]), list(want[1]))

    chain_only = SpecVerifyBackend(
        kv_pool=pool,
        batched_logits_fn=lambda t, n, b: np.zeros((t.shape[0], t.shape[1] + 1, V), np.float32),
        impl="ref",
    )
    with pytest.raises(ValueError, match="tree requests need"):
        chain_only.verify_tree_batch([(0, tokens, [0.9] * 3, parents)])


# Shared with the sharded differential suite (tests/strategies.py) so the
# unsharded and sharded backends stay comparable request-for-request.
_fused_backend = fused_backend


def test_fused_backend_one_launch_matches_composition():
    """fused=True backend == the unfused paged-attention + verify pipeline,
    with batched == per-session (no cross-session leakage through padding)."""
    from repro.kernels.decode_attention import paged_decode_attention
    from repro.kernels.spec_verify import fused_target_logits, spec_verify

    backend, pool, w, V = _fused_backend()
    reqs = [(0, [3, 9, 7], [0.9] * 3), (1, [5], [0.9]), (2, [1, 2, 3, 4], [0.9] * 4)]
    for s, toks, _ in reqs:
        pool.create(s)
        pool.append(s, 5 + s + len(toks) + 1)  # dispatcher-style metadata append
    batched = backend.verify_batch(reqs)
    solo = [backend.verify(s, t, c) for (s, t, c) in reqs]
    assert batched == solo
    # Unfused oracle per session over the SAME materialized pages.
    for (s, toks, _), got in zip(reqs, batched):
        K1 = len(toks) + 1
        q = jnp.asarray(backend.query_fn(s, toks))[None]  # [1, K1, H, hd]
        base = pool.length(s) - len(toks)
        lengths = jnp.asarray([[base + i for i in range(K1)]], jnp.int32)
        tab = jnp.asarray([list(pool.table(s))], jnp.int32)
        o = paged_decode_attention(
            q.reshape(K1, *q.shape[2:]), pool.k_pages[0], pool.v_pages[0],
            jnp.repeat(tab, K1, axis=0), lengths.reshape(-1), impl="ref",
        ).reshape(1, K1, *q.shape[2:]).astype(jnp.float32)
        logits = fused_target_logits(o, jnp.asarray(w), block_v=256, v_true=V)
        na, corr, _ = spec_verify(
            logits, jnp.asarray([toks], jnp.int32), jnp.asarray([len(toks)], jnp.int32),
            impl="ref", block_v=256,
        )
        assert got == (int(np.asarray(na)[0, 0]), int(np.asarray(corr)[0, 0]))


def test_fused_backend_int8_pool_auto_quant():
    """An int8 pool flows its quant params into the fused launch, and the
    integer verdicts track the fp32 pool on the same inputs."""
    fp32, pool32, _, _ = _fused_backend()
    q8, pool8, _, _ = _fused_backend(quantize="int8")
    reqs = [(0, [3, 9, 7], [0.9] * 3), (1, [5], [0.9])]
    for s, toks, _ in reqs:
        for p in (pool32, pool8):
            p.create(s)
            p.append(s, 5 + s + len(toks) + 1)
    assert pool8.k_pages.dtype == jnp.int8
    got32, got8 = fp32.verify_batch(reqs), q8.verify_batch(reqs)
    assert got32 == got8  # sharp LM head: int8 noise can't flip the argmax
    # And the quantized pool is genuinely smaller.
    assert pool8.bytes_per_token * 1.5 <= pool32.bytes_per_token


def test_fused_backend_consecutive_dispatches_interpret_matches_ref():
    """Two dispatches in a row through the interpreted kernel give the
    verdicts and log-probs of ``impl='ref'``.  Each round's fills donate the
    pool's page buffers, so the second dispatch would fail, or read stale
    pages, if the backend held an array from before them."""
    runs = {}
    for impl in ("ref", "interpret"):
        backend, pool, _, _ = _fused_backend(impl=impl)
        rounds = [
            [(0, [3, 9, 7], [0.9] * 3), (1, [5], [0.9])],
            [(0, [2, 4], [0.9] * 2), (1, [8, 1, 6, 2], [0.9] * 4)],
        ]
        out = []
        for reqs in rounds:
            for s, toks, _ in reqs:
                if s not in pool.tables:
                    pool.create(s)
                    pool.append(s, 5 + s)
                pool.append(s, len(toks) + 1)  # dispatcher-style metadata append
            out.append(backend.fused_verify(reqs))
            for s, toks, _ in reqs:  # a round that accepts nothing keeps one slot
                pool.rollback(s, pool.length(s) - len(toks))
        runs[impl] = out
    for got, want in zip(runs["interpret"], runs["ref"]):
        assert [(int(n), int(c)) for n, c, _ in got] == [(int(n), int(c)) for n, c, _ in want]
        for (_, _, lp_got), (_, _, lp_want) in zip(got, want):
            np.testing.assert_allclose(np.asarray(lp_got), np.asarray(lp_want), atol=1e-5)


def _materialized_k(pool, session):
    """Gather the session's K tensors [L, length, H, hd] through its table."""
    tab = pool.table(session)
    kp = np.asarray(pool.k_pages)
    cols = [
        kp[:, int(tab[t // pool.block_size]), t % pool.block_size]
        for t in range(pool.length(session))
    ]
    return np.stack(cols, axis=1)


def test_fused_backend_refills_recycled_pages_after_rollback():
    """REVIEW regression: a rollback that drops a trailing page, followed by
    a foreign session recycling (and dirtying) that page, must not leave the
    regrown slots holding the foreign data — ensure_kv refills from the
    pool's watermark, not a stale backend-side counter."""
    backend, pool, _, _ = _fused_backend()
    H, hd = pool.n_kv_heads, pool.head_dim
    pool.create(0)
    pool.append(0, 9)  # dispatcher-style metadata append: pages [p0, p1, p2]
    backend.ensure_kv(0)
    pool.rollback(0, 6)  # commit 6 -> the trailing page is freed
    pool.create(99)  # a foreign session recycles that page...
    pool.append(99, pool.block_size)
    junk = jnp.full((1, pool.block_size, H, hd), 7.5)
    pool.fill(99, 0, junk, -junk)  # ...and dirties it
    pool.release(99)
    pool.append(0, 3)  # regrow to 9: the dirty page comes back
    backend.ensure_kv(0)
    k, _ = backend.kv_fn(0, 0, 9)
    np.testing.assert_array_equal(_materialized_k(pool, 0), np.asarray(k))


def test_fused_backend_rematerializes_after_eviction():
    """An evicted-then-resumed session re-prefills every slot: its old pages
    may have been handed to (and written by) anyone in between."""
    backend, pool, _, _ = _fused_backend()
    H, hd = pool.n_kv_heads, pool.head_dim
    pool.create(0)
    pool.append(0, 6)
    backend.ensure_kv(0)
    pool.evict(0)  # pool-pressure reclaim
    pool.create(1)  # the pages are recycled and dirtied
    pool.append(1, 8)
    junk = jnp.full((1, 8, H, hd), -3.25)
    pool.fill(1, 0, junk, junk)
    pool.release(1)
    pool.append(0, 6)  # comeback re-prefill (the dispatcher's _kv_secure)
    backend.ensure_kv(0)
    k, _ = backend.kv_fn(0, 0, 6)
    np.testing.assert_array_equal(_materialized_k(pool, 0), np.asarray(k))


def test_fused_backend_reused_session_id_refills_from_scratch():
    """The watermark dies with the table: a reused session id must be fully
    re-materialized, not inherit the dead session's fill state."""
    from repro.models.paged_kv import PagedKVPool
    from repro.runtime import SpecVerifyBackend

    H, hd, V = 2, 8, 256
    pool = PagedKVPool(num_blocks=16, block_size=4, n_layers=1, n_kv_heads=H, head_dim=hd)
    calls = []

    def kv_fn(session, start, count):
        calls.append((session, start, count))
        x = np.full((1, count, H, hd), float(session + 1), np.float32)
        return x, x

    backend = SpecVerifyBackend(
        fused=True, kv_pool=pool, kv_fn=kv_fn, lm_head=np.ones((H * hd, V), np.float32),
        query_fn=lambda s, t: np.zeros((len(t) + 1, H, hd), np.float32), impl="ref",
    )
    pool.create(7)
    pool.append(7, 8)
    backend.ensure_kv(7)
    pool.release(7)  # session died (timeout / detach)
    pool.create(7)  # same id, new life
    pool.append(7, 8)
    assert pool.filled(7) == 0
    backend.ensure_kv(7)
    assert calls == [(7, 0, 8), (7, 0, 8)]


def test_unfused_paged_backend_pads_tables_with_sentinel():
    """Satellite regression: the batched paged forward pads ragged tables
    with the pool's sentinel page, never page 0 (a live page)."""
    from repro.models.paged_kv import PagedKVPool
    from repro.runtime import SpecVerifyBackend

    V = 128
    pool = PagedKVPool(num_blocks=8, block_size=4)
    seen = {}

    def batched_logits_fn(tokens, nd, tables):
        seen["tables"] = np.array(tables)
        return np.zeros((tokens.shape[0], tokens.shape[1] + 1, V), np.float32)

    backend = SpecVerifyBackend(kv_pool=pool, batched_logits_fn=batched_logits_fn, impl="ref")
    pool.create(0)
    pool.append(0, 6)  # pages [0, 1]
    backend.verify_batch([(0, [1, 2, 3], [0.9] * 3)])
    tables = seen["tables"]
    assert tables.shape[1] >= 2
    np.testing.assert_array_equal(tables[0, 2:], pool.sentinel_page)
    assert (tables[1:] == pool.sentinel_page).all()  # pad rows too


def test_fused_backend_full_serve_round_trip():
    """EdgeClient -> CloudVerifier with the fused single-launch backend over a
    shared paged pool (the dispatcher's _kv_secure owns session lifecycle),
    on the virtual clock: streams commit, and fp32 runs are bit-reproducible.
    The int8 pool serves the same flow through the quantized fused launch."""
    from repro.models.paged_kv import PagedKVPool
    from repro.runtime import SpecVerifyBackend
    from repro.runtime.client import EdgeClient, EdgeConfig
    from repro.runtime.server import CloudVerifier
    from repro.runtime.simclock import VirtualClock
    from repro.runtime.transport import Channel, ChannelConfig

    H, hd, V = 2, 16, 512
    w = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (H * hd, V)) * 6, np.float32)

    def query_fn(session, tokens):
        k = jax.random.fold_in(jax.random.PRNGKey(2), session * 997 + len(tokens))
        return np.asarray(jax.random.normal(k, (len(tokens) + 1, H, hd)), np.float32)

    def once(quantize):
        clock = VirtualClock()
        pool = PagedKVPool(num_blocks=256, block_size=8, n_layers=1, n_kv_heads=H,
                           head_dim=hd, quantize=quantize)
        backend = SpecVerifyBackend(fused=True, kv_pool=pool, query_fn=query_fn,
                                    lm_head=w, impl="ref", block_v=512)
        server = CloudVerifier(backend, kv_pool=pool, clock=clock)
        up = Channel(ChannelConfig(alpha=0.02, beta=0.002), "up0", clock=clock)
        dn = Channel(ChannelConfig(alpha=0.01, beta=0.0005), "dn0", clock=clock)
        server.attach(0, up, dn)
        c = EdgeClient(0, up, dn, EdgeConfig(gamma=0.02, nav_timeout=3.0))

        def body():
            server.start()
            st = c.run(48)
            server.stop()
            return st

        st = clock.run(body)
        return list(c.tokens), st["accepted_tokens"], st["rounds"]

    run_a, run_b = once(None), once(None)
    assert run_a == run_b  # virtual clock + deterministic fused verify
    tokens, accepted, _rounds = run_a
    assert accepted >= 48 and len(tokens) == accepted
    tokens8, accepted8, _ = once("int8")
    assert accepted8 >= 48 and len(tokens8) == accepted8


def test_fused_serve_shared_prefix_materialized_once_and_stays_shared():
    """CloudVerifier materializes the shared system prefix ONCE on its owner
    before any fork: serving sessions inherit the watermark, their fills
    never touch (and so never CoW-copy) the shared prefix pages, and the
    prefix-sharing memory win survives the fused tensor path."""
    from repro.models.paged_kv import PagedKVPool
    from repro.runtime import SpecVerifyBackend
    from repro.runtime.client import EdgeClient, EdgeConfig
    from repro.runtime.server import CloudVerifier
    from repro.runtime.simclock import VirtualClock
    from repro.runtime.transport import Channel, ChannelConfig

    H, hd, V = 2, 16, 512
    w = np.asarray(jax.random.normal(jax.random.PRNGKey(3), (H * hd, V)) * 6, np.float32)

    def query_fn(session, tokens):
        k = jax.random.fold_in(jax.random.PRNGKey(4), session * 997 + len(tokens))
        return np.asarray(jax.random.normal(k, (len(tokens) + 1, H, hd)), np.float32)

    clock = VirtualClock()
    pool = PagedKVPool(num_blocks=256, block_size=8, n_layers=1, n_kv_heads=H, head_dim=hd)
    backend = SpecVerifyBackend(
        fused=True, kv_pool=pool, query_fn=query_fn, lm_head=w, impl="ref", block_v=512
    )
    server = CloudVerifier(backend, kv_pool=pool, kv_shared_prefix=32, clock=clock)
    assert pool.filled(CloudVerifier.KV_PREFIX_SESSION) == 32  # filled at init
    clients = []
    for s in range(2):
        up = Channel(ChannelConfig(alpha=0.02, beta=0.002), f"up{s}", clock=clock)
        dn = Channel(ChannelConfig(alpha=0.01, beta=0.0005), f"dn{s}", clock=clock)
        server.attach(s, up, dn)
        clients.append(EdgeClient(s, up, dn, EdgeConfig(gamma=0.02, nav_timeout=3.0)))
        assert pool.filled(s) == 32  # forked: watermark inherited, no refill

    def body():
        server.start()
        stats = [c.run(24) for c in clients]
        server.stop()
        return stats

    st0, st1 = clock.run(body)
    assert st0["accepted_tokens"] >= 24 and st1["accepted_tokens"] >= 24
    # All 4 (page-aligned) prefix pages are still shared by owner + sessions.
    prefix_pages = pool.tables[CloudVerifier.KV_PREFIX_SESSION].blocks
    assert len(prefix_pages) == 4
    assert all(int(pool.refcounts[p]) == 3 for p in prefix_pages)
    assert pool.stats["cow_copies"] == 0  # nothing ever wrote a shared page


def test_verifier_counts_page_writes_from_admission_through_verify():
    """With an unaligned shared prefix, each session's first admission
    CoW-copies the shared tail page (``_kv_secure`` → ``append``): the
    dispatcher's ``kv_page_writes`` counts those copies with the fills, so
    it equals every pool write made after the prefix was materialized."""
    from repro.models.paged_kv import PagedKVPool
    from repro.runtime import SpecVerifyBackend
    from repro.runtime.client import EdgeClient, EdgeConfig
    from repro.runtime.server import CloudVerifier
    from repro.runtime.simclock import VirtualClock
    from repro.runtime.transport import Channel, ChannelConfig

    H, hd, V = 2, 16, 256
    w = np.asarray(jax.random.normal(jax.random.PRNGKey(3), (H * hd, V)) * 6, np.float32)

    def query_fn(session, tokens):
        k = jax.random.fold_in(jax.random.PRNGKey(4), session * 997 + len(tokens))
        return np.asarray(jax.random.normal(k, (len(tokens) + 1, H, hd)), np.float32)

    clock = VirtualClock()
    pool = PagedKVPool(num_blocks=64, block_size=8, n_layers=1, n_kv_heads=H, head_dim=hd)
    backend = SpecVerifyBackend(
        fused=True, kv_pool=pool, query_fn=query_fn, lm_head=w, impl="ref", block_v=256
    )
    server = CloudVerifier(backend, kv_pool=pool, kv_shared_prefix=28, clock=clock)
    before = pool.stats["page_writes"]  # the prefix, materialized at init
    clients = []
    for s in range(2):
        up = Channel(ChannelConfig(alpha=0.02, beta=0.002), f"up{s}", clock=clock)
        dn = Channel(ChannelConfig(alpha=0.01, beta=0.0005), f"dn{s}", clock=clock)
        server.attach(s, up, dn)
        clients.append(EdgeClient(s, up, dn, EdgeConfig(gamma=0.02, nav_timeout=3.0)))

    def body():
        server.start()
        stats = [c.run(6) for c in clients]
        server.stop()
        return stats

    clock.run(body)
    assert pool.stats["cow_copies"] == 2  # one shared tail page per session
    assert server.stats["kv_page_writes"] == pool.stats["page_writes"] - before > 2 * 2
