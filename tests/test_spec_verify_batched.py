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

from strategies import (
    assert_ragged_match,
    fused_backend,
    pad_queries,
    ragged_logits_requests as _ragged_requests,
)

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
    """The logits-fed backend: batched call == per-session calls."""
    from repro.runtime import LogitsVerifyBackend

    V = 512

    def logits_fn(session, tokens):
        rng = np.random.default_rng(1000 + session)
        return rng.standard_normal((len(tokens) + 1, V)).astype(np.float32) * 2

    backend = LogitsVerifyBackend(logits_fn, impl="ref")
    reqs = [
        (0, [3, 99, 7], [0.9] * 3),
        (1, [5], [0.9]),
        (2, [1, 2, 3, 4, 5, 6], [0.9] * 6),
    ]
    batched = backend.verify_batch(reqs)
    solo = [backend.verify(s, t, c) for (s, t, c) in reqs]
    assert batched == solo


# Shared with the sharded differential suite (tests/strategies.py) so the
# unsharded and sharded backends stay comparable request-for-request.
_fused_backend = fused_backend


def test_fused_backend_one_launch_matches_composition():
    """The served backend == the unfused paged-attention + verify pipeline,
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
        kv_pool=pool, kv_fn=kv_fn, lm_head=np.ones((H * hd, V), np.float32),
        query_fn=lambda s, t, k=None: np.zeros((len(t) + 1, H, hd), np.float32), impl="ref",
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


@pytest.mark.parametrize("missing", ["tensor_pool", "query_fn", "lm_head"])
def test_spec_backend_refuses_incomplete_inputs(missing):
    """The served backend needs a tensor-mode pool, ``query_fn`` and
    ``lm_head``; without any one of them it refuses at construction."""
    from repro.models.paged_kv import PagedKVPool
    from repro.runtime import SpecVerifyBackend

    H, hd, V = 2, 8, 256
    kw = dict(
        kv_pool=PagedKVPool(num_blocks=8, block_size=4, n_layers=1, n_kv_heads=H, head_dim=hd),
        query_fn=lambda s, t, k=None: jnp.zeros((len(t) + 1, H, hd)),
        lm_head=np.ones((H * hd, V), np.float32),
        impl="ref",
    )
    SpecVerifyBackend(**kw)  # complete: accepted
    if missing == "tensor_pool":
        kw["kv_pool"] = PagedKVPool(num_blocks=8, block_size=4)  # metadata only
    else:
        del kw[missing]
    with pytest.raises(ValueError, match="tensor-mode kv_pool" if missing == "tensor_pool" else missing):
        SpecVerifyBackend(**kw)


def test_spec_backend_refuses_tree_rounds():
    """The served backend has no fused tree launch: a tree round raises
    ``ValueError`` (the dispatcher's error), an empty one is a no-op."""
    backend, pool, _, _ = _fused_backend()
    pool.create(0)
    pool.append(0, 6)
    with pytest.raises(ValueError, match="tree requests need"):
        backend.verify_tree_batch([(0, [7, 9, 3], [0.9] * 3, [-1, 0, 0])])
    assert backend.verify_tree_batch([]) == []
    assert pool.filled(0) == 0  # refused before any KV was made


def test_fused_backend_pads_tables_with_sentinel(monkeypatch):
    """The served launch gets a round's ragged tables padded with the pool's
    sentinel page, never page 0 (a live page of another session), in pad
    entries and pad rows alike."""
    import repro.kernels.spec_verify.ops as ops

    seen = []
    real = ops.spec_verify_fused

    def spy(q, k_pages, v_pages, w, tables, *args, **kwargs):
        seen.append(np.asarray(tables))
        return real(q, k_pages, v_pages, w, tables, *args, **kwargs)

    monkeypatch.setattr(ops, "spec_verify_fused", spy)
    backend, pool, _, _ = _fused_backend()
    reqs = [(0, [3, 9], [0.9] * 2), (1, [5], [0.9]), (2, [1, 2, 3], [0.9] * 3)]
    for (s, toks, _), length in zip(reqs, (6, 15, 3)):
        pool.create(s)
        pool.append(s, length)
    assert 0 in pool.table(0)  # page 0 is live
    backend.fused_verify(reqs)
    (tables,) = seen
    assert tables.shape == (4, 4)  # Bp = 4 rows, Gp = 4 pages
    for i, (s, _, _) in enumerate(reqs):
        own = list(pool.table(s))
        np.testing.assert_array_equal(tables[i, : len(own)], own)
        assert (tables[i, len(own):] == pool.sentinel_page).all()
    assert (tables[3] == pool.sentinel_page).all()  # the pad row


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

    def query_fn(session, tokens, k=None):
        n = len(tokens) if k is None else k
        draw = jax.random.fold_in(jax.random.PRNGKey(2), session * 997 + n)
        return jax.random.normal(draw, (len(tokens) + 1, H, hd))

    def once(quantize):
        clock = VirtualClock()
        pool = PagedKVPool(num_blocks=256, block_size=8, n_layers=1, n_kv_heads=H,
                           head_dim=hd, quantize=quantize)
        backend = SpecVerifyBackend(kv_pool=pool, query_fn=query_fn,
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

    def query_fn(session, tokens, k=None):
        n = len(tokens) if k is None else k
        draw = jax.random.fold_in(jax.random.PRNGKey(4), session * 997 + n)
        return jax.random.normal(draw, (len(tokens) + 1, H, hd))

    clock = VirtualClock()
    pool = PagedKVPool(num_blocks=256, block_size=8, n_layers=1, n_kv_heads=H, head_dim=hd)
    backend = SpecVerifyBackend(
        kv_pool=pool, query_fn=query_fn, lm_head=w, impl="ref", block_v=512
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

    def query_fn(session, tokens, k=None):
        n = len(tokens) if k is None else k
        draw = jax.random.fold_in(jax.random.PRNGKey(4), session * 997 + n)
        return jax.random.normal(draw, (len(tokens) + 1, H, hd))

    clock = VirtualClock()
    pool = PagedKVPool(num_blocks=64, block_size=8, n_layers=1, n_kv_heads=H, head_dim=hd)
    backend = SpecVerifyBackend(
        kv_pool=pool, query_fn=query_fn, lm_head=w, impl="ref", block_v=256
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


def test_verifier_traces_kv_synthesis_and_counts_launched_rows(monkeypatch):
    """Each ``kv_fn`` call of ``ensure_kv`` is a ``kv.synth`` span under its
    dispatch's ``verify`` (the prefix's, made at construction, has none),
    and ``kernel_rows`` adds up the padded batch of every fused launch, pad
    rows included, in the backend and in the dispatcher's stats alike."""
    import repro.kernels.spec_verify.ops as ops
    from repro.models.paged_kv import PagedKVPool
    from repro.obs.trace import Tracer
    from repro.runtime import SpecVerifyBackend
    from repro.runtime.client import EdgeClient, EdgeConfig
    from repro.runtime.server import CloudVerifier
    from repro.runtime.simclock import VirtualClock
    from repro.runtime.transport import Channel, ChannelConfig

    launched = []
    real = ops.spec_verify_fused

    def spy(q, *args, **kwargs):
        launched.append(int(np.shape(args[-1])[0]))  # n_drafted [Bp]
        return real(q, *args, **kwargs)

    monkeypatch.setattr(ops, "spec_verify_fused", spy)
    H, hd, V = 2, 16, 256
    w = np.asarray(jax.random.normal(jax.random.PRNGKey(3), (H * hd, V)) * 6, np.float32)

    def query_fn(session, tokens, k=None):
        n = len(tokens) if k is None else k
        draw = jax.random.fold_in(jax.random.PRNGKey(4), session * 997 + n)
        return jax.random.normal(draw, (len(tokens) + 1, H, hd))

    clock = VirtualClock()
    tracer = Tracer()
    pool = PagedKVPool(num_blocks=64, block_size=8, n_layers=1, n_kv_heads=H, head_dim=hd)
    backend = SpecVerifyBackend(
        kv_pool=pool, query_fn=query_fn, lm_head=w, impl="ref", block_v=256
    )
    server = CloudVerifier(
        backend, kv_pool=pool, kv_shared_prefix=16, clock=clock, tracer=tracer,
        batch_window=0.01, max_batch=8,
    )
    clients = []
    for s in range(3):
        up = Channel(ChannelConfig(alpha=0.02, beta=0.002), f"up{s}", clock=clock)
        dn = Channel(ChannelConfig(alpha=0.01, beta=0.0005), f"dn{s}", clock=clock)
        server.attach(s, up, dn)
        clients.append(EdgeClient(s, up, dn, EdgeConfig(gamma=0.02, nav_timeout=3.0)))

    def body():
        server.start()
        handles = [clock.spawn(lambda c=c: c.run(6), name=f"cli-{c.session}") for c in clients]
        for h in handles:
            h.join()
        server.stop()

    clock.run(body)
    assert launched and server.stats["batched_calls"] == len(launched)
    assert backend.stats["kernel_rows"] == server.stats["kernel_rows"] == sum(launched)
    # One query draw per fused dispatch, counted and traced once each.
    assert backend.stats["query_launches"] == server.stats["query_launches"] == len(launched)
    assert any(rows > 1 for rows in launched)
    assert all(rows & (rows - 1) == 0 for rows in launched)  # pow2 buckets

    spans = tracer.spans()
    by_sid = {s.sid: s for s in spans if s.sid}
    synths = [s for s in spans if s.name == "kv.synth"]
    assert not synths[0].parent  # the shared prefix, at construction
    assert len(synths) > 1
    for s in synths[1:]:
        verify = by_sid[s.parent]
        assert verify.name == "verify"
        assert s.get("dispatch") == verify.get("dispatch") is not None
        assert verify.t0 <= s.t0 <= s.t1 <= verify.t1
    queries = [by_sid[s.parent].get("dispatch") for s in spans if s.name == "query"]
    assert sorted(queries) == sorted(s.get("dispatch") for s in spans if s.name == "verify")


# --------------------------------------------------------------------------- #
# The device query draw: one per fused round, one program for them all
# --------------------------------------------------------------------------- #
WIDTHS = {"gqa": (4, 2), "mha": (3, 3)}  # (query heads, KV heads)


def _draw_backend(widths, impl="ref", seed=5):
    """A fused backend at ``widths`` whose seeded ``query_fn`` is the launcher's."""
    from repro.models.paged_kv import PagedKVPool
    from repro.runtime import SpecVerifyBackend

    H, Hkv = WIDTHS[widths]
    hd, V = 8, 256
    key = jax.random.PRNGKey(seed)
    pool = PagedKVPool(num_blocks=64, block_size=4, n_layers=1, n_kv_heads=Hkv, head_dim=hd)
    w = np.asarray(jax.random.normal(jax.random.fold_in(key, 77), (H * hd, V)) * 4, np.float32)

    queries = jax.random.fold_in(key, 88)

    def query_fn(session, tokens, k=None):
        n = len(tokens) if k is None else k
        draw = jax.random.fold_in(queries, session * 131 + n)
        return jax.random.normal(draw, (len(tokens) + 1, H, hd))

    backend = SpecVerifyBackend(
        kv_pool=pool, query_fn=query_fn, lm_head=w, impl=impl, block_v=256
    )
    return backend, pool


def _round(pool, ks, base=20, first=0):
    """Requests with ``ks`` drafts, each session at ``base`` committed tokens
    plus the round's slots (the dispatcher's metadata append)."""
    reqs = []
    for i, k in enumerate(ks):
        s = first + i
        pool.create(s)
        pool.append(s, base + k + 1)
        reqs.append((s, [(7 * s + j) % 256 for j in range(k)], [0.9] * k))
    return reqs


@pytest.mark.parametrize("widths", sorted(WIDTHS))
@pytest.mark.parametrize("ks", [[8, 5, 1, 8, 3], [3]], ids=["mixed-bp8", "solo"])
def test_query_draw_matches_query_fn_per_row(widths, ks):
    """The batched draw holds ``query_fn(s, tokens)`` in each row's first
    K+1 positions, bit for bit, and zeros after them and in pad rows."""
    from repro.runtime.server import DRAW_DRAFTS, DRAW_ROWS

    backend, pool = _draw_backend(widths)
    reqs = [(100 + 3 * i, [(5 * i + j) % 256 for j in range(k)], None) for i, k in enumerate(ks)]
    q = backend.draw_queries(reqs)
    assert isinstance(q, jax.Array)  # drawn on the device
    q = np.asarray(q)
    H, _ = WIDTHS[widths]
    assert q.shape == (DRAW_ROWS, DRAW_DRAFTS + 1, H, 8) and q.dtype == np.float32
    for i, (s, toks, _) in enumerate(reqs):
        np.testing.assert_array_equal(q[i, : len(toks) + 1], np.asarray(backend.query_fn(s, toks)))
        assert not q[i, len(toks) + 1 :].any()
    assert not q[len(reqs) :].any()
    assert backend.stats["query_launches"] == 1


@pytest.mark.parametrize("widths", sorted(WIDTHS))
@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_fused_backend_draw_matches_per_session_queries(impl, widths):
    """A round through the backend (queries drawn on the device) gives the
    ``(n_accepted, correction, logp)`` of the same launch fed each
    session's eager ``query_fn`` queries, padded on the host."""
    from repro.kernels.spec_verify import spec_verify_fused_batched

    backend, pool = _draw_backend(widths, impl=impl)
    reqs = _round(pool, [4, 1, 3])
    got = backend.fused_verify(reqs)
    inputs = backend.fused_inputs(reqs)  # pages already filled: no writes
    inputs["q"] = pad_queries([backend.query_fn(s, t) for (s, t, _) in reqs])
    want = spec_verify_fused_batched(**inputs, impl=impl, block_v=backend.block_v)
    assert_ragged_match(got, want, exact_logp=True, label=f"{impl}/{widths}")
    assert backend.stats["query_launches"] == 2  # one per fused round: verify, then inputs


def test_mixed_draft_counts_compile_nothing_new():
    """Once every ``(Bp, Kp)`` has run with K = Kp, as the benchmark's
    warm-up drives them, a round with mixed draft counts adds no entry to
    the draw's or the launch's jit cache.  The draw compiles once for all
    of them."""
    import repro.kernels.spec_verify.ops as ops

    backend, pool = _draw_backend("gqa")

    def draw_programs():
        return sum(draw._cache_size() for draw, _ in backend._draws.values())

    first = 0
    dispatches = 0
    for bp in (1, 2, 4):
        for kp in (1, 2, 4, 8):
            reqs = _round(pool, [kp] * bp, first=first)
            backend.fused_verify(reqs)
            dispatches += 1
            for s, _, _ in reqs:
                pool.release(s)
            first += bp
    launches = ops.spec_verify_fused._cache_size()
    assert list(backend._draws) == [8] and draw_programs() == 1
    reqs = _round(pool, [8, 5, 3], first=first)
    backend.fused_verify(reqs)
    dispatches += 1
    assert ops.spec_verify_fused._cache_size() == launches
    assert list(backend._draws) == [8] and draw_programs() == 1
    assert backend.stats["query_launches"] == dispatches


def test_draw_program_is_the_same_for_every_seed():
    """The arrays ``query_fn`` closes over (its seeded key) are arguments of
    the draw, not constants in its program: two seeds lower to the same
    program text, so one persistent compile-cache entry serves every seed."""
    from repro.runtime.server import DRAW_DRAFTS, DRAW_ROWS

    texts = []
    for seed in (5, 2**31 + 11):
        backend, pool = _draw_backend("gqa", seed=seed)
        draw, consts = backend._draw(DRAW_DRAFTS)
        ids = jax.ShapeDtypeStruct((DRAW_ROWS, DRAW_DRAFTS + 2), jnp.int32)
        texts.append(draw.lower(consts, ids).as_text())
    assert texts[0] == texts[1]


def test_draw_refuses_non_partitionable_threefry():
    """Without partitionable threefry a wider ``jax.random`` draw does not
    start with the draw at the round's own K: the backend refuses."""
    backend, pool = _draw_backend("gqa")
    was = jax.config.jax_threefry_partitionable
    jax.config.update("jax_threefry_partitionable", False)
    try:
        with pytest.raises(RuntimeError, match="jax_threefry_partitionable"):
            backend.draw_queries([(0, [1, 2], None)])
    finally:
        jax.config.update("jax_threefry_partitionable", was)
    assert backend.draw_queries([(0, [1, 2], None)]).shape[1] == 9  # DRAW_DRAFTS + 1
