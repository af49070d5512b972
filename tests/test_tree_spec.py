"""Tree-structured speculation: drafting, tree-NAV verification, serving.

Load-bearing properties:

1. *Kernel parity*: ``spec_verify_tree`` (Pallas, interpret mode) matches the
   pure-JAX ``spec_verify_tree_ref`` bit-exactly on the greedy-NAV integer
   outputs (n_accepted, best node, correction) for random trees, including
   all-accepted / all-rejected rounds, B=1, and non-pow2 vocabs.
2. *Chain reduction*: a width-1 tree is exactly a chain — the tree verifier
   agrees with ``spec_verify_ref`` and the tree drafter with ``draft_round``.
3. *Greedy losslessness*: tree spec decoding emits exactly the target-only
   greedy sequence (the tree generalization of the chain invariant).
4. *Stochastic exactness*: multi-branch rejection sampling preserves the
   target distribution for i.i.d. draft children (SpecInfer-style).
5. *Serving*: tree requests ride the CloudVerifier's continuous-batching
   dispatcher next to chain requests and return accepted paths.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.spec_decode import (
    DraftConfig,
    TreeDraftConfig,
    draft_round,
    replay_path,
    tree_draft_round,
    tree_target_logits,
    tree_verify_stochastic,
)
from repro.kernels.spec_verify import (
    spec_verify_ref,
    spec_verify_tree,
    spec_verify_tree_batched,
    spec_verify_tree_ragged_ref,
    tree_path,
    tree_topology,
)

KEY = jax.random.PRNGKey(7)


# --------------------------------------------------------------------------- #
# Topology helpers
# --------------------------------------------------------------------------- #


def _random_tree(rng, n):
    """Topologically packed random parents (multi-root allowed)."""
    return [int(rng.integers(-1, i)) for i in range(n)]


def test_tree_topology_depths_and_ancestors():
    #       -1 → 0 → 2        (0-rooted chain through 2)
    #       -1 → 1             (second root)
    #        0 → 3             (sibling of 2)
    parents = jnp.asarray([[-1, -1, 0, 0]], jnp.int32)
    prow, depth, anc = tree_topology(parents)
    np.testing.assert_array_equal(np.asarray(prow[0]), [0, 0, 1, 1])
    np.testing.assert_array_equal(np.asarray(depth[0]), [1, 1, 2, 2])
    anc = np.asarray(anc[0])
    assert anc[2].tolist() == [True, False, True, False]  # path of node 2 = {0, 2}
    assert anc[3].tolist() == [True, False, False, True]
    assert anc[1].tolist() == [False, True, False, False]


def test_tree_path_reconstruction():
    parents = [-1, 0, 1, 0, -1]
    assert tree_path(parents, 2) == [0, 1, 2]
    assert tree_path(parents, 4) == [4]
    assert tree_path(parents, -1) == []


# --------------------------------------------------------------------------- #
# Kernel vs pure-JAX ref parity (greedy tree-NAV)
# --------------------------------------------------------------------------- #


def _random_requests(rng, B, max_n, V, match_prob=0.6):
    logits_seq, tokens_seq, parents_seq = [], [], []
    for _ in range(B):
        n = int(rng.integers(1, max_n + 1))
        lg = (rng.standard_normal((n + 1, V)) * 3).astype(np.float32)
        pr = _random_tree(rng, n)
        tk = []
        for i in range(n):
            if rng.random() < match_prob:
                tk.append(int(np.argmax(lg[pr[i] + 1])))  # matches target greedy
            else:
                tk.append(int(rng.integers(0, V)))
        logits_seq.append(lg)
        tokens_seq.append(tk)
        parents_seq.append(pr)
    return logits_seq, tokens_seq, parents_seq


@pytest.mark.parametrize("V", [257, 1024])
def test_tree_kernel_bit_exact_vs_ref(V):
    """Greedy tree-NAV integers must be BIT-EXACT between interpret-mode
    Pallas and the pure-JAX ref; log-probs agree to float tolerance."""
    rng = np.random.default_rng(V)
    for trial in range(6):
        logits_seq, tokens_seq, parents_seq = _random_requests(rng, 3, 9, V)
        ker = spec_verify_tree_batched(
            logits_seq, tokens_seq, parents_seq, impl="interpret", block_v=256
        )
        ref = spec_verify_tree_ragged_ref(logits_seq, tokens_seq, parents_seq)
        for i, ((na, path, corr, lp), (na2, best2, corr2, lp2)) in enumerate(zip(ker, ref)):
            assert na == na2, f"V={V} trial={trial} session={i}"
            assert corr == corr2, f"V={V} trial={trial} session={i}"
            assert (path[-1] if path else -1) == best2
            assert len(path) == na  # the accepted path IS n_accepted long
            np.testing.assert_allclose(lp, lp2, atol=1e-4)


@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_tree_all_accepted_and_all_rejected(impl):
    V = 128
    rng = np.random.default_rng(3)
    # All-accepted: every node's token is the target greedy at its parent row.
    lg = (rng.standard_normal((5, V)) * 4).astype(np.float32)
    parents = [-1, 0, 1, 2]  # a chain-shaped tree, depth 4
    tokens = [int(np.argmax(lg[p + 1])) for p in parents]
    (na, path, corr, _), = spec_verify_tree_batched([lg], [tokens], [parents], impl=impl)
    assert na == 4 and path == [0, 1, 2, 3]
    assert corr == int(np.argmax(lg[4]))  # bonus from the leaf's own row
    # All-rejected: no token matches → n_acc 0, correction from the anchor.
    tokens_bad = [(t + 1) % V for t in tokens]
    (na, path, corr, _), = spec_verify_tree_batched([lg], [tokens_bad], [parents], impl=impl)
    assert na == 0 and path == []
    assert corr == int(np.argmax(lg[0]))


@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_tree_single_session_padding_inert(impl):
    """B=1 rides the pow2 bucketing: pad rows/nodes must not perturb it."""
    rng = np.random.default_rng(11)
    logits_seq, tokens_seq, parents_seq = _random_requests(rng, 1, 5, 192)
    (got,) = spec_verify_tree_batched(
        logits_seq, tokens_seq, parents_seq, impl=impl, block_v=64
    )
    (want,) = spec_verify_tree_ragged_ref(logits_seq, tokens_seq, parents_seq)
    assert (got[0], got[2]) == (want[0], want[2])


def test_tree_sibling_tiebreak_prefers_packed_order():
    """Two accepted siblings at the same depth: the verifier must pick the
    SMALLEST packed index (the drafter packs siblings confidence-sorted)."""
    V = 64
    lg = np.full((3, V), -5.0, np.float32)
    lg[0, 7] = 5.0  # anchor greedy = 7
    lg[1, 3] = 5.0
    lg[2, 4] = 5.0
    parents = [-1, -1]
    tokens = [7, 7]  # both siblings match the anchor greedy
    for impl in ("ref", "interpret"):
        (na, path, corr, _), = spec_verify_tree_batched([lg], [tokens], [parents], impl=impl, block_v=64)
        assert na == 1 and path == [0], impl
        assert corr == 3, impl  # correction from node 0's own row


def test_tree_chain_equivalence_with_chain_verifier():
    """A width-1 tree is a chain: tree-NAV == chain NAV on the same logits."""
    rng = np.random.default_rng(5)
    V, K = 301, 6
    lg = (rng.standard_normal((K + 1, V)) * 3).astype(np.float32)
    tokens = [int(np.argmax(lg[i])) for i in range(3)] + [int(rng.integers(0, V)) for _ in range(3)]
    parents = [-1] + list(range(K - 1))
    na_c, corr_c, _ = spec_verify_ref(
        jnp.asarray(lg)[None], jnp.asarray([tokens], jnp.int32), jnp.asarray([K], jnp.int32)
    )
    (na_t, path, corr_t, _), = spec_verify_tree_batched([lg], [tokens], [parents], impl="ref")
    assert na_t == int(na_c[0, 0])
    assert corr_t == int(corr_c[0, 0])
    assert path == list(range(na_t))


def test_tree_batched_rejects_bad_topology():
    lg = np.zeros((3, 64), np.float32)
    with pytest.raises(ValueError):
        spec_verify_tree_batched([lg], [[1, 2]], [[0, 0]], impl="ref")  # parents[0] must be -1
    with pytest.raises(ValueError):
        spec_verify_tree_batched([lg], [[1, 2]], [[-1, 5]], impl="ref")  # forward reference
    with pytest.raises(ValueError):
        spec_verify_tree_batched([lg], [[1, 2]], [[-1]], impl="ref")  # length mismatch


# --------------------------------------------------------------------------- #
# Tree drafting
# --------------------------------------------------------------------------- #


def _decaying_draft_step(vocab=32):
    """Deterministic synthetic draft: peaked logits that flatten with depth.

    The cache is the step count; confidence decays as the tree deepens so
    threshold pruning has something to bite on.
    """

    def step(params, tok, cache):
        k = cache
        sharp = 4.0 - 0.9 * k.astype(jnp.float32)
        logits = jnp.zeros((tok.shape[0], vocab))
        logits = logits.at[:, 3].set(sharp).at[:, 5].set(sharp - 0.3).at[:, 9].set(sharp - 0.6)
        return logits, k + 1

    return step


def test_tree_draft_round_topology_and_packing():
    cfg = TreeDraftConfig(depth=3, width=2, max_nodes=14)
    res = tree_draft_round(_decaying_draft_step(), None, jnp.int32(0), 0, cfg)
    assert 1 <= res.n_nodes <= 14
    for i in range(res.n_nodes):
        assert -1 <= res.parents[i] < i  # topologically packed
    # Level order + conf-sorted siblings: path_conf = parent's × own conf.
    for i in range(res.n_nodes):
        p = int(res.parents[i])
        parent_conf = 1.0 if p < 0 else float(res.path_confs[p])
        np.testing.assert_allclose(res.path_confs[i], parent_conf * res.confs[i], rtol=1e-6)
        assert res.depths[i] == (1 if p < 0 else res.depths[p] + 1)
    # Siblings are confidence-sorted (verifier tie-break prefers low index).
    by_parent = {}
    for i in range(res.n_nodes):
        by_parent.setdefault(int(res.parents[i]), []).append(float(res.confs[i]))
    for sibs in by_parent.values():
        assert sibs == sorted(sibs, reverse=True)


def test_tree_draft_round_prunes_on_r2_and_stops_on_r1():
    # R2 high: only the strongest child survives each expansion.
    cfg = TreeDraftConfig(depth=3, width=3, max_nodes=20, r2=0.45)
    res = tree_draft_round(_decaying_draft_step(), None, jnp.int32(0), 0, cfg)
    assert all(c > 0.45 for c in res.confs.tolist())
    # R1 close to 1: every path fires immediately → a single level.
    cfg2 = TreeDraftConfig(depth=4, width=2, max_nodes=20, r1=0.999999)
    res2 = tree_draft_round(_decaying_draft_step(), None, jnp.int32(0), 0, cfg2)
    assert int(res2.depths.max()) == 1


def test_tree_draft_round_width1_matches_chain_draft_round():
    """width=1, no thresholds → exactly the greedy chain of draft_round."""
    step = _decaying_draft_step()
    cfg_tree = TreeDraftConfig(depth=5, width=1, max_nodes=5)
    res_t = tree_draft_round(step, None, jnp.int32(0), 0, cfg_tree)
    cfg_chain = DraftConfig(window=5, r1=0.0, r2=0.0)
    res_c = draft_round(step, None, jnp.int32(0), jnp.zeros((1,), jnp.int32), cfg_chain, KEY)
    assert res_t.n_nodes == 5
    np.testing.assert_array_equal(res_t.tokens, np.asarray(res_c.tokens[0]))
    np.testing.assert_array_equal(res_t.parents, [-1, 0, 1, 2, 3])
    np.testing.assert_allclose(res_t.confs, np.asarray(res_c.confs[0]), rtol=1e-5)


def test_tree_draft_round_beam_caps_frontier():
    cfg = TreeDraftConfig(depth=3, width=3, max_nodes=30, beam=1)
    res = tree_draft_round(_decaying_draft_step(), None, jnp.int32(0), 0, cfg)
    # With beam=1 only one node per level is expanded: ≤ width new nodes per
    # level and total ≤ width · depth.
    assert res.n_nodes <= 9
    levels = {}
    for i in range(res.n_nodes):
        levels[int(res.depths[i])] = levels.get(int(res.depths[i]), 0) + 1
    assert all(v <= 3 for v in levels.values())


# --------------------------------------------------------------------------- #
# End-to-end greedy losslessness on a tiny transformer
# --------------------------------------------------------------------------- #


def test_tree_spec_decoding_is_lossless():
    from repro.models import transformer as T
    from repro.models.config import ModelConfig
    from repro.models.kvcache import set_lengths

    def _tiny(name, layers):
        return ModelConfig(name=name, family="dense", n_layers=layers, d_model=48,
                           n_heads=4, n_kv_heads=2, d_ff=96, vocab_size=128,
                           head_dim=12, vocab_pad_to=64)

    tcfg, dcfg = _tiny("target", 2), _tiny("draft", 1)
    tparams = T.init(jax.random.PRNGKey(10), tcfg)
    dparams = T.init(jax.random.PRNGKey(20), dcfg)
    P, N_NEW = 6, 12
    prompt = jax.random.randint(KEY, (1, P), 0, 128)

    # Gold: target-only greedy.
    cache = T.make_cache(tcfg, 1, 256)
    logits, cache = T.prefill(tparams, {"tokens": prompt}, cache, tcfg)
    tok = jnp.argmax(logits[:, -1, :], -1).astype(jnp.int32)
    gold = [int(tok[0])]
    for _ in range(N_NEW):
        logits, cache = T.decode(tparams, tok[:, None], cache, tcfg)
        tok = jnp.argmax(logits[:, 0, :], -1).astype(jnp.int32)
        gold.append(int(tok[0]))

    def draft_step(params, tok, cache):
        lg, c = T.decode(params, tok[:, None], cache, dcfg)
        return lg[:, 0, :], c

    def target_forward(params, seq, cache):
        return T.decode(params, seq, cache, tcfg)

    d_cache = T.make_cache(dcfg, 1, 256)
    t_cache = T.make_cache(tcfg, 1, 256)
    _, d_cache = T.prefill(dparams, {"tokens": prompt}, d_cache, dcfg)
    t_logits, t_cache = T.prefill(tparams, {"tokens": prompt}, t_cache, tcfg)
    last = int(jnp.argmax(t_logits[0, -1, :]))
    out = [last]
    cfg = TreeDraftConfig(depth=3, width=2, max_nodes=8)
    t_len = P
    while len(out) < N_NEW + 1:
        dr = tree_draft_round(draft_step, dparams, d_cache, last, cfg)
        lg = tree_target_logits(
            target_forward, tparams, set_lengths(t_cache, jnp.asarray([t_len])),
            last, dr.tokens, dr.parents,
        )
        na, best, corr, _ = spec_verify_tree(
            lg[None], jnp.asarray(dr.tokens)[None], jnp.asarray(dr.parents)[None],
            jnp.asarray([dr.n_nodes]), impl="ref",
        )
        na, best, corr = int(na[0, 0]), int(best[0, 0]), int(corr[0, 0])
        acc = [int(dr.tokens[j]) for j in tree_path(dr.parents, best)]
        out.extend(acc)
        out.append(corr)
        # Roll forward: target replays anchor+accepted path from the prefix,
        # draft replays the accepted path from the anchor cache (tree-reject
        # rollback = discard everything past the committed prefix).
        seq = jnp.asarray([[last] + acc], jnp.int32)
        _, t_cache = target_forward(tparams, seq, set_lengths(t_cache, jnp.asarray([t_len])))
        t_len += 1 + na
        d_cache = replay_path(draft_step, dparams, dr.anchor_cache, acc)
        last = corr
    assert out[: N_NEW + 1] == gold, "tree spec decode diverged from target-greedy"


# --------------------------------------------------------------------------- #
# Stochastic tree verification
# --------------------------------------------------------------------------- #


def test_tree_verify_stochastic_preserves_target_distribution():
    """Single-level tree, k=2 i.i.d. children from q: the emitted token
    (accepted child or residual correction) must be distributed as p."""
    rng = np.random.default_rng(0)
    V = 6
    p = np.array([0.34, 0.06, 0.18, 0.12, 0.05, 0.25])
    q = np.array([0.05, 0.30, 0.10, 0.15, 0.25, 0.15])
    n_trials = 20_000
    counts = np.zeros(V)
    target_probs = np.stack([p, p, p])  # anchor row + one row per child
    draft_probs = np.stack([q, q, q])
    for _ in range(n_trials):
        children = rng.choice(V, size=2, p=q)
        tokens = [int(children[0]), int(children[1])]
        parents = [-1, -1]
        path, corr = tree_verify_stochastic(target_probs, draft_probs, tokens, parents, rng)
        emitted = tokens[path[0]] if path else corr
        counts[emitted] += 1
    np.testing.assert_allclose(counts / n_trials, p, atol=0.015)


def test_tree_verify_stochastic_chain_reduces_to_single_draft():
    """One child drawn from q ≡ classic speculative sampling: marginal = p."""
    rng = np.random.default_rng(1)
    V = 4
    p = np.array([0.45, 0.05, 0.3, 0.2])
    q = np.array([0.1, 0.4, 0.2, 0.3])
    counts = np.zeros(V)
    n_trials = 20_000
    for _ in range(n_trials):
        tok = int(rng.choice(V, p=q))
        path, corr = tree_verify_stochastic(
            np.stack([p, p]), np.stack([q, q]), [tok], [-1], rng
        )
        counts[tok if path else corr] += 1
    np.testing.assert_allclose(counts / n_trials, p, atol=0.015)


# --------------------------------------------------------------------------- #
# Serving: tree requests through the continuous-batching dispatcher
# --------------------------------------------------------------------------- #


def test_cloud_verifier_dispatches_mixed_chain_and_tree():
    from repro.runtime import (
        Channel,
        ChannelConfig,
        CloudVerifier,
        DraftFragment,
        NavRequest,
        SyntheticBackend,
        TreeNavRequest,
    )

    ts = 0.01
    backend = SyntheticBackend(time_scale=ts, seed=0)
    server = CloudVerifier(backend, batch_window=backend.verify_time * ts, max_batch=8)
    links = {}
    for sid in (0, 1):
        up = Channel(ChannelConfig(alpha=0.001, beta=0.0001, time_scale=ts))
        dn = Channel(ChannelConfig(alpha=0.001, beta=0.0001, time_scale=ts))
        server.attach(sid, up, dn)
        links[sid] = (up, dn)
    server.start()
    try:
        # Session 0: chain round. Session 1: tree round with packed parents.
        up0, dn0 = links[0]
        up0.send(DraftFragment(0, 1, 1, (5, 6, 7), (0.99, 0.99, 0.99)))
        up0.send(NavRequest(0, 2, 1, n_tokens=3))
        up1, dn1 = links[1]
        parents = [-1, -1, 0, 1, 2]
        up1.send(DraftFragment(1, 1, 1, (1, 2, 3, 4, 5), (0.99,) * 5, tuple(parents)))
        up1.send(TreeNavRequest(1, 2, 1, n_tokens=5))
        r0 = dn0.recv(timeout=5.0)
        r1 = dn1.recv(timeout=5.0)
    finally:
        server.stop()
    assert r0 is not None and r0.path is None
    assert 0 <= r0.n_accepted <= 3
    assert r1 is not None and r1.path is not None
    path = r1.path
    assert len(path) == r1.n_accepted
    # The path must be a root→leaf chain under the sent parents.
    for a, b in zip(path, path[1:]):
        assert parents[b] == a
    if path:
        assert parents[path[0]] == -1
    assert server.stats["tokens_verified"] == 8


def test_spec_verify_backend_tree_batch_matches_solo():
    """Kernel-backed tree verify: batched call == per-session calls."""
    from repro.runtime import LogitsVerifyBackend

    V = 256

    def logits_fn(session, tokens):
        rng = np.random.default_rng(500 + session)
        return (rng.standard_normal((len(tokens) + 1, V)) * 2).astype(np.float32)

    backend = LogitsVerifyBackend(logits_fn, impl="ref")
    reqs = [
        (0, [3, 9, 7], [0.9] * 3, [-1, 0, 1]),
        (1, [5, 6], [0.9] * 2, [-1, -1]),
        (2, [1, 2, 3, 4], [0.9] * 4, [-1, 0, 0, 2]),
    ]
    batched = backend.verify_tree_batch(reqs)
    solo = [backend.verify_tree(s, t, c, p) for (s, t, c, p) in reqs]
    assert batched == solo
    for (n_acc, corr, path), (_, _, _, parents) in zip(batched, reqs):
        assert len(path) == n_acc
