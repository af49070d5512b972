"""Sharded target verifier == the unsharded oracle, bit-for-bit.

The tentpole contract: the tensor-parallel spec-verify launch
(``repro.sharding.spec_verify``) running over a host device mesh must be
``assert_array_equal``-exact vs the unsharded one-launch entry for every
shard count — fp32 and int8 pages, GQA head splits that don't divide
evenly, non-pow2 vocabularies, ragged batches — and the dispatcher-facing
backend (``ShardedSpecVerifyBackend``) must be indistinguishable from the
unsharded fused backend through rollback/evict/CoW-fork traffic.

All random cases come from the shared strategy module (``strategies.py``);
``assert_paths_agree`` is the cross-path differential harness.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from strategies import (
    assert_paths_agree,
    assert_ragged_match,
    assert_triples_match,
    composed_logits,
    make_ragged_case,
    make_rect_case,
    ragged_geometries,
)

from repro.sharding import (
    host_mesh,
    plan_shards,
    sharded_target_logits,
    spec_verify_sharded,
    spec_verify_sharded_batched,
)

requires_mesh = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=4 (set in conftest.py)",
)


# --------------------------------------------------------------------------- #
# Shard planning metadata (pure, no mesh needed)
# --------------------------------------------------------------------------- #
def test_plan_shards_even_split():
    p = plan_shards(shards=4, n_heads=8, n_kv_heads=8, head_dim=16, vocab=1024)
    assert p.even_heads and p.even_kv_heads
    assert p.heads_per_shard == 2 and p.padded_heads == 8
    assert p.launch_vocab == p.vocab_per_shard * 4 >= p.padded_vocab


def test_plan_shards_uneven_heads_pad():
    p = plan_shards(shards=4, n_heads=6, n_kv_heads=3, head_dim=8, vocab=384, block_v=128)
    assert not p.even_heads and not p.even_kv_heads
    assert p.padded_heads == 8 and p.heads_per_shard == 2
    assert p.vocab_per_shard % p.block_v == 0
    assert p.launch_vocab >= p.padded_vocab >= p.vocab


def test_plan_shards_rejects_bad_gqa():
    with pytest.raises(ValueError):
        plan_shards(shards=2, n_heads=5, n_kv_heads=2, head_dim=8, vocab=256)


# --------------------------------------------------------------------------- #
# Rectangular kernel-level exactness
# --------------------------------------------------------------------------- #
@requires_mesh
@pytest.mark.parametrize("shards", [1, 2, 4])
def test_sharded_logits_bitexact_vs_composition(shards):
    """Sharded logits == jitted attention + blocked LM head, per logit."""
    B, K, H, Hkv, hd, bs, G, P, V = 2, 3, 4, 2, 8, 4, 4, 16, 384
    q, kp, vp, w, tables, lengths, tokens, nd = make_rect_case(B, K, H, Hkv, hd, bs, G, P, V)
    mesh = host_mesh(shards)
    got = sharded_target_logits(q, kp, vp, w, tables, lengths, mesh=mesh, block_v=128)
    want = composed_logits(q, kp, vp, w, tables, lengths, impl="ref", block_v=128)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@requires_mesh
@pytest.mark.parametrize("shards", [1, 2, 3, 4])
def test_sharded_rect_uneven_gqa_bitexact(shards):
    """H=6/Hkv=3 over 4 shards: padded head lanes stay inert, bit-for-bit."""
    from repro.kernels.spec_verify import spec_verify_fused

    B, K, H, Hkv, hd, bs, G, P, V = 2, 2, 6, 3, 8, 4, 3, 12, 384
    q, kp, vp, w, tables, lengths, tokens, nd = make_rect_case(B, K, H, Hkv, hd, bs, G, P, V, seed=7)
    mesh = host_mesh(shards)
    got = spec_verify_sharded(
        q, kp, vp, w, tables, lengths, tokens, nd, mesh=mesh, block_v=128
    )
    want = spec_verify_fused(
        q, kp, vp, w, tables, lengths, tokens, nd, impl="ref", block_v=128
    )
    assert_triples_match(got, want, ks=np.asarray(nd))


# --------------------------------------------------------------------------- #
# Ragged serving entry: the differential harness
# --------------------------------------------------------------------------- #
@requires_mesh
@pytest.mark.parametrize("quantize", [None, "int8"])
def test_differential_all_paths(quantize):
    """chain / tree / fused / batched / sharded@{1,2,4} agree on one case."""
    case = make_ragged_case([3, 1, 4], Hkv=2, gqa=1, V=256, seed=3, quantize=quantize)
    assert_paths_agree(case, impl="ref", block_v=256, shards=(1, 2, 4))


@requires_mesh
def test_differential_uneven_gqa_nonpow2_vocab():
    """GQA 3-way KV heads + V=384: sharded still bit-matches the pivot."""
    case = make_ragged_case([2, 5], Hkv=3, gqa=2, V=384, seed=11)
    assert_paths_agree(case, impl="ref", block_v=128, shards=(2, 3, 4))


@requires_mesh
@pytest.mark.parametrize("bias,expect", [(1.0, "all"), (0.0, "none")])
def test_differential_forced_accept_reject(bias, expect):
    """Forced accept/reject patterns survive every path unchanged."""
    case = make_ragged_case([3, 2], Hkv=2, gqa=1, V=256, seed=5, sharp=True, accept_bias=bias)
    pivot = assert_paths_agree(case, impl="ref", block_v=256, shards=(1, 2, 4))
    for (na, _corr, _lp), k in zip(pivot, case.ks):
        assert na == (k if expect == "all" else 0)


@requires_mesh
def test_sharded_int8_planes_travel_with_kv():
    """Int8 scale/zero planes shard along the same head axis as their pages:
    the quantized sharded launch == the quantized unsharded launch exactly."""
    from repro.kernels.spec_verify import spec_verify_fused_batched

    case = make_ragged_case([4, 2, 1], Hkv=2, gqa=2, V=256, seed=17, quantize="int8")
    pivot = spec_verify_fused_batched(
        case.q_seq, case.tok_seq, case.tables_seq, case.base,
        case.k_pages, case.v_pages, case.w,
        impl="ref", block_v=256, pad_page_id=case.sentinel_page, quant=case.quant,
    )
    for n in (2, 4):
        got = spec_verify_sharded_batched(
            case.q_seq, case.tok_seq, case.tables_seq, case.base,
            case.k_pages, case.v_pages, case.w,
            shards=n, block_v=256, pad_page_id=case.sentinel_page, quant=case.quant,
        )
        assert_ragged_match(got, pivot, exact_logp=True, label=f"int8 sharded@{n}")


@requires_mesh
@settings(max_examples=8, deadline=None)
@given(geom=ragged_geometries(), shards=st.sampled_from([1, 2, 4]))
def test_property_sharded_differential(geom, shards):
    """Random ragged sweep: the harness holds for any drawn geometry."""
    case = make_ragged_case(**geom)
    assert_paths_agree(case, impl="ref", block_v=128, shards=(shards,))


# --------------------------------------------------------------------------- #
# Backend: dispatcher-oblivious sharding
# --------------------------------------------------------------------------- #
def _twin_backends(shards, quantize=None, num_blocks=32):
    """An unsharded fused backend and a sharded one over twin pools with
    identical seeded contents; any divergence between them is a sharding bug."""
    from strategies import fused_backend

    ref, p_ref, _, _ = fused_backend(quantize, num_blocks=num_blocks)
    sh, p_sh, _, _ = fused_backend(quantize, num_blocks=num_blocks, shards=shards)
    return ref, p_ref, sh, p_sh


@requires_mesh
@pytest.mark.parametrize("shards", [1, 2, 4])
@pytest.mark.parametrize("quantize", [None, "int8"])
def test_backend_matches_unsharded(shards, quantize):
    ref, p_ref, sh, p_sh = _twin_backends(shards, quantize)
    reqs = [(0, [3, 9, 7], [0.9] * 3), (1, [5], [0.9]), (2, [1, 2, 3, 4], [0.9] * 4)]
    for s, toks, _ in reqs:
        for p in (p_ref, p_sh):
            p.create(s)
            p.append(s, 5 + s + len(toks) + 1)
    assert sh.verify_batch(reqs) == ref.verify_batch(reqs)


@requires_mesh
def test_backend_rejects_unfused():
    from repro.runtime import ShardedSpecVerifyBackend

    with pytest.raises(ValueError, match="fused"):
        ShardedSpecVerifyBackend(shards=2, fused=False, lm_head=np.ones((4, 8), np.float32))


@pytest.mark.parametrize("impl", ["pallas", "interpret"])
def test_backend_refuses_impl_it_would_ignore(impl):
    """The sharded launch is jax.numpy: a Pallas impl is refused, not ignored."""
    from repro.runtime import ShardedSpecVerifyBackend

    with pytest.raises(ValueError, match="impl must be 'ref'"):
        ShardedSpecVerifyBackend(shards=1, lm_head=np.ones((4, 8), np.float32), impl=impl)


def test_spec_backend_refuses_unknown_impl():
    from repro.runtime import SpecVerifyBackend

    with pytest.raises(ValueError, match="impl must be one of"):
        SpecVerifyBackend(lambda s, t: None, impl="tpu")


def _launcher():
    import importlib.util
    import pathlib

    path = pathlib.Path(__file__).resolve().parents[1] / "launch" / "serve.py"
    spec = importlib.util.spec_from_file_location("serve_under_test", path)
    serve = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(serve)
    return serve


@pytest.mark.parametrize("shards", [1, 2])
def test_launcher_spec_backend_takes_registry_geometry(monkeypatch, shards):
    """``serve.py --backend spec`` sizes pool and LM head from ``--arch``, runs
    the fused backend with the named impl at one shard, the sharded one above."""
    from repro import configs
    from repro.runtime import ShardedSpecVerifyBackend

    real = configs.get_config
    monkeypatch.setattr(configs, "get_config", lambda name: real(name, reduced=True))
    cfg = real("granite-3-2b", reduced=True)
    serve = _launcher()
    argv = ["--listen", "127.0.0.1:0", "--backend", "spec", "--shards", str(shards)]
    backend, kw = serve._make_backend(serve.build_parser().parse_args(argv + ["--impl", "ref"]))
    pool = kw["kv_pool"]
    assert (pool.n_layers, pool.n_kv_heads, pool.head_dim, pool.block_size, pool.num_blocks) == (
        cfg.n_layers, cfg.n_kv_heads, cfg.head_dim, serve.PAGE_SIZE, serve.KV_BLOCKS,
    )
    assert backend.lm_head.shape == (cfg.n_heads * cfg.head_dim, cfg.vocab_size)
    assert backend.fused and backend.impl == "ref" and backend.block_v == 512
    assert isinstance(backend, ShardedSpecVerifyBackend) == (shards > 1)
    if shards > 1:  # the default --impl pallas is refused, not silently swapped
        with pytest.raises(ValueError, match="impl must be 'ref'"):
            serve._make_backend(serve.build_parser().parse_args(argv))


@requires_mesh
def test_backend_rollback_recycle_matches_unsharded():
    """Rollback frees a page, a foreign session dirties it, the session
    regrows: per-shard watermarks must refill exactly like the oracle."""
    ref, p_ref, sh, p_sh = _twin_backends(2)
    for backend, pool in ((ref, p_ref), (sh, p_sh)):
        pool.create(0)
        pool.append(0, 9)
        backend.ensure_kv(0)
        pool.rollback(0, 6)  # trailing page freed
        pool.create(99)  # foreign session recycles it...
        pool.append(99, pool.block_size)
        junk = jnp.full((1, pool.block_size, pool.n_kv_heads, pool.head_dim), 7.5)
        pool.fill(99, 0, junk, -junk)  # ...and dirties it
        pool.release(99)
        pool.append(0, 3)  # regrow to 9
    reqs = [(0, [3, 9, 7], [0.9] * 3)]
    assert sh.verify_batch(reqs) == ref.verify_batch(reqs)
    np.testing.assert_array_equal(np.asarray(p_sh.k_pages), np.asarray(p_ref.k_pages))


@requires_mesh
def test_backend_evict_rematerialize_matches_unsharded():
    """Evicted-then-resumed sessions re-prefill; shards stay in lockstep."""
    ref, p_ref, sh, p_sh = _twin_backends(2)
    for backend, pool in ((ref, p_ref), (sh, p_sh)):
        pool.create(0)
        pool.append(0, 6)
        backend.ensure_kv(0)
        pool.evict(0)
        pool.create(1)  # pages recycled + dirtied in between
        pool.append(1, 8)
        junk = jnp.full((1, 8, pool.n_kv_heads, pool.head_dim), -3.25)
        pool.fill(1, 0, junk, junk)
        pool.release(1)
        pool.append(0, 6)  # comeback re-prefill
    reqs = [(0, [1, 2], [0.9] * 2)]
    assert sh.verify_batch(reqs) == ref.verify_batch(reqs)


@requires_mesh
@pytest.mark.parametrize("quantize", [None, "int8"])
def test_mesh_placed_pool_fills_in_place(quantize):
    """Fills and CoW copies of a pool laid out over the mesh keep every
    buffer's sharding, so JAX can use the donated buffers: no "donated
    buffers were not usable" warning, the same per-device addresses, and
    the same pages as the unsharded twin."""
    import warnings

    ref, p_ref, sh, p_sh = _twin_backends(2, quantize)
    names = p_sh._buffer_names()
    shardings = [getattr(p_sh, n).sharding for n in names]
    addresses = [[s.data.unsafe_buffer_pointer() for s in getattr(p_sh, n).addressable_shards]
                 for n in names]
    with warnings.catch_warnings():
        warnings.filterwarnings("error", message=".*donated buffers were not usable")
        for backend, pool in ((ref, p_ref), (sh, p_sh)):
            pool.create(0)
            pool.append(0, 6)
            backend.ensure_kv(0)  # a fill across a page end
            pool.fork(0, 1)
            pool.append(1, 2)  # CoW copy of the shared tail page
            backend.ensure_kv(1)
    assert p_sh.stats["write_launches"] == 3
    assert [getattr(p_sh, n).sharding for n in names] == shardings
    assert [[s.data.unsafe_buffer_pointer() for s in getattr(p_sh, n).addressable_shards]
            for n in names] == addresses
    for n in names:
        np.testing.assert_array_equal(np.asarray(getattr(p_sh, n)), np.asarray(getattr(p_ref, n)))


@requires_mesh
def test_backend_cow_fork_matches_unsharded():
    """CoW-forked sessions share prefix pages; the first divergent write
    copies — identically on both backends, so verdicts stay equal."""
    ref, p_ref, sh, p_sh = _twin_backends(2)
    out = {}
    for name, (backend, pool) in (("ref", (ref, p_ref)), ("sh", (sh, p_sh))):
        pool.create(0)
        pool.append(0, 6)  # one full page + a half-filled shared page
        backend.ensure_kv(0)
        pool.fork(0, 1)  # CoW fork: session 1 shares both pages
        assert pool.filled(1) == 6  # watermark inherited per shard
        pool.append(1, 2)  # grow into the shared half page; fill CoW-copies
        out[name] = backend.verify_batch([(0, [3, 9], [0.9] * 2), (1, [5, 1], [0.9] * 2)])
        assert pool.stats["cow_copies"] >= 1
    assert out["sh"] == out["ref"]


@requires_mesh
def test_serve_round_trip_stream_invariant_under_shards():
    """Full EdgeClient -> CloudVerifier flow on the virtual clock: the
    committed token stream is identical at 1, 2, and 4 shards (the router
    and dispatcher cannot observe the shard count)."""
    from repro.models.paged_kv import PagedKVPool
    from repro.runtime import ShardedSpecVerifyBackend
    from repro.runtime.client import EdgeClient, EdgeConfig
    from repro.runtime.server import CloudVerifier
    from repro.runtime.simclock import VirtualClock
    from repro.runtime.transport import Channel, ChannelConfig

    H, hd, V = 2, 16, 512
    w = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (H * hd, V)) * 6, np.float32)

    def query_fn(session, tokens):
        k = jax.random.fold_in(jax.random.PRNGKey(2), session * 997 + len(tokens))
        return np.asarray(jax.random.normal(k, (len(tokens) + 1, H, hd)), np.float32)

    def once(shards):
        clock = VirtualClock()
        pool = PagedKVPool(num_blocks=256, block_size=8, n_layers=1, n_kv_heads=H, head_dim=hd)
        backend = ShardedSpecVerifyBackend(
            shards=shards, kv_pool=pool, query_fn=query_fn, lm_head=w, impl="ref", block_v=512
        )
        server = CloudVerifier(backend, kv_pool=pool, clock=clock)
        up = Channel(ChannelConfig(alpha=0.02, beta=0.002), "up0", clock=clock)
        dn = Channel(ChannelConfig(alpha=0.01, beta=0.0005), "dn0", clock=clock)
        server.attach(0, up, dn)
        c = EdgeClient(0, up, dn, EdgeConfig(gamma=0.02, nav_timeout=3.0))

        def body():
            server.start()
            stats = c.run(32)
            server.stop()
            return stats

        stats = clock.run(body)
        return list(c.tokens), stats["accepted_tokens"]

    tokens1, acc1 = once(1)
    assert acc1 >= 32 and len(tokens1) == acc1
    for n in (2, 4):
        tokens_n, acc_n = once(n)
        assert (tokens_n, acc_n) == (tokens1, acc1), f"stream diverged at shards={n}"


@requires_mesh
def test_fleet_bench_stream_invariant_under_shards():
    """fleet_bench's sharded tensor backend: committed streams at 1/2/4
    shards are identical — the coalescing dispatcher is shard-oblivious."""
    import pathlib
    import sys

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "benchmarks"))
    from fleet_bench import run_fleet

    from repro.runtime.simclock import VirtualClock

    def once(shards):
        report = run_fleet(
            n_sessions=3, tokens_per_session=16, clock=VirtualClock(), seed=3, shards=shards
        )
        assert all(len(s) >= 16 for s in report["streams"].values())
        return report["streams"]

    base = once(1)
    for n in (2, 4):
        assert once(n) == base, f"fleet stream diverged at shards={n}"


def test_host_mesh_errors_when_too_few_devices():
    with pytest.raises(RuntimeError, match="xla_force_host_platform_device_count"):
        host_mesh(jax.device_count() + 1)
