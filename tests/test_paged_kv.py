"""Paged KV block pool: refcount/CoW/rollback/eviction invariants.

The pool invariant under every test: for each physical page, its refcount
equals the number of session block tables referencing it, and free + used
== num_blocks.  CoW divergence, rollback page release, LRU reuse order, and
eviction-under-pressure are the behaviours the serving dispatcher builds on.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.paged_kv import BlockPoolExhausted, PagedKVPool


def _check_invariants(pool: PagedKVPool) -> None:
    counted = np.zeros(pool.num_blocks, np.int32)
    for t in pool.tables.values():
        for page in t.blocks:
            counted[page] += 1
    np.testing.assert_array_equal(counted, pool.refcounts)
    assert pool.free_blocks + pool.used_blocks == pool.num_blocks
    assert set(pool._free).isdisjoint(
        p for t in pool.tables.values() for p in t.blocks
    )
    # The O(1) resident counter must agree with a full recount.
    assert pool.resident_sessions == sum(1 for t in pool.tables.values() if t.blocks)


def test_refcount_fork_free_invariants():
    pool = PagedKVPool(num_blocks=16, block_size=4)
    pool.create(0)
    pool.append(0, 10)  # 3 pages (one partial)
    _check_invariants(pool)
    pool.fork(0, 1)
    pool.fork(0, 2)
    _check_invariants(pool)
    assert pool.used_blocks == 3  # forks allocate nothing
    assert pool.shared_blocks() == 3
    assert all(pool.refcounts[p] == 3 for p in pool.tables[0].blocks)
    pool.release(1)
    _check_invariants(pool)
    assert pool.used_blocks == 3  # still referenced by 0 and 2
    pool.release(0)
    pool.release(2)
    _check_invariants(pool)
    assert pool.used_blocks == 0 and pool.free_blocks == 16


def test_cow_divergence_after_shared_prefix_write():
    """First append into a shared partial tail page copies it; the parent's
    view of the prefix must be unchanged and full pages stay shared."""
    pool = PagedKVPool(num_blocks=8, block_size=4, n_layers=1, n_kv_heads=1, head_dim=2)
    pool.create(0)
    k0 = jnp.arange(1 * 6 * 1 * 2, dtype=jnp.float32).reshape(1, 6, 1, 2)
    pool.write(0, k0, k0 * 10)  # 6 tokens: one full + one partial page
    pool.fork(0, 1)
    before = np.asarray(pool.k_pages).copy()
    parent_tail = pool.tables[0].blocks[-1]

    k1 = jnp.full((1, 1, 1, 2), 99.0)
    pool.write(1, k1, k1)  # child's first write into the shared tail
    _check_invariants(pool)
    assert pool.stats["cow_copies"] == 1
    assert pool.tables[1].blocks[0] == pool.tables[0].blocks[0]  # full page shared
    child_tail = pool.tables[1].blocks[-1]
    assert child_tail != parent_tail  # tail diverged
    # Parent's pages are untouched by the child's write.
    np.testing.assert_array_equal(np.asarray(pool.k_pages)[:, parent_tail], before[:, parent_tail])
    # Child's copied tail carries the shared prefix slots plus the new token.
    got = np.asarray(pool.k_pages)[0, child_tail]
    np.testing.assert_array_equal(got[:2], np.asarray(k0)[0, 4:6])
    np.testing.assert_array_equal(got[2], np.asarray(k1)[0, 0])


def test_rollback_frees_pages():
    pool = PagedKVPool(num_blocks=8, block_size=4)
    pool.create(0)
    pool.append(0, 13)  # 4 pages
    assert pool.used_blocks == 4
    dropped = pool.rollback(0, 5)  # keep 2 pages
    _check_invariants(pool)
    assert dropped == 2 and pool.used_blocks == 2 and pool.length(0) == 5
    # Rollback across a fork only drops THIS session's references.
    pool.fork(0, 1)
    pool.append(1, 7)  # CoW tail + one new page
    shared_full = pool.tables[0].blocks[0]
    assert pool.rollback(1, 0) == 3
    _check_invariants(pool)
    assert pool.refcounts[shared_full] == 1  # parent still holds it
    assert pool.length(0) == 5  # parent untouched
    with pytest.raises(ValueError):
        pool.rollback(0, 6)  # cannot roll forward


def test_eviction_under_pressure():
    pool = PagedKVPool(num_blocks=4, block_size=4)
    pool.create(0)
    pool.append(0, 8)
    pool.create(1)
    pool.append(1, 8)
    assert pool.free_blocks == 0
    with pytest.raises(BlockPoolExhausted):
        pool.append(1, 4)
    # Session 0 is least-recently touched; exclusion protects it.
    assert pool.evict_lru(exclude=[0, 1]) is None
    assert pool.evict_lru(exclude=[1]) == 0
    _check_invariants(pool)
    assert pool.length(0) == 0 and pool.tables[0].blocks == []
    pool.append(1, 4)  # now backed by the reclaimed pages
    _check_invariants(pool)
    assert pool.stats["evictions"] == 1


def test_flat_reservation_semantics():
    """Reserved (flat-baseline) tables: up-front pages, no CoW, no free."""
    pool = PagedKVPool(num_blocks=8, block_size=4)
    pool.create(0)
    pool.reserve(0, 16)  # 4 pages immediately
    assert pool.used_blocks == 4 and pool.length(0) == 0
    pool.append(0, 10)
    assert pool.used_blocks == 4  # growth consumes the reservation
    assert pool.rollback(0, 2) == 0  # flat caches never return pages
    assert pool.used_blocks == 4
    with pytest.raises(BlockPoolExhausted):
        pool.append(0, 15)  # beyond the reservation
    pool.create(1)
    with pytest.raises(BlockPoolExhausted):
        pool.reserve(1, 32)  # 8 pages > 4 free


def test_lru_free_list_reuse_order():
    pool = PagedKVPool(num_blocks=8, block_size=1)
    pool.create(0)
    pool.append(0, 8)
    pages = list(pool.tables[0].blocks)
    pool.rollback(0, 6)  # frees pages[7] then pages[6]
    pool.rollback(0, 4)  # then pages[5], pages[4]
    pool.create(1)
    pool.append(1, 2)
    # Oldest-freed pages are reused first.
    assert pool.tables[1].blocks == [pages[7], pages[6]]


def test_blocks_needed_counts_cow_copy():
    pool = PagedKVPool(num_blocks=8, block_size=4)
    pool.create(0)
    pool.append(0, 6)
    pool.fork(0, 1)
    # Appending 1 token into the shared partial tail needs the CoW page.
    assert pool.blocks_needed(1, 1) == 1
    assert pool.blocks_needed(1, 2) == 1  # fills the copied tail exactly
    assert pool.blocks_needed(1, 3) == 2  # copy + one fresh page
    free = pool.free_blocks
    pool.append(1, 4)
    assert free - pool.free_blocks == 2


def test_engine_sim_tpt_identical_with_pool():
    """Paged accounting must not perturb the simulated timing model."""
    from repro.core.pipeline import (
        ChannelModel,
        CloudModel,
        EdgeModel,
        PipelineEngine,
        SyntheticSource,
        make_framework,
    )

    def run(pool):
        eng = PipelineEngine(
            make_framework("pipesd", autotune=False),
            ChannelModel(),
            CloudModel(),
            EdgeModel(),
            SyntheticSource(seed=5),
            seed=9,
            kv_pool=pool,
        )
        return eng.run(200)

    base = run(None)
    paged = run(PagedKVPool(num_blocks=256, block_size=16, bytes_per_token=1024))
    assert paged.tpt == base.tpt and paged.rounds == base.rounds
    assert paged.kv_resident_bytes and base.kv_resident_bytes == []
    assert paged.peak_kv_resident_bytes > 0


@pytest.mark.slow
def test_fleet_paged_serves_more_sessions_than_flat():
    """Fixed pool budget: paged admits the whole fleet where flat refuses
    half, with pool bookkeeping far below the 5% TPT-impact bound."""
    from benchmarks.fleet_bench import compare_kv

    reps = compare_kv(n_sessions=8, tokens_per_session=30)
    assert reps["flat"]["n_attached"] == 4  # budget fits 4 max_len reservations
    assert reps["paged"]["n_attached"] == 8
    assert reps["paged"]["kv_max_clients"] > reps["flat"]["n_attached"]
    assert reps["paged"]["failovers"] == 0
    st = reps["paged"]["stats"]
    assert 0 < st.kv_bytes_per_session < reps["flat"]["stats"].kv_bytes_per_session
    assert reps["paged_matched"]["kv_overhead_frac"] < 0.05


# --------------------------------------------------------- sentinel page --


def test_sentinel_page_never_allocated_and_zero_filled():
    """The pad sentinel (id num_blocks) is a real zero page no session owns."""
    pool = PagedKVPool(num_blocks=4, block_size=4, n_layers=1, n_kv_heads=2, head_dim=8)
    assert pool.sentinel_page == pool.num_blocks
    assert pool.k_pages.shape[1] == pool.num_blocks + 1
    for s in range(4):  # exhaust the whole allocatable pool
        pool.create(s)
        pool.append(s, pool.block_size)
    owned = {p for t in pool.tables.values() for p in t.blocks}
    assert pool.sentinel_page not in owned
    assert pool.sentinel_page not in pool._free
    with pytest.raises(BlockPoolExhausted):
        pool.append(0, 1)
    assert bool((pool.k_pages[:, pool.sentinel_page] == 0).all())
    assert bool((pool.v_pages[:, pool.sentinel_page] == 0).all())
    _check_invariants(pool)


def test_table_pads_with_sentinel_by_default():
    pool = PagedKVPool(num_blocks=4, block_size=4, n_layers=1, n_kv_heads=2, head_dim=8)
    pool.create(0)
    pool.append(0, 6)
    tab = pool.table(0, pad_to=4)
    np.testing.assert_array_equal(tab[2:], pool.sentinel_page)
    # Explicit pad_id still honoured (legacy pad-with-0 callers).
    assert pool.table(0, pad_to=4, pad_id=0)[-1] == 0


# ------------------------------------------------- materialized watermark --


def _wm_pool():
    return PagedKVPool(num_blocks=8, block_size=4, n_layers=1, n_kv_heads=1, head_dim=2)


def _tok(n, value=1.0):
    return jnp.full((1, n, 1, 2), value, jnp.float32)


def test_fill_advances_watermark_and_rollback_lowers_it():
    """Regrown slots after a rollback may land in recycled physical pages —
    the watermark must expose them as unmaterialized."""
    pool = _wm_pool()
    pool.create(0)
    pool.append(0, 10)
    assert pool.filled(0) == 0  # metadata append materializes nothing
    pool.fill(0, 0, _tok(10), _tok(10))
    assert pool.filled(0) == 10
    pool.rollback(0, 5)
    assert pool.filled(0) == 5
    pool.append(0, 7)  # regrow to 12, possibly into recycled pages
    assert pool.filled(0) == 5
    pool.fill(0, 5, _tok(7), _tok(7))
    assert pool.filled(0) == 12


def test_fill_gap_does_not_advance_watermark():
    pool = _wm_pool()
    pool.create(0)
    pool.append(0, 8)
    pool.fill(0, 4, _tok(2), _tok(2))  # ahead of the watermark: hole at [0, 4)
    assert pool.filled(0) == 0
    pool.fill(0, 0, _tok(4), _tok(4))  # plug the hole
    assert pool.filled(0) == 4  # conservative: [4, 6) must be refilled


def test_watermark_zeroed_by_evict_and_dies_with_release():
    pool = _wm_pool()
    pool.create(0)
    pool.write(0, _tok(6), _tok(6))  # append + fill -> watermark 6
    assert pool.filled(0) == 6
    pool.evict(0)
    assert pool.filled(0) == 0
    pool.append(0, 6)  # comeback: slots exist but hold recycled content
    assert pool.filled(0) == 0
    pool.release(0)
    pool.create(0)  # reused session id: no inherited watermark
    pool.append(0, 6)
    assert pool.filled(0) == 0


def test_fork_inherits_watermark():
    """A child sees the parent's physical pages, so the parent's
    materialized prefix is materialized for the child too."""
    pool = _wm_pool()
    pool.create(0)
    pool.write(0, _tok(6), _tok(6))
    pool.fork(0, 1)
    assert pool.filled(1) == 6


def test_fill_cow_diverges_shared_pages():
    """fill() through a forked table must never mutate the sibling's view
    (REVIEW: in-place fill corrupted siblings under session-dependent KV)."""
    pool = _wm_pool()
    pool.create(0)
    k0 = jnp.arange(12, dtype=jnp.float32).reshape(1, 6, 1, 2)
    pool.write(0, k0, k0 * 10)
    pool.fork(0, 1)
    before = np.asarray(pool.k_pages).copy()
    parent_pages = list(pool.tables[0].blocks)
    k1 = _tok(6, 99.0)
    pool.fill(1, 0, k1, k1)  # session-dependent overwrite of the shared prefix
    _check_invariants(pool)
    assert pool.stats["cow_copies"] == 2  # both shared pages diverged
    assert all(a != b for a, b in zip(parent_pages, pool.tables[1].blocks))
    for p in parent_pages:  # parent's view is untouched
        np.testing.assert_array_equal(np.asarray(pool.k_pages)[:, p], before[:, p])
    got = np.concatenate(
        [np.asarray(pool.k_pages)[0, pg] for pg in pool.tables[1].blocks]
    )[:6]
    np.testing.assert_array_equal(got, np.asarray(k1)[0])


@pytest.mark.parametrize("quantize, planes", [(None, 2), ("int8", 6)])
def test_page_writes_count_each_buffer_update(quantize, planes):
    """A fill across a page boundary is two chunks, each one update of every
    page buffer: (K, V), and the four int8 planes when the pool quantizes.
    A CoW copy updates each buffer once more.  Each call is one ``kv.fill``
    span; the registry mirror sees the same total."""
    from repro.obs.metrics import MetricRegistry
    from repro.obs.trace import Tracer

    pool = PagedKVPool(num_blocks=8, block_size=4, n_layers=1, n_kv_heads=1, head_dim=8,
                       quantize=quantize, metrics=MetricRegistry())
    pool.tracer = Tracer()
    pool.create(0)
    pool.append(0, 6)
    pool.fill(0, 2, _tok8(3), _tok8(3))  # slots 2-3 of page 0, slot 0 of page 1
    assert pool.stats["page_writes"] == 2 * planes
    assert [span.name for span in pool.tracer.spans()] == ["kv.fill"]
    pool.fork(0, 1)
    pool.fill(1, 5, _tok8(1), _tok8(1))  # shared tail page: CoW copy, then one chunk
    assert pool.stats["cow_copies"] == 1
    assert pool.stats["page_writes"] == 4 * planes
    assert [span.name for span in pool.tracer.spans()] == ["kv.fill", "kv.fill"]
    assert pool.metrics.counter("kv_page_writes").value() == 4 * planes


def _tok8(n):
    return jnp.ones((1, n, 1, 8), jnp.float32)


# ------------------------------------------------------- in-place writes --


def _random_pool(quantize, num_blocks=6, block_size=16):
    """A tensor pool whose every buffer (sentinel aside) holds random values."""
    pool = PagedKVPool(num_blocks=num_blocks, block_size=block_size, n_layers=2,
                       n_kv_heads=2, head_dim=8, quantize=quantize)
    rng = np.random.default_rng(5)
    for name in pool._buffer_names():
        buf = getattr(pool, name)
        if buf.dtype == jnp.int8:
            vals = rng.integers(-128, 128, size=buf.shape)
        else:
            vals = rng.normal(size=buf.shape)
        vals[:, pool.sentinel_page] = 0
        setattr(pool, name, jnp.asarray(vals, buf.dtype))
    return pool


def _eager_fill(bufs, pages, start, k, v, block_size):
    """The functional ``.at[].set`` form of a fill, chunk by chunk."""
    if len(bufs) == 6:
        kq, ks, kz = PagedKVPool.quantize_kv(k)
        vq, vs, vz = PagedKVPool.quantize_kv(v)
        news = (kq, vq, ks, kz, vs, vz)
    else:
        news = (k, v)
    T, written = k.shape[1], 0
    while written < T:
        pos = start + written
        page = pages[pos // block_size - start // block_size]
        slot = pos % block_size
        take = min(block_size - slot, T - written)
        bufs = [b.at[:, page, slot:slot + take].set(n[:, written:written + take])
                for b, n in zip(bufs, news)]
        written += take
    return bufs


def _pool_buffers(pool):
    return [getattr(pool, n) for n in pool._buffer_names()]


@pytest.mark.parametrize("quantize", [None, "int8"])
@pytest.mark.parametrize("T, take", [(T, take) for T in range(1, 10) for take in range(1, T + 1)])
def test_fill_matches_eager_updates(T, take, quantize):
    """Every (T, take) split of a fill of up to window + 1 = 9 tokens writes
    exactly what the eager ``.at[].set`` chunks write: first through a
    forked table (CoW copies of the shared pages, then the chunks), then
    through the owner's, whose pages are no longer shared."""
    bs = 16
    pool = _random_pool(quantize, block_size=bs)
    start = bs - take if take < T else bs - T
    rng = np.random.default_rng(T * 10 + take)
    pool.create(0)
    pool.append(0, 2 * bs)
    pool.fork(0, 1)
    for session in (1, 0):
        k = rng.normal(size=(2, T, 2, 8)).astype(np.float32)
        v = rng.normal(size=(2, T, 2, 8)).astype(np.float32)
        want = [jnp.array(b, copy=True) for b in _pool_buffers(pool)]
        old = list(pool.tables[session].blocks)
        pool.fill(session, start, k, v)
        new = pool.tables[session].blocks
        touched = range(start // bs, (start + T - 1) // bs + 1)
        for bi in touched:  # CoW: the eager reference copies the page first
            if new[bi] != old[bi]:
                want = [b.at[:, new[bi]].set(b[:, old[bi]]) for b in want]
        assert (new != old) == (session == 1)
        want = _eager_fill(want, [new[bi] for bi in touched], start,
                           jnp.asarray(k), jnp.asarray(v), bs)
        for got, ref in zip(_pool_buffers(pool), want):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
        assert pool.filled(session) == 0  # the fill starts past the watermark
    _check_invariants(pool)


@pytest.mark.parametrize("quantize", [None, "int8"])
def test_fill_and_cow_copy_write_in_place(quantize):
    """A fill and a CoW page copy donate the page buffers: XLA writes them
    where they are, so each buffer keeps its device address."""
    pool = PagedKVPool(num_blocks=6, block_size=4, n_layers=2, n_kv_heads=2, head_dim=8,
                       quantize=quantize)
    pool.create(0)
    pool.append(0, 6)
    before = [b.unsafe_buffer_pointer() for b in _pool_buffers(pool)]
    held = pool.k_pages
    pool.fill(0, 2, _tok8(3).repeat(2, 0).repeat(2, 2), _tok8(3).repeat(2, 0).repeat(2, 2))
    assert held.is_deleted()  # the donation contract: re-read after a write
    pool.fork(0, 1)
    pool.append(1, 1)  # shared partial tail page: one CoW copy
    assert pool.stats["cow_copies"] == 1
    assert [b.unsafe_buffer_pointer() for b in _pool_buffers(pool)] == before


@pytest.mark.parametrize("quantize, planes", [(None, 2), ("int8", 6)])
def test_write_launches_count_one_per_fill_and_copy(quantize, planes):
    """``write_launches`` counts the jitted updates: one per ``fill``,
    however many chunks it writes, and one per CoW copy; the registry
    mirrors it as ``kv_write_launches``.  ``page_writes`` counts as before."""
    from repro.obs.metrics import MetricRegistry

    pool = PagedKVPool(num_blocks=8, block_size=4, n_layers=1, n_kv_heads=1, head_dim=8,
                       quantize=quantize, metrics=MetricRegistry())
    pool.create(0)
    pool.append(0, 10)
    pool.fill(0, 0, _tok8(10), _tok8(10))  # three chunks, one launch
    assert (pool.stats["write_launches"], pool.stats["page_writes"]) == (1, 3 * planes)
    pool.fork(0, 1)
    pool.append(1, 1)  # CoW copy of the shared tail page
    assert (pool.stats["write_launches"], pool.stats["page_writes"]) == (2, 4 * planes)
    pool.fill(1, 10, _tok8(1), _tok8(1))
    assert (pool.stats["write_launches"], pool.stats["page_writes"]) == (3, 5 * planes)
    pool.fill(1, 8, _tok8(3), _tok8(3))  # page 2 is the child's own now: no copy
    assert (pool.stats["write_launches"], pool.stats["page_writes"]) == (4, 6 * planes)
    assert pool.metrics.counter("kv_write_launches").value() == 4
    assert pool.metrics.counter("kv_page_writes").value() == 6 * planes
    meta = PagedKVPool(num_blocks=4, block_size=4)  # metadata mode launches nothing
    meta.create(0)
    meta.append(0, 3)
    meta.fork(0, 1)
    meta.append(1, 1)
    assert meta.stats["write_launches"] == 0 and meta.stats["cow_copies"] == 1


# ---------------------------------------------------- write dtype boundary --


def test_write_casts_mismatched_dtype_at_boundary():
    """f32 writes into a bf16 pool cast explicitly — no scatter FutureWarning,
    and the byte accounting invariant holds against the real buffers."""
    import warnings

    pool = PagedKVPool(
        num_blocks=4, block_size=4, n_layers=2, n_kv_heads=2, head_dim=8,
        dtype=jnp.bfloat16,
    )
    pool.create(0)
    rng = np.random.default_rng(3)
    k = jnp.asarray(rng.normal(size=(2, 5, 2, 8)), jnp.float32)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        pool.write(0, k, k + 1)
    assert pool.k_pages.dtype == jnp.bfloat16
    assert pool.tensor_nbytes() == (pool.num_blocks + 1) * pool.bytes_per_block


def test_write_rejects_bad_dtypes():
    pool = PagedKVPool(num_blocks=4, block_size=4, n_layers=1, n_kv_heads=2, head_dim=8)
    pool.create(0)
    k = jnp.zeros((1, 2, 2, 8), jnp.float32)
    with pytest.raises(TypeError, match="floating"):
        pool.write(0, k.astype(jnp.int32), k.astype(jnp.int32))
    with pytest.raises(TypeError, match="mismatch"):
        pool.write(0, k, k.astype(jnp.bfloat16))


# ------------------------------------------------------------ int8 pages --


def _gather_dequant(pages, scale, zero, tab, length, block_size):
    out = []
    for t in range(length):
        pg, sl = int(tab[t // block_size]), t % block_size
        out.append(
            PagedKVPool.dequantize_kv(pages[:, pg, sl], scale[:, pg, sl], zero[:, pg, sl])
        )
    return jnp.stack(out, axis=1)


def test_int8_pool_roundtrip_within_error_bound():
    """Quantize-on-write then dequant stays within (max-min)/510 per element."""
    rng = np.random.default_rng(0)
    pool = PagedKVPool(
        num_blocks=6, block_size=4, n_layers=2, n_kv_heads=2, head_dim=16,
        quantize="int8",
    )
    pool.create(0)
    k = jnp.asarray(4.0 * rng.normal(size=(2, 10, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 10, 2, 16)), jnp.float32)
    pool.write(0, k, v)
    assert pool.k_pages.dtype == jnp.int8
    tab = pool.table(0, pad_to=4)
    for ref, pages, scale, zero in (
        (k, pool.k_pages, pool.k_scale, pool.k_zero),
        (v, pool.v_pages, pool.v_scale, pool.v_zero),
    ):
        hat = _gather_dequant(pages, scale, zero, tab, 10, pool.block_size)
        bound = (jnp.max(ref, -1) - jnp.min(ref, -1)) / 510.0 + 1e-6
        assert bool(jnp.all(jnp.max(jnp.abs(hat - ref), -1) <= bound))
    # int8 accounting: payload + two f32 params per token-head, k and v.
    assert pool.bytes_per_token == 2 * 2 * 2 * (16 + 8)
    assert pool.tensor_nbytes() == (pool.num_blocks + 1) * pool.bytes_per_block


def test_int8_cow_copies_quant_params():
    """CoW divergence must copy scale/zero pages along with the payload."""
    rng = np.random.default_rng(1)
    pool = PagedKVPool(
        num_blocks=8, block_size=4, n_layers=1, n_kv_heads=1, head_dim=8,
        quantize="int8",
    )
    pool.create(0)
    k = jnp.asarray(rng.normal(size=(1, 6, 1, 8)), jnp.float32)
    pool.write(0, k, k)
    pool.fork(0, 1)
    extra = jnp.asarray(rng.normal(size=(1, 1, 1, 8)), jnp.float32)
    pool.write(1, extra, extra)  # CoW-copies the shared tail page
    tab0, tab1 = pool.table(0), pool.table(1)
    assert tab0[1] != tab1[1]
    # Parent's tokens 4..5 readable identically through either table.
    a = _gather_dequant(pool.k_pages, pool.k_scale, pool.k_zero, tab0, 6, 4)
    b = _gather_dequant(pool.k_pages, pool.k_scale, pool.k_zero, tab1, 6, 4)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    _check_invariants(pool)


def test_pool_rejects_unknown_quantize_mode():
    with pytest.raises(ValueError, match="quantize"):
        PagedKVPool(num_blocks=4, block_size=4, quantize="fp4")


# --------------------------------------------------------------------------- #
# Per-shard layout: the partitioned pool behind the sharded verifier
# --------------------------------------------------------------------------- #
def _mesh2():
    import jax

    if jax.device_count() < 2:
        pytest.skip("needs a multi-device host platform (conftest sets XLA_FLAGS)")
    from repro.sharding.shardctx import host_mesh

    return host_mesh(2)


def test_shard_metadata_even_uneven():
    even = PagedKVPool(num_blocks=4, block_size=4, n_layers=1, n_kv_heads=4, head_dim=2)
    assert even.shard_axes(1) and even.shard_axes(2) and even.shard_axes(4)
    assert not even.shard_axes(3)
    kspec, planes = even.shard_spec(2)
    assert tuple(kspec) == (None, None, None, "model", None)
    assert tuple(planes) == (None, None, None, "model")
    # Uneven head counts (and shards=1) replicate.
    assert tuple(even.shard_spec(3)[0]) == (None, None, None, None, None)
    assert tuple(even.shard_spec(1)[0]) == (None, None, None, None, None)
    with pytest.raises(ValueError, match="shards"):
        even.shard_axes(0)
    meta = PagedKVPool(num_blocks=4, block_size=4)  # metadata mode: no heads
    assert not meta.shard_axes(2)


def test_place_on_mesh_partitions_head_axis():
    """Each device holds only its Hkv/shards head slice of every page, the
    sentinel page included — so per-shard sentinel padding stays valid."""
    mesh = _mesh2()
    pool = PagedKVPool(num_blocks=4, block_size=4, n_layers=1, n_kv_heads=2, head_dim=2)
    pool.create(0)
    k = jnp.arange(1 * 6 * 2 * 2, dtype=jnp.float32).reshape(1, 6, 2, 2)
    pool.write(0, k, -k)
    host_before = np.asarray(pool.k_pages)
    spec = pool.place_on_mesh(mesh)
    assert tuple(spec) == (None, None, None, "model", None)
    shards = pool.k_pages.addressable_shards
    assert len(shards) == 2
    for i, sh in enumerate(shards):
        data = np.asarray(sh.data)
        assert data.shape == (1, pool.num_blocks + 1, 4, 1, 2)  # half the heads
        np.testing.assert_array_equal(data[..., 0, :], host_before[..., i, :])
        assert not data[:, pool.sentinel_page].any()  # sentinel zero per shard
    # Values round-trip unchanged through the placement.
    np.testing.assert_array_equal(np.asarray(pool.k_pages), host_before)


def test_place_on_mesh_uneven_heads_replicates():
    mesh = _mesh2()
    pool = PagedKVPool(num_blocks=4, block_size=4, n_layers=1, n_kv_heads=3, head_dim=2)
    spec = pool.place_on_mesh(mesh)
    assert tuple(spec) == (None, None, None, None, None)
    for sh in pool.k_pages.addressable_shards:
        assert sh.data.shape == pool.k_pages.shape  # full copy per device


def test_place_on_mesh_metadata_pool_is_noop():
    mesh = _mesh2()
    pool = PagedKVPool(num_blocks=4, block_size=4)
    assert pool.place_on_mesh(mesh) is not None and pool.k_pages is None


def test_sharded_pool_refcount_cow_rollback_invariants():
    """The metadata machine is untouched by placement: fork/CoW/rollback/
    evict keep every invariant, and fills after placement land sharded."""
    mesh = _mesh2()
    pool = PagedKVPool(num_blocks=8, block_size=4, n_layers=1, n_kv_heads=2, head_dim=2)
    pool.place_on_mesh(mesh)
    pool.create(0)
    k = jnp.ones((1, 6, 2, 2), jnp.float32)
    pool.write(0, k, -k)  # fill through the sharded buffers
    _check_invariants(pool)
    assert pool.filled(0) == 6
    pool.fork(0, 1)
    _check_invariants(pool)
    assert pool.filled(1) == 6  # watermark inherited under placement
    extra = jnp.full((1, 1, 2, 2), 2.0, jnp.float32)
    pool.write(1, extra, extra)  # CoW copy of the shared tail page
    _check_invariants(pool)
    assert pool.stats["cow_copies"] == 1
    assert pool.tables[0].blocks[-1] != pool.tables[1].blocks[-1]
    # Parent prefix readable and intact through the sharded buffers.
    page0 = np.asarray(pool.k_pages)[0, pool.tables[0].blocks[0]]
    np.testing.assert_array_equal(page0, np.ones((4, 2, 2), np.float32))
    n_freed = pool.rollback(0, 2)
    _check_invariants(pool)
    assert n_freed == 1 and pool.filled(0) == 2  # watermark clamped per shard
    pool.evict(1)
    _check_invariants(pool)
    assert pool.filled(1) == 0
    pool.release(0)
    _check_invariants(pool)


def test_resident_bytes_per_shard_tracks_lifecycle():
    """Per-shard footprint = resident_bytes/shards on an even split, and it
    moves with append/rollback exactly like the unsharded accounting."""
    pool = PagedKVPool(num_blocks=8, block_size=4, n_layers=1, n_kv_heads=2, head_dim=2)
    pool.create(0)
    pool.append(0, 10)  # 3 pages
    assert pool.resident_bytes_per_shard(1) == pool.resident_bytes()
    assert pool.resident_bytes_per_shard(2) == pool.resident_bytes() // 2
    before = pool.resident_bytes_per_shard(2)
    pool.rollback(0, 4)  # frees 2 pages
    after = pool.resident_bytes_per_shard(2)
    assert after == before - 2 * pool.bytes_per_block // 2
    # Uneven head counts replicate: each shard carries the full footprint.
    odd = PagedKVPool(num_blocks=8, block_size=4, n_layers=1, n_kv_heads=3, head_dim=2)
    odd.create(0)
    odd.append(0, 4)
    assert odd.resident_bytes_per_shard(2) == odd.resident_bytes()


def test_int8_quant_planes_shard_with_their_pages():
    mesh = _mesh2()
    rng = np.random.default_rng(3)
    pool = PagedKVPool(
        num_blocks=4, block_size=4, n_layers=1, n_kv_heads=2, head_dim=4,
        quantize="int8",
    )
    pool.create(0)
    k = jnp.asarray(rng.normal(size=(1, 6, 2, 4)), jnp.float32)
    pool.write(0, k, -k)
    planes_before = np.asarray(pool.k_scale)
    pool.place_on_mesh(mesh)
    for buf, want_heads in ((pool.k_pages, 1), (pool.k_scale, 1), (pool.v_zero, 1)):
        shards = buf.addressable_shards
        assert len(shards) == 2 and shards[0].data.shape[3] == want_heads
    np.testing.assert_array_equal(np.asarray(pool.k_scale), planes_before)
