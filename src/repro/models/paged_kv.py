"""Paged KV-cache: a global block pool with copy-on-write prefix sharing.

The flat ``KVCache`` (``models/kvcache.py``) allocates every session a
contiguous ``[L, 1, max_len, Hkv, hd]`` buffer, so verifier memory scales with
``sessions x max_len`` no matter how short the actual prefixes are.  This
module replaces that with the standard production layout (vLLM-style):

* **Physical pages.**  KV storage is a pool of ``num_blocks`` fixed-size
  pages of ``block_size`` token slots each; a page spans all layers
  (``k_pages/v_pages: [L, num_blocks + 1, block_size, Hkv, hd]``; the
  ``+ 1`` is the pad sentinel below).
* **Block tables.**  A session's logical cache is an ordered list of int32
  physical page ids plus a valid ``length``; logical position ``p`` lives in
  page ``table[p // block_size]`` at slot ``p % block_size``.  Attention
  kernels gather through the table (``kernels.decode_attention``'s paged
  entry) instead of assuming contiguity.
* **Copy-on-write prefix sharing.**  ``fork`` gives a child session the
  parent's page ids and bumps refcounts — sessions verified from a common
  system/prompt prefix reference the SAME physical pages.  The first append
  into a shared partial tail page copies just that page (``cow_copies``
  stat); full shared pages stay shared forever.
* **Refcounted free + LRU reuse.**  ``rollback`` (speculative-decoding
  rejection, tree ``replay_path`` anchor restore) releases whole pages past
  the committed length instead of deep-copying buffers; pages return to an
  LRU free list (oldest-freed reused first).  ``evict``/``evict_lru``
  reclaim idle sessions' pages under pool pressure (the victim re-prefills
  on its next round).
* **Sentinel pad page.**  Physical page id ``num_blocks`` (one past the
  allocatable pool) is a dedicated zero-filled page that is NEVER handed to
  a session: ragged block tables pad with it (``table(pad_to=...)``,
  ``sentinel_page``), so a padded lane in a bucketed batched launch can
  only ever DMA the sentinel — never another session's KV pages.  Tensor
  mode sizes the page buffers ``num_blocks + 1`` so the sentinel is a valid
  gather index; it is excluded from the free list, refcounts, and byte
  accounting.
* **Int8 quantized pages** (``quantize='int8'``).  Tensor-mode pages store
  KV as int8 with per-(layer, slot, head) affine parameters
  (``k_scale/k_zero`` etc., float32, shaped ``[L, num_blocks + 1,
  block_size, Hkv]``): ``write`` quantizes each token-head vector over its
  ``head_dim`` range (``x_hat = (q + 128) * scale + zero``, ``scale =
  (max - min) / 255``, ``zero = min``) and the paged attention kernels
  dequantize in-VMEM.  Worst-case per-element error is ``scale / 2 =
  (max - min) / 510``; bytes/token drop from ``2*L*Hkv*hd*4`` (fp32) to
  ``2*L*Hkv*(hd + 8)`` (int8 payload + two float32 parameters per
  token-head).

The pool runs in two modes: **metadata-only** (default — no tensor storage;
used by the serving dispatcher and the simulation engine for admission and
byte accounting) and **tensor mode** (``n_layers > 0`` — real jax page
buffers written through ``write`` and consumed by the paged attention
kernel).

Example (metadata mode; 4-token pages)::

    >>> pool = PagedKVPool(num_blocks=8, block_size=4)
    >>> pool.create(0)
    >>> pool.append(0, 6)        # 6 tokens -> 2 pages (one partial)
    >>> pool.used_blocks
    2
    >>> pool.fork(0, 1)          # CoW prefix share: no new pages
    >>> pool.used_blocks
    2
    >>> pool.append(1, 1)        # first write into the shared tail page
    >>> pool.used_blocks         # ... copies it (CoW divergence)
    3
    >>> pool.rollback(1, 2)      # reject back to 2 tokens: page freed
    1
    >>> pool.used_blocks
    2
    >>> pool.stats["cow_copies"]
    1
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.trace import NULL_TRACER

__all__ = ["BlockPoolExhausted", "BlockTable", "PagedKVPool"]


class BlockPoolExhausted(RuntimeError):
    """Raised when an allocation needs more physical pages than are free."""


@dataclass
class BlockTable:
    """Per-session page list + valid length (the logical->physical map)."""

    blocks: List[int] = field(default_factory=list)
    length: int = 0
    reserved: bool = False  # flat-mode contiguous reservation (no CoW/free)
    last_touch: int = 0  # pool clock at last append/rollback (LRU eviction key)
    # Materialized-KV watermark: positions [0, filled) hold real tensors
    # written through ``fill``/``write``.  Rollback lowers it (content past
    # the kept prefix is dead — and regrown slots may land in RECYCLED
    # physical pages holding another session's data), eviction zeroes it,
    # and it dies with the table on release, so tensor-filling backends can
    # trust it instead of tracking their own (see ``PagedKVPool.filled``).
    filled: int = 0

    def capacity(self, block_size: int) -> int:
        """Token slots currently backed by physical pages."""
        return len(self.blocks) * block_size


class PagedKVPool:
    """Global physical-page pool with per-session block tables.

    Parameters
    ----------
    num_blocks, block_size:
        Pool geometry — ``num_blocks`` pages of ``block_size`` token slots.
    n_layers, n_kv_heads, head_dim, dtype:
        Tensor mode: when ``n_layers > 0``, real page buffers
        ``k_pages/v_pages: [L, num_blocks + 1, block_size, Hkv, hd]`` are
        allocated (the extra page is the zero-filled pad sentinel) and
        ``write`` scatters tokens into them.  ``dtype`` is the storage dtype
        of unquantized pools; writes in any other float dtype are cast at
        the boundary so the page buffers (and the byte accounting derived
        from them) never change dtype behind the pool's back.
    quantize:
        ``'int8'`` stores pages as int8 with per-(layer, slot, head) affine
        scale/zero parameters (quantize-on-``write``, in-kernel dequant);
        ``None`` (default) stores ``dtype`` pages.
    bytes_per_token:
        Byte-accounting override for metadata mode.  Tensor mode derives it
        from the KV geometry (k+v); metadata mode defaults to 1 so
        ``resident_bytes`` counts token slots.
    """

    def __init__(
        self,
        num_blocks: int,
        block_size: int,
        *,
        n_layers: int = 0,
        n_kv_heads: int = 0,
        head_dim: int = 0,
        dtype=jnp.float32,
        quantize: Optional[str] = None,
        bytes_per_token: Optional[int] = None,
        metrics=None,
    ):
        if num_blocks < 1 or block_size < 1:
            raise ValueError("num_blocks and block_size must be >= 1")
        if quantize not in (None, "int8"):
            raise ValueError(f"unsupported quantize mode {quantize!r}")
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.quantize = quantize
        self.refcounts = np.zeros(self.num_blocks, np.int32)
        # LRU free list: freed pages append right, allocation pops left.
        self._free: Deque[int] = deque(range(self.num_blocks))
        self.tables: Dict[int, BlockTable] = {}
        self._clock = 0
        self._resident = 0  # sessions holding >=1 page, maintained incrementally
        # ``page_writes`` counts functional updates of a page buffer (k, v,
        # and the int8 planes): each copies the whole multi-layer buffer
        # unless XLA updates it in place.
        self.stats = {"allocs": 0, "frees": 0, "cow_copies": 0, "evictions": 0, "page_writes": 0}
        # Optional repro.obs.metrics.MetricRegistry: op counts are mirrored
        # into ``kv_<op>`` counters as they happen (stats stays the source
        # of truth; the mirror feeds the telemetry endpoint).
        self.metrics = metrics
        # repro.obs tracer for the ``kv.fill`` span; a CloudVerifier hands
        # its own to a pool that has none.
        self.tracer = NULL_TRACER
        # Host seconds spent in metadata mutations (append/rollback/fork/
        # reserve/evict) — the pool's entire latency cost on the serving
        # path, so benchmarks can bound the TPT impact of paging.
        self.op_seconds = 0.0
        self.max_used_blocks = 0
        self.max_resident_sessions = 0
        self.dtype = jnp.dtype(dtype)
        self.n_layers = int(n_layers)
        self.n_kv_heads = int(n_kv_heads)
        self.head_dim = int(head_dim)
        self.k_pages: Optional[jax.Array] = None
        self.v_pages: Optional[jax.Array] = None
        self.k_scale: Optional[jax.Array] = None
        self.k_zero: Optional[jax.Array] = None
        self.v_scale: Optional[jax.Array] = None
        self.v_zero: Optional[jax.Array] = None
        if n_layers > 0:
            # One extra physical page: the zero-filled pad sentinel at id
            # ``num_blocks``, a valid gather target that no session owns.
            shape = (n_layers, self.num_blocks + 1, self.block_size, n_kv_heads, head_dim)
            if self.quantize == "int8":
                self.k_pages = jnp.zeros(shape, jnp.int8)
                self.v_pages = jnp.zeros(shape, jnp.int8)
                pshape = shape[:-1]
                self.k_scale = jnp.zeros(pshape, jnp.float32)
                self.k_zero = jnp.zeros(pshape, jnp.float32)
                self.v_scale = jnp.zeros(pshape, jnp.float32)
                self.v_zero = jnp.zeros(pshape, jnp.float32)
                # int8 payload + (scale, zero) float32 per token-head, k + v.
                self.bytes_per_token = 2 * n_layers * n_kv_heads * (head_dim + 8)
            else:
                self.k_pages = jnp.zeros(shape, self.dtype)
                self.v_pages = jnp.zeros(shape, self.dtype)
                self.bytes_per_token = 2 * n_layers * n_kv_heads * head_dim * self.dtype.itemsize
        else:
            self.bytes_per_token = int(bytes_per_token) if bytes_per_token else 1
        self.bytes_per_block = self.bytes_per_token * self.block_size

    # ------------------------------------------------------------ geometry --
    @property
    def sentinel_page(self) -> int:
        """The zero-filled pad page id (``num_blocks``) — never allocated.

        Ragged block tables pad with this id so padded lanes in a bucketed
        batched launch can never DMA a page owned by a session.  Tensor mode
        sizes the page buffers ``num_blocks + 1`` so it is a valid index;
        external page buffers consumed through sentinel-padded tables must
        match that ``num_blocks + 1`` sizing (see ``table``).
        """
        return self.num_blocks

    @property
    def free_blocks(self) -> int:
        """Pages currently on the free list."""
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        """Distinct pages referenced by at least one session."""
        return self.num_blocks - len(self._free)

    @property
    def resident_sessions(self) -> int:
        """Sessions currently holding at least one page (O(1) counter)."""
        return self._resident

    def blocks_for(self, n_tokens: int) -> int:
        """Pages needed to back ``n_tokens`` from an empty table."""
        return -(-max(int(n_tokens), 0) // self.block_size)

    def blocks_needed(self, session: int, n_tokens: int) -> int:
        """Fresh pages an ``append(session, n_tokens)`` would allocate.

        Counts the CoW copy of a shared partial tail page, so admission
        control can gate on the exact allocation the append will perform.
        """
        t = self._table(session)
        need = self.blocks_for(t.length + n_tokens) - len(t.blocks)
        if n_tokens > 0 and self._tail_is_shared(t):
            need += 1  # the append CoW-copies the shared tail page
        return max(need, 0)

    def can_append(self, session: int, n_tokens: int) -> bool:
        """True iff ``append(session, n_tokens)`` would not exhaust the pool."""
        t = self._table(session)
        if t.reserved:
            return t.length + int(n_tokens) <= t.capacity(self.block_size)
        return self.blocks_needed(session, n_tokens) <= self.free_blocks

    # ---------------------------------------------------------- allocation --
    def _table(self, session: int) -> BlockTable:
        if session not in self.tables:
            raise KeyError(f"unknown session {session}")
        return self.tables[session]

    def _tail_is_shared(self, t: BlockTable) -> bool:
        if t.reserved or not t.blocks or t.length % self.block_size == 0:
            return False  # no partial tail page to write into
        return int(self.refcounts[t.blocks[-1]]) > 1

    def _count(self, op: str, n: int = 1) -> None:
        self.stats[op] += n
        if self.metrics is not None:
            self.metrics.counter(f"kv_{op}", "Paged-KV pool page operations").inc(n)

    def _alloc_page(self) -> int:
        if not self._free:
            raise BlockPoolExhausted(f"pool of {self.num_blocks} pages exhausted")
        page = self._free.popleft()
        self.refcounts[page] = 1
        self._count("allocs")
        return page

    def _decref(self, page: int) -> None:
        self.refcounts[page] -= 1
        if self.refcounts[page] == 0:
            self._free.append(page)  # LRU: most recently freed goes last
            self._count("frees")

    def _touch(self, t: BlockTable) -> None:
        self._clock += 1
        t.last_touch = self._clock
        self.max_used_blocks = max(self.max_used_blocks, self.used_blocks)
        self.max_resident_sessions = max(self.max_resident_sessions, self.resident_sessions)

    def create(self, session: int) -> None:
        """Register an empty session (no pages held until ``append``)."""
        if session in self.tables:
            raise ValueError(f"session {session} already exists")
        self.tables[session] = BlockTable()

    def fork(self, parent: int, child: int) -> None:
        """Copy-on-write fork: ``child`` shares all of ``parent``'s pages.

        No pages are allocated; every shared page's refcount is bumped.  The
        first append into a shared *partial* tail page copies it (see
        ``append``); full shared pages are never copied.
        """
        t0 = time.perf_counter()
        p = self._table(parent)
        if child in self.tables:
            raise ValueError(f"session {child} already exists")
        # The child sees the parent's physical pages, so whatever prefix the
        # parent materialized is materialized for the child too.
        self.tables[child] = BlockTable(
            blocks=list(p.blocks), length=p.length, filled=p.filled
        )
        for page in p.blocks:
            self.refcounts[page] += 1
        if p.blocks:
            self._resident += 1
        self._touch(self.tables[child])
        self.op_seconds += time.perf_counter() - t0

    def reserve(self, session: int, max_tokens: int) -> None:
        """Flat-mode baseline: contiguously reserve pages for ``max_tokens``.

        Models the flat ``KVCache``'s up-front ``max_len`` allocation inside
        the same pool accounting, so flat-vs-paged residency is an
        apples-to-apples comparison.  Reserved tables never share, CoW, or
        release pages on rollback — exactly the flat cache's behaviour.
        """
        t0 = time.perf_counter()
        t = self._table(session)
        if t.blocks:
            raise ValueError(f"session {session} already holds pages")
        need = self.blocks_for(max_tokens)
        if need > self.free_blocks:
            raise BlockPoolExhausted(
                f"flat reservation of {need} pages exceeds {self.free_blocks} free"
            )
        t.blocks = [self._alloc_page() for _ in range(need)]
        t.reserved = True
        if t.blocks:
            self._resident += 1
        self._touch(t)
        self.op_seconds += time.perf_counter() - t0

    def append(self, session: int, n_tokens: int) -> None:
        """Extend a session by ``n_tokens`` slots, allocating pages on demand.

        If the session's tail page is partial *and* shared (post-``fork``),
        the tail is first copied to a fresh page — copy-on-write divergence:
        the writer pays one page copy, the other holders keep the original.
        Raises ``BlockPoolExhausted`` (leaving the table untouched) when the
        pool cannot back the growth; callers park or evict and retry.
        """
        t0 = time.perf_counter()
        t = self._table(session)
        n_tokens = int(n_tokens)
        if n_tokens <= 0:
            return
        if t.reserved:
            if t.length + n_tokens > t.capacity(self.block_size):
                raise BlockPoolExhausted(
                    f"flat reservation of session {session} overflows at "
                    f"{t.length + n_tokens} tokens"
                )
            t.length += n_tokens
            self._touch(t)
            self.op_seconds += time.perf_counter() - t0
            return
        if self.blocks_needed(session, n_tokens) > self.free_blocks:
            raise BlockPoolExhausted(
                f"append of {n_tokens} tokens needs "
                f"{self.blocks_needed(session, n_tokens)} pages, "
                f"{self.free_blocks} free"
            )
        if self._tail_is_shared(t):
            old = t.blocks[-1]
            new = self._alloc_page()
            self._copy_page(old, new)
            self._count("cow_copies")
            t.blocks[-1] = new
            self._decref(old)
        had_pages = bool(t.blocks)
        while t.capacity(self.block_size) < t.length + n_tokens:
            t.blocks.append(self._alloc_page())
        if not had_pages and t.blocks:
            self._resident += 1
        t.length += n_tokens
        self._touch(t)
        self.op_seconds += time.perf_counter() - t0

    def rollback(self, session: int, new_length: int) -> int:
        """Truncate to ``new_length`` tokens, releasing whole trailing pages.

        The speculative-decoding rejection path: instead of deep-copying
        buffers, pages wholly past the committed prefix are decref'd (and
        freed when unshared).  Returns the number of pages this session
        dropped.  Reserved (flat) tables only move the length — the flat
        cache never returns memory.
        """
        t0 = time.perf_counter()
        t = self._table(session)
        new_length = int(new_length)
        if new_length > t.length:
            raise ValueError(f"rollback to {new_length} > current length {t.length}")
        t.length = new_length
        # Tensors past the kept prefix are dead: the rejected round's KV must
        # never be trusted again, and slots regrown after this rollback may
        # land in recycled physical pages holding another session's data.
        t.filled = min(t.filled, new_length)
        if t.reserved:
            self._touch(t)
            self.op_seconds += time.perf_counter() - t0
            return 0
        keep = self.blocks_for(new_length)
        dropped = t.blocks[keep:]
        t.blocks = t.blocks[:keep]
        for page in reversed(dropped):
            self._decref(page)
        if dropped and not t.blocks:
            self._resident -= 1
        self._touch(t)
        self.op_seconds += time.perf_counter() - t0
        return len(dropped)

    def release(self, session: int) -> None:
        """Drop a session entirely, decref'ing every page it held."""
        t = self._table(session)
        for page in reversed(t.blocks):
            self._decref(page)
        if t.blocks:
            self._resident -= 1
        del self.tables[session]

    def evict(self, session: int) -> int:
        """Reclaim a session's pages under pool pressure (it re-prefills later).

        The session stays registered with ``length = 0`` so its next round
        starts from an empty cache.  Returns the pages released.
        """
        t0 = time.perf_counter()
        t = self._table(session)
        dropped = len(t.blocks)
        for page in reversed(t.blocks):
            self._decref(page)
        if t.blocks:
            self._resident -= 1
        t.blocks = []
        t.length = 0
        t.filled = 0  # every materialized tensor went back with the pages
        t.reserved = False
        self._count("evictions")
        self.op_seconds += time.perf_counter() - t0
        return dropped

    def evict_lru(self, exclude: Sequence[int] = ()) -> Optional[int]:
        """Evict the least-recently-touched page-holding session not excluded.

        Returns the victim's id, or None when every resident session is
        excluded (nothing safe to reclaim).
        """
        skip = set(exclude)
        victims = [
            (t.last_touch, sid)
            for sid, t in self.tables.items()
            if t.blocks and sid not in skip
        ]
        if not victims:
            return None
        _, sid = min(victims)
        self.evict(sid)
        return sid

    # ------------------------------------------------------------- tensors --
    def _copy_page(self, src: int, dst: int) -> None:
        if self.k_pages is not None:
            self.k_pages = self.k_pages.at[:, dst].set(self.k_pages[:, src])
            self.v_pages = self.v_pages.at[:, dst].set(self.v_pages[:, src])
            if self.quantize == "int8":
                self.k_scale = self.k_scale.at[:, dst].set(self.k_scale[:, src])
                self.k_zero = self.k_zero.at[:, dst].set(self.k_zero[:, src])
                self.v_scale = self.v_scale.at[:, dst].set(self.v_scale[:, src])
                self.v_zero = self.v_zero.at[:, dst].set(self.v_zero[:, src])
            self._count("page_writes", 6 if self.quantize == "int8" else 2)

    @staticmethod
    def quantize_kv(x: jax.Array):
        """Affine-int8 quantize ``x`` over its last axis.

        Returns ``(q int8, scale f32, zero f32)`` with ``scale/zero`` shaped
        like ``x`` minus the last axis: ``x_hat = (q + 128) * scale + zero``,
        ``scale = (max - min) / 255`` (1 when the range is empty) and
        ``zero = min``.  Worst-case per-element error is ``scale / 2``.
        """
        x = x.astype(jnp.float32)
        lo = jnp.min(x, axis=-1)
        hi = jnp.max(x, axis=-1)
        scale = jnp.where(hi > lo, (hi - lo) / 255.0, 1.0)
        q = jnp.round((x - lo[..., None]) / scale[..., None]) - 128.0
        return jnp.clip(q, -128, 127).astype(jnp.int8), scale, lo

    @staticmethod
    def dequantize_kv(q: jax.Array, scale: jax.Array, zero: jax.Array) -> jax.Array:
        """Invert ``quantize_kv``: ``(q + 128) * scale + zero`` in float32."""
        return (q.astype(jnp.float32) + 128.0) * scale[..., None] + zero[..., None]

    def _check_write_dtype(self, k_new: jax.Array, v_new: jax.Array):
        """Validate/cast incoming KV at the pool boundary.

        JAX's scatter would otherwise silently cast mismatched dtypes lane
        by lane (a ``FutureWarning`` today, an error in future releases) —
        and a caller assuming the pages follow the operand dtype would see
        ``resident_bytes`` accounting drift from the true footprint.  The
        pool's storage dtype is authoritative: floats cast here, explicitly;
        anything non-float is rejected.
        """
        if k_new.dtype != v_new.dtype:
            raise TypeError(f"k/v dtype mismatch: {k_new.dtype} vs {v_new.dtype}")
        if not jnp.issubdtype(k_new.dtype, jnp.floating):
            raise TypeError(f"KV writes must be floating point, got {k_new.dtype}")
        want = jnp.float32 if self.quantize == "int8" else self.dtype
        return k_new.astype(want), v_new.astype(want)

    def write(self, session: int, k_new: jax.Array, v_new: jax.Array) -> None:
        """Append ``T`` tokens of KV (``[L, T, Hkv, hd]``) into the pages.

        Tensor mode only.  Handles page allocation + CoW via ``append``;
        tokens scatter into (page, slot) per the block table.  Writes whose
        dtype differs from the pool's storage dtype are cast here, at the
        boundary (see ``_check_write_dtype``); int8 pools quantize each
        token-head vector and store its scale/zero alongside the payload.
        """
        start = self._table(session).length
        self.append(session, k_new.shape[1])
        self.fill(session, start, k_new, v_new)

    def fill(self, session: int, start: int, k_new: jax.Array, v_new: jax.Array) -> None:
        """Write ``T`` tokens of KV into ALREADY-APPENDED slots at ``start``.

        The dispatcher path: ``_kv_secure`` appends a round's page metadata
        before verification, then the backend materializes tensors here
        without double-appending.  Same boundary dtype validation and int8
        quantize-on-write as ``write``.

        A target page shared with another session (refcount > 1, post
        ``fork``) is CoW-copied first, exactly like ``append`` — writing
        through it in place would mutate every sibling's view.  The copy can
        raise ``BlockPoolExhausted``; callers that must not diverge shared
        prefix pages should materialize the prefix on its OWNER before
        forking, so children inherit the ``filled`` watermark and never
        fill shared slots.

        Advances the session's materialized watermark (``filled``) when the
        write extends the contiguous materialized prefix.  Traced as
        ``kv.fill``.
        """
        with self.tracer.span("kv.fill"):
            self._fill(session, start, k_new, v_new)

    def _fill(self, session: int, start: int, k_new: jax.Array, v_new: jax.Array) -> None:
        if self.k_pages is None:
            raise RuntimeError("pool was built without tensor storage (n_layers=0)")
        k_new, v_new = self._check_write_dtype(k_new, v_new)
        if self.quantize == "int8":
            k_new, k_sc, k_zp = self.quantize_kv(k_new)
            v_new, v_sc, v_zp = self.quantize_kv(v_new)
        t = self._table(session)
        T = k_new.shape[1]
        if start < 0 or start + T > t.length:
            raise ValueError(
                f"fill [{start}, {start + T}) outside the session's {t.length} slots"
            )
        written = 0
        while written < T:
            pos = start + written
            bi = pos // self.block_size
            page = t.blocks[bi]
            if not t.reserved and int(self.refcounts[page]) > 1:
                new = self._alloc_page()
                self._copy_page(page, new)
                self._count("cow_copies")
                t.blocks[bi] = new
                self._decref(page)
                page = new
            slot = pos % self.block_size
            take = min(self.block_size - slot, T - written)
            ksl = jax.lax.dynamic_slice_in_dim(k_new, written, take, axis=1)
            vsl = jax.lax.dynamic_slice_in_dim(v_new, written, take, axis=1)
            self.k_pages = self.k_pages.at[:, page, slot : slot + take].set(ksl)
            self.v_pages = self.v_pages.at[:, page, slot : slot + take].set(vsl)
            if self.quantize == "int8":
                sl = slice(slot, slot + take)
                for pages, new in (
                    ("k_scale", k_sc), ("k_zero", k_zp), ("v_scale", v_sc), ("v_zero", v_zp),
                ):
                    cut = jax.lax.dynamic_slice_in_dim(new, written, take, axis=1)
                    setattr(self, pages, getattr(self, pages).at[:, page, sl].set(cut))
            self._count("page_writes", 6 if self.quantize == "int8" else 2)
            written += take
        if start <= t.filled:  # gap-free writes extend the materialized prefix
            t.filled = max(t.filled, start + T)

    # ------------------------------------------------------------- sharding --
    def shard_axes(self, shards: int) -> bool:
        """True iff the pool's KV head axis splits evenly over ``shards``.

        The divisibility gate for the tensor-parallel verifier: an even
        split stores ``Hkv / shards`` heads per device; an uneven one
        replicates the pages (the sharded launch still pads the GQA-expanded
        query heads, so correctness never depends on this answer).
        """
        if shards < 1:
            raise ValueError(f"shards must be >= 1, got {shards}")
        return self.n_kv_heads > 0 and self.n_kv_heads % shards == 0

    def shard_spec(self, shards: int, axis: str = "model"):
        """PartitionSpecs for the page buffers on a 1-D ``(axis,)`` mesh.

        Returns ``(pages_spec, planes_spec)`` — for the
        ``[L, num_blocks + 1, bs, Hkv, hd]`` payload buffers and the int8
        ``[L, num_blocks + 1, bs, Hkv]`` scale/zero planes.  The head axis is
        sharded only when it divides evenly (``shard_axes``); otherwise both
        specs replicate.  Block-table metadata stays host-side and is
        replicated to every device at launch (per-device block tables), so
        the sentinel page — the last page of every buffer — exists in each
        shard's local head slice and the pad contract holds per shard.
        """
        if self.shard_axes(shards) and shards > 1:
            from jax.sharding import PartitionSpec as P

            return P(None, None, None, axis, None), P(None, None, None, axis)
        from jax.sharding import PartitionSpec as P

        return P(None, None, None, None, None), P(None, None, None, None)

    def place_on_mesh(self, mesh, axis: str = "model"):
        """Lay the tensor-mode page buffers out over ``mesh`` (head axis).

        ``device_put``s ``k_pages``/``v_pages`` (and the int8 scale/zero
        planes) with the ``shard_spec`` layout, so each device holds only
        its ``Hkv / shards`` head slice of every physical page — the
        partitioned-pool state the sharded verify launch consumes.  Returns
        the pages spec used.  Metadata mode is a no-op (there is nothing to
        place); uneven head counts replicate, as per ``shard_spec``.
        """
        from jax.sharding import NamedSharding

        shards = int(np.prod(list(mesh.shape.values())))
        pages_spec, planes_spec = self.shard_spec(shards, axis=axis)
        if self.k_pages is None:
            return pages_spec
        pages_sh = NamedSharding(mesh, pages_spec)
        planes_sh = NamedSharding(mesh, planes_spec)
        self.k_pages = jax.device_put(self.k_pages, pages_sh)
        self.v_pages = jax.device_put(self.v_pages, pages_sh)
        if self.quantize == "int8":
            self.k_scale = jax.device_put(self.k_scale, planes_sh)
            self.k_zero = jax.device_put(self.k_zero, planes_sh)
            self.v_scale = jax.device_put(self.v_scale, planes_sh)
            self.v_zero = jax.device_put(self.v_zero, planes_sh)
        return pages_spec

    def resident_bytes_per_shard(self, shards: int) -> int:
        """Bytes of in-use pages RESIDENT ON EACH DEVICE at ``shards`` shards.

        With an even head split every page's payload (and its int8 quant
        planes, which shard with their KV) divides by ``shards``; an uneven
        split replicates, so each shard carries the full footprint.  At
        ``shards=1`` this equals ``resident_bytes()``.
        """
        total = self.resident_bytes()
        if self.shard_axes(shards):
            return total // shards
        return total

    def tensor_nbytes(self) -> int:
        """Actual bytes held by ALL page buffers (payload + quant params).

        Always ``(num_blocks + 1) * bytes_per_block`` in tensor mode — the
        invariant that pins the byte accounting to the real buffer
        footprint (``tests/test_paged_kv.py``).  Metadata mode returns 0.
        """
        bufs = (self.k_pages, self.v_pages, self.k_scale, self.k_zero,
                self.v_scale, self.v_zero)
        return sum(b.nbytes for b in bufs if b is not None)

    # ----------------------------------------------------------- reporting --
    def table(
        self, session: int, pad_to: Optional[int] = None, pad_id: Optional[int] = None
    ) -> np.ndarray:
        """The session's block table as int32, optionally padded to ``pad_to``.

        Pad entries carry ``pad_id``, defaulting to ``sentinel_page`` — the
        zero-filled page no session can own, so padded lanes never prefetch
        another session's KV even before length masking applies (see
        ``docs/kernels.md``).

        The sentinel id is ``num_blocks``, one past the allocatable pool:
        it indexes the pool's own ``num_blocks + 1``-page tensor buffers,
        but any EXTERNAL page buffer gathered through a sentinel-padded
        table (a ``batched_logits_fn`` consumer's arrays, or any buffer
        paired with a metadata-mode pool, which has no tensor storage of
        its own) must likewise be sized ``num_blocks + 1`` with a zeroed
        last page — a strict gather otherwise indexes out of bounds (and
        ``jnp`` indexing silently clamps to the last live page).  Callers
        that cannot resize their buffers must pass an in-range ``pad_id``
        explicitly.
        """
        t = self._table(session)
        ids = t.blocks
        if pad_to is not None:
            if len(ids) > pad_to:
                raise ValueError(f"table of {len(ids)} pages exceeds pad_to={pad_to}")
            fill = self.sentinel_page if pad_id is None else pad_id
            ids = ids + [fill] * (pad_to - len(ids))
        return np.asarray(ids, np.int32)

    def length(self, session: int) -> int:
        """The session's committed token count."""
        return self._table(session).length

    def filled(self, session: int) -> int:
        """Positions ``[0, filled)`` hold materialized tensors (tensor mode).

        The watermark tensor-filling backends must refill from: ``fill``
        advances it, ``rollback`` lowers it past rejected (and possibly
        recycled) slots, ``evict`` zeroes it, and it dies with the table on
        ``release`` — so a reused session id never inherits a dead
        session's watermark.
        """
        return self._table(session).filled

    def shared_blocks(self) -> int:
        """Distinct pages referenced by more than one session."""
        return int(np.sum(self.refcounts > 1))

    def resident_bytes(self) -> int:
        """Bytes backing all distinct in-use pages (sharing counted once)."""
        return self.used_blocks * self.bytes_per_block

    def resident_bytes_for(self, session: int) -> int:
        """Bytes of pages this session references (shared pages counted fully).

        Summing this over sessions exceeds ``resident_bytes()`` exactly by
        the prefix-sharing win.
        """
        return len(self._table(session).blocks) * self.bytes_per_block

    def load_summary(self) -> dict:
        """Point-in-time pool metrics for benchmarks and the serving monitor."""
        n_resident = self.resident_sessions
        return dict(
            kv_used_blocks=self.used_blocks,
            kv_free_blocks=self.free_blocks,
            kv_resident_bytes=self.resident_bytes(),
            kv_bytes_per_session=(self.resident_bytes() / n_resident if n_resident else 0.0),
            kv_shared_blocks=self.shared_blocks(),
            kv_resident_sessions=n_resident,
            kv_max_resident_sessions=self.max_resident_sessions,
            kv_max_used_blocks=self.max_used_blocks,
            kv_cow_copies=self.stats["cow_copies"],
            kv_evictions=self.stats["evictions"],
            kv_op_seconds=self.op_seconds,
        )
