"""Cloud verifier service (the paper's FastAPI server, §4.2, App. I).

A continuous-batching dispatcher serves any number of edge sessions
(beyond-paper optimization #5 — the cross-request analogue of the paper's
§3.2 resource-utilization argument, in the spirit of FlowSpec/DiP-SD):

* buffers draft tokens per session as batches stream in (pipelined upload);
* a NAV request whose tokens are not all buffered yet is parked on the
  session and dispatched the moment the remaining proactively-uploaded
  drafts arrive;
* requests that arrive within ``batch_window`` of each other coalesce into
  ONE padded backend call (``verify_batch``), amortizing the target forward
  across clients — the batched path runs through
  ``kernels.spec_verify.spec_verify_batched`` when a JAX backend is used;
* admission control: at most ``max_batch`` requests per backend call, with
  **fair reinsertion** — when oversubscribed, the least-recently-served
  sessions go first, so long-draft sessions cannot starve short ones;
* straggler mitigation: requests carry client deadlines; work whose deadline
  has already passed (the client has failed over to local decoding) and work
  for sessions that disconnected is dropped, not verified;
* tree speculation: a ``TreeNavRequest`` round's draft fragments carry packed
  tree parents alongside their tokens; tree requests ride the same buffers,
  admission control, and coalescing window as chains, and are padded by NODE
  count through ``spec_verify_tree_batched`` (one ancestor-masked launch per
  dispatch).  Results additionally carry the accepted root→leaf ``path``;
* paged target KV (``kv_pool``): the verifier's per-session cache state
  lives in a ``models.paged_kv.PagedKVPool`` — sessions fork from a shared
  system-prefix session copy-on-write, each verify appends the round's
  ``K+1`` positions and the rejection rollback releases whole pages back to
  the pool.  Admission is additionally gated on the free-block budget: a
  request whose KV growth the pool cannot back first tries to reclaim pages
  from the least-recently-active idle session (``evict_lru``), then parks
  back at the queue head (``kv_parked`` stat) until rollbacks free pages.

Per-dispatch batch size, queue depth, and KV-pool residency are fed to an
``EnvironmentMonitor`` (core.monitor) so benchmarks can lift verifier
occupancy/queue-depth/KV-residency into ``RunStats`` (core.pipeline).

The backend is pluggable: ``SyntheticBackend`` (trace-driven acceptance, used
by benchmarks), or ``SpecVerifyBackend`` running the real fused NAV kernel
(Pallas on TPU, pure-JAX ``ref`` on CPU), optionally with a batched paged
target forward (``batched_logits_fn`` + the sessions' KV block tables).
"""

from __future__ import annotations

import threading
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Deque, Dict, List, Optional, Sequence, Set, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.monitor import EnvironmentMonitor
from repro.kernels.spec_verify.kernel import DEFAULT_BV
from repro.kernels.spec_verify.ops import batch_rows
from repro.models.paged_kv import BlockPoolExhausted, PagedKVPool
from repro.obs.trace import NULL_TRACER
from .protocol import (
    Detach,
    DraftFragment,
    Drain,
    Hello,
    NavRequest,
    NavResult,
    Reset,
    TelemetryRequest,
    TelemetrySnapshot,
    TreeNavRequest,
    handshake_reply,
)
from .simclock import SYSTEM_CLOCK
from .transport import Transport

__all__ = [
    "VerifyBackend",
    "SyntheticBackend",
    "SpecVerifyBackend",
    "ShardedSpecVerifyBackend",
    "CloudVerifier",
    "VerifierDraining",
]


class VerifierDraining(RuntimeError):
    """Raised by ``CloudVerifier.attach`` when the verifier is draining."""


class VerifyBackend:
    """Interface: verify a session's drafted tokens → (n_accepted, correction)."""

    #: Positional backends are stateless: the dispatcher routes them through
    #: ``verify_batch_pos`` with the stream position each NAV request carries.
    positional: bool = False

    def verify(self, session: int, tokens: List[int], confs: List[float]):  # pragma: no cover
        """Verify one session's chain drafts → ``(n_accepted, correction)``."""
        raise NotImplementedError

    def verify_batch(self, requests: Sequence[Tuple[int, List[int], List[float]]]):
        """Verify many sessions in one call; default loops over ``verify``."""
        return [self.verify(s, t, c) for (s, t, c) in requests]

    def verify_batch_pos(
        self, requests: Sequence[Tuple[int, List[int], List[float], Optional[int]]]
    ):  # pragma: no cover
        """Positional batch verify ``[(session, tokens, confs, pos)]``.

        Only meaningful on ``positional`` backends (``runtime.oracle``).
        """
        raise NotImplementedError

    def verify_tree(self, session: int, tokens: List[int], confs: List[float], parents: List[int]):
        """Tree request → (n_accepted, correction, path-node-indices)."""
        raise NotImplementedError  # pragma: no cover

    def verify_tree_batch(
        self, requests: Sequence[Tuple[int, List[int], List[float], List[int]]]
    ):
        """Verify many sessions' token trees; default loops over ``verify_tree``."""
        return [self.verify_tree(s, t, c, p) for (s, t, c, p) in requests]


@dataclass
class SyntheticBackend(VerifyBackend):
    """Acceptance ~ conf^kappa per token (matches core.pipeline.SyntheticSource).

    ``verify_batch`` models the batched target forward: ONE padded pass whose
    cost scales with the *longest* draft in the batch, not the sum — this is
    the amortization the continuous-batching dispatcher exists to exploit.
    """

    kappa: float = 0.8
    seed: int = 0
    verify_time: float = 0.080  # simulated target forward time [s]
    verify_time_per_token: float = 0.004
    time_scale: float = 1.0
    clock: Any = None  # simclock surface; None -> SYSTEM_CLOCK

    def __post_init__(self) -> None:
        self._rng = np.random.default_rng(self.seed)
        if self.clock is None:
            self.clock = SYSTEM_CLOCK

    def _accept(self, confs: List[float]) -> Tuple[int, int]:
        n_acc = 0
        for c in confs:
            if self._rng.random() < c**self.kappa:
                n_acc += 1
            else:
                break
        correction = int(self._rng.integers(0, 1 << 16))
        return n_acc, correction

    def verify(self, session: int, tokens: List[int], confs: List[float]):
        """One simulated target forward for one session's chain drafts."""
        self.clock.sleep((self.verify_time + self.verify_time_per_token * len(tokens)) * self.time_scale)
        return self._accept(confs)

    def verify_batch(self, requests):
        """One padded pass: cost scales with the longest draft, not the sum."""
        if not requests:
            return []
        max_len = max(len(t) for (_, t, _) in requests)
        self.clock.sleep((self.verify_time + self.verify_time_per_token * max_len) * self.time_scale)
        return [self._accept(c) for (_, _, c) in requests]

    def _accept_tree(self, confs: List[float], parents: List[int]) -> Tuple[int, int, List[int]]:
        """Per-node accept draw w.p. conf^kappa, conditioned on the parent.

        The accepted path is the deepest chain of accepting nodes; siblings
        are tried in packed order, so the tree wins whenever ANY branch at a
        level accepts — the accepted-tokens-per-NAV edge over a chain.
        """
        n = len(confs)
        children: List[List[int]] = [[] for _ in range(n + 1)]
        for i, p in enumerate(parents):
            children[p + 1].append(i)
        path: List[int] = []
        cur = 0  # anchor
        while True:
            nxt = None
            for c in children[cur]:
                if self._rng.random() < confs[c] ** self.kappa:
                    nxt = c
                    break
            if nxt is None:
                break
            path.append(nxt)
            cur = nxt + 1
        correction = int(self._rng.integers(0, 1 << 16))
        return len(path), correction, path

    def verify_tree(self, session, tokens, confs, parents):
        """One simulated tree-NAV call (cost scales with the node count)."""
        self.clock.sleep((self.verify_time + self.verify_time_per_token * len(tokens)) * self.time_scale)
        return self._accept_tree(confs, parents)

    def verify_tree_batch(self, requests):
        """One padded tree pass: cost scales with the largest node count."""
        if not requests:
            return []
        max_len = max(len(t) for (_, t, _, _) in requests)
        self.clock.sleep((self.verify_time + self.verify_time_per_token * max_len) * self.time_scale)
        return [self._accept_tree(c, p) for (_, _, c, p) in requests]


# Kernel implementations a verify backend can be asked for (see docs/kernels.md).
IMPLS = ("pallas", "interpret", "ref")


class SpecVerifyBackend(VerifyBackend):
    """Real NAV verification through the fused spec_verify kernel.

    ``logits_fn(session, tokens) -> [len(tokens)+1, V]`` produces the target
    logits for one session (a model forward in a real deployment, a seeded
    synthetic sampler in tests).  ``verify_batch`` pads the ragged requests
    and runs them through ``spec_verify_batched`` in ONE launch.  ``impl``
    is the caller's choice and is never changed behind its back: the
    compiled Pallas kernel on a TPU (``'pallas'``), the same kernel under
    the CPU interpreter (``'interpret'``), or the pure-JAX oracle
    (``'ref'``).

    **Paged target forward.**  With ``batched_logits_fn`` (and a ``kv_pool``
    supplying per-session KV block tables) the per-session ``logits_fn``
    calls are replaced by ONE batched forward over the padded
    ``(tokens, n_drafted, block_tables)`` arrays — the fused
    paged-attention + NAV dispatch shape a production verifier compiles
    (see ``kernels.spec_verify.spec_verify_batched``).  Ragged tables pad
    with the pool's zero-filled sentinel page (id ``num_blocks``), so a
    padded lane can never prefetch KV owned by another session — a
    ``batched_logits_fn`` gathering from its OWN page buffers must size
    them ``num_blocks + 1`` with a zeroed last page to honour that pad id
    (see ``PagedKVPool.table``).

    **Fused one-launch verify** (``fused=True``).  Requires a TENSOR-mode
    ``kv_pool``, a ``query_fn(session, tokens) -> [K+1, H, hd]`` producing
    the target's per-position queries, and ``lm_head [H*hd, V]``: chain
    rounds then run ``spec_verify_fused_batched`` — paged attention over
    the sessions' block tables + LM-head projection + NAV scan in ONE
    Pallas launch instead of forward-then-verify.  The round's KV slots
    (metadata-appended by the dispatcher's ``_kv_secure``) are materialized
    through ``kv_fn(session, start, count) -> (k, v)`` just before the
    launch, from the pool's per-session ``filled`` watermark (``ensure_kv``)
    — so slots regrown after a rollback or eviction are always refilled,
    never trusted to still hold this session's tensors.  The default
    ``kv_fn`` synthesizes deterministic position-keyed tensors, so
    re-prefills reproduce the original values bit-for-bit.  A shared-prefix
    ``CloudVerifier`` materializes the prefix ONCE on its owner session
    before any fork; children inherit the watermark and never fill shared
    pages (``PagedKVPool.fill`` would CoW-copy them, forfeiting the
    sharing).  An int8 pool (``quantize='int8'``) is picked up
    automatically — the launch dequantizes pages in-kernel.

    ``tracer`` (a ``repro.obs`` tracer, ``NULL_TRACER`` unless a
    ``CloudVerifier`` hands down its own) times one ``query`` per session
    in the fused path's input assembly, and each ``kv_fn`` call of
    ``ensure_kv`` as ``kv.synth``.  ``stats["kernel_rows"]`` adds up the
    padded batch of each fused launch, pad rows included.
    """

    def __init__(
        self,
        logits_fn: Optional[Callable] = None,
        *,
        impl: str,
        block_v: int = DEFAULT_BV,
        kv_pool: Optional[PagedKVPool] = None,
        batched_logits_fn: Optional[Callable] = None,
        batched_tree_logits_fn: Optional[Callable] = None,
        fused: bool = False,
        query_fn: Optional[Callable] = None,
        lm_head: Optional[Any] = None,
        kv_fn: Optional[Callable] = None,
    ):
        if fused:
            if kv_pool is None or kv_pool.k_pages is None:
                raise ValueError("fused=True needs a tensor-mode kv_pool")
            if query_fn is None or lm_head is None:
                raise ValueError("fused=True needs query_fn and lm_head")
        elif logits_fn is None and batched_logits_fn is None:
            raise ValueError("need logits_fn or batched_logits_fn")
        if impl not in IMPLS:
            raise ValueError(f"impl must be one of {IMPLS}, got {impl!r}")
        self.logits_fn = logits_fn
        self.impl = impl
        self.block_v = block_v
        self.kv_pool = kv_pool
        self.batched_logits_fn = batched_logits_fn
        self.batched_tree_logits_fn = batched_tree_logits_fn
        self.fused = fused
        self.query_fn = query_fn
        # Device-resident once: a host LM head would cross to the device on
        # every dispatch.
        self.lm_head = None if lm_head is None else jnp.asarray(lm_head, jnp.float32)
        self.kv_fn = kv_fn if kv_fn is not None else self._default_kv_fn
        self.tracer = NULL_TRACER
        self.stats = {"kernel_rows": 0}

    def _tables(self, sessions: Sequence[int]):
        if self.kv_pool is None:
            return None
        return [
            list(self.kv_pool.table(s)) if s in self.kv_pool.tables else []
            for s in sessions
        ]

    @property
    def _pad_page_id(self) -> int:
        return self.kv_pool.sentinel_page if self.kv_pool is not None else 0

    def _default_kv_fn(self, session: int, start: int, count: int):
        """Deterministic position-keyed synthetic KV (the modeled target).

        Keyed by POSITION only — not session — so CoW-shared prefix pages
        hold the same values no matter which session materializes them, and
        re-prefills after eviction/rollback reproduce the original tensors
        bit-for-bit.
        """
        pool = self.kv_pool
        shape = (pool.n_layers, count, pool.n_kv_heads, pool.head_dim)
        pos = start + np.arange(count, dtype=np.float32)
        phase = np.arange(
            pool.n_layers * pool.n_kv_heads * pool.head_dim, dtype=np.float32
        ).reshape(pool.n_layers, 1, pool.n_kv_heads, pool.head_dim)
        base = np.sin(pos[None, :, None, None] * 0.37 + phase * 0.11).astype(np.float32)
        return np.reshape(base, shape), np.reshape(np.roll(base, 1, axis=-1) * 0.5, shape)

    def ensure_kv(self, session: int) -> None:
        """Materialize tensors for every slot past the pool's filled watermark.

        The pool's per-session ``filled`` watermark is authoritative — NOT a
        backend-side counter: rollback lowers it past rejected positions
        (whose replacements may land in recycled physical pages holding
        another session's data), eviction zeroes it, and it dies with the
        table on release, so re-grown or re-registered sessions always
        refill from their true materialized prefix.
        """
        pool = self.kv_pool
        have = pool.filled(session)
        need = pool.length(session)
        if need > have:
            with self.tracer.span("kv.synth"):
                k, v = self.kv_fn(session, have, need - have)
            pool.fill(session, have, k, v)

    def verify(self, session: int, tokens: List[int], confs: List[float]):
        """Verify one session through the batched path (batch of one)."""
        return self.verify_batch([(session, tokens, confs)])[0]

    def verify_batch(self, requests):
        """Pad the ragged requests and run ONE fused NAV kernel launch."""
        if not requests:
            return []
        from repro.kernels.spec_verify import spec_verify_batched

        if self.fused:
            return [(int(n_acc), int(corr)) for (n_acc, corr, _) in self.fused_verify(requests)]
        tokens = [t for (_, t, _) in requests]
        if self.batched_logits_fn is not None:
            out = spec_verify_batched(
                None,
                tokens,
                impl=self.impl,
                block_v=self.block_v,
                block_tables_seq=self._tables([s for (s, _, _) in requests]),
                batched_logits_fn=self.batched_logits_fn,
                pad_page_id=self._pad_page_id,
            )
        else:
            logits = [self.logits_fn(s, t) for (s, t, _) in requests]
            out = spec_verify_batched(logits, tokens, impl=self.impl, block_v=self.block_v)
        return [(int(n_acc), int(corr)) for (n_acc, corr, _) in out]

    def fused_inputs(self, requests):
        """The fused launch's arguments for one round, as keyword arguments.

        Fills any unmaterialized KV slots first (the dispatcher appends page
        metadata in ``_kv_secure`` before we run), then gathers queries,
        block tables, base lengths, the pool's layer-0 page tensors (+ int8
        quant params when the pool quantizes) and the LM head — the shared
        signature of ``spec_verify_fused_batched`` and its sharded twin.
        """
        pool = self.kv_pool
        sessions = [s for (s, _, _) in requests]
        for s in sessions:
            self.ensure_kv(s)
        quant = None
        if pool.quantize == "int8":
            quant = (pool.k_scale[0], pool.k_zero[0], pool.v_scale[0], pool.v_zero[0])
        q_seq = []
        for s, t, _ in requests:
            with self.tracer.span("query"):
                q_seq.append(np.asarray(self.query_fn(s, t), np.float32))
        return dict(
            q_seq=q_seq,
            tokens_seq=[t for (_, t, _) in requests],
            block_tables_seq=self._tables(sessions),
            base_lengths=[max(pool.length(s) - len(t), 0) for (s, t, _) in requests],
            # Read after the fills: each donates the buffers it writes.
            k_pages=pool.k_pages[0],
            v_pages=pool.v_pages[0],
            w=self.lm_head,
            pad_page_id=pool.sentinel_page,
            quant=quant,
        )

    def fused_verify(self, requests):
        """ONE launch for the whole round: attention + LM head + NAV scan.

        Returns ``(n_accepted, correction, logp[K_i])`` per request.
        """
        from repro.kernels.spec_verify import spec_verify_fused_batched

        inputs = self.fused_inputs(requests)
        self.stats["kernel_rows"] += batch_rows(len(requests))
        return spec_verify_fused_batched(**inputs, impl=self.impl, block_v=self.block_v)

    def verify_tree(self, session, tokens, confs, parents):
        """Verify one session's tree through the batched path (batch of one)."""
        return self.verify_tree_batch([(session, tokens, confs, parents)])[0]

    def verify_tree_batch(self, requests):
        """One padded tree-NAV launch over the batch (pad by node count).

        ``logits_fn(session, tokens)`` must return ``[len(tokens)+1, V]`` rows
        in packed-tree order (row 0 anchor, row 1+i node i) when the request
        is a tree — the same contract ``tree_target_logits`` produces.
        """
        if not requests:
            return []
        from repro.kernels.spec_verify import spec_verify_tree_batched

        tokens = [t for (_, t, _, _) in requests]
        parents = [p for (_, _, _, p) in requests]
        if self.batched_tree_logits_fn is not None:
            out = spec_verify_tree_batched(
                None,
                tokens,
                parents,
                impl=self.impl,
                block_v=self.block_v,
                block_tables_seq=self._tables([s for (s, _, _, _) in requests]),
                batched_logits_fn=self.batched_tree_logits_fn,
                pad_page_id=self._pad_page_id,
            )
        elif self.logits_fn is None:
            raise ValueError(
                "tree requests need logits_fn or batched_tree_logits_fn "
                "(this backend was built with only a chain batched_logits_fn)"
            )
        else:
            logits = [self.logits_fn(s, t) for (s, t, _, _) in requests]
            out = spec_verify_tree_batched(
                logits, tokens, parents, impl=self.impl, block_v=self.block_v
            )
        return [(int(n_acc), int(corr), list(path)) for (n_acc, path, corr, _) in out]


class ShardedSpecVerifyBackend(SpecVerifyBackend):
    """Tensor-parallel fused verify: the same one-launch contract, sharded.

    Drop-in for ``SpecVerifyBackend(fused=True)``: chain rounds run the
    SHARDED fused launch (``repro.sharding.spec_verify``) across a 1-D
    ``("model",)`` device mesh — head-parallel paged attention,
    vocab-parallel LM head, replicated NAV scan — while the dispatcher,
    router, and every protocol message stay oblivious to the shard count.
    The pool's page buffers are laid out over the mesh on construction
    (``PagedKVPool.place_on_mesh``: head axis when divisible, replicated
    otherwise) and block tables are replicated per device at launch, so the
    sentinel-page padding contract holds on every shard.  Bit-exact against
    the unsharded backend (``tests/test_sharded_verify.py``) for fp32 and
    int8 pools, including GQA head counts that don't divide the mesh.

    The sharded launch is XLA ``jax.numpy`` code — the stage-by-stage
    oracle of the fused kernel, partitioned — not a Pallas kernel.  So
    ``impl`` can only be ``'ref'``; any other value is refused rather than
    silently ignored.

    Pass either a prebuilt ``mesh`` or a ``shards`` count; the latter builds
    a mesh over the first ``shards`` visible devices (``host_mesh``): the
    chips of a TPU host, or on a CPU host the virtual devices that
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` creates.
    """

    def __init__(self, *, shards: int = 1, mesh: Any = None, impl: str = "ref", **kwargs: Any):
        kwargs.setdefault("fused", True)
        if not kwargs["fused"]:
            raise ValueError("ShardedSpecVerifyBackend requires the fused path")
        if impl != "ref":
            raise ValueError(
                f"ShardedSpecVerifyBackend runs the sharded jax.numpy verify, not a "
                f"Pallas kernel: impl must be 'ref', got {impl!r}"
            )
        super().__init__(impl=impl, **kwargs)
        from repro.sharding.shardctx import host_mesh

        self.mesh = mesh if mesh is not None else host_mesh(int(shards))
        self.shards = int(np.prod(list(self.mesh.shape.values())))
        if self.kv_pool is not None:
            self.kv_pool.place_on_mesh(self.mesh)

    def fused_verify(self, requests):
        """ONE SHARDED launch for the whole round (see the unsharded twin)."""
        from repro.sharding.spec_verify import spec_verify_sharded_batched

        inputs = self.fused_inputs(requests)
        self.stats["kernel_rows"] += batch_rows(len(requests))
        return spec_verify_sharded_batched(**inputs, mesh=self.mesh, block_v=self.block_v)


@dataclass
class _VerifyRequest:
    session: int
    tokens: List[int]
    confs: List[float]
    msg: NavRequest  # the originating (typed) request; its seq keys the reply
    t_enqueue: float
    deadline: Optional[float]  # absolute monotonic; None = never drop
    parents: Optional[List[int]] = None  # packed tree parents; None = chain
    kv_secured: bool = False  # this dispatch appended the round's KV pages
    pos: Optional[int] = None  # client stream position of the round's first draft
    epoch: int = 0  # session reset-epoch at enqueue; stale epochs never commit


@dataclass
class _Session:
    # Draft buffers keyed by the client's round id. Per-round keying makes
    # message loss recoverable: a round whose drafts were partially dropped
    # parks and is eventually abandoned WITHOUT consuming the next round's
    # tokens, so one lost draft_batch cannot desync the whole session.
    # Round-less (legacy) messages all land in round 0 and behave like a
    # single shared buffer.  The third buffer lane carries packed tree
    # parents (absolute node indices within the round); chain rounds leave
    # it empty.
    # Per-round draft fragments keyed by message seq.  Flattening in seq
    # order reassembles the client's draft order even when batches arrive
    # reorder-delayed, so the verifier never evaluates a scrambled round.
    buffers: Dict[int, Dict[int, Tuple[List[int], List[float], List[int]]]] = field(
        default_factory=dict
    )
    # NAV round that arrived before its proactively-uploaded drafts did.
    pending_request: Optional[NavRequest] = None
    last_seen: float = 0.0
    served: int = 0  # rounds verified — fairness key for admission
    kv_committed: int = 0  # logical target-cache length (tokens committed)
    # Duplicate suppression under retransmission faults: message seqs already
    # folded into each round's buffer (dropped with the buffer), and the
    # highest round id already enqueued for dispatch (a duplicated
    # nav_request must not verify — and KV-commit — the round twice).
    buf_seqs: Dict[int, Set[int]] = field(default_factory=dict)
    max_round_enqueued: int = 0
    # Bumped by re-attach reconciliation; an in-flight round enqueued under
    # an older epoch was abandoned by the edge and must not commit.
    epoch: int = 0

    def buf(self, rnd: int) -> Tuple[List[int], List[float], List[int]]:
        """The round's (tokens, confs, parents), flattened in seq order."""
        toks: List[int] = []
        confs: List[float] = []
        pars: List[int] = []
        frags = self.buffers.get(rnd, {})
        for seq in sorted(frags):
            t, c, p = frags[seq]
            toks.extend(t)
            confs.extend(c)
            pars.extend(p)
        return toks, confs, pars


class CloudVerifier:
    """Continuous-batching dispatcher over (uplink, downlink) pairs per session.

    With ``kv_pool`` the verifier also manages per-session target KV state in
    a paged block pool: sessions fork from a ``kv_shared_prefix``-token
    common prefix (CoW), each dispatch appends the round's ``K+1`` cache
    positions, and the post-verify rollback releases rejected pages.
    ``kv_flat_reserve`` instead reserves that many contiguous token slots per
    session up front — the flat-cache baseline, inside the same pool
    accounting so paged-vs-flat residency is directly comparable.
    """

    #: Pool session id owning the shared system/prompt prefix pages.
    KV_PREFIX_SESSION = -1

    def __init__(
        self,
        backend: VerifyBackend,
        batch_window: float = 0.0,  # >0 → coalesce concurrent NAV requests
        session_timeout: float = 30.0,
        max_batch: Optional[int] = None,
        drop_expired: bool = True,
        monitor_window: int = 1_000_000,
        kv_pool: Optional[PagedKVPool] = None,
        kv_shared_prefix: int = 0,
        kv_flat_reserve: Optional[int] = None,
        clock=None,
        tracer=None,
        metrics=None,
        verifier_id: int = 0,
    ):
        self.clock = clock or SYSTEM_CLOCK
        self.backend = backend
        # Observability (repro.obs): span tracer + metric registry, both
        # no-ops by default — tracing/metrics are strictly opt-in so the
        # serving hot path pays one attribute check when disabled.
        self.tracer = tracer if tracer is not None else NULL_TRACER
        # The backend and the pool record their spans into the same tracer
        # unless they were given one of their own.
        for part in (backend, getattr(backend, "kv_pool", None), kv_pool):
            if getattr(part, "tracer", None) is NULL_TRACER:
                setattr(part, "tracer", self.tracer)
        self.metrics = metrics
        self.verifier_id = int(verifier_id)
        self.batch_window = batch_window
        self.session_timeout = session_timeout
        self.kv_pool = kv_pool
        self.kv_shared_prefix = int(kv_shared_prefix)
        self.kv_flat_reserve = kv_flat_reserve
        if kv_pool is not None and kv_flat_reserve is None and self.kv_shared_prefix > 0:
            kv_pool.create(self.KV_PREFIX_SESSION)
            kv_pool.append(self.KV_PREFIX_SESSION, self.kv_shared_prefix)
            # Tensor-filling backends materialize the prefix ONCE, on its
            # owner, BEFORE any session forks it: children then inherit the
            # pool's filled watermark and only ever fill their own pages —
            # filling through a forked table would CoW-copy every shared
            # prefix page (pool.fill diverges shared pages), forfeiting the
            # prefix-sharing win.
            if (
                getattr(backend, "fused", False)
                and getattr(backend, "kv_pool", None) is kv_pool
            ):
                backend.ensure_kv(self.KV_PREFIX_SESSION)
        # Default: batching only when a coalescing window was requested.
        # batch_window == 0 keeps strict per-session serving (one request per
        # backend call, summed costs) so baselines measure what they claim.
        if max_batch is None:
            max_batch = 32 if batch_window > 0 else 1
        self.max_batch = max(int(max_batch), 1)
        self.drop_expired = drop_expired
        self.draining = False  # set by drain(): attach refuses new sessions
        self.links: Dict[int, tuple] = {}  # session -> (uplink, downlink)
        self.sessions: Dict[int, _Session] = {}
        self.stats = {
            "nav_calls": 0,
            "tokens_verified": 0,
            "accepted_tokens": 0,  # accepted DRAFT tokens (corrections excluded)
            "batched_calls": 0,
            "dropped_stragglers": 0,
            "dropped_dead_sessions": 0,
            "max_queue_depth": 0,
            # Paged-KV pressure: admissions deferred for lack of free pages,
            # and flat reservations that saturated (the flat cache's hard cap).
            "kv_parked": 0,
            "kv_cap_hits": 0,
            # Clock seconds the backend spent inside verify calls — the busy
            # time the energy model charges at (p_active − p_idle) watts.
            "verify_busy_time": 0.0,
            # Page buffer writes the pool made during dispatches, from
            # admission (copy-on-write copies) through the verify call
            # (``PagedKVPool.stats["page_writes"]``; 0 without tensor pages).
            "kv_page_writes": 0,
            # Rows the backend's fused launches carried, pad rows included
            # (``SpecVerifyBackend.stats["kernel_rows"]``; 0 for others).
            "kernel_rows": 0,
        }
        # The monitor here is an accumulator for the whole serving run, not
        # the paper's 100-observation estimator — size the window accordingly
        # so benchmark occupancy/queue series are not tail-truncated.
        self.monitor = EnvironmentMonitor(window=monitor_window)
        self._stop = threading.Event()
        self._threads: List = []  # clock spawn handles (Thread or ActorHandle)
        self._lock = threading.Lock()
        self._work = self.clock.condition(self._lock)
        self._queue: Deque[_VerifyRequest] = deque()
        self._dispatches = 0  # dispatches made; the traced ``dispatch`` number

    def attach(self, session: int, uplink: Transport, downlink: Transport) -> None:
        """Register a session and start its receive loop.

        With a flat-reserve KV pool the up-front contiguous reservation
        happens here and ``BlockPoolExhausted`` propagates to the caller —
        the flat baseline's hard admission limit.  Paged sessions instead
        fork the shared prefix copy-on-write (no pages allocated).

        Raises ``VerifierDraining`` while draining (the control plane must
        place new sessions elsewhere).  Re-attaching an existing session id
        (router restart / migration replay) supersedes the old links: the old
        receive loop ends, the old epoch's in-flight rounds never commit, and
        the session keeps its KV pages and committed position until the
        follow-up ``Reset`` reconciles them.
        """
        with self._lock:
            if self.draining:
                raise VerifierDraining(f"draining: session {session} refused")
            old = self.sessions.get(session)
            if old is not None:
                old_up, _ = self.links[session]
                old_up.close()  # ends the superseded receive loop
            sess = _Session(last_seen=self.clock.monotonic())
            if old is not None:
                sess.epoch = old.epoch + 1
                sess.kv_committed = old.kv_committed
                sess.served = old.served
            if self.kv_pool is not None:
                if session not in self.kv_pool.tables:
                    self._kv_register(session)
                if (
                    old is None
                    and self.kv_flat_reserve is None
                    and self.kv_shared_prefix > 0
                ):
                    sess.kv_committed = self.kv_shared_prefix
            self.links[session] = (uplink, downlink)
            self.sessions[session] = sess
        self._threads.append(
            self.clock.spawn(lambda: self._rx_loop(session), name=f"rx-{session}")
        )

    def drain(self) -> None:
        """Stop admitting new sessions; existing sessions keep serving."""
        with self._lock:
            self.draining = True

    def start(self) -> None:
        """Start the dispatch loop (receive loops start per ``attach``)."""
        self._threads.append(self.clock.spawn(self._dispatch_loop, name="dispatch"))

    def stop(self) -> None:
        """Close uplinks and drain in-flight dispatch before returning."""
        self._stop.set()
        with self._work:
            self._work.notify_all()
        for s, (up, dn) in list(self.links.items()):
            up.close()
        for t in self._threads:  # drain in-flight dispatch before reporting
            t.join(timeout=5.0)

    def load_summary(self) -> dict:
        """Occupancy/queue-depth/KV-residency view for benchmarks (→ RunStats)."""
        out = dict(
            batch_occupancy=self.monitor.verifier_occupancy() or 0.0,
            mean_queue_depth=self.monitor.verifier_queue_depth() or 0.0,
            verifier_batches=list(self.monitor.verifier_batches()),
            verifier_queue_depths=list(self.monitor.verifier_depths()),
            # Results delivered but not yet consumed by edge clients.
            dn_backlog=sum(dn.qsize() for (_, dn) in self.links.values()),
            **self.stats,
        )
        if self.kv_pool is not None:
            out.update(self.kv_pool.load_summary())
            out["kv_bytes_series"] = self.monitor.kv_bytes_series()
            out["kv_sessions_series"] = self.monitor.kv_sessions_series()
        return out

    def telemetry_snapshot(self, seq: int = 0, session: int = -1) -> TelemetrySnapshot:
        """Point-in-time :class:`TelemetrySnapshot` of this verifier.

        The typed reply to a :class:`TelemetryRequest` (and the building
        block the router aggregates fleet-wide).  Fixed fields carry the
        serving hot metrics; the ``names``/``values`` lanes carry the
        long-tail counters (drops, parking, backlog) without protocol churn.
        """
        with self._lock:
            queue_depth = len(self._queue)
            sessions_active = len(self.sessions)
            dn_backlog = sum(dn.qsize() for (_, dn) in self.links.values())
            extras = [
                ("dn_backlog", float(dn_backlog)),
                ("dropped_dead_sessions", float(self.stats["dropped_dead_sessions"])),
                ("dropped_stragglers", float(self.stats["dropped_stragglers"])),
                ("kv_parked", float(self.stats["kv_parked"])),
                ("max_queue_depth", float(self.stats["max_queue_depth"])),
            ]
            kv = dict(
                kv_used_blocks=0, kv_free_blocks=0, kv_resident_bytes=0,
                kv_resident_sessions=0,
            )
            if self.kv_pool is not None:
                kv = dict(
                    kv_used_blocks=self.kv_pool.used_blocks,
                    kv_free_blocks=self.kv_pool.free_blocks,
                    kv_resident_bytes=self.kv_pool.resident_bytes(),
                    kv_resident_sessions=self.kv_pool.resident_sessions,
                )
            return TelemetrySnapshot(
                session=session,
                seq=seq,
                verifier=self.verifier_id,
                n_verifiers=1,
                t=self.clock.monotonic(),
                sessions_active=sessions_active,
                queue_depth=queue_depth,
                nav_calls=self.stats["nav_calls"],
                tokens_verified=self.stats["tokens_verified"],
                accepted_tokens=self.stats["accepted_tokens"],
                batched_calls=self.stats["batched_calls"],
                occupancy=self.monitor.verifier_occupancy() or 0.0,
                verify_busy_time=self.stats["verify_busy_time"],
                kv_cap_hits=self.stats["kv_cap_hits"],
                names=tuple(k for k, _ in extras),
                values=tuple(v for _, v in extras),
                **kv,
            )

    # ------------------------------------------------------------ receive --
    def _enqueue_round(self, session: int, sess: _Session, msg: NavRequest) -> None:
        """Pop the round's tokens off its buffer and queue the request.

        Caller holds ``self._lock``.
        """
        n = msg.n_tokens
        rnd = msg.round
        toks, confs, pars = sess.buf(rnd)
        take_t, take_c, take_p = toks[:n], confs[:n], pars[:n]
        rest = (toks[n:], confs[n:], pars[n:])
        if rest[0]:
            # Collapse the leftover into one tail fragment at the round's
            # highest seq, keeping the seq-ordered reassembly invariant.
            sess.buffers[rnd] = {max(sess.buffers[rnd]): rest}
        else:
            sess.buffers.pop(rnd, None)
            sess.buf_seqs.pop(rnd, None)
        sess.max_round_enqueued = max(sess.max_round_enqueued, rnd)
        self._queue.append(
            _VerifyRequest(
                session,
                take_t,
                take_c,
                msg,
                self.clock.monotonic(),
                msg.deadline,
                parents=take_p if isinstance(msg, TreeNavRequest) else None,
                pos=msg.pos,
                epoch=sess.epoch,
            )
        )
        self._work.notify_all()

    def _rx_loop(self, session: int) -> None:
        up, dn = self.links[session]
        while not self._stop.is_set():
            msg = up.recv(timeout=0.25)
            if msg is None:
                if getattr(up, "closed", False):
                    # The link is permanently gone (socket EOF / channel
                    # close): end the receive loop instead of hot-polling a
                    # dead transport.  Dispatch-side session cleanup still
                    # runs through the session-timeout path.
                    return
                continue
            with self._lock:
                sess = self.sessions.get(session)
                if sess is None or self.links.get(session, (None,))[0] is not up:
                    # Detached, or superseded by a re-attach: late messages
                    # on the old link must not touch the new session's state.
                    return
            sess.last_seen = self.clock.monotonic()
            if isinstance(msg, Drain):
                self.drain()
                continue
            if isinstance(msg, DraftFragment):
                rnd = msg.round
                with self._lock:
                    # A retransmitted (duplicated) fragment must not extend the
                    # round buffer twice — dedupe on the message seq; the
                    # fragment map keys on seq so reorder-delayed fragments
                    # reassemble into the client's draft order.
                    seen = sess.buf_seqs.setdefault(rnd, set())
                    if msg.seq in seen:
                        continue
                    seen.add(msg.seq)
                    sess.buffers.setdefault(rnd, {})[msg.seq] = (
                        list(msg.tokens),
                        list(msg.confs),
                        list(msg.parents),
                    )
                    # A parked NAV round becomes dispatchable the moment its
                    # proactively-uploaded drafts complete the buffer.
                    pend = sess.pending_request
                    if (
                        pend is not None
                        and pend.round == rnd
                        and len(sess.buf(rnd)[0]) >= pend.n_tokens
                    ):
                        sess.pending_request = None
                        self._enqueue_round(session, sess, pend)
            elif isinstance(msg, NavRequest):  # chain and tree alike
                rnd = msg.round
                with self._lock:
                    # A duplicated NavRequest for an already-enqueued round
                    # must not verify (and KV-commit) the round twice, and a
                    # stale (reorder-delayed) request from a round the client
                    # has since abandoned must not displace a newer parked
                    # round.
                    pend = sess.pending_request
                    pend_rnd = pend.round if pend is not None else 0
                    if 0 < rnd and (rnd <= sess.max_round_enqueued or rnd < pend_rnd):
                        continue
                    # Abandoned earlier rounds (failover on the client) can
                    # never be requested again — drop their buffers, and any
                    # still-parked older request, without touching this round.
                    for stale in [r for r in sess.buffers if r < rnd]:
                        del sess.buffers[stale]
                        sess.buf_seqs.pop(stale, None)
                    if sess.pending_request is not None and sess.pending_request.round < rnd:
                        sess.pending_request = None
                    if len(sess.buf(rnd)[0]) >= msg.n_tokens:
                        self._enqueue_round(session, sess, msg)
                    else:
                        sess.pending_request = msg
            elif isinstance(msg, Reset):
                with self._lock:
                    sess.buffers.clear()
                    sess.buf_seqs.clear()
                    sess.pending_request = None
                    self._kv_reconcile(session, sess, msg.position)
            elif isinstance(msg, TelemetryRequest):
                # Telemetry poll on a session link: reply with this
                # verifier's snapshot (the router intercepts requests on
                # routed sessions and answers fleet-wide instead).
                dn.send(self.telemetry_snapshot(seq=msg.seq, session=msg.session))
            elif isinstance(msg, Hello):
                # In-band attach (socket clients handshake at the listener;
                # an in-process Hello still gets a well-formed reply).
                dn.send(handshake_reply(msg, session=session))
            elif isinstance(msg, Detach):
                # The client is done: drop buffered rounds, return the
                # session's KV pages to the pool, deregister the session, and
                # end the receive loop.  (Migration sends this on the OLD
                # verifier so its placement slot frees immediately.)
                with self._lock:
                    if self.sessions.get(session) is not sess:
                        return  # superseded mid-handling; nothing to clean
                    sess.buffers.clear()
                    sess.buf_seqs.clear()
                    sess.pending_request = None
                    if self.kv_pool is not None and session in self.kv_pool.tables:
                        self.kv_pool.release(session)
                    del self.sessions[session]
                    self.links.pop(session, None)
                return
            # Heartbeat (and anything unrecognized): last_seen was refreshed.

    # ----------------------------------------------------------- dispatch --
    def _kv_reconcile(self, session: int, sess: _Session, position: int) -> None:
        """Re-attach reconciliation: adopt the edge's committed stream length.

        After an offline spell the edge's position is authoritative — it kept
        decoding locally.  The verifier's logical cache length moves to the
        edge position; cloud-side pages past it (rounds verified whose
        results the edge never received) roll back to the fork, and the
        re-prefill gap (tokens the edge decoded offline) is appended by the
        next dispatch's ``_kv_secure`` exactly like a post-eviction comeback
        — replaying the paged-KV fork on the cloud side.  Caller holds
        ``self._lock``.
        """
        base = (
            self.kv_shared_prefix
            if (self.kv_pool is not None and self.kv_flat_reserve is None)
            else 0
        )
        sess.epoch += 1  # rounds still in flight were abandoned by the edge
        sess.kv_committed = base + max(position, 0)
        if self.kv_pool is not None and session in self.kv_pool.tables:
            keep = min(self.kv_pool.length(session), sess.kv_committed)
            self.kv_pool.rollback(session, keep)

    def _kv_register(self, session: int) -> None:
        """Give a session its pool table per the configured KV policy.

        Flat mode creates + reserves up front (``BlockPoolExhausted``
        propagates — the flat admission limit — with the half-made table
        cleaned up); shared-prefix mode forks the prefix owner CoW; plain
        paged mode starts empty.  Used at ``attach`` and when a
        timed-out-then-resumed session needs its released table back.
        Caller holds ``self._lock``.
        """
        if self.kv_flat_reserve is not None:
            self.kv_pool.create(session)
            try:
                self.kv_pool.reserve(session, self.kv_flat_reserve)
            except BlockPoolExhausted:
                self.kv_pool.release(session)
                raise
        elif self.kv_shared_prefix > 0:
            self.kv_pool.fork(self.KV_PREFIX_SESSION, session)
        else:
            self.kv_pool.create(session)

    def _kv_secure(self, req: _VerifyRequest, active: set) -> bool:
        """Back a round's KV growth with pool pages (caller holds the lock).

        The round writes ``K+1`` cache positions past the session's committed
        prefix (plus any re-prefill gap if the session was evicted).  Paged
        sessions that cannot be backed first reclaim pages from the
        least-recently-active idle session, then report failure (the caller
        parks the request).  Flat reservations never block — they saturate at
        their fixed capacity (``kv_cap_hits``), exactly like a flat cache
        sized at ``max_len``.

        A session whose table was released as dead (timeout) but that later
        resumed is re-registered here — re-forking the shared prefix (paged)
        or re-reserving (flat; parks while the budget is full) — so a
        comeback never serves outside the pool's admission control.
        """
        pool = self.kv_pool
        if pool is None:
            return True
        if req.session not in pool.tables:
            try:
                self._kv_register(req.session)
            except BlockPoolExhausted:
                return False  # comeback parks until the budget has room
        sess = self.sessions[req.session]
        need = sess.kv_committed - pool.length(req.session) + len(req.tokens) + 1
        if need <= 0:
            req.kv_secured = True
            return True
        table = pool.tables[req.session]
        if table.reserved:
            room = table.capacity(pool.block_size) - pool.length(req.session)
            if need > room:
                self.stats["kv_cap_hits"] += 1
                need = room
            if need > 0:
                pool.append(req.session, need)
            req.kv_secured = True
            return True
        while not pool.can_append(req.session, need):
            if pool.evict_lru(exclude=active) is None:
                return False
        pool.append(req.session, need)
        req.kv_secured = True
        return True

    def _admit(self) -> Tuple[List[_VerifyRequest], int]:
        """Admission control under ``self._lock``: drop dead work, pick fairly.

        Returns (admitted batch, queue depth at admission time).  Requests
        beyond ``max_batch`` are *reinserted* at the head in arrival order,
        so nothing is lost — but admission order is (served-rounds, arrival),
        which keeps chatty long-draft sessions from starving short ones.
        With a KV pool, admission is additionally gated on the free-block
        budget: a request whose cache growth cannot be backed (even after
        LRU eviction of idle sessions) parks back at the queue head.
        """
        now = self.clock.monotonic()
        live: List[_VerifyRequest] = []
        for req in self._drain_queue():
            if self.drop_expired and req.deadline is not None and now > req.deadline:
                self.stats["dropped_stragglers"] += 1  # client already failed over
                continue
            sess = self.sessions.get(req.session)
            if sess is None or now - sess.last_seen > self.session_timeout:
                self.stats["dropped_dead_sessions"] += 1
                if self.kv_pool is not None and req.session in self.kv_pool.tables:
                    self.kv_pool.release(req.session)  # reclaim a dead cache
                continue
            live.append(req)
        depth = len(live)
        self.stats["max_queue_depth"] = max(self.stats["max_queue_depth"], depth)
        if depth <= self.max_batch:
            admitted, overflow = live, []
        else:
            order = sorted(
                range(depth),
                key=lambda i: (self.sessions[live[i].session].served, live[i].t_enqueue),
            )
            take = set(order[: self.max_batch])
            admitted = [live[i] for i in sorted(take)]
            overflow = [live[i] for i in range(depth) if i not in take]
        if self.kv_pool is not None and admitted:
            # Sessions with in-flight or queued work must keep their pages:
            # evicting them would desync committed lengths mid-round.
            active = {r.session for r in live} | {self.KV_PREFIX_SESSION}
            active.update(s for s, sess in self.sessions.items() if sess.pending_request)
            secured = []
            for req in admitted:
                if self._kv_secure(req, active):
                    secured.append(req)
                else:
                    self.stats["kv_parked"] += 1  # retried next dispatch round
                    overflow.insert(0, req)
            admitted = secured
        for req in reversed(overflow):
            self._queue.appendleft(req)  # fair reinsertion, arrival order kept
        return admitted, depth

    def _drain_queue(self) -> List[_VerifyRequest]:
        drained = list(self._queue)
        self._queue.clear()
        return drained

    def _dispatch_loop(self) -> None:
        tracer, vid = self.tracer, self.verifier_id
        while not self._stop.is_set():
            # The number this iteration's dispatch takes if it admits work;
            # every span it records (and each span under them) carries it,
            # with the verifier's id.
            n = self._dispatches + 1
            with tracer.span("dispatch.wait", verifier=vid, dispatch=n):
                with self._work:
                    while not self._queue and not self._stop.is_set():
                        self._work.wait(timeout=0.25)
                    if self._stop.is_set():
                        return
            if self.batch_window > 0:
                with self._lock:
                    full = len(self._queue) >= self.max_batch
                if not full:  # a full batch needs no coalescing delay
                    with tracer.span("dispatch.coalesce", verifier=vid, dispatch=n):
                        self.clock.sleep(self.batch_window)  # absorb concurrent arrivals
            writes = self._page_writes()
            with tracer.span("dispatch.admit", verifier=vid, dispatch=n):
                with self._lock:
                    batch, depth = self._admit()
            if not batch:
                self.stats["kv_page_writes"] += self._page_writes() - writes
                # Nothing admitted but work may remain queued (all requests
                # KV-parked): back off instead of hot-spinning until pages
                # free up, a deadline expires, or new work arrives.
                with tracer.span("dispatch.wait", verifier=vid, dispatch=n):
                    with self._work:
                        if self._queue and not self._stop.is_set():
                            self._work.wait(timeout=0.05)
                continue
            self._dispatches = n
            self._dispatch(batch, depth, n, writes)

    def _page_writes(self) -> int:
        """The pool's page buffer writes so far (0 without a pool)."""
        pool = getattr(self.backend, "kv_pool", None)
        if pool is None:
            pool = self.kv_pool
        return pool.stats["page_writes"] if pool is not None else 0

    def _kernel_rows(self) -> int:
        """Rows the backend's fused launches carried so far (0 for others)."""
        return getattr(self.backend, "stats", {}).get("kernel_rows", 0)

    def _dispatch(self, batch: List[_VerifyRequest], depth: int, n: int, writes: int) -> None:
        """Verify one admitted batch, commit its rounds and send the results.

        ``writes`` is the pool's page write count before admission.
        """
        tracer = self.tracer
        # Chain and tree requests share the admission queue but pad
        # differently (draft length vs node count), so each kind gets its
        # own backend launch within ONE dispatch round.
        chain = [r for r in batch if r.parents is None]
        tree = [r for r in batch if r.parents is not None]
        results: Dict[int, tuple] = {}
        rows = self._kernel_rows()
        with tracer.span(
            "verify", verifier=self.verifier_id, batch=len(batch), depth=depth, dispatch=n
        ):
            verify_t0 = self.clock.monotonic()
            if chain:
                if self.backend.positional:
                    # Positional backends (runtime.oracle) verify statelessly
                    # against the stream position carried by the NAV request.
                    out = self.backend.verify_batch_pos(
                        [(r.session, r.tokens, r.confs, r.pos) for r in chain]
                    )
                else:
                    out = self.backend.verify_batch(
                        [(r.session, r.tokens, r.confs) for r in chain]
                    )
                for r, (n_acc, corr) in zip(chain, out):
                    results[id(r)] = (n_acc, corr, None)
            if tree:
                out = self.backend.verify_tree_batch(
                    [(r.session, r.tokens, r.confs, r.parents) for r in tree]
                )
                for r, (n_acc, corr, path) in zip(tree, out):
                    results[id(r)] = (n_acc, corr, path)
            verify_t1 = self.clock.monotonic()
        self.stats["verify_busy_time"] += verify_t1 - verify_t0
        self.stats["kv_page_writes"] += self._page_writes() - writes
        self.stats["kernel_rows"] += self._kernel_rows() - rows
        self.stats["nav_calls"] += len(batch)
        self.stats["batched_calls"] += 1
        self.monitor.observe_verifier_batch(len(batch), depth)
        with tracer.span("dispatch.reply", verifier=self.verifier_id, dispatch=n):
            self._reply(batch, depth, n, results, verify_t0)

    def _reply(self, batch, depth: int, n: int, results: Dict[int, tuple], verify_t0: float) -> None:
        """Commit or roll back each verified round and send its ``NavResult``."""
        if self.tracer.enabled:
            # One nav_queue span per admitted request covering enqueue →
            # backend start.
            for req in batch:
                self.tracer.add(
                    "nav_queue", req.t_enqueue, verify_t0,
                    session=req.session, round=req.msg.round,
                    verifier=self.verifier_id, dispatch=n,
                )
        if self.metrics is not None:
            self.metrics.counter(
                "verifier_nav_calls", "NAV requests verified"
            ).inc(len(batch), verifier=self.verifier_id)
            self.metrics.histogram(
                "verifier_batch_size", "Admitted NAV batch sizes"
            ).observe(len(batch), verifier=self.verifier_id)
            self.metrics.gauge(
                "verifier_queue_depth", "Queue depth at admission"
            ).set(depth, verifier=self.verifier_id)
        for req in batch:
            n_acc, corr, path = results[id(req)]
            self.stats["tokens_verified"] += len(req.tokens)
            self.stats["accepted_tokens"] += n_acc
            sess = self.sessions.get(req.session)
            if sess is not None:
                sess.served += 1
                # Commit accepted + correction tokens; with a pool, also
                # release every page wholly past the new prefix (rejection
                # rollback is a page free, not a buffer copy).  A round
                # verified across a re-attach reconciliation (stale epoch)
                # was abandoned by the edge: committing it would inflate
                # the reconciled position, so it is dropped here (the
                # client discards its stale result by seq anyway).
                with self._lock:
                    if req.epoch == sess.epoch:
                        sess.kv_committed += n_acc + 1
                        if (
                            req.kv_secured
                            and self.kv_pool is not None
                            and req.session in self.kv_pool.tables
                        ):
                            self.kv_pool.rollback(
                                req.session,
                                min(sess.kv_committed, self.kv_pool.length(req.session)),
                            )
            link = self.links.get(req.session)
            if link is None:
                continue
            _, dn = link
            dn.send(
                NavResult(
                    session=req.session,
                    seq=req.msg.seq,
                    n_accepted=n_acc,
                    correction=corr,
                    n_drafted=len(req.tokens),
                    # Chain rounds carry no path; tree rounds carry the
                    # accepted packed node indices (possibly empty).
                    path=tuple(path) if path is not None else None,
                )
            )
        if self.kv_pool is not None:
            with self._lock:
                self.monitor.observe_kv(
                    self.kv_pool.resident_bytes(), self.kv_pool.resident_sessions
                )
