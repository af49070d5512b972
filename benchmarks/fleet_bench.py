"""Fleet load benchmark: N edge sessions against one continuous-batched verifier.

Drives N ≥ 8 threaded ``EdgeClient``s with Poisson arrivals through the live
``CloudVerifier`` across the paper's four scenarios (§5.1 / App. G.2), in two
serving modes:

* ``per_session`` — every NAV request is its own backend call (the seed
  behaviour: ``batch_window = 0``, ``max_batch = 1``);
* ``batched``     — continuous batching: requests coalescing within
  ``batch_window`` share ONE padded verify whose cost scales with the
  longest draft, not the sum (beyond-paper optimization #5).

and two speculation variants:

* ``chain`` — linear drafts (the PipeSD default);
* ``tree``  — top-k branching draft trees verified by batched tree-NAV; the
  hedge across siblings raises accepted-tokens-per-NAV exactly where chains
  stall (hard/low-confidence token streams), at the price of more verified
  nodes per call.

and two verifier KV layouts at a FIXED block-pool byte budget (``kv=``):

* ``flat``  — every session reserves ``KV_FLAT_MAX_LEN`` contiguous token
  slots up front (the flat ``KVCache`` behaviour, expressed inside the pool
  accounting): admission stops when reservations exhaust the budget;
* ``paged`` — on-demand pages + copy-on-write sharing of a
  ``KV_SHARED_PREFIX``-token system prompt (``models/paged_kv.py``): the
  same budget serves strictly more concurrent sessions because resident
  bytes track *actual* prefix lengths, with per-session TPT within a few
  percent of flat (the pool is bookkeeping, not compute).

Reported per (scenario, mode, variant): per-session TPT (mean/worst),
accepted-tokens-per-NAV, verifier batch occupancy, mean queue depth, and
p50/p99 NAV round-trip latency — plus, for KV runs, resident KV bytes per
session and the max concurrent resident sessions — all de-scaled to
simulated seconds and funneled through ``core.pipeline.RunStats``.

    PYTHONPATH=src python -m benchmarks.fleet_bench            # quick compare
    PYTHONPATH=src python benchmarks/fleet_bench.py            # same
    PYTHONPATH=src python -m benchmarks.run fleet              # harness CSV
"""

from __future__ import annotations

import sys
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

_ROOT = Path(__file__).resolve().parent.parent
for _p in (str(_ROOT), str(_ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np

from benchmarks.common import csv_row, scenario
from repro.core.pipeline import CloudModel, RunStats
from repro.core.policy import AdaptivePolicyController, PolicyDecision
from repro.models.paged_kv import BlockPoolExhausted, PagedKVPool
from repro.runtime import (
    FAULT_MATRIX,
    Channel,
    ChannelConfig,
    CloudVerifier,
    DraftFragment,
    EdgeClient,
    EdgeConfig,
    FaultScenario,
    LinkFaults,
    LocalVerifier,
    NavRequest,
    NavResult,
    OracleBackend,
    OracleDraft,
    OracleStream,
    Router,
    SyntheticBackend,
    SyntheticDraft,
    SystemClock,
    VirtualClock,
    decode,
    encode,
)

TS = 0.01  # run the timing model 100× faster than real time
MODES = ("per_session", "batched")
VARIANTS = ("chain", "tree")

# Verifier KV geometry for the paged-vs-flat comparison: a 7B-class target
# (32 layers x 8 KV heads x 128 head_dim, bf16 k+v = 128 KiB/token) paged in
# 16-token blocks.  Flat mode reserves KV_FLAT_MAX_LEN slots per session up
# front; paged mode shares a KV_SHARED_PREFIX-token system prompt CoW.
KV_BYTES_PER_TOKEN = 2 * 32 * 8 * 128 * 2
KV_BLOCK_TOKENS = 16
KV_SHARED_PREFIX = 256
KV_FLAT_MAX_LEN = 512
KV_MODES = ("flat", "paged")


from dataclasses import dataclass  # noqa: E402  (after sys.path setup)


@dataclass(frozen=True)
class SessionProfile:
    """Per-session heterogeneity: device speed, link quality, workload mix.

    Scales are applied to the fleet's baseline draft γ, the scenario
    channel's (α, β), and the offline local-decode multiplier; ``p_hard``
    overrides the fleet default.  ``run_fleet(profiles=...)`` assigns
    profile ``sid % len(profiles)`` to session ``sid`` round-robin.
    """

    name: str
    gamma_scale: float = 1.0
    alpha_scale: float = 1.0
    beta_scale: float = 1.0
    local_gamma_scale: float = 1.0
    p_hard: Optional[float] = None


# The paper's device tiers as a mixed fleet: laptop on WiFi (Scenario 1's
# 5.1 GHz baseline), phone on 5G (2.5 GHz device, faster link), IoT board
# on 4G (1.2 GHz device, slow lossy link, harder on-device draft mix).
HETERO_PROFILES: Tuple[SessionProfile, ...] = (
    SessionProfile("laptop_wifi"),
    SessionProfile("phone_5g", gamma_scale=5.1 / 2.5, alpha_scale=0.6, beta_scale=0.5),
    SessionProfile("iot_4g", gamma_scale=5.1 / 1.2, alpha_scale=1.5, beta_scale=3.0, p_hard=0.22),
)


def _sharded_spec_backend(shards: int, seed: int):
    """The real sharded fused verifier for the fleet harness.

    A tensor-mode ``PagedKVPool`` (pages partitioned per shard on the head
    axis) plus a seeded deterministic target (queries + LM head) behind
    ``SpecVerifyBackend(mesh=...)`` — the same geometry the serve launcher's
    ``--backend spec`` uses, sized for the fleet's session counts.  The
    returned backend carries the synthetic ``verify_time`` so the batched
    coalescing window is identical to the simulated backends'.
    """
    import jax

    from repro.runtime import SpecVerifyBackend
    from repro.sharding.shardctx import host_mesh

    H, hd, bs, V = 2, 8, 4, 256
    pool = PagedKVPool(
        num_blocks=1024, block_size=bs, n_layers=1, n_kv_heads=H, head_dim=hd
    )
    key = jax.random.PRNGKey(seed)
    w = np.asarray(jax.random.normal(jax.random.fold_in(key, 77), (H * hd, V)) * 4, np.float32)

    queries = jax.random.fold_in(key, 88)

    def query_fn(session, tokens, k=None):  # the launcher's target (launch/serve.py)
        n = len(tokens) if k is None else k
        draw = jax.random.fold_in(queries, session * 131 + n)
        return jax.random.normal(draw, (len(tokens) + 1, H, hd))

    backend = SpecVerifyBackend(
        kv_pool=pool, query_fn=query_fn, lm_head=w, impl="ref", block_v=256,
        mesh=host_mesh(shards),
    )
    backend.verify_time = 0.080  # align the coalescing window with SyntheticBackend
    return backend, pool


def run_fleet(
    n_sessions: int = 8,
    mode: str = "batched",
    scen: int = 1,
    tokens_per_session: int = 60,
    arrival_rate: float = 2.0,  # Poisson session arrivals [1/simulated-s]
    seed: int = 0,
    ts: float = TS,
    variant: str = "chain",
    p_hard: float = 0.15,
    kv: Optional[str] = None,
    kv_budget_bytes: Optional[int] = None,
    clock=None,
    faults: Optional[FaultScenario] = None,
    oracle: bool = False,
    nav_timeout: float = 8.0,
    backoff_init: float = 0.5,
    local_gamma: Optional[float] = None,
    shards: Optional[int] = None,
    profiles: Optional[Sequence[SessionProfile]] = None,
    policy: Optional[str] = None,
    p_hard_schedule: Optional[Tuple[Tuple[int, float], ...]] = None,
) -> dict:
    """Serve ``n_sessions`` Poisson-arriving edge clients; returns a report.

    The report carries a ``RunStats`` with the fleet's NAV latencies and the
    verifier's batch/queue series, plus per-session TPT (simulated seconds
    per accepted token, §5.1 Metrics).  ``variant='tree'`` switches every
    client to tree drafting (width 2, node budget 16 vs the chain's window
    8 — same max depth, so the tree spends extra nodes on sibling hedges).
    ``p_hard`` sets the fleet's share of hard tokens; the default matches
    the historical chain baseline (so batched-vs-per_session rows stay
    comparable across commits), while ``compare_tree`` raises it into the
    low-acceptance regime where hedging pays.

    ``kv='flat'|'paged'`` runs the verifier against a ``PagedKVPool`` sized
    at ``kv_budget_bytes``: flat mode reserves ``KV_FLAT_MAX_LEN`` tokens
    per session up front (sessions beyond the budget are REFUSED at attach —
    the report's ``n_attached`` drops below ``n_sessions``), paged mode
    allocates on demand with a CoW-shared ``KV_SHARED_PREFIX``.

    ``clock`` selects the time base: the default ``SystemClock`` measures
    wall time (historical behaviour, host-scheduler noisy); a
    ``VirtualClock`` runs the identical serving code on deterministic
    discrete-event time — bit-reproducible from ``seed``, simulated seconds
    exact, host cost near zero.  ``faults`` attaches a declarative
    ``FaultScenario`` to every client's link, and ``oracle=True`` swaps in
    the deterministic oracle draft/verifier pair so the chaos harness can
    assert the committed streams are fault-invariant.

    ``shards=N`` swaps in the REAL sharded fused verifier
    (``SpecVerifyBackend`` over an N-device host mesh, with a
    tensor-mode paged KV pool partitioned on the head axis) instead of the
    simulated backend — the dispatcher, clients, and the rest of the
    harness run unchanged, so committed streams at different shard counts
    must be identical (the dispatcher-obliviousness check in
    ``tests/test_sharded_verify.py``).  Chain variant only.

    ``profiles=`` makes the fleet heterogeneous: session ``sid`` takes
    ``profiles[sid % len(profiles)]``, scaling its draft γ, link (α, β),
    and hard-token mix (``HETERO_PROFILES`` is the paper's device tiers as
    one mixed fleet).  ``policy='adaptive'`` attaches a per-session
    ``AdaptivePolicyController`` (chain/tree/local + BO retunes on drift);
    ``p_hard_schedule`` makes every synthetic draft's hardness drift
    mid-run (deterministic), the regime the adaptive policy targets.
    """
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}")
    if variant not in VARIANTS:
        raise ValueError(f"variant must be one of {VARIANTS}")
    if policy not in (None, "adaptive"):
        raise ValueError(f"policy must be None or 'adaptive', got {policy!r}")
    if policy is not None and oracle:
        raise ValueError("policy= is a synthetic-fleet knob (oracle fleets pin the variant)")
    if kv is not None and kv not in KV_MODES:
        raise ValueError(f"kv must be one of {KV_MODES}")
    if oracle and variant == "tree":
        raise ValueError("oracle=True supports only variant='chain' (OracleBackend has no tree verify path)")
    clock = clock or SystemClock()
    edge, channel = scenario(scen)
    # Fleet tier: faster drafts + short windows. The verifier becomes the
    # contended resource (the regime §3.2's utilization argument targets):
    # per-session serving saturates at ~9 NAV/s while batching absorbs it.
    gamma = edge.effective_gamma() * 0.1
    kv_kwargs = {}
    if shards is not None:
        if variant != "chain":
            raise ValueError("shards= supports only variant='chain'")
        if oracle or kv is not None:
            raise ValueError("shards= brings its own tensor-mode pool (no oracle/kv)")
        backend, shard_pool = _sharded_spec_backend(shards, seed)
        kv_kwargs = dict(kv_pool=shard_pool)
    elif oracle:
        backend = OracleBackend(time_scale=ts, seed=seed, clock=clock)
    else:
        backend = SyntheticBackend(time_scale=ts, seed=seed, clock=clock)
    if kv is not None:
        budget = kv_budget_bytes or (256 * KV_BLOCK_TOKENS * KV_BYTES_PER_TOKEN)
        pool = PagedKVPool(
            max(budget // (KV_BLOCK_TOKENS * KV_BYTES_PER_TOKEN), 1),
            KV_BLOCK_TOKENS,
            bytes_per_token=KV_BYTES_PER_TOKEN,
        )
        kv_kwargs = dict(kv_pool=pool)
        if kv == "flat":
            kv_kwargs["kv_flat_reserve"] = KV_FLAT_MAX_LEN
        else:
            kv_kwargs["kv_shared_prefix"] = KV_SHARED_PREFIX
    server = CloudVerifier(
        backend,
        batch_window=(backend.verify_time * ts if mode == "batched" else 0.0),
        max_batch=(64 if mode == "batched" else 1),
        clock=clock,
        **kv_kwargs,
    )
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / arrival_rate, n_sessions))
    clients: List[EdgeClient] = []
    session_gammas: List[float] = []
    session_betas: List[float] = []
    for sid in range(n_sessions):
        prof = profiles[sid % len(profiles)] if profiles else SessionProfile("uniform")
        gamma_s = gamma * prof.gamma_scale
        beta_up_s = channel.beta_up * prof.beta_scale
        lf = (lambda d: LinkFaults(faults, d, seed=seed * 1009 + sid, time_scale=ts)) if faults else (lambda d: None)
        up = Channel(
            ChannelConfig(alpha=channel.alpha_up * prof.alpha_scale, beta=beta_up_s, time_scale=ts),
            f"up{sid}", clock=clock, faults=lf("up"),
        )
        dn = Channel(
            ChannelConfig(alpha=channel.alpha_dn * prof.alpha_scale, beta=channel.beta_dn * prof.beta_scale, time_scale=ts),
            f"dn{sid}", clock=clock, faults=lf("dn"),
        )
        try:
            server.attach(sid, up, dn)
        except BlockPoolExhausted:
            break  # flat reservation refused: the budget is full
        lg = gamma_s * local_gamma * prof.local_gamma_scale if local_gamma is not None else None
        cfg = EdgeConfig(
            time_scale=ts, gamma=gamma_s, local_gamma=lg, window=8,
            nav_timeout=nav_timeout, backoff_init=backoff_init,
        )
        if variant == "tree":
            cfg = EdgeConfig(
                time_scale=ts, gamma=gamma_s, local_gamma=lg, window=16,
                nav_timeout=nav_timeout, backoff_init=backoff_init,
                variant="tree", tree_width=2, tree_depth=8,
            )
        # Oracle fleets share ONE target stream (same prompt, same truth) so
        # the chaos harness can diff committed streams across scenarios.
        p_hard_s = prof.p_hard if prof.p_hard is not None else p_hard
        draft = (
            OracleDraft(seed=seed)
            if oracle
            else SyntheticDraft(seed=sid, p_hard=p_hard_s, p_hard_schedule=p_hard_schedule)
        )
        controller = None
        if policy == "adaptive":
            controller = AdaptivePolicyController(
                base=PolicyDecision(
                    mode=cfg.variant, r1=cfg.r1, r2=cfg.r2,
                    tree_width=cfg.tree_width, tree_depth=cfg.tree_depth,
                    window=cfg.window,
                ),
                seed=seed * 31 + sid,
                session=sid,
            )
        session_gammas.append(gamma_s)
        session_betas.append(beta_up_s)
        clients.append(EdgeClient(sid, up, dn, cfg, draft=draft, policy=controller))
    server.start()
    results: Dict[int, dict] = {}
    streams: Dict[int, List[int]] = {}

    def _drive(c: EdgeClient, start_s: float) -> None:
        clock.sleep(start_s * ts)  # Poisson arrival (scaled)
        results[c.session] = c.run(tokens_per_session)
        streams[c.session] = list(c.tokens)

    def _serve() -> float:
        handles = [
            clock.spawn(
                (lambda c=c, s=float(arrivals[i]): _drive(c, s)),
                name=f"drive-{c.session}",
            )
            for i, c in enumerate(clients)
        ]
        t0 = clock.monotonic()
        for h in handles:
            h.join(timeout=600 if not getattr(clock, "virtual", False) else None)
        wall_ = clock.monotonic() - t0
        server.stop()
        return wall_

    wall = clock.run(_serve)

    load = server.load_summary()
    # Paper's two-sided energy model (§5.3): edge joules from the per-client
    # decode/upload busy times, cloud joules from verifier busy time.  All
    # times are de-scaled back to unscaled model seconds first.
    edge_joules = sum(
        edge.edge_energy(
            r.get("draft_time_s", 0.0),
            r.get("tx_time_s", 0.0),
            r["wall_time"] / ts,
        )
        for r in results.values()
    )
    cloud = CloudModel()
    cloud_joules = (cloud.p_active - cloud.p_idle) * load.get("verify_busy_time", 0.0) / ts
    stats = RunStats(
        accepted_tokens=sum(r["accepted_tokens"] for r in results.values()),
        nav_calls=load["nav_calls"],
        rounds=sum(r["rounds"] for r in results.values()),
        wall_time=wall / ts,  # de-scaled simulated seconds
        verifier_batches=load["verifier_batches"],
        verifier_queue_depths=load["verifier_queue_depths"],
        nav_latencies=[lat / ts for r in results.values() for lat in r["nav_latencies"]],
        kv_resident_bytes=load.get("kv_bytes_series", []),
        kv_resident_sessions=load.get("kv_sessions_series", []),
        kv_cap_hits=load.get("kv_cap_hits", 0),
        failovers=sum(r["failovers"] for r in results.values()),
        fallback_tokens=sum(r["fallback_tokens"] for r in results.values()),
        lost_draft_tokens=sum(r["lost_draft_tokens"] for r in results.values()),
        recovery_latencies=[
            lat / ts for r in results.values() for lat in r["recovery_latencies"]
        ],
        cloud_energy=cloud_joules,
        edge_energy=edge_joules,
        session_gammas=session_gammas[: len(clients)],
        session_betas=session_betas[: len(clients)],
    )
    per_session_tpt = {
        sid: r["wall_time"] / ts / max(r["accepted_tokens"], 1) for sid, r in results.items()
    }
    # Client sessions concurrently holding pages (the shared-prefix owner is
    # pool-resident but not a client).
    kv_max_clients = load.get("kv_max_resident_sessions", 0)
    if kv == "paged" and KV_SHARED_PREFIX > 0:
        kv_max_clients = max(kv_max_clients - 1, 0)
    return dict(
        mode=mode,
        variant=variant,
        kv=kv,
        scenario=scen,
        n_sessions=n_sessions,
        n_attached=len(clients),
        kv_max_clients=kv_max_clients,
        stats=stats,
        per_session_tpt=per_session_tpt,
        failovers=stats.failovers,
        streams=streams,
        server=load,
        policy_mode_switches=sum(r.get("policy_mode_switches", 0) for r in results.values()),
        policy_retunes=sum(r.get("policy_retunes", 0) for r in results.values()),
    )


def _report_lines(rep: dict) -> List[str]:
    st: RunStats = rep["stats"]
    p50, p99 = st.nav_latency_quantiles()
    tpts = list(rep["per_session_tpt"].values()) or [float("nan")]
    lines = [
        f"  mode={rep['mode']:<12} variant={rep['variant']:<6} sessions={rep['n_sessions']}"
        f" occupancy={st.verifier_batch_occupancy:.2f}"
        f" queue_depth={st.mean_queue_depth:.2f}",
        f"    per-session TPT mean={np.mean(tpts)*1e3:.1f}ms worst={np.max(tpts)*1e3:.1f}ms"
        f" | tokens/NAV={st.tokens_per_nav:.2f}"
        f" | NAV latency p50={p50*1e3:.1f}ms p99={p99*1e3:.1f}ms"
        f" | backend calls={rep['server']['batched_calls']}"
        f" nav={st.nav_calls} failovers={rep['failovers']}",
    ]
    if rep.get("kv"):
        lines.append(
            f"    kv={rep['kv']:<5} attached={rep['n_attached']}/{rep['n_sessions']}"
            f" max_resident={rep['kv_max_clients']}"
            f" | resident mean={st.mean_kv_resident_bytes/2**20:.0f}MiB"
            f" peak={st.peak_kv_resident_bytes/2**20:.0f}MiB"
            f" per-session={st.kv_bytes_per_session/2**20:.1f}MiB"
            f" | shared_blocks={rep['server'].get('kv_shared_blocks', 0)}"
            f" cow={rep['server'].get('kv_cow_copies', 0)}"
            f" evictions={rep['server'].get('kv_evictions', 0)}"
            f" parked={rep['server'].get('kv_parked', 0)}"
        )
    return lines


def compare_kv(
    n_sessions: int = 16,
    scen: int = 1,
    kv_budget_bytes: Optional[int] = None,
    tokens_per_session: int = 60,
) -> dict:
    """Paged vs flat verifier KV at one fixed block-pool byte budget.

    Three runs: ``flat`` (attaches only as many sessions as ``max_len``
    reservations fit the budget), ``paged`` with the SAME offered fleet
    (serves strictly more concurrent sessions from the same bytes), and
    ``paged_matched`` at flat's session count — the apples-to-apples TPT
    comparison.  Wall-clock TPT from the threaded runtime is noisy (host
    scheduler jitter swamps single runs), so the robust parity evidence is
    the measured **bookkeeping share**: the pool's total mutation host-time
    (``kv_op_seconds``) as a fraction of serving wall time bounds the TPT
    cost paging can add, and stays far under 5% (the deterministic
    simulation engine shows exact parity — ``tests/test_paged_kv.py``).
    Returns ``{name: report}`` plus the budget and per-run overhead bounds.
    """
    budget = kv_budget_bytes or (
        (n_sessions // 2) * (KV_FLAT_MAX_LEN // KV_BLOCK_TOKENS)
        * KV_BLOCK_TOKENS * KV_BYTES_PER_TOKEN
    )
    common = dict(
        scen=scen, mode="batched", kv_budget_bytes=budget, tokens_per_session=tokens_per_session
    )
    flat = run_fleet(n_sessions=n_sessions, kv="flat", **common)
    paged = run_fleet(n_sessions=n_sessions, kv="paged", **common)
    # A budget below one flat reservation admits zero sessions; the matched
    # paged run still needs >= 1 client to produce a well-formed report.
    matched = run_fleet(n_sessions=max(flat["n_attached"], 1), kv="paged", **common)
    out = dict(flat=flat, paged=paged, paged_matched=matched, kv_budget_bytes=budget)
    for name in ("flat", "paged", "paged_matched"):
        rep = out[name]
        host_wall = rep["stats"].wall_time * TS  # de-scaled back to host seconds
        rep["kv_overhead_frac"] = rep["server"].get("kv_op_seconds", 0.0) / max(host_wall, 1e-9)
    return out


def compare_tree(
    scenarios=(1, 2, 3, 4), n_sessions: int = 8, mode: str = "batched", p_hard: float = 0.35
) -> dict:
    """Chain-vs-tree accepted-tokens-per-NAV across the paper's scenarios.

    Returns {scenario: {variant: report}}; both variants see the SAME hard
    confidence stream (``p_hard``) — the regime where sibling hedges rescue
    rounds a chain would end at the first rejection, so the tree variant
    should win tokens/NAV.
    """
    out: Dict[int, dict] = {}
    for scen in scenarios:
        out[scen] = {
            v: run_fleet(n_sessions=n_sessions, mode=mode, scen=scen, variant=v, p_hard=p_hard)
            for v in VARIANTS
        }
    return out


def run_chaos(
    scenarios: Tuple[FaultScenario, ...] = FAULT_MATRIX,
    n_sessions: int = 4,
    tokens_per_session: int = 120,
    seed: int = 0,
    scen: int = 1,
) -> dict:
    """Chaos mode: the oracle fleet under every fault scenario, virtually.

    Each scenario serves ``n_sessions`` oracle clients on a fresh
    ``VirtualClock`` with the scenario's faults on every link, and reports
    offline-robustness metrics in exact simulated seconds: failovers,
    fallback share, **recovery latency** (failover → next verified round)
    and **tokens lost per outage** (drafted tokens whose round was abandoned,
    divided by the scenario's outage windows).  ``conformant`` asserts the
    paper's robustness claim end-to-end: every session's committed stream is
    bit-identical to the oracle (≡ the fault-free stream).  Runs are
    bit-reproducible from ``seed`` — the CI chaos job diffs two of them.
    """
    oracle_ref = OracleStream(seed)
    out: Dict[str, dict] = {}
    for fs in scenarios:
        rep = run_fleet(
            n_sessions=n_sessions,
            mode="batched",
            scen=scen,
            tokens_per_session=tokens_per_session,
            seed=seed,
            ts=1.0,  # virtual seconds are free — run the model at true scale
            clock=VirtualClock(),
            faults=fs,
            oracle=True,
            nav_timeout=1.0,
            backoff_init=0.1,
            local_gamma=8.0,  # offline full-model decode is ~8x slower
        )
        st: RunStats = rep["stats"]
        n_outages = len(fs.outage_windows("up")) + len(fs.outage_windows("dn"))
        rep["scenario_name"] = fs.name
        rep["conformant"] = all(
            stream == oracle_ref.prefix(len(stream)) and len(stream) >= tokens_per_session
            for stream in rep["streams"].values()
        )
        # Per-outage attribution only makes sense when the scenario HAS
        # outage windows; lossy-but-outage-free scenarios report 0 here and
        # their abandoned drafts via ``lost_draft_tokens`` directly.
        rep["n_outages"] = n_outages
        rep["tokens_lost_per_outage"] = (
            st.lost_draft_tokens / n_outages if n_outages else 0.0
        )
        rep["recovery_latency_s"] = st.mean_recovery_latency
        out[fs.name] = rep
    return out


def _chaos_lines(reports: dict) -> List[str]:
    lines = []
    for name, rep in reports.items():
        st: RunStats = rep["stats"]
        lost = (
            f" lost/outage={rep['tokens_lost_per_outage']:.0f}"
            if rep["n_outages"]
            else f" lost_drafts={st.lost_draft_tokens}"
        )
        lines.append(
            f"  {name:<18} conformant={rep['conformant']}"
            f" failovers={st.failovers}"
            f" fallback={st.fallback_fraction*100:.0f}%"
            f" recovery={st.mean_recovery_latency*1e3:.0f}ms"
            + lost
            + f" navs={st.nav_calls} wall={st.wall_time:.1f}s"
        )
    return lines


def chaos(n_sessions: int = 4, seed: int = 0) -> Tuple[list, List[str]]:
    """Harness entry (benchmarks.run): one CSV row per fault scenario.

    Deterministic by construction (virtual clock + seeded everything): two
    invocations with the same arguments emit byte-identical rows, which is
    exactly what the CI chaos job diffs.
    """
    reports = run_chaos(n_sessions=n_sessions, seed=seed)
    rows, lines = [], []
    for name, rep in reports.items():
        st: RunStats = rep["stats"]
        row = dict(
            scenario_name=name,
            conformant=rep["conformant"],
            failovers=st.failovers,
            fallback_fraction=st.fallback_fraction,
            recovery_latency_s=st.mean_recovery_latency,
            lost_draft_tokens=st.lost_draft_tokens,
            n_outages=rep["n_outages"],
            tokens_lost_per_outage=rep["tokens_lost_per_outage"],
            wall_time_s=st.wall_time,
        )
        rows.append(row)
        derived = (
            f"conformant={rep['conformant']};failovers={st.failovers};"
            f"fallback_pct={st.fallback_fraction*100:.1f};"
            f"recovery_ms={st.mean_recovery_latency*1e3:.1f};"
            f"lost_drafts={st.lost_draft_tokens};"
            f"lost_per_outage={rep['tokens_lost_per_outage']:.1f};"
            f"navs={st.nav_calls};wall_s={st.wall_time:.3f}"
        )
        lines.append(csv_row(f"chaos/{name}", st.wall_time * 1e6, derived))
    return rows, lines


def codec_bench(n_iters: int = 50_000) -> Tuple[list, List[str]]:
    """Wire-codec overhead: encode+decode round-trip cost per message.

    Times the three messages that dominate serving traffic (a 16-token
    ``DraftFragment``, a ``NavRequest``, a ``NavResult``) and reports
    ns/message plus frame bytes.  The sanity bound the row exists to check:
    codec time per *drafted token* must sit orders of magnitude below the
    link's per-token serialization cost (Hockney β = 2 ms at the paper's
    operating point), i.e. framing is never the serving bottleneck.
    """
    import time

    msgs = {
        "draft16": DraftFragment(
            session=1, seq=7, round=3,
            tokens=tuple(range(1000, 1016)), confs=tuple(0.5 + 0.03 * i for i in range(16)),
        ),
        "nav_request": NavRequest(session=1, seq=8, round=3, n_tokens=16, deadline=1.25, pos=640),
        "nav_result": NavResult(session=1, seq=8, n_accepted=12, correction=31337, n_drafted=16),
    }
    rows, lines = [], []
    for name, msg in msgs.items():
        frame = encode(msg)
        assert decode(frame) == msg  # round-trip exact, every run
        t0 = time.perf_counter()
        for _ in range(n_iters):
            decode(encode(msg))
        dt = time.perf_counter() - t0
        ns_per_msg = dt / n_iters * 1e9
        # ``host_`` prefix: wall-time measurement, host-noisy by nature —
        # bench_diff treats it as informational (skipped in comparisons).
        row = dict(message=name, host_ns_per_msg=ns_per_msg, frame_bytes=len(frame))
        rows.append(row)
        derived = f"ns_per_msg={ns_per_msg:.0f};frame_bytes={len(frame)};iters={n_iters}"
        lines.append(csv_row(f"fleet/codec/{name}", ns_per_msg * 1e-3, derived))
    return rows, lines


# --------------------------------------------------------------------------- #
# Router scaling: N verifiers behind the control plane
# --------------------------------------------------------------------------- #


class _MeteredChannel(Channel):
    """A ``Channel`` that counts encoded wire bytes on send."""

    def __init__(self, *args, **kwargs) -> None:
        super().__init__(*args, **kwargs)
        self.bytes_sent = 0

    def send(self, msg) -> None:
        self.bytes_sent += len(encode(msg))
        super().send(msg)


def run_router_fleet(
    n_verifiers: int,
    n_sessions: int = 16,
    tokens_per_session: int = 60,
    seed: int = 0,
    traced: bool = False,
) -> dict:
    """Serve an oracle fleet through the ``Router`` over ``n_verifiers``.

    The regime is deliberately verifier-bound: per-session serving
    (``batch_window = 0``) with a verify cost that dominates the round, a
    fast edge draft, and enough sessions to saturate the largest fleet —
    so aggregate throughput scales ~linearly with fleet size and the bench
    measures the control plane's placement spread, not batching effects.

    Everything runs on one ``VirtualClock``: the report is bit-reproducible
    from ``seed`` and throughput is exact simulated tokens/second.  Every
    committed stream is asserted against the oracle before reporting —
    a routed fleet that scales but mis-commits would fail here, not in CI.

    ``traced=True`` attaches a ``repro.obs`` span tracer + metric registry
    (on the SAME virtual clock) to every verifier, client, and the router.
    Because tracing only *reads* the virtual clock, a traced run's committed
    rows are bit-identical to the untraced run — the ``router/x1_traced``
    row in ``BENCH_fleet.json`` is that overhead gate, committed.  The
    report gains ``n_spans`` plus private ``_tracer``/``_metrics`` handles
    (underscored: stripped before rows are written).
    """
    clock = VirtualClock()
    tracer = metrics = None
    if traced:
        from repro.obs.metrics import MetricRegistry
        from repro.obs.trace import Tracer

        tracer = Tracer(clock=clock)
        metrics = MetricRegistry(clock=clock)
    oracle_ref = OracleStream(seed)
    fleet = []
    for vid in range(n_verifiers):
        backend = OracleBackend(
            seed=seed, verify_time=0.06, verify_time_per_token=0.002, clock=clock
        )
        cv = CloudVerifier(
            backend, batch_window=0.0, max_batch=1, clock=clock,
            tracer=tracer, metrics=metrics, verifier_id=vid,
        )
        cv.start()
        fleet.append(LocalVerifier(vid, cv, clock=clock))
    router = Router(fleet, clock=clock, control_interval=1.0, tracer=tracer)
    link = ChannelConfig(alpha=0.005, beta=0.0005)
    clients: List[EdgeClient] = []
    channels: List[_MeteredChannel] = []
    for sid in range(n_sessions):
        up = _MeteredChannel(link, f"up{sid}", clock=clock)
        dn = _MeteredChannel(link, f"dn{sid}", clock=clock)
        channels.extend((up, dn))
        router.attach(sid, up, dn)
        cfg = EdgeConfig(gamma=0.004, window=8, nav_timeout=30.0)
        clients.append(
            EdgeClient(sid, up, dn, cfg, draft=OracleDraft(seed=seed), tracer=tracer)
        )
    results: Dict[int, dict] = {}
    streams: Dict[int, List[int]] = {}

    def _drive(c: EdgeClient) -> None:
        results[c.session] = c.run(tokens_per_session)
        streams[c.session] = list(c.tokens)

    def _serve() -> float:
        router.start()
        handles = [
            clock.spawn((lambda c=c: _drive(c)), name=f"drive-{c.session}")
            for c in clients
        ]
        t0 = clock.monotonic()
        for h in handles:
            h.join()
        wall_ = clock.monotonic() - t0
        router.stop()
        for vc in fleet:
            vc.stop()
        return wall_

    wall = clock.run(_serve)

    for sid, stream in streams.items():
        assert len(stream) >= tokens_per_session and stream == oracle_ref.prefix(
            len(stream)
        ), f"routed session {sid} diverged from the oracle"
    placed: Dict[int, int] = {vid: 0 for vid in range(n_verifiers)}
    for sid in range(n_sessions):
        placed[router.sessions[sid].verifier] += 1
    accepted = sum(r["accepted_tokens"] for r in results.values())
    navs = sum(r["rounds"] for r in results.values())
    lats = sorted(lat for r in results.values() for lat in r["nav_latencies"])
    p50 = lats[len(lats) // 2] if lats else float("nan")
    p99 = lats[min(len(lats) - 1, int(len(lats) * 0.99))] if lats else float("nan")
    rep = dict(
        n_verifiers=n_verifiers,
        n_sessions=n_sessions,
        tokens_per_s=accepted / wall,
        tokens_per_nav=accepted / max(navs, 1),
        nav_p50_ms=p50 * 1e3,
        nav_p99_ms=p99 * 1e3,
        bytes_per_session=sum(ch.bytes_sent for ch in channels) / n_sessions,
        placement=placed,
        spread=max(placed.values()) - min(placed.values()),
        failovers=sum(r["failovers"] for r in results.values()),
        wall_s=wall,
        router_stats=dict(router.stats),
    )
    if traced:
        rep["n_spans"] = len(tracer)
        rep["_tracer"] = tracer
        rep["_metrics"] = metrics
    return rep


def router_bench(verifier_counts: Tuple[int, ...] = (1, 2, 4)) -> Dict[int, dict]:
    """Router scaling sweep: ``{n_verifiers: report}`` with speedups vs x1.

    The acceptance bar (ISSUE / CI): >= 1.7x aggregate throughput at 2
    verifiers and >= 3x at 4, in the verifier-bound regime above.
    """
    out: Dict[int, dict] = {}
    for n in verifier_counts:
        out[n] = run_router_fleet(n)
    base = out[min(out)]["tokens_per_s"]
    for rep in out.values():
        rep["speedup"] = rep["tokens_per_s"] / base
    return out


def _router_lines(reports: Dict[int, dict]) -> List[str]:
    lines = []
    for n, rep in sorted(reports.items()):
        lines.append(
            f"  x{n} verifiers: {rep['tokens_per_s']:.1f} tok/s"
            f" ({rep['speedup']:.2f}x) spread={rep['spread']}"
            f" tokens/NAV={rep['tokens_per_nav']:.2f}"
            f" nav p50={rep['nav_p50_ms']:.1f}ms p99={rep['nav_p99_ms']:.1f}ms"
            f" bytes/session={rep['bytes_per_session']:.0f}"
            f" failovers={rep['failovers']}"
        )
    return lines


def router(verifier_counts: Tuple[int, ...] = (1, 2, 4)) -> Tuple[list, List[str]]:
    """Harness entry (benchmarks.run): one CSV row per fleet size.

    ``us_per_call`` is microseconds per committed token (1e6 / tokens/s), so
    smaller is better and the x1 -> x4 drop IS the scaling claim.  Rows are
    deterministic (virtual clock, oracle fleet): this is what lands in
    ``BENCH_fleet.json``.
    """
    reports = router_bench(verifier_counts)
    rows, lines = [], []
    for n, rep in sorted(reports.items()):
        row = dict(
            n_verifiers=n,
            n_sessions=rep["n_sessions"],
            tokens_per_s=rep["tokens_per_s"],
            speedup=rep["speedup"],
            tokens_per_nav=rep["tokens_per_nav"],
            nav_p50_ms=rep["nav_p50_ms"],
            nav_p99_ms=rep["nav_p99_ms"],
            bytes_per_session=rep["bytes_per_session"],
            placement_spread=rep["spread"],
            failovers=rep["failovers"],
        )
        rows.append(row)
        derived = (
            f"tokens_per_s={rep['tokens_per_s']:.1f};speedup={rep['speedup']:.2f};"
            f"spread={rep['spread']};tokens_per_nav={rep['tokens_per_nav']:.2f};"
            f"nav_p50_ms={rep['nav_p50_ms']:.1f};nav_p99_ms={rep['nav_p99_ms']:.1f};"
            f"bytes_per_session={rep['bytes_per_session']:.0f};"
            f"failovers={rep['failovers']}"
        )
        lines.append(csv_row(f"fleet/router/x{n}", 1e6 / rep["tokens_per_s"], derived))
    return rows, lines


def export_fleet_trace(seed: int = 0) -> str:
    """Chrome-trace JSON of a seeded, traced router fleet run.

    The export is a pure function of ``seed``: spans are stamped off the
    run's ``VirtualClock`` and serialized with sorted keys, so two calls
    with the same seed return byte-identical JSON on any host — the CI
    obs-smoke job diffs exactly that.
    """
    rep = run_router_fleet(2, n_sessions=8, tokens_per_session=30, seed=seed, traced=True)
    return rep["_tracer"].export_chrome_trace()


def fleet_committed() -> Tuple[list, List[str]]:
    """Harness entry (benchmarks.run): every row family of BENCH_fleet.json.

    Four families, all deterministic except where marked:

    * ``router/*`` — the scaling sweep (``router()`` rows, unchanged);
    * ``router/x1_traced`` — the tracing overhead gate: the SAME x1 run
      with the full obs stack attached.  Tracing only reads the virtual
      clock, so ``tokens_per_s`` must equal the untraced x1 row exactly
      (``overhead_pct == 0.0`` committed — far inside the <2% budget);
    * ``chaos/*`` — per-fault-scenario recovery/fallback counters
      (recovery latency, lost drafts, failovers: the chaos contract);
    * ``codec/*`` — frame sizes (exact) + ``host_ns_per_msg`` (wall-time,
      informational: bench_diff skips ``host_``-prefixed fields).
    """
    rows, lines = router()
    untraced = next(r["tokens_per_s"] for r in rows if r["n_verifiers"] == 1)
    rep = run_router_fleet(1, traced=True)
    overhead_pct = (untraced - rep["tokens_per_s"]) / untraced * 100.0
    rows.append(
        dict(
            name="router/x1_traced",
            tokens_per_s=rep["tokens_per_s"],
            tokens_per_nav=rep["tokens_per_nav"],
            nav_p50_ms=rep["nav_p50_ms"],
            nav_p99_ms=rep["nav_p99_ms"],
            n_spans=rep["n_spans"],
            overhead_pct=overhead_pct,
        )
    )
    lines.append(
        csv_row(
            "fleet/router/x1_traced",
            1e6 / rep["tokens_per_s"],
            f"tokens_per_s={rep['tokens_per_s']:.1f};n_spans={rep['n_spans']};"
            f"overhead_pct={overhead_pct:.3f}",
        )
    )
    chaos_rows, chaos_lines = chaos()
    rows.extend(chaos_rows)
    lines.extend(chaos_lines)
    codec_rows, codec_lines = codec_bench()
    rows.extend(codec_rows)
    lines.extend(codec_lines)
    return rows, lines


def _row(rep: dict, **extra) -> Tuple[dict, str]:
    st: RunStats = rep["stats"]
    p50, p99 = st.nav_latency_quantiles()
    tpts = list(rep["per_session_tpt"].values()) or [float("nan")]
    row = dict(
        scenario=rep["scenario"],
        mode=rep["mode"],
        variant=rep["variant"],
        occupancy=st.verifier_batch_occupancy,
        tpt_ms=float(np.mean(tpts)) * 1e3,
        tokens_per_nav=st.tokens_per_nav,
        nav_p50_ms=p50 * 1e3,
        nav_p99_ms=p99 * 1e3,
        **extra,
    )
    derived = (
        f"occupancy={st.verifier_batch_occupancy:.2f};queue={st.mean_queue_depth:.2f};"
        f"tokens_per_nav={st.tokens_per_nav:.2f};"
        f"nav_p50={p50*1e3:.1f}ms;nav_p99={p99*1e3:.1f}ms;failovers={rep['failovers']}"
    )
    return row, derived


def fleet(scenarios=(1, 2, 3, 4), n_sessions: int = 8) -> Tuple[list, List[str]]:
    """Harness entry (benchmarks.run): CSV rows per scenario.

    Three row families: the historical batched-vs-per_session chain rows
    (``fleet/scenN/{mode}``, unchanged stream statistics so they stay
    comparable across commits), the chain-vs-tree speculation comparison
    on a hard stream (``fleet/scenN/cmp/{variant}``), and the paged-vs-flat
    verifier-KV comparison at a fixed pool budget (``fleet/kv/{layout}``,
    scenario 1).
    """
    rows, lines = [], []
    for scen in scenarios:
        for mode in MODES:
            rep = run_fleet(n_sessions=n_sessions, mode=mode, scen=scen)
            row, derived = _row(rep)
            rows.append(row)
            lines.append(csv_row(f"fleet/scen{scen}/{mode}", row["tpt_ms"] * 1e3, derived))
        for variant, rep in compare_tree(scenarios=(scen,), n_sessions=n_sessions)[scen].items():
            row, derived = _row(rep, p_hard=0.35)
            rows.append(row)
            lines.append(csv_row(f"fleet/scen{scen}/cmp/{variant}", row["tpt_ms"] * 1e3, derived))
    kv_reps = compare_kv(n_sessions=2 * n_sessions)
    for name in ("flat", "paged", "paged_matched"):
        rep = kv_reps[name]
        st: RunStats = rep["stats"]
        row, derived = _row(rep)
        row.update(
            kv=name,
            kv_max_clients=rep["kv_max_clients"],
            kv_bytes_per_session=st.kv_bytes_per_session,
            kv_peak_bytes=st.peak_kv_resident_bytes,
        )
        rows.append(row)
        derived += (
            f";kv_max_clients={rep['kv_max_clients']};attached={rep['n_attached']}"
            f";kv_per_session_mib={st.kv_bytes_per_session/2**20:.1f}"
            f";kv_peak_mib={st.peak_kv_resident_bytes/2**20:.0f}"
            f";kv_overhead_pct={rep['kv_overhead_frac']*100:.2f}"
        )
        lines.append(csv_row(f"fleet/kv/{name}", row["tpt_ms"] * 1e3, derived))
    codec_rows, codec_lines = codec_bench()
    rows.extend(codec_rows)
    lines.extend(codec_lines)
    return rows, lines


def main() -> None:
    if len(sys.argv) > 1 and sys.argv[1] == "router":
        # Deterministic router-scaling report (virtual clock, oracle fleet).
        print("=== router scaling, 16 oracle sessions, per-session serving ===")
        for line in _router_lines(router_bench()):
            print(line)
        return
    if len(sys.argv) > 1 and sys.argv[1] == "trace":
        # Seeded Chrome-trace export (virtual clock): byte-identical across
        # runs/hosts for a given seed — the CI obs-smoke job diffs two of
        # these.  Usage: fleet_bench.py trace OUT.json [seed]
        if len(sys.argv) < 3:
            sys.exit("usage: fleet_bench.py trace OUT.json [seed]")
        try:
            seed = int(sys.argv[3]) if len(sys.argv) > 3 else 0
        except ValueError:
            sys.exit(f"usage: fleet_bench.py trace OUT.json [seed]  (got {sys.argv[3]!r})")
        blob = export_fleet_trace(seed=seed)
        Path(sys.argv[2]).write_text(blob)
        print(f"TRACE {sys.argv[2]} {len(blob)} bytes seed={seed}")
        return
    if len(sys.argv) > 1 and sys.argv[1] == "chaos":
        # Deterministic chaos report (virtual clock): every printed value is
        # a pure function of the seed, so CI diffs two runs byte-for-byte.
        try:
            seed = int(sys.argv[2]) if len(sys.argv) > 2 else 0
        except ValueError:
            sys.exit(f"usage: fleet_bench.py [chaos [seed] | n_sessions]  (got {sys.argv[2]!r})")
        print(f"=== chaos matrix, oracle fleet, virtual clock, seed {seed} ===")
        for line in _chaos_lines(run_chaos(seed=seed)):
            print(line)
        return
    try:
        n = int(sys.argv[1]) if len(sys.argv) > 1 else 8
    except ValueError:
        sys.exit(f"usage: fleet_bench.py [chaos [seed] | n_sessions]  (got {sys.argv[1]!r})")
    print(f"=== fleet serving, {n} edge sessions, Poisson arrivals, scenario 1 ===")
    reports = {mode: run_fleet(n_sessions=n, mode=mode, scen=1) for mode in MODES}
    for mode in MODES:
        for line in _report_lines(reports[mode]):
            print(line)
    occ = reports["batched"]["stats"].verifier_batch_occupancy
    p99_solo = reports["per_session"]["stats"].nav_latency_quantiles()[1]
    p99_batch = reports["batched"]["stats"].nav_latency_quantiles()[1]
    print(
        f"batched verifier occupancy {occ:.2f} (>1 amortizes the target forward);"
        f" p99 NAV {p99_solo*1e3:.1f}ms -> {p99_batch*1e3:.1f}ms"
    )
    print(f"=== chain vs tree speculation, {n} sessions, batched serving ===")
    for scen, reps in compare_tree(n_sessions=n).items():
        for variant in VARIANTS:
            for line in _report_lines(reps[variant]):
                print(f"scen{scen}{line}")
        tc = reps["chain"]["stats"].tokens_per_nav
        tt = reps["tree"]["stats"].tokens_per_nav
        print(f"scen{scen}: tokens/NAV chain={tc:.2f} tree={tt:.2f} ({'tree' if tt > tc else 'chain'} wins)")
    kv_reps = compare_kv(n_sessions=2 * n)
    budget = kv_reps["kv_budget_bytes"]
    print(f"=== paged vs flat verifier KV, {2*n} offered sessions, {budget/2**20:.0f}MiB pool ===")
    for name in ("flat", "paged", "paged_matched"):
        for line in _report_lines(kv_reps[name]):
            print(f"{name:<14}{line}")
    flat_cap = kv_reps["flat"]["n_attached"]
    paged_cap = kv_reps["paged"]["kv_max_clients"]
    tpt_flat = float(np.mean(list(kv_reps["flat"]["per_session_tpt"].values())))
    tpt_match = float(np.mean(list(kv_reps["paged_matched"]["per_session_tpt"].values())))
    print(
        f"same {budget/2**20:.0f}MiB budget: flat serves {flat_cap} sessions, paged serves"
        f" {paged_cap} ({'paged' if paged_cap > flat_cap else 'flat'} wins);"
        f" matched-load TPT {tpt_flat*1e3:.0f}ms vs {tpt_match*1e3:.0f}ms"
        f" (wall-clock, scheduler-noisy); pool bookkeeping"
        f" {kv_reps['paged_matched']['kv_overhead_frac']*100:.2f}% of serving time"
        f" bounds the paging TPT cost (sim parity is exact)"
    )
    codec_rows, _ = codec_bench(n_iters=20_000)
    print("=== wire-codec overhead (encode+decode round trip) ===")
    for row in codec_rows:
        ns = row["host_ns_per_msg"]
        per_tok_ns = ns / 16 if row["message"] == "draft16" else ns
        print(
            f"  {row['message']:<12} {ns:>8.0f} ns/msg"
            f" {row['frame_bytes']:>4d} B/frame"
            f"  ({per_tok_ns/2e6*100:.4f}% of the 2ms/token link budget)"
        )


if __name__ == "__main__":
    main()
