"""Two-process cloud-edge serving over the socket transport.

This is the paper's testbed shape (edge client and cloud verifier as
separate machines talking over the network) on the repo's typed wire
protocol: the cloud process runs ``CloudVerifier`` behind a
``SocketListener``, the edge process dials it with ``connect_transport``
(``Hello``/``Attach`` version handshake) and streams tokens through
``EdgeClient`` over length-prefixed protocol frames.

Run the two roles in two shells (or two machines)::

    PYTHONPATH=src python launch/serve.py --listen 127.0.0.1:7421 --sessions 1
    PYTHONPATH=src python launch/serve.py --connect 127.0.0.1:7421 --tokens 64

With the default deterministic oracle draft/backend pair, the edge
process's committed stream equals the oracle stream exactly — compare
with::

    PYTHONPATH=src python launch/serve.py --print-oracle 64

(``--check-oracle`` makes the client do that diff itself and exit
non-zero on any mismatch.)  ``--demo`` runs both roles over a loopback
socket in one process.

``--router`` runs the multi-verifier control plane in front of a fleet:
clients dial the router exactly as they would a lone verifier; sessions
are placed least-loaded and can live-migrate between fleet members
mid-stream.  The fleet is either in-process (``--verifiers N``) or
remote verifier processes (repeatable ``--verifier HOST:PORT``)::

    PYTHONPATH=src python launch/serve.py --listen 127.0.0.1:7431 --sessions 0
    PYTHONPATH=src python launch/serve.py --listen 127.0.0.1:7432 --sessions 0
    PYTHONPATH=src python launch/serve.py --router 127.0.0.1:7421 \\
        --verifier 127.0.0.1:7431 --verifier 127.0.0.1:7432 --migrate-every 0.3
    PYTHONPATH=src python launch/serve.py --connect 127.0.0.1:7421 \\
        --tokens 64 --check-oracle

``--migrate-every S`` forces a round-robin migration sweep every S
seconds — the committed stream must stay oracle-exact through every
hand-off (this is the CI router-smoke job).

``--metrics-port N`` (cloud or router role) starts the live telemetry
endpoint next to the listener — Prometheus text at ``/metrics``, JSON at
``/snapshot`` — announced as ``METRICS host:port`` (0 = ephemeral).  The
terminal fleet dashboard polls it::

    PYTHONPATH=src python launch/serve.py --router 127.0.0.1:7421 \\
        --verifiers 2 --metrics-port 9100
    PYTHONPATH=src python launch/serve.py --dashboard 127.0.0.1:9100
    python -m repro.obs.dashboard 127.0.0.1:9100        # equivalent

``--backend spec`` swaps in the real fused NAV verifier over a paged KV
pool, with a seeded synthetic target at the widths of ``--arch`` (default
``granite-3-2b``: 32 query heads, 8 KV heads, head_dim 64, vocab 49155, a
40-layer pool of 16-token pages).  ``--impl`` names the kernel and is never
changed behind your back: ``pallas`` (default) is the compiled TPU kernel,
``interpret`` runs it under the CPU interpreter, ``ref`` is the pure-JAX
oracle.  ``--shards N`` shards the target forward across an N-device mesh
(``ShardedSpecVerifyBackend``: head-parallel pages, one ``shard_map``
launch per dispatch, XLA code rather than Pallas, so it takes ``--impl
ref``).  On a CPU host, export
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` first so the mesh
has N devices; the wire protocol and every client stay oblivious to N::

    PYTHONPATH=src python launch/serve.py --listen 127.0.0.1:7421 \\
        --backend spec --sessions 1                      # one TPU chip
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
    PYTHONPATH=src python launch/serve.py --listen 127.0.0.1:7421 \\
        --backend spec --impl ref --shards 4 --sessions 1

JAX's persistent compile cache goes where ``JAX_COMPILATION_CACHE_DIR``
points, or else to ``.jax_cache/`` in the checkout
(``repro.launch.compile_cache``).
"""

from __future__ import annotations

import argparse
import math
import sys
from pathlib import Path
from typing import Tuple

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

from repro.launch.compile_cache import place_compile_cache  # noqa: E402 (path bootstrap above)
from repro.runtime import (  # noqa: E402
    SYSTEM_CLOCK,
    ChannelConfig,
    CloudVerifier,
    Detach,
    EdgeClient,
    EdgeConfig,
    LocalVerifier,
    OracleBackend,
    OracleDraft,
    OracleStream,
    RemoteVerifier,
    Router,
    SocketListener,
    SyntheticBackend,
    SyntheticDraft,
    connect_transport,
)
from repro.runtime.server import IMPLS  # noqa: E402


# Spec backend pool: 512 pages of 16 tokens hold 8k tokens — a few sessions
# of 1-2k context; at granite-3-2b widths that is 1.3 GB of fp32 KV.
KV_BLOCKS, PAGE_SIZE = 512, 16


def _host_port(spec: str) -> Tuple[str, int]:
    host, _, port = spec.rpartition(":")
    if not host or not port.isdigit():
        raise argparse.ArgumentTypeError(f"expected HOST:PORT, got {spec!r}")
    return host, int(port)


def _start_metrics_endpoint(args, source):
    """Start a ``TelemetryEndpoint`` when ``--metrics-port`` asks for one.

    Announced as ``METRICS host:port`` right after the listener's own
    ``LISTENING`` line so harnesses can scrape the ephemeral port.
    """
    if args.metrics_port is None:
        return None
    from repro.obs.endpoint import TelemetryEndpoint

    ep = TelemetryEndpoint(source, host="127.0.0.1", port=args.metrics_port)
    print(f"METRICS {ep.host}:{ep.port}", flush=True)
    return ep


def run_server(args) -> int:
    """Cloud role: listen, attach socket sessions, serve until they finish."""
    host, port = args.listen
    backend, cv_kwargs = _make_backend(args)
    verifier = CloudVerifier(backend, batch_window=args.batch_window, **cv_kwargs)
    listener = SocketListener(
        lambda sid, transport: verifier.attach(sid, transport, transport),
        host=host,
        port=port,
    )
    verifier.start()
    # Port 0 binds ephemerally; announce the real port for the client side.
    print(f"LISTENING {listener.host}:{listener.port}", flush=True)
    endpoint = _start_metrics_endpoint(args, verifier.telemetry_snapshot)
    try:
        while True:
            SYSTEM_CLOCK.sleep(0.1)
            done = sum(t.closed for t in listener.transports)
            if args.sessions and done >= args.sessions:
                break
    except KeyboardInterrupt:
        pass
    finally:
        if endpoint is not None:
            endpoint.close()
        listener.close()
        verifier.stop()
    s = verifier.stats
    print(
        f"SERVED sessions={listener.stats['accepted']} nav_calls={s['nav_calls']}"
        f" tokens_verified={s['tokens_verified']} batched_calls={s['batched_calls']}",
        flush=True,
    )
    return 0


def _make_backend(args):
    """Build ``(backend, extra CloudVerifier kwargs)`` for the chosen mode."""
    if args.backend == "spec":
        return _spec_backend(args)
    if args.backend == "oracle":
        backend = OracleBackend(
            seed=args.seed, verify_time=args.verify_time, verify_time_per_token=0.0
        )
        return backend, {}
    return SyntheticBackend(seed=args.seed, verify_time=args.verify_time), {}


def _spec_backend(args):
    """The real fused NAV verifier at the widths of ``--arch``.

    A tensor-mode paged KV pool (``n_layers`` deep, ``KV_BLOCKS`` pages of
    ``PAGE_SIZE`` tokens) and a seeded deterministic target
    (per-position queries + LM head ``[n_heads * head_dim, vocab]``) drive
    ``SpecVerifyBackend(fused=True, impl=--impl)`` — one fused Pallas
    launch per dispatch — or, with ``--shards N > 1``,
    ``ShardedSpecVerifyBackend`` over N devices.  The dispatcher and the
    wire protocol are oblivious to both choices.
    """
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.models.paged_kv import PagedKVPool
    from repro.runtime import ShardedSpecVerifyBackend, SpecVerifyBackend

    cfg = get_config(args.arch)
    H, Hkv, hd, V = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.vocab_size
    pool = PagedKVPool(
        num_blocks=KV_BLOCKS, block_size=PAGE_SIZE, n_layers=cfg.n_layers,
        n_kv_heads=Hkv, head_dim=hd, quantize="int8" if args.kv_quant == "int8" else None,
    )
    key = jax.random.PRNGKey(args.seed)
    # Scaled by 1/sqrt(fan-in) so the logit spread does not grow with width.
    w = jax.random.normal(jax.random.fold_in(key, 77), (H * hd, V)) * (16.0 / math.sqrt(H * hd))

    def query_fn(session, tokens):
        k = jax.random.fold_in(jax.random.fold_in(key, 88), session * 131 + len(tokens))
        return np.asarray(jax.random.normal(k, (len(tokens) + 1, H, hd)), np.float32)

    kw = dict(kv_pool=pool, query_fn=query_fn, lm_head=w, impl=args.impl)
    if args.shards > 1:
        backend = ShardedSpecVerifyBackend(shards=args.shards, **kw)
    else:
        backend = SpecVerifyBackend(fused=True, **kw)
    return backend, {"kv_pool": pool}


def run_router(args) -> int:
    """Control-plane role: route socket clients across a verifier fleet."""
    host, port = args.router
    fleet = []
    for vhost, vport in args.verifier or ():
        fleet.append(
            RemoteVerifier(
                len(fleet), vhost, vport, cfg=ChannelConfig(alpha=0.001, beta=0.0001)
            )
        )
    for _ in range(args.verifiers):
        backend, cv_kwargs = _make_backend(args)
        v = CloudVerifier(backend, batch_window=args.batch_window, **cv_kwargs)
        v.start()
        fleet.append(LocalVerifier(len(fleet), v))
    if not fleet:
        print("--router needs --verifier HOST:PORT and/or --verifiers N", file=sys.stderr)
        return 2
    router = Router(fleet, rebalance_interval=args.migrate_every)
    # FleetFullError propagates into the listener, which hangs up on the
    # refused client; everyone already placed keeps streaming.
    listener = SocketListener(
        lambda sid, t: router.attach(sid, t, t), host=host, port=port
    )
    router.start()
    print(f"LISTENING {listener.host}:{listener.port}", flush=True)
    endpoint = _start_metrics_endpoint(args, router.telemetry)
    try:
        while True:
            SYSTEM_CLOCK.sleep(0.1)
            done = sum(1 for rs in list(router.sessions.values()) if rs.done)
            if args.sessions and done >= args.sessions:
                break
    except KeyboardInterrupt:
        pass
    finally:
        if endpoint is not None:
            endpoint.close()
        listener.close()
        router.stop()
        for vc in fleet:
            vc.stop()
    s = router.stats
    print(
        f"ROUTED sessions={s['sessions_placed']} migrations={s['migrations']}"
        f" failover_migrations={s['failover_migrations']} drains={s['drains']}"
        f" crashes={s['verifier_crashes']} refusals={s['admission_refusals']}",
        flush=True,
    )
    return 0


def stream_session(args, session: int):
    """Dial ``args.connect`` as ``session``, stream ``--tokens`` tokens, detach.

    Returns ``(session id granted by the server, committed stream, client
    stats)``; the edge role and ``chip_smoke.py`` both stream through it.
    """
    host, port = args.connect
    transport = connect_transport(
        host, port, session=session, cfg=ChannelConfig(alpha=0.001, beta=0.0001)
    )
    if args.draft == "oracle":
        draft = OracleDraft(seed=args.seed)
    else:
        draft = SyntheticDraft(seed=args.seed)
    cfg = EdgeConfig(gamma=args.gamma, window=8, nav_timeout=args.nav_timeout)
    client = EdgeClient(transport.session, transport, transport, cfg, draft=draft)
    stats = client.run(args.tokens)
    client.seq += 1
    transport.send(Detach(session=transport.session, seq=client.seq))
    transport.close()
    return transport.session, client.tokens[: args.tokens], stats


def run_client(args) -> int:
    """Edge role: dial the cloud, stream ``--tokens`` tokens, print them."""
    session, stream, stats = stream_session(args, args.session)
    for tok in stream:
        print(tok)
    print(
        f"# session={session} rounds={stats['rounds']}"
        f" accepted={stats['accepted_tokens']} failovers={stats['failovers']}"
        f" wall={stats['wall_time']:.2f}s",
        file=sys.stderr,
    )
    if args.check_oracle:
        expect = OracleStream(args.seed).prefix(len(stream))
        if stream != expect:
            print("# ORACLE MISMATCH", file=sys.stderr)
            return 1
        print("# stream == oracle: OK", file=sys.stderr)
    return 0


def run_demo(args) -> int:
    """Both roles over a loopback socket in one process (quickstart)."""
    backend = OracleBackend(seed=args.seed, verify_time=args.verify_time, verify_time_per_token=0.0)
    verifier = CloudVerifier(backend, batch_window=args.batch_window)
    listener = SocketListener(
        lambda sid, t: verifier.attach(sid, t, t), host="127.0.0.1", port=0
    )
    verifier.start()
    args.connect = (listener.host, listener.port)
    args.check_oracle = True
    try:
        return run_client(args)
    finally:
        listener.close()
        verifier.stop()


def build_parser() -> argparse.ArgumentParser:
    """The launcher's command line (shared with ``chip_smoke.py``)."""
    p = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    role = p.add_mutually_exclusive_group(required=True)
    role.add_argument("--listen", type=_host_port, metavar="HOST:PORT", help="run the cloud verifier")
    role.add_argument("--connect", type=_host_port, metavar="HOST:PORT", help="run the edge client")
    role.add_argument("--router", type=_host_port, metavar="HOST:PORT", help="run the fleet router")
    role.add_argument("--demo", action="store_true", help="loopback demo: both roles, one process")
    role.add_argument(
        "--print-oracle", type=int, metavar="N", help="print the first N oracle tokens and exit"
    )
    role.add_argument(
        "--dashboard", type=_host_port, metavar="HOST:PORT",
        help="render the live fleet dashboard from a --metrics-port endpoint",
    )
    p.add_argument("--seed", type=int, default=7, help="oracle/synthetic seed (must match across roles)")
    p.add_argument("--backend", choices=("oracle", "synthetic", "spec"), default="oracle")
    p.add_argument(
        "--arch", default="granite-3-2b",
        help="spec backend: model config whose widths the synthetic target takes",
    )
    p.add_argument(
        "--impl", choices=IMPLS, default="pallas",
        help="spec backend: compiled TPU kernel, CPU interpreter, or pure-JAX oracle",
    )
    p.add_argument(
        "--shards", type=int, default=1,
        help="spec backend: shard the target verify over N mesh devices (needs --impl ref)",
    )
    p.add_argument(
        "--kv-quant", choices=("none", "int8"), default="none",
        help="spec backend: paged-KV page storage (int8 = quantized pages)",
    )
    p.add_argument("--draft", choices=("oracle", "synthetic"), default="oracle")
    p.add_argument("--sessions", type=int, default=1, help="server exits after N sessions finish (0 = forever)")
    p.add_argument("--session", type=int, default=0, help="client's proposed session id")
    p.add_argument("--tokens", type=int, default=64, help="tokens to stream per client")
    p.add_argument(
        "--check-oracle", action="store_true",
        help="client: verify the committed stream equals the oracle stream (exit 1 on mismatch)",
    )
    p.add_argument(
        "--verifier", type=_host_port, action="append", metavar="HOST:PORT",
        help="router: add a remote fleet member (repeatable)",
    )
    p.add_argument(
        "--verifiers", type=int, default=0,
        help="router: number of in-process fleet members to spawn",
    )
    p.add_argument(
        "--migrate-every", type=float, default=None, metavar="S",
        help="router: force a round-robin migration sweep every S seconds",
    )
    p.add_argument(
        "--metrics-port", type=int, default=None, metavar="N",
        help="server/router: HTTP telemetry endpoint port (0 = ephemeral, "
        "announced as 'METRICS host:port'); serves /metrics and /snapshot",
    )
    p.add_argument(
        "--once", action="store_true",
        help="dashboard: draw one frame and exit (no ANSI clear)",
    )
    p.add_argument(
        "--interval", type=float, default=1.0, metavar="S",
        help="dashboard: poll period [s]",
    )
    p.add_argument("--gamma", type=float, default=0.005, help="edge per-token draft time [s]")
    p.add_argument("--nav-timeout", type=float, default=5.0, help="edge NAV timeout before failover [s]")
    p.add_argument("--batch-window", type=float, default=0.002, help="server NAV coalescing window [s]")
    p.add_argument("--verify-time", type=float, default=0.002, help="simulated target forward time [s]")
    return p


def main(argv=None) -> int:
    """CLI entry: ``--listen`` (cloud), ``--connect`` (edge), or helpers."""
    args = build_parser().parse_args(argv)
    place_compile_cache()
    if args.print_oracle is not None:
        for tok in OracleStream(args.seed).prefix(args.print_oracle):
            print(tok)
        return 0
    if args.dashboard:
        from repro.obs.dashboard import run_dashboard

        host, port = args.dashboard
        drawn = run_dashboard(
            host, port, interval=args.interval, frames=1 if args.once else None
        )
        return 0 if drawn else 1
    if args.demo:
        return run_demo(args)
    if args.listen:
        return run_server(args)
    if args.router:
        return run_router(args)
    return run_client(args)


if __name__ == "__main__":
    raise SystemExit(main())
