"""Smoke run of the served NAV verify path on TPU chips.

Edge clients stream tokens over a loopback socket to a ``CloudVerifier``
whose backend ``launch/serve.py --backend spec`` builds: a seeded synthetic
target at granite-3-2b widths (32 query heads, 8 KV heads, head_dim 64,
vocab 49155, a 40-layer pool of 16-token pages) behind the fused verify.
Everything runs in this one process, because a chip belongs to one process.

    python chip_smoke.py              # one chip
    python chip_smoke.py --shards 4   # four chips

One chip: the served verify is the compiled Pallas kernel
(``--impl pallas``).  The script checks that its compiled program holds the
kernel (``tpu_custom_call``), serves the clients, and re-runs every round
the server dispatched through ``impl='ref'`` on the same chip: verdicts must
match exactly, log-probs of in-vocabulary drafts within ``atol=1e-4,
rtol=1e-5``.

``--shards 4`` runs only the tensor-parallel path: the same clients through
``ShardedSpecVerifyBackend`` over four chips, each round re-run through the
one-chip Pallas kernel; per-round verdicts must be identical, the mesh must
span four devices and the pool's pages must live on all four.

The last line of standard output is ``{"ok": true, "device": {...}}`` and is
printed only when every phase passed.  It exits non-zero, printing no result,
when JAX finds no TPU.  The other lines are smoke output, not benchmark
metrics.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import math
import sys
import threading
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
CLIENTS, TOKENS, SEED = 8, 64, 7  # edge sessions, tokens each streams, target/draft seed
WINDOW = 8  # drafts per round at most: stream_session's EdgeConfig(window=8)
DEADLINE = 900.0  # [s] for all clients to finish, compiles included
LOGP_ATOL, LOGP_RTOL = 1e-4, 1e-5  # docs/kernels.md, verification kernels on the chip


def _fail(msg: str) -> int:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    return 1


def _smoke(**fields) -> None:
    print("smoke " + " ".join(f"{k}={v}" for k, v in fields.items()), flush=True)


def _load_launcher():
    spec = importlib.util.spec_from_file_location("serve", ROOT / "launch" / "serve.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _compile_check(backend) -> float:
    """Compile the served fused verify at its full-batch shape; check the kernel.

    Returns the compile seconds (cold, or a persistent-cache hit).
    """
    import jax
    import jax.numpy as jnp

    from repro.kernels.spec_verify import spec_verify_fused

    pool = backend.kv_pool
    H, hd = backend.lm_head.shape[0] // pool.head_dim, pool.head_dim
    B = 1 << (CLIENTS - 1).bit_length()
    K1 = WINDOW + 1
    G = 1 << (math.ceil((TOKENS + 2 * WINDOW) / pool.block_size) - 1).bit_length()
    shape = jax.ShapeDtypeStruct
    args = (
        shape((B, K1, H, hd), jnp.float32),
        shape(pool.k_pages.shape[1:], pool.k_pages.dtype),
        shape(pool.v_pages.shape[1:], pool.v_pages.dtype),
        shape(backend.lm_head.shape, jnp.float32),
        shape((B, G), jnp.int32),
        shape((B, K1), jnp.int32),
        shape((B, WINDOW), jnp.int32),
        shape((B,), jnp.int32),
    )
    t0 = time.perf_counter()
    compiled = spec_verify_fused.lower(*args, impl=backend.impl, block_v=backend.block_v).compile()
    seconds = time.perf_counter() - t0
    if "tpu_custom_call" not in compiled.as_text():
        raise AssertionError("the compiled fused verify holds no tpu_custom_call (no Pallas kernel)")
    _smoke(phase="compile", B=B, K1=K1, G=G, block_v=backend.block_v, compile_s=f"{seconds:.3f}")
    return seconds


def _serve(serve, args, backend, cv_kwargs, check, deadline: float):
    """Serve ``CLIENTS`` edge sessions; re-run each dispatched round via ``check``.

    Returns ``(per-client results, per-round comparisons, errors)``.
    """
    from repro.runtime import CloudVerifier, SocketListener

    rounds, errors = [], []
    served_fn = backend.fused_verify

    def fused_verify(requests):
        try:
            served = served_fn(requests)
            rounds.append((requests, served, check(backend.fused_inputs(requests))))
            return served
        except BaseException:
            errors.append(traceback.format_exc())
            raise

    backend.fused_verify = fused_verify
    verifier = CloudVerifier(backend, batch_window=args.batch_window, **cv_kwargs)
    listener = SocketListener(lambda sid, t: verifier.attach(sid, t, t), host="127.0.0.1", port=0)
    verifier.start()
    args.connect = (listener.host, listener.port)
    results = {}

    def client(i):
        try:
            results[i] = serve.stream_session(args, session=i)
        except BaseException:
            errors.append(traceback.format_exc())

    threads = [threading.Thread(target=client, args=(i,), daemon=True) for i in range(CLIENTS)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(max(deadline - time.monotonic(), 0.0))
    finally:
        listener.close()
        verifier.stop()
    stalled = [i for i, t in enumerate(threads) if t.is_alive()]
    if stalled:
        errors.append(f"clients {stalled} still streaming at the deadline")
    return results, rounds, errors, verifier.stats


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--shards", type=int, choices=(1, 4), default=1,
                   help="4: only the sharded four-chip path against the one-chip kernel")
    opts = p.parse_args(argv)
    if not (ROOT / "launch" / "serve.py").is_file() or not (ROOT / "src" / "repro").is_dir():
        return _fail(f"{ROOT} holds no checkout of this repository (launch/serve.py, src/repro)")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import place_compile_cache

    cache = place_compile_cache()
    import jax
    import numpy as np

    from repro.kernels.spec_verify import spec_verify_fused_batched

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        return _fail(f"JAX found platform {dev.platform!r} ({dev.device_kind}), not a TPU")
    if len(devices) < opts.shards:
        return _fail(f"--shards {opts.shards} needs {opts.shards} chips, JAX sees {len(devices)}")
    _smoke(phase="start", platform=dev.platform, kind=repr(dev.device_kind),
           count=len(devices), compile_cache=cache)

    serve = _load_launcher()
    impl = "pallas" if opts.shards == 1 else "ref"
    args = serve.build_parser().parse_args([
        "--listen", "127.0.0.1:0", "--backend", "spec", "--impl", impl,
        "--shards", str(opts.shards), "--seed", str(SEED), "--tokens", str(TOKENS),
        "--nav-timeout", str(DEADLINE),
    ])
    t_start = time.perf_counter()
    try:
        backend, cv_kwargs = serve._make_backend(args)
        if opts.shards == 1:
            _compile_check(backend)
            check_impl = "ref"
        else:
            mesh_devices = set(backend.mesh.devices.flat)
            page_devices = backend.kv_pool.k_pages.sharding.device_set
            _smoke(phase="mesh", mesh_devices=len(mesh_devices), page_devices=len(page_devices),
                   pages_spec=repr(str(backend.kv_pool.k_pages.sharding.spec)))
            if len(mesh_devices) != 4 or page_devices != mesh_devices:
                return _fail(f"mesh spans {len(mesh_devices)} devices, pages live on {len(page_devices)}")
            check_impl = "pallas"

        def check(inputs):
            if opts.shards > 1:  # the one-chip kernel reads its own copy of the pages
                inputs = {k: jax.device_put(v, dev) if isinstance(v, jax.Array) else v
                          for k, v in inputs.items()}
            return spec_verify_fused_batched(**inputs, impl=check_impl, block_v=backend.block_v)

        deadline = time.monotonic() + DEADLINE
        results, rounds, errors, stats = _serve(serve, args, backend, cv_kwargs, check, deadline)
    except Exception:
        traceback.print_exc()
        return _fail("a phase raised")
    wall = time.perf_counter() - t_start
    if errors:
        print("\n".join(errors), file=sys.stderr)
        return _fail(f"{len(errors)} error(s) while serving")

    bad = []
    for i in range(CLIENTS):
        if i not in results:
            bad.append(f"client {i}: no result")
            continue
        _, stream, st = results[i]
        _smoke(phase="client", session=i, committed=len(stream), rounds=st["rounds"],
               accepted=st["accepted_tokens"], failovers=st["failovers"])
        if st["failovers"] or len(stream) < TOKENS:
            bad.append(f"client {i}: {len(stream)} tokens, {st['failovers']} failovers")
    # Edge drafts draw ids from 2**16, past the target's vocab: those never
    # match, and have no log-prob, so log-probs are compared in-vocab only.
    vocab = backend.lm_head.shape[1]
    verdict_mismatch, worst_logp, n_checked, n_logp = 0, 0.0, 0, 0
    for requests, served, other in rounds:
        for (_, toks, _), (na, corr, lp), (na2, corr2, lp2) in zip(requests, served, other):
            n_checked += 1
            verdict_mismatch += (int(na), int(corr)) != (int(na2), int(corr2))
            keep = np.asarray(toks) < vocab
            lp, lp2 = np.asarray(lp)[keep], np.asarray(lp2)[keep]
            if lp.size:
                n_logp += lp.size
                worst_logp = max(worst_logp, float(np.max(np.abs(lp - lp2))))
                if opts.shards == 1 and not np.allclose(lp, lp2, atol=LOGP_ATOL, rtol=LOGP_RTOL):
                    bad.append(f"logp off by {np.max(np.abs(lp - lp2))}")
    if not rounds:
        bad.append("the server dispatched no verify round")
    if verdict_mismatch:
        bad.append(f"{verdict_mismatch} of {n_checked} verdicts differ from impl={check_impl!r}")
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    _smoke(phase="serve", dispatches=len(rounds), session_rounds=n_checked,
           nav_calls=stats["nav_calls"], tokens_verified=stats["tokens_verified"],
           checked_against=check_impl, verdict_mismatches=verdict_mismatch,
           logp_compared=n_logp, max_abs_logp_diff=worst_logp, wall_s=f"{wall:.3f}", peak_bytes_in_use=peak)
    if bad:
        return _fail("; ".join(bad[:10]))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
