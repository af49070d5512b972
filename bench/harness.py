"""One run of one benchmark cell: set-up, warm-up, a timed window, the check.

The cell names a configuration and a traffic mix in ``BENCHMARK.json``; both
are data files found by name, and every metric is a reader file of its own
under ``bench/metrics``.  The system under test is what ``launch/serve.py``
serves: ``_make_backend`` builds ``SpecVerifyBackend(fused=True)`` over the
launcher's paged KV pool and seeded synthetic target, and a
``CloudVerifier`` behind a loopback ``SocketListener`` dispatches the edge
sessions' rounds into it.  The edge sessions run in ``bench/edge.py``, a
CPU-only process of their own.

The benchmark wraps, and never replaces, three methods of the backend:
``fused_inputs`` and ``ensure_kv`` (timed, and named for the profiler) and
``fused_verify`` (named, and its rounds and results recorded for the check).
"""

from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import shutil
import subprocess
import sys
import threading
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Dict, List, Optional

import numpy as np

from . import compare, counts, trace_reduce, traffic

ROOT = Path(__file__).resolve().parents[1]  # the checkout: src/, launch/, BENCHMARK.json
SCRATCH_SESSION = 1_000_000  # pool ids for warm-up sessions, clear of the edge's
FAULTS = ("pool_unchanged", "half_batch", "token_altered")

class CompileClock:
    """Times of JAX lowerings (one per in-memory compile-cache miss).

    JAX's monitoring listeners are process-wide and cannot be removed, so
    one instance is registered per process (``compile_clock``).
    """

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        from jax import monitoring

        self.times: List[float] = []
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name: str, *_args, **_kwargs) -> None:
        if name == self.EVENT:
            self.times.append(time.monotonic())

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.times if t0 <= t < t1)


_CLOCK: List[CompileClock] = []


def compile_clock() -> CompileClock:
    if not _CLOCK:
        _CLOCK.append(CompileClock())
    return _CLOCK[0]


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


# ----------------------------------------------------------------- lookup --
def load_benchmark(root: Path = ROOT) -> Dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def resolve_cell(bench: Dict, workload: str, root: Path = ROOT) -> Dict:
    """The cell, its configuration file and its traffic mix, by name."""
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; have {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[cell["config"]]
    model = json.loads((Path(root) / entry["file"]).read_text())
    mix = traffic.load_mix(cell["traffic"], Path(root) / "bench" / "traffic")
    return {"cell": cell, "config": entry, "model": model, "mix": mix}


def cell_metrics(bench: Dict, workload: str, kind: str) -> List[Dict]:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    return [m for m in bench[kind] if workload in m.get("workloads", [workload])]


def load_metric(name: str, root: Path = ROOT):
    path = Path(root) / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks_for(kind: str, root: Path = ROOT) -> Dict:
    table = json.loads((Path(root) / "bench" / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json ({table['source']})")
    return table["devices"][kind]


def _load_launcher():
    spec = importlib.util.spec_from_file_location("bench_serve", ROOT / "launch" / "serve.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _next_pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def _pow2_upto(n: int) -> List[int]:
    return [1 << i for i in range(_next_pow2(n).bit_length())]


def launcher_seed(seed: int) -> int:
    """The seed the launcher and the edge draft take: ``--seed`` folded to 31 bits."""
    return int(seed) % (1 << 31)


# ------------------------------------------------------------ instrument --
class Recorder:
    """Wraps the backend's methods; records dispatched rounds and host spans."""

    def __init__(self, backend, fault: Optional[str] = None, vocab: int = 0):
        import jax

        self.annotate = jax.profiler.TraceAnnotation
        self.recording = False
        self.dispatched: List[Dict] = []
        self.prep_spans: List[tuple] = []  # host (start, end) of fused_inputs
        self.lock = threading.Lock()
        inputs, ensure, verify = backend.fused_inputs, backend.ensure_kv, backend.fused_verify

        def fused_inputs(requests):
            t0 = time.monotonic()
            with self.annotate("bench.fused_inputs"):
                out = inputs(requests)
            if self.recording:
                with self.lock:
                    self.prep_spans.append((t0, time.monotonic()))
            return out

        def ensure_kv(session):
            with self.annotate("bench.ensure_kv"):
                return ensure(session)

        def fused_verify(requests):
            t0 = time.monotonic()
            with self.annotate("bench.fused_verify"):
                if fault == "half_batch" and self.recording and len(requests) > 1:
                    half = verify(requests[: (len(requests) + 1) // 2])
                    out = half + [half[0]] * (len(requests) - len(half))
                else:
                    out = verify(requests)
            if fault == "token_altered" and self.recording:
                out = [(n, (c + 1) % vocab, lp) for n, c, lp in out]
            if self.recording:
                with self.lock:
                    for (s, toks, _), (n, c, lp) in zip(requests, out):
                        self.dispatched.append({
                            "session": int(s), "tokens": [int(t) for t in toks],
                            "n_accepted": int(n), "correction": int(c),
                            "logp": [float(x) for x in np.asarray(lp)], "t": t0,
                        })
            return out

        backend.fused_inputs = fused_inputs
        backend.ensure_kv = ensure_kv
        backend.fused_verify = fused_verify
        if fault == "pool_unchanged":
            pool = backend.kv_pool
            real_fill = pool.fill

            def fill(session, start, k_new, v_new):
                if not self.recording:
                    return real_fill(session, start, k_new, v_new)
                t = pool._table(session)
                t.filled = max(t.filled, start + k_new.shape[1])  # claims the write, makes none

            pool.fill = fill
        elif fault is not None and fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}")


# ---------------------------------------------------------------- warm-up --
def warm_up(backend, verifier, mix: Dict) -> Dict:
    """Run every shape the cell's traffic can use once, before the window.

    Kernel buckets: ``Bp`` up to the mix's concurrency, ``Kp`` up to the edge
    window, ``Gp`` over the contexts its sessions reach.  Pool writes: every
    ``(T, take)`` split of a ``T``-token fill (``T`` = 1 .. window+1) over a
    page boundary.  Queries: every draft count.  All through the backend's
    own methods, on scratch sessions forked from the shared prefix.
    """
    pool = backend.kv_pool
    bs = pool.block_size
    window = int(mix["edge"]["window"])
    prefix = int(mix["prefix"])
    tok = mix["tokens"]
    owner = verifier.KV_PREFIX_SESSION
    top = prefix + int(tok["max"]) + 2 * window + 1  # longest context a round can read
    gps = sorted({_next_pow2(-(-L // bs)) for L in range(prefix + 2, top + 1)})
    sid = iter(range(SCRATCH_SESSION, SCRATCH_SESSION + 1_000_000))

    def session(length: int) -> int:
        s = next(sid)
        pool.fork(owner, s)
        if length > pool.length(s):
            pool.append(s, length - pool.length(s))
        return s

    # Pool writes: a fill of T tokens that starts `take` slots before a page end.
    s = session(prefix + 2 * bs)
    for T in range(1, window + 2):
        for take in range(1, T + 1):
            start = prefix + bs - take if take < T else prefix + bs - T
            k, v = backend.kv_fn(s, start, T)
            pool.fill(s, start, k, v)
    for K in range(1, window + 1):  # queries come at the round's own K, not its bucket
        backend.query_fn(s, [0] * K)
    pool.release(s)
    n_calls = 0
    for gp in gps:
        # The shortest context whose table pads to gp pages once the round's
        # slots are appended; its tokens past the prefix are filled in steps
        # of at most window+1, the writes the window itself makes.
        length = max(prefix, (gp // 2) * bs)
        for bp in _pow2_upto(traffic.concurrency(mix)):
            for kp in _pow2_upto(window):
                ss = [session(length) for _ in range(bp)]
                for s in ss:
                    for a in range(prefix, length, window + 1):
                        T = min(window + 1, length - a)
                        pool.fill(s, a, *backend.kv_fn(s, a, T))
                    pool.append(s, kp + 1)
                backend.fused_verify([(s, [0] * kp, [1.0] * kp) for s in ss])
                for s in ss:
                    pool.release(s)
                n_calls += 1
    return {"kernel_shapes": n_calls, "gp": gps}


# -------------------------------------------------------------------- run --
def _edge_process(schedule: Dict, seed: int):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONUNBUFFERED="1")
    return subprocess.Popen(
        [sys.executable, str(Path(__file__).resolve().parent / "edge.py"),
         json.dumps(schedule), str(seed)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, env=env, cwd=str(ROOT),
    )


def _percentile(xs, q):
    return float(np.percentile(xs, q)) if len(xs) else float("nan")


def run(
    workload: str,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    t_start: float,
    root: Path = ROOT,
    impl: str = "pallas",
    fault: Optional[str] = None,
    dump_trace: Optional[Path] = None,
) -> Dict:
    """One run; returns the result line's fields plus what the report prints.

    ``t_start`` is when the process started (``setup_s`` runs from there).
    ``impl`` and ``fault`` serve the tests, which drive a run on the CPU with
    the timed path broken underneath (``FAULTS``).
    """
    bench = load_benchmark(root)
    spec = resolve_cell(bench, workload, root)
    lseed = launcher_seed(seed)
    edge = _edge_process(traffic.make_schedule(spec["mix"], seed), lseed)
    try:
        return _run(bench, spec, workload, lseed, seconds, trace, edge, t_start, root, impl,
                    fault, dump_trace)
    finally:
        if edge.poll() is None:
            edge.kill()
        edge.wait()


def _run(bench, spec, workload, lseed, seconds, trace, edge, t_start, root, impl, fault,
         dump_trace) -> Dict:
    import jax

    from repro.configs import get_config
    from repro.obs.trace import Tracer
    from repro.runtime import CloudVerifier, SocketListener

    compiles = compile_clock()
    model, mix = spec["model"], spec["mix"]
    dev = jax.devices()[0]

    # --- set-up: the launcher's backend at the configuration's widths ---
    prog = get_config(model["arch"])
    want = {"n_heads": model["num_attention_heads"], "n_kv_heads": model["num_key_value_heads"],
            "head_dim": model["head_dim"], "vocab_size": model["vocab_size"],
            "n_layers": model["kv_pool_layers"]}
    got = {k: getattr(prog, k) for k in want}
    if got != want:
        raise RuntimeError(f"the program's {model['arch']!r} is {got}, the configuration file says {want}")
    serve = _load_launcher()
    vcfg = mix["verifier"]
    args = serve.build_parser().parse_args([
        "--listen", "127.0.0.1:0", "--backend", "spec", "--impl", impl, "--arch", model["arch"],
        "--seed", str(lseed), "--batch-window", str(vcfg["batch_window"]),
    ])
    backend, cv_kwargs = serve._make_backend(args)
    if backend.kv_pool.block_size != mix["page"]:
        raise RuntimeError(f"pool pages hold {backend.kv_pool.block_size} tokens, the mix says {mix['page']}")
    rec = Recorder(backend, fault=fault, vocab=model["vocab_size"])
    tracer = Tracer(capacity=1 << 20) if trace else None
    verifier = CloudVerifier(
        backend, batch_window=vcfg["batch_window"], max_batch=vcfg["max_batch"],
        kv_shared_prefix=mix["prefix"], tracer=tracer, **cv_kwargs,
    )
    warm = warm_up(backend, verifier, mix)
    listener = SocketListener(lambda sid, t: verifier.attach(sid, t, t), host="127.0.0.1", port=0)
    verifier.start()
    setup_s = time.monotonic() - t_start
    log(f"setup_s={setup_s:.3f} warm kernel_shapes={warm['kernel_shapes']} gp={warm['gp']}")

    # --- the window ---
    if edge.stdout.readline().strip() != "READY":
        raise RuntimeError("the edge process did not start")
    rec.recording = True  # every round the edge sends is checked, the ramp's too
    t_go = time.monotonic()
    t0 = t_go + float(mix["ramp_s"])
    t1 = t0 + float(seconds)
    edge.stdin.write(f"GO {listener.host} {listener.port} {t1!r}\n")
    edge.stdin.flush()
    time.sleep(max(t0 - time.monotonic(), 0.0))
    if trace:
        tdir = ROOT / "bench_out" / "trace"
        shutil.rmtree(tdir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tdir), profiler_options=opts)
        t0 = time.monotonic()  # the window opens once the profiler runs
        t1 = t0 + float(seconds)
    c_start = dict(verifier.stats)
    ann = rec.annotate("bench.window")
    ann.__enter__()
    time.sleep(max(t1 - time.monotonic(), 0.0))
    ann.__exit__(None, None, None)
    c_end = dict(verifier.stats)
    n_compiles = compiles.between(t0, t1)
    if trace:
        jax.profiler.stop_trace()

    # --- the edge finishes its rounds in flight and reports ---
    grace = 2 * float(mix["edge"]["nav_timeout"]) + 60.0
    out, _ = edge.communicate(timeout=max(t1 + grace - time.monotonic(), 1.0))
    rec.recording = False
    listener.close()
    verifier.stop()
    lines = [ln for ln in out.splitlines() if ln.startswith("{")]
    if edge.returncode != 0 or not lines:
        raise RuntimeError(f"the edge process exited with {edge.returncode} and no report")
    report = json.loads(lines[-1])
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    spans = tracer.spans() if tracer is not None else []

    # --- free the program's state before the reference runs ---
    rounds_all = report["rounds"]
    dispatched = list(rec.dispatched)
    bench_spans = {"bench.fused_inputs": [b - a for a, b in rec.prep_spans if t0 <= a < t1]}
    program_spans = [(s.name, s.t0, s.t1) for s in spans if t0 <= s.t1 < t1]
    del verifier, backend, listener, rec, tracer, cv_kwargs, spans
    del serve
    gc.collect()

    in_window = [r for r in rounds_all if r.get("t_res") is not None and t0 <= r["t_res"] < t1]
    sent = [r for r in rounds_all if t0 <= r["t_req"] < t1]
    lost = [r for r in sent if r.get("t_res") is None]
    lags = report["start_lag_s"]
    ctx = SimpleNamespace(
        rounds=in_window, window_s=t1 - t0, setup_s=setup_s,
        counters_start=c_start, counters_end=c_end,
        program_spans=program_spans, bench_spans=bench_spans, trace=None, traced_dispatches=[],
        peaks=None, roofline_bound=None,
    )
    result: Dict = {}
    extra: Dict = {
        "rounds_in_window": len(in_window), "rounds_total": len(rounds_all),
        "compiles_in_window": n_compiles, "start_lag_p95_ms": 1e3 * _percentile(lags, 95),
        "sessions": len(report["sessions"]), "edge_errors": report["errors"],
        "failovers": sum(s["failovers"] for s in report["sessions"]),
        "acceptance": (sum(r["n_accepted"] for r in in_window)
                       / max(sum(len(r["tokens"]) for r in in_window), 1)),
        "dispatches_in_window": len({d["t"] for d in dispatched if t0 <= d["t"] < t1}),
        "memory_peak_bytes": mem,
    }
    if trace:
        peaks = peaks_for(dev.device_kind, root) if dev.platform == "tpu" else None
        pairs, _ = compare.match_dispatches(rounds_all, dispatched)
        base = {}
        for r, req in pairs:
            if req is not None:
                base[id(req)] = mix["prefix"] + r["pos"] + 1
        by_time: Dict[float, List[Dict]] = {}
        for d in dispatched:
            if t0 <= d["t"] < t1 and id(d) in base:
                by_time.setdefault(d["t"], []).append({"k": len(d["tokens"]), "base": base[id(d)]})
        traced = []
        for rows in by_time.values():
            c = counts.dispatch_counts(model, rows, prefix=mix["prefix"], page=mix["page"],
                                       dtype_bytes=model["dtype_bytes"])
            if peaks is not None:
                roof = counts.roofline_seconds(c, peaks)
                c.update(roofline_s=roof["seconds"], bound=roof["bound"])
            traced.append(c)
        ctx.traced_dispatches, ctx.peaks = traced, peaks
        if traced and peaks is not None:
            bounds = [d["bound"] for d in traced]
            ctx.roofline_bound = max(set(bounds), key=bounds.count)
            extra["roofline_bound"] = ctx.roofline_bound
        files = sorted(Path(tdir).rglob("*.xplane.pb"))
        plain = None
        if files and dev.platform == "tpu":
            if dump_trace:
                shutil.copy(files[-1], f"{dump_trace}.xplane.pb")
            plain = trace_reduce.load_xplane(str(files[-1]))
        shutil.rmtree(tdir, ignore_errors=True)
        if plain is not None:
            if dump_trace:
                Path(dump_trace).write_text(json.dumps(plain))
            ctx.trace = trace_reduce.reduce_trace(plain, trace_reduce.kernel_names())
            tr = ctx.trace
            result["device_extra"] = {"busy_s": tr["busy_s"], "window_s": tr["window_s"]}
            result["breakdown"] = {"device_ops": tr["device_ops"], "idle_gaps": tr["idle_gaps"]}
            extra["kernel_launches"] = tr["kernel_count"]["verify_kernel"]
            if traced and not tr["kernel_count"]["verify_kernel"]:
                raise RuntimeError(
                    f"{len(traced)} dispatches in the traced window, but no device op matches "
                    f"the verify kernel's names in bench/kernels.json")
        else:
            result["device_extra"] = None
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in cell_metrics(bench, workload, kind):
        mod = load_metric(m["name"], root)
        if (mod.UNIT, mod.SOURCE) != (m["unit"], m["source"]):
            raise RuntimeError(f"{m['name']}: reader says {mod.UNIT}/{mod.SOURCE}, BENCHMARK.json {m['unit']}/{m['source']}")
        value = mod.read(ctx)
        if value is not None and math.isfinite(value):
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    result.update(metrics=metrics, memory_peak_bytes=mem, extra=extra,
                  attempted=len(sent), failed=len(lost) + len(report["errors"]))

    # --- the check, after the window, with the program's state freed ---
    t_ref = time.monotonic()
    reference = compare.load_reference(model["reference"], Path(root) / "bench" / "references")
    values = compare.readings(model, lseed, rounds_all, dispatched, prefix=mix["prefix"],
                              reference=reference)
    values["delivery_mismatches"] += len(report["errors"])
    correct, table = compare.judge(values, model["correct_limits"])
    extra.update(reference_s=time.monotonic() - t_ref, rounds_compared=values["rounds_compared"],
                 served_tokens=values["served_tokens"], logp_compared=values["logp_compared"],
                 logp_max_abs_diff=values["logp_max_abs_diff"])
    result.update(correct=correct and values["rounds_compared"] > 0, compared=table, readings=values,
                  rounds=rounds_all, model=model, prefix=mix["prefix"], launcher_seed=lseed)
    return result
