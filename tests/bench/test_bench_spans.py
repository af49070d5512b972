"""A traced run at CPU size reads the program's own spans and counters.

``bench.harness.run`` with ``trace=True`` on the tiny cell: the readers of
``kv_fill_ms_per_dispatch``, ``kv_writes_per_dispatch``,
``query_ms_per_dispatch`` and ``dispatcher_busy_pct`` find what the served
path records, and the spans nest as the verifier causes them.  No chip,
so no device trace: the device metrics are left out.
"""

import math
import time

import bench_tiny

from bench import harness

NEW = ("kv_fill_ms_per_dispatch", "kv_writes_per_dispatch", "query_ms_per_dispatch",
       "dispatcher_busy_pct")


def test_traced_run_reads_the_program_spans(tmp_path, monkeypatch):
    import repro.obs.trace as trace

    tracers, recorders = [], []

    class Kept(trace.Tracer):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            tracers.append(self)

    class KeptRecorder(harness.Recorder):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            recorders.append(self)

    monkeypatch.setattr(trace, "Tracer", Kept)
    monkeypatch.setattr(harness, "Recorder", KeptRecorder)
    bench_tiny.tiny_arch(monkeypatch)
    root = bench_tiny.tiny_root(tmp_path)
    res = harness.run(bench_tiny.CELL, 2**31 + 23, 2.0, True, t_start=time.monotonic(),
                      root=root, impl="ref")
    assert res["correct"], res["compared"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(NEW) <= set(m), m
    assert all(math.isfinite(m[k]) and m[k] > 0 for k in NEW), m
    assert m["dispatcher_busy_pct"] <= 100.0
    # Every session-round appends and fills K+1 >= 2 slots, within a page
    # or across one: at least (K, V) per session per dispatch.
    assert m["kv_writes_per_dispatch"] >= 2 * m["batch_occupancy"]

    (tracer,) = tracers
    assert tracer.dropped == 0
    spans = tracer.spans()
    by_sid = {s.sid: s for s in spans if s.sid}
    served = min(s.t0 for s in spans if s.name == "verify")  # warm-up ran before this
    fills = [s for s in spans if s.name == "kv.fill" and s.t0 >= served]
    assert fills
    queries = [s for s in spans if s.name == "query" and s.t0 >= served]
    assert queries
    for s in fills + queries:
        verify = by_sid[s.parent]
        assert verify.name == "verify"
        assert s.get("dispatch") == verify.get("dispatch") is not None
        assert verify.t0 <= s.t0 <= s.t1 <= verify.t1
    verifies = {s.get("dispatch") for s in spans if s.name == "verify"}
    queued = {s.get("dispatch") for s in spans if s.name == "nav_queue"}
    assert queued == verifies and len(verifies) == len([s for s in spans if s.name == "verify"])

    # kv.fill and query lie inside the benchmark's own span around
    # fused_inputs, the source of host_prep_ms_per_dispatch: dispatch by
    # dispatch, their sum is at most its length.
    (rec,) = recorders
    prep = [(a, b) for a, b in rec.prep_spans if a >= served]
    inner = [s for s in spans if s.name in ("kv.fill", "query") and s.t0 >= served]
    assert len(prep) >= 10 and inner
    for a, b in prep:
        assert sum(s.duration for s in inner if a <= s.t0 and s.t1 <= b) <= b - a
    assert all(any(a <= s.t0 and s.t1 <= b for a, b in prep) for s in inner if s.t1 <= prep[-1][1])
    for name in ("dispatch.admit", "dispatch.reply"):
        assert {s.get("dispatch") for s in spans if s.name == name and s.t0 >= served} <= verifies


def test_new_readers_read_nothing_from_a_program_without_the_spans():
    """At a program that records only ``verify`` and ``nav_queue`` spans and
    has no ``kv_page_writes`` counter, the new readers return None."""
    import types

    ctx = types.SimpleNamespace(
        program_spans=[("verify", 0.0, 0.2), ("nav_queue", 0.0, 0.1), ("verify", 0.3, 0.5)],
        counters_start={"batched_calls": 0, "nav_calls": 0},
        counters_end={"batched_calls": 2, "nav_calls": 4},
        window_s=1.0, trace=None, traced_dispatches=[],
    )
    for name in NEW:
        assert harness.load_metric(name).read(ctx) is None, name


def test_dispatcher_busy_share_counts_gaps_as_idle():
    import types

    spans = [("dispatch.wait", 0.0, 1.0), ("dispatch.admit", 1.0, 1.5), ("verify", 1.5, 4.0),
             ("dispatch.reply", 4.0, 4.5), ("dispatch.coalesce", 5.0, 6.0), ("nav_queue", 0.0, 9.0)]
    ctx = types.SimpleNamespace(program_spans=spans)
    # busy [1, 4.5) of the loop's [0, 6): the gap [4.5, 5) and the wait count as idle
    assert harness.load_metric("dispatcher_busy_pct").read(ctx) == 100.0 * 3.5 / 6.0


def test_tracer_cost_run_traces_without_the_profiler(tmp_path, monkeypatch):
    """``tools/tracer_cost_run.py`` gives the verifier an enabled tracer in
    an untraced run: end-to-end metrics as under ``--trace 0``, and the
    program's spans recorded into the ring."""
    import importlib.util

    import repro.runtime as runtime

    monkeypatch.setattr(runtime, "CloudVerifier", runtime.CloudVerifier)  # restored after
    spec = importlib.util.spec_from_file_location(
        "tracer_cost_run", bench_tiny.REPO / "tools" / "tracer_cost_run.py")
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    tool.trace_every_verifier()
    bench_tiny.tiny_arch(monkeypatch)
    root = bench_tiny.tiny_root(tmp_path)
    res = harness.run(bench_tiny.CELL, 2**31 + 29, 1.0, False, t_start=time.monotonic(),
                      root=root, impl="ref")
    assert res["correct"], res["compared"]
    assert res["metrics"]["committed_tok_s"]["value"] > 0 and not set(NEW) & set(res["metrics"])
    (tracer,) = tool.TRACERS
    names = {s.name for s in tracer.spans()}
    assert {"verify", "kv.fill", "query", "dispatch.admit", "nav_queue"} <= names
    assert tracer.dropped == 0
