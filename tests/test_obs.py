"""Deterministic observability subsystem: traces, metrics, telemetry, dashboard.

The contracts under test (ISSUE 10 acceptance):

* span tracing is clock-driven — under ``VirtualClock`` two seeded runs
  export **byte-identical** Chrome-trace JSON, and a traced router-fleet
  run reports the SAME committed rows as the untraced run (tracing only
  *reads* simulated time, so the overhead gate holds exactly, not just
  within the <2% budget);
* the per-round analyzer (wall / busy / bubble / critical stage) is exact
  on hand-built span timelines;
* the metric registry exposes Prometheus text with deterministic ordering
  and correct counter/gauge/histogram semantics;
* ``TelemetrySnapshot`` matches the verifier's own ground-truth stats, the
  router's fleet aggregate matches the per-verifier sum, and the snapshot
  codec round-trips exactly (hypothesis-covered in test_protocol.py);
* the HTTP endpoint serves ``/metrics`` + ``/snapshot`` on wall time only
  (``VirtualClock`` is rejected), and the dashboard renders a frame from
  the polled payload as a pure function.
"""

import json

import pytest

from repro.obs.dashboard import render_dashboard
from repro.obs.endpoint import (
    SNAPSHOT_COUNTER_FIELDS,
    SNAPSHOT_GAUGE_FIELDS,
    TelemetryEndpoint,
    aggregate_snapshots,
    prometheus_text_from_snapshots,
    snapshot_to_dict,
)
from repro.obs.metrics import LATENCY_BUCKETS, MetricRegistry, absorb_monitor
from repro.obs.trace import (
    NULL_TRACER,
    ROUND_STAGES,
    Span,
    Tracer,
    critical_path,
    round_report,
    session_bubble_fractions,
)
from repro.runtime import (
    Channel,
    ChannelConfig,
    CloudVerifier,
    EdgeClient,
    EdgeConfig,
    LocalVerifier,
    OracleBackend,
    OracleDraft,
    Router,
    TelemetrySnapshot,
    VirtualClock,
    decode,
    encode,
)

# --------------------------------------------------------------------------- #
# Traced fleet fixture: Router + 2 oracle verifiers + N clients, one clock
# --------------------------------------------------------------------------- #


def _run_traced_fleet(seed=0, n_verifiers=2, n_sessions=4, tokens=20):
    """Serve a small traced oracle fleet; capture telemetry pre-shutdown."""
    clock = VirtualClock()
    tracer = Tracer(clock=clock)
    registry = MetricRegistry(clock=clock)
    fleet = []
    for vid in range(n_verifiers):
        backend = OracleBackend(
            seed=seed, verify_time=0.06, verify_time_per_token=0.002, clock=clock
        )
        cv = CloudVerifier(
            backend, batch_window=0.0, max_batch=1, clock=clock,
            tracer=tracer, metrics=registry, verifier_id=vid,
        )
        cv.start()
        fleet.append(LocalVerifier(vid, cv, clock=clock))
    router = Router(fleet, clock=clock, control_interval=1.0, tracer=tracer)
    link = ChannelConfig(alpha=0.005, beta=0.0005)
    clients = []
    for sid in range(n_sessions):
        up = Channel(link, f"up{sid}", clock=clock)
        dn = Channel(link, f"dn{sid}", clock=clock)
        router.attach(sid, up, dn)
        cfg = EdgeConfig(gamma=0.004, window=8, nav_timeout=30.0)
        clients.append(
            EdgeClient(sid, up, dn, cfg, draft=OracleDraft(seed=seed), tracer=tracer)
        )
    results, telem = {}, {}

    def _drive(c):
        results[c.session] = c.run(tokens)

    def _serve():
        router.start()
        handles = [
            clock.spawn((lambda c=c: _drive(c)), name=f"drive-{c.session}")
            for c in clients
        ]
        for h in handles:
            h.join()
        telem["snaps"], telem["agg"] = router.telemetry(seq=7)
        router.stop()
        for vc in fleet:
            vc.stop()

    clock.run(_serve)
    return dict(
        tracer=tracer, registry=registry, fleet=fleet, router=router,
        results=results, snaps=telem["snaps"], agg=telem["agg"],
    )


@pytest.fixture(scope="module")
def traced_fleet():
    return _run_traced_fleet()


# --------------------------------------------------------------------------- #
# Tracing
# --------------------------------------------------------------------------- #


def test_tracer_records_spans_on_the_injected_clock():
    clock = VirtualClock()
    tracer = Tracer(clock=clock)

    def _work():
        with tracer.span("draft", session=3, round=0):
            clock.sleep(0.25)
        tracer.add("upload", 0.25, 0.5, session=3, round=0)

    clock.run(_work)
    spans = tracer.spans()
    assert [s.name for s in spans] == ["draft", "upload"]
    assert spans[0].t0 == 0.0 and spans[0].t1 == 0.25
    assert spans[0].duration == 0.25
    assert spans[0].get("session") == 3 and spans[0].get("missing", -1) == -1


def test_tracer_ring_buffer_bounds_memory():
    tracer = Tracer(clock=VirtualClock(), capacity=4)
    for i in range(10):
        tracer.add("verify", float(i), float(i) + 0.5, round=i)
    spans = tracer.spans()
    assert len(tracer) == 4
    assert [s.get("round") for s in spans] == [6, 7, 8, 9]  # oldest evicted


def test_null_tracer_is_inert_and_clock_free():
    assert NULL_TRACER.enabled is False
    assert NULL_TRACER.clock is None
    with NULL_TRACER.span("draft", session=1):
        pass
    NULL_TRACER.add("verify", 0.0, 1.0)
    assert len(NULL_TRACER) == 0


def test_tracer_ring_counts_the_spans_it_drops():
    tracer = Tracer(clock=VirtualClock(), capacity=4)
    for i in range(10):
        tracer.add("verify", float(i), float(i) + 0.5)
    assert tracer.dropped == 6
    assert json.loads(tracer.export_chrome_trace())["dropped_spans"] == 6
    tracer.clear()
    assert tracer.dropped == 0 and "dropped_spans" not in json.loads(tracer.export_chrome_trace())


def test_span_parents_and_dispatch_on_nested_and_cross_thread_spans():
    """A context span's parent is the span open on its own thread; it takes
    ``dispatch``/``verifier`` from that parent unless it sets its own."""
    import threading

    tracer = Tracer()
    inner_open, other_done = threading.Event(), threading.Event()

    def other_thread():
        inner_open.wait(5.0)
        with tracer.span("query", session=9):  # overlaps main's spans in time
            pass
        other_done.set()

    t = threading.Thread(target=other_thread)
    t.start()
    with tracer.span("verify", verifier=2, dispatch=7):
        with tracer.span("outer", session=1):
            with tracer.span("kv.fill"):
                inner_open.set()
                other_done.wait(5.0)
        with tracer.span("sibling", dispatch=8):
            pass
    t.join()
    by = {s.name: s for s in tracer.spans()}
    verify, outer, fill = by["verify"], by["outer"], by["kv.fill"]
    assert verify.parent == 0 and outer.parent == verify.sid and fill.parent == outer.sid
    assert by["sibling"].parent == verify.sid and by["sibling"].get("dispatch") == 8
    assert outer.get("session") == 1 and fill.get("session") is None  # only dispatch/verifier pass down
    for s in (outer, fill):
        assert (s.get("dispatch"), s.get("verifier")) == (7, 2)
    query = by["query"]
    assert query.parent == 0 and query.get("dispatch") is None  # other thread: no parent
    assert fill.t0 <= query.t0 and query.t1 <= fill.t1
    assert len({s.sid for s in tracer.spans()}) == 5
    tracer.add("nav_queue", 0.0, 1.0, dispatch=7)  # post-hoc: no id, no parent
    assert (tracer.spans()[-1].sid, tracer.spans()[-1].parent) == (0, 0)


def test_null_tracer_reads_no_clock_and_keeps_no_stack():
    """The off path: one shared no-op context whatever the attributes, no
    clock to read, no per-thread stack, nothing recorded."""
    with NULL_TRACER.span("verify", dispatch=1):
        with NULL_TRACER.span("kv.fill"):
            pass
    assert NULL_TRACER.span("a") is NULL_TRACER.span("b", dispatch=2)  # one shared no-op context
    assert NULL_TRACER.clock is None and len(NULL_TRACER) == 0 and NULL_TRACER.dropped == 0
    assert not hasattr(NULL_TRACER, "_local")


def test_chrome_export_carries_span_ids_and_stays_deterministic():
    def _build():
        clock = VirtualClock()
        t = Tracer(clock=clock)

        def work():
            with t.span("verify", dispatch=1):
                with t.span("query"):
                    clock.sleep(0.001)
            t.add("nav_queue", 0.0, 0.001, session=2, dispatch=1)

        clock.run(work)
        return t.export_chrome_trace()

    blob = _build()
    assert blob == _build()
    events = {e["name"]: e for e in json.loads(blob)["traceEvents"]}
    assert events["verify"]["args"] == {"dispatch": 1, "parent": 0, "sid": 1}
    assert events["query"]["args"] == {"dispatch": 1, "parent": 1, "sid": 2}
    assert events["nav_queue"]["args"] == {"dispatch": 1}


def test_chrome_export_is_valid_and_deterministic():
    def _build():
        t = Tracer(clock=VirtualClock())
        t.add("draft", 0.0, 0.001, session=1, round=0)
        t.add("verify", 0.002, 0.004, session=1, round=0)
        t.add("frame", 0.001, 0.0015, link="up1", bytes=64)
        return t.export_chrome_trace()

    blob = _build()
    assert blob == _build()  # bit-identical re-render
    doc = json.loads(blob)
    events = doc["traceEvents"]
    assert len(events) == 3 and all(e["ph"] == "X" for e in events)
    draft = next(e for e in events if e["name"] == "draft")
    assert draft["pid"] == 1 and draft["ts"] == 0.0 and draft["dur"] == 1000.0
    frame = next(e for e in events if e["name"] == "frame")
    assert frame["pid"] == 0 and frame["args"] == {"bytes": 64, "link": "up1"}


def test_seeded_fleet_trace_export_is_byte_identical():
    """The headline determinism claim: same seed => same bytes, twice."""
    a = _run_traced_fleet(seed=3, n_sessions=2, tokens=10)
    b = _run_traced_fleet(seed=3, n_sessions=2, tokens=10)
    blob_a = a["tracer"].export_chrome_trace()
    blob_b = b["tracer"].export_chrome_trace()
    assert blob_a == blob_b
    assert len(json.loads(blob_a)["traceEvents"]) == len(a["tracer"])
    c = _run_traced_fleet(seed=4, n_sessions=2, tokens=10)
    assert c["tracer"].export_chrome_trace() != blob_a  # seed actually matters


def test_fleet_spans_cover_every_pipeline_stage(traced_fleet):
    names = {s.name for s in traced_fleet["tracer"].spans()}
    assert set(ROUND_STAGES) <= names, names


def test_dispatcher_spans_share_the_dispatch_number(traced_fleet):
    """Each dispatch's admit, verify and reply spans, and the nav_queue
    spans of its requests, carry one (verifier, dispatch) pair."""
    spans = traced_fleet["tracer"].spans()

    def keys(name):
        return [(s.get("verifier"), s.get("dispatch")) for s in spans if s.name == name]

    verifies = keys("verify")
    assert verifies and len(set(verifies)) == len(verifies)
    assert set(keys("nav_queue")) == set(verifies)
    assert set(verifies) <= set(keys("dispatch.admit")) and set(keys("dispatch.reply")) == set(verifies)
    for vid in {v for v, _ in verifies}:  # numbered 1, 2, ... per verifier
        assert sorted(d for v, d in verifies if v == vid) == list(range(1, 1 + sum(v == vid for v, _ in verifies)))
    total = sum(vc.verifier.stats["batched_calls"] for vc in traced_fleet["fleet"])
    assert len(verifies) == total


# --------------------------------------------------------------------------- #
# Round analyzer: wall / busy / bubble / critical stage
# --------------------------------------------------------------------------- #


def _span(name, t0, t1, session=0, rnd=0):
    return Span(name, t0, t1, (("round", rnd), ("session", session)))


def test_round_report_on_a_gapless_round():
    spans = [
        _span("draft", 0.0, 1.0),
        _span("upload", 1.0, 2.0),
        _span("nav_queue", 2.0, 2.5),
        _span("verify", 2.5, 4.0),
        _span("commit", 4.0, 4.5),
    ]
    (rep,) = round_report(spans)
    assert rep["wall"] == pytest.approx(4.5)
    assert rep["busy"] == pytest.approx(4.5)
    assert rep["bubble_fraction"] == pytest.approx(0.0)
    assert rep["critical_stage"] == "verify"
    assert rep["stage_s"]["nav_queue"] == pytest.approx(0.5)


def test_round_report_measures_bubbles_and_overlap():
    # draft [0,1], verify [2,4]: a 1s hole => bubble 1/4; overlapping spans
    # must not double-count busy time (union, not sum).
    spans = [
        _span("draft", 0.0, 1.0),
        _span("verify", 2.0, 4.0),
        _span("commit", 3.5, 4.0),  # overlaps verify entirely
    ]
    (rep,) = round_report(spans)
    assert rep["wall"] == pytest.approx(4.0)
    assert rep["busy"] == pytest.approx(3.0)
    assert rep["bubble_fraction"] == pytest.approx(0.25)
    assert rep["critical_stage"] == "verify"


def test_round_report_ties_break_in_pipeline_order():
    spans = [_span("draft", 0.0, 1.0), _span("upload", 1.0, 2.0)]
    (rep,) = round_report(spans)
    assert rep["critical_stage"] == "draft"  # equal durations: earliest stage wins


def test_round_report_groups_by_session_and_round():
    spans = [
        _span("draft", 0.0, 1.0, session=1, rnd=0),
        _span("draft", 5.0, 5.5, session=1, rnd=1),
        _span("verify", 0.0, 2.0, session=2, rnd=0),
        Span("frame", 0.0, 1.0, ()),  # not a round stage: ignored
        Span("draft", 0.0, 1.0, (("session", 9),)),  # no round attr: ignored
    ]
    reps = round_report(spans)
    assert [(r["session"], r["round"]) for r in reps] == [(1, 0), (1, 1), (2, 0)]
    assert critical_path(spans, 2, 0) == "verify"
    assert critical_path(spans, 7, 7) is None
    bubbles = session_bubble_fractions(spans)
    assert bubbles[1] == pytest.approx(0.0) and bubbles[2] == pytest.approx(0.0)


def test_fleet_rounds_analyze_cleanly(traced_fleet):
    reps = round_report(traced_fleet["tracer"].spans())
    assert reps, "traced fleet produced no analyzable rounds"
    for rep in reps:
        assert 0.0 <= rep["bubble_fraction"] <= 1.0
        assert rep["critical_stage"] in ROUND_STAGES
        assert rep["busy"] <= rep["wall"] + 1e-12


# --------------------------------------------------------------------------- #
# Metrics registry
# --------------------------------------------------------------------------- #


def test_counter_and_gauge_semantics():
    reg = MetricRegistry(clock=VirtualClock())
    c = reg.counter("navs", "NAV calls")
    c.inc()
    c.inc(2.0)
    c.inc(link="up0")
    assert c.value() == 3.0 and c.value(link="up0") == 1.0
    with pytest.raises(ValueError):
        c.inc(-1.0)  # counters are monotone
    g = reg.gauge("depth", "queue depth")
    g.set(4.0)
    g.inc(-1.0)
    assert g.value() == 3.0
    # Get-or-create: same name returns the SAME metric; kind conflicts raise.
    assert reg.counter("navs") is c
    with pytest.raises(ValueError):
        reg.gauge("navs")


def test_histogram_buckets_and_moments():
    reg = MetricRegistry(clock=VirtualClock())
    h = reg.histogram("lat", "latency", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v)
    assert h.count() == 5
    assert h.sum() == pytest.approx(56.05)
    # Prometheus semantics: cumulative per-edge counts, +Inf implicit (the
    # 50.0 observation only shows up in count()).
    assert h.bucket_counts() == {0.1: 1, 1.0: 3, 10.0: 4}


def test_prometheus_text_is_deterministic_and_complete():
    reg = MetricRegistry(clock=VirtualClock())
    reg.counter("b_total", "second").inc(2.0)
    reg.counter("a_total", "first").inc(1.0, link="up0")
    reg.histogram("h", "hist", buckets=(1.0,)).observe(0.5)
    text = reg.prometheus_text()
    assert text == reg.prometheus_text()
    lines = text.splitlines()
    # Metric families render in sorted-name order with TYPE headers.
    assert lines.index("# TYPE a_total counter") < lines.index("# TYPE b_total counter")
    assert 'a_total{link="up0"} 1' in text
    assert "b_total 2" in text
    assert 'h_bucket{le="1"} 1' in text and 'h_bucket{le="+Inf"} 1' in text
    assert "h_sum 0.5" in text and "h_count 1" in text


def test_registry_samples_are_clock_stamped():
    clock = VirtualClock()
    reg = MetricRegistry(clock=clock)

    def _work():
        g = reg.gauge("x")
        g.set(1.0)
        clock.sleep(2.0)
        g.set(5.0)

    clock.run(_work)
    assert reg.get("x").samples() == [(0.0, 1.0), (2.0, 5.0)]


def test_absorb_monitor_mirrors_pipeline_monitor(traced_fleet):
    reg = MetricRegistry(clock=VirtualClock())
    absorb_monitor(traced_fleet["fleet"][0].verifier.monitor, reg)
    assert any(n.startswith("monitor_") for n in reg.names())


def test_fleet_registry_mirrors_verifier_stats(traced_fleet):
    reg = traced_fleet["registry"]
    total_navs = sum(
        vc.verifier.stats["nav_calls"] for vc in traced_fleet["fleet"]
    )
    navs = reg.get("verifier_nav_calls")
    assert navs is not None
    assert sum(navs.series().values()) == total_navs


# --------------------------------------------------------------------------- #
# Telemetry snapshots: wire codec, ground truth, fleet aggregation
# --------------------------------------------------------------------------- #


def test_snapshot_matches_verifier_ground_truth(traced_fleet):
    for vc in traced_fleet["fleet"]:
        snap = vc.verifier.telemetry_snapshot(seq=5)
        st = vc.verifier.stats
        assert snap.nav_calls == st["nav_calls"]
        assert snap.tokens_verified == st["tokens_verified"]
        assert snap.accepted_tokens == st["accepted_tokens"]
        assert snap.batched_calls == st["batched_calls"]
        assert snap.verify_busy_time == pytest.approx(st["verify_busy_time"])
        assert snap.verifier == vc.verifier_id and snap.seq == 5
        assert decode(encode(snap)) == snap  # exact through the wire


def test_router_aggregate_matches_per_verifier_sum(traced_fleet):
    snaps, agg = traced_fleet["snaps"], traced_fleet["agg"]
    assert len(snaps) == len(traced_fleet["fleet"])
    assert agg.verifier == -1 and agg.n_verifiers == len(snaps)
    for field in ("nav_calls", "tokens_verified", "accepted_tokens", "queue_depth"):
        assert getattr(agg, field) == sum(getattr(s, field) for s in snaps), field
    # ...and the per-verifier numbers are the fleet's real serving totals.
    assert agg.nav_calls == sum(
        vc.verifier.stats["nav_calls"] for vc in traced_fleet["fleet"]
    )
    # Verifier-side accepted_tokens counts accepted DRAFT tokens; clients
    # additionally commit one correction per NAV round.
    committed = sum(r["accepted_tokens"] for r in traced_fleet["results"].values())
    rounds = sum(r["rounds"] for r in traced_fleet["results"].values())
    assert committed == agg.accepted_tokens + rounds
    assert agg.occupancy == pytest.approx(
        sum(s.occupancy for s in snaps) / len(snaps)
    )
    # Router-side counters ride the extras lanes.
    assert "router_sessions_placed" in dict(zip(agg.names, agg.values))
    assert decode(encode(agg)) == agg


def test_aggregate_snapshots_field_classes_are_exhaustive():
    fields = set(SNAPSHOT_COUNTER_FIELDS) | set(SNAPSHOT_GAUGE_FIELDS)
    numeric = {
        f for f in TelemetrySnapshot.__dataclass_fields__
        if f not in ("session", "seq", "verifier", "n_verifiers", "t", "names", "values")
    }
    assert fields == numeric  # adding a snapshot field must classify it


def test_aggregate_snapshots_sums_and_averages():
    a = TelemetrySnapshot(verifier=0, t=1.0, nav_calls=10, occupancy=2.0,
                          sessions_active=3, names=("lane",), values=(1.0,))
    b = TelemetrySnapshot(verifier=1, t=2.0, nav_calls=5, occupancy=4.0,
                          sessions_active=1, names=("lane",), values=(2.0,))
    agg = aggregate_snapshots([a, b], seq=9)
    assert agg.nav_calls == 15 and agg.sessions_active == 4
    assert agg.occupancy == pytest.approx(3.0)  # mean, not sum
    assert agg.t == 2.0 and agg.seq == 9 and agg.n_verifiers == 2
    assert dict(zip(agg.names, agg.values))["lane"] == 3.0
    d = snapshot_to_dict(agg)
    assert d["nav_calls"] == 15 and d["extras"]["lane"] == 3.0
    assert "names" not in d and "values" not in d


def test_prometheus_text_from_snapshots(traced_fleet):
    snaps, agg = traced_fleet["snaps"], traced_fleet["agg"]
    text = prometheus_text_from_snapshots(snaps, aggregate=agg)
    assert "# TYPE pipesd_nav_calls counter" in text
    for s in snaps:
        assert f'pipesd_nav_calls{{verifier="{s.verifier}"}} {s.nav_calls}' in text
    assert f'pipesd_nav_calls{{verifier="-1"}} {agg.nav_calls}' in text
    assert f"pipesd_n_verifiers {len(snaps)}" in text


# --------------------------------------------------------------------------- #
# HTTP endpoint + dashboard
# --------------------------------------------------------------------------- #


def test_endpoint_serves_metrics_and_snapshot_over_http(traced_fleet):
    import urllib.request

    snaps, agg = traced_fleet["snaps"], traced_fleet["agg"]
    with TelemetryEndpoint(lambda: (snaps, agg), port=0) as ep:
        base = f"http://{ep.host}:{ep.port}"
        with urllib.request.urlopen(f"{base}/metrics", timeout=5) as resp:
            body = resp.read().decode()
            assert resp.headers["Content-Type"].startswith("text/plain")
        assert f'pipesd_nav_calls{{verifier="-1"}} {agg.nav_calls}' in body
        with urllib.request.urlopen(f"{base}/snapshot", timeout=5) as resp:
            payload = json.loads(resp.read().decode())
        assert payload["aggregate"]["nav_calls"] == agg.nav_calls
        assert len(payload["verifiers"]) == len(snaps)
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"{base}/nope", timeout=5)
    # The dashboard frame is a pure function of that payload.
    frame = render_dashboard(payload)
    assert frame.startswith("PipeSD fleet @ t=")
    assert f"verifiers={len(snaps)}" in frame
    lines = frame.splitlines()
    assert lines[-len(snaps) - 2].split()[:2] == ["vid", "sess"]  # header row
    assert render_dashboard(payload, ansi=True).startswith("\x1b[2J\x1b[H")


def test_endpoint_rejects_virtual_clock():
    with pytest.raises(ValueError, match="wall time"):
        TelemetryEndpoint(lambda: [], clock=VirtualClock())


def test_endpoint_registry_rides_the_metrics_page():
    reg = MetricRegistry(clock=VirtualClock())
    reg.counter("extra_total", "side metric").inc(3.0)
    snap = TelemetrySnapshot(verifier=0, nav_calls=1)
    with TelemetryEndpoint(lambda: snap, registry=reg, port=0) as ep:
        body = ep.render_metrics()
    assert 'pipesd_nav_calls{verifier="0"} 1' in body
    assert "extra_total 3" in body


# --------------------------------------------------------------------------- #
# Overhead gate: traced committed rows == untraced committed rows
# --------------------------------------------------------------------------- #


def test_traced_router_bench_rows_match_untraced_exactly():
    """Tracing must not perturb the committed bench: spans only READ the
    virtual clock, so every reported number is bit-identical — far inside
    the <2% overhead budget the committed ``router/x1_traced`` row gates."""
    from benchmarks.fleet_bench import run_router_fleet

    plain = run_router_fleet(1, n_sessions=4, tokens_per_session=20)
    traced = run_router_fleet(1, n_sessions=4, tokens_per_session=20, traced=True)
    for field in (
        "tokens_per_s", "tokens_per_nav", "nav_p50_ms", "nav_p99_ms",
        "bytes_per_session", "placement", "spread", "failovers", "wall_s",
    ):
        assert plain[field] == traced[field], field
    assert traced["n_spans"] == len(traced["_tracer"]) > 0


def test_committed_overhead_gate_row():
    rows = json.loads(
        (__import__("pathlib").Path(__file__).parent.parent / "BENCH_fleet.json")
        .read_text()
    )["rows"]
    gate = next(r for r in rows if r.get("name") == "router/x1_traced")
    x1 = next(r for r in rows if r.get("n_verifiers") == 1)
    assert gate["overhead_pct"] == 0.0
    assert gate["tokens_per_s"] == x1["tokens_per_s"]
    assert gate["n_spans"] > 0
    # The other committed families rode along: chaos counters + codec sizes.
    assert any("recovery_latency_s" in r for r in rows)
    assert any("host_ns_per_msg" in r for r in rows)


# --------------------------------------------------------------------------- #
# RunStats: summary field contract + metrics export
# --------------------------------------------------------------------------- #

SUMMARY_FIELDS = frozenset({
    "tpt_ms", "ecs_j", "ecs_edge_j", "ecs_total_j", "verification_frequency",
    "mean_draft_length", "acceptance_rate", "rounds", "nav_calls",
    "accepted_tokens", "wall_time_s", "overhead_dp", "overhead_bo",
    "overhead_measure", "verifier_batch_occupancy", "mean_queue_depth",
    "nav_p50_ms", "nav_p99_ms", "tokens_per_nav", "mean_tree_nodes",
    "mean_tree_depth", "kv_resident_mb", "kv_peak_mb",
    "kv_bytes_per_session_mb", "kv_cap_hits", "failovers",
    "fallback_fraction", "lost_draft_tokens", "recovery_latency_s",
})


def test_runstats_summary_field_contract():
    """Downstream consumers (bench CSVs, to_metrics, dashboards) key on
    these names: adding a field is fine ONLY by updating this contract."""
    from repro.core.pipeline import RunStats

    assert set(RunStats().summary()) == SUMMARY_FIELDS


def test_runstats_to_metrics_exports_gauges_and_histograms():
    from repro.core.pipeline import RunStats

    st = RunStats(accepted_tokens=50, rounds=10, nav_calls=10, wall_time=2.0)
    st.nav_latencies.extend([0.01, 0.02, 0.3])
    st.verifier_batches.extend([1, 2, 4])
    reg = MetricRegistry(clock=VirtualClock())
    st.to_metrics(reg)
    assert reg.get("run_accepted_tokens").value() == 50.0
    assert reg.get("run_nav_latency_s").count() == 3
    assert reg.get("run_verifier_batch").sum() == pytest.approx(7.0)
    assert set(SUMMARY_FIELDS) <= {n[len("run_"):] for n in reg.names()}
    assert LATENCY_BUCKETS[0] < 0.01  # the histogram resolves fast NAVs
