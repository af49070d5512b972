"""Lookup by name, and BENCHMARK.json against the benchmark's contract.

A configuration, a traffic mix and a metric added to a copy of the benchmark
are found by their names alone: no file that was there changes.
"""

import hashlib
import json
import re
import types

import bench_tiny
import pytest

from bench import harness, traffic

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _digests(root):
    return {p: hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "bench").rglob("*")) if p.is_file() and "__pycache__" not in p.parts}


def test_added_files_are_found_by_name(tmp_path):
    root = bench_tiny.tiny_root(tmp_path)
    before = _digests(root)
    cfg = json.loads((root / "bench" / "configs" / "tiny.json").read_text())
    (root / "bench" / "configs" / "tiny-wide.json").write_text(json.dumps(dict(cfg, name="tiny-wide")))
    mix = json.loads((root / "bench" / "traffic" / "tiny4.json").read_text())
    (root / "bench" / "traffic" / "tiny8.json").write_text(json.dumps(dict(mix, sessions=8)))
    (root / "bench" / "metrics" / "rounds_per_s.py").write_text(
        'LAYER = "dispatcher"\nUNIT = "rounds/s"\nSOURCE = "host_clock"\nMOVES = "committed_tok_s"\n\n'
        "def read(ctx):\n    return len(ctx.rounds) / ctx.window_s\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-wide", "source": "test", "file": "bench/configs/tiny-wide.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-wide.tiny8", "config": "tiny-wide", "traffic": "tiny8",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "rounds_per_s", "unit": "rounds/s", "better": "higher",
                               "source": "host_clock", "layer": "dispatcher", "moves": "committed_tok_s",
                               "workloads": ["tiny-wide.tiny8"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    spec = harness.resolve_cell(harness.load_benchmark(root), "tiny-wide.tiny8", root)
    assert spec["model"]["name"] == "tiny-wide" and spec["mix"]["sessions"] == 8
    assert traffic.make_schedule(spec["mix"], 3)["slots"] == 8
    names = [m["name"] for m in harness.cell_metrics(harness.load_benchmark(root), "tiny-wide.tiny8", "per_layer")]
    assert names == ["rounds_per_s"]
    reader = harness.load_metric("rounds_per_s", root)
    assert reader.read(types.SimpleNamespace(rounds=[{}] * 30, window_s=10.0)) == 3.0
    after = _digests(root)
    assert {p: d for p, d in after.items() if p in before} == before


def test_schedules_share_their_work_across_seeds():
    mix = traffic.load_mix("fleet32")
    a, b = traffic.make_schedule(mix, 1), traffic.make_schedule(mix, 2**31 + 5)
    assert sorted(a["lengths"]) == sorted(b["lengths"]) and a["lengths"] != b["lengths"]
    assert min(a["lengths"]) >= mix["tokens"]["min"] and max(a["lengths"]) <= mix["tokens"]["max"]
    assert traffic.make_schedule(mix, 7) == traffic.make_schedule(mix, 7)


def _contract_problems(bench, root):
    bad = []
    if set(bench) != {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}:
        bad.append("top-level keys")
    if not 1 <= bench["run_seconds"] <= 51:
        bad.append("run_seconds")
    for p in bench["paths"]:
        if not re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) or ".." in p or p.startswith("/"):
            bad.append(f"path {p}")
    names = set()
    for kind, keys in (("configs", {"name", "source", "file", "reduced", "why"}),
                       ("workloads", {"name", "config", "traffic", "chips", "why"})):
        for e in bench[kind]:
            if set(e) != keys or not NAME.match(e["name"]) or e["name"] in names:
                bad.append(f"{kind} {e.get('name')}")
            names.add(e["name"])
            if not 1 <= len(e["why"]) <= 200:
                bad.append(f"why of {e['name']}")
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        if c["name"] not in used or not (root / c["file"]).is_file():
            bad.append(f"config {c['name']} unused or missing")
        if any(k.endswith(("_dim", "_rank")) or "size" in k for k in c["reduced"]):
            bad.append(f"config {c['name']} reduces a width")
    cells = {w["name"] for w in bench["workloads"]}
    for w in bench["workloads"]:
        if w["chips"] not in (1, 4) or not (root / "bench" / "traffic" / f"{w['traffic']}.json").is_file():
            bad.append(f"cell {w['name']}")
    e2e = {m["name"] for m in bench["end_to_end"]}
    if "setup_s" not in e2e:
        bad.append("no setup_s")
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            allowed = {"name", "unit", "better", "source", "workloads"} | (
                {"bound"} if kind == "end_to_end" else {"layer", "moves"})
            if not set(m) <= allowed or not NAME.match(m["name"]) or m["name"] in names:
                bad.append(f"metric {m['name']} keys or name")
            names.add(m["name"])
            if not UNIT.match(m["unit"]) or m["better"] not in ("lower", "higher") or m["source"] not in SOURCES:
                bad.append(f"metric {m['name']} unit/better/source")
            if not set(m.get("workloads", [])) <= cells:
                bad.append(f"metric {m['name']} workloads")
            reader = harness.load_metric(m["name"], root)
            if (reader.UNIT, reader.SOURCE) != (m["unit"], m["source"]):
                bad.append(f"reader of {m['name']}")
            if kind == "end_to_end":
                if not (0.01 <= m["bound"] <= 0.25) or m["source"] not in ("host_clock", "device_trace"):
                    bad.append(f"bound/source of {m['name']}")
            else:
                if m["moves"] not in e2e or (reader.LAYER, reader.MOVES) != (m["layer"], m["moves"]):
                    bad.append(f"layer/moves of {m['name']}")
                if m["unit"] == "%" and "roofline" in m["name"] and not m["name"].endswith("_roofline"):
                    bad.append(f"roofline name {m['name']}")
    return bad


def test_benchmark_json_keeps_the_contract():
    bench = json.loads((bench_tiny.REPO / "BENCHMARK.json").read_text())
    assert len((bench_tiny.REPO / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    assert _contract_problems(bench, bench_tiny.REPO) == []


@pytest.mark.parametrize("edit", ["bound", "unit", "moves"])
def test_contract_check_sees_a_broken_entry(edit):
    bench = json.loads((bench_tiny.REPO / "BENCHMARK.json").read_text())
    if edit == "bound":
        bench["end_to_end"][0]["bound"] = 0.5
    elif edit == "unit":
        bench["end_to_end"][0]["unit"] = "tokens per second"
    else:
        bench["per_layer"][0]["moves"] = "nav_rtt_p95_ms"
    assert _contract_problems(bench, bench_tiny.REPO)
