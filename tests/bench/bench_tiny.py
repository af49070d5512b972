"""A benchmark root at CPU size for the tests: tiny target, four sessions.

``tiny_root(tmp)`` copies ``bench/`` into ``tmp`` beside a ``BENCHMARK.json``
holding one cell, ``tiny.tiny4``, on ``data/configs/tiny.json`` and
``data/traffic/tiny4.json``.  ``tiny_arch(monkeypatch)`` makes the program's
config registry answer ``tiny-bench`` with the same widths, since the
launcher sizes its target from ``repro.configs.get_config``.
"""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
for p in (REPO, REPO / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

CELL = "tiny.tiny4"


def tiny_root(tmp: Path) -> Path:
    root = Path(tmp) / "bench_root"
    shutil.copytree(REPO / "bench", root / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(DATA / "configs" / "tiny.json", root / "bench" / "configs" / "tiny.json")
    shutil.copy(DATA / "traffic" / "tiny4.json", root / "bench" / "traffic" / "tiny4.json")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "source": "test", "file": "bench/configs/tiny.json",
                         "reduced": [], "why": "CPU size"}]
    bench["workloads"] = [{"name": CELL, "config": "tiny", "traffic": "tiny4", "chips": 1,
                           "why": "CPU size"}]
    for m in bench["per_layer"]:
        m["workloads"] = [CELL]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def tiny_arch(monkeypatch) -> None:
    import repro.configs
    from repro.models.config import ModelConfig

    cfg = json.loads((DATA / "configs" / "tiny.json").read_text())
    tiny = ModelConfig(
        name="tiny-bench", family="dense", n_layers=cfg["kv_pool_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_ff=128, vocab_size=cfg["vocab_size"],
        head_dim=cfg["head_dim"],
    )
    real = repro.configs.get_config
    monkeypatch.setattr(repro.configs, "get_config",
                        lambda arch, reduced=False: tiny if arch == "tiny-bench" else real(arch, reduced))
