"""Benchmark of the served NAV verifier on the chip: one cell, one run.

    python bench/run.py --workload granite-3-2b.fleet32 --seed 7 --seconds 30 --trace 0

Names the device first and refuses anything but a TPU with the chips the
cell asks for.  Then one run of ``bench/harness.py``: set-up and warm-up
(``setup_s``), a window of ``--seconds`` of edge traffic, the comparison
with the plain reference after it.  ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics from a profiler
trace of the window.  The last lines of standard error are the numbers
compared with their limits; the last line of standard output is the result
as one JSON object.

JAX's persistent compilation cache goes where ``JAX_COMPILATION_CACHE_DIR``
says, or else to ``.jax_cache`` in this checkout, and takes every compile.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--dump-trace", type=Path, default=None,
                   help="also write the reduced trace's plain data to this file")
    a = p.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    try:
        import jax

        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        devices = jax.devices()
        dev = devices[0]
        print(f"bench: device platform={dev.platform} kind={dev.device_kind!r} count={len(devices)}",
              file=sys.stderr, flush=True)
        from bench import harness

        cell = harness.resolve_cell(harness.load_benchmark(), a.workload)["cell"]
        if dev.platform != "tpu":
            print(f"bench: FAIL: JAX found {dev.platform!r}, not a TPU", file=sys.stderr)
            return 1
        if len(devices) < cell["chips"]:
            print(f"bench: FAIL: the cell needs {cell['chips']} chips, JAX sees {len(devices)}",
                  file=sys.stderr)
            return 1
        res = harness.run(a.workload, a.seed, a.seconds, bool(a.trace), t_start=T_START,
                          dump_trace=a.dump_trace)
    except Exception:
        traceback.print_exc()
        print("bench: FAIL: the run raised", file=sys.stderr, flush=True)
        return 1
    for k, v in res["extra"].items():
        print(f"bench: {k}={v}", file=sys.stderr)
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": cell["chips"],
              "memory_peak_bytes": res["memory_peak_bytes"]}
    if a.trace:
        if not res.get("device_extra"):
            print("bench: FAIL: the traced run found no trace", file=sys.stderr)
            return 1
        device.update(res["device_extra"])
    line = {"correct": res["correct"], "attempted": res["attempted"], "failed": res["failed"],
            "metrics": res["metrics"], "device": device}
    if a.trace and res.get("breakdown"):
        line["breakdown"] = res["breakdown"]
    line["compared"] = res["compared"]
    for name, t in res["compared"].items():
        print(f"compared {name}={t['value']!r} limit={t['limit']!r}", file=sys.stderr)
    print(f"compared correct={res['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
