"""Readings for the limits of ``correct``: the program and its control, many seeds.

    python bench/calibrate.py --workload granite-3-2b.fleet32 --seconds 30 \\
        --seeds 101,102,103 --control-seeds 101,102,103 --out bench_out/cal.json

For each seed, one run as ``bench/run.py --trace 0`` makes it (set-up,
window at the cell's own load, comparison with the reference), all in one
process so that compiles are paid once.  The program's numbers are its
readings.  For each control seed, the same run's rounds are then given to
the reference computed one step below the configuration's precision
(``high``: three bfloat16 passes for float32 at ``highest``) and one step
further (``bf16``), in the program's place: the gap of the token that the
lower precision puts first, and its draft log-probs against the float32
reference, judged by the configuration's limits as a run of the program
is: a sound control comes out not correct.  Not part of a benchmark run;
it needs a TPU like one.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", required=True, help="comma-separated seeds of sound runs")
    p.add_argument("--control-seeds", default="", help="seeds whose rounds the control reads")
    p.add_argument("--out", type=Path, required=True)
    a = p.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"calibrate: FAIL: JAX found {dev.platform!r}, not a TPU", file=sys.stderr)
        return 1
    from bench import compare, harness

    controls = {int(s) for s in a.control_seeds.split(",") if s}
    rows = []
    t_start = T_START
    for seed in (int(s) for s in a.seeds.split(",")):
        res = harness.run(a.workload, seed, a.seconds, False, t_start=t_start)
        row = {"seed": seed, "correct": res["correct"], "program": res["readings"],
               "reference_s": res["extra"]["reference_s"],
               "metrics": {k: v["value"] for k, v in res["metrics"].items()}}
        if seed in controls:
            ref = compare.load_reference(res["model"]["reference"])
            for prec in ("high", "bf16"):
                t0 = time.monotonic()
                c = compare.control_readings(
                    res["model"], res["launcher_seed"], res["rounds"], prefix=res["prefix"],
                    precision=prec, reference=ref)
                c.update(correct=compare.judge(c, res["model"]["correct_limits"])[0],
                         seconds=time.monotonic() - t0)
                row[f"control_{prec}"] = c
        rows.append(row)
        print(json.dumps(row), flush=True)
        del res
        gc.collect()
        t_start = time.monotonic()
        a.out.parent.mkdir(parents=True, exist_ok=True)
        a.out.write_text(json.dumps({"workload": a.workload, "device": dev.device_kind, "rows": rows}, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
