#!/usr/bin/env python3
"""One benchmark run with the program's tracer on and the profiler off.

    python3 tools/tracer_cost_run.py --workload granite-3-2b.fleet32 --seed 7 --seconds 20

Takes ``bench/run.py``'s options and prints what it prints; give it
``--trace 0`` (the default).  Every ``CloudVerifier`` built without a tracer
records into an enabled ``repro.obs`` ``Tracer``, as under ``--trace 1``,
but no profiler runs, so the end-to-end metrics are read as under
``--trace 0``.  Set against a ``--trace 0`` run at the same seed, its
``committed_tok_s`` is the tracer's own cost; against ``--trace 1``, the
profiler's.  The last line of standard error gives the spans recorded and
those the ring dropped.
"""

from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run  # noqa: E402  (first, so that setup_s starts here)

TRACERS: list = []


def trace_every_verifier() -> None:
    """Give each ``CloudVerifier`` built without a tracer an enabled one."""
    import repro.runtime as runtime
    from repro.obs.trace import Tracer

    base = runtime.CloudVerifier

    class TracedVerifier(base):
        def __init__(self, *args, tracer=None, **kwargs):
            if tracer is None:
                tracer = Tracer(capacity=1 << 20)
                TRACERS.append(tracer)
            super().__init__(*args, tracer=tracer, **kwargs)

    runtime.CloudVerifier = TracedVerifier


def main(argv=None) -> int:
    trace_every_verifier()
    rc = run.main(argv)
    spans = sum(len(t) for t in TRACERS)
    dropped = sum(t.dropped for t in TRACERS)
    print(f"tracer spans={spans} dropped={dropped}", file=sys.stderr, flush=True)
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
