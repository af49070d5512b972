"""Host time of the verify backend's input assembly, per dispatch.

The benchmark's own span around ``SpecVerifyBackend.fused_inputs`` (which
runs ``ensure_kv`` -> ``PagedKVPool.fill`` for every session, the queries
and the block tables), host clock, mean over the dispatches in the window.
"""

LAYER = "verify backend host prep"
UNIT = "ms"
SOURCE = "host_clock"
MOVES = "nav_rtt_p50_ms"


def read(ctx):
    spans = ctx.bench_spans.get("bench.fused_inputs", [])
    return 1e3 * sum(spans) / len(spans) if spans else None
