"""Host time of the paged KV pool's writes per dispatch, from the program's spans.

``PagedKVPool.fill`` records a ``kv.fill`` span (under ``ensure_kv``, under
the dispatch's ``verify``).  The durations of those that end in the window,
summed, over the number of ``verify`` spans that end in the window.  A
program without the span reads nothing.
"""

LAYER = "paged KV pool writes"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "nav_rtt_p50_ms"


def read(ctx):
    dispatches = sum(1 for name, _, _ in ctx.program_spans if name == "verify")
    fills = [t1 - t0 for name, t0, t1 in ctx.program_spans if name == "kv.fill"]
    if not dispatches or not fills:
        return None
    return 1e3 * sum(fills) / dispatches
