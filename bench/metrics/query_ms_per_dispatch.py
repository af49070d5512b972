"""Host time of the target's queries per dispatch, from the program's spans.

``SpecVerifyBackend.fused_inputs`` records one ``query`` span per session:
``query_fn`` and the copy of its result to the host.  The durations of
those that end in the window, summed, over the number of ``verify`` spans
that end in the window.  A program without the span reads nothing.
"""

LAYER = "verify backend host prep"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "nav_rtt_p50_ms"


def read(ctx):
    dispatches = sum(1 for name, _, _ in ctx.program_spans if name == "verify")
    queries = [t1 - t0 for name, t0, t1 in ctx.program_spans if name == "query"]
    if not dispatches or not queries:
        return None
    return 1e3 * sum(queries) / dispatches
