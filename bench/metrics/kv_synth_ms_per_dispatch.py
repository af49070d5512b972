"""Host time of the target's KV synthesis per dispatch, from the program's spans.

``SpecVerifyBackend.ensure_kv`` records a ``kv.synth`` span around
``kv_fn``, the host synthesis of each round's K and V (under the dispatch's
``verify``).  The durations of those that end in the window, summed, over
the number of ``verify`` spans that end in the window.  A program without
the span reads nothing.
"""

LAYER = "verify backend host prep"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "nav_rtt_p50_ms"


def read(ctx):
    dispatches = sum(1 for name, _, _ in ctx.program_spans if name == "verify")
    synths = [t1 - t0 for name, t0, t1 in ctx.program_spans if name == "kv.synth"]
    if not dispatches or not synths:
        return None
    return 1e3 * sum(synths) / dispatches
