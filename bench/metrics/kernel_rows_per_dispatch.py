"""Rows the fused verify kernel launches per dispatch, from the dispatcher's counters.

``CloudVerifier.stats["kernel_rows"]`` adds up the padded batch ``Bp`` of
each fused launch, pad rows included: every launched row streams the whole
LM head.  Its change over the window, over the change of
``batched_calls``.  It bounds ``batch_occupancy`` from above.  A program
without the counter reads nothing.
"""

LAYER = "fused verify kernel"
UNIT = "rows"
SOURCE = "program_counter"
MOVES = "nav_rtt_p50_ms"


def read(ctx):
    if "kernel_rows" not in ctx.counters_end:
        return None
    calls = ctx.counters_end["batched_calls"] - ctx.counters_start["batched_calls"]
    if calls <= 0:
        return None
    return (ctx.counters_end["kernel_rows"] - ctx.counters_start["kernel_rows"]) / calls
