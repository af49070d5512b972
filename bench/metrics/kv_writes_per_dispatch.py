"""Page buffer writes of the paged KV pool per dispatch, from the dispatcher's counters.

``CloudVerifier.stats["kv_page_writes"]`` adds up, over the dispatches
from admission through the verify call, the pool's ``page_writes``: each
functional update of a page buffer (k, v, and the int8 planes), the
copy-on-write copies of admission included.  Its change over the window, over the change of
``batched_calls``.  A program without the counter reads nothing.
"""

LAYER = "paged KV pool writes"
UNIT = "writes"
SOURCE = "program_counter"
MOVES = "nav_rtt_p50_ms"


def read(ctx):
    if "kv_page_writes" not in ctx.counters_end:
        return None
    calls = ctx.counters_end["batched_calls"] - ctx.counters_start["batched_calls"]
    if calls <= 0:
        return None
    return (ctx.counters_end["kv_page_writes"] - ctx.counters_start["kv_page_writes"]) / calls
