"""Sessions per verify dispatch: the dispatcher's counters over the window.

``CloudVerifier.stats``: change in ``nav_calls`` over change in
``batched_calls`` between the window's open and close.
"""

LAYER = "dispatcher"
UNIT = "sessions"
SOURCE = "program_counter"
MOVES = "committed_tok_s"


def read(ctx):
    calls = ctx.counters_end["batched_calls"] - ctx.counters_start["batched_calls"]
    if calls <= 0:
        return None
    return (ctx.counters_end["nav_calls"] - ctx.counters_start["nav_calls"]) / calls
