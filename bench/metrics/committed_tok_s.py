"""Tokens committed to all sessions by results received in the window, per second.

Each ``NavResult`` commits its accepted drafts plus the correction
(``n_accepted + 1``), stamped by the benchmark's wrapper at the edge.
"""

UNIT = "tokens/s"
SOURCE = "host_clock"


def read(ctx):
    return sum(r["n_accepted"] + 1 for r in ctx.rounds) / ctx.window_s
