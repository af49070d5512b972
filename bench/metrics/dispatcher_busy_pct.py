"""Share of the dispatcher loop's time spent working, from the program's spans.

``CloudVerifier``'s dispatch loop records ``dispatch.wait`` (for work),
``dispatch.coalesce`` (the ``batch_window`` sleep), ``dispatch.admit``,
``verify`` and ``dispatch.reply``.  Of those that end in the window: the
union of ``dispatch.admit``, ``verify`` and ``dispatch.reply`` over the
time from the first one's start to the last one's end, in percent.  Time
between spans counts as not working.  A program without the dispatch spans
reads nothing.
"""

LAYER = "dispatcher"
UNIT = "%"
SOURCE = "program_span"
MOVES = "committed_tok_s"

BUSY = ("dispatch.admit", "verify", "dispatch.reply")
LOOP = BUSY + ("dispatch.wait", "dispatch.coalesce")


def read(ctx):
    loop = [(name, t0, t1) for name, t0, t1 in ctx.program_spans if name in LOOP]
    if not any(name == "dispatch.admit" for name, _, _ in loop):
        return None
    lo, hi = min(t0 for _, t0, _ in loop), max(t1 for _, _, t1 in loop)
    if hi <= lo:
        return None
    busy, end = 0.0, lo
    for t0, t1 in sorted((t0, t1) for name, t0, t1 in loop if name in BUSY):
        if t1 > end:
            busy += t1 - max(t0, end)
            end = t1
    return 100.0 * busy / (hi - lo)
