"""The fused verify kernel's share of its roofline, in percent.

For each dispatch in the traced window, the least time the chip could take
for what the algorithm needs (``bench/counts.py``: LM head once, distinct
pages once, useful rows only) at the peaks of ``bench/peaks.json``; their
sum over the kernel's device time in the trace.  ``ctx.roofline_bound``
says which bound (bytes or FLOPs) binds more often.
"""

LAYER = "fused verify kernel"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "committed_tok_s"


def read(ctx):
    if ctx.trace is None or not ctx.traced_dispatches:
        return None
    k = ctx.trace["kernel_s"]["verify_kernel"]
    if k <= 0:
        return None
    return 100.0 * sum(d["roofline_s"] for d in ctx.traced_dispatches) / k
