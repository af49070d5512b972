"""Useful FLOPs of every dispatch in the traced window over window x bf16 peak.

The whole verify step's share of the chip's peak (``bench/peaks.json``,
bf16; the configuration computes in float32, so this overstates nothing).
"""

LAYER = "verify step"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "committed_tok_s"


def read(ctx):
    if ctx.trace is None or not ctx.traced_dispatches:
        return None
    flops = sum(d["flops"] for d in ctx.traced_dispatches)
    return 100.0 * flops / (ctx.trace["window_s"] * ctx.peaks["flops_per_s"])
