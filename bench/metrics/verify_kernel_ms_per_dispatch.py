"""Device time of the fused verify kernel per dispatch, from the profiler trace."""

LAYER = "fused verify kernel"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "nav_rtt_p50_ms"


def read(ctx):
    if ctx.trace is None or not ctx.traced_dispatches or not ctx.trace["kernel_count"]["verify_kernel"]:
        return None
    return 1e3 * ctx.trace["kernel_s"]["verify_kernel"] / len(ctx.traced_dispatches)
