"""Device time of every op but the verify kernel, per dispatch (pool writes,
input copies, the LM-head pad and GQA repeat), from the profiler trace."""

LAYER = "device ops outside the kernel"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "nav_rtt_p50_ms"


def read(ctx):
    if ctx.trace is None or not ctx.traced_dispatches or not ctx.trace["kernel_count"]["verify_kernel"]:
        return None
    other = ctx.trace["busy_s"] - ctx.trace["kernel_s"]["verify_kernel"]
    return 1e3 * other / len(ctx.traced_dispatches)
