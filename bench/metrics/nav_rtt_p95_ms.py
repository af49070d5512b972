"""95th percentile of the NAV round trip (same rounds as ``nav_rtt_p50_ms``)."""

import numpy as np

UNIT = "ms"
SOURCE = "host_clock"


def read(ctx):
    rtt = [r["t_res"] - r["t_req"] for r in ctx.rounds]
    return float(np.percentile(rtt, 95)) * 1e3 if rtt else None
