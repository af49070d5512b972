"""Median NAV round trip: ``NavRequest`` sent to its ``NavResult`` received.

Over every round whose result arrived in the window, timed at the
benchmark's wrapper around each edge client's transport.
"""

import numpy as np

UNIT = "ms"
SOURCE = "host_clock"


def read(ctx):
    rtt = [r["t_res"] - r["t_req"] for r in ctx.rounds]
    return float(np.percentile(rtt, 50)) * 1e3 if rtt else None
