"""95th percentile of the dispatcher's queue wait, from its ``nav_queue`` spans.

``CloudVerifier`` records one ``nav_queue`` span per admitted request, from
enqueue to the start of the backend call; spans that end in the window count.
"""

import numpy as np

LAYER = "dispatcher"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "nav_rtt_p95_ms"


def read(ctx):
    waits = [t1 - t0 for name, t0, t1 in ctx.program_spans if name == "nav_queue"]
    return float(np.percentile(waits, 95)) * 1e3 if waits else None
