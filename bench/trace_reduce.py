"""From a profiler trace to device numbers: busy time, kernel time, breakdown.

A trace is reduced to plain data first (``load_xplane``): planes, their
lines, and each line's events as ``[name, start_ns, duration_ns]``, all on
the profiler's one clock.  ``reduce_trace`` then works on that data alone,
so the tests check it on a small recorded trace without a chip.

* The window is the benchmark's own ``bench.window`` host span.
* Device busy time is the union of the intervals of the device's op events
  (line ``XLA Ops`` of each ``/device:TPU:<n>`` plane) clipped to the
  window, averaged over the chips.
* A kernel's device time is the sum of its op events, found by the names in
  ``bench/kernels.json``.
* Idle gaps are the holes in that union.  Each is named by the innermost
  ``bench.*`` host span that covers its midpoint, or ``no bench span``.
"""

from __future__ import annotations

import json
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, Sequence, Tuple

import numpy as np

KERNELS_FILE = Path(__file__).resolve().parent / "kernels.json"
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."


def kernel_names(path: Path = KERNELS_FILE) -> Dict[str, List[str]]:
    """``{kernel: [substrings of its op names in the trace]}``."""
    return {k: v["trace_names"] for k, v in json.loads(Path(path).read_text()).items()}


def load_xplane(path: str, keep_host_prefix: str = SPAN_PREFIX) -> Dict:
    """Read an ``.xplane.pb`` into plain data, keeping what the reduction reads.

    Device planes keep their op line; host planes keep only events whose
    name starts with ``keep_host_prefix``.
    """
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    planes = []
    for plane in data.planes:
        device = plane.name.startswith(DEVICE_PLANE)
        lines = []
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            evs = [[e.name, float(e.start_ns), float(e.duration_ns)] for e in line.events
                   if device or e.name.startswith(keep_host_prefix)]
            if evs:
                lines.append({"name": line.name, "events": evs})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _clip(a: float, b: float, lo: float, hi: float):
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


def _labeller(labels: Sequence[Tuple[float, float, str]]):
    """``f(t) -> name`` of the shortest labelled span covering ``t``, or None."""
    if not labels:
        return lambda t: None
    bounds = np.unique([x for a, b, _ in labels for x in (a, b)])
    best = np.full(len(bounds), np.inf)
    owner = np.full(len(bounds), -1)
    for i, (a, b, _) in enumerate(labels):
        i0, i1 = np.searchsorted(bounds, [a, b])
        seg = slice(i0, i1)  # elementary segments [bounds[j], bounds[j+1]) inside [a, b)
        shorter = best[seg] > (b - a)
        owner[seg][shorter] = i
        best[seg] = np.where(shorter, b - a, best[seg])

    def name(t: float):
        j = int(np.searchsorted(bounds, t, side="right")) - 1
        if j < 0 or owner[j] < 0:
            return None
        return labels[owner[j]][2]

    return name


def reduce_trace(trace: Dict, kernels: Dict[str, Sequence[str]], top: int = 10) -> Dict:
    """Device numbers over the ``bench.window`` span, in seconds."""
    host_spans = []
    for plane in trace["planes"]:
        if plane["name"].startswith(DEVICE_PLANE):
            continue
        for line in plane["lines"]:
            host_spans += [(s, s + d, n) for n, s, d in line["events"] if n.startswith(SPAN_PREFIX)]
    windows = [(a, b) for a, b, n in host_spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    lo, hi = windows[0]
    label_of = _labeller([(a, b, n) for a, b, n in host_spans if n != WINDOW_SPAN])
    devices = [p for p in trace["planes"] if p["name"].startswith(DEVICE_PLANE)]
    if not devices:
        raise ValueError("the trace holds no device plane")
    busy, kernel_s, kernel_n = 0.0, defaultdict(float), defaultdict(int)
    op_s: Dict[str, float] = defaultdict(float)
    gaps: Dict[str, float] = defaultdict(float)
    n_gaps = 0
    for plane in devices:
        ivs = []
        for line in plane["lines"]:
            for name, s, d in line["events"]:
                c = _clip(s, s + d, lo, hi)
                if c is None:
                    continue
                ivs.append(c)
                op_s[name] += (c[1] - c[0]) * 1e-9
                for k, pats in kernels.items():
                    if any(p in name for p in pats):
                        kernel_s[k] += (c[1] - c[0]) * 1e-9
                        kernel_n[k] += 1
        merged = _union(ivs)
        busy += sum(b - a for a, b in merged) * 1e-9
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            gaps[label_of(0.5 * (a + b)) or "no bench span"] += (b - a) * 1e-9
            n_gaps += 1
    nd = len(devices)
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy / nd,
        "kernel_s": {k: kernel_s[k] / nd for k in kernels},
        "kernel_count": {k: kernel_n[k] / nd for k in kernels},
        "device_ops": sorted(([n, s / nd] for n, s in op_s.items()), key=lambda x: -x[1])[:top],
        "idle_gaps": sorted(([n, s / nd] for n, s in gaps.items()), key=lambda x: -x[1])[:top],
        "n_gaps": n_gaps / nd,
    }
