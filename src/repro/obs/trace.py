"""Span-based tracing on the injectable clock (deterministic under VirtualClock).

A :class:`Tracer` records named *spans* — ``draft``, ``upload``,
``nav_queue``, ``verify``, ``commit``, ``migrate``, ``frame`` — with
arbitrary scalar attributes (session, round, verifier, …) into a bounded
ring buffer.  Every timestamp comes from the tracer's clock, so a run under
``VirtualClock`` produces the *same* spans on every rerun: the exported
Chrome trace-event JSON is byte-identical across seeded reruns (asserted in
``tests/test_obs.py`` and the CI ``obs-smoke`` job).

The export (:meth:`Tracer.export_chrome_trace`) is the standard Chrome
``traceEvents`` format, loadable in ``chrome://tracing`` or Perfetto.  The
pure-Python analyzer (:func:`round_report` / :func:`session_bubble_fractions`)
reconstructs each (session, round)'s stage timeline and reports the pipeline
*bubble fraction* — the share of the round's wall span covered by no stage —
which is exactly the overlap PipeSD's pipelined drafting (§3.2/§4) exists to
shrink.

Instrumentation sites hold a tracer that defaults to the module-level
:data:`NULL_TRACER`, whose ``span`` context manager never reads the clock —
tracing disabled costs one attribute lookup and a no-op ``with``.

An enabled tracer's context spans (:meth:`Tracer.span`, not the post-hoc
:meth:`Tracer.add`) record their ``parent`` (the span open on the same
thread when it started) and take the ``dispatch`` and ``verifier``
attributes of their parent when they set none, so every span a verifier's
dispatch causes carries that dispatch's number.
"""

from __future__ import annotations

import json
import threading
from collections import deque
from dataclasses import dataclass
from typing import Any, Deque, Dict, List, Optional, Tuple

__all__ = [
    "Span",
    "Tracer",
    "NullTracer",
    "NULL_TRACER",
    "STAGES",
    "round_report",
    "session_bubble_fractions",
]

#: Canonical stage names in pipeline order; the Chrome export maps each to a
#: fixed track (tid) so Perfetto lays rounds out consistently.
STAGES: Tuple[str, ...] = ("draft", "upload", "nav_queue", "verify", "commit", "migrate", "frame")

#: Stages that represent productive pipeline work for the bubble analyzer
#: (``migrate``/``frame`` are control-plane, not round stages).
ROUND_STAGES: Tuple[str, ...] = ("draft", "upload", "nav_queue", "verify", "commit")

#: Attributes a context span takes from its parent when it does not set them.
INHERITED: Tuple[str, ...] = ("dispatch", "verifier")


def _default_clock():
    """The process-wide ``SYSTEM_CLOCK``, imported lazily.

    ``repro.runtime`` instruments itself with this package, so a module-level
    import here would be circular; resolving the default at first use keeps
    the dependency one-directional at import time.
    """
    from ..runtime.simclock import SYSTEM_CLOCK

    return SYSTEM_CLOCK


@dataclass(frozen=True)
class Span:
    """One finished span: half-open interval ``[t0, t1)`` plus attributes.

    ``attrs`` is a key-sorted tuple of (name, value) pairs so spans are
    hashable, comparable, and render deterministically.  ``sid`` numbers
    the context spans of one tracer in the order they started (1, 2, …) and
    ``parent`` is the ``sid`` of the span open on the same thread when this
    one started; both are 0 for spans recorded with :meth:`Tracer.add`.
    """

    name: str
    t0: float
    t1: float
    attrs: Tuple[Tuple[str, Any], ...] = ()
    sid: int = 0
    parent: int = 0

    @property
    def duration(self) -> float:
        """Span length [s] (never negative)."""
        return max(self.t1 - self.t0, 0.0)

    def get(self, key: str, default: Any = None) -> Any:
        """Attribute lookup by name."""
        for k, v in self.attrs:
            if k == key:
                return v
        return default


class _SpanContext:
    """Context manager produced by :meth:`Tracer.span`; records on exit."""

    __slots__ = ("_tracer", "_name", "_attrs", "_t0", "_sid", "_parent")

    def __init__(self, tracer: "Tracer", name: str, attrs: Dict[str, Any]):
        self._tracer = tracer
        self._name = name
        self._attrs = attrs
        self._t0 = 0.0
        self._sid = 0
        self._parent = 0

    def __enter__(self) -> "_SpanContext":
        tracer = self._tracer
        stack = tracer._open_spans()
        if stack:
            top = stack[-1]
            self._parent = top._sid
            for key in INHERITED:
                if key not in self._attrs and key in top._attrs:
                    self._attrs[key] = top._attrs[key]
        with tracer._lock:
            tracer._last_sid += 1
            self._sid = tracer._last_sid
        stack.append(self)
        self._t0 = tracer.clock.monotonic()
        return self

    def __exit__(self, *exc) -> bool:
        tracer = self._tracer
        t1 = tracer.clock.monotonic()
        tracer._open_spans().pop()
        tracer._record(
            Span(self._name, float(self._t0), float(t1), tuple(sorted(self._attrs.items())),
                 self._sid, self._parent)
        )
        return False


class Tracer:
    """Clock-driven span recorder with bounded ring-buffer storage.

    Thread-safe: spans may be recorded from any actor/thread; the ring
    buffer holds the most recent ``capacity`` finished spans, and
    ``dropped`` counts the older ones it let go, so a trace whose ring
    overflowed cannot pass for a complete one.  Under ``VirtualClock`` the
    recording order is deterministic, so exports are byte-reproducible.
    """

    enabled = True

    def __init__(self, clock=None, capacity: int = 65536):
        self.clock = clock if clock is not None else _default_clock()
        self._spans: Deque[Span] = deque(maxlen=int(capacity))
        self._lock = threading.Lock()
        self._local = threading.local()
        self._last_sid = 0
        self.dropped = 0

    def _open_spans(self) -> List[_SpanContext]:
        """This thread's stack of open context spans, innermost last."""
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _record(self, span: Span) -> None:
        with self._lock:
            if len(self._spans) == self._spans.maxlen:
                self.dropped += 1
            self._spans.append(span)

    # -------------------------------------------------------------- record --
    def span(self, name: str, **attrs: Any) -> _SpanContext:
        """Context manager timing a stage: ``with tracer.span("draft", session=3):``."""
        return _SpanContext(self, name, attrs)

    def add(self, name: str, t0: float, t1: float, **attrs: Any) -> None:
        """Record an already-timed span (for queue waits measured from stamps).

        It has no parent and takes no attributes from an open span.
        """
        self._record(Span(name, float(t0), float(t1), tuple(sorted(attrs.items()))))

    # --------------------------------------------------------------- query --
    def spans(self) -> List[Span]:
        """Snapshot of the ring buffer, oldest first."""
        with self._lock:
            return list(self._spans)

    def clear(self) -> None:
        """Drop every recorded span (and the count of those the ring dropped)."""
        with self._lock:
            self._spans.clear()
            self.dropped = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._spans)

    # -------------------------------------------------------------- export --
    def export_chrome_trace(self) -> str:
        """Chrome trace-event JSON (Perfetto-loadable), deterministically rendered.

        Events are complete (``ph="X"``) spans with microsecond timestamps;
        ``pid`` is the span's ``session`` attribute (0 when absent) and
        ``tid`` the stage's fixed track index, so one session renders as one
        process with a lane per stage.  A context span's args also carry its
        ``sid`` and ``parent``, and the document carries ``dropped_spans``
        when the ring overflowed.  Keys are sorted and floats rounded to the
        microsecond domain's 3 decimals — two identical runs produce
        byte-identical output.
        """
        events = []
        for s in self.spans():
            args = {k: v for k, v in s.attrs}
            if s.sid:
                args.update(sid=s.sid, parent=s.parent)
            tid = STAGES.index(s.name) if s.name in STAGES else len(STAGES)
            events.append(
                dict(
                    name=s.name,
                    ph="X",
                    ts=round(s.t0 * 1e6, 3),
                    dur=round(s.duration * 1e6, 3),
                    pid=int(args.pop("session", 0)),
                    tid=tid,
                    args=args,
                )
            )
        events.sort(key=lambda e: (e["ts"], e["pid"], e["tid"], e["name"]))
        doc: Dict[str, Any] = {"displayTimeUnit": "ms", "traceEvents": events}
        if self.dropped:
            doc["dropped_spans"] = self.dropped
        return json.dumps(doc, sort_keys=True, separators=(",", ":"))


class _NullSpanContext:
    """Shared no-op context manager (never reads the clock)."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpanContext":
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NULL_CTX = _NullSpanContext()


class NullTracer(Tracer):
    """Disabled tracer: ``span``/``add`` are no-ops with zero clock reads."""

    enabled = False

    def __init__(self):
        # No clock at all: the null tracer never reads one, and resolving
        # the default would import the runtime during its own import.
        self.clock = None
        self._spans = deque(maxlen=1)
        self._lock = threading.Lock()
        self.dropped = 0

    def span(self, name: str, **attrs: Any) -> _NullSpanContext:  # type: ignore[override]
        """A shared do-nothing context manager."""
        return _NULL_CTX

    def add(self, name: str, t0: float, t1: float, **attrs: Any) -> None:
        """Discard the span."""


#: Default tracer for every instrumentation site — tracing is opt-in.
NULL_TRACER = NullTracer()


# --------------------------------------------------------------------------- #
# Critical-path / overlap analysis
# --------------------------------------------------------------------------- #


def _union_length(intervals: List[Tuple[float, float]]) -> float:
    """Total length covered by a set of (possibly overlapping) intervals."""
    if not intervals:
        return 0.0
    intervals = sorted(intervals)
    total = 0.0
    cur_lo, cur_hi = intervals[0]
    for lo, hi in intervals[1:]:
        if lo > cur_hi:
            total += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        else:
            cur_hi = max(cur_hi, hi)
    total += cur_hi - cur_lo
    return total


def round_report(spans: List[Span]) -> List[Dict[str, Any]]:
    """Per-(session, round) stage timeline: wall, busy, bubble, critical stage.

    For every (session, round) key seen in ``ROUND_STAGES`` spans, reports:

    * ``wall`` — earliest stage start to latest stage end;
    * ``busy`` — interval-union time covered by *any* stage;
    * ``bubble_fraction`` — ``1 − busy/wall``: the share of the round during
      which the pipeline sat idle (the quantity early upload shrinks);
    * ``critical_stage`` — the stage with the largest total duration (ties
      break in pipeline order), i.e. the round's dominant latency term;
    * per-stage total durations under ``stage_s``.

    Spans missing a ``round`` attribute are ignored; sessions default to 0.
    """
    by_round: Dict[Tuple[int, int], List[Span]] = {}
    for s in spans:
        if s.name not in ROUND_STAGES:
            continue
        rnd = s.get("round")
        if rnd is None:
            continue
        key = (int(s.get("session", 0)), int(rnd))
        by_round.setdefault(key, []).append(s)

    reports: List[Dict[str, Any]] = []
    for (session, rnd) in sorted(by_round):
        group = by_round[(session, rnd)]
        t0 = min(s.t0 for s in group)
        t1 = max(s.t1 for s in group)
        wall = max(t1 - t0, 0.0)
        busy = _union_length([(s.t0, s.t1) for s in group if s.t1 > s.t0])
        stage_s = {name: 0.0 for name in ROUND_STAGES}
        for s in group:
            stage_s[s.name] += s.duration
        critical = max(ROUND_STAGES, key=lambda n: (stage_s[n], -ROUND_STAGES.index(n)))
        reports.append(
            dict(
                session=session,
                round=rnd,
                t0=t0,
                t1=t1,
                wall=wall,
                busy=min(busy, wall) if wall > 0 else busy,
                bubble_fraction=(1.0 - min(busy, wall) / wall) if wall > 0 else 0.0,
                critical_stage=critical,
                stage_s=stage_s,
            )
        )
    return reports


def session_bubble_fractions(spans: List[Span]) -> Dict[int, float]:
    """Per-session pipeline bubble fraction aggregated over its rounds.

    ``1 − Σ busy / Σ wall`` across the session's rounds — 0.0 means the
    stages tile the round perfectly (no idle gaps), higher means the
    pipeline is stalling between stages.
    """
    totals: Dict[int, Tuple[float, float]] = {}
    for rep in round_report(spans):
        wall, busy = totals.get(rep["session"], (0.0, 0.0))
        totals[rep["session"]] = (wall + rep["wall"], busy + rep["busy"])
    return {
        session: (1.0 - busy / wall) if wall > 0 else 0.0
        for session, (wall, busy) in sorted(totals.items())
    }


def critical_path(spans: List[Span], session: int, rnd: int) -> Optional[str]:
    """The dominant stage of one (session, round), or None when unrecorded."""
    for rep in round_report(spans):
        if rep["session"] == session and rep["round"] == rnd:
            return rep["critical_stage"]
    return None
