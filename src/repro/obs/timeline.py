"""Host spans (DESIGN.md §3.15, layer 2): one primitive, ``span``.

``span(name)`` opens a ``jax.profiler.TraceAnnotation``, so the span
lands in the profiler's host plane on the device trace's clock; adds its
count and seconds to a process-wide table (``span_totals``); and, given a
session with a timeline, records it into that ``Timeline`` too.  Spans
are **host-observed** intervals: XLA executes asynchronously, so a span
around a dispatch measures the host's view, and a span around a blocking
read (``graphlab.done``) holds the device's tail.  Where the device's
time goes inside a step is the profiler's to say, under the step's
``jax.named_scope`` names (``graphlab.select``/``edge_weight``/...).
Every program span name starts with ``graphlab.``.

Export (``obs/export.py``) emits the Chrome trace event format, which
Perfetto and chrome://tracing both load, for runs without the profiler.
"""
from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

PREFIX = "graphlab."


class SpanTotal(NamedTuple):
    count: int
    seconds: float


_totals: Dict[str, SpanTotal] = {}
_totals_lock = threading.Lock()


def span_totals() -> Dict[str, SpanTotal]:
    """Count and host seconds of every span closed in this process since
    the last ``reset_span_totals``, by name."""
    with _totals_lock:
        return dict(_totals)


def reset_span_totals() -> None:
    with _totals_lock:
        _totals.clear()


@contextmanager
def span(name: str, *, session=None, track: str = "host", cat: str = "span",
         args: Optional[Dict[str, Any]] = None):
    """The span primitive: a profiler annotation, a ``span_totals`` entry
    and, when ``session`` (an ``ObsSession``) has a timeline, a timeline
    event on ``track``."""
    if not name.startswith(PREFIX):
        raise ValueError(f"span {name!r}: program spans start with {PREFIX!r}")
    tl = getattr(session, "timeline", None)
    t0 = time.perf_counter()
    try:
        with TraceAnnotation(name):
            yield
    finally:
        t1 = time.perf_counter()
        with _totals_lock:
            c, s = _totals.get(name, (0, 0.0))
            _totals[name] = SpanTotal(c + 1, s + (t1 - t0))
        if tl is not None:
            tl.span(name, t0 - tl._t0, t1 - tl._t0, track=track, cat=cat,
                    args=args)


class Timeline:
    """An append-only list of Chrome-trace events with a private epoch;
    ``ts``/``dur`` are microseconds since construction.  Spans reach it
    through ``span(..., session=)``."""

    def __init__(self):
        self.events: List[Dict[str, Any]] = []
        self._t0 = time.perf_counter()
        self._tracks: Dict[str, int] = {}

    def now(self) -> float:
        """Seconds since the timeline epoch."""
        return time.perf_counter() - self._t0

    def _tid(self, track: str) -> int:
        if track not in self._tracks:
            self._tracks[track] = len(self._tracks)
        return self._tracks[track]

    def span(self, name: str, t0: float, t1: float, *, track: str = "host",
             cat: str = "step", args: Optional[Dict[str, Any]] = None
             ) -> None:
        """A complete ("X") event covering ``[t0, t1]`` (timeline
        seconds, e.g. from ``now()``)."""
        self.events.append({
            "name": name, "cat": cat, "ph": "X",
            "ts": t0 * 1e6, "dur": max(t1 - t0, 0.0) * 1e6,
            "pid": 0, "tid": self._tid(track), "args": dict(args or {}),
        })

    def instant(self, name: str, *, track: str = "events", cat: str = "event",
                args: Optional[Dict[str, Any]] = None) -> None:
        self.events.append({
            "name": name, "cat": cat, "ph": "i", "s": "p",
            "ts": self.now() * 1e6,
            "pid": 0, "tid": self._tid(track), "args": dict(args or {}),
        })

    def counter(self, name: str, values: Dict[str, float], *,
                track: str = "counters") -> None:
        self.events.append({
            "name": name, "cat": "counter", "ph": "C",
            "ts": self.now() * 1e6,
            "pid": 0, "tid": self._tid(track),
            "args": {k: float(v) for k, v in values.items()},
        })

    def metadata_events(self) -> List[Dict[str, Any]]:
        """Thread-name metadata rows so Perfetto labels the tracks."""
        return [{"name": "thread_name", "ph": "M", "pid": 0, "tid": tid,
                 "args": {"name": track}}
                for track, tid in self._tracks.items()]

