"""The per-run telemetry container (DESIGN.md §3.15).

An ``ObsSession`` is what a driver (engine ``run``, the Supervisor, a
benchmark) writes into: drained metric rows, a structured event log,
and — when ``ObsConfig.timeline`` is on — a ``Timeline`` of host spans.
It is deliberately dumb: no I/O, no device access; exporters
(``obs/export.py``) serialize it after the run."""
from __future__ import annotations

from typing import Any, Dict, List, Optional

from repro.obs.config import ObsConfig
from repro.obs.metrics import MetricsFrame
from repro.obs.timeline import Timeline, span


class ObsSession:
    def __init__(self, config: Optional[ObsConfig] = None):
        self.config = config if config is not None \
            else ObsConfig(enabled=True)
        self.rows: List[Dict[str, Any]] = []
        self.events: List[Dict[str, Any]] = []
        self.timeline: Optional[Timeline] = (
            Timeline() if self.config.timeline else None)
        self.drains = 0  # host-transfer batches (RowCollector drains)

    # -- metrics ----------------------------------------------------------
    def add_rows(self, rows: List[Dict[str, Any]]) -> None:
        self.rows.extend(rows)
        self.drains += 1

    def frames(self) -> List[MetricsFrame]:
        return [MetricsFrame.from_row(r) for r in self.rows]

    # -- events -----------------------------------------------------------
    def event(self, kind: str, **data: Any) -> Dict[str, Any]:
        """Appends a structured event (JSONL-able) and mirrors it as a
        timeline instant when tracing is on."""
        ev = {"kind": kind, **data}
        if self.timeline is not None:
            ev.setdefault("t", self.timeline.now())
            self.timeline.instant(kind, args=data)
        self.events.append(ev)
        return ev

    def span(self, name: str, **kw):
        """``obs.span`` into this session: recorded into the timeline when
        tracing is on — instrumentation sites never need to branch."""
        return span(name, session=self, **kw)


def attach_session(engine, session: Optional[ObsSession]) -> None:
    """Pins a session to an engine so out-of-loop instrumentation sites
    (``apply_delta``/``regrow_engine`` splices, migration rebuilds) can
    span into the same timeline the run loop writes.  Migration carries
    the attachment to the rebuilt engine (dist/migrate.py)."""
    engine._obs_session = session


def engine_session(engine) -> Optional[ObsSession]:
    return getattr(engine, "_obs_session", None)


def engine_span(engine, name: str, **kw):
    """``obs.span`` into the engine's attached session, if any."""
    return span(name, session=engine_session(engine), **kw)
