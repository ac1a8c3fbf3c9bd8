"""repro.obs — unified telemetry (DESIGN.md §3.15).

Three layers: typed metrics frames drained in batches
(``obs.metrics``), host spans through one primitive, ``span``, that
writes the profiler's trace, a process-wide ``span_totals`` table and
an optional ``Timeline`` with Chrome-trace/Perfetto export
(``obs.timeline`` / ``obs.export``), and the ``Supervisor`` control
loop that consumes the live stream inside ``run()``
(``obs.supervisor``).  With ``ObsConfig`` disabled the jitted
step jaxprs are byte-identical to an engine built without telemetry —
every metric derives from counters already riding the state.
"""
from repro.obs.config import ObsConfig
from repro.obs.export import chrome_trace, write_chrome_trace, \
    write_events_jsonl
from repro.obs.metrics import (METRICS_SCHEMA, MetricsFrame, RowCollector,
                               aligned_aggregate, lazy_dist_row,
                               lazy_local_row, live_aggregate, mixing_report)
from repro.obs.session import (ObsSession, attach_session, engine_session,
                               engine_span)
from repro.obs.supervisor import Supervisor
from repro.obs.timeline import (Timeline, reset_span_totals, span,
                                span_totals)

__all__ = [
    "ObsConfig", "ObsSession", "MetricsFrame", "METRICS_SCHEMA",
    "RowCollector", "lazy_local_row", "lazy_dist_row",
    "aligned_aggregate", "live_aggregate", "mixing_report",
    "Timeline", "span", "span_totals", "reset_span_totals",
    "chrome_trace", "write_chrome_trace", "write_events_jsonl",
    "Supervisor", "attach_session", "engine_session", "engine_span",
]
