"""Telemetry configuration (DESIGN.md §3.15).

One frozen knob object, threaded through engine constructors
(``Engine(..., obs=ObsConfig(...))`` / ``ShardEngineBase(..., obs=...)``).
The hard contract of the subsystem is the **zero-overhead off-switch**:
an ``ObsConfig`` — enabled or not — never changes how ``_make_step`` /
``_step`` are built.  Every metric derives from counters that *already*
ride ``EngineState`` / ``DistState`` (``update_count``, ``traffic_*``,
``beats``, ``prio``), read lazily on the host, so the jitted step's
jaxpr is byte-identical with telemetry on or off
(tests/test_obs.py asserts the strings are equal).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ObsConfig:
    """Knobs for the telemetry layer.

    enabled
        Master switch.  Off (the default) reproduces the pre-telemetry
        trace behavior exactly: ``run`` returns rows only when asked
        (``trace_fn`` locally; always for the dist engines).
    trace_every
        Batch size of the host drain: lazy per-step rows accumulate as
        device scalars and are converted with **one** ``device_get``
        every ``trace_every`` steps (and once at loop exit).  Rows are
        still recorded for *every* step — only the host transfer is
        batched.  1 (default) matches the old per-step behavior.
    timeline
        Record the host spans (``graphlab.run``/``done``/``dispatch``,
        migrations, steals, ``apply_delta``/regrow) into an
        ``obs.Timeline`` for Chrome-trace/Perfetto export; the spans
        reach the profiler's trace either way.
    residual_quantiles
        Extra residual quantiles (e.g. ``(0.5, 0.9)``) appended to each
        row as ``residual_q50``/``residual_q90``; None records only
        ``residual_max``.  Computed lazily outside the jitted step.
    """

    enabled: bool = False
    trace_every: int = 1
    timeline: bool = False
    residual_quantiles: Optional[Tuple[float, ...]] = None

    def __post_init__(self):
        if int(self.trace_every) < 1:
            raise ValueError("trace_every must be >= 1")
