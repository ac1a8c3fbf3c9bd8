"""The program's own names for its layers: its device scopes
(``jax.named_scope("graphlab.<layer>")``) joined to a profiler trace's
operations through the compiled program, and its host spans' totals.

A trace names each operation by its HLO instruction (``%fusion.548 =
f32[262144]{0} fusion(...)``) and carries no scope that
``jax.profiler.ProfileData`` exposes; the compiled module's text
(``Compiled.as_text()``) holds each instruction with its ``op_name``
metadata, ``jit(_step)/graphlab.edge_weight/gather``, and a fusion
carries the ``op_name`` of its root.  An instruction belongs to the
innermost ``graphlab.*`` component of its ``op_name``.

Host spans (``repro.obs.span``) keep a process-wide count and seconds per
name (``repro.obs.span_totals``); one run of a cell is one process.
"""
from __future__ import annotations

import re
from typing import Dict, Optional

PREFIX = "graphlab."
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def innermost(op_name: str) -> Optional[str]:
    """The innermost ``graphlab.*`` component of an ``op_name``."""
    for part in reversed(op_name.split("/")):
        if part.startswith(PREFIX):
            return part
    return None


def scope_map(hlo_text: str) -> Dict[str, str]:
    """{instruction name → innermost ``graphlab.*`` scope} over every
    instruction of ``hlo_text`` whose ``op_name`` holds one."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        op = _OP_NAME.search(line)
        scope = innermost(op.group(1)) if op else None
        if scope is not None:
            out[m.group(1)] = scope
    return out


def op_name(event: str) -> str:
    """The instruction name of a trace's operation event
    (``%fusion.548 = ...`` → ``fusion.548``)."""
    return event.split(" = ", 1)[0].strip().lstrip("%")


def seconds_by_scope(ops: Dict[str, float], scopes: Dict[str, str]
                     ) -> Dict[Optional[str], float]:
    """Device seconds of ``ops`` (trace event → seconds, as
    ``bench/trace.py`` reduces them) by scope; operations that map to
    none fall under ``None``."""
    out: Dict[Optional[str], float] = {}
    for event, sec in ops.items():
        key = scopes.get(op_name(event))
        out[key] = out.get(key, 0.0) + sec
    return out


def span_seconds(name: str) -> Optional[float]:
    """Host seconds of the program's spans called ``name`` in this
    process; None where the program keeps no span table (it predates
    ``repro.obs.span_totals``) or closed no such span."""
    try:
        from repro.obs import span_totals
    except ImportError:
        return None
    total = span_totals().get(name)
    return total.seconds if total else None
