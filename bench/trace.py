"""Reduction of a profiler trace to device busy time, operation time by name
and idle gaps attributed to the harness's own spans.

The harness traces its window with ``jax.profiler.trace`` and marks its
calls into the program with ``jax.profiler.TraceAnnotation`` spans named
``bench.*`` (the window itself is ``bench.window``).  The profiler writes
an ``.xplane.pb``; ``jax.profiler.ProfileData`` reads it.  Device planes
are ``/device:TPU:<i>``; their ``XLA Ops`` line holds one event per
operation the device ran, on the same clock as the host's spans.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW = "bench.window"
N_GAPS = 10
# loops and calls that hold other operations of the same line
CONTAINERS = ("while", "conditional", "call")
_SUFFIX = re.compile(r"\.\d+$")


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def union_length(intervals: List[Tuple[float, float]]) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def idle_intervals(intervals, lo: float, hi: float):
    """The parts of ``[lo, hi]`` that no interval covers."""
    gaps, cursor = [], lo
    for s, e in sorted(intervals):
        if s > cursor:
            gaps.append((cursor, min(s, hi)))
        cursor = max(cursor, e)
        if cursor >= hi:
            break
    if cursor < hi:
        gaps.append((cursor, hi))
    return [(s, e) for s, e in gaps if e > s]


def _innermost(spans, t: float) -> str:
    best = None
    for s, e, name in spans:
        if s <= t <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else WINDOW


def label(op: str) -> str:
    """A short label for an ``XLA Ops`` event, whose name is the whole HLO
    instruction: its name and result type, ``fusion.646 f32[2097152]``."""
    name, _, rest = op.partition(" = ")
    kind = rest.split(" ", 1)[0]
    return f"{name.lstrip('%')} {re.sub(r'{[^}]*}', '', kind)}"[:80].strip()


def _container(op: str) -> bool:
    return _SUFFIX.sub("", label(op).split(" ")[0]) in CONTAINERS


def reduce(xplane: str) -> Dict:
    """``reduce_profile`` of the trace in the file ``xplane``."""
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(xplane), xplane)


def reduce_profile(data, where: str = "the trace") -> Dict:
    """Busy and idle time of the devices inside the ``bench.window`` span,
    operation time by name, and the idle gaps by host span.

    Busy time is the union of all operations, loops included; operation
    time leaves out the loops and calls that hold other operations, so no
    time is counted twice.  Returns ``busy_s`` and ``window_s`` (averaged
    over devices), ``ops`` (operation name → device seconds, averaged over
    devices), ``op_text``
    (operation name → its name and stats as one string, for matching) and
    ``gaps`` (``(span name, seconds)`` of device 0's ``N_GAPS`` longest
    idle gaps, longest first)."""
    spans, devices, op_text = [], {}, {}
    for plane in data.planes:
        on_device = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            ops = devices.setdefault(plane.name, []) \
                if on_device and line.name.startswith(OPS_LINE) else None
            for ev in line.events:
                if ops is not None:
                    ops.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name))
                    if ev.name not in op_text:
                        op_text[ev.name] = " ".join(
                            [ev.name] + [str(v) for _, v in ev.stats])
                elif ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name))
    windows = [s for s in spans if s[2] == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW} span in {where}")
    lo, hi = windows[0][0], windows[0][1]
    if not devices:
        raise ValueError(f"no {DEVICE_PREFIX}* plane with an {OPS_LINE!r} "
                         f"line in {where}")
    busy, ops = [], {}
    first = sorted(devices)[0]
    gaps = []
    for name in sorted(devices):
        clipped = [(max(s, lo), min(e, hi), op) for s, e, op in devices[name]
                   if e > lo and s < hi]
        busy.append(union_length([(s, e) for s, e, _ in clipped]))
        for s, e, op in clipped:
            if not _container(op):
                ops[op] = ops.get(op, 0.0) + (e - s) * 1e-9 / len(devices)
        if name == first:
            idle = idle_intervals([(s, e) for s, e, _ in clipped], lo, hi)
            idle.sort(key=lambda g: g[0] - g[1])
            inner = [s for s in spans if s[2] != WINDOW]
            gaps = [(_innermost(inner, 0.5 * (s + e)) if inner else WINDOW,
                     (e - s) * 1e-9) for s, e in idle[:N_GAPS]]
    return {"busy_s": sum(busy) / len(busy) * 1e-9,
            "window_s": (hi - lo) * 1e-9, "n_devices": len(devices),
            "ops": ops, "op_text": op_text, "gaps": gaps}


def seconds_matching(reduced: Dict, pattern: str) -> Optional[float]:
    """Device seconds of the operations whose name or stats contain
    ``pattern``; None when no operation does."""
    hits = [op for op, text in reduced["op_text"].items() if pattern in text]
    if not hits:
        return None
    return sum(reduced["ops"].get(op, 0.0) for op in hits)


def top_ops(reduced: Dict, k: int = 10) -> List[list]:
    """The ``k`` operations that took the most device time, by label."""
    merged: Dict[str, float] = {}
    for op, sec in reduced["ops"].items():
        merged[label(op)] = merged.get(label(op), 0.0) + sec
    return [[n, s] for n, s in sorted(merged.items(),
                                      key=lambda x: -x[1])[:k]]
