"""Typed metrics frames, batched host draining, and snapshot-aligned
aggregation (DESIGN.md §3.15, layer 1).

Every engine's ``run`` used to invent its own trace dict (local:
``total_updates``/``edges_touched``; dist: ``ghost_rows``/``rank_bytes``;
snapshot driver: ``max_prio``/``marker_rows``) and forced a device sync
per step to build it.  This module replaces all three with one schema
(``METRICS_SCHEMA``) recorded **lazily**: each step pushes a dict of
unevaluated device scalars into a ``RowCollector``, and one
``jax.device_get`` per ``trace_every`` steps converts the whole batch.
Collection never adds an op to the jitted step — every field derives
from counters already riding the state.

Snapshot-aligned aggregation (the paper's §4.3 move turned on the
metrics themselves): a live per-step reduction over a distributed mesh
mixes rows from different logical times — machine A's row may already
reflect updates that machine B's row predates.  ``aligned_aggregate``
instead reduces over the rows a *completed* Chandy-Lamport cut saved,
so the aggregate is a function of one consistent global state, anchored
to the cut's journal offset when the engine is streaming (the same
anchor ``dist/snapshot.py:save_snapshot`` records).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# -- canonical schema ---------------------------------------------------------

#: name -> (kind, doc).  Kinds: "i" counter/int, "f" float, "ti" tuple of
#: per-machine ints.  Rows may add engine-specific extras (snapshot driver:
#: ``marker_rows``/``snapshot_done_frac``) and user ``trace_fn`` keys.
METRICS_SCHEMA: Dict[str, Tuple[str, str]] = {
    "step": ("i", "engine step index after this step"),
    "updates": ("i", "cumulative vertex updates executed"),
    "edges_touched": ("i", "cumulative edge gathers (local engines only)"),
    "residual_max": ("f", "max scheduler priority (global residual)"),
    "backlog": ("i", "scheduled vertices (prio > tolerance)"),
    "wire_backlog": ("i", "ghost rows owed by the quantized wire's "
                          "deferral (0 for default wire / local)"),
    "traffic_rows_v": ("i", "vertex ghost rows shipped, cumulative"),
    "traffic_bytes_v": ("i", "vertex ghost payload bytes shipped"),
    "traffic_rows_e": ("i", "reverse-edge ghost rows shipped"),
    "traffic_bytes_e": ("i", "reverse-edge ghost payload bytes shipped"),
    "traffic_rows_r": ("i", "arbitration rank rows shipped (locking)"),
    "traffic_bytes_r": ("i", "arbitration rank payload bytes shipped"),
    "beats": ("ti", "per-machine heartbeat counters (dist only)"),
}

@dataclasses.dataclass
class MetricsFrame:
    """One step's metrics under the canonical schema; unknown row keys
    (user ``trace_fn`` fields, driver extras) land in ``extra``."""

    step: int = 0
    updates: int = 0
    edges_touched: int = 0
    residual_max: float = float("nan")
    backlog: int = 0
    wire_backlog: int = 0
    traffic_rows_v: int = 0
    traffic_bytes_v: int = 0
    traffic_rows_e: int = 0
    traffic_bytes_e: int = 0
    traffic_rows_r: int = 0
    traffic_bytes_r: int = 0
    beats: Optional[Tuple[int, ...]] = None
    extra: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @classmethod
    def from_row(cls, row: Dict[str, Any]) -> "MetricsFrame":
        known = {f.name for f in dataclasses.fields(cls)} - {"extra"}
        kw = {k: v for k, v in row.items() if k in known}
        kw["extra"] = {k: v for k, v in row.items() if k not in known}
        return cls(**kw)

    def to_row(self) -> Dict[str, Any]:
        row = {f.name: getattr(self, f.name)
               for f in dataclasses.fields(self) if f.name != "extra"}
        if row["beats"] is None:
            del row["beats"]
        row.update(self.extra)
        return row


# -- lazy rows + batched draining --------------------------------------------

def _py(v: Any) -> Any:
    """Host-converted scalar/tuple from a fetched numpy value."""
    if isinstance(v, np.ndarray):
        return v.item() if v.ndim == 0 else tuple(v.tolist())
    if isinstance(v, np.generic):
        return v.item()
    return v


class RowCollector:
    """Accumulates lazy per-step rows (dicts of device scalars) and
    converts them host-side in batches of ``every`` — one
    ``jax.device_get`` per drain, so telemetry adds no per-step sync.
    ``drains`` counts the transfers (asserted by tests)."""

    def __init__(self, every: int = 1, session=None):
        self.every = max(1, int(every))
        self.session = session
        self.rows: List[Dict[str, Any]] = []
        self.drains = 0
        self._pending: List[Tuple[Dict[str, Any], Optional[Dict]]] = []

    def push(self, lazy_row: Dict[str, Any],
             extra: Optional[Dict[str, Any]] = None) -> None:
        self._pending.append((lazy_row, extra))
        if len(self._pending) >= self.every:
            self.drain()

    def drain(self) -> None:
        if not self._pending:
            return
        fetched = jax.device_get(self._pending)  # ONE transfer for the batch
        self._pending = []
        self.drains += 1
        batch = []
        for raw, extra in fetched:
            rq = raw.pop(_RQ_KEY, None)
            row = {k: _py(v) for k, v in raw.items()}
            _resolve_quantiles(row, rq)
            if extra:
                row.update({k: _py(v) for k, v in extra.items()})
            row.setdefault("step", None)
            batch.append(row)
        self.rows.extend(batch)
        if self.session is not None:
            self.session.add_rows(batch)


def lazy_local_row(state, tolerance: float,
                   quantiles: Optional[Sequence[float]] = None
                   ) -> Dict[str, Any]:
    """Canonical row for a shared-memory ``EngineState`` — all device
    scalars left unevaluated; traffic fields are structurally zero."""
    row = {
        "step": state.step_index,
        "updates": state.total_updates,
        "edges_touched": state.edges_touched,
        "residual_max": jnp.max(state.prio),
        "backlog": jnp.sum(state.prio > tolerance),
        "wire_backlog": 0,
        "traffic_rows_v": 0, "traffic_bytes_v": 0,
        "traffic_rows_e": 0, "traffic_bytes_e": 0,
        "traffic_rows_r": 0, "traffic_bytes_r": 0,
    }
    _add_quantiles(row, state.prio, quantiles)
    return row


def lazy_dist_row(state, tolerance: float,
                  quantiles: Optional[Sequence[float]] = None,
                  beats: bool = False) -> Dict[str, Any]:
    """Canonical row for a sharded ``DistState``.  NaN-safe on a mesh
    with a dead machine: poisoned priorities make ``residual_max`` NaN
    (honest) while ``backlog`` uses ``prio > tol`` (NaN compares
    False)."""
    row = {
        "step": state.step_index,
        "updates": jnp.sum(state.update_count),
        "edges_touched": 0,
        "residual_max": jnp.max(state.prio),
        "backlog": jnp.sum(state.prio > tolerance),
        "wire_backlog": (jnp.sum(state.wire["backlog"])
                         if state.wire is not None else 0),
        "traffic_rows_v": jnp.sum(state.traffic_v),
        "traffic_bytes_v": jnp.sum(state.traffic_bytes_v),
        "traffic_rows_e": jnp.sum(state.traffic_e),
        "traffic_bytes_e": jnp.sum(state.traffic_bytes_e),
        "traffic_rows_r": jnp.sum(state.traffic_r),
        "traffic_bytes_r": jnp.sum(state.traffic_bytes_r),
    }
    if beats:
        row["beats"] = state.beats
    _add_quantiles(row, state.prio, quantiles)
    return row


#: reserved row key: (prio_array, quantile tuple), resolved at drain time
_RQ_KEY = "__residual_quantiles__"


def _add_quantiles(row, prio, quantiles) -> None:
    # deferred to the host at drain time: XLA's CPU sort prices a
    # device-side quantile at several ms per step while np.quantile on
    # the drained batch is ~0.1 ms (benchmarks/obs_bench.py holds the
    # total ≤5%).  The row carries the prio *reference*; the batched
    # device_get fetches it with the same single transfer.  Steps never
    # donate state buffers, so the reference stays valid across steps.
    if quantiles:
        row[_RQ_KEY] = (prio, tuple(float(q) for q in quantiles))


def _resolve_quantiles(row: Dict[str, Any], rq) -> None:
    if rq is None:
        return
    prio, qs = rq
    vals = np.quantile(np.asarray(prio), qs)
    for i, q in enumerate(qs):
        row[f"residual_q{int(round(q * 100))}"] = float(vals[i])


# -- snapshot-aligned aggregation ---------------------------------------------

def _select_field(tree, field: Optional[str]):
    if field is None:
        leaves = jax.tree.leaves(tree)
        if len(leaves) != 1:
            raise ValueError(
                f"vertex data has {len(leaves)} leaves; pass field=<name>")
        return leaves[0]
    return tree[field]


def live_aggregate(engine, state, field: Optional[str] = None,
                   reduce: Callable = np.sum) -> float:
    """The *naive* global aggregate: reduce over the live owned rows.
    On a multi-machine mesh mid-run this mixes rows from different
    logical times — use only as the strawman / for converged states."""
    vd = _select_field(engine.vertex_data(state), field)
    return float(reduce(np.asarray(vd, np.float64)))


def aligned_aggregate(engine, state, field: Optional[str] = None,
                      reduce: Callable = np.sum) -> Dict[str, Any]:
    """Globally-consistent aggregate over a **completed** Chandy-Lamport
    cut: the reduction runs over the rows the marker wave saved, i.e.
    one consistent global state, regardless of how far individual
    machines have since advanced.  Returns the value plus the cut's
    anchor: the save-step range and — when the engine is streaming with
    an attached journal — the journal offset the cut reflects (the same
    anchor ``save_snapshot`` records, so metrics and checkpoints name
    cuts identically)."""
    if state.snap is None:
        raise ValueError("no snapshot attached; start one and step until "
                         "snapshot_complete before aligned aggregation")
    if not engine.snapshot_complete(state):
        raise ValueError(
            "marker wave still in flight (done_frac="
            f"{engine.snapshot_done_frac(state):.3f}); an aligned "
            "aggregate needs the completed cut")
    snap = engine.assemble_snapshot(state)  # global vertex order
    vd = _select_field(snap.saved_v, field)
    value = float(reduce(np.asarray(vd, np.float64)))
    steps = np.asarray(snap.save_step)[np.asarray(snap.done)]
    anchor: Dict[str, Any] = {
        "save_step_min": int(steps.min()) if steps.size else 0,
        "save_step_max": int(steps.max()) if steps.size else 0,
    }
    if getattr(engine, "_stream_journal", None) is not None:
        anchor["journal_offset"] = int(engine._stream_offset)
    return {"value": value, "anchor": anchor}


def mixing_report(engine, state, field: Optional[str] = None
                  ) -> Dict[str, int]:
    """How inconsistent the naive aggregate is: per-vertex comparison of
    the live rows against the completed cut.  ``rows_post_cut`` > 0
    means the live reduction already mixes post-snapshot values into a
    sum that other machines contribute pre-snapshot values to."""
    snap = engine.assemble_snapshot(state)
    live = np.asarray(_select_field(engine.vertex_data(state), field))
    saved = np.asarray(_select_field(snap.saved_v, field))
    done = np.asarray(snap.done)
    same = np.isclose(live, saved, rtol=0.0, atol=0.0)
    while same.ndim > 1:
        same = same.all(axis=-1)
    return {
        "rows_pre_cut": int(np.sum(done & same)),
        "rows_post_cut": int(np.sum(done & ~same)),
    }
