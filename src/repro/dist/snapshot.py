"""Asynchronous Chandy-Lamport snapshots over the ghost channels
(paper Sec. 4.3, Alg. 5; DESIGN.md §3.10).

The distributed half of the fault-tolerance pillar: the snapshot update
runs *inside* the shard_map step as a prioritized phase that executes
before any regular update of that step (the engines' bodies are wrapped by
``ShardEngineBase._wrap_step``).  The mapping onto the bulk engine:

  processes   machines (mesh slices along the ``data`` axis)
  channels    the versioned ghost-exchange lanes between machine pairs
  markers     *pure version bits* riding the existing ghost tables — the
              marker "row" has an empty payload, so a marker is exactly
              one ``ship`` flag of the changed-only machinery PR 3 used
              for lock ranks (``exchange({}, frontier, ...)``); it ships
              once per (vertex, caching machine) pair, when the vertex
              enters the frontier (``traffic_m`` counts them)
  wave        the per-machine marker wave is the scheduler subsystem's
              prioritized phase: the frontier is ``pending ∧ ¬done`` and
              ``scheduler.marker_wave_local`` floods receivers of newly
              marked sources (own frontier + markers that just arrived)
  channel     captured on the *receiver* side: owned edge rows whose
  state       source's marker just became visible are captured with their
              pre-marker value, before the same step's regular exchange
              can merge the source's post-snapshot rows

Consistency of the cut: a machine captures vertex scopes (frontier rows)
and channel state (edge rows at marker arrival) at the top of the step,
and only afterwards run the regular phases that merge ghost rows.  Because
the marker for vertex u ships in the same synchronized marker exchange of
the step in which u saves, it can neither overtake u's earlier data rows
nor lag behind u's post-snapshot rows — the single exchange lane is FIFO
by construction.  The ``own_stale``/``ghost_stale`` bits record every row
known to carry post-snapshot data; a capture that reads one increments
``violations``, so "no post-snapshot ghost row is ever merged into a saved
scope" is machine-checked at run time (tests/test_dist_snapshot.py asserts
the counter stays zero over random graphs × mesh shapes × initiators).

Completed snapshots leave the device as per-machine journals
(``shard_journals``) written through ``CheckpointManager.save_shards`` —
one ``shard_<m>.npz`` per machine under an atomically committed
``ckpt_<step>`` directory.  Each journal embeds its own ``own_gid`` /
``erow_gid`` index maps, so ``snapshot_from_journals`` can stitch the
global cut back together from *any* shard count: restoring a 4-machine
snapshot onto a 2-machine mesh (elastic re-shard, the two-phase-atom
property) is the same code path as same-size restore.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import (CheckpointManager, flatten_with_paths,
                                      young_interval)
from repro.core.graph import DataGraph
from repro.core.scheduler import marker_wave_local
from repro.core.snapshot import SnapshotState, capture_rows, stitch_rows

Pytree = Any


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DistSnapshotState:
    """Sharded snapshot state: row blocks follow ``DistState`` (machine m
    owns block m along every leading dim)."""

    pending: jnp.ndarray       # [S*n_loc] bool — marker received, save due
    done: jnp.ndarray          # [S*n_loc] bool — own scope saved
    save_step: jnp.ndarray     # [S*n_loc] i32 — step the scope was saved
    saved_v: Pytree            # like vown — captured vertex data
    saved_e: Pytree            # like edata — captured owned edges
    saved_e_mask: jnp.ndarray  # [S*e_loc] bool
    ghost_marked: jnp.ndarray  # [S*(S*B)] bool — remote vertex known saved
    ghost_stale: jnp.ndarray   # [S*(S*B)] bool — post-cut row merged
    own_stale: jnp.ndarray     # [S*n_loc] bool — own vertex updated post-save
    traffic_m: jnp.ndarray     # [S] i32 — marker rows shipped
    violations: jnp.ndarray    # [S] i32 — post-cut data read by a capture

    def replace(self, **kw) -> "DistSnapshotState":
        return dataclasses.replace(self, **kw)


def init_dist_snapshot(pending: jnp.ndarray, vown: Pytree, edata: Pytree,
                       e_rows: int, g_rows: int,
                       n_machines: int) -> DistSnapshotState:
    """Fresh snapshot state over the given initiator ``pending`` mask.

    ``e_rows``/``g_rows`` are the padded owned-edge and ghost-slab row
    counts (``S*e_loc`` and ``S*(S*B)`` globally); the per-machine
    counters are ``[n_machines]`` like the engine's traffic counters."""
    n_rows = pending.shape[0]
    return DistSnapshotState(
        pending=pending,
        done=jnp.zeros(n_rows, bool),
        save_step=jnp.full(n_rows, -1, jnp.int32),
        saved_v=jax.tree.map(jnp.zeros_like, vown),
        saved_e=jax.tree.map(jnp.zeros_like, edata),
        saved_e_mask=jnp.zeros(e_rows, bool),
        ghost_marked=jnp.zeros(g_rows, bool),
        ghost_stale=jnp.zeros(g_rows, bool),
        own_stale=jnp.zeros(n_rows, bool),
        traffic_m=jnp.zeros(n_machines, jnp.int32),
        violations=jnp.zeros(n_machines, jnp.int32),
    )


def make_marker_phase(exchange, n_loc: int, budget: int):
    """Builds the prioritized snapshot phase for a shard_map body.

    ``exchange`` is the engine's versioned ghost exchange closure
    (``ShardEngineBase._make_phase_helpers``); the marker rides it with an
    empty payload — the ship bit *is* the marker.  Runs before every
    regular phase of the step, so captures read pre-step values.
    """

    def marker_phase(tb, snap: DistSnapshotState, vown: Pytree,
                     edata: Pytree, step: jnp.ndarray) -> DistSnapshotState:
        own = tb["own_mask"]
        frontier = jnp.logical_and(
            jnp.logical_and(snap.pending, jnp.logical_not(snap.done)), own)

        # 1. scope capture: the frontier's vertex data, before this step's
        # regular updates touch it (Alg. 5's prioritization condition)
        saved_v = capture_rows(snap.saved_v, vown, frontier)

        # 2. marker exchange: an empty-payload versioned row per newly
        # frontier (vertex, caching machine) pair — the received changed
        # bits ARE the markers
        _, recv_ch, shipped = exchange(
            {}, frontier, tb["send_idx"], tb["send_mask"], budget)
        ghost_new = jnp.logical_and(recv_ch,
                                    jnp.logical_not(snap.ghost_marked))
        ghost_marked = jnp.logical_or(snap.ghost_marked, recv_ch)

        # 3. channel-state capture: an owned edge row is captured the
        # moment its source's marker becomes visible here (local frontier
        # or a marker that just crossed the channel) — still pre-merge, so
        # the value is the last pre-snapshot write of the source
        sl, emask = tb["senders_local"], tb["edge_mask"]
        marked_new = jnp.concatenate([frontier, ghost_new])
        e_new = jnp.logical_and(
            jnp.logical_and(marked_new[sl], emask),
            jnp.logical_not(snap.saved_e_mask))
        post = jnp.concatenate([snap.own_stale, snap.ghost_stale])
        violations = snap.violations + jnp.sum(
            jnp.logical_and(e_new, post[sl]), dtype=jnp.int32)
        saved_e = capture_rows(snap.saved_e, edata, e_new)

        # 4. wave: receivers of newly marked sources become pending
        recv_idx = jnp.where(emask, tb["receivers_local"], n_loc)
        pending = jnp.logical_and(
            marker_wave_local(marked_new, snap.pending, sl, recv_idx,
                              n_loc), own)

        return snap.replace(
            pending=pending,
            done=jnp.logical_or(snap.done, frontier),
            save_step=jnp.where(frontier, step, snap.save_step),
            saved_v=saved_v, saved_e=saved_e,
            saved_e_mask=jnp.logical_or(snap.saved_e_mask, e_new),
            ghost_marked=ghost_marked,
            traffic_m=snap.traffic_m + shipped,
            violations=violations)

    return marker_phase


def mark_stale(snap: DistSnapshotState, active: jnp.ndarray,
               recv_ch: jnp.ndarray) -> DistSnapshotState:
    """Versioned-stale accounting, called from the regular phase update:
    an own row updating after its save, and a ghost row arriving from an
    already-saved remote vertex, both carry post-snapshot data.  Captures
    never read them when the phase ordering is right; ``violations``
    machine-checks that."""
    return snap.replace(
        own_stale=jnp.logical_or(snap.own_stale,
                                 jnp.logical_and(active, snap.done)),
        ghost_stale=jnp.logical_or(snap.ghost_stale,
                                   jnp.logical_and(recv_ch,
                                                   snap.ghost_marked)))


# ---------------------------------------------------------------------------
# Host-side assembly + sharded journals
# ---------------------------------------------------------------------------

def assemble_snapshot(layout, snap: DistSnapshotState,
                      n_vertices: int, n_edges: int) -> SnapshotState:
    """Stitches the sharded cut back to the global ``SnapshotState`` —
    ``restore_engine_state`` then restarts *any* engine (local or
    distributed, any mesh) from it."""
    v = stitch_rows(
        {"pending": np.asarray(snap.pending), "done": np.asarray(snap.done),
         "save_step": np.asarray(snap.save_step)},
        layout.own_gid, n_vertices)
    e = stitch_rows(
        {"mask": np.asarray(snap.saved_e_mask)}, layout.erow_gid, n_edges)
    return SnapshotState(
        pending=jnp.asarray(v["pending"]), done=jnp.asarray(v["done"]),
        save_step=jnp.asarray(v["save_step"]),
        saved_v=jax.tree.map(
            jnp.asarray, stitch_rows(snap.saved_v, layout.own_gid,
                                     n_vertices)),
        saved_e=jax.tree.map(
            jnp.asarray, stitch_rows(snap.saved_e, layout.erow_gid,
                                     n_edges)),
        saved_e_mask=jnp.asarray(e["mask"]))


def _flat(tree: Pytree, prefix: str) -> Dict[str, np.ndarray]:
    """Journal keys: the checkpoint layer's one path→key rule, prefixed."""
    return {f"{prefix}/{k}": v
            for k, v in flatten_with_paths(tree).items()}


def _unflat(flat: Dict[str, np.ndarray], prefix: str, like: Pytree) -> Pytree:
    leaves_like, treedef = jax.tree_util.tree_flatten(like)
    # flatten_with_paths iterates in tree_flatten leaf order
    leaves = [flat[f"{prefix}/{k}"].astype(np.asarray(l).dtype)
              for k, l in zip(flatten_with_paths(like), leaves_like)]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def shard_journals(layout, snap: DistSnapshotState) -> List[Dict[str, np.ndarray]]:
    """One journal per machine: that machine's owned rows of the cut plus
    its own index maps, so restore needs no partition metadata beyond the
    journals themselves (elastic by construction)."""
    S, n_loc, e_loc = layout.n_machines, layout.n_loc, layout.e_loc

    def rows(x, per):
        return np.asarray(x).reshape((S, per) + np.asarray(x).shape[1:])

    # one device→host flatten per leaf, sliced per machine below
    by_v = {
        "own_gid": layout.own_gid.reshape(S, n_loc),
        "save_step": rows(snap.save_step, n_loc),
        "done": rows(snap.done, n_loc),
        "pending": rows(snap.pending, n_loc),
        **{k: rows(v, n_loc) for k, v in _flat(snap.saved_v,
                                               "saved_v").items()},
    }
    by_e = {
        "erow_gid": layout.erow_gid.reshape(S, e_loc),
        "saved_e_mask": rows(snap.saved_e_mask, e_loc),
        **{k: rows(v, e_loc) for k, v in _flat(snap.saved_e,
                                               "saved_e").items()},
    }
    return [{k: v[m] for kv in (by_v, by_e) for k, v in kv.items()}
            for m in range(S)]


def snapshot_from_journals(journals: Sequence[Dict[str, np.ndarray]],
                           graph: DataGraph) -> SnapshotState:
    """Reassembles the global cut from per-machine journals of *any* shard
    count (the elastic 4→2 restore path): every journal carries its own
    gid maps, so we just scatter each machine's rows into global order."""
    n, e = graph.structure.n_vertices, graph.structure.n_edges
    agg_v: Dict[str, np.ndarray] = {}
    agg_e: Dict[str, np.ndarray] = {}

    def scatter(agg, key, vals, gid, size):
        x = np.asarray(vals)
        if key not in agg:
            agg[key] = np.zeros((size,) + x.shape[1:], x.dtype)
        ok = gid >= 0
        agg[key][gid[ok]] = x[ok]

    for j in journals:
        vgid = np.asarray(j["own_gid"]).astype(np.int64)
        egid = np.asarray(j["erow_gid"]).astype(np.int64)
        for key in ("save_step", "done", "pending"):
            scatter(agg_v, key, j[key], vgid, n)
        scatter(agg_e, "saved_e_mask", j["saved_e_mask"], egid, e)
        for key in j:
            if key.startswith("saved_v/"):
                scatter(agg_v, key, j[key], vgid, n)
            elif key.startswith("saved_e/"):
                scatter(agg_e, key, j[key], egid, e)
    saved_v = _unflat(agg_v, "saved_v", graph.vertex_data)
    saved_e = _unflat(agg_e, "saved_e", graph.edge_data)
    return SnapshotState(
        pending=jnp.asarray(agg_v["pending"]),
        done=jnp.asarray(agg_v["done"]),
        save_step=jnp.asarray(agg_v["save_step"]),
        saved_v=jax.tree.map(jnp.asarray, saved_v),
        saved_e=jax.tree.map(jnp.asarray, saved_e),
        saved_e_mask=jnp.asarray(agg_e["saved_e_mask"]))


def save_snapshot(manager: CheckpointManager, step: int, engine,
                  state, extra_meta: Optional[Dict] = None) -> None:
    """Journals a *completed* snapshot: per-machine shards, atomic commit
    (``CheckpointManager.save_shards``).

    When the engine carries a delta journal (``stream.ingest.attach_
    journal``), the cut's anchor offset — the journal prefix the cut
    reflects — is recorded as ``journal_offset`` in the checkpoint's
    meta.json: recovery restores the cut and replays the journal suffix
    from there (``stream/recovery.py``).  The fence in ``apply_delta``
    guarantees no batch landed while the wave was in flight, so the
    anchor is exact, not approximate."""
    if state.snap is None:
        raise ValueError("no snapshot attached to this state")
    if not engine.snapshot_complete(state):
        raise ValueError("snapshot incomplete: refusing to journal a "
                         "non-consistent cut")
    violations = engine.snapshot_violations(state)
    if violations:
        raise ValueError(
            f"snapshot captured {violations} post-cut row(s): the cut is "
            f"inconsistent (phase-ordering bug) and must not be journaled")
    meta = dict(extra_meta or {})
    if getattr(engine, "_stream_journal", None) is not None:
        meta.setdefault("journal_offset", int(engine._stream_offset))
    manager.save_shards(step, shard_journals(engine.layout, state.snap),
                        meta=meta or None)


def load_snapshot(manager: CheckpointManager, graph: DataGraph,
                  step: Optional[int] = None) -> Tuple[int, SnapshotState]:
    """Latest-committed (or given-step) journal set → global cut."""
    step, journals = manager.restore_shards(step)
    return step, snapshot_from_journals(journals, graph)


# ---------------------------------------------------------------------------
# The Young-interval snapshot driver
# ---------------------------------------------------------------------------

class DistSnapshotDriver:
    """Runs a sharded engine with periodic asynchronous snapshots journaled
    through a ``CheckpointManager``.

    The period follows Young's first-order optimal interval (paper Eq. 3)
    translated to steps: ``interval = sqrt(2 * T_ckpt * T_mtbf/S) /
    t_step``; pass ``interval_steps`` to pin it directly (tests do).
    Regular computation proceeds every step — only the marker frontier does
    snapshot work (Fig. 4's "computation proceeds" property; see
    benchmarks/snapshot_bench.py for the sync-flatline contrast).
    """

    def __init__(
        self,
        engine,
        manager: Optional[CheckpointManager] = None,
        *,
        interval_steps: Optional[int] = None,
        t_step_s: float = 1.0,
        t_checkpoint_s: float = 60.0,
        t_mtbf_node_s: float = 365 * 24 * 3600.0,
        initiators: Sequence[int] = (0,),
    ):
        self.engine = engine
        self.manager = manager
        if interval_steps is None:
            interval_steps = max(1, int(round(
                young_interval(t_checkpoint_s, t_mtbf_node_s,
                               engine.layout.n_machines) / t_step_s)))
        self.interval_steps = int(interval_steps)
        self.initiators = tuple(initiators)

    def run(self, state, max_steps: int = 1000,
            first_snapshot_at: Optional[int] = None):
        """Steps until convergence (and until any in-flight snapshot
        completes), initiating a snapshot every ``interval_steps``.
        Returns ``(state, trace)``; the trace records per-step updates and
        snapshot progress."""
        eng = self.engine
        next_at = (self.interval_steps if first_snapshot_at is None
                   else int(first_snapshot_at))
        trace = []
        prev_done = -1
        for _ in range(max_steps):
            snapping = state.snap is not None
            converged = float(jnp.max(state.prio)) <= eng.tolerance
            if converged and not snapping:
                break
            if not snapping and int(state.step_index) >= next_at:
                state = eng.start_snapshot(state, self.initiators)
                snapping = True
                prev_done = -1
            state = eng.step(state)
            if snapping and not eng.snapshot_complete(state):
                # the wave grows `done` every step or it never will again
                # (an empty frontier ships no markers): a stall means the
                # initiators cannot reach some vertex — fail loudly rather
                # than burn max_steps journaling nothing
                now_done = int(np.asarray(state.snap.done).sum())
                if now_done == prev_done:
                    raise RuntimeError(
                        "snapshot marker wave stalled before completion "
                        f"({eng.snapshot_done_frac(state):.0%} saved): the "
                        "initiators cannot reach every vertex — is the "
                        "graph connected?")
                prev_done = now_done
            # canonical telemetry keys (obs.metrics.METRICS_SCHEMA) plus
            # the driver's snapshot-progress extras
            rec = {
                "step": int(state.step_index),
                "updates": int(np.asarray(state.update_count).sum()),
                "residual_max": float(jnp.max(state.prio)),
                "marker_rows": eng.marker_rows_sent(state),
                "snapshot_done_frac": eng.snapshot_done_frac(state),
            }
            trace.append(rec)
            if snapping and eng.snapshot_complete(state):
                if self.manager is not None:
                    save_snapshot(self.manager, int(state.step_index),
                                  eng, state)
                state = eng.clear_snapshot(state)
                next_at = int(state.step_index) + self.interval_steps
        return state, trace
