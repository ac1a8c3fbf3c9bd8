"""The autonomous control loop (DESIGN.md §3.15, layer 3).

ROADMAP item 1 left the self-healing mesh half-closed: the `Watchdog`
and `StragglerMonitor` *detect* from the heartbeat counters, but the
remedies — ``migrate_leave``/``migrate_join``/``shed_atoms``/
``steal_backlog`` — were invoked by the host harness (benchmarks), not
by anything inside ``run()``.  The ``Supervisor`` closes that loop: the
engine run loops call ``supervisor.observe(engine, state)`` once per
step, and the supervisor consumes the live metrics stream (beats,
per-machine/per-queue update counters, backlog) to fire the remedies
itself, returning the possibly-rebuilt ``(engine, state)`` pair.

State machine per machine (dist path)::

    LIVE --skew>=straggler_skew--> STRAGGLER --patience--> SHED (once)
      |                                 |__ beats resume __ REINSTATED
      |--missed>=suspect_after--> SUSPECT --beats resume--> REINSTATED
      |--missed>=dead_after--> DEAD --> MIGRATE_LEAVE (mesh S-1, from
                                        the latest committed cut)
    offered mesh (offer_machine) --wd healthy, no wave--> MIGRATE_JOIN

Every transition is recorded in ``self.actions`` and mirrored into the
``ObsSession`` event log / timeline, so remediation is auditable from
the exported Perfetto trace.  Chaos *injection* (``kill_machine``,
``stall_machine``) stays with the harness — only remediation moved.

The local path (shared-memory ``Engine`` + ``WorkStealingScheduler``)
watches per-queue cumulative update counters: when some queues sit idle
(no progress, empty queue) for ``steal_skew`` consecutive observations
while a victim's backlog exceeds its pipeline length, the supervisor
calls ``steal_backlog`` — a pure scheduler-state value update, zero
retrace — closing the "straggler detection feeding ``steal_backlog``
mid-``run()``" leftover.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

import jax
import numpy as np

from repro.obs.timeline import span


class Supervisor:
    """Consumes the metrics stream inside ``run()`` and fires
    remediation.  Pass one to ``Engine.run`` / ``ShardEngineBase.run``
    via ``supervisor=``; after the run, ``supervisor.engine`` is the
    (possibly rebuilt) engine to keep using.

    manager / mesh_factory
        A ``CheckpointManager`` holding committed cuts and a callable
        ``n_machines -> mesh``; both are required for death healing
        (``migrate_leave``) — without them a dead machine is reported
        but left to the host.
    snapshot_every
        When set (and ``manager`` given), the supervisor also owns the
        checkpoint cadence: it starts a Chandy-Lamport wave every N
        observed steps (only on a healthy mesh), saves the completed
        cut, and abandons waves that freeze (a stalled machine cannot
        forward markers).
    """

    def __init__(self, *, manager=None, mesh_factory=None, session=None,
                 suspect_after: int = 2, dead_after: int = 5,
                 straggler_skew: int = 4, straggler_patience: int = 2,
                 shed_frac: float = 1.0,
                 snapshot_every: Optional[int] = None,
                 initiators=(0,),
                 steal_skew: int = 3, steal_frac: float = 0.5,
                 wave_stall_patience: int = 10):
        self.manager = manager
        self.mesh_factory = mesh_factory
        self.session = session
        self.suspect_after = int(suspect_after)
        self.dead_after = int(dead_after)
        self.straggler_skew = int(straggler_skew)
        self.straggler_patience = int(straggler_patience)
        self.shed_frac = float(shed_frac)
        self.snapshot_every = snapshot_every
        self.initiators = tuple(initiators)
        self.steal_skew = int(steal_skew)
        self.steal_frac = float(steal_frac)
        self.wave_stall_patience = int(wave_stall_patience)

        self.engine = None
        self.actions: List[Dict[str, Any]] = []
        self.cuts_committed = 0
        #: updates executed on pre-rebuild engines (rebuilds reset the
        #: device counters; ``info["updates_before"]`` carries them here)
        self.updates_carried = 0
        self.ticks = 0

        self._wd = None
        self._mon = None
        self._shedded: set = set()
        self._pending_joins: List[Any] = []
        self._unremediated_dead: set = set()
        self._steps_since_cut = 0
        self._snap_owned = False
        self._wave_frac = -1.0
        self._wave_frozen = 0
        # local (work-stealing) path
        self._qu_last = None
        self._idle_streak = 0

    # -- public knobs ------------------------------------------------------
    def offer_machine(self, mesh) -> None:
        """Queues spare hardware; the join executes at the next healthy
        observation (all machines live, no marker wave in flight)."""
        self._pending_joins.append(mesh)
        self._record("offer_machine", mesh_axes=dict(mesh.shape))

    def pending_work(self) -> bool:
        """True while the supervisor still owes remediation — the run
        loop keeps stepping (even a converged state) until this clears,
        so joins/heals land inside ``run()`` rather than leaking back to
        the host."""
        if self._pending_joins:
            return True
        if self._wd is not None and self._wd.dead():
            return True
        if self._snap_owned:
            return True
        # a cadence-owed checkpoint: keep stepping (a converged state
        # included) until the wave commits, so a run always leaves
        # behind a cut no older than ``snapshot_every``; bounded because
        # waves complete even through stalled machines (see
        # _tick_snapshot), and a DEAD machine drops the clause entirely
        return (self.snapshot_every is not None
                and self.manager is not None
                and self._wd is not None and not self._wd.dead()
                and self._steps_since_cut >= int(self.snapshot_every))

    # -- bookkeeping -------------------------------------------------------
    def _record(self, kind: str, **data) -> Dict[str, Any]:
        act = {"kind": kind, "tick": self.ticks, **data}
        self.actions.append(act)
        if self.session is not None:
            self.session.event(kind, **{k: v for k, v in act.items()
                                        if k != "kind"})
        return act

    def _reset_monitors(self) -> None:
        self._wd = None
        self._mon = None
        self._shedded.clear()
        self._unremediated_dead.clear()

    def _span(self, kind: str, **kw):
        """``obs.span`` ``graphlab.<kind>`` of a remedy, on the
        supervisor's timeline track."""
        return span(f"graphlab.{kind}", session=self.session,
                    track="supervisor", cat="control", **kw)

    # -- dispatch ----------------------------------------------------------
    def observe(self, engine, state):
        """One control-loop tick; returns the (possibly rebuilt)
        ``(engine, state)``."""
        self.ticks += 1
        if hasattr(state, "beats") and hasattr(engine, "layout"):
            engine, state = self._observe_dist(engine, state)
        elif isinstance(getattr(state, "sched", None), dict) \
                and "queue_of" in state.sched:
            engine, state = self._observe_local(engine, state)
        self.engine = engine
        return engine, state

    # -- distributed path --------------------------------------------------
    def _observe_dist(self, engine, state):
        from repro.dist.balance import StragglerMonitor
        from repro.dist.membership import Watchdog

        S = engine.layout.n_machines
        if self._wd is None or self._wd.n_machines != S:
            self._wd = Watchdog(S, suspect_after=self.suspect_after,
                                dead_after=self.dead_after)
            self._mon = StragglerMonitor(S, skew=self.straggler_skew,
                                         patience=self.straggler_patience)

        beats = np.asarray(jax.device_get(state.beats)).reshape(-1)
        for kind, m in self._wd.observe(beats):
            self._record(f"watchdog_{kind}", machine=int(m))
            if kind == "reinstated":
                self._shedded.discard(int(m))

        engine, state = self._tick_snapshot(engine, state)

        dead = self._wd.dead()
        if dead:
            engine, state, healed = self._heal_dead(engine, state, dead[0])
            if healed:
                return engine, state  # monitors reset; next tick re-baselines

        engine, state, joined = self._tick_join(engine, state)
        if joined:
            return engine, state  # monitors reset; next tick re-baselines
        engine, state = self._tick_straggler(engine, state, beats)
        return engine, state

    def _heal_dead(self, engine, state, m: int):
        if self.manager is None or self.mesh_factory is None:
            if m not in self._unremediated_dead:
                self._unremediated_dead.add(m)
                self._record("dead_unremediated", machine=int(m),
                             reason="no manager/mesh_factory configured")
            return engine, state, False
        from repro.dist.migrate import migrate_leave
        if state.snap is not None:
            state = engine.clear_snapshot(state)
            self._snap_owned = False
            self._record("snapshot_abandoned", reason="dead machine")
        S = engine.layout.n_machines
        with self._span("migrate_leave", args={"machine": int(m)}):
            engine, state, info = migrate_leave(
                engine, state, m, mesh=self.mesh_factory(S - 1),
                manager=self.manager)
        self.updates_carried += int(info.get("updates_before", 0))
        self._record("migrate_leave", machine=int(m),
                     restored_step=int(info.get("restored_step", -1)),
                     lost_vertices=int(info.get("lost_vertices", 0)),
                     survivor_rescheduled=int(
                         info.get("survivor_rescheduled", 0)))
        self._reset_monitors()
        self._steps_since_cut = 0  # the restored cut is the new baseline
        return engine, state, True

    def _tick_join(self, engine, state):
        if not self._pending_joins:
            return engine, state, False
        if not self._wd.healthy() or state.snap is not None:
            return engine, state, False
        from repro.dist.migrate import migrate_join
        mesh = self._pending_joins.pop(0)
        with self._span("migrate_join"):
            engine, state, info = migrate_join(engine, state, mesh=mesh)
        self.updates_carried += int(info.get("updates_before", 0))
        self._record("migrate_join",
                     joined_machine=int(info.get("joined_machine", -1)),
                     moved_atoms=int(info.get("moved_atoms", 0)),
                     survivor_rescheduled=int(
                         info.get("survivor_rescheduled", 0)))
        self._reset_monitors()
        return engine, state, True

    def _tick_straggler(self, engine, state, beats):
        to_shed = []
        for kind, m in self._mon.observe(beats, exclude=self._wd.dead()):
            self._record(kind, machine=int(m), lead=int(beats.max()),
                         beats=int(beats[m]))
            if kind == "straggler":
                to_shed.append(int(m))
            elif kind == "recovered":
                self._shedded.discard(int(m))
        for m in to_shed:
            if m in self._shedded:
                continue
            from repro.dist.faults import machine_data_lost
            from repro.dist.migrate import shed_atoms
            if machine_data_lost(engine, state, m):
                # silent-from-beats but NaN-poisoned: this is a death in
                # progress, not a straggler — shedding would move poisoned
                # rows onto survivors; let the watchdog escalate to
                # migrate_leave instead
                self._record("shed_skipped_data_lost", machine=int(m))
                continue
            if state.snap is not None:
                state = engine.clear_snapshot(state)
                self._snap_owned = False
                self._record("snapshot_abandoned", reason="straggler shed")
            try:
                with self._span("shed_atoms", args={"machine": int(m)}):
                    engine, state, info = shed_atoms(
                        engine, state, m, frac=self.shed_frac)
            except ValueError as e:  # e.g. streaming engines can't migrate
                self._shedded.add(m)
                self._record("shed_unavailable", machine=int(m),
                             reason=str(e))
                continue
            self.updates_carried += int(info.get("updates_before", 0))
            self._shedded.add(m)
            self._record("shed_atoms", machine=int(m),
                         shed_atoms=int(info.get("shed_atoms", 0)),
                         shed_vertices=int(info.get("shed_vertices", 0)))
            # the rebuild reset the beat counters to zero; keep the
            # shed ledger but re-baseline both monitors, else every
            # machine reads as regressed (a miss) until its fresh
            # counter overtakes the pre-rebuild one
            self._wd = None
            self._mon = None
            break  # one remedy per tick
        return engine, state

    def _tick_snapshot(self, engine, state):
        if self.snapshot_every is None or self.manager is None:
            return engine, state
        self._steps_since_cut += 1
        if state.snap is not None:
            if engine.snapshot_complete(state):
                from repro.dist.snapshot import save_snapshot
                if not self._cut_finite(engine, state):
                    # the wave closed over a machine whose data was
                    # already destroyed (a silent death the watchdog has
                    # not escalated yet): committing it would hand the
                    # poison to the next migrate_leave — discard, and let
                    # the heal restore the previous good cut
                    state = engine.clear_snapshot(state)
                    self._snap_owned = False
                    self._record("snapshot_discarded",
                                 reason="non-finite rows in the cut")
                    return engine, state
                save_snapshot(self.manager, int(state.step_index),
                              engine, state)
                state = engine.clear_snapshot(state)
                self.cuts_committed += 1
                self._snap_owned = False
                self._record("snapshot_saved", step=int(state.step_index),
                             cut=self.cuts_committed)
                self._steps_since_cut = 0
                self._wave_frac, self._wave_frozen = -1.0, 0
            else:
                frac = engine.snapshot_done_frac(state)
                self._wave_frozen = (self._wave_frozen + 1
                                     if frac == self._wave_frac else 0)
                self._wave_frac = frac
                if self._snap_owned and \
                        self._wave_frozen >= self.wave_stall_patience:
                    state = engine.clear_snapshot(state)
                    self._snap_owned = False
                    self._record("snapshot_abandoned",
                                 reason="marker wave stalled",
                                 done_frac=float(frac))
        elif (self._steps_since_cut >= int(self.snapshot_every)
                and not self._wd.dead()):
            # merely-SUSPECT machines don't block the cadence: marker
            # capture is not stall-gated, so a wave closes through a
            # stalled machine and captures its intact (if frozen) rows —
            # still a consistent cut.  Only a DEAD machine blocks, and
            # the finiteness guard above catches the silent poison of a
            # death the watchdog has not escalated yet.
            try:
                state = engine.start_snapshot(state,
                                              initiators=self.initiators)
            except ValueError as e:
                self._record("snapshot_unavailable", reason=str(e))
                self.snapshot_every = None  # don't retry every tick
                return engine, state
            self._snap_owned = True
            self._wave_frac, self._wave_frozen = -1.0, 0
            self._record("snapshot_started", step=int(state.step_index))
        return engine, state

    @staticmethod
    def _cut_finite(engine, state) -> bool:
        cut = engine.assemble_snapshot(state)
        for leaf in jax.tree.leaves((cut.saved_v, cut.saved_e)):
            leaf = np.asarray(leaf)
            if np.issubdtype(leaf.dtype, np.floating) \
                    and not np.isfinite(leaf).all():
                return False
        return True

    # -- local (work-stealing) path ---------------------------------------
    def _observe_local(self, engine, state):
        sched = state.sched
        scheduler = engine.scheduler
        S = int(getattr(scheduler, "n_machines", 0))
        if S <= 1:
            return engine, state
        q = np.asarray(jax.device_get(sched["queue_of"]))
        prio = np.asarray(jax.device_get(state.prio))
        uc = np.asarray(jax.device_get(state.update_count), np.float64)
        per_q_updates = np.bincount(q, weights=uc, minlength=S)
        active = np.nan_to_num(prio) > scheduler.tolerance
        backlog = np.bincount(q[active], minlength=S)

        if self._qu_last is None or self._qu_last.size != S:
            self._qu_last = per_q_updates
            self._idle_streak = 0
            return engine, state
        delta = per_q_updates - self._qu_last
        self._qu_last = per_q_updates

        idle = (delta == 0) & (backlog == 0)
        starved = backlog > scheduler.pipeline_length
        if idle.any() and starved.any():
            self._idle_streak += 1
        else:
            self._idle_streak = 0
        if self._idle_streak >= self.steal_skew:
            from repro.dist.balance import steal_backlog
            victim = int(np.argmax(backlog))
            to = [int(m) for m in np.nonzero(idle)[0]]
            with self._span("steal_backlog", args={"victim": victim}):
                new_sched, moved = steal_backlog(
                    scheduler, sched, state.prio, victim,
                    frac=self.steal_frac, to=to)
            if int(moved) > 0:
                state = state.replace(sched=new_sched)
                self._record("steal_backlog", victim=victim, to=to,
                             moved=int(moved))
            self._idle_streak = 0
        return engine, state
