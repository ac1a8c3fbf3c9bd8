"""The autonomous control loop (obs/supervisor.py, DESIGN §3.15 layer 3).

ROADMAP item 1's leftover was that the Watchdog/StragglerMonitor only
*detected* failures — remediation (``migrate_leave``/``migrate_join``/
``shed_atoms``/``steal_backlog``) was host-harness choreography.  These
tests close the loop: a ``Supervisor`` handed to ``run()`` consumes the
live beat/backlog stream and fires the remedies itself, with ZERO
migration or steal calls in the test body — every action here is read
back out of ``supervisor.actions`` and the ObsSession event log, which
is the acceptance shape the churn benchmark asserts too.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps.pagerank import PageRankProgram, make_pagerank_graph
from repro.checkpoint.manager import CheckpointManager
from repro.core import Engine
from repro.core.graph import GraphStructure
from repro.dist.balance import (StragglerMonitor, WorkStealingScheduler,
                                stolen_updates)
from repro.dist.engine import DistributedEngine
from repro.dist.faults import kill_machine, resume_machine
from repro.graphs.generators import connected_power_law_graph as \
    connected_graph
from repro.obs import ObsConfig, ObsSession, Supervisor

needs_mesh = pytest.mark.skipif(
    jax.device_count() < 4, reason="needs 4 forced host devices "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=4)")


def _pagerank_case(n=80, seed=3):
    g = make_pagerank_graph(connected_graph(n, seed=seed))
    return g, PageRankProgram(0.15, n), "rank", 1e-9


def _make(prog, g, mesh, tol):
    return DistributedEngine(prog, g, mesh, tolerance=tol, method="bfs")


def _session():
    return ObsSession(ObsConfig(enabled=True, timeline=True))


def _kinds(sup):
    return [a["kind"] for a in sup.actions]


# ---------------------------------------------------------------------------
# death: watchdog escalation -> migrate_leave, all inside run()
# ---------------------------------------------------------------------------

@needs_mesh
class TestDeathHealing:
    def test_dead_machine_healed_inside_run(self, cpu_mesh, sub_mesh,
                                            tmp_path):
        """A mode="dead" loss mid-run: the supervisor owns the snapshot
        cadence, declares the machine dead from frozen beats, rebuilds
        the mesh at S-1 from its own committed cut, and the run
        reconverges — the host never calls a migrate_* function."""
        g, prog, key, tol = _pagerank_case()
        ref_eng = _make(prog, g, cpu_mesh, tol)
        rs, _ = ref_eng.run(ref_eng.init(), max_steps=3000)
        ref = ref_eng.vertex_data(rs)[key]

        eng = _make(prog, g, cpu_mesh, tol)
        ses = _session()
        sup = Supervisor(manager=CheckpointManager(str(tmp_path)),
                         mesh_factory=sub_mesh, session=ses,
                         suspect_after=2, dead_after=4, snapshot_every=3)
        state, _ = eng.run(eng.init(), max_steps=14, supervisor=sup)
        eng = sup.engine
        assert sup.cuts_committed >= 1, \
            "supervisor must commit a cut before the fault"

        state = kill_machine(eng, state, 2, mode="dead")
        final, _ = eng.run(state, max_steps=3000, supervisor=sup)
        eng = sup.engine

        kinds = _kinds(sup)
        assert "watchdog_dead" in kinds
        assert "migrate_leave" in kinds
        leave = next(a for a in sup.actions if a["kind"] == "migrate_leave")
        assert leave["machine"] == 2
        assert eng.layout.n_machines == 3
        assert float(jnp.max(final.prio)) <= tol
        out = eng.vertex_data(final)[key]
        assert np.abs(out - ref).max() <= 1e-5

        # remediation is auditable from the session: structured event +
        # a timeline span on the supervisor track
        assert any(e["kind"] == "migrate_leave" for e in ses.events)
        spans = [e for e in ses.timeline.events
                 if e.get("ph") == "X"
                 and e["name"] == "graphlab.migrate_leave"]
        assert spans and spans[0]["args"]["machine"] == 2

    def test_dead_without_manager_is_reported_not_hidden(self, cpu_mesh):
        g, prog, _, tol = _pagerank_case(n=40)
        eng = _make(prog, g, cpu_mesh, tol)
        sup = Supervisor(suspect_after=1, dead_after=2)
        state, _ = eng.run(eng.init(), max_steps=4, supervisor=sup)
        state = kill_machine(eng, state, 1, mode="stall")
        eng.run(state, max_steps=8, supervisor=sup)
        kinds = _kinds(sup)
        assert "dead_unremediated" in kinds
        # reported exactly once, not every tick
        assert kinds.count("dead_unremediated") == 1


# ---------------------------------------------------------------------------
# straggler: flagged from beats alone, shed, reinstated on recovery
# (satellite: StragglerMonitor regression)
# ---------------------------------------------------------------------------

@needs_mesh
class TestStragglerLoop:
    def test_stall_flagged_shed_and_reinstated(self, cpu_mesh):
        """kill_machine(mode="stall") — data intact, beats frozen.  The
        supervisor must flag the straggler within K steps from beats
        alone, shed its backlog (data is intact so the data-lost guard
        passes), and on resume_machine reinstate it without a spurious
        steal; the run still reaches the uninterrupted fixed point."""
        K = 10
        g, prog, key, tol = _pagerank_case()
        ref_eng = _make(prog, g, cpu_mesh, tol)
        rs, _ = ref_eng.run(ref_eng.init(), max_steps=3000)
        ref = ref_eng.vertex_data(rs)[key]

        eng = _make(prog, g, cpu_mesh, tol)
        ses = _session()
        # dead_after high: the watchdog may suspect but must not declare
        # death — this scenario belongs to the straggler path
        sup = Supervisor(session=ses, suspect_after=2, dead_after=50,
                         straggler_skew=3, straggler_patience=2,
                         shed_frac=1.0)
        state, _ = eng.run(eng.init(), max_steps=4, supervisor=sup)
        eng = sup.engine
        tick0 = sup.ticks

        state = kill_machine(eng, state, 1, mode="stall")
        state, _ = eng.run(state, max_steps=K, supervisor=sup)
        eng = sup.engine
        flags = [a for a in sup.actions if a["kind"] == "straggler"]
        assert flags and flags[0]["machine"] == 1
        assert flags[0]["tick"] - tick0 <= K, \
            "straggler must be flagged within K steps from beats alone"
        sheds = [a for a in sup.actions if a["kind"] == "shed_atoms"]
        assert sheds and sheds[0]["machine"] == 1
        assert sheds[0]["shed_atoms"] > 0

        resume_machine(eng, 1)
        final, _ = eng.run(state, max_steps=3000, supervisor=sup)
        eng = sup.engine
        kinds = _kinds(sup)
        assert "recovered" in kinds, "beat progress must clear the flag"
        assert "watchdog_reinstated" in kinds
        assert "steal_backlog" not in kinds, "no spurious steal"
        assert "migrate_leave" not in kinds
        assert float(jnp.max(final.prio)) <= tol
        out = eng.vertex_data(final)[key]
        assert np.abs(out - ref).max() <= 1e-5

    def test_data_lost_straggler_is_not_shed(self, cpu_mesh):
        """mode="dead" looks like a straggler (silent beats) before the
        watchdog escalates — shedding would move NaN-poisoned rows onto
        survivors, so the supervisor must skip the shed and let the
        watchdog own the machine."""
        g, prog, _, tol = _pagerank_case(n=40)
        eng = _make(prog, g, cpu_mesh, tol)
        # straggler fires well before death is declared
        sup = Supervisor(suspect_after=2, dead_after=40,
                         straggler_skew=2, straggler_patience=1)
        state, _ = eng.run(eng.init(), max_steps=4, supervisor=sup)
        state = kill_machine(sup.engine, state, 2, mode="dead")
        sup.engine.run(state, max_steps=10, supervisor=sup)
        kinds = _kinds(sup)
        assert "shed_skipped_data_lost" in kinds
        assert "shed_atoms" not in kinds


class TestStragglerMonitorObserve:
    """Unit shape of the stateful detector: beats are cumulative, so a
    recovered machine stays behind in absolute skew forever — progress,
    not position, clears the flag."""

    def test_flags_frozen_laggard_then_recovers_on_progress(self):
        mon = StragglerMonitor(4, skew=4, patience=2)
        assert mon.observe([10, 10, 10, 10]) == []  # baseline
        assert mon.observe([12, 12, 10, 12]) == []  # lag 2 < skew
        assert mon.observe([14, 14, 10, 14]) == []  # streak 1 < patience
        assert mon.observe([16, 16, 10, 16]) == [("straggler", 2)]
        assert mon.observe([18, 18, 10, 18]) == []  # flagged is sticky
        # one beat of progress clears it despite absolute lag of 9
        assert mon.observe([20, 20, 11, 20]) == [("recovered", 2)]

    def test_uniformly_slow_mesh_never_flags(self):
        mon = StragglerMonitor(3, skew=2, patience=1)
        beats = np.zeros(3, np.int64)
        for _ in range(6):
            beats = beats + 1
            assert mon.observe(beats) == []

    def test_exclude_masks_watchdog_owned_machines(self):
        mon = StragglerMonitor(2, skew=1, patience=1)
        mon.observe([5, 5])
        assert mon.observe([9, 5], exclude=(1,)) == []
        assert mon.observe([13, 5]) == [("straggler", 1)]


# ---------------------------------------------------------------------------
# join: offered hardware lands inside run()
# ---------------------------------------------------------------------------

@needs_mesh
class TestJoin:
    def test_offered_machine_joins_inside_run(self, cpu_mesh, sub_mesh):
        g, prog, key, tol = _pagerank_case()
        ref_eng = _make(prog, g, cpu_mesh, tol)
        rs, _ = ref_eng.run(ref_eng.init(), max_steps=3000)
        ref = ref_eng.vertex_data(rs)[key]

        eng = _make(prog, g, sub_mesh(3), tol)
        ses = _session()
        sup = Supervisor(session=ses)
        sup.offer_machine(cpu_mesh)
        assert sup.pending_work(), "an offered machine is owed work"
        final, _ = eng.run(eng.init(), max_steps=3000, supervisor=sup)
        eng = sup.engine

        assert eng.layout.n_machines == 4
        joins = [a for a in sup.actions if a["kind"] == "migrate_join"]
        assert joins and joins[0]["joined_machine"] == 3
        assert not sup.pending_work()
        assert float(jnp.max(final.prio)) <= tol
        out = eng.vertex_data(final)[key]
        assert np.abs(out - ref).max() <= 1e-5
        assert any(e["kind"] == "offer_machine" for e in ses.events)


# ---------------------------------------------------------------------------
# local path: progress-skew fires steal_backlog mid-run, zero retrace
# ---------------------------------------------------------------------------

class TestLocalSteal:
    def test_supervisor_fires_steal_backlog_mid_run(self):
        """Queues 1-3 own only isolated vertices (converged after one
        update, never rescheduled) while queue 0 owns a 50-ring: the
        supervisor sees idle queues next to a starved victim and fires
        ``steal_backlog`` itself — a scheduler value update, no retrace —
        and the stolen vertices execute (``stolen_updates > 0``)."""
        n, tol = 60, 1e-7
        ring = np.arange(50)
        st_, _ = GraphStructure.undirected(ring, (ring + 1) % 50, n)
        g = make_pagerank_graph(st_)
        prog = PageRankProgram(0.15, n)

        ref_eng = Engine(prog, g, tolerance=tol)
        ref_state, _ = ref_eng.run(ref_eng.init(g), max_steps=3000)
        ref = np.asarray(ref_state.graph.vertex_data["rank"])

        machine_of = np.zeros(n, np.int32)
        machine_of[50:] = 1 + np.arange(10) % 3
        ws = WorkStealingScheduler(prog, st_, tol, machine_of,
                                   pipeline_length=4)
        eng = Engine(prog, g, tolerance=tol, scheduler=ws)
        ses = _session()
        sup = Supervisor(session=ses, steal_skew=2, steal_frac=0.8)
        state, _ = eng.run(eng.init(g), max_steps=3000, supervisor=sup)

        steals = [a for a in sup.actions if a["kind"] == "steal_backlog"]
        assert steals, "supervisor never fired steal_backlog"
        assert steals[0]["victim"] == 0
        assert set(steals[0]["to"]) <= {1, 2, 3}
        assert steals[0]["moved"] > 0
        assert stolen_updates(state.sched) > 0, \
            "stolen vertices must actually execute"
        out = np.asarray(state.graph.vertex_data["rank"])
        assert np.abs(out - ref).max() <= 1e-5
        assert any(e["kind"] == "steal_backlog" for e in ses.events)

    def test_balanced_queues_never_steal(self):
        g, prog, _, _ = _pagerank_case(n=40)
        st_ = g.structure
        machine_of = np.arange(st_.n_vertices) % 4
        ws = WorkStealingScheduler(prog, st_, 1e-6, machine_of,
                                   pipeline_length=8)
        eng = Engine(prog, g, tolerance=1e-6, scheduler=ws)
        sup = Supervisor(steal_skew=2)
        eng.run(eng.init(g), max_steps=200, supervisor=sup)
        assert "steal_backlog" not in _kinds(sup)
