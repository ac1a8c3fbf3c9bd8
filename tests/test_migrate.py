"""Live shard migration (dist/migrate.py, core/partition.py rebalance;
DESIGN §3.13).

The tentpole property, per engine and per app: after a machine dies
mid-run (poison + silence, ``mode="dead"``), ``migrate_leave`` rebuilds
*only* the lost shard from the latest committed cut, carries every
survivor's live state onto the smaller mesh, reschedules nothing outside
the lost vertices' closed scopes, and the survivor mesh reconverges to
≤ 1e-5 of the uninterrupted fixed point.  ``migrate_join`` is the
reverse direction with the stronger contract — pure handoff: a converged
mesh stays converged through a join.  ``shed_atoms`` moves a straggler's
pending backlog at the placement level.  Also covered: incremental
rebalance stability (surviving atoms don't move on a leave), the
containment guard (escaped poison is refused, not laundered), and the
refusal paths (streaming engines, atom-less explicit placements).
"""
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps.lbp import LoopyBPProgram, make_mrf_graph
from repro.apps.pagerank import PageRankProgram, make_pagerank_graph
from repro.checkpoint.manager import CheckpointManager
from repro.core.partition import (atom_meta_index, overpartition,
                                  rebalance_placement)
from repro.dist.engine import DistributedEngine
from repro.dist.faults import kill_machine
from repro.dist.locking import DistributedLockingEngine
from repro.dist.migrate import migrate_join, migrate_leave, shed_atoms
from repro.dist.snapshot import save_snapshot
from repro.graphs.generators import connected_power_law_graph as \
    connected_graph

pytestmark = pytest.mark.skipif(
    jax.device_count() < 4, reason="needs 4 forced host devices "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=4)")


def _pagerank_case(n=80, seed=3):
    g = make_pagerank_graph(connected_graph(n, seed=seed))
    return g, PageRankProgram(0.15, n), "rank", 1e-9


# The LBP residual is Σ_k |Δ belief_k| over f32 log-beliefs, which here
# reach |b| in [8, 16), where one ulp is 2**-20 (9.5e-7).  Rounding carried
# around the graph's loops leaves period-2 cycles in which a belief moves
# 1–2 ulps in two of its three states, and a vertex's priority sums such
# residuals over its neighbors: 7 ulps (6.7e-6) on this instance.  A
# tolerance of 1e-6 is about one ulp, below that floor, so the sweep engine
# never empties its scheduler; 16 ulps clears it.
LBP_TOL = 16 * 2.0 ** -20


def _lbp_case(n=60, seed=3):
    g = make_mrf_graph(connected_graph(n, seed=seed), n_states=3, seed=1)
    return g, LoopyBPProgram(3), "belief", LBP_TOL


# bfs atoms keep lost scopes contiguous — the placement the paper's
# two-phase scheme produces; hash placement still reconverges but scatters
# the reseed over every survivor
ENGINES = {
    "sweep": lambda prog, g, mesh, tol: DistributedEngine(
        prog, g, mesh, tolerance=tol, method="bfs"),
    "locking": lambda prog, g, mesh, tol: DistributedLockingEngine(
        prog, g, mesh, pipeline_length=16, tolerance=tol, method="bfs"),
}


def _committed_cut(eng, state, mgr):
    state = eng.start_snapshot(state, (0,))
    while not eng.snapshot_complete(state):
        state = eng.step(state)
    save_snapshot(mgr, int(state.step_index), eng, state)
    return eng.clear_snapshot(state)


class TestMigrateLeave:
    @pytest.mark.parametrize("engine_kind", ["sweep", "locking"])
    @pytest.mark.parametrize("case", [_pagerank_case, _lbp_case],
                             ids=["pagerank", "lbp"])
    def test_leave_reconverges_without_full_restart(self, cpu_mesh,
                                                    sub_mesh, engine_kind,
                                                    case):
        g, prog, key, tol = case()
        make = ENGINES[engine_kind]
        ref_eng = make(prog, g, cpu_mesh, tol)
        rs, _ = ref_eng.run(ref_eng.init(), max_steps=3000)
        ref = np.asarray(ref_eng.vertex_data(rs)[key])

        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d, async_writes=False)
            eng = make(prog, g, cpu_mesh, tol)
            state = _committed_cut(eng, eng.step(eng.init()), mgr)
            state = eng.step(state)
            state = kill_machine(eng, state, 1, mode="dead")
            # survivors keep stepping on the wounded mesh (watchdog window)
            state = eng.step(eng.step(state))
            eng3, state3, info = migrate_leave(eng, state, 1,
                                               mesh=sub_mesh(3),
                                               manager=mgr)

        assert eng3.layout.n_machines == 3
        assert info["dead_machine"] == 1 and info["lost_vertices"] > 0
        # the zero-restart evidence: every rescheduled survivor sits inside
        # the lost vertices' closed scopes
        assert info["survivor_rescheduled"] <= int(info["scope_mask"].sum())
        n = g.structure.n_vertices
        assert info["survivor_rescheduled"] < n - info["lost_vertices"]

        state3, _ = eng3.run(state3, max_steps=3000)
        assert float(jnp.max(state3.prio)) <= tol
        out = np.asarray(eng3.vertex_data(state3)[key])
        assert np.abs(out - ref).max() <= 1e-5

    def test_leave_refuses_escaped_poison(self, cpu_mesh, sub_mesh):
        """If NaN ever reaches a *survivor* row (here: a second machine's
        data is destroyed too), migrate_leave must refuse to launder it
        into the new mesh rather than patch only the declared-dead shard."""
        g, prog, _, tol = _pagerank_case()
        eng = ENGINES["sweep"](prog, g, cpu_mesh, tol)
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d, async_writes=False)
            state = _committed_cut(eng, eng.step(eng.init()), mgr)
            state = kill_machine(eng, state, 1, mode="dead")
            state = kill_machine(eng, state, 0, mode="kill")
            with pytest.raises(RuntimeError, match="escaped containment"):
                migrate_leave(eng, state, 1, mesh=sub_mesh(3), manager=mgr)

    def test_leave_validates_mesh_size(self, cpu_mesh):
        g, prog, _, tol = _pagerank_case()
        eng = ENGINES["sweep"](prog, g, cpu_mesh, tol)
        state = eng.init()
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d, async_writes=False)
            with pytest.raises(ValueError, match="survivor mesh"):
                migrate_leave(eng, state, 0, mesh=cpu_mesh, manager=mgr)


class TestMigrateJoin:
    def test_join_of_converged_mesh_stays_converged(self, cpu_mesh,
                                                    sub_mesh):
        """Pure handoff: a converged 3-mesh takes a 4th machine; nothing is
        rescheduled, the fixed point survives bit-for-policy."""
        g, prog, key, tol = _pagerank_case()
        eng = ENGINES["sweep"](prog, g, sub_mesh(3), tol)
        state, _ = eng.run(eng.init(), max_steps=3000)
        assert float(jnp.max(state.prio)) <= tol
        before = np.asarray(eng.vertex_data(state)[key])

        eng4, state4, info = migrate_join(eng, state, mesh=cpu_mesh)
        assert eng4.layout.n_machines == 4
        assert info["joined_machine"] == 3
        assert info["moved_atoms"] > 0 and info["moved_vertices"] > 0
        assert info["survivor_rescheduled"] == 0
        # converged stays converged: nothing to do on the wider mesh
        assert float(jnp.max(state4.prio)) <= tol
        state4 = eng4.step(state4)
        out = np.asarray(eng4.vertex_data(state4)[key])
        assert np.abs(out - before).max() <= 1e-7

    def test_join_validates_mesh_size(self, cpu_mesh):
        g, prog, _, tol = _pagerank_case()
        eng = ENGINES["sweep"](prog, g, cpu_mesh, tol)
        with pytest.raises(ValueError, match="join: mesh"):
            migrate_join(eng, eng.init(), mesh=cpu_mesh)


class TestShedAtoms:
    def test_shed_moves_backlog_and_preserves_fixed_point(self, cpu_mesh):
        g, prog, key, tol = _pagerank_case()
        ref_eng = ENGINES["sweep"](prog, g, cpu_mesh, tol)
        rs, _ = ref_eng.run(ref_eng.init(), max_steps=3000)
        ref = np.asarray(ref_eng.vertex_data(rs)[key])

        eng = ENGINES["sweep"](prog, g, cpu_mesh, tol)
        state = eng.step(eng.init())  # mid-run: real backlog everywhere
        eng2, state2, info = shed_atoms(eng, state, 0, frac=1.0)
        assert info["shed_atoms"] > 0 and info["shed_vertices"] > 0
        assert info["shed_backlog"] > 0.0
        state2, _ = eng2.run(state2, max_steps=3000)
        out = np.asarray(eng2.vertex_data(state2)[key])
        assert np.abs(out - ref).max() <= 1e-5

    def test_shed_noops_when_converged(self, cpu_mesh):
        g, prog, _, tol = _pagerank_case()
        eng = ENGINES["sweep"](prog, g, cpu_mesh, tol)
        state, _ = eng.run(eng.init(), max_steps=3000)
        eng2, state2, info = shed_atoms(eng, state, 2)
        assert info["shed_atoms"] == 0
        assert eng2 is eng and state2 is state  # no rebuild, no retrace


class TestRefusals:
    def test_atomless_engine_is_not_migratable(self, cpu_mesh):
        g, prog, _, tol = _pagerank_case()
        n = g.structure.n_vertices
        eng = DistributedEngine(prog, g, cpu_mesh, tolerance=tol,
                                machine_of=np.arange(n) % 4)
        assert eng.atom_of is None
        with pytest.raises(ValueError, match="without atoms"):
            migrate_join(eng, eng.init(), mesh=cpu_mesh)

    def test_streaming_engine_is_refused(self, cpu_mesh):
        g, prog, _, tol = _pagerank_case()
        eng = ENGINES["sweep"](prog, g, cpu_mesh, tol)
        eng.streaming = True  # what a stream-built dist engine reports
        with pytest.raises(NotImplementedError,
                           match="recover_from_journal"):
            shed_atoms(eng, eng.init(), 0)


class TestRebalancePlacement:
    def test_leave_is_incremental_and_join_balances(self):
        st = connected_graph(120, seed=5)
        atom_of = overpartition(st, 12, method="bfs", seed=0)
        index = atom_meta_index(st, atom_of)
        w = (index.atom_nv + index.atom_ne).astype(np.int64)
        placement = np.asarray(np.arange(12) % 4, np.int32)

        out = rebalance_placement(index, placement, 4, remove=(2,))
        # evacuation only: atoms that lived on survivors did not move
        survivors = placement != 2
        np.testing.assert_array_equal(out[survivors], placement[survivors])
        assert not (out == 2).any()

        # join: the new machine gets real load, nobody is overloaded worse
        grown = rebalance_placement(index, out, 5)
        assert (grown == 4).any()
        load = np.zeros(5, np.int64)
        np.add.at(load, grown, w)
        assert load.max() <= 2 * max(1, load[load > 0].min())

    def test_rebalance_needs_a_machine(self):
        st = connected_graph(20, seed=1)
        atom_of = overpartition(st, 4, method="bfs", seed=0)
        index = atom_meta_index(st, atom_of)
        with pytest.raises(ValueError):
            rebalance_placement(index, np.zeros(4, np.int32), 1,
                                remove=(0,))
