"""Kill/restore chaos harness + checkpoint crash safety (dist/faults.py,
checkpoint/manager.py; ISSUE 4 satellites).

The acceptance scenario: a machine killed mid-run on the 4-device mesh is
recovered from an asynchronously captured distributed snapshot and both
dist engines reconverge to ≤ 1e-5 of the uninterrupted fixed point — on
PageRank and LBP, including the elastic 4→2 device restore.

Failure injection is deterministic: the kill site comes from
``REPRO_CHAOS_SEED`` (default 0); tier-1 covers the default and CI's
dedicated chaos step pins seed 7 for a second deterministic kill site.
"""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps.lbp import LoopyBPProgram, make_mrf_graph
from repro.apps.pagerank import PageRankProgram, make_pagerank_graph
from repro.checkpoint.manager import CheckpointManager
from repro.core.snapshot import restore_engine_state
from repro.dist.engine import DistributedEngine
from repro.dist.faults import kill_machine, machine_data_lost, \
    run_kill_restore
from repro.dist.locking import DistributedLockingEngine
from repro.dist.snapshot import (DistSnapshotDriver, load_snapshot,
                                 save_snapshot, shard_journals,
                                 snapshot_from_journals)
from repro.graphs.generators import connected_power_law_graph as \
    connected_graph

pytestmark = pytest.mark.skipif(
    jax.device_count() < 4, reason="needs 4 forced host devices "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=4)")

CHAOS_SEED = int(os.environ.get("REPRO_CHAOS_SEED", "0"))


def _pagerank_case(n=80, seed=3):
    struct = connected_graph(n, seed=seed)
    g = make_pagerank_graph(struct)
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
    struct = connected_graph(n, seed=seed)
    g = make_mrf_graph(struct, n_states=3, seed=1)
    return g, LoopyBPProgram(3), "belief", LBP_TOL


ENGINES = {
    "sweep": lambda prog, g, mesh, tol: DistributedEngine(
        prog, g, mesh, tolerance=tol),
    "locking": lambda prog, g, mesh, tol: DistributedLockingEngine(
        prog, g, mesh, pipeline_length=16, tolerance=tol),
}


class TestKillRestore:
    @pytest.mark.parametrize("engine_kind", ["sweep", "locking"])
    @pytest.mark.parametrize("case", [_pagerank_case, _lbp_case],
                             ids=["pagerank", "lbp"])
    def test_reconverges_after_machine_loss(self, cpu_mesh, engine_kind,
                                            case):
        """Kill a machine mid-run; restore the journaled async cut;
        reconverge to ≤ 1e-5 of the uninterrupted fixed point."""
        g, prog, key, tol = case()
        make = ENGINES[engine_kind]
        ref_eng = make(prog, g, cpu_mesh, tol)
        rs, _ = ref_eng.run(ref_eng.init(), max_steps=3000)
        assert float(jnp.max(rs.prio)) <= tol
        ref = ref_eng.vertex_data(rs)[key]

        with tempfile.TemporaryDirectory() as d:
            eng = make(prog, g, cpu_mesh, tol)
            used, final, info = run_kill_restore(
                eng, CheckpointManager(d), kill_step=20, seed=CHAOS_SEED,
                max_steps=3000)
        assert float(jnp.max(final.prio)) <= tol
        assert info["restored_step"] <= info["kill_step"]
        out = used.vertex_data(final)[key]
        assert np.abs(out - ref).max() <= 1e-5, \
            f"{engine_kind} did not reconverge after machine loss"

    @pytest.mark.parametrize("engine_kind", ["sweep", "locking"])
    def test_elastic_4_to_2_restore(self, cpu_mesh, sub_mesh,
                                    engine_kind):
        """The journaled 4-machine cut restores onto a 2-machine mesh
        (two-phase atom elasticity) and reconverges."""
        g, prog, key, tol = _pagerank_case()
        make = ENGINES[engine_kind]
        ref_eng = make(prog, g, cpu_mesh, tol)
        rs, _ = ref_eng.run(ref_eng.init(), max_steps=3000)
        ref = ref_eng.vertex_data(rs)[key]

        with tempfile.TemporaryDirectory() as d:
            eng = make(prog, g, cpu_mesh, tol)
            small = make(prog, g, sub_mesh(2), tol)
            used, final, info = run_kill_restore(
                eng, CheckpointManager(d), kill_step=20, seed=CHAOS_SEED,
                restore_engine=small, max_steps=3000)
        assert used is small
        assert used.layout.n_machines == 2
        out = used.vertex_data(final)[key]
        assert np.abs(out - ref).max() <= 1e-5

    def test_kill_poisons_and_drops_inflight_snapshot(self, cpu_mesh):
        g, prog, _, tol = _pagerank_case()
        eng = DistributedEngine(prog, g, cpu_mesh, tolerance=tol)
        state = eng.start_snapshot(eng.step(eng.init()), (0,))
        state = eng.step(state)
        assert state.snap is not None
        state = kill_machine(eng, state, 1)
        assert state.snap is None, "in-flight wave must die with the machine"
        assert machine_data_lost(eng, state, 1)
        # surviving machines' data is intact
        assert not machine_data_lost(eng, state, 0)

    def test_no_snapshot_before_kill_raises(self, cpu_mesh):
        g, prog, _, tol = _pagerank_case()
        eng = DistributedEngine(prog, g, cpu_mesh, tolerance=tol)
        with tempfile.TemporaryDirectory() as d:
            with pytest.raises(RuntimeError, match="no snapshot completed"):
                run_kill_restore(eng, CheckpointManager(d), kill_step=1,
                                 snapshot_at=0, seed=CHAOS_SEED)


class TestShardedJournals:
    def test_journal_roundtrip_any_shard_count(self, cpu_mesh,
                                               sub_mesh):
        """save_shards → restore_shards → stitched cut is bit-identical to
        the directly assembled one, and restores onto a 2-machine engine
        (elastic round-trip)."""
        g, prog, _, tol = _pagerank_case()
        eng = DistributedEngine(prog, g, cpu_mesh, tolerance=tol)
        state = eng.start_snapshot(eng.step(eng.init()), (0,))
        while not eng.snapshot_complete(state):
            state = eng.step(state)
        direct = eng.assemble_snapshot(state)

        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d)
            save_snapshot(mgr, int(state.step_index), eng, state)
            mgr.wait()
            step, cut = load_snapshot(mgr, g)
        assert step == int(state.step_index)
        np.testing.assert_array_equal(np.asarray(cut.save_step),
                                      np.asarray(direct.save_step))
        for a, b in zip(jax.tree.leaves(cut.saved_v),
                        jax.tree.leaves(direct.saved_v)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

        small = DistributedEngine(prog, g, sub_mesh(2), tolerance=tol)
        restored = restore_engine_state(small, g, cut)
        np.testing.assert_allclose(
            small.vertex_data(restored)["rank"],
            np.asarray(direct.saved_v["rank"]), rtol=0, atol=0)

    def test_journals_stitch_regardless_of_partition(self, cpu_mesh):
        """snapshot_from_journals only trusts the embedded gid maps:
        shuffling journal order changes nothing."""
        g, prog, _, tol = _pagerank_case(n=40, seed=9)
        eng = DistributedEngine(prog, g, cpu_mesh, tolerance=tol)
        state = eng.start_snapshot(eng.step(eng.init()), (0,))
        while not eng.snapshot_complete(state):
            state = eng.step(state)
        journals = shard_journals(eng.layout, state.snap)
        a = snapshot_from_journals(journals, g)
        b = snapshot_from_journals(list(reversed(journals)), g)
        np.testing.assert_array_equal(np.asarray(a.save_step),
                                      np.asarray(b.save_step))
        np.testing.assert_array_equal(np.asarray(a.saved_v["rank"]),
                                      np.asarray(b.saved_v["rank"]))


class TestCrashDuringWrite:
    def test_torn_shard_dir_never_selected(self):
        """A crash mid-write leaves shards but no COMMITTED marker: the
        torn directory must be invisible to restore."""
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d, async_writes=False)
            mgr.save_shards(1, [{"x": np.arange(3)}])
            torn = os.path.join(d, "ckpt_0000000099")
            os.makedirs(torn)
            np.savez(os.path.join(torn, "shard_00000.npz"), x=np.arange(3))
            assert mgr.all_steps() == [1]
            step, shards = mgr.restore_shards(None)
            assert step == 1 and len(shards) == 1

    def test_crash_mid_shard_write_commits_nothing(self, monkeypatch):
        """Simulated crash while writing shard 2 of 3: the atomic-commit
        guarantee means no ckpt directory (and no partial shard set) ever
        becomes visible."""
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d, async_writes=False)
            mgr.save_shards(1, [{"x": np.arange(3)}] * 3)

            calls = {"n": 0}
            real_savez = np.savez

            def crashing_savez(path, **kw):
                calls["n"] += 1
                if calls["n"] == 2:
                    raise OSError("disk died mid-journal")
                return real_savez(path, **kw)

            monkeypatch.setattr(np, "savez", crashing_savez)
            with pytest.raises(OSError, match="disk died"):
                mgr.save_shards(5, [{"x": np.arange(3)}] * 3)
            monkeypatch.setattr(np, "savez", real_savez)

            assert mgr.all_steps() == [1], "torn checkpoint became visible"
            assert not [n for n in os.listdir(d) if n.startswith(".tmp")], \
                "crash left tmp debris behind"
            step, shards = mgr.restore_shards(None)
            assert step == 1 and len(shards) == 3

    def test_async_crash_surfaces_on_wait(self, monkeypatch):
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d, async_writes=True)

            def boom(path, **kw):
                raise OSError("async disk died")

            monkeypatch.setattr(np, "savez", boom)
            mgr.save_shards(3, [{"x": np.arange(2)}])
            with pytest.raises(OSError, match="async disk died"):
                mgr.wait()
            assert mgr.all_steps() == []


class TestYoungIntervalDriver:
    def test_periodic_snapshots_journaled(self, cpu_mesh):
        """The Young-interval driver keeps journaling completed cuts while
        computation proceeds; the latest one restores and reconverges."""
        g, prog, key, tol = _pagerank_case(n=100, seed=5)
        eng = DistributedEngine(prog, g, cpu_mesh, tolerance=1e-10)
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d, max_to_keep=10)
            driver = DistSnapshotDriver(eng, mgr, interval_steps=6)
            final, trace = driver.run(eng.init(), max_steps=300)
            mgr.wait()
            steps = mgr.all_steps()
            assert len(steps) >= 1, "driver never journaled a snapshot"
            assert float(jnp.max(final.prio)) <= 1e-10
            direct = eng.vertex_data(final)[key]

            _, cut = load_snapshot(mgr, g)
            rs, _ = eng.run(restore_engine_state(eng, g, cut),
                            max_steps=500)
            np.testing.assert_allclose(eng.vertex_data(rs)[key], direct,
                                       atol=1e-7)
        # snapshot work never paused computation (Fig. 4 async property):
        # updates strictly accumulate every pre-convergence step, snapshot
        # in flight or not (post-convergence steps only drain the wave)
        live = [t for t in trace if t["max_prio"] > 1e-10]
        assert len(live) >= 3
        assert all(b["updates"] > a["updates"]
                   for a, b in zip(live, live[1:]))

    def test_stalled_wave_fails_loudly(self, cpu_mesh):
        """A marker wave that cannot reach every vertex (disconnected
        graph) must raise, not silently burn max_steps journaling
        nothing."""
        from repro.core.graph import GraphStructure
        n = 16
        u = np.concatenate([np.arange(0, 7), np.arange(8, 15)])
        st2, _ = GraphStructure.undirected(u, u + 1, n)  # two paths
        g = make_pagerank_graph(st2)
        eng = DistributedEngine(PageRankProgram(0.15, n), g, cpu_mesh,
                                tolerance=1e-12)
        driver = DistSnapshotDriver(eng, None, interval_steps=1,
                                    initiators=(0,))
        with pytest.raises(RuntimeError, match="stalled"):
            driver.run(eng.init(), max_steps=200)

    def test_young_interval_derivation(self, cpu_mesh):
        g, prog, _, tol = _pagerank_case(n=24, seed=1)
        eng = DistributedEngine(prog, g, cpu_mesh, tolerance=tol)
        drv = DistSnapshotDriver(eng, None, t_step_s=60.0,
                                 t_checkpoint_s=120.0,
                                 t_mtbf_node_s=365 * 24 * 3600.0)
        # paper's example: ~3h interval at 1-minute steps on 4 machines
        assert drv.interval_steps == int(round(
            (2 * 120.0 * 365 * 24 * 3600.0 / 4) ** 0.5 / 60.0))
