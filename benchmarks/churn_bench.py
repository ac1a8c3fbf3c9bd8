"""Churn chaos benchmark: the self-healing mesh under membership churn,
healed autonomously by the telemetry control loop (DESIGN §3.13, §3.15).

The scenario the elastic mesh exists for, measured end to end on the
4-machine mesh: mid-run, one machine **dies** (silently — data poisoned
AND the machine stops beating, so only the heartbeat watchdog can notice),
one machine **joins** back, and one machine **straggles** (silent stall).
The harness only *injects* the chaos (``kill_machine`` / ``stall_machine``
/ ``resume_machine`` / ``offer_machine``); every remedy is fired by the
``obs.Supervisor`` inside ``run()`` — the host makes ZERO migration or
steal calls:

  death      → watchdog declares it dead → supervisor rebuilds just the
               lost shard via ``migrate_leave`` from its own committed
               Chandy-Lamport cut (the supervisor also owns the snapshot
               cadence) while survivors carry their state across;
  join       → the offered mesh lands via ``migrate_join`` at the next
               healthy observation, zero rescheduling;
  straggler  → flagged from frozen beats alone → ``shed_atoms`` moves its
               pending backlog to its peers, the mesh converges *while
               the straggler is still stalled*, and resuming it
               reinstates the suspect without any migration.

Self-check verdicts per case (PageRank + LBP): the churned run reconverges
to ≤ 1e-5 of the uninterrupted fixed point; total vertex updates stay
≤ 2.5× the uninterrupted run (wall clock is recorded but not asserted —
each heal retraces the jitted step once, which dominates wall time at
benchmark scale but is amortized at production scale); the death was
detected by beats with zero NaNs on survivor rows; the join rescheduled
nothing; every remedy appears in the exported Perfetto timeline
(``BENCH_churn_trace.json``, uploaded as a CI artifact) — zero
full-engine restarts, zero host-harness remediation calls.

Deterministic: the dead/straggler machines come from ``REPRO_CHURN_SEED``
(default 0); CI pins a different seed so a second churn pattern is
exercised every run.
"""
from __future__ import annotations

import os
import tempfile
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

CHURN_SEED = int(os.environ.get("REPRO_CHURN_SEED", "0"))
MAX_STEPS = 3000


def _mesh(n):
    devs = np.asarray(jax.devices()[:n]).reshape(n, 1)
    return jax.sharding.Mesh(devs, ("data", "model"))


def _case(name):
    from repro.apps.lbp import LoopyBPProgram, make_mrf_graph
    from repro.apps.pagerank import PageRankProgram, make_pagerank_graph
    from repro.graphs.generators import connected_power_law_graph

    if name == "pagerank":
        st = connected_power_law_graph(80, seed=3)
        return make_pagerank_graph(st), PageRankProgram(0.15, 80), \
            "rank", 1e-9
    # churn reorders the async update schedule, so LBP must run in its
    # unique-fixed-point (weak-coupling) regime: at the default Potts
    # smoothing 2.0 loopy BP on this graph is multi-stable and ANY
    # reordering lands in a different attractor (error ~ the whole
    # belief scale) — which no amount of healing can undo
    st = connected_power_law_graph(60, seed=3)
    return make_mrf_graph(st, n_states=3, seed=1), \
        LoopyBPProgram(3, smoothing=0.6), "belief", 1e-5


def _sum_updates(state) -> int:
    return int(np.nansum(np.asarray(state.update_count, np.float64)))


def _all_finite(engine, state) -> bool:
    for leaf in jax.tree.leaves(engine.vertex_data(state)):
        leaf = np.asarray(leaf)
        if np.issubdtype(leaf.dtype, np.floating) \
                and not np.isfinite(leaf).all():
            return False
    return True


def _acts(sup, kind: str) -> List[Dict]:
    return [a for a in sup.actions if a["kind"] == kind]


def _one_case(name: str, rng: np.random.Generator) -> Dict:
    from repro.checkpoint.manager import CheckpointManager
    from repro.dist.engine import DistributedEngine
    from repro.dist.faults import kill_machine, resume_machine, \
        stall_machine
    from repro.obs import ObsConfig, ObsSession, Supervisor, \
        write_chrome_trace

    g, prog, key, tol = _case(name)
    make = lambda mesh: DistributedEngine(prog, g, mesh, tolerance=tol,
                                          method="bfs")

    # ---- uninterrupted reference ---------------------------------------
    t0 = time.time()
    ref_eng = make(_mesh(4))
    rs, _ = ref_eng.run(ref_eng.init(), max_steps=MAX_STEPS)
    ref = np.asarray(ref_eng.vertex_data(rs)[key])
    ref_updates = _sum_updates(rs)
    ref_wall = time.time() - t0

    dead = int(rng.integers(4))
    straggler = int((dead + 1 + rng.integers(3)) % 4)
    t0 = time.time()
    rec: Dict = {"case": name, "dead_machine": dead,
                 "straggler_machine": straggler, "seed": CHURN_SEED}

    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, async_writes=False)
        ses = ObsSession(ObsConfig(enabled=True, timeline=True))
        # dead_after sits far above the straggler flag (skew+patience) so
        # a mere straggler sheds, never migrates — even across the long
        # converge-while-stalled segment; the silently-dead machine also
        # gets straggler-flagged first, where the data-lost guard must
        # refuse the shed and leave it to the watchdog
        sup = Supervisor(manager=mgr, mesh_factory=_mesh, session=ses,
                         suspect_after=2, dead_after=60,
                         straggler_skew=3, straggler_patience=2,
                         shed_frac=1.0, snapshot_every=3)
        eng = make(_mesh(4))
        state = eng.init()

        # ---- fault 1: straggler, stalled from the very first step ----
        # (so the fault lands while work remains even for fast-converging
        # programs — LBP reaches its fixed point in ~8 sweeps).  The
        # supervisor's snapshot cadence commits its cut right through the
        # stalled machine: marker capture is not stall-gated and a stall
        # is not data loss, so the cut is finite and consistent.
        stall_machine(eng, straggler)
        state, _ = eng.run(state, max_steps=MAX_STEPS, supervisor=sup,
                           session=ses)
        eng = sup.engine
        sheds = [a for a in _acts(sup, "shed_atoms")
                 if a["machine"] == straggler]
        rec["straggler_shed_by_supervisor"] = bool(sheds)
        rec["shed_atoms"] = int(sheds[0]["shed_atoms"]) if sheds else 0
        rec["converged_despite_straggler"] = bool(
            float(jnp.max(state.prio)) <= tol)
        rec["cut_before_fault"] = sup.cuts_committed >= 1
        resume_machine(eng, straggler)

        # ---- fault 2: silent death (injection only); the resumed
        # straggler's reinstatement also lands in this segment's ticks --
        state = kill_machine(eng, state, dead, mode="dead")
        state, _ = eng.run(state, max_steps=MAX_STEPS, supervisor=sup,
                           session=ses)
        eng = sup.engine
        rec["straggler_reinstated"] = any(
            a["machine"] == straggler
            for a in _acts(sup, "watchdog_reinstated")
            + _acts(sup, "recovered"))
        rec["detected_dead"] = any(a["machine"] == dead for a in
                                   _acts(sup, "watchdog_dead"))
        leaves = _acts(sup, "migrate_leave")
        rec["healed_by_supervisor"] = bool(
            leaves and leaves[0]["machine"] == dead)
        rec["shed_guard_held"] = not any(
            a["machine"] == dead for a in _acts(sup, "shed_atoms"))
        rec["survivors"] = eng.layout.n_machines
        # the stall gate + cut restore contained the poison
        rec["survivors_clean"] = _all_finite(eng, state)

        # ---- fault 3 (anti-fault): offer the spare back --------------
        sup.offer_machine(_mesh(4))
        state, _ = eng.run(state, max_steps=MAX_STEPS, supervisor=sup,
                           session=ses)
        eng = sup.engine
        joins = _acts(sup, "migrate_join")
        rec["join_by_supervisor"] = bool(joins)
        rec["join_rescheduled"] = int(
            joins[0]["survivor_rescheduled"]) if joins else -1
        rec["join_moved_atoms"] = int(
            joins[0]["moved_atoms"]) if joins else 0

        updates = sup.updates_carried + _sum_updates(state)
        out = np.asarray(eng.vertex_data(state)[key])

    # zero host-harness remediation: every migrate/shed above came out of
    # supervisor.actions — the harness only injected chaos
    rec["host_remediation_calls"] = 0
    remedy_kinds = {"graphlab.migrate_leave", "graphlab.migrate_join",
                    "graphlab.shed_atoms"}
    rec["timeline_has_remedies"] = remedy_kinds <= {
        e["name"] for e in ses.timeline.events if e.get("ph") == "X"}
    if name == "pagerank":
        write_chrome_trace("BENCH_churn_trace.json", ses.timeline,
                           metadata={"bench": "churn", "seed": CHURN_SEED})

    rec["fixed_point_err"] = float(np.abs(out - ref).max())
    rec["reconverged"] = bool(rec["fixed_point_err"] <= 1e-5)
    rec["updates"] = updates
    rec["ref_updates"] = ref_updates
    rec["updates_ratio"] = round(updates / max(ref_updates, 1), 3)
    rec["graceful"] = bool(rec["updates_ratio"] <= 2.5)
    rec["wall_s"] = round(time.time() - t0, 1)
    rec["ref_wall_s"] = round(ref_wall, 1)
    return rec


def churn_chaos() -> List[Dict]:
    """1 death + 1 join + 1 straggler healed by the supervisor inside
    run(): reconverge ≤1e-5 at ≤2.5× updates, zero host remediation."""
    if jax.device_count() < 4:
        return [{"case": "skipped",
                 "reason": "needs 4 devices "
                           "(XLA_FLAGS=--xla_force_host_platform_"
                           "device_count=4)"}]
    rng = np.random.default_rng(CHURN_SEED)
    records = [_one_case(name, rng) for name in ("pagerank", "lbp")]
    for r in records:
        assert r["cut_before_fault"], r
        assert r["detected_dead"] and r["survivors_clean"], r
        assert r["healed_by_supervisor"] and r["shed_guard_held"], r
        assert r["join_by_supervisor"] and r["join_rescheduled"] == 0, r
        assert r["straggler_shed_by_supervisor"], r
        assert r["converged_despite_straggler"], r
        assert r["straggler_reinstated"], r
        assert r["reconverged"], r
        assert r["graceful"], r
        assert r["timeline_has_remedies"], r
    return records
