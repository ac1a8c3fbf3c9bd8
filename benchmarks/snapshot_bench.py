"""Snapshot-overhead sweep (paper Fig. 4, Sec. 4.3; ISSUE 4 satellite).

The paper's fault-tolerance trade-off on the *sharded* engine: the
synchronous snapshot suspends execution — all machines halt at a step
barrier while the full graph is journaled, so the updates-over-time curve
**flatlines** — while the asynchronous Chandy-Lamport snapshot runs as a
prioritized update inside the shard_map step and **computation proceeds**:
only the marker frontier does snapshot work, and regular updates keep
accumulating every step the wave is in flight.

Both schemes run adaptive PageRank on the same partitioned graph over a
(data=S, model=1) mesh built from every available device (CI forces 4 host
devices).  Each record is one engine step: ``updates`` is the cumulative
update count and ``paused`` marks the sync flatline steps.  The records
carry two self-checking verdicts so BENCH_snapshot.json reads standalone:
``async_no_flatline`` (updates strictly increased through every in-flight
wave step) and ``sync_flatlined`` (the sync curve has exactly
``CAPTURE_STEPS`` paused steps with zero update progress).
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.apps.pagerank import PageRankProgram, make_pagerank_graph
from repro.dist.engine import DistributedEngine
from repro.graphs.generators import connected_power_law_graph
from repro.launch.mesh import auto_mesh

N_VERTICES = 400
TOLERANCE = 1e-10
SNAPSHOT_AT = 3
CAPTURE_STEPS = 5   # sync journaling modeled as engine steps, like Fig. 4(a)
MAX_STEPS = 400


def snapshot_overhead() -> List[Dict]:
    """Fig. 4: sync snapshot flatlines, async computation proceeds."""
    S = jax.device_count()
    mesh = auto_mesh((S, 1), ("data", "model"))
    struct = connected_power_law_graph(N_VERTICES, seed=0)
    g = make_pagerank_graph(struct)
    prog = PageRankProgram(0.15, struct.n_vertices)
    out: List[Dict] = []

    # -- async: the Chandy-Lamport marker wave rides the engine step ------
    eng = DistributedEngine(prog, g, mesh, tolerance=TOLERANCE)
    state = eng.init()
    t0 = time.time()
    in_flight: List[int] = []
    for _ in range(MAX_STEPS):
        converged = float(jnp.max(state.prio)) <= TOLERANCE
        if converged and state.snap is None:
            break
        if state.snap is None and int(state.step_index) == SNAPSHOT_AT:
            state = eng.start_snapshot(state, (0,))
        state = eng.step(state)
        frac = eng.snapshot_done_frac(state)
        rec = {
            "fig": "4", "scheme": "async",
            "step": int(state.step_index),
            "updates": int(np.asarray(state.update_count).sum()),
            "snapshot_done_frac": round(frac, 4),
            "paused": 0,
        }
        out.append(rec)
        if state.snap is not None:
            if 0.0 < frac < 1.0 and not converged:
                in_flight.append(rec["updates"])
            if eng.snapshot_complete(state):
                assert eng.snapshot_violations(state) == 0
                state = eng.clear_snapshot(state)
    async_wall = round(time.time() - t0, 2)
    async_no_flatline = len(in_flight) >= 1 and all(
        b > a for a, b in zip(in_flight, in_flight[1:]))

    # -- sync: stop-the-world barrier + journal, Fig. 4(a)'s flatline -----
    eng2 = DistributedEngine(prog, g, mesh, tolerance=TOLERANCE)
    state = eng2.init()
    t0 = time.time()
    paused = 0
    step_clock = 0
    for _ in range(MAX_STEPS):
        if float(jnp.max(state.prio)) <= TOLERANCE:
            break
        if int(state.step_index) == SNAPSHOT_AT and paused == 0:
            # barrier: all machines halt, channels flush, full copy
            jax.tree.map(np.asarray, state.vown)
            for _ in range(CAPTURE_STEPS):
                paused += 1
                step_clock += 1
                out.append({
                    "fig": "4", "scheme": "sync", "step": step_clock,
                    "updates": int(np.asarray(state.update_count).sum()),
                    "snapshot_done_frac": 1.0, "paused": 1,
                })
        state = eng2.step(state)
        step_clock += 1
        out.append({
            "fig": "4", "scheme": "sync", "step": step_clock,
            "updates": int(np.asarray(state.update_count).sum()),
            "snapshot_done_frac": 1.0 if paused else 0.0, "paused": 0,
        })
    sync_wall = round(time.time() - t0, 2)

    sync_steps = [r for r in out if r["scheme"] == "sync"]
    flat = [r for r in sync_steps if r["paused"]]
    sync_flatlined = (len(flat) == CAPTURE_STEPS and all(
        a["updates"] == flat[0]["updates"] for a in flat))
    for r in out:
        r["n_machines"] = S
        r["async_no_flatline"] = bool(async_no_flatline)
        r["sync_flatlined"] = bool(sync_flatlined)
        r["wall_s"] = async_wall if r["scheme"] == "async" else sync_wall
    return out
