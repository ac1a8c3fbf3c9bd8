"""Streaming reconvergence vs from-scratch recompute (DESIGN.md §3.11).

The ASYMP claim, measured: after a **10%-growth delta** lands on a
converged engine, incremental reconvergence (``stream/ingest.apply_delta``
re-seeding only the touched scopes) should cost far fewer vertex updates
than recomputing the grown graph from scratch.

Two delta shapes, because honesty requires both:

  ``cluster``  a new power-law *site* (10% of vertices and edges) attaches
               to the web at a few points — teleport-heavy PageRank's
               perturbation stays near the attachment boundary, so the
               reconvergence region is the new cluster plus a ripple and
               incremental wins by roughly |V| / |cluster| (the headline
               ≥ 5x verdict).
  ``uniform``  the same edge budget shuffled uniformly over existing
               vertices — every hub's out-weights renormalize, the
               perturbation is global, and the honest expectation is only
               a modest win (the record carries its own, weaker verdict).

Each record self-checks ``incremental_updates < scratch_updates``; the
cluster records additionally carry ``beats_5x``.  Runs for the local
engine and (when ≥ 2 devices are available) the distributed sweep engine.
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import numpy as np

from repro.apps.pagerank import PageRankProgram
from repro.launch.mesh import auto_mesh
from repro.stream import (SlackConfig, apply_delta_growing,
                          make_dist_engine, make_local_engine, readback,
                          total_updates)
from repro.stream.sources import (pagerank_arrivals,
                                  pagerank_cluster_arrival)

N_LOCAL = 20000     # local cluster scenario (the headline)
N_DIST = 6000       # distributed scenario (shard_map steps are pricier)
N_UNIFORM = 2000    # uniform-arrival contrast scenario
ALPHA = 0.8         # teleport-heavy PageRank: perturbations die in ~2 hops
TOL = 1e-6
MAX_STEPS = 400


def _measure(eng, state, batches, scratch_engine, scratch_state):
    """(incremental updates after the delta, scratch updates, fixed-point
    agreement) — the incremental side converges the prefix first (that is
    the serving state, not part of the bill).  Counted per batch after
    splicing, so a regrow's counter reset can't skew the bill."""
    state, _ = eng.run(state, max_steps=MAX_STEPS)
    incremental = 0
    for b in batches:
        eng, state, _ = apply_delta_growing(eng, state, b)
        before = total_updates(eng, state)
        state, _ = eng.run(state, max_steps=MAX_STEPS)
        incremental += total_updates(eng, state) - before

    s, _ = scratch_engine.run(scratch_state, max_steps=MAX_STEPS)
    scratch = int(np.asarray(
        s.update_count).sum()) if hasattr(s, "update_count") \
        else int(s.total_updates)
    out = np.asarray(readback(eng, state).vertex_data["rank"])
    ref = (scratch_engine.vertex_data(s)["rank"]
           if hasattr(scratch_engine, "vertex_data")
           else np.asarray(s.graph.vertex_data["rank"]))
    err = float(np.abs(out - np.asarray(ref)).max())
    return incremental, scratch, err


def stream_reconvergence() -> List[Dict]:
    """10%-growth delta: incremental reconvergence vs scratch recompute."""
    from repro.core import Engine
    from repro.dist import DistributedEngine

    out: List[Dict] = []

    # ---- local engine, cluster arrival (headline) -----------------------
    t0 = time.time()
    prefix_g, batches, full_g, in_cap = pagerank_cluster_arrival(
        N_LOCAL, growth=0.10, alpha=ALPHA, seed=0)
    n_total = full_g.structure.n_vertices
    prog = PageRankProgram(ALPHA, n_total)
    eng, state = make_local_engine(
        prog, prefix_g, tolerance=TOL,
        slack=SlackConfig(vertex_frac=0.15), in_capacity=in_cap)
    scr = Engine(prog, full_g, tolerance=TOL)
    inc, scratch, err = _measure(eng, state, batches, scr,
                                 scr.init(full_g))
    out.append({
        "engine": "local", "scenario": "cluster", "n_vertices": n_total,
        "growth": 0.10, "incremental_updates": inc,
        "scratch_updates": scratch, "speedup": round(scratch / max(inc, 1),
                                                     2),
        "fixed_point_err": err, "wall_s": round(time.time() - t0, 1),
        "incremental_beats_scratch": bool(inc < scratch),
        "beats_5x": bool(scratch >= 5 * inc),
    })

    # ---- local engine, uniform arrivals (the honest hard case) ----------
    t0 = time.time()
    prefix_g, batches, full_g = pagerank_arrivals(
        power_law_struct(N_UNIFORM), prefix_frac=1 / 1.1, n_batches=1,
        seed=0)
    prog = PageRankProgram(ALPHA, N_UNIFORM)
    eng, state = make_local_engine(
        prog, prefix_g, tolerance=TOL,
        slack=SlackConfig(edge_frac=1.0, edge_min=8))
    scr = Engine(prog, full_g, tolerance=TOL)
    inc, scratch, err = _measure(eng, state, batches, scr,
                                 scr.init(full_g))
    out.append({
        "engine": "local", "scenario": "uniform", "n_vertices": N_UNIFORM,
        "growth": 0.10, "incremental_updates": inc,
        "scratch_updates": scratch,
        "speedup": round(scratch / max(inc, 1), 2),
        "fixed_point_err": err, "wall_s": round(time.time() - t0, 1),
        "incremental_beats_scratch": bool(inc < scratch),
        "beats_5x": bool(scratch >= 5 * inc),
    })

    # ---- distributed sweep engine, cluster arrival ----------------------
    S = jax.device_count()
    if S >= 2:
        t0 = time.time()
        mesh = auto_mesh((S, 1), ("data", "model"))
        prefix_g, batches, full_g, in_cap = pagerank_cluster_arrival(
            N_DIST, growth=0.10, alpha=ALPHA, seed=0)
        n_total = full_g.structure.n_vertices
        prog = PageRankProgram(ALPHA, n_total)
        eng, state = make_dist_engine(
            prog, prefix_g, mesh, tolerance=TOL,
            slack=SlackConfig(vertex_frac=0.15, ghost_slack=256),
            in_capacity=in_cap)
        scr = DistributedEngine(prog, full_g, mesh, tolerance=TOL)
        inc, scratch, err = _measure(eng, state, batches, scr, scr.init())
        out.append({
            "engine": "dist_sweep", "scenario": "cluster",
            "n_vertices": n_total, "growth": 0.10,
            "incremental_updates": inc, "scratch_updates": scratch,
            "speedup": round(scratch / max(inc, 1), 2),
            "fixed_point_err": err, "wall_s": round(time.time() - t0, 1),
            "incremental_beats_scratch": bool(inc < scratch),
            "beats_5x": bool(scratch >= 5 * inc),
        })

    for r in out:
        assert r["fixed_point_err"] <= 1e-4, r
        assert r["incremental_beats_scratch"], r
    assert any(r["beats_5x"] for r in out
               if r["scenario"] == "cluster"), out
    return out


def power_law_struct(n):
    from repro.graphs.generators import power_law_graph
    return power_law_graph(n, avg_degree=8, seed=0)
