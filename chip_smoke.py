#!/usr/bin/env python3
"""Chip smoke test: the graph-parallel engines' main path on a TPU.

    python chip_smoke.py              # one chip: ChromaticEngine PageRank
    python chip_smoke.py --chips 4    # four chips: DistributedEngine PageRank

PageRank (alpha = 0.15, the paper's and LDBC Graphalytics' first algorithm)
on ``power_law_graph(2**scale, avg_degree=16, alpha=2.1)``, generated from
``--seed``: Graph500 scale 21 at edge factor 16 by default, about 2.1M
vertices and 29M directed edges.  The engine runs through its public API
(``init``, ``compile``, ``run``) on the fused GAS path (``use_fused`` left at
its default) until its scheduler empties.  The script then checks:

  - the compiled step contains the Pallas kernels (``tpu_custom_call``), so
    a fallback to the jnp oracle or to interpret mode fails the run;
  - the ranks lie within ``L1_BOUND`` (L1 distance) of a float64 NumPy power
    iteration of the same PageRank, which shares no code with the engines;
  - with ``--chips 4``, every device holds a shard.

The engine tolerance is ``TOL_PER_VERTEX / n``: ranks average 1/n, so a
fixed tolerance would mean something else at every graph size.

Progress lines go to stdout; the last line is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Without a TPU
the script exits with code 2 before it builds anything; any failed check or
phase exits non-zero without that line.  Everything runs in this one
process, which holds the chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ALPHA = 0.15
AVG_DEGREE = 16
DEGREE_EXPONENT = 2.1
TOL_PER_VERTEX = 1e-4
L1_BOUND = 1e-3
MAX_SWEEPS = 300


def log(phase: str, **fields) -> None:
    print(phase, " ".join(f"{k}={v}" for k, v in fields.items()), flush=True)


def reference_pagerank(structure, alpha: float) -> np.ndarray:
    """Float64 power iteration of R = alpha/n + (1 - alpha) Σ_{u→v}
    R(u)/outdeg(u), with ``np.bincount`` — no engine code."""
    n = structure.n_vertices
    send, recv = structure.senders, structure.receivers
    inv_deg = 1.0 / np.maximum(np.bincount(send, minlength=n), 1)
    r = np.full(n, 1.0 / n)
    for _ in range(1000):
        nxt = alpha / n + (1.0 - alpha) * np.bincount(
            recv, weights=(r * inv_deg)[send], minlength=n)
        delta = np.abs(nxt - r).sum()
        r = nxt
        if delta < 1e-12:
            break
    return r


def device_bytes(devices) -> list:
    return [int(d.memory_stats().get("bytes_in_use", 0)) for d in devices]


def check_kernels(compiled, what: str) -> None:
    n_kernels = compiled.as_text().count("tpu_custom_call")
    log("kernels", step=what, tpu_custom_call=n_kernels)
    if n_kernels == 0:
        raise SystemExit(f"{what}: the compiled step has no Pallas kernel")


def check_ranks(ranks: np.ndarray, ref: np.ndarray) -> None:
    l1 = float(np.abs(ranks.astype(np.float64) - ref).sum())
    log("accuracy", l1=l1, bound=L1_BOUND, rank_sum=float(ranks.sum()),
        ref_sum=float(ref.sum()))
    if not np.isfinite(ranks).all() or not l1 <= L1_BOUND:
        raise SystemExit(f"ranks off the float64 reference: L1 {l1}")


def run_chromatic(graph, program, tol: float):
    """One chip: ChromaticEngine over per-color edge ranges."""
    import jax

    from repro.core import ChromaticEngine

    t0 = time.time()
    engine = ChromaticEngine(program, graph, tolerance=tol)
    state = engine.init(graph)
    log("engine", engine="ChromaticEngine", fused=engine.use_fused,
        colors=engine.num_colors, setup_s=round(time.time() - t0, 2))
    if not engine.use_fused:
        raise SystemExit("PageRank did not take the fused GAS path")
    gas_bytes = sum(x.nbytes for x in jax.tree.leaves(engine._consts["gas"]))
    state_bytes = sum(x.nbytes for x in jax.tree.leaves(state))
    log("device_bytes", edge_sets=gas_bytes, state=state_bytes,
        in_use=device_bytes(jax.devices()[:1]))

    t0 = time.time()
    compiled = engine.compile(state)
    log("compile", seconds=round(time.time() - t0, 2))
    check_kernels(compiled, "ChromaticEngine")

    t0 = time.time()
    state, _ = engine.run(state, max_steps=MAX_SWEEPS)
    ranks = np.asarray(jax.block_until_ready(
        state.graph.vertex_data["rank"]))
    seconds = time.time() - t0
    done = bool(engine.scheduler.done(state.sched, state.prio))
    log("run", sweeps=int(state.step_index), updates=int(state.total_updates),
        edges_touched=int(state.edges_touched), seconds=round(seconds, 2),
        converged=done)
    if not done:
        raise SystemExit(f"scheduler not empty after {MAX_SWEEPS} sweeps")
    return ranks


def run_distributed(graph, program, tol: float, devices):
    """Four chips: DistributedEngine (sweep schedule, f32 wire) on a
    (4, 1) ("data", "model") mesh."""
    import jax

    from repro.dist import DistributedEngine
    from repro.launch.mesh import auto_mesh

    mesh = auto_mesh((len(devices), 1), ("data", "model"), devices=devices)
    t0 = time.time()
    engine = DistributedEngine(program, graph, mesh, tolerance=tol)
    state = engine.init(graph)
    log("engine", engine="DistributedEngine", colors=engine.num_colors,
        machines=len(devices), setup_s=round(time.time() - t0, 2))
    per_device = device_bytes(devices)
    log("device_bytes", in_use=per_device)
    if min(per_device) == 0:
        raise SystemExit(f"a device holds no shard: {per_device}")

    t0 = time.time()
    compiled = engine.compile(state)
    log("compile", seconds=round(time.time() - t0, 2))
    check_kernels(compiled, "DistributedEngine")

    t0 = time.time()
    state, rows = engine.run(state, max_steps=MAX_SWEEPS)
    ranks = np.asarray(jax.block_until_ready(
        engine.vertex_data(state)["rank"]))
    seconds = time.time() - t0
    done = float(np.asarray(state.prio).max()) <= tol
    log("run", sweeps=int(state.step_index),
        ghost_rows=engine.ghost_rows_sent(state), seconds=round(seconds, 2),
        converged=done)
    log("device_bytes", in_use=device_bytes(devices))
    if not done:
        raise SystemExit(f"scheduler not empty after {MAX_SWEEPS} sweeps")
    return ranks


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--scale", type=int, default=21,
                    help="log2 of the vertex count (Graph500 scale)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX found {devices[0].platform} devices",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} TPU devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 2
    devices = devices[:args.chips]

    from repro.apps.pagerank import PageRankProgram, make_pagerank_graph
    from repro.graphs.generators import power_law_graph
    from repro.launch.compile_cache import use_compile_cache

    log("cache", dir=use_compile_cache())
    t0 = time.time()
    structure = power_law_graph(2 ** args.scale, avg_degree=AVG_DEGREE,
                                alpha=DEGREE_EXPONENT, seed=args.seed)
    n = structure.n_vertices
    log("graph", scale=args.scale, vertices=n, edges=structure.n_edges,
        max_in_degree=int(structure.in_degree.max()),
        seconds=round(time.time() - t0, 2))
    # the host reference runs beside the engine's set-up and compile
    ref = {}
    t_ref = time.time()
    worker = threading.Thread(target=lambda: ref.update(
        ranks=reference_pagerank(structure, ALPHA),
        seconds=round(time.time() - t_ref, 2)))
    worker.start()

    tol = TOL_PER_VERTEX / n
    log("tolerance", value=tol, per_vertex=TOL_PER_VERTEX)
    graph = make_pagerank_graph(structure)
    program = PageRankProgram(ALPHA, n)
    if args.chips == 1:
        ranks = run_chromatic(graph, program, tol)
    else:
        ranks = run_distributed(graph, program, tol, devices)
    worker.join()
    log("reference", seconds=ref["seconds"])
    check_ranks(ranks, ref["ranks"])
    stats = devices[0].memory_stats()
    log("memory", peak_bytes_in_use=stats.get("peak_bytes_in_use"),
        bytes_limit=stats.get("bytes_limit"))

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
