"""Netflix ALS (paper Sec. 5.1): serializable vs racing, dynamic vs BSP.

    PYTHONPATH=src python examples/netflix_als.py

Reproduces Fig. 1(d) (non-serializable dynamic ALS is unstable) and
Fig. 9(a) (dynamic scheduling reaches the same test error in roughly half
the updates of a static BSP schedule).  Last, ALS-WR (``λ·n_v·I``, each
vertex's regulariser weighted by its number of training ratings) on the
chromatic engine's fused GAS path, as the benchmark's Netflix cell runs it.
"""
import numpy as np

from repro.apps.als import (ALSProgram, als_rmse, make_als_graph,
                            with_rating_counts)
from repro.core import BSPEngine, ChromaticEngine, DynamicEngine

D = 8
TOL = 5e-3


def trace_run(engine, graph, label, max_steps=60):
    state = engine.init(graph)
    state, trace = engine.run(
        state, max_steps=max_steps,
        trace_fn=lambda s: {"test_rmse": als_rmse(s.graph, train=False)})
    ups = [t["updates"] for t in trace]
    rmse = [t["test_rmse"] for t in trace]
    print(f"{label:32s} updates={ups[-1]:7d} test RMSE={rmse[-1]:.4f} "
          f"(min {min(rmse):.4f})")
    return ups, rmse


if __name__ == "__main__":
    graph, info = make_als_graph(n_users=300, n_movies=200, n_ratings=12000,
                                 d=D, seed=0, noise=0.05)
    print(f"bipartite ratings graph: {graph.n_vertices} vertices, "
          f"{graph.n_edges // 2} ratings, d={D}")
    prog = ALSProgram(d=D, reg=0.05)

    trace_run(BSPEngine(prog, graph, tolerance=TOL), graph,
              "BSP (static sweeps)")
    trace_run(ChromaticEngine(prog, graph, tolerance=TOL), graph,
              "Chromatic (2-color, serializable)")
    trace_run(DynamicEngine(prog, graph, pipeline_length=128,
                            serializable=True, tolerance=TOL), graph,
              "Dynamic serializable")
    _, rmse_racing = trace_run(
        DynamicEngine(prog, graph, pipeline_length=128, serializable=False,
                      tolerance=TOL), graph,
        "Dynamic RACING (Fig. 1(d))", max_steps=60)
    swings = np.abs(np.diff(rmse_racing)).max() if len(rmse_racing) > 1 else 0
    print(f"racing max RMSE swing between steps: {swings:.4f} "
          "(instability signature)")

    wr_graph = with_rating_counts(graph)
    # λ·n_v with n_v ~ 30 training ratings: about the 0.05 of reg·I above
    wr = ChromaticEngine(ALSProgram(d=D, reg=0.002, weighted=True),
                         wr_graph, tolerance=TOL)
    assert wr.use_fused
    trace_run(wr, wr_graph, "Chromatic ALS-WR (fused GAS path)")
