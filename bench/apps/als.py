"""How the benchmark drives the program for ALS-WR: the generated rating
pairs become the program's ALS-WR data graph (``ratings_graph``: users
first, then movies; each vertex's count of training ratings, ALS-WR's
``n_v``, as a vertex field); the answer is the factor table."""
from __future__ import annotations

import numpy as np


def build(inst, cfg):
    """``(program, data graph, engine tolerance)``."""
    from repro.apps.als import ALSProgram, ratings_graph

    graph = ratings_graph(inst["users"], inst["movies"], inst["n_users"],
                          inst["n_movies"], inst["rating"], inst["train"],
                          inst["factor0"])
    return (ALSProgram(cfg["d"], cfg["lambda"], weighted=True), graph,
            cfg["tolerance"])


def adjacency(inst):
    """``(n, src, dst)``: both directions of every pair."""
    nu = inst["n_users"]
    u = inst["users"].astype(np.int64)
    m = inst["movies"].astype(np.int64) + nu
    return nu + inst["n_movies"], np.concatenate([u, m]), \
        np.concatenate([m, u])


def answer(state):
    return {"factor": np.asarray(state.graph.vertex_data["factor"])}
