"""How the benchmark drives the program for PageRank: the generated
undirected pairs become the program's symmetric structure and its
out-degree-normalised PageRank graph; the answer is the rank vector."""
from __future__ import annotations

import numpy as np


def build(inst, cfg):
    """``(program, data graph, engine tolerance)``."""
    from repro.apps.pagerank import PageRankProgram, make_pagerank_graph
    from repro.core.graph import GraphStructure

    n = inst["n"]
    st, _ = GraphStructure.undirected(inst["u"], inst["v"], n)
    p = cfg
    return (PageRankProgram(p["alpha"], n), make_pagerank_graph(st),
            p["tolerance_per_vertex"] / n)


def adjacency(inst):
    """``(n, src, dst)``: both directions of every pair."""
    u, v = inst["u"], inst["v"]
    return inst["n"], np.concatenate([u, v]), np.concatenate([v, u])


def answer(state):
    return {"rank": np.asarray(state.graph.vertex_data["rank"])}
