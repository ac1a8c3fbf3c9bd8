"""Graph500 Kronecker (R-MAT) graph, symmetrised as LDBC Graphalytics does.

Graph500 specification, section 3 ("Graph Generation"): ``edge_factor``
x 2**scale edges, each placed by ``scale`` independent quadrant choices
with probabilities A, B, C and D = 1 - A - B - C, then the vertex labels
are permuted at random.  LDBC Graphalytics' undirected Graph500 graphs
drop self-loops and duplicate pairs.

The edges come from the configuration's ``graph_seed``, so every run holds
the same graph: the same degrees, colors and per-color edge counts, hence
the same compiled program and the same amount of work.  The run's seed
draws the vertex labelling, among the labellings that keep each degree
class in its order (``keep_greedy_order``): the program colors greedily,
largest degree first and ties by label, so each seed gets the same
coloring, under other labels, and its edges in another order.

``generate`` returns the undirected pairs (``u < v``), each once; the
harness builds both directed edges from them.  The specification's final
shuffle of the edge list is left out: every consumer sorts the edges.
"""
from __future__ import annotations

import numpy as np


def kronecker_pairs(params: dict, seed: int):
    """``(n, u, v)``: the symmetrised pairs.  Reads ``scale``,
    ``edge_factor``, ``a``, ``b``, ``c``.  Vectorised over all edges, one
    bit level at a time, as the specification's own Octave reference is
    written."""
    scale = int(params["scale"])
    n = 1 << scale
    m = int(params["edge_factor"]) * n
    a, b, c = float(params["a"]), float(params["b"]), float(params["c"])
    rng = np.random.default_rng(seed)
    ab = np.float32(a + b)
    c_norm = np.float32(c / (1.0 - (a + b)))
    a_norm = np.float32(a / (a + b))
    ii = np.zeros(m, np.int32)
    jj = np.zeros(m, np.int32)
    for bit in range(scale):
        ii_bit = rng.random(m, dtype=np.float32) > ab
        r = rng.random(m, dtype=np.float32)
        jj_bit = np.where(ii_bit, r > c_norm, r > a_norm)
        ii |= ii_bit.astype(np.int32) << bit
        jj |= jj_bit.astype(np.int32) << bit
    perm = rng.permutation(n).astype(np.int32)
    ii, jj = perm[ii], perm[jj]
    keep = ii != jj
    lo = np.minimum(ii[keep], jj[keep]).astype(np.int64)
    hi = np.maximum(ii[keep], jj[keep])
    key = np.unique(lo * n + hi)
    return n, key // n, key % n


def keep_greedy_order(n: int, u, v, seed: int) -> np.ndarray:
    """A random labelling ``new = pi[old]`` that keeps, within each degree
    class, the order of the old labels: largest-degree-first with ties by
    label then visits the same vertices in the same order."""
    deg = np.bincount(u, minlength=n) + np.bincount(v, minlength=n)
    order = np.argsort(-deg, kind="stable")
    cls = np.concatenate([[0], np.cumsum(np.diff(deg[order]) != 0)])
    labels = np.random.default_rng(seed).permutation(n)
    pi = np.empty(n, np.int64)
    pi[order] = labels[np.lexsort((labels, cls))]
    return pi


def generate(params: dict, seed: int) -> dict:
    n, u, v = kronecker_pairs(params, int(params["graph_seed"]))
    pi = keep_greedy_order(n, u, v, seed)
    u, v = pi[u], pi[v]
    return {"n": n, "u": np.minimum(u, v).astype(np.int32),
            "v": np.maximum(u, v).astype(np.int32)}
