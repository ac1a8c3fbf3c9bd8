"""The vectorised bipartite check (``core/coloring.py:bipartite_coloring``)
gives the colors of the per-vertex search it replaced, kept here as the
oracle: on random bipartite graphs, forests of components (isolated
vertices among them), and graphs that are not bipartite (an odd cycle, a
self-loop, a Kronecker graph), where both give None.  Each call is counted
under the host span ``graphlab.bipartite``."""
from __future__ import annotations

import numpy as np
import pytest

from repro.core.coloring import bipartite_coloring, coloring_for
from repro.core.consistency import Consistency
from repro.core.graph import GraphStructure
from repro.obs import reset_span_totals, span_totals


def search_oracle(structure):
    """The search the vectorised check replaced: a root per component in
    index order, color 0, a stack of vertices, one neighbor at a time."""
    n = structure.n_vertices
    s = np.concatenate([structure.senders, structure.receivers])
    r = np.concatenate([structure.receivers, structure.senders])
    sort = np.argsort(r, kind="stable")
    s, r = s[sort], r[sort]
    offsets = np.concatenate([[0], np.cumsum(np.bincount(r, minlength=n))])
    colors = np.full(n, -1, dtype=np.int32)
    for root in range(n):
        if colors[root] >= 0:
            continue
        colors[root] = 0
        stack = [root]
        while stack:
            v = stack.pop()
            for u in s[offsets[v]:offsets[v + 1]]:
                if colors[u] < 0:
                    colors[u] = 1 - colors[v]
                    stack.append(int(u))
                elif colors[u] == colors[v]:
                    return None
    return colors


def random_bipartite(seed):
    rng = np.random.default_rng(seed)
    left, right = rng.integers(1, 60, 2)
    m = int(rng.integers(0, 4 * (left + right)))
    u = rng.integers(0, left, m)
    v = rng.integers(0, right, m) + left
    # labels shuffled, so the sides interleave and the smallest vertex of
    # a component may lie on either side
    pi = rng.permutation(left + right)
    return GraphStructure.undirected(pi[u], pi[v], left + right)[0]


def forest(seed):
    """Random trees of 1 to 12 vertices side by side, under shuffled
    labels: many components, each rooted at its smallest vertex."""
    rng = np.random.default_rng(seed)
    u, v, n = [], [], 0
    for _ in range(int(rng.integers(5, 40))):
        size = int(rng.integers(1, 13))
        for k in range(1, size):
            u.append(n + int(rng.integers(0, k)))
            v.append(n + k)
        n += size
    pi = rng.permutation(n)
    return GraphStructure.undirected(pi[np.array(u, np.int64)],
                                     pi[np.array(v, np.int64)], n)[0]


def odd_cycle(seed):
    """A bipartite graph with a triangle closed: two neighbors of one
    vertex joined, or a triangle of three new vertices where no vertex
    has two neighbors."""
    st = random_bipartite(seed)
    n, u, v = st.n_vertices, list(st.senders), list(st.receivers)
    for x in range(n):
        nb = np.unique(st.senders[st.receivers == x])
        if nb.size >= 2:
            u.append(nb[0])
            v.append(nb[1])
            break
    else:
        u += [n, n + 1, n + 2]
        v += [n + 1, n + 2, n]
        n += 3
    return GraphStructure.undirected(np.array(u), np.array(v), n)[0]


def self_loop(seed):
    st = random_bipartite(seed)
    u = np.concatenate([st.senders, [st.n_vertices - 1]])
    v = np.concatenate([st.receivers, [st.n_vertices - 1]])
    return GraphStructure.from_edges(u, v, st.n_vertices)[0]


def directed(seed):
    """One direction of each pair only: the check symmetrises it."""
    st = random_bipartite(seed)
    keep = st.senders < st.receivers
    return GraphStructure.from_edges(st.senders[keep], st.receivers[keep],
                                     st.n_vertices)[0]


def kronecker(seed):
    """A Graph500 Kronecker (R-MAT 0.57/0.19/0.19) graph at scale 9, edge
    factor 8, symmetrised: skewed, with isolated vertices and triangles."""
    rng = np.random.default_rng(seed)
    scale, m = 9, 8 << 9
    u = np.zeros(m, np.int64)
    v = np.zeros(m, np.int64)
    for bit in range(scale):
        down = rng.random(m) > 0.76
        right = rng.random(m) > np.where(down, 0.19 / 0.24, 0.57 / 0.76)
        u |= down.astype(np.int64) << bit
        v |= right.astype(np.int64) << bit
    keep = u != v
    return GraphStructure.undirected(u[keep], v[keep], 1 << scale)[0]


CASES = {"bipartite": random_bipartite, "forest": forest,
         "odd_cycle": odd_cycle, "self_loop": self_loop,
         "directed": directed, "kronecker": kronecker}


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("case", sorted(CASES))
def test_same_colors_as_the_search(case, seed):
    st = CASES[case](seed)
    want = search_oracle(st)
    got = bipartite_coloring(st)
    if case in ("odd_cycle", "self_loop", "kronecker"):
        assert want is None and got is None
    else:
        assert want is not None
        np.testing.assert_array_equal(got, want)
        assert got.dtype == np.int32


def test_empty_and_edgeless_graphs():
    st = GraphStructure.from_edges(np.zeros(0, np.int32),
                                   np.zeros(0, np.int32), 5)[0]
    np.testing.assert_array_equal(bipartite_coloring(st), np.zeros(5))
    assert bipartite_coloring(GraphStructure.from_edges(
        np.zeros(0, np.int32), np.zeros(0, np.int32), 0)[0]).size == 0


def test_counted_under_its_span():
    reset_span_totals()
    coloring_for(random_bipartite(1), Consistency.EDGE)
    coloring_for(kronecker(1), Consistency.EDGE)
    total = span_totals()["graphlab.bipartite"]
    assert total.count == 2 and total.seconds > 0
