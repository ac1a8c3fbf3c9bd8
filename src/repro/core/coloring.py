"""Graph coloring for the chromatic engine (paper Sec. 4.2.1).

Greedy (largest-degree-first) proper coloring; distance-2 coloring for the
full consistency model; bipartite detection (the paper notes many MLDM
graphs — ALS, CoEM — are two-colorable "for free").  Host-side numpy: the
coloring is computed once at ingress.
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from repro.core.consistency import Consistency
from repro.core.graph import GraphStructure
from repro.obs.timeline import span


def _csr(structure: GraphStructure) -> Tuple[np.ndarray, np.ndarray]:
    """Receiver-sorted CSR view: (offsets[N+1], senders-as-neighbors[E])."""
    offsets = structure.receiver_offsets()
    return offsets, structure.senders


def greedy_coloring(
    structure: GraphStructure, order: Optional[np.ndarray] = None
) -> np.ndarray:
    """Greedy proper vertex coloring, largest-degree-first by default.

    Works on the symmetrized adjacency (a proper coloring must separate both
    edge directions).  Returns int32 colors [N].

    Sequential greedy gives each vertex, in ``order``, the smallest color
    its already-colored neighbors do not use.  It is computed here in
    rounds (Jones–Plassmann with the order as priority): a vertex is
    colored once every neighbor earlier in the order is, and the earlier
    neighbors are exactly the colored ones sequential greedy would see —
    the same coloring, with numpy work per round instead of Python work
    per vertex.
    """
    n = structure.n_vertices
    deg = structure.in_degree + structure.out_degree
    if order is None:
        order = np.argsort(-deg, kind="stable")
    rank = np.empty(n, np.int64)
    rank[np.asarray(order)] = np.arange(n)

    # symmetrized adjacency as CSR: v's neighbors are nbr[offsets[v]:...]
    s = np.concatenate([structure.senders, structure.receivers])
    r = np.concatenate([structure.receivers, structure.senders])
    sort = np.argsort(r, kind="stable")
    nbr, r = s[sort], r[sort]
    offsets = np.concatenate([[0], np.cumsum(np.bincount(r, minlength=n))])
    earlier = rank[nbr] < rank[r]
    waiting = np.bincount(r[earlier], minlength=n)   # uncolored earlier nbrs

    colors = np.full(n, -1, dtype=np.int32)
    ready = np.nonzero(waiting == 0)[0]
    while ready.size:
        lens = offsets[ready + 1] - offsets[ready]
        edge = (np.repeat(offsets[ready] - np.cumsum(lens) + lens, lens)
                + np.arange(lens.sum()))
        owner = np.repeat(np.arange(ready.size), lens)
        # smallest color missing among each ready vertex's earlier nbrs
        e = earlier[edge]
        pairs = np.unique(owner[e].astype(np.int64) * (n + 1)
                          + colors[nbr[edge[e]]])
        who, col = pairs // (n + 1), pairs % (n + 1)
        first = np.searchsorted(who, np.arange(ready.size))
        slot = np.arange(who.size) - first[who]
        gap = np.full(ready.size, np.iinfo(np.int64).max)
        np.minimum.at(gap, who[col != slot], slot[col != slot])
        used = np.bincount(who, minlength=ready.size)
        colors[ready] = np.minimum(gap, used)
        # later neighbors lose one waiting neighbor per edge
        later = nbr[edge[~e]]
        later = later[rank[later] > rank[r[edge[~e]]]]
        waiting -= np.bincount(later, minlength=n)
        cand = np.unique(later)
        ready = cand[(waiting[cand] == 0) & (colors[cand] < 0)]
    return colors


def distance2_coloring(structure: GraphStructure) -> np.ndarray:
    """Greedy coloring of the square graph G² (full consistency model)."""
    n = structure.n_vertices
    s = np.concatenate([structure.senders, structure.receivers])
    r = np.concatenate([structure.receivers, structure.senders])
    sort = np.argsort(r, kind="stable")
    s, r = s[sort], r[sort]
    offsets = np.concatenate([[0], np.cumsum(np.bincount(r, minlength=n))])

    deg = structure.in_degree + structure.out_degree
    order = np.argsort(-deg, kind="stable")
    colors = np.full(n, -1, dtype=np.int32)
    for v in order:
        n1 = s[offsets[v]:offsets[v + 1]]
        # distance-2 neighborhood: neighbors + neighbors-of-neighbors
        chunks = [n1] + [s[offsets[u]:offsets[u + 1]] for u in n1]
        nbrs = np.concatenate(chunks) if chunks else np.zeros(0, np.int32)
        nbr_colors = colors[nbrs]
        nbr_colors = nbr_colors[nbr_colors >= 0]
        if nbr_colors.size == 0:
            colors[v] = 0
            continue
        used = np.zeros(nbr_colors.max() + 2, dtype=bool)
        used[nbr_colors] = True
        colors[v] = int(np.argmin(used))
    return colors


def bipartite_coloring(structure: GraphStructure) -> Optional[np.ndarray]:
    """2-coloring by breadth-first search from each component's smallest
    vertex, which takes color 0; None if the graph is not bipartite.

    Level-synchronous and vectorised: every vertex starts as its own root
    with key ``2·v`` (root ``v``, parity 0), and each round every vertex
    takes the smallest of its key and its neighbors' keys with the parity
    flipped.  After as many rounds as the farthest vertex is from its
    component's smallest vertex, each key holds that root and the parity
    of a path from it: the color.  A key is always the root and parity of
    a real walk, so an edge whose two ends hold the same key closes a walk
    of odd length: the graph has an odd cycle, and the search stops there.
    """
    with span("graphlab.bipartite"):
        n = structure.n_vertices
        s, r = structure.senders, structure.receivers    # receiver-sorted
        if not structure.is_symmetric():
            s = np.concatenate([structure.senders, structure.receivers])
            r = np.concatenate([structure.receivers, structure.senders])
            sort = np.argsort(r, kind="stable")
            s, r = s[sort], r[sort]
        if s.size == 0:
            return np.zeros(n, np.int32)
        deg = np.bincount(r, minlength=n)
        has = deg > 0
        starts = (np.cumsum(deg) - deg)[has]
        key = 2 * np.arange(n, dtype=np.int64)
        while True:
            ks = key[s]
            if (ks == key[r]).any():
                return None
            nxt = key.copy()
            nxt[has] = np.minimum(key[has],
                                  np.minimum.reduceat(ks ^ 1, starts))
            if (nxt == key).all():
                return (key & 1).astype(np.int32)
            key = nxt


def coloring_for(
    structure: GraphStructure, consistency: Consistency
) -> np.ndarray:
    """Paper Sec. 4.2.1: pick the coloring that realizes a consistency model."""
    if consistency == Consistency.VERTEX:
        return np.zeros(structure.n_vertices, dtype=np.int32)
    if consistency == Consistency.EDGE:
        bip = bipartite_coloring(structure)
        return bip if bip is not None else greedy_coloring(structure)
    if consistency == Consistency.FULL:
        return distance2_coloring(structure)
    raise ValueError(consistency)


def verify_coloring(
    structure: GraphStructure, colors: np.ndarray, radius: int = 1
) -> bool:
    """Checks no two vertices within ``radius`` share a color.

    radius 0 (vertex consistency) imposes nothing; 1 = proper coloring;
    2 additionally separates two-hop pairs (full consistency)."""
    if radius < 1:
        return True
    s, r = structure.senders, structure.receivers
    mask = s != r
    if (colors[s[mask]] == colors[r[mask]]).any():
        return False
    if radius >= 2:
        n = structure.n_vertices
        # two-hop conflicts: for each vertex, all in-neighbors must have
        # pairwise distinct colors (they are distance 2 through it).
        offsets = structure.receiver_offsets()
        for v in range(n):
            nb = np.unique(s[offsets[v]:offsets[v + 1]])  # multigraph-safe
            nb = nb[nb != v]
            c = np.sort(colors[nb])
            if c.size > 1 and (c[1:] == c[:-1]).any():
                return False
    return True
