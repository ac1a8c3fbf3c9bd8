"""The generator: the same seed gives the same graph, the sizes are as
configured, the pairs are simple (no self-loop, no repeated pair), so that
both directions of each make the symmetric graph the engines need, and
every seed gives the program the same coloring and the same compiled step
under other labels."""
import re

import numpy as np

from bench import spec

KRON = {"scale": 12, "edge_factor": 16, "a": 0.57, "b": 0.19, "c": 0.19,
        "graph_seed": 5}


def test_kronecker_is_seeded_and_simple():
    gen = spec.module("graphs", "kronecker")
    a, b = gen.generate(KRON, 7), gen.generate(KRON, 7)
    c = gen.generate(KRON, 2**31 + 5)
    assert a["n"] == 4096
    np.testing.assert_array_equal(a["u"], b["u"])
    np.testing.assert_array_equal(a["v"], b["v"])
    assert not np.array_equal(a["u"], c["u"])
    u, v = a["u"].astype(np.int64), a["v"].astype(np.int64)
    assert (u < v).all() and v.max() < a["n"]          # no self-loop
    assert np.unique(u * a["n"] + v).size == u.size      # no repeated pair
    # R-MAT at edge factor 16 keeps most of its 16n draws after dedup
    assert 0.6 * 16 * 4096 < u.size <= 16 * 4096


def test_kronecker_degrees_are_skewed():
    gen = spec.module("graphs", "kronecker")
    g = gen.generate(KRON, 3)
    deg = np.bincount(np.concatenate([g["u"], g["v"]]), minlength=g["n"])
    assert deg.max() > 20 * deg.mean()
    assert (deg == 0).mean() > 0.1                       # isolated vertices


def test_seeds_relabel_one_graph():
    """Two seeds: the same graph under a relabelling, and another graph
    seed gives another graph."""
    gen = spec.module("graphs", "kronecker")
    a, b = gen.generate(KRON, 1), gen.generate(KRON, 2)
    n, u, v = gen.kronecker_pairs(KRON, KRON["graph_seed"])
    pi = gen.keep_greedy_order(n, u, v, 2)
    key = np.sort(np.minimum(pi[u], pi[v]) * n + np.maximum(pi[u], pi[v]))
    np.testing.assert_array_equal(
        key, np.sort(b["u"].astype(np.int64) * n + b["v"]))
    assert a["u"].size == b["u"].size
    other = gen.generate(dict(KRON, graph_seed=6), 1)
    assert other["u"].size != a["u"].size


def test_every_seed_gives_the_same_program():
    """The program's greedy coloring follows the relabelling, so its color
    classes, its per-color edge sets and the lowered step are the same for
    every seed: a checkout compiles the cell's program once."""
    from repro.apps.pagerank import PageRankProgram, make_pagerank_graph
    from repro.core import ChromaticEngine
    from repro.core.coloring import greedy_coloring
    from repro.core.graph import GraphStructure

    gen = spec.module("graphs", "kronecker")
    params = dict(KRON, scale=10)
    n, u, v = gen.kronecker_pairs(params, params["graph_seed"])
    base, _ = GraphStructure.undirected(u.astype(np.int32),
                                        v.astype(np.int32), n)
    colors = greedy_coloring(base)
    texts = []
    for seed in (1, 2**31 + 9):
        pi = gen.keep_greedy_order(n, u, v, seed)
        g = gen.generate(params, seed)
        st, _ = GraphStructure.undirected(g["u"], g["v"], n)
        relabelled = np.empty(n, np.int32)
        relabelled[pi] = colors
        np.testing.assert_array_equal(greedy_coloring(st), relabelled)
        graph = make_pagerank_graph(st)
        eng = ChromaticEngine(PageRankProgram(0.15, n), graph,
                              tolerance=1e-4 / n)
        s = eng.init(graph)
        text = eng._jit_step.lower(s, eng._tables, eng._consts).as_text()
        texts.append(re.sub(r"loc\(.*?\)", "", text))
    assert texts[0] == texts[1]


def test_adjacency_is_symmetric():
    inst = spec.module("graphs", "kronecker").generate(KRON, 1)
    n, src, dst = spec.module("apps", "pagerank").adjacency(inst)
    fwd = np.sort(src.astype(np.int64) * n + dst)
    back = np.sort(dst.astype(np.int64) * n + src)
    np.testing.assert_array_equal(fwd, back)
