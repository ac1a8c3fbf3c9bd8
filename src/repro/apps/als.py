"""Alternating Least Squares collaborative filtering (paper Sec. 5.1).

Netflix: sparse ratings matrix R ~ U V^T over the bipartite user-movie
graph.  Vertex data: the d-dim latent factor.  Edge data: the rating (and a
train/test flag for the Fig. 9(a) test-error curves).  The update recomputes
the least-squares solution for one vertex from its neighbors' factors:

    x_v = (sum_u x_u x_u^T + lambda I)^{-1} (sum_u r_uv x_u)

Because the graph is bipartite (2-colorable) and edge consistency suffices,
the chromatic engine runs it exactly as the paper does.  The *dynamic* ALS
of Fig. 1(d)/9(a) schedules a vertex's neighbors only on significant factor
change — and is unstable when allowed to race (run with
``DynamicEngine(serializable=False)``; simultaneous updates of adjacent
user/movie vertices oscillate).

The update complexity O(d^3 + deg·d^2) is the paper's computation-
communication knob (Fig. 6(c)): sweep ``d``.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.consistency import Consistency
from repro.core.graph import DataGraph, GraphStructure
from repro.core.update import ApplyOut, EdgeCtx, FusedGather, VertexProgram
from repro.graphs.generators import bipartite_graph


class ALSProgram(VertexProgram):
    combiner = "sum"
    consistency = Consistency.EDGE
    schedule_neighbors = True

    def __init__(self, d: int, reg: float = 0.05):
        self.d = int(d)
        self.reg = float(reg)

    def gather(self, ctx: EdgeCtx):
        x = ctx.src["factor"]                      # [E, d]
        w = ctx.edata["train"][:, None]            # test edges excluded
        return {
            "xxt": w[..., None] * x[:, :, None] * x[:, None, :],  # [E, d, d]
            "rx": w * ctx.edata["rating"][:, None] * x,           # [E, d]
        }

    def fused_gather(self):
        # Both leaves are weighted-src-sums of *derived* per-vertex features:
        # the x xᵀ outer product is an [N, d, d] vertex table (cheap — N ≪ E),
        # so the [E, d, d] per-edge messages never materialize (DESIGN §3.5).
        return {
            "xxt": FusedGather(
                "weighted_src_sum",
                feature=lambda v: v["factor"][:, :, None]
                * v["factor"][:, None, :],
                weight=lambda e: e["train"]),
            "rx": FusedGather(
                "weighted_src_sum",
                feature=lambda v: v["factor"],
                weight=lambda e: e["train"] * e["rating"]),
        }

    def apply(self, vertex_data, acc, glob=None) -> ApplyOut:
        d = self.d
        A = acc["xxt"] + self.reg * jnp.eye(d, dtype=acc["xxt"].dtype)
        b = acc["rx"]
        new = jnp.linalg.solve(A, b[..., None])[..., 0]
        residual = jnp.sum(jnp.abs(new - vertex_data["factor"]), axis=-1)
        return ApplyOut({"factor": new}, residual)


def make_als_graph(
    n_users: int,
    n_movies: int,
    n_ratings: int,
    d: int,
    seed: int = 0,
    test_frac: float = 0.2,
    noise: float = 0.1,
    dtype=jnp.float32,
) -> Tuple[DataGraph, dict]:
    """Synthetic low-rank ratings with planted factors (so test RMSE is a
    real generalization signal, not memorization)."""
    rng = np.random.default_rng(seed)
    st, perm = bipartite_graph(n_users, n_movies, n_ratings, seed=seed)

    u_true = rng.normal(0, 1.0 / np.sqrt(d), size=(n_users, d))
    m_true = rng.normal(0, 1.0 / np.sqrt(d), size=(n_movies, d))

    # edge (s -> r): rating of the (user, movie) pair; symmetric duplicate
    half = st.n_edges // 2
    # recover pair (user, movie) per directed edge from endpoints
    s, r = st.senders, st.receivers
    user_of = np.where(s < n_users, s, r)
    movie_of = np.where(s < n_users, r, s) - n_users
    rating = np.einsum("ed,ed->e", u_true[user_of], m_true[movie_of])

    # noise and train/test split per undirected pair: both directions of a
    # rating must agree, or the user and movie updates minimize different
    # objectives and the alternating sweeps never settle
    pair_key = user_of.astype(np.int64) * n_movies + movie_of
    uniq, inv = np.unique(pair_key, return_inverse=True)
    rating = rating + rng.normal(0, noise, size=uniq.size)[inv]
    is_test_pair = rng.random(uniq.size) < test_frac
    train = (~is_test_pair[inv]).astype(rating.dtype)

    factors = rng.normal(0, 0.1, size=(st.n_vertices, d))
    vdata = {"factor": jnp.asarray(factors, dtype)}
    edata = {"rating": jnp.asarray(rating, dtype),
             "train": jnp.asarray(train, dtype)}
    g = DataGraph.build(st, vdata, edata)
    info = {"n_users": n_users, "n_movies": n_movies,
            "user_of": user_of, "movie_of": movie_of}
    return g, info


def als_rmse(graph: DataGraph, train: bool) -> float:
    """Global RMSE over train or test edges (benchmark metric, Fig. 9(a))."""
    st = graph.structure
    x = np.asarray(graph.vertex_data["factor"])
    pred = np.einsum("ed,ed->e", x[st.senders], x[st.receivers])
    rating = np.asarray(graph.edge_data["rating"])
    mask = np.asarray(graph.edge_data["train"]) > 0.5
    if not train:
        mask = ~mask
    err = (pred[mask] - rating[mask]) ** 2
    return float(np.sqrt(err.mean())) if err.size else 0.0
