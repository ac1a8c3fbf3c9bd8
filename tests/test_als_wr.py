"""ALS-WR (``ALSProgram(weighted=True)``: ``λ·n_v·I``, Zhou et al. 2008)
through the chromatic engine's fused GAS path, against a plain float64
NumPy ALS run of the same colors in the same order (users, then movies),
on a seeded small Netflix-shaped graph: heavy-tailed user degrees and
movie popularity, held-out pairs, movies without a training rating.

Tolerance: the largest factor difference over the largest factor entry,
1e-5.  The engine runs in float32 (the factors' rounding is ~6e-8 of the
largest entry) and each sweep solves 20×20 systems whose condition number
is bounded by the regulariser (``λ·n_v`` against ``Σ x xᵀ``, at most a few
hundred here); a few sweeps amplify the rounding to ~1e-6, and a wrong
regulariser, a dropped color-step or a gather over the wrong edges moves
the factors by 1e-2 or more.
"""
from __future__ import annotations

import jax
import numpy as np
import pytest

from repro.apps.als import (ALSProgram, make_als_graph, ratings_graph,
                            with_rating_counts)
from repro.core.chromatic import ChromaticEngine
from repro.kernels.gas import gas

D = 20
LAM = 0.065
REL = 1e-5


def netflix_like(n_users=90, n_movies=70, seed=0, d=D):
    """Pairs with lognormal user degrees (median 12, a few users with most
    movies) and lognormal movie popularity; planted rank-``d`` ratings with
    noise; 20% held out; ALS-WR's start (a movie's first entry its mean
    training rating)."""
    rng = np.random.default_rng(seed)
    deg = np.clip(np.rint(rng.lognormal(np.log(12), 1.0, n_users)), 1,
                  n_movies).astype(np.int64)
    pop = rng.lognormal(0.0, 1.5, n_movies)
    keys = np.unique(np.repeat(np.arange(n_users), deg) * n_movies
                     + rng.choice(n_movies, deg.sum(), p=pop / pop.sum()))
    users, movies = keys // n_movies, keys % n_movies
    s = (0.9 / d) ** 0.25
    u = rng.normal(0, s, (n_users, d))
    m = rng.normal(0, s, (n_movies, d))
    rating = (u[users] * m[movies]).sum(1) + rng.normal(0, 0.3, users.size)
    train = rng.random(users.size) >= 0.2
    x = rng.normal(0, 0.01, (n_users + n_movies, d))
    cnt = np.bincount(movies, train, n_movies)
    x[n_users:, 0] = np.bincount(movies, rating * train, n_movies) \
        / np.maximum(cnt, 1)
    return {"users": users, "movies": movies, "rating": rating,
            "train": train, "factor0": x, "n_users": n_users,
            "n_movies": n_movies}


def numpy_als(g, sweeps, weighted=True, reg=LAM):
    """Plain float64 ALS: every user solved, then every movie, each from
    the other side's latest factors."""
    nu, nm = g["n_users"], g["n_movies"]
    x = g["factor0"].astype(np.float64).copy()
    t = g["train"]
    sides = [(g["users"][t], g["movies"][t] + nu, np.arange(nu)),
             (g["movies"][t] + nu, g["users"][t], np.arange(nu, nu + nm))]
    r = g["rating"][t]
    for _ in range(sweeps):
        for recv, send, verts in sides:
            a = np.zeros((nu + nm, D, D))
            b = np.zeros((nu + nm, D))
            np.add.at(a, recv, x[send][:, :, None] * x[send][:, None, :])
            np.add.at(b, recv, r[:, None] * x[send])
            n_v = np.maximum(np.bincount(recv, minlength=nu + nm), 1)
            lam = reg * (n_v if weighted else np.ones_like(n_v))
            a += lam[:, None, None] * np.eye(D)
            x[verts] = np.linalg.solve(a[verts], b[verts][..., None])[..., 0]
    return x


def engine_factors(graph, sweeps, weighted=True, **kw):
    eng = ChromaticEngine(ALSProgram(D, LAM, weighted=weighted), graph,
                          tolerance=1e-30, use_fused=True, **kw)
    assert eng.use_fused and eng.num_colors == 2
    state, _ = eng.run(eng.init(graph), max_steps=sweeps)
    assert int(state.step_index) == sweeps
    return np.asarray(state.graph.vertex_data["factor"])


def rel_err(got, want):
    return np.abs(got - want).max() / np.abs(want).max()


@pytest.fixture(scope="module")
def small():
    g = netflix_like()
    assert (np.bincount(g["movies"], g["train"], g["n_movies"]) == 0).any()
    return g, ratings_graph(g["users"], g["movies"], g["n_users"],
                            g["n_movies"], g["rating"], g["train"],
                            g["factor0"])


def test_rating_counts_are_training_degrees(small):
    g, graph = small
    nu = g["n_users"]
    want = np.concatenate([np.bincount(g["users"], g["train"], nu),
                           np.bincount(g["movies"], g["train"],
                                       g["n_movies"])])
    np.testing.assert_array_equal(
        np.asarray(graph.vertex_data["n_train"]), want)


@pytest.mark.parametrize("weighted,sweeps", [(True, 6), (False, 2)],
                         ids=["als_wr", "reg_I"])
def test_fused_engine_matches_numpy_als(small, weighted, sweeps):
    """``reg·I`` (ALS-WR off) runs two sweeps, not six: unweighted, λ is
    small against a busy vertex's ``Σ x xᵀ``, its systems are worse
    conditioned, and the float32 difference grows from 5.8e-6 at two
    sweeps to 1.2e-5 at six (ALS-WR: 1.6e-6 and 1.1e-6)."""
    g, graph = small
    got = engine_factors(graph, sweeps, weighted=weighted)
    want = numpy_als(g, sweeps, weighted=weighted)
    assert rel_err(got, want) <= REL
    # the two regularisers give different factors: the test tells them apart
    other = numpy_als(g, sweeps, weighted=not weighted)
    assert rel_err(got, other) > 1e-2


@pytest.fixture(params=["resident", "hbm"])
def table_residence(request, monkeypatch):
    """The row-block kernel's table kept in VMEM, or staged from HBM by
    row DMAs (``RESIDENT_BYTES`` 0), with the kernel's compiled traces
    dropped on both sides so that the choice is traced again."""
    seen = []
    kernel = gas._row_block_kernel

    def spy(*, n_planes, resident, outer=False):
        seen.append((n_planes, resident, outer))
        return kernel(n_planes=n_planes, resident=resident, outer=outer)

    monkeypatch.setattr(gas, "_row_block_kernel", spy)
    if request.param == "hbm":
        monkeypatch.setattr(gas, "RESIDENT_BYTES", 0)
    jax.clear_caches()
    yield request.param, seen
    jax.clear_caches()


def test_row_block_kernel_at_d20(small, table_residence):
    """The Pallas row-block kernel, in interpret mode, at the cell's widths:
    the 400-wide ``xxt`` leaf (4 planes of 128 lanes) and the 20-wide
    ``rx`` leaf (1 plane).  Where the 20-wide table stays resident the
    kernel forms ``x xᵀ`` itself (``outer``); where it does not, the
    400-wide table of outer products is gathered by row DMAs."""
    g, graph = small
    where, seen = table_residence
    got = engine_factors(graph, 2, gas_interpret=True)
    assert rel_err(got, numpy_als(g, 2)) <= REL
    resident = where == "resident"
    assert sorted(set(seen)) == [(1, resident, False),
                                 (4, resident, resident)]


def test_reg_I_ignores_the_counts():
    """With ALS-WR off, the program is the plain ``reg·I`` ALS: the same
    factors, bit for bit, whether or not the graph carries ``n_train``."""
    plain, _ = make_als_graph(30, 35, 1000, d=4, seed=1)
    counted = with_rating_counts(plain)
    out = []
    for graph in (plain, counted):
        eng = ChromaticEngine(ALSProgram(4), graph, tolerance=1e-4)
        state, _ = eng.run(eng.init(graph), max_steps=10)
        out.append(np.asarray(state.graph.vertex_data["factor"]))
    np.testing.assert_array_equal(out[0], out[1])
