"""Netflix-shaped bipartite rating graph, from the Netflix Prize data set's
published statistics (Bennett & Lanning, "The Netflix Prize", KDD Cup and
Workshop 2007), with planted low-rank ratings.

Users rate movies.  A user's number of ratings is lognormal, fitted to the
data set's median ratings per user and to a mean
(``user_degree_draw_mean``) above the data set's, which the pairing below
brings down to it, and capped at the number of movies.  A movie's number of ratings is lognormal too, fitted to the data
set's median ratings per movie scaled to this graph's users, with the
spread chosen so that the movies' ratings add up to the users', each
capped at the most rated movie's published share of the users.  The two
sides' ratings are then paired at random (a bipartite configuration
model), a pair drawn twice is kept once, and the ratings that the repeats
left unpaired are paired again, for ``refill_rounds`` rounds; what is
still unpaired then (heavy users' ratings of the most rated movies) is
left out.  No Python loop runs over ratings.

Ratings are planted (``planted``), centred on the global mean, and a
``test_share`` of the pairs is held out (Fig. 9(a)'s test error).

The graph, its ratings and the initial factors come from the
configuration's ``graph_seed``, so every run holds the same graph.  The
run's seed permutes user labels among users and movie labels among movies,
and the initial factors follow their vertex: every seed gets the same
2-coloring, the same per-color edge sets and compiled program, and the
same work, in another order.  The initial movie factors are as ALS-WR's
(Zhou et al. 2008, section 3): the movie's mean training rating first,
small random numbers after; user factors are small random numbers.

``generate`` returns ``n_users``, ``n_movies``, each pair once (``users``
in ``[0, n_users)``, ``movies`` in ``[0, n_movies)``), its ``rating`` and
``train`` flag, and ``factor0`` (``[n_users + n_movies, d]``, users
first).
"""
from __future__ import annotations

import numpy as np


def lognormal_degrees(rng, n: int, mean: float, median: float, cap: int
                      ) -> np.ndarray:
    """``n`` whole degrees, lognormal with the given mean and median,
    in ``[1, cap]``."""
    sigma = np.sqrt(2.0 * np.log(mean / median))
    deg = np.rint(rng.lognormal(np.log(median), sigma, n))
    return np.clip(deg, 1, cap).astype(np.int64)


def movie_degrees(z: np.ndarray, median: float, total: int, cap: float
                  ) -> np.ndarray:
    """Whole ratings per movie adding up to ``total``: ``median ·
    exp(sigma · z)`` capped at ``cap``, with ``sigma`` found by bisection so
    that they add up to ``total``, rounded by largest remainder."""
    lo, hi = 0.0, 8.0
    for _ in range(60):
        sigma = 0.5 * (lo + hi)
        if np.minimum(median * np.exp(sigma * z), cap).sum() < total:
            lo = sigma
        else:
            hi = sigma
    want = np.minimum(median * np.exp(hi * z), cap)
    want *= total / want.sum()
    deg = np.floor(want).astype(np.int64)
    extra = total - int(deg.sum())
    deg[np.argsort(deg - want)[:extra]] += 1
    return deg


def rating_pairs(p: dict, rng):
    """``(users, movies)``, each pair once: every user's and every movie's
    ratings drawn as degrees, then paired at random (a bipartite
    configuration model), a pair drawn more than once kept once, and the
    leftovers paired again."""
    nu, nm = int(p["n_users"]), int(p["n_movies"])
    deg = lognormal_degrees(rng, nu, p["user_degree_draw_mean"],
                            p["user_degree_median"], nm)
    total = int(deg.sum())
    scale = nu / p["published"]["users"]
    mdeg = movie_degrees(rng.standard_normal(nm),
                         p["movie_degree_median"] * scale, total,
                         p["movie_max_share"] * nu)
    keys = np.zeros(0, np.int64)
    need_u, need_m = deg, mdeg
    for _ in range(int(p["refill_rounds"])):
        users = np.repeat(np.arange(nu, dtype=np.int64), need_u)
        movies = rng.permutation(np.repeat(np.arange(nm, dtype=np.int64),
                                           need_m))
        keys = np.unique(np.concatenate([keys, users * nm + movies]))
        # a repeat leaves one rating unpaired on each side: pair them again
        need_u = deg - np.bincount(keys // nm, minlength=nu)
        need_m = mdeg - np.bincount(keys % nm, minlength=nm)
        if not need_u.any():
            break
    return (keys // nm).astype(np.int32), (keys % nm).astype(np.int32)


def planted(p: dict, rng, users, movies):
    """Ratings centred on the global mean: a rank-``d`` product of
    per-user and per-movie factors with normal entries of mean 0, as the
    program's ``make_als_graph`` plants them, plus normal noise, scaled so
    that the ratings have the configured standard deviation, of which
    ``noise_std`` is noise."""
    d = int(p["d"])
    # Var(Σ_k a_k b_k) = d s⁴ for a_k, b_k ~ N(0, s²), all independent
    s = ((p["rating_std"] ** 2 - p["noise_std"] ** 2) / d) ** 0.25
    u = rng.normal(0.0, s, (int(p["n_users"]), d))
    m = rng.normal(0.0, s, (int(p["n_movies"]), d))
    out = np.empty(users.size)
    for lo in range(0, users.size, 1 << 20):
        sl = slice(lo, lo + (1 << 20))
        out[sl] = np.einsum("ed,ed->e", u[users[sl]], m[movies[sl]])
    return out + rng.normal(0.0, p["noise_std"], users.size)


def initial_factors(p: dict, rng, movies, rating, train) -> np.ndarray:
    """ALS-WR's start: a movie's first entry is its mean training rating
    (the mean over all ratings for a movie with none), every other entry
    small and random."""
    nu, nm, d = int(p["n_users"]), int(p["n_movies"]), int(p["d"])
    x = rng.normal(0.0, p["init_std"], (nu + nm, d))
    t = train.astype(np.float64)
    count = np.bincount(movies, weights=t, minlength=nm)
    total = np.bincount(movies, weights=rating * t, minlength=nm)
    mean = np.where(count > 0, total / np.maximum(count, 1),
                    (rating * t).sum() / max(t.sum(), 1.0))
    x[nu:, 0] = mean
    return x


def base_graph(p: dict) -> dict:
    """The graph of ``graph_seed``, under its own labels."""
    rng = np.random.default_rng(int(p["graph_seed"]))
    users, movies = rating_pairs(p, rng)
    rating = planted(p, rng, users, movies)
    train = rng.random(users.size) >= p["test_share"]
    factor0 = initial_factors(p, rng, movies, rating, train)
    return {"users": users, "movies": movies, "rating": rating,
            "train": train, "factor0": factor0}


def generate(params: dict, seed: int) -> dict:
    g = base_graph(params)
    nu, nm = int(params["n_users"]), int(params["n_movies"])
    rng = np.random.default_rng(seed)
    pu, pm = rng.permutation(nu), rng.permutation(nm)
    factor0 = np.empty_like(g["factor0"])
    factor0[pu] = g["factor0"][:nu]
    factor0[nu + pm] = g["factor0"][nu:]
    return {"n_users": nu, "n_movies": nm,
            "users": pu[g["users"]].astype(np.int32),
            "movies": pm[g["movies"]].astype(np.int32),
            "rating": g["rating"], "train": g["train"],
            "factor0": factor0}
