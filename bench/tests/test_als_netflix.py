"""The ALS-WR cell ``als-netflix8.solve`` on the CPU at a small size: the
Netflix-shaped generator, the program it compiles, a sound run, planted
faults of the timed path, the row-block work count, the lower-precision
control and the new per-layer readers."""
from __future__ import annotations

import json
import os
import re
import sys
import types

import jax.numpy as jnp
import numpy as np
import pytest

from bench import compare, control, spec
from bench import trace as tr
from conftest import TINY, copy_benchmark
from test_harness import drive

CELL = "als-netflix8.solve"
# a graph small enough for the CPU, with users rating a third of the
# movies so that d = 20 stays well determined (a solve takes ~20 sweeps)
SMALL = {"n_users": 300, "n_movies": 200, "user_degree_draw_mean": 100,
         "user_degree_median": 60, "movie_degree_median": 120000}
SIZES = dict(TINY, **{"als-netflix8": SMALL})
SEEDS = [11, 2**31 + 3, 77]


@pytest.fixture
def als_root(tmp_path):
    return copy_benchmark(str(tmp_path), SIZES)


def config(**over):
    cfg = spec.load_json(os.path.join(spec.ROOT, "bench", "configs",
                                      "als-netflix8.json"))
    cfg.update(over)
    return cfg


def netflix():
    return spec.module("graphs", "netflix")


def test_generator_is_seeded_and_relabels_one_graph():
    gen, cfg = netflix(), config(**SMALL)
    a, b = gen.generate(cfg, 5), gen.generate(cfg, 5)
    for k in ("users", "movies", "rating", "train", "factor0"):
        np.testing.assert_array_equal(a[k], b[k])
    c = gen.generate(cfg, 2**31 + 5)
    assert not np.array_equal(a["users"], c["users"])
    # another seed: the same pairs, ratings and starts under new labels
    base = gen.base_graph(cfg)
    nu = cfg["n_users"]
    for g in (a, c):
        pu = np.empty(nu, np.int64)
        pu[base["users"]] = g["users"]
        pm = np.empty(cfg["n_movies"], np.int64)
        pm[base["movies"]] = g["movies"]
        np.testing.assert_array_equal(g["users"], pu[base["users"]])
        np.testing.assert_array_equal(g["movies"], pm[base["movies"]])
        np.testing.assert_array_equal(g["rating"], base["rating"])
        np.testing.assert_array_equal(g["factor0"][pu],
                                      base["factor0"][:nu])
    key = a["users"].astype(np.int64) * cfg["n_movies"] + a["movies"]
    assert np.unique(key).size == key.size              # each pair once
    other = gen.base_graph(dict(cfg, graph_seed=1))
    assert other["users"].size != base["users"].size


def test_degree_statistics_at_the_cell_size():
    """The configured graph, from its own seed: within 3% of the
    published mean ratings per user (209.25) and of the total it implies,
    5% of the medians (96 a user; 561 a movie at full scale, 70.1 here),
    the most rated movie under its cap, and the planted ratings' spread
    and held-out share as configured."""
    cfg = config()
    g = netflix().base_graph(cfg)
    nu, nm = cfg["n_users"], cfg["n_movies"]
    du = np.bincount(g["users"], minlength=nu)
    dm = np.bincount(g["movies"], minlength=nm)
    assert abs(du.mean() / cfg["user_degree_mean"] - 1) < 0.03
    assert abs(g["users"].size / (cfg["user_degree_mean"] * nu) - 1) < 0.03
    assert abs(np.median(du) / cfg["user_degree_median"] - 1) < 0.05
    scale = nu / cfg["published"]["users"]
    assert abs(np.median(dm) / (cfg["movie_degree_median"] * scale)
               - 1) < 0.05
    assert dm.max() <= cfg["movie_max_share"] * nu
    assert du.max() > 20 * np.median(du) and dm.max() > 100 * np.median(dm)
    assert du.min() >= 1
    assert abs(g["rating"].std() / cfg["rating_std"] - 1) < 0.02
    assert abs(1 - g["train"].mean() - cfg["test_share"]) < 0.005


def test_every_seed_gives_the_same_program():
    """Users are always color 0 and movies color 1, and relabelling
    within a side keeps every color's edge count: the per-color edge sets,
    their kernel grids and the lowered step are the same for every seed.
    (The CPU's step takes the reference gather, which reads no grid, so
    the grids' shapes are compared too.)"""
    import jax
    from repro.core import ChromaticEngine

    cfg = config(**SMALL)
    app = spec.module("apps", "als")
    texts, shapes = [], []
    for seed in (1, 2**31 + 9):
        program, graph, tol = app.build(netflix().generate(cfg, seed), cfg)
        eng = ChromaticEngine(program, graph, tolerance=tol)
        colors = np.asarray(eng.colors)
        assert (colors[:cfg["n_users"]] == 0).all()
        s = eng.init(graph)
        text = eng._jit_step.lower(s, eng._tables, eng._consts).as_text()
        texts.append(re.sub(r"loc\(.*?\)", "", text))
        shapes.append([x.shape for x in jax.tree.leaves(eng._consts)])
    assert texts[0] == texts[1]
    assert shapes[0] == shapes[1]


def test_sound_run_is_correct(als_root, capsys):
    res = drive(als_root, CELL, capsys)
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"tol_s", "setup_s"}
    assert set(res["checks"]) == {"solve_finished", "grad_rel", "test_rmse"}
    assert res["device"]["count"] == 1


def unweighted(init):
    def wrapped(self, d, reg=0.05, weighted=False):
        init(self, d, reg, weighted=False)
    return wrapped


def skip_movies(apply_phase):
    """A color-step of the movies that runs as a no-op: the scheduler
    consumes their priorities, their factors stay as they were."""
    def wrapped(program, graph, mask, glob, **kw):
        new, residual, et = apply_phase(program, graph, mask, glob, **kw)
        movies = jnp.any(mask[SMALL["n_users"]:])
        vdata = {k: jnp.where(movies, graph.vertex_data[k], v)
                 for k, v in new.vertex_data.items()}
        return (new.replace(vertex_data=vdata),
                jnp.where(movies, 0.0, residual), et)
    return wrapped


def done_early(done):
    """The scheduler reports itself empty at 100 times the tolerance."""
    def wrapped(self, sched, prio):
        return jnp.max(prio) <= 100.0 * self.tolerance
    return wrapped


@pytest.mark.parametrize("fault", ["reg_I", "dropped_color_step",
                                   "stopped_early"])
def test_planted_fault_is_not_correct(als_root, fault, capsys, monkeypatch):
    from repro.apps.als import ALSProgram
    from repro.core import engine_base
    from repro.core.scheduler import SweepScheduler

    if fault == "reg_I":
        monkeypatch.setattr(ALSProgram, "__init__",
                            unweighted(ALSProgram.__init__))
    elif fault == "dropped_color_step":
        monkeypatch.setattr(engine_base, "apply_phase",
                            skip_movies(engine_base.apply_phase))
    else:
        monkeypatch.setattr(SweepScheduler, "done",
                            done_early(SweepScheduler.done))
    res = drive(als_root, CELL, capsys)
    assert res["correct"] is False and res["failed"] == 1
    # the reference itself tells each apart, whether or not the solve
    # emptied its scheduler
    assert res["checks"]["grad_rel"]["value"] > \
        res["checks"]["grad_rel"]["limit"]


def test_row_block_work_count_is_a_lower_bound():
    """Counted per call from the engine's per-vertex update counts, the
    row-block gather's work is at most what the updates of each sweep,
    counted one sweep at a time, need: every in-edge of an updated vertex,
    every distinct neighbor once, every updated row."""
    from bench import run

    rng = np.random.default_rng(0)
    n, widths = 12, [400, 20]
    u = rng.integers(0, 6, 40)
    m = rng.integers(6, n, 40)
    graph = run.Graph(n, np.concatenate([u, m]), np.concatenate([m, u]))
    sweeps = [rng.random(n) < 0.5 for _ in range(6)]
    calls = [sweeps[:2], sweeps[2:3], sweeps[3:]]
    deltas = [np.sum(c, axis=0).astype(np.int64) for c in calls]
    work = spec.module("work", "gas_gather_row_block").work
    cfg = {"work": {"gather": {"leaf_widths": widths}}}
    flops, nbytes = work(run.UpdateCounts(graph, deltas), cfg)
    w, leaves = sum(widths), len(widths)
    brute_flops = brute_bytes = 0
    for upd in sweeps:
        edges = int(graph.degree[upd].sum())
        nbrs = np.unique(graph.src[np.isin(graph.dst, np.nonzero(upd)[0])])
        brute_flops += 2 * w * edges
        brute_bytes += (4 * edges * (1 + leaves)
                        + 4 * w * (nbrs.size + int(upd.sum())))
    assert flops == brute_flops
    assert 0 < nbytes <= brute_bytes
    assert work(run.UpdateCounts(graph, deltas),
                {"work": {"gather": {"source_width": 1}}}) is None


@pytest.mark.parametrize("seed", SEEDS)
def test_bf16_control_is_not_correct(als_root, capsys, seed):
    """The reference's ALS-WR in bfloat16 storage, put in the program's
    place, comes out not correct by the run's own comparison, where the
    program's float32 solve and the same control in float32 come out
    correct: what fails it is the precision."""
    from bench import run

    c = spec.load_cell(CELL, als_root)
    gen = spec.module("graphs", c.config["generator"], als_root)
    ref = spec.module("reference", c.config["app"], als_root)
    assert run.main(["--workload", CELL, "--seed", str(seed),
                     "--seconds", "0.5"], root=als_root,
                    require_tpu=False) == 0
    program = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert program["correct"] is True
    inst = gen.generate(c.config, seed)
    low = control.judge(inst, c.config, ref, ref.control(inst, c.config))
    assert compare.correct(low) is False
    assert low["grad_rel"]["value"] > low["grad_rel"]["limit"]
    same = control.judge(inst, c.config, ref,
                         ref.control(inst, c.config, dtype="float32"))
    assert compare.correct(same) is True


def test_traced_run_reads_the_bipartite_span(als_root, capsys, monkeypatch):
    """On the CPU the GAS kernels do not run (the CPU takes the reference
    path), so the row-block roofline is left out; the host span of the
    bipartite check is read."""
    from repro.obs import reset_span_totals
    monkeypatch.setattr(tr, "DEVICE_PREFIX", "/host:CPU")
    monkeypatch.setattr(tr, "OPS_LINE", "tf_XLAPjRtCpuClient")
    reset_span_totals()                 # one set-up, as in a run's process
    m = drive(als_root, CELL, capsys, trace=1)["metrics"]
    assert 0 < m["bipartite_coloring_s"]["value"] \
        <= m["engine_init_s"]["value"]
    assert m["bipartite_coloring_s"]["unit"] == "s"
    assert "gas_gather_row_block_roofline" not in m
    assert "coloring_s" not in m and "weight_preps" not in m


def test_readers_give_none_without_their_sources(monkeypatch):
    from repro.obs import reset_span_totals
    reset_span_totals()
    assert spec.module("metrics", "bipartite_coloring_s").read(None) is None
    monkeypatch.setitem(sys.modules, "repro.obs",
                        types.ModuleType("repro.obs"))
    assert spec.module("metrics", "bipartite_coloring_s").read(None) is None
    view = types.SimpleNamespace(kernel_seconds=lambda p: None)
    assert spec.module("metrics",
                       "gas_gather_row_block_roofline").read(view) is None
