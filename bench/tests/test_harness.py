"""Whole runs of the harness on the CPU at a small size: the look for a
chip is skipped (``require_tpu=False``) and everything else runs as on the
chip.  A sound run is correct; a run whose timed path is broken comes out
not correct; a run without a TPU, or from a directory holding only the
benchmark, prints no result."""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

from bench import run, spec

CELLS = ["pagerank-kron19.solve"]
SEED = 2**31 + 12345


def drive(root, cell, capsys, trace=0, seconds=1.0):
    rc = run.main(["--workload", cell, "--seed", str(SEED), "--seconds",
                   str(seconds), "--trace", str(trace)],
                  root=root, require_tpu=False)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(tiny_root, cell, capsys):
    res = drive(tiny_root, cell, capsys)
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"tol_s", "setup_s"}
    assert res["metrics"]["tol_s"]["value"] > 0
    assert list(res)[-1] == "checks"
    assert res["device"]["count"] == 1


def test_no_tpu_prints_no_result(tiny_root, capsys):
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"],
                  root=tiny_root)
    assert rc != 0
    assert "correct" not in capsys.readouterr().out


def test_benchmark_alone_does_not_run(tmp_path):
    """Only BENCHMARK.json and bench/: the program is missing."""
    from conftest import copy_benchmark
    root = copy_benchmark(str(tmp_path))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        CELLS[0], "--seed", "1", "--seconds", "1"],
                       cwd=root, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert "correct" not in p.stdout


def unchanged_step(self, state):
    return state


def drop_half(select):
    def wrapped(self, sched, prio, phase=0, tables=None):
        mask, sched = select(self, sched, prio, phase, tables)
        return mask & (jnp.arange(mask.shape[0]) % 2 == 0), sched
    return wrapped


def alter_answer(apply):
    def wrapped(self, vertex_data, acc, glob=None):
        out = apply(self, vertex_data, acc, glob)
        new = {k: v.at[0].add(0.5 * jnp.max(jnp.abs(v)) + 1e-3)
               for k, v in out.vertex_data.items()}
        return out._replace(vertex_data=new)
    return wrapped


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch",
                                   "altered_answer"])
def test_broken_timed_path_is_not_correct(tiny_root, cell, fault, capsys,
                                          monkeypatch):
    from repro.apps.pagerank import PageRankProgram
    from repro.core.engine_base import Engine
    from repro.core.scheduler import SweepScheduler

    if fault == "unchanged_state":
        monkeypatch.setattr(Engine, "step", unchanged_step)
    elif fault == "half_batch":
        monkeypatch.setattr(SweepScheduler, "select",
                            drop_half(SweepScheduler.select))
    else:
        monkeypatch.setattr(PageRankProgram, "apply",
                            alter_answer(PageRankProgram.apply))
    res = drive(tiny_root, cell, capsys)
    assert res["correct"] is False and res["failed"] == 1


def test_traced_run_reports_per_layer_metrics(tiny_root, capsys, monkeypatch):
    """On the CPU the operations run on a host thread, which stands in for
    the device plane here; the GAS kernels do not run (the CPU takes the
    reference path), so their rooflines are left out."""
    from bench import trace as tr
    monkeypatch.setattr(tr, "DEVICE_PREFIX", "/host:CPU")
    monkeypatch.setattr(tr, "OPS_LINE", "tf_XLAPjRtCpuClient")
    res = drive(tiny_root, CELLS[0], capsys, trace=1)
    m = res["metrics"]
    for name in ("device_idle_share", "sweep_s", "sweeps_per_solve",
                 "graph_build_s", "engine_init_s", "compile_s"):
        assert name in m, name
    assert "gas_scatter_roofline" not in m
    assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
    assert len(res["breakdown"]["device_ops"]) <= 10


def write(root, rel, text):
    with open(os.path.join(root, rel), "w") as f:
        f.write(text)


ONE_SOLVE = """
from bench.drivers import solve


CAP = {"max_sweeps_per_solve": 400}


def drive(engine, state0, seconds, params, *, count_updates=False):
    return solve.drive(engine, state0, seconds, dict(params, **CAP),
                       count_updates=count_updates)


def finish(engine, state, in_solve, params):
    return solve.finish(engine, state, in_solve, dict(params, **CAP))
"""

OTHER_ENGINE = """
def build(program, graph, tolerance, cfg, devices):
    from repro.core import ChromaticEngine
    return ChromaticEngine(program, graph, tolerance=tolerance)
"""


def test_new_pieces_need_no_edit(tiny_root, capsys):
    """A new metric reader, a new configuration with an engine file of its
    own, a new traffic mix of data alone and a mix with a driver of its
    own are files and entries: the harness finds them by name, and no
    file that was there changes."""
    before = {os.path.join(d, f): open(os.path.join(d, f), "rb").read()
              for d, _, fs in os.walk(os.path.join(tiny_root, "bench"))
              for f in fs if not f.endswith(".pyc")}
    write(tiny_root, "bench/metrics/warmup_s.py",
          "def read(run):\n    return run.timings['warmup_s']\n")
    write(tiny_root, "bench/engines/OtherChromatic.py", OTHER_ENGINE)
    write(tiny_root, "bench/drivers/one_solve.py", ONE_SOLVE)
    write(tiny_root, "bench/traffic/short.json", json.dumps(
        {"why": "t", "driver": "solve", "max_sweeps_per_solve": 300,
         "finish_timeout_s": 60}))
    write(tiny_root, "bench/traffic/once.json", json.dumps(
        {"why": "t", "driver": "one_solve", "finish_timeout_s": 60}))
    path = os.path.join(tiny_root, "BENCHMARK.json")
    bench = json.load(open(path))
    src = os.path.join(tiny_root, "bench", "configs", "pagerank-kron19.json")
    cfg = json.load(open(src))
    cfg.update(name="pagerank-kron9", scale=9, engine="OtherChromatic")
    write(tiny_root, "bench/configs/pagerank-kron9.json", json.dumps(cfg))
    bench["configs"].append(dict(bench["configs"][0], name="pagerank-kron9",
                                 file="bench/configs/pagerank-kron9.json"))
    new = ["pagerank-kron9.solve", "pagerank-kron9.short",
           "pagerank-kron19.once"]
    for name in new:
        conf, traffic = name.split(".")
        bench["workloads"].append({"name": name, "config": conf,
                                   "traffic": traffic, "chips": 1,
                                   "why": "t"})
    bench["end_to_end"].append({"name": "warmup_s", "unit": "s",
                                "better": "lower", "bound": 0.25,
                                "source": "host_clock",
                                "workloads": new[:1]})
    json.dump(bench, open(path, "w"))
    for name in new:
        res = drive(tiny_root, name, capsys)
        assert res["correct"] is True, name
        want = {"tol_s", "setup_s"} | ({"warmup_s"} if name == new[0]
                                       else set())
        assert set(res["metrics"]) == want, name
    assert spec.load_cell(CELLS[0], tiny_root).end_to_end[-1]["name"] \
        == "setup_s"
    for f, data in before.items():
        assert open(f, "rb").read() == data, f
