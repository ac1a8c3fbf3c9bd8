#!/usr/bin/env python3
"""One run of one benchmark cell, in one process that holds the chip:

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

1. Set-up (``setup_s``): the cell's generator builds its graph from
   ``--seed``; the app module turns it into the program's data graph; the
   configuration's engine file builds the engine, which is initialised and
   compiled (the persistent compile cache serves it after a checkout's
   first run: every seed gives the same program); one step warms the
   executable and the scheduler's check.
2. Window: the traffic mix's driver (``bench/drivers/<driver>.py``) drives
   ``Engine.run`` for ``--seconds``, the window ending on a sweep
   boundary.  With ``--trace 1`` the profiler traces the window.
3. The driver finishes the solve in flight after the window, untimed; the peak
   device memory is read; the program's answer is copied to the host and
   the program's state freed; the plain reference judges the answer.
4. Progress goes to stdout, the compared numbers beside their limits to the
   end of stderr, and the last stdout line is one JSON object: ``correct``,
   ``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
   ``breakdown``, and ``checks`` last.

Without a TPU, or with fewer chips than the cell asks for, it exits with
code 2 and prints no result.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from bench import compare, spec, trace as tr  # noqa: E402


def log(what: str, **fields) -> None:
    print(what, " ".join(f"{k}={v}" for k, v in fields.items()), flush=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the profiler's trace into this directory "
                         "(how bench/tests/data's chip trace was recorded)")
    return ap.parse_args(argv)


def use_compile_cache(jax, root: str) -> str:
    """JAX's persistent compile cache, as the program's
    ``launch/compile_cache.py`` places it: ``JAX_COMPILATION_CACHE_DIR``
    when it is set (JAX reads it by itself), else ``.jax_cache/`` at the
    root of the checkout (a fixed path: the path is part of an entry's
    key).  Kept here so that a change to the program cannot move the
    cache under ``setup_s``; unlike the program's, every program is
    cached, however short its compile."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class Graph:
    """The benchmark's own directed adjacency of a cell's graph (both
    directions of every pair), for work counts: ``src``/``dst`` sorted by
    ``dst``, ``offsets`` into them, ``degree``."""

    def __init__(self, n: int, src: np.ndarray, dst: np.ndarray):
        order = np.argsort(dst, kind="stable")
        self.n = n
        self.src, self.dst = src[order], dst[order]
        self.degree = np.bincount(self.dst, minlength=n).astype(np.int64)
        self.offsets = np.concatenate([[0], np.cumsum(self.degree)])


class RunView:
    """What a metric reader reads: set-up timings, the window's counts, the
    trace's reduction, the least work of a kernel over the traced window,
    and the device's peaks."""

    def __init__(self, cell, timings, window, device, reduced=None,
                 counts=None, graph=None):
        self.cell, self.timings, self.window = cell, timings, window
        self.device, self.trace = device, reduced
        self._counts, self._graph = counts, graph

    def kernel_seconds(self, pattern: str):
        if self.trace is None:
            return None
        return tr.seconds_matching(self.trace, pattern)

    def peaks(self) -> dict:
        return spec.peaks(self.device["kind"], self.cell.root)

    def work(self, kernel: str):
        """``(flops, bytes)`` that ``bench/work/<kernel>.py`` counts for the
        traced window, or None where it does not apply."""
        if self._counts is None or self._graph is None:
            return None
        mod = spec.module("work", kernel, self.cell.root)
        return mod.work(UpdateCounts(self._graph, self._counts),
                        self.cell.config)


class UpdateCounts:
    """The vertices the traced window updated, from the engine's per-vertex
    update counter read at each call's end: ``deltas`` holds, per call, how
    often each vertex was updated in it."""

    def __init__(self, graph: Graph, deltas):
        self.graph, self.deltas = graph, deltas

    def updates(self) -> int:
        """Σ over calls of updated vertices (each update writes its row)."""
        return int(sum(d.sum() for d in self.deltas))

    def updated_edges(self) -> int:
        """Σ over updates of the updated vertex's degree: every update
        reads each of its in-edges, every reschedule each out-edge."""
        return int(sum((d * self.graph.degree).sum() for d in self.deltas))

    def neighbor_reads(self) -> int:
        """A lower bound on Σ over sweeps of the vertices adjacent to an
        updated vertex.  A vertex updates at most once per sweep, so a
        neighbor of a vertex updated k times in a call is read in at least
        k of its sweeps: Σ_w max_{v ∈ N(w)} Δ_v, per call."""
        g = self.graph
        total = 0
        for d in self.deltas:
            vals = d[g.src].astype(np.int64)
            nonempty = g.degree > 0
            mx = np.zeros(g.n, np.int64)
            mx[nonempty] = np.maximum.reduceat(
                vals, g.offsets[:-1][nonempty])
            total += int(mx.sum())
        return total


def run_cell(cell, seed: int, seconds: float, traced: bool, devices,
             keep_trace=None):
    """Set-up, window, comparison.  Returns ``(result, checks)``."""
    import jax

    cfg, traffic = cell.config, cell.traffic
    app = spec.module("apps", cfg["app"], cell.root)
    gen = spec.module("graphs", cfg["generator"], cell.root)
    ref = spec.module("reference", cfg["app"], cell.root)
    driver = spec.module("drivers", traffic["driver"], cell.root)
    timings = {}
    t_start = time.perf_counter()
    mark = [t_start]

    def lap(name):
        now = time.perf_counter()
        timings[name] = now - mark[0]
        mark[0] = now
        log(name, seconds=round(timings[name], 3))

    inst = gen.generate(cfg, seed)
    lap("generate_s")
    program, graph, tolerance = app.build(inst, cfg)
    lap("graph_build_s")
    engine = spec.module("engines", cfg["engine"], cell.root).build(
        program, graph, tolerance, cfg, devices)
    state0 = jax.block_until_ready(engine.init(graph))
    lap("engine_init_s")
    if cfg.get("fused") and not engine.use_fused:
        raise SystemExit(f"{cfg['app']} did not take the fused GAS path")
    engine.compile(state0)
    lap("compile_s")
    jax.block_until_ready(engine.run(state0, max_steps=1)[0])
    lap("warmup_s")
    setup_s = time.perf_counter() - t_start
    log("setup", setup_s=round(setup_s, 3), vertices=graph.n_vertices,
        edges=graph.structure.n_edges,
        phases=getattr(engine, "num_colors", 1),
        tolerance=tolerance)

    own = Graph(*app.adjacency(inst)) if traced else None
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    reduced = None
    if traced:
        jax.profiler.start_trace(trace_dir)
    try:
        with jax.profiler.TraceAnnotation(tr.WINDOW):
            window, state, deltas = driver.drive(
                engine, state0, seconds, traffic, count_updates=traced)
    finally:
        if traced:
            jax.profiler.stop_trace()
    log("window", **{k: v for k, v in window.items()
                     if k != "solve_sweeps"})

    finished = True
    if window["in_flight"] > 0 or window["solves"] == 0:
        state, sps, finished = driver.finish(engine, state,
                                             window["in_flight"], traffic)
    else:
        sps = window["solve_sweeps"][-1]
    if window["solve_sweeps"] and sps not in window["solve_sweeps"]:
        log("note", solve_sweeps=window["solve_sweeps"], compared=sps)
    log("solve", sweeps=sps, finished=finished)

    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    answer = app.answer(state)
    del state, state0, engine, graph, program
    gc.collect()

    if traced:
        xplane = tr.find_xplane(trace_dir)
        if keep_trace:
            os.makedirs(keep_trace, exist_ok=True)
            shutil.copy(xplane, keep_trace)
        reduced = tr.reduce(xplane)
        shutil.rmtree(trace_dir, ignore_errors=True)
        log("trace", busy_s=reduced["busy_s"], window_s=reduced["window_s"])

    t_ref = time.perf_counter()
    values = ref.check(inst, cfg, answer)
    log("reference", seconds=round(time.perf_counter() - t_ref, 3))
    checks = compare.checks(cfg, values, finished)
    correct = compare.correct(checks)

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    window["sweeps_per_solve"] = sps
    window["finished"] = finished
    timings["setup_s"] = setup_s
    view = RunView(cell, timings, window, device, reduced, deltas, own)
    wanted = cell.per_layer if traced else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = spec.module("metrics", m["name"], cell.root).read(view)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    result = {"correct": bool(correct),
              "attempted": window["solves"] + (1 if window["in_flight"]
                                               else 0),
              "failed": 0 if correct else 1,
              "metrics": metrics, "device": device}
    if traced:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": tr.top_ops(reduced),
                               "idle_gaps": [list(g) for g in
                                             reduced["gaps"]]}
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                        for k, c in checks.items()}
    return result, checks


def main(argv=None, root: str = ROOT, require_tpu: bool = True) -> int:
    args = parse(argv)
    cell = spec.load_cell(args.workload, root)
    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        print(f"no TPU: JAX found {devices[0].platform} devices",
              file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"{cell.name} needs {cell.chips} chips, found {len(devices)}",
              file=sys.stderr)
        return 2
    log("cache", dir=use_compile_cache(jax, root))
    result, checks = run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), devices[:cell.chips],
                              args.keep_trace)
    sys.stdout.flush()
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} {c['rule']} {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
