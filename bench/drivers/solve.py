"""Driver of solve mixes: solves through ``Engine.run`` from the initial
state, restarted from it each time the scheduler empties.

A traffic mix ``bench/traffic/<name>.json`` names its driver
(``"driver": "solve"``) and holds its parameters; mixes that share a driver
are data alone.  Parameters read here: ``max_sweeps_per_solve`` (a solve
that reaches it is not finished) and ``finish_timeout_s`` (how long the
solve in flight at the window's close may take to finish, untimed).
"""
from __future__ import annotations

import time

import numpy as np


def drive(engine, state0, seconds, params, *, count_updates=False):
    """Solves from ``state0`` for ``seconds``.  Calls are sized from the
    sweep time seen so far, so the window ends on a sweep boundary just
    past its length.  Returns the window's counts, the state of the solve
    in flight (or of the last solve, where none is in flight) and, with
    ``count_updates``, each call's per-vertex update counts."""
    from jax.profiler import TraceAnnotation as annotate

    cap = int(params["max_sweeps_per_solve"])
    state, in_solve, sweeps, solves, sps = state0, 0, 0, 0, []
    last = None
    zeros = np.zeros(state0.update_count.shape, np.int64)
    prev, deltas = zeros, []
    t0 = time.perf_counter()
    deadline, now = t0 + seconds, t0
    while now < deadline and in_solve < cap:
        k = 1 if sweeps == 0 else \
            max(1, int((deadline - now) / ((now - t0) / sweeps)))
        k = min(k, cap - in_solve)
        start = int(state.step_index)
        with annotate("bench.engine_run"):
            nxt, _ = engine.run(state, max_steps=k)
            done = int(nxt.step_index) - start
        if count_updates:
            counts = np.asarray(nxt.update_count).astype(np.int64)
            deltas.append(counts - prev)
            prev = counts
        sweeps += done
        in_solve += done
        now = time.perf_counter()
        if done < k:
            if in_solve == 0:
                break           # the initial state is already solved
            with annotate("bench.restart"):
                solves += 1
                sps.append(in_solve)
                last, state, in_solve, prev = nxt, state0, 0, zeros
        else:
            state = nxt
    return {"window_s": now - t0, "sweeps": sweeps, "solves": solves,
            "solve_sweeps": sps, "in_flight": in_solve,
            "capped": in_solve >= cap}, \
        (state if in_solve or last is None else last), deltas


def finish(engine, state, in_solve, params):
    """Runs the solve in flight to the scheduler's end, untimed; returns
    ``(state, sweeps of the solve, finished)``."""
    cap = int(params["max_sweeps_per_solve"])
    stop = time.perf_counter() + float(params["finish_timeout_s"])
    while in_solve < cap and time.perf_counter() < stop:
        k = min(8, cap - in_solve)
        start = int(state.step_index)
        nxt, _ = engine.run(state, max_steps=k)
        done = int(nxt.step_index) - start
        in_solve += done
        state = nxt
        if done < k:
            return state, in_solve, in_solve > 0
    return state, in_solve, False
