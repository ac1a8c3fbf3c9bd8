"""Sweeps of the solve the run completes: the engine's ``step_index`` when
its scheduler empties."""


def read(run):
    w = run.window
    return float(w["sweeps_per_solve"]) if w["finished"] else None
