"""Time to tolerance: the window's seconds times the sweeps a solve takes,
over the sweeps the window completed.  Where the window holds whole solves
this is window / solves; where a solve is longer, the window's time over
its share of a solve.  ``sweeps_per_solve`` comes from the solve that the
run completes."""


def read(run):
    w = run.window
    if not w["finished"] or w["sweeps"] == 0:
        return None
    return w["window_s"] * w["sweeps_per_solve"] / w["sweeps"]
