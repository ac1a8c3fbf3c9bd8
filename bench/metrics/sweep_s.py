"""Seconds per sweep over the window (host clock): the engine step."""


def read(run):
    w = run.window
    return w["window_s"] / w["sweeps"] if w["sweeps"] else None
