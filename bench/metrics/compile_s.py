"""``Engine.compile``: lowering on every run, the compile itself served
from the persistent cache after a checkout's first run (every seed of a
cell gives the same program)."""


def read(run):
    return run.timings["compile_s"]
