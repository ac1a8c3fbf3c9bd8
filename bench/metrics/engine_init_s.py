"""Host set-up of the engine: ``Engine.__init__`` (coloring, per-color edge
sets, upload) and ``init``."""


def read(run):
    return run.timings["engine_init_s"]
