"""Set-up time: generation, the program's graph build, engine init,
compile (from the cache after a checkout's first run) and the warm-up
step."""


def read(run):
    return run.timings["setup_s"]
