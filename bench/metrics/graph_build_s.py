"""The program's graph build from the generated pairs
(``GraphStructure.undirected``, the app's data graph)."""


def read(run):
    return run.timings["graph_build_s"]
