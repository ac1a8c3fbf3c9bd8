"""Host seconds of the program's ``graphlab.coloring`` span: the greedy
coloring and its check in ``ChromaticEngine.__init__``, part of
``engine_init_s``."""
from bench.scopes import span_seconds


def read(run):
    return span_seconds("graphlab.coloring")
