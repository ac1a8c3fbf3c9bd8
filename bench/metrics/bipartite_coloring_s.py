"""Host seconds of the program's ``graphlab.bipartite`` span: the bipartite
check (``core/coloring.py:bipartite_coloring``) inside
``graphlab.coloring``, part of ``engine_init_s``; None where the program
has no such span."""
from bench.scopes import span_seconds


def read(run):
    return span_seconds("graphlab.bipartite")
