"""Host seconds of the program's ``graphlab.edge_sets`` span: the fused
path's per-color edge sets built and uploaded in ``Engine.__init__``
(``_phase_edge_sets``), part of ``engine_init_s``."""
from bench.scopes import span_seconds


def read(run):
    return span_seconds("graphlab.edge_sets")
