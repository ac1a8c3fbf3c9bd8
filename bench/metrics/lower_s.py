"""Host seconds of the program's ``graphlab.lower`` span: lowering the
step in ``Engine.compile``, which the persistent compile cache does not
skip; the rest of ``compile_s`` is ``graphlab.compile``."""
from bench.scopes import span_seconds


def read(run):
    return span_seconds("graphlab.lower")
