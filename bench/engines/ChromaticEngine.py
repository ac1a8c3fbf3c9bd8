"""The program's ``ChromaticEngine`` on one chip: color-steps one at a
time, a proper coloring for edge consistency.  ``build`` is how a
configuration naming this engine (``"engine": "ChromaticEngine"``) gets it;
an engine over a mesh of chips is a file of its own beside this one."""


def build(program, graph, tolerance, cfg, devices):
    from repro.core import ChromaticEngine

    return ChromaticEngine(program, graph, tolerance=tolerance)
