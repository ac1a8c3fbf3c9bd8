"""Calls of the program's ``graphlab.weights`` span: how often the engine
prepared the fused gather's per-color edge weights from the edge data
(``Engine.prepare_weights``).  One per engine where they are prepared once,
in ``Engine.init``; None where the program keeps no span table or prepares
none (it gathers the weights inside every color-step)."""


def read(run):
    try:
        from repro.obs import span_totals
    except ImportError:
        return None
    total = span_totals().get("graphlab.weights")
    return total.count if total else None
