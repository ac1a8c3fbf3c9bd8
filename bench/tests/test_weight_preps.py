"""``weight_preps``: the count of the program's ``graphlab.weights`` span,
which prepares the fused gather's per-color edge weights once per edge
data.  A traced run of the tiny cell reads one (its process builds one
engine, and every restart reuses the initial state), and the reader gives
None, not an error, where the program keeps no such span."""
import sys
import types

from bench import spec
from bench import trace as tr

CELL = "pagerank-kron19.solve"


def test_one_preparation_in_a_traced_run(tiny_root, capsys, monkeypatch):
    from repro.obs import reset_span_totals
    from test_harness import drive
    monkeypatch.setattr(tr, "DEVICE_PREFIX", "/host:CPU")
    monkeypatch.setattr(tr, "OPS_LINE", "tf_XLAPjRtCpuClient")
    reset_span_totals()                 # one set-up, as in a run's process
    m = drive(tiny_root, CELL, capsys, trace=1)["metrics"]
    assert m["weight_preps"] == {"value": 1.0, "unit": "calls"}


def test_reads_none_without_the_span(monkeypatch):
    from repro.obs import reset_span_totals
    read = spec.module("metrics", "weight_preps").read
    reset_span_totals()
    assert read(None) is None
    monkeypatch.setitem(sys.modules, "repro.obs",
                        types.ModuleType("repro.obs"))
    assert read(None) is None
