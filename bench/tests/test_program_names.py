"""What the benchmark reads of the program's own names for its layers:
its host spans' totals (the set-up metrics ``coloring_s``, ``edge_sets_s``,
``lower_s``), its device scopes joined to trace operations through the
compiled step (``bench/scopes.py``), and the reduction of the recorded
chip trace, frozen so that a later change to the reduction shows."""
import hashlib
import json
import os
import sys
import types

import pytest

from bench import scopes, spec
from bench import trace as tr

DATA = os.path.join(os.path.dirname(__file__), "data")
CELL = "pagerank-kron19.solve"
SPAN_METRICS = {"coloring_s": "graphlab.coloring",
                "edge_sets_s": "graphlab.edge_sets",
                "lower_s": "graphlab.lower"}


def test_set_up_span_metrics_in_a_traced_run(tiny_root, capsys,
                                             monkeypatch):
    from repro.obs import reset_span_totals
    from test_harness import drive
    monkeypatch.setattr(tr, "DEVICE_PREFIX", "/host:CPU")
    monkeypatch.setattr(tr, "OPS_LINE", "tf_XLAPjRtCpuClient")
    reset_span_totals()                 # one set-up, as in a run's process
    m = drive(tiny_root, CELL, capsys, trace=1)["metrics"]
    for name in SPAN_METRICS:
        assert m[name]["value"] > 0 and m[name]["unit"] == "s", name
    assert m["coloring_s"]["value"] + m["edge_sets_s"]["value"] \
        <= m["engine_init_s"]["value"]
    assert m["lower_s"]["value"] <= m["compile_s"]["value"]


@pytest.mark.parametrize("metric", sorted(SPAN_METRICS))
def test_span_metrics_read_none_without_the_span(metric, monkeypatch):
    """No such span closed, and a program without the span table (as
    before the program kept one): None, not an error."""
    from repro.obs import reset_span_totals
    read = spec.module("metrics", metric).read
    reset_span_totals()
    assert read(None) is None
    monkeypatch.setitem(sys.modules, "repro.obs",
                        types.ModuleType("repro.obs"))
    assert read(None) is None


def test_scope_map_joins_the_compiled_step():
    from repro.apps.pagerank import PageRankProgram, make_pagerank_graph
    from repro.core import ChromaticEngine
    from repro.graphs.generators import connected_power_law_graph

    g = make_pagerank_graph(connected_power_law_graph(60, seed=3))
    eng = ChromaticEngine(PageRankProgram(0.15, 60), g, tolerance=1e-6)
    smap = scopes.scope_map(eng.compile(eng.init(g)).as_text())
    assert {"graphlab.select", "graphlab.edge_weight", "graphlab.gather",
            "graphlab.apply", "graphlab.reschedule",
            "graphlab.scatter"} <= set(smap.values())
    fused = sorted(n for n in smap if "fusion" in n)
    assert fused
    # trace events name the instruction and its type; unknown ones map
    # to no scope
    ops = {f"%{fused[0]} = f32[8]{{0}} fusion(f32[8]{{0}} %p)": 2.0,
           "%no.such.op = f32[] add(f32[] %a, f32[] %b)": 1.0}
    assert scopes.seconds_by_scope(ops, smap) == \
        {smap[fused[0]]: 2.0, None: 1.0}


def test_scope_map_parses_instruction_lines():
    text = "\n".join([
        '  %fusion.3 = f32[4]{0} fusion(%p.1), kind=kLoop, calls=%c, '
        'metadata={op_name="jit(_step)/while/body/graphlab.apply/'
        'graphlab.gather/mul" stack_frame_id=3}',
        '  ROOT %tuple.9 = (f32[4]{0}) tuple(%fusion.3)',
        '  %copy.1 = f32[4]{0} copy(%p.1), metadata={op_name="jit(f)/copy"}',
    ])
    assert scopes.scope_map(text) == {"fusion.3": "graphlab.gather"}
    assert scopes.op_name("%fusion.3 = f32[4]{0} fusion(...)") == "fusion.3"


def test_reduction_of_the_recorded_trace_is_frozen():
    """``reduce`` of the recorded chip trace, frozen: what the accepted
    metrics read must not move when the reduction grows."""
    r = tr.reduce(os.path.join(DATA, "pagerank_tiny.xplane.pb"))
    assert r["n_devices"] == 1
    assert r["busy_s"] == 0.018803537000000002
    assert r["window_s"] == 0.052649177000000005
    assert (len(r["ops"]), len(r["op_text"])) == (50, 51)
    digest = lambda d: hashlib.sha256(
        json.dumps(sorted(d.items())).encode()).hexdigest()
    assert digest(r["ops"]) == \
        "1e1c648c893dcd5ba7a59d6d554272fe54dc1b3d6b21e7e07e96fcff9f4e2e57"
    assert digest(r["op_text"]) == \
        "752359d6a29ccee2c90fc5009965428c1c55f7859340cf92ff4863ed407c8535"
