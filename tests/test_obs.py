"""Telemetry subsystem (repro/obs/; DESIGN §3.15).

Covered here: (1) the unified trace schema — local and dist ``run``
emit the same canonical keys and no others; (2) batched host draining — rows are identical for
any ``trace_every`` and the number of host transfers shrinks to
``ceil(steps / trace_every)``; (3) the zero-overhead off-switch — an
engine built with telemetry enabled has a byte-identical step jaxpr to
one built without (collection never adds an op to the jitted step);
(4) snapshot-aligned aggregation — the naive live reduction over a
4-machine mesh mixes pre/post-cut rows while the marker-anchored
aggregate equals a single-machine oracle restored from the same cut,
bit-exactly; (5) Chrome-trace/JSONL export structure; (6) the span
primitive — host spans in the profiler's trace and in ``span_totals``,
and the ``graphlab.*`` device scopes in the compiled step.
"""
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps.pagerank import PageRankProgram, make_pagerank_graph
from repro.core import Engine
from repro.core.snapshot import restore_engine_state
from repro.dist.engine import DistributedEngine
from repro.dist.locking import DistributedLockingEngine
from repro.graphs.generators import connected_power_law_graph
from repro.obs import (METRICS_SCHEMA, MetricsFrame, ObsConfig, ObsSession,
                       Supervisor, aligned_aggregate, chrome_trace,
                       live_aggregate, mixing_report, reset_span_totals,
                       span, span_totals, write_chrome_trace,
                       write_events_jsonl)

needs_mesh = pytest.mark.skipif(
    jax.device_count() < 4, reason="needs 4 forced host devices "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=4)")


def _case(n=80, seed=3, tol=1e-9):
    g = make_pagerank_graph(connected_power_law_graph(n, seed=seed))
    return g, PageRankProgram(0.15, n), tol


def _dist(cpu_mesh, tol=1e-9, **kw):
    g, prog, _ = _case(tol=tol)
    eng = DistributedEngine(prog, g, cpu_mesh, tolerance=tol, method="bfs",
                            **kw)
    return eng, eng.init()


# ---------------------------------------------------------------------------
# satellite: one schema across local / dist / snapshot driver
# ---------------------------------------------------------------------------

CANONICAL = set(METRICS_SCHEMA) - {"beats"}


class TestUnifiedSchema:
    def test_local_rows_canonical_with_aliases(self):
        g, prog, tol = _case(n=40, tol=1e-6)
        eng = Engine(prog, g, tolerance=tol)
        _, trace = eng.run(eng.init(g), max_steps=30,
                           trace_fn=lambda s: {"custom": 1.0})
        assert trace, "local run with trace_fn must emit rows"
        row = trace[0]
        # the canonical keys and the trace_fn extra: no pre-§3.15 alias
        assert set(row) == CANONICAL | {"custom"}
        assert row["custom"] == 1.0
        # local engines ship nothing: traffic fields structurally zero
        assert row["traffic_rows_v"] == row["traffic_bytes_v"] == 0
        # rows are plain python scalars (drained, not device arrays)
        assert isinstance(row["updates"], int)
        assert isinstance(row["residual_max"], float)

    @needs_mesh
    def test_dist_rows_canonical_with_aliases(self, cpu_mesh):
        eng, state = _dist(cpu_mesh, tol=1e-6)
        _, trace = eng.run(state, max_steps=30)
        row = trace[0]
        assert set(row) == CANONICAL
        last = trace[-1]
        assert last["traffic_rows_v"] > 0
        # default f32 wire: bytes are rows x a fixed per-row payload size
        assert last["traffic_bytes_v"] % last["traffic_rows_v"] == 0
        assert last["traffic_bytes_v"] >= 4 * last["traffic_rows_v"]

    def test_frames_roundtrip(self):
        g, prog, tol = _case(n=40, tol=1e-6)
        eng = Engine(prog, g, tolerance=tol)
        _, trace = eng.run(eng.init(g), max_steps=10,
                           trace_fn=lambda s: {"custom": 2.5})
        f = MetricsFrame.from_row(trace[0])
        assert f.updates == trace[0]["updates"]
        assert f.extra["custom"] == 2.5
        back = f.to_row()
        assert back == trace[0]


# ---------------------------------------------------------------------------
# satellite: batched host draining (trace_every)
# ---------------------------------------------------------------------------

class TestTraceEvery:
    def test_rows_identical_and_transfers_batched(self):
        g, prog, tol = _case(n=40, tol=1e-6)
        runs = {}
        for every in (1, 4):
            eng = Engine(prog, g, tolerance=tol,
                         obs=ObsConfig(enabled=True, trace_every=every))
            ses = ObsSession(ObsConfig(enabled=True))
            state, trace = eng.run(eng.init(g), max_steps=30, session=ses)
            runs[every] = (trace, ses.drains)
        t1, d1 = runs[1]
        t4, d4 = runs[4]
        assert t1 == t4, "batching must not change row values"
        steps = len(t1)
        assert steps > 4
        assert d1 == steps
        assert d4 == math.ceil(steps / 4)

    @needs_mesh
    def test_dist_rows_identical_across_batching(self, cpu_mesh):
        eng, state = _dist(cpu_mesh, tol=1e-6)
        _, t1 = eng.run(state, max_steps=12, trace_every=1)
        eng2, state2 = _dist(cpu_mesh, tol=1e-6)
        _, t5 = eng2.run(state2, max_steps=12, trace_every=5)
        assert t1 == t5


# ---------------------------------------------------------------------------
# zero-overhead off-switch: obs never touches the jitted step
# ---------------------------------------------------------------------------

class TestZeroOverhead:
    def test_local_step_jaxpr_identical(self):
        g, prog, tol = _case(n=40, tol=1e-6)
        off = Engine(prog, g, tolerance=tol)
        on = Engine(prog, g, tolerance=tol,
                    obs=ObsConfig(enabled=True, trace_every=8,
                                  timeline=True,
                                  residual_quantiles=(0.5, 0.9)))
        joff = jax.make_jaxpr(lambda s: off._step(s))(off.init(g))
        jon = jax.make_jaxpr(lambda s: on._step(s))(on.init(g))
        assert str(joff) == str(jon)

    @needs_mesh
    @pytest.mark.parametrize("engine_cls", [DistributedEngine,
                                            DistributedLockingEngine],
                             ids=["sweep", "locking"])
    def test_dist_step_jaxpr_identical(self, cpu_mesh, engine_cls):
        g, prog, tol = _case(tol=1e-6)
        off = engine_cls(prog, g, cpu_mesh, tolerance=tol, method="bfs")
        on = engine_cls(prog, g, cpu_mesh, tolerance=tol, method="bfs",
                        obs=ObsConfig(enabled=True, timeline=True,
                                      residual_quantiles=(0.5,)))
        joff = jax.make_jaxpr(off._make_step())(off.init(), off._tables)
        jon = jax.make_jaxpr(on._make_step())(on.init(), on._tables)
        assert str(joff) == str(jon)


# ---------------------------------------------------------------------------
# snapshot-aligned aggregation (tentpole layer 1, aligned mode)
# ---------------------------------------------------------------------------

@needs_mesh
class TestAlignedAggregate:
    def test_marker_anchored_matches_oracle_naive_mixes(self, cpu_mesh):
        # moderate tolerance so the mesh is *partially* converged when the
        # wave starts: converged vertices stop executing (their live rows
        # stay at the cut value) while active ones keep updating during
        # the multi-step wave (their live rows advance past it) — the
        # pre/post mixture a naive per-step sum cannot see
        g, prog, tol = _case(n=80, tol=1e-4)
        eng = DistributedEngine(prog, g, cpu_mesh, tolerance=tol,
                                method="bfs")
        state = eng.init()
        n = g.structure.n_vertices
        for _ in range(200):
            state = eng.step(state)
            active = int((np.asarray(jax.device_get(state.prio))
                          > tol).sum())
            if active < n // 2:
                break
        assert 0 < active < n, "need a partially-converged mesh"
        state = eng.start_snapshot(state, (0,))
        while not eng.snapshot_complete(state):
            state = eng.step(state)
        assert eng.snapshot_violations(state) == 0

        mix = mixing_report(eng, state, field="rank")
        assert mix["rows_post_cut"] > 0, \
            "live rows must have advanced past the cut"
        assert mix["rows_pre_cut"] > 0, \
            "some rows must still be at their cut values"

        naive = live_aggregate(eng, state, field="rank")
        aligned = aligned_aggregate(eng, state, field="rank")
        assert naive != aligned["value"], \
            "the naive per-step sum mixes pre/post-cut rows"

        # single-machine oracle: restore the same cut into a local engine
        # and reduce there — bit-exact agreement, not approximate
        local = Engine(prog, g, tolerance=tol)
        restored = restore_engine_state(local, g, eng.assemble_snapshot(state))
        oracle = float(np.sum(np.asarray(
            restored.graph.vertex_data["rank"], np.float64)))
        assert aligned["value"] == oracle
        anchor = aligned["anchor"]
        assert anchor["save_step_max"] >= anchor["save_step_min"] >= 0

    def test_aligned_requires_completed_cut(self, cpu_mesh):
        eng, state = _dist(cpu_mesh)
        with pytest.raises(ValueError, match="no snapshot"):
            aligned_aggregate(eng, state, field="rank")
        state = eng.start_snapshot(state, (0,))
        state = eng.step(state)
        if not eng.snapshot_complete(state):
            with pytest.raises(ValueError, match="in flight"):
                aligned_aggregate(eng, state, field="rank")


# ---------------------------------------------------------------------------
# timeline + export
# ---------------------------------------------------------------------------

class TestTimelineExport:
    @needs_mesh
    def test_chrome_trace_and_jsonl(self, cpu_mesh, tmp_path):
        ses = ObsSession(ObsConfig(enabled=True, timeline=True))
        eng, state = _dist(cpu_mesh, tol=1e-6,
                           obs=ObsConfig(enabled=True, timeline=True))
        eng.run(state, max_steps=5, session=ses)
        ses.event("unit_test_marker", detail=42)

        doc = chrome_trace(ses.timeline, metadata={"case": "pagerank"})
        spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        by = lambda n: [e for e in spans if e["name"] == n]
        steps, runs = by("graphlab.dispatch"), by("graphlab.run")
        assert len(steps) == 5 and len(by("graphlab.done")) == 5
        assert len(runs) == 1
        assert all(e["dur"] >= 0 and e["ts"] >= 0 for e in spans)
        run = runs[0]
        assert all(run["ts"] <= e["ts"] and
                   e["ts"] + e["dur"] <= run["ts"] + run["dur"]
                   for e in steps)
        # every span is measured: nothing is synthesized per color
        assert all(e["name"].startswith("graphlab.") for e in spans)
        assert not any("logical" in e["args"] for e in spans)
        names = [e for e in doc["traceEvents"] if e.get("ph") == "M"]
        assert names, "thread_name metadata labels the tracks"

        p = tmp_path / "trace.json"
        write_chrome_trace(str(p), ses.timeline)
        assert json.loads(p.read_text())["traceEvents"]

        q = tmp_path / "events.jsonl"
        write_events_jsonl(str(q), ses.events)
        lines = [json.loads(ln) for ln in q.read_text().splitlines()]
        assert any(ev["kind"] == "unit_test_marker" for ev in lines)

    def test_session_rows_flow_from_local_run(self):
        g, prog, tol = _case(n=40, tol=1e-6)
        ses = ObsSession(ObsConfig(enabled=True, timeline=True))
        eng = Engine(prog, g, tolerance=tol, obs=ObsConfig(enabled=True))
        _, trace = eng.run(eng.init(g), max_steps=20, session=ses)
        assert ses.rows == trace
        assert len(ses.frames()) == len(trace)
        assert any(e["ph"] == "X" for e in ses.timeline.events)


# ---------------------------------------------------------------------------
# one span primitive: the profiler's trace, span_totals, the timeline
# ---------------------------------------------------------------------------

def _host_spans(trace_dir):
    """(name, start_ns, end_ns) of the ``graphlab.*`` events in the
    profiler's host planes."""
    import glob
    import os

    from jax.profiler import ProfileData
    path = max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in line.events
                    if ev.name.startswith("graphlab.")]
    return out


def _chromatic(n=60, **kw):
    from repro.core import ChromaticEngine
    g, prog, _ = _case(n=n, tol=1e-6)
    return ChromaticEngine(prog, g, tolerance=1e-6, **kw), g


class TestSpans:
    def test_run_writes_nested_spans_into_the_profiler(self, tmp_path):
        eng, g = _chromatic()
        state = eng.init(g)
        eng.run(state, max_steps=1)                  # compile outside
        ses = ObsSession(ObsConfig(enabled=True, timeline=True))
        with jax.profiler.trace(str(tmp_path)):
            eng.run(state, max_steps=3)
            with ses.span("graphlab.unit_test"):
                pass
        spans = _host_spans(str(tmp_path))
        runs = [s for s in spans if s[0] == "graphlab.run"]
        done = [s for s in spans if s[0] == "graphlab.done"]
        dispatch = [s for s in spans if s[0] == "graphlab.dispatch"]
        assert len(runs) == 1 and len(dispatch) == 3 and len(done) == 3
        _, lo, hi = runs[0]
        assert all(lo <= s <= e <= hi for _, s, e in done + dispatch)
        # a session span lands in both records
        assert [s for s in spans if s[0] == "graphlab.unit_test"]
        assert [e for e in ses.timeline.events
                if e["name"] == "graphlab.unit_test"]

    def test_set_up_spans_counted_once_per_engine(self):
        reset_span_totals()
        for _ in range(2):
            eng, g = _chromatic()
            eng.compile(eng.init(g))
        totals = span_totals()
        for name in ("graphlab.coloring", "graphlab.edge_sets",
                     "graphlab.upload", "graphlab.lower",
                     "graphlab.compile"):
            assert totals[name].count == 2, name
            assert totals[name].seconds > 0, name
        assert "graphlab.run" not in totals
        reset_span_totals()
        assert span_totals() == {}

    def test_program_spans_are_named_graphlab(self):
        with pytest.raises(ValueError, match="graphlab"):
            with span("step"):
                pass


SCOPES = ("graphlab.select", "graphlab.edge_weight", "graphlab.gather",
          "graphlab.apply", "graphlab.reschedule", "graphlab.scatter",
          "graphlab.edge_sets")


class TestDeviceScopes:
    def test_compiled_step_carries_scopes(self):
        """Every layer's scope survives into the compiled module's
        ``op_name`` metadata, and instructions (fusions included) map to
        the innermost ``graphlab.*`` component of theirs."""
        import re
        eng, g = _chromatic()
        text = eng.compile(eng.init(g)).as_text()
        scope_of = {}
        for line in text.splitlines():
            m = re.match(r'\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*'
                         r'op_name="([^"]*)"', line)
            if m:
                inner = [p for p in m.group(2).split("/")
                         if p.startswith("graphlab.")]
                if inner:
                    scope_of[m.group(1)] = inner[-1]
        assert set(SCOPES) <= set(scope_of.values())
        assert any("fusion" in name for name in scope_of)

    def test_run_while_scopes_done_and_sync(self):
        from repro.core.sync_op import FnSyncOp
        total = FnSyncOp(map_fn=lambda v: {"s": v["rank"]},
                         finalize=lambda z, n: z["s"], name="total")
        eng, g = _chromatic(sync_ops=(total,))
        text = jax.jit(lambda s: eng.run_while(s, 5)).lower(
            eng.init(g)).as_text(debug_info=True)
        assert "graphlab.done" in text and "graphlab.sync" in text
