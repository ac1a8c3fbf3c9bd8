"""Fused-engine equivalence (ISSUE 2 acceptance): ChromaticEngine with
per-color edge ranges + the fused GAS kernel matches the seed dense engine
to ≤ 1e-5 on PageRank, ALS, and LBP — LBP exercising the non-fuseable
fallback — and the fused path's edges-touched stays strictly below the
dense path's ``num_colors × E`` per sweep.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps.als import ALSProgram, make_als_graph
from repro.apps.lbp import LoopyBPProgram, make_mrf_graph
from repro.apps.pagerank import PageRankProgram, make_pagerank_graph
from repro.core.bsp import BSPEngine
from repro.core.chromatic import ChromaticEngine
from repro.core.dynamic import DynamicEngine
from repro.graphs.generators import grid3d_graph, power_law_graph

TOL = 1e-5


def _fixed_point(engine, graph, leaf, max_steps=60):
    state, _ = engine.run(engine.init(graph), max_steps=max_steps)
    return np.asarray(state.graph.vertex_data[leaf]), state


@pytest.fixture(scope="module")
def pagerank_setup():
    st = power_law_graph(260, avg_degree=5, seed=11)
    g = make_pagerank_graph(st)
    return PageRankProgram(n_vertices=st.n_vertices), g


class TestChromaticEquivalence:
    def test_pagerank(self, pagerank_setup):
        prog, g = pagerank_setup
        dense = ChromaticEngine(prog, g, tolerance=1e-6, use_fused=False)
        fused = ChromaticEngine(prog, g, tolerance=1e-6, use_fused=True)
        assert not dense.use_fused and fused.use_fused
        rd, sd = _fixed_point(dense, g, "rank")
        rf, sf = _fixed_point(fused, g, "rank")
        assert np.abs(rf - rd).max() <= TOL
        # adaptivity: fused sweeps touch strictly fewer edges than dense
        assert int(sf.edges_touched) < int(sd.edges_touched)

    def test_pagerank_kernel_interpret(self, pagerank_setup):
        """The real Pallas kernel body (interpret mode) inside the engine."""
        prog, g = pagerank_setup
        dense = ChromaticEngine(prog, g, tolerance=1e-4, use_fused=False)
        kern = ChromaticEngine(prog, g, tolerance=1e-4, use_fused=True,
                               gas_interpret=True)
        rd, _ = _fixed_point(dense, g, "rank", max_steps=8)
        rk, _ = _fixed_point(kern, g, "rank", max_steps=8)
        assert np.abs(rk - rd).max() <= TOL

    def test_als(self):
        # 1000 drawn ratings leave ~260 distinct training pairs for the 65
        # four-dimensional factors.  With 260 draws there were 111: the
        # problem is underdetermined, its sweeps drift along flat
        # directions, and 40 of them grow the 1-ulp rounding difference
        # between XLA's fusions of the two jitted steps past 1e-5.
        g, _ = make_als_graph(30, 35, 1000, d=4, seed=1)
        prog = ALSProgram(d=4)
        dense = ChromaticEngine(prog, g, tolerance=1e-4, use_fused=False)
        fused = ChromaticEngine(prog, g, tolerance=1e-4, use_fused=True)
        assert fused.use_fused
        fd, _ = _fixed_point(dense, g, "factor", max_steps=40)
        ff, _ = _fixed_point(fused, g, "factor", max_steps=40)
        assert np.abs(ff - fd).max() <= TOL

    def test_lbp_falls_back_to_dense(self):
        st = grid3d_graph(4, 4, 3)
        g = make_mrf_graph(st, n_states=3, seed=0)
        prog = LoopyBPProgram(n_states=3)
        dense = ChromaticEngine(prog, g, tolerance=1e-4, use_fused=False)
        fused = ChromaticEngine(prog, g, tolerance=1e-4, use_fused=True)
        # edge writes are non-fuseable: requesting fusion must fall back
        assert not fused.use_fused and fused._consts["gas"] is None
        bd, _ = _fixed_point(dense, g, "belief", max_steps=30)
        bf, _ = _fixed_point(fused, g, "belief", max_steps=30)
        assert np.abs(bf - bd).max() <= TOL


def _fixed_point_in_calls(engine, graph, leaf, calls):
    """The fixed point reached in several ``run`` calls of ``calls`` steps
    each, then again from the initial state in one call of their sum:
    returns both answers."""
    state0 = engine.init(graph)
    state = state0
    for k in calls:
        state, _ = engine.run(state, max_steps=k)
    again, _ = engine.run(state0, max_steps=sum(calls))
    return (np.asarray(state.graph.vertex_data[leaf]),
            np.asarray(again.graph.vertex_data[leaf]))


@pytest.mark.parametrize("interpret,tol,calls", [
    (None, 1e-6, (5, 10, 45)),          # the jnp oracle, to convergence
    (True, 1e-4, (3, 3, 2)),            # the Pallas kernel body
], ids=["oracle", "interpret"])
def test_pagerank_fused_matches_dense_over_calls_and_restart(
        pagerank_setup, interpret, tol, calls):
    """The prepared weights serve every ``run`` call and a restart from
    the initial state: each agrees with the dense path, and the restart
    repeats the answer of the calls bit for bit."""
    prog, g = pagerank_setup
    dense = ChromaticEngine(prog, g, tolerance=tol, use_fused=False)
    fused = ChromaticEngine(prog, g, tolerance=tol, use_fused=True,
                            gas_interpret=interpret)
    rd, _ = _fixed_point(dense, g, "rank", max_steps=sum(calls))
    rf, again = _fixed_point_in_calls(fused, g, "rank", calls)
    assert np.abs(rf - rd).max() <= TOL
    np.testing.assert_array_equal(again, rf)


class TestPreparedWeights:
    """The fused gather's per-color edge weights are prepared once per edge
    data (``Engine.prepare_weights``) and carried in the state."""

    @staticmethod
    def _weights_count():
        from repro.obs import span_totals
        total = span_totals().get("graphlab.weights")
        return total.count if total else 0

    @staticmethod
    def _case(app):
        if app == "pagerank":
            st = power_law_graph(600, avg_degree=8, seed=11)
            return (PageRankProgram(n_vertices=st.n_vertices),
                    make_pagerank_graph(st))
        g, _ = make_als_graph(30, 35, 1000, d=4, seed=1)
        return ALSProgram(d=4), g

    @pytest.mark.parametrize("app", ["pagerank", "als"])
    def test_prepared_equal_numpy_weights_by_perm(self, app):
        from repro.core.update import fused_gather_leaves
        prog, g = self._case(app)
        eng = ChromaticEngine(prog, g, tolerance=1e-4)
        state = eng.init(g)
        leaves, _ = fused_gather_leaves(prog)
        edata = {k: np.asarray(v) for k, v in g.edge_data.items()}
        full = [np.asarray(leaf.weight(edata)).astype(np.float32)
                for leaf in leaves]
        assert len(state.gas_weights) == len(eng._phase_groups)
        for (first, n), perm, group in zip(eng._phase_groups, eng._gas_perms,
                                           state.gas_weights):
            perm = np.asarray(perm)
            assert perm.shape[0] == n and len(group) == len(leaves)
            for w, prepared in zip(full, group):
                assert prepared.dtype == jnp.float32
                np.testing.assert_array_equal(np.asarray(prepared),
                                              w[perm].reshape(-1))
        # the step gathers nothing through a perm: the sets it takes carry
        # none
        assert all(s["gather"].perm is None for s in eng._consts["gas"])

    @pytest.mark.parametrize("make", [
        lambda p, g: BSPEngine(p, g, tolerance=1e-6, use_fused=True),
        lambda p, g: DynamicEngine(p, g, pipeline_length=64, tolerance=1e-6,
                                   use_fused=True),
    ], ids=["bsp", "dynamic"])
    def test_full_edge_engines_prepare_nothing(self, pagerank_setup, make):
        prog, g = pagerank_setup
        eng = make(prog, g)
        before = self._weights_count()
        state = eng.init(g)
        assert eng.use_fused and state.gas_weights == ()
        state, _ = eng.run(state, max_steps=3)
        assert state.gas_weights == ()
        assert self._weights_count() == before

    def test_replaced_edge_data_is_prepared_again(self):
        prog, g = self._case("pagerank")
        eng = ChromaticEngine(prog, g, tolerance=1e-6)
        state0 = eng.init(g)
        # host code that keeps the edge data keeps the weights
        kept = state0.replace(graph=state0.graph.replace(
            vertex_data=dict(state0.graph.vertex_data)))
        assert kept.gas_weights is state0.gas_weights
        g2 = g.replace(edge_data={"w": g.edge_data["w"] * 0.75})
        stale = state0.replace(graph=state0.graph.replace(
            edge_data=g2.edge_data))
        assert stale.gas_weights == ()
        before = self._weights_count()
        got, _ = eng.run(stale, max_steps=60)
        assert self._weights_count() == before + 1
        fresh = ChromaticEngine(prog, g2, colors=eng.colors, tolerance=1e-6)
        want, _ = fresh.run(fresh.init(g2), max_steps=60)
        np.testing.assert_array_equal(
            np.asarray(got.graph.vertex_data["rank"]),
            np.asarray(want.graph.vertex_data["rank"]))
        assert int(got.step_index) == int(want.step_index)

    def test_step_gathers_no_weights_and_prepares_once(self):
        """The compiled step holds no ``gather`` under
        ``graphlab.edge_weight`` (only each color-step's slice of its
        prepared row), and ``init`` plus three ``run`` calls from the
        initial state prepare once."""
        import importlib.util
        import os
        import re
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "bench", "scopes.py")
        spec = importlib.util.spec_from_file_location("bench_scopes", path)
        scopes = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(scopes)

        prog, g = self._case("pagerank")
        before = self._weights_count()
        eng = ChromaticEngine(prog, g, tolerance=1e-6)
        state0 = eng.init(g)
        assert [n for _, n in eng._phase_groups].count(1) >= 1
        assert max(n for _, n in eng._phase_groups) > 1
        text = eng.compile(state0).as_text()
        scope_of = scopes.scope_map(text)
        opcodes = []
        for line in text.splitlines():
            m = re.match(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*\S+\s+"
                         r"([\w\-]+)\(", line)
            if m and scope_of.get(m.group(1)) == "graphlab.edge_weight":
                opcodes.append(m.group(2))
        assert "dynamic-slice" in opcodes
        assert "gather" not in opcodes, opcodes
        for _ in range(3):
            state, _ = eng.run(state0, max_steps=4)
        assert self._weights_count() == before + 1
        # the step hands the same weights on instead of copying them
        assert all(a is b for a, b in zip(jax.tree.leaves(state.gas_weights),
                                          jax.tree.leaves(state0.gas_weights)))


class TestEdgesTouched:
    def test_first_sweep_below_dense(self, pagerank_setup):
        """Everything scheduled: a fused sweep touches exactly E edges
        (Σ_c E_c), vs the dense sweep's num_colors × E."""
        prog, g = pagerank_setup
        E = g.n_edges
        fused = ChromaticEngine(prog, g, use_fused=True)
        dense = ChromaticEngine(prog, g, use_fused=False)
        sf = fused.step(fused.init(g))
        sd = dense.step(dense.init(g))
        assert int(sf.edges_touched) == E
        assert int(sd.edges_touched) == dense.num_colors * E
        assert int(sf.edges_touched) < int(sd.edges_touched)

    def test_drained_scheduler_touches_fewer(self, pagerank_setup):
        """Active-block skipping: scheduling one vertex costs ≤ the edge
        blocks of the row blocks its color-steps activate, not E."""
        prog, g = pagerank_setup
        fused = ChromaticEngine(prog, g, use_fused=True)
        prio = np.zeros(g.n_vertices, np.float32)
        prio[3] = 1.0
        s = fused.step(fused.init(g, initial_prio=jnp.asarray(prio)))
        assert 0 < int(s.edges_touched) < g.n_edges


class TestOtherEngines:
    def test_bsp_fused_matches_dense(self, pagerank_setup):
        prog, g = pagerank_setup
        rd, _ = _fixed_point(
            BSPEngine(prog, g, tolerance=1e-6, use_fused=False), g, "rank")
        rf, _ = _fixed_point(
            BSPEngine(prog, g, tolerance=1e-6, use_fused=True), g, "rank")
        assert np.abs(rf - rd).max() <= TOL

    def test_dynamic_fused_matches_dense(self, pagerank_setup):
        prog, g = pagerank_setup
        mk = lambda fused: DynamicEngine(prog, g, pipeline_length=64,
                                         tolerance=1e-6, use_fused=fused)
        rd, _ = _fixed_point(mk(False), g, "rank", max_steps=80)
        rf, _ = _fixed_point(mk(True), g, "rank", max_steps=80)
        assert np.abs(rf - rd).max() <= TOL


class TestDistributedFused:
    def test_dist_pagerank_matches_chromatic(self, cpu_mesh, pagerank_setup):
        from repro.dist.engine import DistributedEngine
        prog, g = pagerank_setup
        dist = DistributedEngine(prog, g, cpu_mesh, tolerance=1e-6)
        assert dist._use_fused  # fused local compute inside shard_map
        chrom = ChromaticEngine(prog, g, colors=dist.colors, tolerance=1e-6,
                                use_fused=True)
        ds, _ = dist.run(dist.init(), max_steps=60)
        rv = dist.vertex_data(ds)["rank"]
        rc, _ = _fixed_point(chrom, g, "rank")
        assert np.abs(rv - rc).max() <= TOL

    @pytest.mark.parametrize("interpret", [None, True],
                             ids=["oracle", "interpret"])
    def test_dist_als_fused_matches_dense(self, cpu_mesh, interpret):
        """ALS's fused declaration (its ``xxt`` leaf a
        ``weighted_src_outer``) through the distributed engine's fused
        local compute, on the materialised table (oracle) and formed in
        the row-block kernel (interpret), against the dense gather."""
        from repro.dist.engine import DistributedEngine
        g, _ = make_als_graph(30, 35, 1000, d=4, seed=1)
        prog = ALSProgram(d=4)
        fused = DistributedEngine(prog, g, cpu_mesh, tolerance=1e-4,
                                  gas_interpret=interpret)
        dense = DistributedEngine(prog, g, cpu_mesh, tolerance=1e-4,
                                  use_fused=False)
        assert fused._use_fused and not dense._use_fused
        text = fused._jit_step.lower(fused.init(), fused._tables).as_text(
            debug_info=True)
        assert ("graphlab.outer" in text) == bool(interpret)
        sf, _ = fused.run(fused.init(), max_steps=40)
        sd, _ = dense.run(dense.init(), max_steps=40)
        assert np.abs(fused.vertex_data(sf)["factor"]
                      - dense.vertex_data(sd)["factor"]).max() <= TOL

    def test_dist_dense_knob_matches_fused(self, cpu_mesh, pagerank_setup):
        """use_fused=False forces the seed dense shard_map body (A/B)."""
        from repro.dist.engine import DistributedEngine
        prog, g = pagerank_setup
        fused = DistributedEngine(prog, g, cpu_mesh, tolerance=1e-6)
        dense = DistributedEngine(prog, g, cpu_mesh, tolerance=1e-6,
                                  use_fused=False)
        assert fused._use_fused and not dense._use_fused
        sf, _ = fused.run(fused.init(), max_steps=60)
        sd, _ = dense.run(dense.init(), max_steps=60)
        assert np.abs(fused.vertex_data(sf)["rank"]
                      - dense.vertex_data(sd)["rank"]).max() <= TOL


class TestRegistryKinds:
    """src_copy and degree_normalized_src through a real engine step —
    the app programs only exercise weighted_src_sum."""

    def _run_kind(self, kind):
        from repro.core.update import ApplyOut, FusedGather, VertexProgram

        class KindProgram(VertexProgram):
            combiner = "sum"
            schedule_neighbors = True

            def gather(self, ctx):
                x = ctx.src["x"]
                if kind == "degree_normalized_src":
                    return x / jnp.maximum(
                        ctx.src_deg.astype(x.dtype), 1.0)[:, None]
                return x

            def fused_gather(self):
                return FusedGather(kind, feature=lambda v: v["x"])

            def apply(self, vertex_data, acc, glob=None):
                return ApplyOut(
                    {"x": acc}, jnp.sum(jnp.abs(acc - vertex_data["x"]),
                                        axis=-1))

        st = power_law_graph(150, avg_degree=4, seed=2)
        rng = np.random.default_rng(0)
        from repro.core.graph import DataGraph
        g = DataGraph.build(st, {"x": jnp.asarray(
            rng.normal(size=(st.n_vertices, 6)), jnp.float32)})
        prog = KindProgram()
        res = {}
        for fused in (False, True):
            eng = BSPEngine(prog, g, use_fused=fused)
            assert eng.use_fused == fused
            s = eng.step(eng.init(g))
            res[fused] = np.asarray(s.graph.vertex_data["x"])
        return res

    @pytest.mark.parametrize("kind",
                             ["src_copy", "degree_normalized_src"])
    def test_kind_matches_dense(self, kind):
        res = self._run_kind(kind)
        assert np.abs(res[True] - res[False]).max() <= TOL


class TestFullEdgesRetrace:
    def test_run_while_after_run_does_not_leak_tracers(self):
        """Regression: the lazy full-graph EdgeSet is first built while
        tracing the jitted step; without ensure_compile_time_eval the
        cached index arrays were that trace's tracers, and any second
        trace (run_while's while_loop body) crashed with an
        UnexpectedTracerError."""
        st = power_law_graph(120, avg_degree=4, seed=0)
        g = make_pagerank_graph(st)
        prog = PageRankProgram(0.15, st.n_vertices)
        eng = DynamicEngine(prog, g, pipeline_length=32, tolerance=1e-6)
        assert eng.use_fused
        s, _ = eng.run(eng.init(g), max_steps=500)        # first trace
        sw = eng.run_while(eng.init(g), max_steps=500)    # second trace
        assert np.abs(np.asarray(sw.graph.vertex_data["rank"])
                      - np.asarray(s.graph.vertex_data["rank"])).max() \
            <= 1e-5
