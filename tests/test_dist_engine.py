"""DistributedEngine vs ChromaticEngine: same fixed point, versioned traffic.

The acceptance bar for the shard_map path (ISSUE 1): on a multi-device CPU
mesh the distributed engine must converge to the shared-memory chromatic
fixed point (<= 1e-5), and its ghost exchange must ship *only* vertices
whose data changed — the paper's Sec. 5.1 versioning guarantee.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps.lbp import LoopyBPProgram, make_mrf_graph
from repro.apps.pagerank import (PageRankProgram, exact_pagerank,
                                 make_pagerank_graph)
from repro.core import ChromaticEngine, DataGraph
from repro.core.update import ApplyOut
from repro.dist.engine import DistributedEngine
from repro.graphs.generators import power_law_graph

pytestmark = pytest.mark.skipif(
    jax.device_count() < 4, reason="needs 4 forced host devices "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=4)")


def _engines(prog, graph, mesh, tol):
    """Chromatic reference + distributed engine sharing one coloring."""
    ce = ChromaticEngine(prog, graph, tolerance=tol)
    de = DistributedEngine(prog, graph, mesh, tolerance=tol,
                           colors=np.asarray(ce.colors))
    return ce, de


class TestFixedPointParity:
    def test_pagerank_matches_chromatic(self, cpu_mesh, small_power_law):
        st = small_power_law
        g = make_pagerank_graph(st)
        prog = PageRankProgram(0.15, st.n_vertices)
        ce, de = _engines(prog, g, cpu_mesh, tol=1e-7)

        cs, _ = ce.run(ce.init(g), max_steps=300)
        ds, _ = de.run(de.init(), max_steps=300)

        ref = np.asarray(cs.graph.vertex_data["rank"])
        out = de.vertex_data(ds)["rank"]
        assert np.abs(out - ref).max() <= 1e-5
        assert int(ds.step_index) == int(cs.step_index)
        # both at the true fixed point, not just agreeing with each other
        exact = exact_pagerank(st, 0.15, iters=500)
        assert np.abs(out - exact).max() <= 1e-4

    def test_pagerank_kernel_interpret_matches_chromatic(
            self, cpu_mesh, small_power_law):
        """The Pallas kernel bodies (interpret mode) inside the sweep
        engine, over its per-color local edge sets."""
        st = small_power_law
        g = make_pagerank_graph(st)
        prog = PageRankProgram(0.15, st.n_vertices)
        ce = ChromaticEngine(prog, g, tolerance=1e-6)
        de = DistributedEngine(prog, g, cpu_mesh, tolerance=1e-6,
                               colors=np.asarray(ce.colors),
                               gas_interpret=True)
        assert de._color_sets is not None
        cs, _ = ce.run(ce.init(g), max_steps=300)
        ds, _ = de.run(de.init(), max_steps=300)
        ref = np.asarray(cs.graph.vertex_data["rank"])
        assert np.abs(de.vertex_data(ds)["rank"] - ref).max() <= 1e-5

    def test_pagerank_update_counts_match(self, cpu_mesh, small_power_law):
        st = small_power_law
        g = make_pagerank_graph(st)
        prog = PageRankProgram(0.15, st.n_vertices)
        ce, de = _engines(prog, g, cpu_mesh, tol=1e-6)

        cs, _ = ce.run(ce.init(g), max_steps=300)
        ds, _ = de.run(de.init(), max_steps=300)
        # identical adaptive schedules: same per-step active sets
        assert int(np.asarray(ds.update_count).sum()) == int(cs.total_updates)

    def test_lbp_matches_chromatic(self, cpu_mesh):
        st = power_law_graph(120, avg_degree=4, seed=3)
        g = make_mrf_graph(st, n_states=3, seed=1)
        prog = LoopyBPProgram(3)
        ce, de = _engines(prog, g, cpu_mesh, tol=1e-6)

        cs, _ = ce.run(ce.init(g), max_steps=150)
        ds, _ = de.run(de.init(), max_steps=150)

        ref = np.asarray(cs.graph.vertex_data["belief"])
        out = de.vertex_data(ds)["belief"]
        assert np.abs(out - ref).max() <= 1e-5
        # adjacent-edge writes (BP messages) must also agree where owned
        assert int(ds.step_index) == int(cs.step_index)

    def test_gather_only_rev_edata_reader(self, cpu_mesh):
        """A program that reads ctx.rev_edata in gather but never writes
        edges must declare reads_rev_edata=True and then match the
        shared-memory engine (which always supplies real rev_edata)."""

        class RevWeightedRank(PageRankProgram):
            reads_rev_edata = True

            def gather(self, ctx):
                # weight by the REVERSE edge's weight: exercises remote
                # reverse-edge caches without any edge writes
                return ctx.rev_edata["w"] * ctx.src["rank"]

        st = power_law_graph(150, avg_degree=4, seed=9)
        g = make_pagerank_graph(st)
        # asymmetric sub-stochastic weights so forward != reverse and the
        # iteration stays contractive
        w = np.asarray(g.edge_data["w"]) * (
            0.4 + 0.2 * (st.senders % 3).astype(np.float32))
        g = DataGraph.build(st, g.vertex_data, {"w": jnp.asarray(w)})
        prog = RevWeightedRank(0.15, st.n_vertices)
        ce, de = _engines(prog, g, cpu_mesh, tol=1e-6)

        cs, _ = ce.run(ce.init(g), max_steps=200)
        ds, _ = de.run(de.init(), max_steps=200)
        assert np.abs(de.vertex_data(ds)["rank"]
                      - np.asarray(cs.graph.vertex_data["rank"])).max() \
            <= 1e-5
        # edge data never changes: reverse caches stay valid with zero
        # edge-ghost traffic
        assert de.ghost_edge_rows_sent(ds) == 0

    def test_tiny_graph_pads_empty_machines(self, cpu_mesh):
        # |V| < n_machines * anything: some machines end up empty/padded
        st = power_law_graph(8, avg_degree=2, seed=5)
        g = make_pagerank_graph(st)
        prog = PageRankProgram(0.15, st.n_vertices)
        ce, de = _engines(prog, g, cpu_mesh, tol=1e-7)
        cs, _ = ce.run(ce.init(g), max_steps=100)
        ds, _ = de.run(de.init(), max_steps=100)
        assert np.abs(de.vertex_data(ds)["rank"]
                      - np.asarray(cs.graph.vertex_data["rank"])).max() <= 1e-5


class TestVersionedGhostTraffic:
    def test_first_sweep_ships_each_ghost_pair_once(self, cpu_mesh,
                                                    small_power_law):
        st = small_power_law
        g = make_pagerank_graph(st)
        prog = PageRankProgram(0.15, st.n_vertices)
        _, de = _engines(prog, g, cpu_mesh, tol=1e-7)
        ds = de.init()
        ds = de.step(ds)
        # every vertex is initially scheduled and has exactly one color, so
        # sweep 1 ships each (vertex, caching machine) pair exactly once —
        # "each machine receives each modified vertex data at most once"
        assert de.ghost_rows_sent(ds) == de.total_ghost_slots()

    def test_traffic_decays_as_schedule_drains(self, cpu_mesh,
                                               small_power_law):
        st = small_power_law
        g = make_pagerank_graph(st)
        prog = PageRankProgram(0.15, st.n_vertices)
        _, de = _engines(prog, g, cpu_mesh, tol=1e-7)
        ds, trace = de.run(de.init(), max_steps=300)
        n_steps = int(ds.step_index)
        assert n_steps > 2
        total = de.ghost_rows_sent(ds)
        # strictly fewer than the unversioned exchange would ship
        assert total < n_steps * de.total_ghost_slots()

    def test_converged_step_ships_nothing(self, cpu_mesh, small_power_law):
        st = small_power_law
        g = make_pagerank_graph(st)
        prog = PageRankProgram(0.15, st.n_vertices)
        _, de = _engines(prog, g, cpu_mesh, tol=1e-7)
        ds, _ = de.run(de.init(), max_steps=300)
        before = de.ghost_rows_sent(ds)
        ds = de.step(ds)  # empty scheduler: no updates, no traffic
        assert de.ghost_rows_sent(ds) == before
        assert de.ghost_edge_rows_sent(ds) == 0  # no edge_out program

    def test_lbp_edge_traffic_versioned(self, cpu_mesh):
        st = power_law_graph(120, avg_degree=4, seed=3)
        g = make_mrf_graph(st, n_states=3, seed=1)
        prog = LoopyBPProgram(3)
        _, de = _engines(prog, g, cpu_mesh, tol=1e-6)
        ds, _ = de.run(de.init(), max_steps=150)
        before_v, before_e = (de.ghost_rows_sent(ds),
                              de.ghost_edge_rows_sent(ds))
        assert before_e > 0  # cross-machine reverse edges exist
        ds = de.step(ds)
        assert de.ghost_rows_sent(ds) == before_v
        assert de.ghost_edge_rows_sent(ds) == before_e
