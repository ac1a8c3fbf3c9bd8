"""Dynamic-graph ingestion tests (repro/stream/, DESIGN.md §3.11).

The subsystem's three contracts, each tested directly:

  1. **Zero recompilations** — applying a delta batch within capacity
     slack never retraces the jitted step (trace counters on every
     engine), and the GAS active-block bitmap confines post-delta work to
     the touched row blocks.
  2. **Incremental ≡ rebuild** — hypothesis property: converge a prefix,
     stream the remainder as delta batches, reconverge; the fixed point
     matches an engine built from scratch on the full graph (≤ 1e-5),
     across local/dist engines × PageRank/LBP × 2- and 4-machine meshes,
     including batches that force a ``regrow()``.
  3. **An atom file is a replayable delta stream** — journals written by
     ``core/partition.py:build_atoms`` replay through ``apply_delta`` into
     an empty streaming engine and reproduce the original graph's fixed
     point: loading and growing are the same operation.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.apps.als import ALSProgram, als_rmse
from repro.apps.lbp import LoopyBPProgram
from repro.apps.pagerank import (PageRankProgram, exact_pagerank,
                                 make_pagerank_graph)
from repro.core import (ChromaticEngine, DataGraph, DynamicEngine, Engine,
                        UnsupportedStreamingError)
from repro.core.graph import GraphStructure
from repro.core.partition import build_atoms, overpartition
from repro.core.update import FusedGather
from repro.dist import DistributedEngine, DistributedLockingEngine
from repro.graphs.generators import power_law_graph
from repro.stream import (AddEdge, AddVertex, CapacityError, DelEdge,
                          DeltaBatch, DeltaJournal, DelVertex,
                          SetEdgeData, SetVertexData, SlackConfig,
                          SnapshotInFlightError,
                          StreamingGraph, als_rating_arrivals, apply_delta,
                          apply_delta_growing, lbp_arrivals, lbp_churn,
                          make_dist_engine, make_local_engine,
                          pagerank_arrivals, pagerank_churn, readback,
                          stream_colors)

pytestmark = pytest.mark.skipif(
    jax.device_count() < 4, reason="needs 4 forced host devices "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=4)")

ROOMY = SlackConfig(edge_frac=1.0, edge_min=8)


class _DegreePageRank(PageRankProgram):
    """PageRank on a graph without edge data: each source's rank is split
    over its live out-degree (``degree_normalized_src``)."""

    def gather(self, ctx):
        return ctx.src["rank"] / jnp.maximum(
            ctx.src_deg.astype(jnp.float32), 1.0)

    def fused_gather(self):
        return FusedGather("degree_normalized_src",
                           feature=lambda v: v["rank"])


def _mesh(n):
    devs = np.asarray(jax.devices()[:n]).reshape(n, 1)
    return jax.sharding.Mesh(devs, ("data", "model"))


def _connected_power_law(n, deg, seed):
    """power_law_graph plus a path: the churn sources (and the snapshot
    marker wave) need every vertex reachable."""
    st_ = power_law_graph(n, avg_degree=deg, seed=seed)
    pairs = {(min(int(s), int(r)), max(int(s), int(r)))
             for s, r in zip(st_.senders, st_.receivers) if s != r}
    pairs |= {(i, i + 1) for i in range(n - 1)}
    a = np.asarray([p[0] for p in sorted(pairs)], np.int32)
    b = np.asarray([p[1] for p in sorted(pairs)], np.int32)
    st2, _ = GraphStructure.from_edges(np.concatenate([a, b]),
                                       np.concatenate([b, a]), n)
    return st2


# ---------------------------------------------------------------------------
# StreamingGraph unit behaviour
# ---------------------------------------------------------------------------

class TestStreamingGraph:
    def test_build_preserves_graph(self):
        st_ = power_law_graph(80, avg_degree=5, seed=0)
        sg, perm = StreamingGraph.build(st_)
        assert sg.n_real == 80 and sg.n_real_edges == st_.n_edges
        # capacity receivers sorted (the GAS invariant), real slots match
        assert (np.diff(sg.receivers) >= 0).all()
        assert np.array_equal(sg.senders[perm], st_.senders)
        assert np.array_equal(sg.receivers[perm], st_.receivers)
        # reverse links survive the slot mapping
        has = st_.reverse_perm >= 0
        assert np.array_equal(sg.rev_idx[perm[has]],
                              perm[st_.reverse_perm[has]])
        # slack slots are inert self-loops, their own reverse
        slack = ~sg.edge_mask
        assert np.array_equal(sg.senders[slack], sg.receivers[slack])
        assert (sg.rev_idx[slack] == np.nonzero(slack)[0]).all()
        cap = sg.capacity_structure()
        assert cap.is_symmetric() == st_.is_symmetric()

    def test_add_edge_links_reverse_and_degrees(self):
        st_, _ = GraphStructure.undirected([0, 1], [1, 2], 5)
        sg, _ = StreamingGraph.build(st_, SlackConfig(edge_min=4,
                                                      vertex_min=2))
        a = sg.add_edge(3, 4)
        assert sg.rev_idx[a] == -1
        b = sg.add_edge(4, 3)
        assert sg.rev_idx[a] == b and sg.rev_idx[b] == a
        assert sg.out_deg[3] == 1 and sg.fill[4] == 1
        with pytest.raises(ValueError):
            sg.add_edge(3, 4)  # duplicate

    def test_capacity_errors(self):
        st_, _ = GraphStructure.undirected([0], [1], 3)
        sg, _ = StreamingGraph.build(
            st_, SlackConfig(edge_min=1, vertex_min=1, edge_frac=0.0,
                             vertex_frac=0.0))
        sg.add_edge(2, 1)  # fills vertex 1's single slack slot
        with pytest.raises(CapacityError):
            sg.add_edge(1, 1)
        v = sg.add_vertex()
        assert v == 3
        with pytest.raises(CapacityError):
            sg.add_vertex()

    def test_compact_roundtrip(self):
        st_ = power_law_graph(60, avg_degree=4, seed=1)
        g = make_pagerank_graph(st_)
        sg, perm = StreamingGraph.build(st_)
        from repro.stream import pad_edge_data, pad_vertex_data
        vd = pad_vertex_data(g.vertex_data, sg.n_cap)
        ed = pad_edge_data(g.edge_data, sg, perm)
        out = sg.compact(vd, ed)
        assert out.structure.n_vertices == 60
        assert out.structure.n_edges == st_.n_edges
        # same edge multiset with matching weights
        key = lambda s_, r_: np.asarray(s_, np.int64) * 60 + r_
        a = np.sort(key(out.structure.senders, out.structure.receivers))
        b = np.sort(key(st_.senders, st_.receivers))
        assert np.array_equal(a, b)


class TestDeletion:
    def test_del_edge_swap_keeps_region_contiguous(self):
        st_, _ = GraphStructure.undirected([0, 1, 2], [1, 2, 3], 5)
        sg, _ = StreamingGraph.build(st_, SlackConfig(edge_min=4,
                                                      vertex_min=2))
        # give vertex 1 a second in-edge so deleting the first swaps
        sg.add_edge(3, 1)
        n0 = sg.n_real_edges
        slot, moved_from = sg.del_edge(0, 1)
        assert sg.n_real_edges == n0 - 1
        assert (0, 1) not in sg.edge_slot
        # the region tail moved into the hole; the vacated slot is inert
        assert moved_from is not None
        assert sg.senders[slot] == 3 and sg.edge_slot[(3, 1)] == slot
        assert not sg.edge_mask[moved_from]
        assert sg.senders[moved_from] == 1  # inert self-loop of dst
        assert sg.rev_idx[moved_from] == moved_from
        # the surviving twin (1, 0) lost its reverse link
        assert sg.rev_idx[sg.slot_of(1, 0)] == -1
        # the in-region stays contiguous: fill occupied slots, no holes
        occ = sg.in_slots(1)
        assert sg.edge_mask[occ].all() and len(occ) == 2
        with pytest.raises(KeyError):
            sg.del_edge(0, 1)  # already gone

    def test_delete_then_readd_relinks_reverse(self):
        st_, _ = GraphStructure.undirected([0, 1], [1, 2], 4)
        sg, _ = StreamingGraph.build(st_, SlackConfig(edge_min=4,
                                                      vertex_min=2))
        sg.del_edge(0, 1)
        a = sg.add_edge(0, 1)
        b = sg.slot_of(1, 0)
        assert sg.rev_idx[a] == b and sg.rev_idx[b] == a

    def test_del_vertex_requires_isolation_then_frees_slot(self):
        st_, _ = GraphStructure.undirected([0, 1], [1, 2], 4)
        sg, _ = StreamingGraph.build(st_, SlackConfig(edge_min=4,
                                                      vertex_min=2))
        with pytest.raises(ValueError):
            sg.del_vertex(2)  # still has incident edges
        sg.del_edge(1, 2)
        sg.del_edge(2, 1)
        sg.del_vertex(2)
        assert not sg.vertex_active[2]
        # the freed id is reusable by a later AddVertex
        assert sg.add_vertex() == 2
        assert sg.vertex_active[2]




class TestZeroRecompile:
    def test_local_fused_and_dense(self):
        st_ = power_law_graph(200, avg_degree=5, seed=1)
        prefix_g, batches, _ = pagerank_arrivals(st_, prefix_frac=0.85,
                                                 n_batches=3, seed=0)
        prog = PageRankProgram(0.15, st_.n_vertices)
        for fused in (True, False):
            eng, state = make_local_engine(prog, prefix_g, tolerance=1e-6,
                                           slack=ROOMY, use_fused=fused)
            state, _ = eng.run(state, max_steps=100)
            before = eng._trace_count
            assert before >= 1
            for b in batches:
                state = apply_delta(eng, state, b)
                state, _ = eng.run(state, max_steps=100)
            assert eng._trace_count == before, (
                "delta application retraced the jitted step")

    def test_dist_engines(self, cpu_mesh):
        st_ = power_law_graph(150, avg_degree=5, seed=2)
        prefix_g, batches, _ = pagerank_arrivals(st_, prefix_frac=0.85,
                                                 n_batches=2, seed=0)
        prog = PageRankProgram(0.15, st_.n_vertices)
        for cls, kw in [(DistributedEngine, {}),
                        (DistributedLockingEngine,
                         {"pipeline_length": 32})]:
            eng, state = make_dist_engine(prog, prefix_g, cpu_mesh,
                                          engine_cls=cls, tolerance=1e-6,
                                          slack=ROOMY, **kw)
            state, _ = eng.run(state, max_steps=200)
            before = eng._trace_count
            assert before >= 1
            for b in batches:
                state = apply_delta(eng, state, b)
                state, _ = eng.run(state, max_steps=200)
            assert eng._trace_count == before, cls.__name__

    @pytest.mark.parametrize("kind",
                             ["delete", "set_edge_data", "no_edge_data"])
    def test_local_fused_weights_follow_each_batch(self, kind):
        """The fused streaming engine's edge weights are prepared on the
        capacity edge data, masked by the live edge mask, with source
        degrees from the live tables: once per batch, without retracing
        the step, and the engine reaches the dense streaming engine's
        fixed point after every batch.  ``no_edge_data`` streams a graph
        without edge data, whose weights come from the tables alone
        (``degree_normalized_src``), through an add and a delete batch."""
        from repro.obs import span_totals

        def preps():
            total = span_totals().get("graphlab.weights")
            return total.count if total else 0

        st_ = _connected_power_law(120, 5, 4)
        order = np.random.default_rng(0).permutation(st_.n_edges)
        picked = [[(int(st_.senders[e]), int(st_.receivers[e]))
                   for e in picks] for picks in (order[:6], order[6:12])]
        if kind == "no_edge_data":
            g = DataGraph(vertex_data={"rank": jnp.full(
                st_.n_vertices, 1.0 / st_.n_vertices, jnp.float32)},
                edge_data=None, structure=st_)
            prog = _DegreePageRank(0.15, st_.n_vertices)
            have = set(zip(st_.senders.tolist(), st_.receivers.tolist()))
            absent = dict.fromkeys(
                (int(s), int(r)) for s, r in zip(order % 120, order // 7 % 120)
                if s != r and (s, r) not in have)
            batches = [DeltaBatch([AddEdge(s, r) for s, r in list(absent)[:6]]),
                       DeltaBatch([DelEdge(s, r) for s, r in picked[0]])]
        else:
            g = make_pagerank_graph(st_)
            prog = PageRankProgram(0.15, st_.n_vertices)
            w = np.asarray(g.edge_data["w"])
            batches = [DeltaBatch(
                [DelEdge(s, r) for s, r in edges] if kind == "delete" else
                [SetEdgeData(s, r, {"w": np.float32(0.5 * w[e])})
                 for (s, r), e in zip(edges, picks)])
                for edges, picks in zip(picked, (order[:6], order[6:12]))]
        fused, fs = make_local_engine(prog, g, tolerance=1e-7, slack=ROOMY)
        dense, ds = make_local_engine(prog, g, tolerance=1e-7, slack=ROOMY,
                                      use_fused=False)
        assert fused.use_fused and not dense.use_fused
        fs, _ = fused.run(fs, max_steps=300)
        ds, _ = dense.run(ds, max_steps=300)
        traces = fused._trace_count
        for b in batches:
            fs = apply_delta(fused, fs, b)
            ds = apply_delta(dense, ds, b)
            assert fs.gas_weights == ()
            before = preps()
            fs, _ = fused.run(fs, max_steps=300)
            ds, _ = dense.run(ds, max_steps=300)
            assert preps() == before + 1
            (row,), = fs.gas_weights
            tables = {k: np.asarray(v) for k, v in fused._tables.items()}
            if kind == "no_edge_data":
                deg = tables["out_deg"][tables["senders"]]
                live = 1.0 / np.maximum(deg.astype(np.float32), 1.0)
            else:
                live = np.asarray(fs.graph.edge_data["w"])
            want = np.where(tables["edge_mask"], live, 0.0)
            np.testing.assert_array_equal(np.asarray(row)[:want.size], want)
            assert int(fs.step_index) < 300
            np.testing.assert_allclose(
                np.asarray(readback(fused, fs).vertex_data["rank"]),
                np.asarray(readback(dense, ds).vertex_data["rank"]),
                rtol=0, atol=1e-5)
        assert fused._trace_count == traces

    def test_small_delta_activates_few_row_blocks(self):
        """The GAS active-block wiring: reconverging a one-edge delta must
        stream far fewer edges per step than full sweeps do — only the row
        blocks holding the re-seeded scopes are gathered."""
        st_ = power_law_graph(6000, avg_degree=5, seed=3)
        g = make_pagerank_graph(st_)
        prog = PageRankProgram(0.8, st_.n_vertices)  # strong teleport
        eng, state = make_local_engine(prog, g, tolerance=1e-6, slack=ROOMY)
        assert eng.use_fused
        state, _ = eng.run(state, max_steps=100)
        steps0, touched0 = int(state.step_index), int(state.edges_touched)
        per_sweep = touched0 / max(steps0, 1)

        # two low-degree endpoints: their closed neighborhoods span only a
        # handful of the ~47 row blocks
        deg = st_.in_degree + st_.out_degree
        u = int(np.argmin(deg[: 3000]))
        v = int(np.argmin(deg[3000:])) + 3000
        batch = DeltaBatch([AddEdge(u, v), AddEdge(v, u)])
        state = apply_delta(eng, state, batch)
        state, _ = eng.run(state, max_steps=100)
        steps1 = int(state.step_index) - steps0
        touched1 = int(state.edges_touched) - touched0
        assert steps1 >= 1
        # post-delta steps touch a small fraction of the edge set
        assert touched1 / steps1 < 0.5 * per_sweep, (
            touched1 / steps1, per_sweep)


# ---------------------------------------------------------------------------
# contract 2: incremental ≡ rebuild (the hypothesis property)
# ---------------------------------------------------------------------------

def _pagerank_case(n, seed, prefix_frac, n_batches):
    st_ = power_law_graph(n, avg_degree=5, seed=seed)
    prefix_g, batches, full_g = pagerank_arrivals(
        st_, prefix_frac=prefix_frac, n_batches=n_batches, seed=seed)
    prog = PageRankProgram(0.15, st_.n_vertices)
    scratch = Engine(prog, full_g, tolerance=1e-7)
    s, _ = scratch.run(scratch.init(full_g), max_steps=300)
    ref = np.asarray(s.graph.vertex_data["rank"])
    return prog, prefix_g, batches, ref, "rank", 1e-7, 300


# The LBP residual is Σ_k |Δ belief_k| over f32 log-beliefs, whose ulp here
# is 2**-22 to 2**-20 (|b| up to 16), and the incoming-message sums behind
# them round coarser still at hubs.  Rounding carried around the graph's
# loops leaves cycles that move beliefs by several ulps, so a vertex's
# priority (its neighbors' residuals summed) can stay above 1e-6 for good:
# up to 2.4e-5 after the deltas, and the scratch run itself does not reach
# 1e-6 in 80 sweeps on some seeds.  16 ulps of |b| < 16 clears that floor.
LBP_TOL = 16 * 2.0 ** -20


def _lbp_case(n, seed, prefix_frac, n_batches):
    st_ = power_law_graph(n, avg_degree=4, seed=seed)
    prefix_g, batches, full_g = lbp_arrivals(
        st_, 3, prefix_frac=prefix_frac, n_batches=n_batches, seed=seed)
    prog = LoopyBPProgram(3, smoothing=0.7)
    scratch = ChromaticEngine(prog, full_g, tolerance=LBP_TOL)
    s, _ = scratch.run(scratch.init(full_g), max_steps=80)
    ref = np.asarray(s.graph.vertex_data["belief"])
    return prog, prefix_g, batches, ref, "belief", LBP_TOL, 80


class TestIncrementalEquivalence:
    @settings(max_examples=3, deadline=None)
    @given(seed=st.integers(0, 100), case=st.sampled_from(["pr", "lbp"]))
    def test_local(self, seed, case):
        make = _pagerank_case if case == "pr" else _lbp_case
        prog, prefix_g, batches, ref, k, tol, steps = make(
            90, seed % 7, 0.85, 2)
        cls = Engine if case == "pr" else ChromaticEngine
        eng, state = make_local_engine(prog, prefix_g, engine_cls=cls,
                                       tolerance=tol, slack=ROOMY)
        state, _ = eng.run(state, max_steps=steps)
        for b in batches:
            state = apply_delta(eng, state, b)
            state, _ = eng.run(state, max_steps=steps)
        out = np.asarray(readback(eng, state).vertex_data[k])
        assert np.abs(out - ref).max() <= 1e-5

    @settings(max_examples=2, deadline=None)
    @given(seed=st.integers(0, 100), case=st.sampled_from(["pr", "lbp"]),
           n_machines=st.sampled_from([2, 4]))
    def test_dist_sweep(self, seed, case, n_machines):
        make = _pagerank_case if case == "pr" else _lbp_case
        prog, prefix_g, batches, ref, k, tol, steps = make(
            80, seed % 5, 0.85, 2)
        eng, state = make_dist_engine(prog, prefix_g, _mesh(n_machines),
                                      tolerance=tol, slack=ROOMY)
        state, _ = eng.run(state, max_steps=steps * eng.num_colors)
        for b in batches:
            state = apply_delta(eng, state, b)
            state, _ = eng.run(state, max_steps=steps * eng.num_colors)
        out = np.asarray(readback(eng, state).vertex_data[k])
        assert np.abs(out - ref).max() <= 1e-5

    @settings(max_examples=2, deadline=None)
    @given(seed=st.integers(0, 100), n_machines=st.sampled_from([2, 4]))
    def test_dist_locking(self, seed, n_machines):
        prog, prefix_g, batches, ref, k, tol, steps = _pagerank_case(
            80, seed % 5, 0.85, 2)
        eng, state = make_dist_engine(
            prog, prefix_g, _mesh(n_machines),
            engine_cls=DistributedLockingEngine, pipeline_length=1024,
            tolerance=tol, slack=ROOMY)
        state, _ = eng.run(state, max_steps=400)
        for b in batches:
            state = apply_delta(eng, state, b)
            state, _ = eng.run(state, max_steps=400)
        out = np.asarray(readback(eng, state).vertex_data[k])
        assert np.abs(out - ref).max() <= 1e-5

    @settings(max_examples=2, deadline=None)
    @given(seed=st.integers(0, 100), kind=st.sampled_from(["local", "dist"]))
    def test_regrow_forced(self, seed, kind):
        """A batch exceeding the (deliberately tiny) slack must regrow
        through the atom path and still land on the scratch fixed point."""
        prog, prefix_g, batches, ref, k, tol, steps = _pagerank_case(
            90, seed % 5, 0.8, 2)
        tiny = SlackConfig(edge_frac=0.0, edge_min=1, vertex_min=1,
                           ghost_slack=1, eghost_slack=1)
        if kind == "local":
            eng, state = make_local_engine(prog, prefix_g, tolerance=tol,
                                           slack=tiny)
        else:
            eng, state = make_dist_engine(prog, prefix_g, _mesh(2),
                                          tolerance=tol, slack=tiny)
        state, _ = eng.run(state, max_steps=300)
        regrew = 0
        for b in batches:
            eng, state, rg = apply_delta_growing(eng, state, b)
            regrew += rg
            state, _ = eng.run(state, max_steps=300)
        assert regrew >= 1, "tiny slack was expected to force a regrow"
        out = np.asarray(readback(eng, state).vertex_data[k])
        assert np.abs(out - ref).max() <= 1e-5


# ---------------------------------------------------------------------------
# full lifecycle: delete ≡ rebuild (the hypothesis property)
# ---------------------------------------------------------------------------

def _pagerank_churn_case(n, seed):
    st_ = _connected_power_law(n, 5, seed)
    full_g, batches, post_g, dead = pagerank_churn(
        st_, frac_del_edges=0.2, n_del_vertices=2, n_batches=2, seed=seed)
    prog = PageRankProgram(0.15, st_.n_vertices)
    scratch = Engine(prog, post_g, tolerance=1e-7)
    s, _ = scratch.run(scratch.init(post_g), max_steps=300)
    ref = np.asarray(s.graph.vertex_data["rank"])
    alive = np.setdiff1d(np.arange(st_.n_vertices), np.asarray(dead))
    return prog, full_g, batches, ref, alive, "rank", 1e-7, 300


def _lbp_churn_case(n, seed):
    st_ = _connected_power_law(n, 4, seed)
    full_g, batches, post_g, dead = lbp_churn(
        st_, 3, frac_del_edges=0.2, n_del_vertices=2, n_batches=2,
        seed=seed)
    prog = LoopyBPProgram(3, smoothing=0.7)
    scratch = ChromaticEngine(prog, post_g, tolerance=1e-6)
    s, _ = scratch.run(scratch.init(post_g), max_steps=80)
    ref = np.asarray(s.graph.vertex_data["belief"])
    alive = np.setdiff1d(np.arange(st_.n_vertices), np.asarray(dead))
    return prog, full_g, batches, ref, alive, "belief", 1e-6, 80


class TestDeleteEquivalence:
    """Converge the full graph, stream deletion batches (edges, whole
    vertices, renormalized weights), reconverge — the fixed point over the
    surviving vertices matches an engine built from scratch on the
    post-deletion graph (deleted ids stay behind as isolated slots)."""

    @settings(max_examples=3, deadline=None)
    @given(seed=st.integers(0, 100), case=st.sampled_from(["pr", "lbp"]))
    def test_local(self, seed, case):
        make = _pagerank_churn_case if case == "pr" else _lbp_churn_case
        prog, full_g, batches, ref, alive, k, tol, steps = make(
            90, seed % 7)
        cls = Engine if case == "pr" else ChromaticEngine
        eng, state = make_local_engine(prog, full_g, engine_cls=cls,
                                       tolerance=tol, slack=ROOMY)
        state, _ = eng.run(state, max_steps=steps)
        for b in batches:
            assert b.n_deletions > 0
            state = apply_delta(eng, state, b)
            state, _ = eng.run(state, max_steps=steps)
        out = np.asarray(readback(eng, state).vertex_data[k])
        assert np.abs(out[alive] - ref[alive]).max() <= 1e-5

    @settings(max_examples=2, deadline=None)
    @given(seed=st.integers(0, 100), case=st.sampled_from(["pr", "lbp"]),
           n_machines=st.sampled_from([2, 4]))
    def test_dist(self, seed, case, n_machines):
        make = _pagerank_churn_case if case == "pr" else _lbp_churn_case
        prog, full_g, batches, ref, alive, k, tol, steps = make(
            80, seed % 5)
        eng, state = make_dist_engine(prog, full_g, _mesh(n_machines),
                                      tolerance=tol, slack=ROOMY)
        state, _ = eng.run(state, max_steps=steps * eng.num_colors)
        for b in batches:
            state = apply_delta(eng, state, b)
            state, _ = eng.run(state, max_steps=steps * eng.num_colors)
        out = np.asarray(readback(eng, state).vertex_data[k])
        assert np.abs(out[alive] - ref[alive]).max() <= 1e-5


# ---------------------------------------------------------------------------
# incremental color repair (DESIGN §3.12)
# ---------------------------------------------------------------------------

def _assert_no_conflicts(sg, colors):
    bad = [(s, r) for (s, r) in sg.edge_slot
           if s != r and colors[s] == colors[r]]
    assert not bad, f"same-color conflicting edges survived: {bad[:5]}"


class TestColorRepair:
    """Delta edges joining same-colored vertices must be repaired at
    apply_delta time — between regrows, the live coloring stays a proper
    coloring for every radius ≥ 1 program."""

    def test_local_lbp_arrivals(self):
        st_ = power_law_graph(120, avg_degree=4, seed=9)
        prefix_g, batches, _ = lbp_arrivals(st_, 3, prefix_frac=0.8,
                                            n_batches=3, seed=1)
        prog = LoopyBPProgram(3, smoothing=0.7)
        eng, state = make_local_engine(prog, prefix_g,
                                       engine_cls=ChromaticEngine,
                                       tolerance=1e-6, slack=ROOMY)
        assert eng.num_colors > int(stream_colors(eng).max()) + 1, \
            "color slack should reserve spare phases"
        for b in batches:
            state = apply_delta(eng, state, b)
            _assert_no_conflicts(eng._stream_graph, stream_colors(eng))
        state, _ = eng.run(state, max_steps=80)

    def test_dist_lbp_arrivals(self, cpu_mesh):
        st_ = power_law_graph(100, avg_degree=4, seed=10)
        prefix_g, batches, _ = lbp_arrivals(st_, 3, prefix_frac=0.8,
                                            n_batches=2, seed=2)
        prog = LoopyBPProgram(3, smoothing=0.7)
        eng, state = make_dist_engine(prog, prefix_g, cpu_mesh,
                                      tolerance=1e-6, slack=ROOMY)
        for b in batches:
            state = apply_delta(eng, state, b)
            _assert_no_conflicts(eng._stream_graph, stream_colors(eng))
        state, _ = eng.run(state, max_steps=200)


# ---------------------------------------------------------------------------
# snapshot × delta fence (the fixed undefined-behavior hole)
# ---------------------------------------------------------------------------

class TestSnapshotFence:
    def test_apply_delta_rejected_while_marker_wave_in_flight(self,
                                                              cpu_mesh):
        st_ = _connected_power_law(80, 4, seed=11)
        full_g, batches, _, _ = pagerank_churn(st_, frac_del_edges=0.15,
                                               n_del_vertices=1,
                                               n_batches=1, seed=0)
        prog = PageRankProgram(0.15, st_.n_vertices)
        eng, state = make_dist_engine(prog, full_g, cpu_mesh,
                                      tolerance=1e-6, slack=ROOMY)
        state, _ = eng.run(state, max_steps=200)
        state = eng.start_snapshot(state, (0,))
        sg = eng._stream_graph
        before = sg.n_real_edges
        with pytest.raises(SnapshotInFlightError):
            apply_delta(eng, state, batches[0])
        assert sg.n_real_edges == before, "fence must reject pre-mutation"
        # drain the wave; afterwards the same batch applies cleanly
        for _ in range(200):
            if eng.snapshot_complete(state):
                break
            state = eng.step(state)
        assert eng.snapshot_complete(state)
        state = eng.clear_snapshot(state)
        state = apply_delta(eng, state, batches[0])
        state, _ = eng.run(state, max_steps=200)


# ---------------------------------------------------------------------------
# engines that cannot stream say so at construction
# ---------------------------------------------------------------------------

class TestUnsupportedStreaming:
    def test_dynamic_engine_rejected_at_construction(self):
        st_ = power_law_graph(40, avg_degree=4, seed=12)
        g = make_pagerank_graph(st_)
        prog = PageRankProgram(0.15, st_.n_vertices)
        with pytest.raises(UnsupportedStreamingError):
            make_local_engine(prog, g, engine_cls=DynamicEngine,
                              tolerance=1e-6, slack=ROOMY)
        # the same engine still builds fine on static structure
        DynamicEngine(prog, g, tolerance=1e-6)


# ---------------------------------------------------------------------------
# DeltaJournal: durable, offset-ordered, gap-checked
# ---------------------------------------------------------------------------

class TestDeltaJournal:
    def _batches(self):
        return [
            DeltaBatch([AddVertex(vid=7),
                        AddEdge(0, 1, data=[np.float32(0.5)]),
                        SetVertexData(2, [np.asarray([0.25], np.float32)])]),
            DeltaBatch([DelEdge(0, 1), DelVertex(7)]),
        ]

    def test_roundtrip_through_reopen(self, tmp_path):
        j = DeltaJournal(str(tmp_path))
        assert j.next_offset == 0
        for b in self._batches():
            j.append(b)
        j2 = DeltaJournal(str(tmp_path))  # fresh scan of the directory
        assert len(j2) == 2 and j2.next_offset == 2
        got = list(j2.read_since(0))
        assert [k for k, _ in got] == [0, 1]
        for (_, rb), b in zip(got, self._batches()):
            assert [type(c) for c in rb] == [type(c) for c in b]
        b0 = got[0][1]
        assert b0.commands[0].vid == 7
        assert (b0.commands[1].src, b0.commands[1].dst) == (0, 1)
        np.testing.assert_allclose(b0.commands[1].data[0], 0.5)
        np.testing.assert_allclose(b0.commands[2].data[0], [0.25])
        b1 = got[1][1]
        assert (b1.commands[0].src, b1.commands[0].dst) == (0, 1)
        assert b1.commands[1].vid == 7
        # read_since(1) is the replay suffix of a cut anchored at 1
        assert [k for k, _ in j2.read_since(1)] == [1]

    def test_gap_detection(self, tmp_path):
        j = DeltaJournal(str(tmp_path))
        for b in self._batches():
            j.append(b)
        import os
        os.unlink(os.path.join(str(tmp_path), "delta_0000000000.npz"))
        with pytest.raises(ValueError, match="gap"):
            DeltaJournal(str(tmp_path))

    def test_torn_final_entry_truncated_with_warning(self, tmp_path):
        """ISSUE 7 satellite 2: a torn *tail* (truncated bytes — power
        loss after rename, a bad copy) is warned about and truncated on
        reopen; the surviving prefix stays fully readable and appendable."""
        import os
        j = DeltaJournal(str(tmp_path))
        for b in self._batches() + [DeltaBatch([AddVertex(vid=9)])]:
            j.append(b)
        last = os.path.join(str(tmp_path), "delta_0000000002.npz")
        with open(last, "r+b") as f:
            f.truncate(os.path.getsize(last) // 2)
        with pytest.warns(RuntimeWarning, match="torn final entry"):
            j2 = DeltaJournal(str(tmp_path))
        assert j2.next_offset == 2
        assert not os.path.exists(last)  # the torn bytes are gone
        # the committed prefix is intact and the log accepts new appends
        assert [k for k, _ in j2.read_since(0)] == [0, 1]
        assert j2.append(DeltaBatch([AddVertex(vid=11)])) == 2
        assert j2.read(2).commands[0].vid == 11
        # double-crash: two torn tails in a row truncate twice
        for off in (1, 2):
            p = os.path.join(str(tmp_path), f"delta_000000000{off}.npz")
            with open(p, "r+b") as f:
                f.truncate(4)
        with pytest.warns(RuntimeWarning, match="torn final entry"):
            j3 = DeltaJournal(str(tmp_path))
        assert j3.next_offset == 1
        assert [k for k, _ in j3.read_since(0)] == [0]

    def test_journal_records_committed_batches_only(self, tmp_path):
        """attach_journal + apply_delta: committed batches append under
        monotone offsets; a batch that fails capacity is not recorded."""
        from repro.stream import attach_journal
        st_ = power_law_graph(60, avg_degree=4, seed=13)
        g = make_pagerank_graph(st_)
        prog = PageRankProgram(0.15, st_.n_vertices)
        tiny = SlackConfig(edge_frac=0.0, edge_min=1, vertex_min=1,
                           ghost_slack=1, eghost_slack=1)
        eng, state = make_local_engine(prog, g, tolerance=1e-6, slack=tiny)
        journal = DeltaJournal(str(tmp_path))
        attach_journal(eng, journal)
        sg = eng._stream_graph
        ok = next(i for i in range(1, 59)
                  if (i, 0) not in sg.edge_slot and sg.fill[0] <
                  sg.slot_start[1] - sg.slot_start[0])
        state = apply_delta(eng, state, DeltaBatch([AddEdge(ok, 0)]))
        assert journal.next_offset == 1 and eng._stream_offset == 1
        fresh = [i for i in range(1, 59) if (i, 0) not in sg.edge_slot][:6]
        with pytest.raises(CapacityError):
            apply_delta(eng, state, DeltaBatch(
                [AddEdge(i, 0) for i in fresh]))
        assert journal.next_offset == 1, "failed batch must not journal"


# ---------------------------------------------------------------------------
# capacity-error atomicity
# ---------------------------------------------------------------------------

class TestCapacityAtomicity:
    def test_failed_batch_leaves_state_unchanged(self, cpu_mesh):
        st_ = power_law_graph(60, avg_degree=4, seed=5)
        g = make_pagerank_graph(st_)
        prog = PageRankProgram(0.15, st_.n_vertices)
        tiny = SlackConfig(edge_frac=0.0, edge_min=1, vertex_min=1,
                           ghost_slack=1, eghost_slack=1)
        eng, state = make_dist_engine(prog, g, cpu_mesh, tolerance=1e-6,
                                      slack=tiny)
        state, _ = eng.run(state, max_steps=100)
        ref = readback(eng, state)
        sg = eng._stream_graph
        n_edges_before = sg.n_real_edges
        # overload one vertex's region mid-batch (fresh senders only)
        fresh = [i for i in range(1, 59)
                 if (i, 0) not in sg.edge_slot][:5]
        bad = DeltaBatch([AddEdge(i, 0) for i in fresh])
        with pytest.raises(CapacityError):
            apply_delta(eng, state, bad)
        assert sg.n_real_edges == n_edges_before
        # the engine still steps and the state is untouched
        out = readback(eng, state)
        assert np.array_equal(np.asarray(out.vertex_data["rank"]),
                              np.asarray(ref.vertex_data["rank"]))
        eng.step(state)


# ---------------------------------------------------------------------------
# contract 3: atom journals replay as delta streams
# ---------------------------------------------------------------------------

class TestAtomReplay:
    def test_journal_replay_reaches_scratch_fixed_point(self, tmp_path):
        st_ = power_law_graph(70, avg_degree=4, seed=6)
        g = make_pagerank_graph(st_)
        atom_of = overpartition(st_, 6, seed=0)
        index = build_atoms(g, atom_of, str(tmp_path))

        empty_st, _ = GraphStructure.from_edges(
            np.zeros(0, np.int32), np.zeros(0, np.int32), 0)
        empty = DataGraph.build(
            empty_st,
            jax.tree.map(lambda x: np.asarray(x)[:0], g.vertex_data),
            jax.tree.map(lambda x: np.asarray(x)[:0], g.edge_data))
        prog = PageRankProgram(0.15, st_.n_vertices)
        eng, state = make_local_engine(
            prog, empty, tolerance=1e-7, slack=SlackConfig(edge_min=2),
            n_cap=st_.n_vertices + 4,
            in_capacity=st_.in_degree.astype(np.int64) + 2)
        for path in index.files:
            batch = DeltaBatch.from_atom_file(path)
            state = apply_delta(eng, state, batch)
        state, _ = eng.run(state, max_steps=300)
        out = np.asarray(readback(eng, state).vertex_data["rank"])
        exact = exact_pagerank(st_, 0.15, iters=500)
        assert out.shape[0] == st_.n_vertices
        assert np.abs(out - exact).max() <= 1e-5


# ---------------------------------------------------------------------------
# streaming ratings into ALS (the Sec. 5.1 workload)
# ---------------------------------------------------------------------------

class TestALSStreaming:
    def test_rating_stream_with_late_movies(self):
        prefix_g, batches, full_g, _ = als_rating_arrivals(
            50, 25, 400, d=4, prefix_frac=0.85, n_batches=2,
            n_late_movies=3, seed=0)
        assert sum(b.n_new_vertices for b in batches) == 3
        prog = ALSProgram(d=4)
        eng, state = make_local_engine(prog, prefix_g,
                                       engine_cls=ChromaticEngine,
                                       tolerance=1e-5, slack=ROOMY)
        state, _ = eng.run(state, max_steps=60)
        for b in batches:
            eng, state, _ = apply_delta_growing(eng, state, b)
            state, _ = eng.run(state, max_steps=60)
        stream_g = readback(eng, state)
        assert stream_g.structure.n_vertices == full_g.structure.n_vertices
        assert stream_g.structure.n_edges == full_g.structure.n_edges

        scratch = ChromaticEngine(prog, full_g, tolerance=1e-5)
        s2, _ = scratch.run(scratch.init(full_g), max_steps=60)
        # ALS fixed points are not unique (alternating least squares is
        # non-convex) — compare the quality metric, not the factors
        tr_s, tr_r = als_rmse(stream_g, True), als_rmse(s2.graph, True)
        assert tr_s <= tr_r + 0.05, (tr_s, tr_r)
        assert als_rmse(stream_g, False) <= 1.5
