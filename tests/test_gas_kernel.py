"""Fused GAS kernel vs the dense oracle (ISSUE 2 test satellite).

Property tests sweep degree-skewed graphs — power-law, isolated vertices,
E = 0, single vertex, all-inactive mask — comparing the interpret-mode
Pallas kernel against both the jnp oracle and an independent numpy
reference.  The jaxpr-inspection tests assert the fused engine step never
materializes an ``[E, D]`` intermediate (the tentpole's whole point).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.gas.gas import EDGE_BLOCK, ROW_BLOCK
from repro.kernels.gas.ops import EdgeSet, active_row_blocks, gather_combine
from repro.kernels.gas.ref import gather_combine_ref


def _numpy_truth(feat, w, snd, recv, n, block_active=None):
    """Independent dense reference (pure numpy — not ref.py)."""
    acc = np.zeros((n, feat.shape[1]), np.float32)
    if snd.size:
        np.add.at(acc, recv, w[:, None] * feat[snd])
    if block_active is not None:
        keep = np.repeat(np.asarray(block_active).astype(bool),
                         ROW_BLOCK)[:n]
        acc[~keep] = 0.0
    return acc


def _random_edges(rng, n, e, skew):
    if skew:  # power-law receiver degrees: hot rows (the GraphLab workload)
        recv = np.minimum((rng.pareto(1.2, e) * 3).astype(np.int64), n - 1)
    else:
        recv = rng.integers(0, n, e)
    recv = np.sort(recv).astype(np.int32)
    snd = rng.integers(0, n, e).astype(np.int32)
    return snd, recv


class TestGatherCombine:
    @settings(max_examples=10, deadline=None)
    @given(e=st.integers(0, 2500), d=st.integers(1, 140),
           n=st.integers(1, 600), seed=st.integers(0, 10**6),
           skew=st.booleans(), frac=st.sampled_from([1.0, 0.3, 0.0]))
    def test_matches_oracle_and_numpy(self, e, d, n, seed, skew, frac):
        rng = np.random.default_rng(seed)
        snd, recv = _random_edges(rng, n, e, skew)
        w = rng.normal(size=e).astype(np.float32)
        feat = rng.normal(size=(n, d)).astype(np.float32)
        edges = EdgeSet.build(snd, recv, n)

        mask = rng.random(n) < frac
        blk = active_row_blocks(jnp.asarray(mask))
        truth = _numpy_truth(feat, w, snd, recv, n, np.asarray(blk))

        kern = np.asarray(gather_combine(
            jnp.asarray(feat), jnp.asarray(w), edges, block_active=blk,
            interpret=True))
        orac = np.asarray(gather_combine(
            jnp.asarray(feat), jnp.asarray(w), edges, block_active=blk,
            interpret=None))  # CPU → ref.py oracle
        scale = np.abs(truth).max() + 1e-6
        assert np.abs(kern - truth).max() / scale < 2e-5
        assert np.abs(orac - truth).max() / scale < 2e-5

    def test_all_inactive_mask_is_exact_zero(self):
        rng = np.random.default_rng(0)
        snd, recv = _random_edges(rng, 300, 1500, True)
        edges = EdgeSet.build(snd, recv, 300)
        feat = jnp.asarray(rng.normal(size=(300, 16)), jnp.float32)
        w = jnp.asarray(rng.normal(size=1500), jnp.float32)
        blk = active_row_blocks(jnp.zeros(300, bool))
        for interp in (True, None):
            out = gather_combine(feat, w, edges, block_active=blk,
                                 interpret=interp)
            assert float(jnp.abs(out).sum()) == 0.0

    def test_isolated_vertices_are_zero(self):
        # every edge lands on vertex 7; everyone else is isolated
        snd = np.arange(64, dtype=np.int32)
        recv = np.full(64, 7, np.int32)
        edges = EdgeSet.build(snd, recv, 200)
        feat = jnp.ones((200, 4), jnp.float32)
        w = jnp.ones(64, jnp.float32)
        out = np.asarray(gather_combine(feat, w, edges, interpret=True))
        assert out[7].sum() == pytest.approx(64 * 4)
        rest = np.delete(np.arange(200), 7)
        assert np.abs(out[rest]).sum() == 0.0

    def test_empty_graph(self):
        edges = EdgeSet.build(np.zeros(0, np.int32), np.zeros(0, np.int32),
                              50)
        feat = jnp.ones((50, 8), jnp.float32)
        w = jnp.zeros(0, jnp.float32)
        for interp in (True, None):
            out = gather_combine(feat, w, edges, interpret=interp)
            assert float(jnp.abs(out).sum()) == 0.0

    def test_single_vertex_self_loop(self):
        edges = EdgeSet.build(np.zeros(3, np.int32), np.zeros(3, np.int32), 1)
        feat = jnp.full((1, 2), 2.0, jnp.float32)
        w = jnp.asarray([1.0, 2.0, 3.0], jnp.float32)
        out = np.asarray(gather_combine(feat, w, edges, interpret=True))
        np.testing.assert_allclose(out, [[12.0, 12.0]], rtol=1e-6)

    def test_block_skipping_reads_match_block_counts(self):
        """Edges-touched accounting: block_counts sums to E and partitions
        by receiver row block."""
        rng = np.random.default_rng(3)
        snd, recv = _random_edges(rng, 500, 4000, True)
        edges = EdgeSet.build(snd, recv, 500)
        counts = np.asarray(edges.block_counts)
        assert counts.sum() == 4000
        expect = np.bincount(recv // ROW_BLOCK, minlength=counts.size)
        np.testing.assert_array_equal(counts, expect)

    def test_structure_csr_blocks_covers_every_edge(self):
        """The flattened grid over a structure's receivers visits, for
        every edge, the (receiver row block, edge block) pair it lives in;
        row blocks come in order and each has at least one step."""
        from repro.core.graph import GraphStructure
        from repro.kernels.gas.gas import csr_steps
        rng = np.random.default_rng(4)
        snd, recv = _random_edges(rng, 400, 3000, True)
        st_, _ = GraphStructure.from_edges(snd, recv, 400)
        step_rb, step_eb = csr_steps(st_.receivers, st_.n_vertices)
        assert (np.diff(step_rb) >= 0).all()
        np.testing.assert_array_equal(np.unique(step_rb),
                                      np.arange(-(-400 // ROW_BLOCK)))
        pairs = set(zip(step_rb.tolist(), step_eb.tolist()))
        eblk_of_edge = np.arange(st_.n_edges) // EDGE_BLOCK
        rblk_of_edge = st_.receivers // ROW_BLOCK
        assert set(zip(rblk_of_edge.tolist(), eblk_of_edge.tolist())) <= pairs

    def test_exact_edge_block_multiple_stays_in_range(self):
        """E an exact EDGE_BLOCK multiple with trailing empty row blocks:
        every step's edge block must stay inside the real block range (the
        compiled kernel would read out of bounds otherwise)."""
        n = 600
        e = EDGE_BLOCK  # all receivers < 128 → row blocks 1.. are empty
        rng = np.random.default_rng(5)
        recv = np.sort(rng.integers(0, 100, e)).astype(np.int32)
        snd = rng.integers(0, n, e).astype(np.int32)
        edges = EdgeSet.build(snd, recv, n)
        nblocks = edges.senders.shape[0] // EDGE_BLOCK
        step_eb = np.asarray(edges.step_eb)
        assert ((step_eb >= 0) & (step_eb < nblocks)).all(), step_eb
        feat = jnp.asarray(rng.normal(size=(n, 4)), jnp.float32)
        w = jnp.asarray(rng.normal(size=e), jnp.float32)
        out = np.asarray(gather_combine(feat, w, edges, interpret=True))
        truth = _numpy_truth(np.asarray(feat), np.asarray(w), snd, recv, n)
        assert np.abs(out - truth).max() < 1e-5 * (np.abs(truth).max() + 1)


# ---------------------------------------------------------------------------
# weighted_src_outer: Σ w·x xᵀ formed in the row-block kernel from the
# d-wide table, and the dispatch to the materialised [N, d²] table
# ---------------------------------------------------------------------------

def _numpy_outer_truth(x, w, snd, recv, n, block_active=None):
    """Independent ``Σ_{u→v} w_e x_u x_uᵀ`` in float64 → [n, d²]."""
    d = x.shape[1]
    acc = np.zeros((n, d, d))
    xs = x[snd].astype(np.float64)
    np.add.at(acc, recv, w[:, None, None] * xs[:, :, None] * xs[:, None, :])
    if block_active is not None:
        keep = np.repeat(np.asarray(block_active).astype(bool),
                         ROW_BLOCK)[:n]
        acc[~keep] = 0.0
    return acc.reshape(n, d * d)


def _outer_case(case, d, seed=0):
    """(x, w, snd, recv, n, active mask) of one outer-gather case: a hub
    whose in-edges span several edge blocks, general or 0/1 weights,
    partly or wholly inactive row blocks, or no edges."""
    rng = np.random.default_rng(seed + d)
    n = 300
    x = rng.normal(size=(n, d)).astype(np.float32)
    if case == "empty":
        snd = recv = np.zeros(0, np.int32)
    else:
        hub = np.full(3 * EDGE_BLOCK, 5)     # one receiver, 3+ edge blocks
        rest = np.minimum((rng.pareto(1.2, 1500) * 3).astype(np.int64),
                          n - 1)
        recv = np.sort(np.concatenate([hub, rest])).astype(np.int32)
        snd = rng.integers(0, n, recv.size).astype(np.int32)
    if case == "general":
        w = rng.normal(size=snd.size)
    else:                                    # ALS's train flags
        w = (rng.random(snd.size) < 0.8).astype(np.float64)
    frac = {"partial": 0.3, "inactive": 0.0}.get(case, 1.0)
    mask = rng.random(n) < frac
    if case == "partial":
        mask[5] = True                       # the hub's row block stays on
    return x, w.astype(np.float32), snd, recv, n, mask


class TestOuterGather:
    @pytest.mark.parametrize("d", [1, 4, 20, 24])
    @pytest.mark.parametrize("case", ["general", "binary", "partial",
                                      "inactive", "empty"])
    def test_kernel_matches_numpy(self, case, d):
        """The row-block kernel's outer path (interpret mode) against a
        float64 NumPy ``Σ w·x xᵀ``; with 0/1 weights it agrees with
        today's gather of the materialised table: bit for bit where that
        is the row-block kernel (d > 1), to float32 rounding where it is
        the edge-major kernel, which sums in another order (d = 1)."""
        from repro.kernels.gas.gas import kernel_takes_outer
        x, w, snd, recv, n, mask = _outer_case(case, d)
        assert kernel_takes_outer(n, d)
        edges = EdgeSet.build(snd, recv, n)
        blk = active_row_blocks(jnp.asarray(mask))
        got = np.asarray(gather_combine(
            jnp.asarray(x), jnp.asarray(w), edges, block_active=blk,
            interpret=True, outer=True))
        truth = _numpy_outer_truth(x, w, snd, recv, n, np.asarray(blk))
        assert got.shape == (n, d * d)
        if case in ("inactive", "empty"):
            assert not got.any()
            return
        scale = np.abs(truth).max()
        assert np.abs(got - truth).max() / scale < 2e-5
        if case != "general":
            table = (x[:, :, None] * x[:, None, :]).reshape(n, d * d)
            today = np.asarray(gather_combine(
                jnp.asarray(table), jnp.asarray(w), edges, block_active=blk,
                interpret=True))
            if d > 1:
                np.testing.assert_array_equal(got, today)
            else:
                np.testing.assert_allclose(got, today, rtol=1e-6)

    @pytest.mark.parametrize("why", ["d2_too_wide", "not_resident"])
    def test_fallback_gathers_the_table(self, why, monkeypatch):
        """Where the kernel cannot form the messages itself — ``d²`` wider
        than ``MAX_FEAT`` (d = 40), or a d-wide table too large to stay
        resident — the outer gather is the gather of the materialised
        ``[N, d²]`` table, bit for bit, and takes no ``graphlab.outer``."""
        from repro.kernels.gas import gas
        d = 40 if why == "d2_too_wide" else 20
        x, w, snd, recv, n, mask = _outer_case("binary", d)
        if why == "not_resident":
            monkeypatch.setattr(gas, "RESIDENT_BYTES",
                                n * gas.LANES * 4 - 1)
        assert not gas.kernel_takes_outer(n, d)
        jax.clear_caches()
        edges = EdgeSet.build(snd, recv, n)
        blk = active_row_blocks(jnp.asarray(mask))
        table = jnp.asarray((x[:, :, None] * x[:, None, :]).reshape(n, -1))

        def outer(x, w):
            return gather_combine(x, w, edges, block_active=blk,
                                  interpret=True, outer=True)

        args = (jnp.asarray(x), jnp.asarray(w))
        got = np.asarray(outer(*args))
        want = np.asarray(gather_combine(table, args[1], edges,
                                         block_active=blk, interpret=True))
        jax.clear_caches()
        np.testing.assert_array_equal(got, want)
        assert "graphlab.outer" not in jax.jit(outer).lower(
            *args).as_text(debug_info=True)
        truth = _numpy_outer_truth(x, w, snd, recv, n, np.asarray(blk))
        assert np.abs(got - truth).max() / np.abs(truth).max() < 2e-5

    def test_outer_leaf_needs_a_weight(self):
        from repro.core.update import (FusedGather, VertexProgram,
                                       fused_gather_leaves)

        class NoWeight(VertexProgram):
            def fused_gather(self):
                return FusedGather("weighted_src_outer",
                                   feature=lambda v: v["x"])

        with pytest.raises(ValueError, match="weighted_src_outer"):
            fused_gather_leaves(NoWeight())


# ---------------------------------------------------------------------------
# jaxpr inspection: the fused step materializes no [E, D] intermediate
# ---------------------------------------------------------------------------

def _collect_shapes(obj, out):
    """Recursively collect every float eqn output shape, descending into
    closed jaxprs (pjit bodies, pallas kernels, scan/cond branches).
    Integer outputs are skipped: gather/scatter *index* arrays are [E, 1]
    by construction and are not message materialization."""
    jaxpr = getattr(obj, "jaxpr", obj)
    if not hasattr(jaxpr, "eqns"):
        return
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            if (aval is not None and hasattr(aval, "shape")
                    and jnp.issubdtype(getattr(aval, "dtype", np.int32),
                                       jnp.floating)):
                out.append(tuple(aval.shape))
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                    _collect_shapes(sub, out)


def _named_eqns(fn, args):
    """(primitive, jit name, output shape, dtype, name stack) of every
    eqn output of ``fn``'s jaxpr, recursing into closed jaxprs; a name
    stack carries its enclosing eqns' scopes."""
    out = []

    def walk(obj, prefix):
        jaxpr = getattr(obj, "jaxpr", obj)
        for eqn in getattr(jaxpr, "eqns", ()):
            names = "/".join(p for p in (
                prefix, str(eqn.source_info.name_stack)) if p)
            for v in eqn.outvars:
                aval = getattr(v, "aval", None)
                if aval is not None and hasattr(aval, "shape"):
                    out.append((eqn.primitive.name, eqn.params.get("name"),
                                tuple(aval.shape), aval.dtype, names))
            for p in eqn.params.values():
                for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                    if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                        walk(sub, names)

    walk(jax.make_jaxpr(fn)(*args), "")
    return out


def _edge_row_intermediates(fn, args, edge_dims):
    shapes = []
    _collect_shapes(jax.make_jaxpr(fn)(*args), shapes)
    return [s for s in shapes if len(s) >= 2 and s[0] in edge_dims]


def _edge_dims(E):
    e_pad = max(-(-E // EDGE_BLOCK), 1) * EDGE_BLOCK
    # block sizes must not collide with the edge counts we scan for
    assert E not in (EDGE_BLOCK, ROW_BLOCK) and e_pad != EDGE_BLOCK
    return {E, e_pad}


class TestNoEdgeDimIntermediates:
    def _engines(self, make, *, use_fused, **kw):
        from repro.core.chromatic import ChromaticEngine
        prog, graph = make()
        return ChromaticEngine(prog, graph, use_fused=use_fused, **kw), graph

    @staticmethod
    def _pagerank():
        from repro.apps.pagerank import PageRankProgram, make_pagerank_graph
        from repro.graphs.generators import power_law_graph
        st_ = power_law_graph(260, avg_degree=5, seed=11)
        return (PageRankProgram(n_vertices=st_.n_vertices),
                make_pagerank_graph(st_))

    @staticmethod
    def _als():
        from repro.apps.als import ALSProgram, make_als_graph
        g, _ = make_als_graph(60, 70, 700, d=4, seed=5)
        return ALSProgram(d=4), g

    def test_fused_pagerank_step_has_no_edge_matrix(self):
        eng, graph = self._engines(self._pagerank, use_fused=True,
                                   gas_interpret=True)
        assert eng.use_fused
        state = eng.init(graph)
        bad = _edge_row_intermediates(eng._step, (state,),
                                      _edge_dims(graph.n_edges))
        assert not bad, f"fused PageRank step materializes {bad}"

    def test_fused_als_step_has_no_edge_matrix_but_dense_does(self):
        eng, graph = self._engines(self._als, use_fused=True,
                                   gas_interpret=True)
        dense, _ = self._engines(self._als, use_fused=False)
        dims = _edge_dims(graph.n_edges)
        state = eng.init(graph)
        bad = _edge_row_intermediates(eng._step, (state,), dims)
        assert not bad, f"fused ALS step materializes {bad}"
        # sanity: the seed dense path really does build [E, d, d]
        dstate = dense.init(graph)
        dense_bad = _edge_row_intermediates(dense._step, (dstate,), dims)
        assert any(len(s) == 3 for s in dense_bad), dense_bad

    @staticmethod
    def _outer_tables(eng, graph):
        """Float ``[N, d, d]`` / ``[N, d²]`` values the step computes
        outside the apply, other than the gather's own result (the kernel
        entry's output, its slice and its reshape): the x xᵀ vertex table
        a materialised gather would build."""
        n, d = graph.n_vertices, 4
        return [(prim, shape) for prim, _, shape, dtype, names
                in _named_eqns(eng._step, (eng.init(graph),))
                if shape in ((n, d, d), (n, d * d))
                and jnp.issubdtype(dtype, jnp.floating)
                and "graphlab.apply" not in names
                and prim not in ("jit", "slice", "reshape")]

    def test_fused_als_step_builds_no_outer_table(self):
        """On the kernel path the ``xxt`` leaf is formed in the kernel from
        the d-wide factor table, under ``graphlab.outer``; the ``rx``
        leaf's gather is not."""
        eng, graph = self._engines(self._als, use_fused=True,
                                   gas_interpret=True)
        assert not self._outer_tables(eng, graph)
        n, d = graph.n_vertices, 4
        gathers = {shape: "graphlab.outer" in names
                   for _, name, shape, _, names
                   in _named_eqns(eng._step, (eng.init(graph),))
                   if name == "gas_gather_combine_pallas"}
        assert gathers == {(n, d * d): True, (n, d): False}
        # sanity on the instrument: off the kernel path the step gathers
        # the materialised table, and the check finds it
        oracle, _ = self._engines(self._als, use_fused=True)
        assert ("mul", (n, d, d)) in self._outer_tables(oracle, graph)

    def test_fused_pagerank_step_takes_no_outer_path(self):
        eng, graph = self._engines(self._pagerank, use_fused=True,
                                   gas_interpret=True)
        names = [e[4] for e in _named_eqns(eng._step, (eng.init(graph),))]
        assert any("graphlab.gather" in n for n in names)
        assert not any("graphlab.outer" in n for n in names)


# ---------------------------------------------------------------------------
# fused scatter/reschedule (ISSUE 8): kernel vs oracle vs numpy, and the
# jaxpr guarantees — no dense float scatter temp, no f32 all_to_all under a
# quantized wire
# ---------------------------------------------------------------------------

from repro.kernels.gas.ops import scatter_reschedule  # noqa: E402


def _numpy_reschedule(contrib, prio, consume, w, snd, recv, n):
    """Independent dense reference for T ← (T \\ executed) ∪ T'."""
    out = np.where(consume, 0.0, prio).astype(np.float32)
    real = recv < n
    np.add.at(out, recv[real],
              (w[real] * contrib[snd[real]]).astype(np.float32))
    return out


class TestScatterReschedule:
    @settings(max_examples=10, deadline=None)
    @given(e=st.integers(0, 2500), n=st.integers(1, 600),
           seed=st.integers(0, 10**6), skew=st.booleans(),
           frac=st.sampled_from([1.0, 0.3, 0.0]))
    def test_matches_oracle_and_numpy(self, e, n, seed, skew, frac):
        rng = np.random.default_rng(seed)
        snd, recv = _random_edges(rng, n, e, skew)
        w = rng.normal(size=e).astype(np.float32)
        edges = EdgeSet.build(snd, recv, n)
        # sparse contribs: zero rows make whole edge blocks inactive, so
        # the activity bitmap's skipping is exercised, not just computed
        contrib = np.where(rng.random(n) < frac,
                           rng.normal(size=n), 0.0).astype(np.float32)
        prio = rng.uniform(0, 1, n).astype(np.float32)
        consume = rng.random(n) < 0.5

        w_pad = np.zeros(edges.senders.shape[0], np.float32)
        w_pad[:e] = w
        truth = _numpy_reschedule(contrib, prio, consume, w_pad,
                                  np.asarray(edges.senders),
                                  np.asarray(edges.receivers), n)
        args = (jnp.asarray(contrib), jnp.asarray(prio),
                jnp.asarray(consume), edges, jnp.asarray(w))
        kern = np.asarray(scatter_reschedule(*args, interpret=True))
        orac = np.asarray(scatter_reschedule(*args, interpret=None))
        scale = np.abs(truth).max() + 1e-6
        assert np.abs(kern - truth).max() / scale < 2e-5
        assert np.abs(orac - truth).max() / scale < 2e-5

    def test_all_consumed_zeroes_unbumped_rows(self):
        rng = np.random.default_rng(1)
        snd, recv = _random_edges(rng, 200, 900, True)
        edges = EdgeSet.build(snd, recv, 200)
        prio = jnp.asarray(rng.uniform(0.5, 1, 200), jnp.float32)
        out = scatter_reschedule(jnp.zeros(200), prio,
                                 jnp.ones(200, bool), edges,
                                 interpret=True)
        assert float(jnp.abs(out).sum()) == 0.0


def _collect_prims(obj, out):
    """(primitive name, shape, dtype) of every eqn output, recursing into
    closed jaxprs like ``_collect_shapes``."""
    jaxpr = getattr(obj, "jaxpr", obj)
    if not hasattr(jaxpr, "eqns"):
        return
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "shape"):
                out.append((eqn.primitive.name, tuple(aval.shape),
                            getattr(aval, "dtype", None)))
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                    _collect_prims(sub, out)


def _float_scatters(fn, args):
    prims = []
    _collect_prims(jax.make_jaxpr(fn)(*args), prims)
    return [p for p in prims
            if "scatter" in p[0] and p[2] is not None
            and jnp.issubdtype(p[2], jnp.floating)]


class TestFusedRescheduleJaxpr:
    """The fused phase's whole point, asserted on the lowered step: the
    reschedule runs inside the kernel — no dense float scatter-add into an
    [N]-row temp survives in the fused step's jaxpr."""

    @staticmethod
    def _pagerank_engine(use_fused):
        from repro.apps.pagerank import PageRankProgram, make_pagerank_graph
        from repro.core.chromatic import ChromaticEngine
        from repro.graphs.generators import power_law_graph
        st_ = power_law_graph(260, avg_degree=5, seed=11)
        g = make_pagerank_graph(st_)
        prog = PageRankProgram(n_vertices=st_.n_vertices)
        kw = {"gas_interpret": True} if use_fused else {}
        return ChromaticEngine(prog, g, use_fused=use_fused, **kw), g

    def test_fused_step_has_no_float_scatter(self):
        eng, g = self._pagerank_engine(True)
        assert eng.use_fused
        bad = _float_scatters(eng._step, (eng.init(g),))
        assert not bad, f"fused step still scatters floats: {bad}"

    def test_dense_step_does_scatter(self):
        # sanity on the instrument: the seed dense path reschedules via a
        # float segment-sum scatter-add — if this stops tripping, the
        # fused assertion above is vacuous
        eng, g = self._pagerank_engine(False)
        bad = _float_scatters(eng._step, (eng.init(g),))
        assert bad


@pytest.mark.skipif(jax.device_count() < 2,
                    reason="needs >= 2 forced host devices")
class TestQuantizedWireJaxpr:
    """Under an int8 wire the ghost exchange ships encoded rows: the dist
    step's jaxpr must contain no f32 all_to_all (DESIGN §3.14)."""

    @staticmethod
    def _dist_engine(wire):
        from repro.apps.pagerank import PageRankProgram, make_pagerank_graph
        from repro.dist.engine import DistributedEngine
        from repro.graphs.generators import power_law_graph
        st_ = power_law_graph(120, avg_degree=5, seed=3)
        g = make_pagerank_graph(st_)
        n = min(jax.device_count(), 4)
        mesh = jax.sharding.Mesh(
            np.asarray(jax.devices()[:n]).reshape(n, 1), ("data", "model"))
        return DistributedEngine(PageRankProgram(0.15, st_.n_vertices), g,
                                 mesh, tolerance=1e-7, wire=wire)

    @staticmethod
    def _all_to_alls(eng):
        prims = []
        state = eng.init()
        _collect_prims(jax.make_jaxpr(eng._jit_step)(state, eng._tables),
                       prims)
        return [p for p in prims if p[0] == "all_to_all"]

    def test_int8_wire_ships_no_f32(self):
        from repro.dist.wire import WireConfig
        eng = self._dist_engine(WireConfig(codec="int8", top_k=8))
        a2a = self._all_to_alls(eng)
        assert a2a, "no all_to_all found — exchange shape changed?"
        f32 = [p for p in a2a
               if p[2] is not None and jnp.issubdtype(p[2], jnp.floating)
               and jnp.dtype(p[2]).itemsize >= 4]
        assert not f32, f"f32 rows on the quantized wire: {f32}"

    def test_default_wire_does_ship_f32(self):
        # sanity on the instrument (see TestFusedRescheduleJaxpr)
        eng = self._dist_engine(None)
        f32 = [p for p in self._all_to_alls(eng)
               if p[2] is not None and jnp.issubdtype(p[2], jnp.floating)]
        assert f32
