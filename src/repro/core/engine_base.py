"""Shared engine machinery (paper Sec. 3.3 execution model, Sec. 4.2 engines).

``EngineState`` is the distributed program state: the data graph, the
scheduler T (a priority array — active ⇔ prio > tolerance, plus the
scheduler's own pytree state for stateful schedulers like FIFO), per-vertex
update counts (Fig. 1(b)) and the sync operation's global values.

An engine IS a scheduler choice (DESIGN.md §3.8): the base ``_step`` runs
``scheduler.num_phases`` select → apply → reschedule phases and subclasses
only pick the scheduler (BSP = single-color sweep, chromatic = color-range
sweep, dynamic = prioritized pipeline) plus per-phase extras such as the
chromatic per-color edge ranges.  ``run`` is the shared host loop with
convergence tracing; ``run_while`` the fully-jitted ``lax.while_loop`` used
by the dry-run path ("all vertices in T are eventually executed" is the
only ordering requirement the paper imposes).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

import numpy as np

from repro.core.graph import DataGraph, segment_combine
from repro.core.scheduler import Scheduler, SweepScheduler, reschedule_prio
from repro.core.sync_op import SyncOp, run_syncs
from repro.core.update import (EdgeCtx, VertexProgram, edge_ctx,
                               fused_edge_weight, fused_gather_leaves,
                               masked_update, supports_fused_gather)
from repro.kernels.gas.gas import EDGE_BLOCK, ROW_BLOCK, csr_steps
from repro.kernels.gas.ops import (EdgeSet, ScatterCtx, active_row_blocks,
                                   gather_combine, stack_edge_sets)
from repro.obs.timeline import span

Pytree = Any


class UnsupportedStreamingError(ValueError):
    """Raised at construction when an engine/scheduler combination cannot
    run against dynamic structure tables (it would silently compute on the
    stale structure baked into its trace)."""


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class EngineState:
    graph: DataGraph
    prio: jnp.ndarray          # [N] f32 — the scheduler T with priorities
    update_count: jnp.ndarray  # [N] i32 — paper Fig. 1(b) statistic
    step_index: jnp.ndarray    # scalar i32
    total_updates: jnp.ndarray  # scalar i64-ish (i32 fine for tests)
    edges_touched: jnp.ndarray  # scalar i64-ish — gathered-edge accounting
    globals_: Pytree           # sync-op outputs readable by update fns
    sched: Pytree = ()         # scheduler-private state (() if stateless)
    # fused-gather edge weights prepared from ``graph.edge_data`` in each
    # color-step's edge order (``Engine.prepare_weights``); () if none
    gas_weights: Pytree = ()

    def replace(self, **kw) -> "EngineState":
        """``dataclasses.replace``, except that a ``graph`` whose
        ``edge_data`` is another object drops ``gas_weights`` (prepared
        from the old edge data) unless the caller passes them too."""
        graph = kw.get("graph")
        if (graph is not None and "gas_weights" not in kw
                and graph.edge_data is not self.graph.edge_data):
            kw["gas_weights"] = ()
        return dataclasses.replace(self, **kw)


def init_state(
    program: VertexProgram,
    graph: DataGraph,
    initial_prio: Optional[jnp.ndarray] = None,
    sync_ops: Sequence[SyncOp] = (),
    scheduler: Optional[Scheduler] = None,
) -> EngineState:
    n = graph.n_vertices
    prio = (jnp.asarray(initial_prio, jnp.float32) if initial_prio is not None
            else program.initial_priority(n).astype(jnp.float32))
    globals_ = run_syncs(sync_ops, graph.vertex_data, graph.vertex_data, n)
    return EngineState(
        graph=graph,
        prio=prio,
        update_count=jnp.zeros(n, jnp.int32),
        step_index=jnp.zeros((), jnp.int32),
        total_updates=jnp.zeros((), jnp.int32),
        edges_touched=jnp.zeros((), jnp.int32),
        globals_=globals_,
        sched=scheduler.init(prio) if scheduler is not None else (),
    )


def apply_phase(
    program: VertexProgram,
    graph: DataGraph,
    mask: jnp.ndarray,
    glob: Pytree,
    *,
    edges: Optional[EdgeSet] = None,
    weights: Optional[Sequence[jnp.ndarray]] = None,
    interpret: Optional[bool] = None,
    residual_dtype=jnp.float32,
) -> Tuple[DataGraph, jnp.ndarray, jnp.ndarray]:
    """Executes ``f(v, S_v)`` for every vertex in ``mask`` simultaneously.

    Gather → ⊕-combine → apply (masked write-back) → edge_out (masked to
    out-edges of updated vertices).  Returns (new graph, residual·mask,
    edges touched).  Passing ``edges`` (a prepared ``EdgeSet``) routes the
    gather⊕combine through the fused GAS kernel with active-block skipping
    (DESIGN.md §3.5), with ``weights`` as ``fused_apply_phase`` takes them;
    the dense path gathers all E edges regardless of mask.

    ``residual_dtype`` is the scheduler's priority precision: f32 by
    default, f64 opt-in for tolerance regimes below the f32 residual floor
    (~1e-6; requires jax x64 and f64 graph data to matter).
    """
    if edges is not None:
        return fused_apply_phase(program, graph, mask, glob, edges,
                                 weights=weights, interpret=interpret,
                                 residual_dtype=residual_dtype)
    st = graph.structure
    receivers = jnp.asarray(st.receivers)
    senders = jnp.asarray(st.senders)

    with jax.named_scope("graphlab.gather"):
        ctx = edge_ctx(graph)
        msgs = program.gather(ctx)
        acc = segment_combine(msgs, receivers, st.n_vertices,
                              program.combiner, receivers_np=st.receivers)

    with jax.named_scope("graphlab.apply"):
        new_v, residual = program.apply(graph.vertex_data, acc, glob)
        vdata = masked_update(graph.vertex_data, new_v, mask)
    graph = graph.replace(vertex_data=vdata)

    if program.has_edge_out:
        # The update at v owns its adjacent edges (edge consistency): we
        # rewrite out-edges of updated vertices, reading freshly applied
        # vertex data (Gauss-Seidel within the step).
        ctx2 = edge_ctx(graph)
        new_src = jax.tree.map(lambda x: x[senders], vdata)
        src_acc = jax.tree.map(lambda a: a[senders], acc)
        new_e = program.edge_out(ctx2, new_src, src_acc)
        edata = masked_update(graph.edge_data, new_e, mask[senders])
        graph = graph.replace(edge_data=edata)

    with jax.named_scope("graphlab.apply"):
        residual = jnp.where(mask, residual.astype(residual_dtype), 0.0)
    return graph, residual, jnp.asarray(st.n_edges, jnp.int32)


def _source_degrees(st, leaves) -> Optional[jnp.ndarray]:
    """Out-degree of each full-edge source, or None: only
    degree_normalized_src leaves consult it, so don't gather/ship an [E]
    array otherwise."""
    if any(leaf.kind == "degree_normalized_src" for leaf in leaves):
        return jnp.asarray(st.out_degree[st.senders])
    return None


def fused_apply_phase(
    program: VertexProgram,
    graph: DataGraph,
    mask: jnp.ndarray,
    glob: Pytree,
    edges: EdgeSet,
    *,
    weights: Optional[Sequence[jnp.ndarray]] = None,
    interpret: Optional[bool] = None,
    residual_dtype=jnp.float32,
) -> Tuple[DataGraph, jnp.ndarray, jnp.ndarray]:
    """The fused GAS path: one kernel per declared gather leaf, no edge_ctx,
    no [E, D] message materialization, inactive row blocks skipped.

    Per leaf: the per-vertex feature table ``[N, ...]`` and the per-edge
    scalar weight are formed outside the kernel (both sub-[E, D]), the
    kernel streams the ``edges`` subset and accumulates in VMEM.  The
    weights are evaluated on the full edge data, or, for a subset of the
    edges (a color's), come prepared in ``weights``: one ``[E_pad]`` array
    per leaf in the subset's edge order (``Engine.prepare_weights``).
    Rows outside active blocks come back as zeros; they belong to
    unscheduled vertices whose apply output is discarded by
    ``masked_update`` and whose residual is masked below, so the fixed
    point matches the dense path.
    """
    st = graph.structure
    leaves, treedef = fused_gather_leaves(program)
    assert weights is not None or edges.perm is None, \
        "a subset of the edges takes its weights prepared"
    with jax.named_scope("graphlab.gather"):
        block_active = active_row_blocks(mask)
    src_deg = _source_degrees(st, leaves) if weights is None else None

    acc_leaves = []
    for i, leaf in enumerate(leaves):
        with jax.named_scope("graphlab.gather"):
            feat = leaf.feature(graph.vertex_data)
        trailing = feat.shape[1:]
        feat2 = feat.reshape(st.n_vertices, -1)
        if weights is not None:
            w = weights[i]
        else:
            with jax.named_scope("graphlab.edge_weight"):
                w = fused_edge_weight(leaf, graph.edge_data, st.n_edges,
                                      src_deg)
        acc = gather_combine(feat2, w, edges, block_active=block_active,
                             interpret=interpret)
        acc_leaves.append(acc.reshape((st.n_vertices,) + trailing))
    acc = jax.tree.unflatten(treedef, acc_leaves)

    with jax.named_scope("graphlab.apply"):
        new_v, residual = program.apply(graph.vertex_data, acc, glob)
        vdata = masked_update(graph.vertex_data, new_v, mask)
        residual = jnp.where(mask, residual.astype(residual_dtype), 0.0)
    graph = graph.replace(vertex_data=vdata)
    with jax.named_scope("graphlab.gather"):
        edges_touched = jnp.sum(jnp.where(
            block_active > 0, edges.block_counts, 0)).astype(jnp.int32)
    return graph, residual, edges_touched


def stream_apply_phase(
    program: VertexProgram,
    graph: DataGraph,
    mask: jnp.ndarray,
    glob: Pytree,
    tables: Dict[str, jnp.ndarray],
    *,
    fused_meta=None,
    interpret: Optional[bool] = None,
    tolerance: float = 1e-3,
    residual_dtype=jnp.float32,
) -> Tuple[DataGraph, jnp.ndarray, jnp.ndarray, Optional[jnp.ndarray]]:
    """``apply_phase`` over a *dynamic* edge structure (DESIGN.md §3.11).

    The streaming engines trade the baked-in structure constants for the
    ``tables`` dict of traced arrays {senders, receivers, edge_mask,
    rev_idx, in_deg, out_deg, block_counts}: a delta batch patches the
    table *values* (same shapes) and the jitted step never retraces.
    Capacity (slack) edge rows carry ``edge_mask == False`` and are routed
    to a dropped segment / zero weight, so they contribute exactly nothing.

    ``fused_meta`` (from ``Engine._build_stream_fused``) carries the static
    grid schedule of the capacity layout — receivers never move (slot
    reservation per receiver), so the GAS kernel's steps are computed once
    and only the senders/weights stream through the trace.

    Returns ``(graph, residual, edges_touched, prio_bump)``.  For
    edge-writing programs, ``prio_bump`` carries the *message residual*
    scattered to each written edge's receiver (Elidan-style BP
    scheduling): a delta edge's message jumps from its init value to a
    real one while the writer's own residual stays zero, so without the
    bump the reader would never re-execute and the stream would converge
    to a stale fixed point.  ``None`` for pure-gather programs.
    """
    st = graph.structure
    n = st.n_vertices
    senders, receivers = tables["senders"], tables["receivers"]
    emask = tables["edge_mask"]
    e_cap = senders.shape[0]

    if fused_meta is not None:
        leaves, treedef, step_rb, step_eb, e_pad = fused_meta
        with jax.named_scope("graphlab.gather"):
            block_active = active_row_blocks(mask)
        snd = jnp.pad(senders, (0, e_pad - e_cap))
        rcv = jnp.pad(receivers, (0, e_pad - e_cap),
                      constant_values=n + ROW_BLOCK)
        es = EdgeSet(n_vertices=n, senders=snd,
                     receivers=rcv, step_rb=step_rb, step_eb=step_eb)
        src_deg_e = tables["out_deg"][senders] if any(
            leaf.kind == "degree_normalized_src" for leaf in leaves) else None
        acc_leaves = []
        for leaf in leaves:
            with jax.named_scope("graphlab.gather"):
                feat = leaf.feature(graph.vertex_data)
            trailing = feat.shape[1:]
            with jax.named_scope("graphlab.edge_weight"):
                w = fused_edge_weight(leaf, graph.edge_data, e_cap,
                                      src_deg_e)
                w = jnp.where(emask, w, 0.0)
            acc = gather_combine(feat.reshape(n, -1), w, es,
                                 block_active=block_active,
                                 interpret=interpret)
            acc_leaves.append(acc.reshape((n,) + trailing))
        acc = jax.tree.unflatten(treedef, acc_leaves)
        with jax.named_scope("graphlab.gather"):
            edges_touched = jnp.sum(
                jnp.where(block_active > 0, tables["block_counts"], 0)
            ).astype(jnp.int32)
    else:
        rp = jnp.maximum(tables["rev_idx"], 0)
        has_rev = tables["rev_idx"] >= 0

        def _rev(x):
            y = x[rp]
            m = has_rev.reshape((-1,) + (1,) * (y.ndim - 1))
            return jnp.where(m, y, jnp.zeros_like(y))

        with jax.named_scope("graphlab.gather"):
            ctx = EdgeCtx(
                edata=graph.edge_data,
                rev_edata=jax.tree.map(_rev, graph.edge_data),
                src=jax.tree.map(lambda x: x[senders], graph.vertex_data),
                dst=jax.tree.map(lambda x: x[receivers], graph.vertex_data),
                src_deg=tables["out_deg"][senders],
                dst_deg=tables["in_deg"][receivers])
            msgs = program.gather(ctx)
            recv_idx = jnp.where(emask, receivers, n)
            acc = segment_combine(msgs, recv_idx, n + 1, program.combiner,
                                  indices_are_sorted=False)
            acc = jax.tree.map(lambda a: a[:n], acc)
            edges_touched = jnp.sum(emask.astype(jnp.int32))

    with jax.named_scope("graphlab.apply"):
        new_v, residual = program.apply(graph.vertex_data, acc, glob)
        vdata = masked_update(graph.vertex_data, new_v, mask)
    graph = graph.replace(vertex_data=vdata)

    prio_bump = None
    if program.has_edge_out:
        assert fused_meta is None, "edge_out programs keep the dense path"
        new_src = jax.tree.map(lambda x: x[senders], vdata)
        src_acc = jax.tree.map(lambda a: a[senders], acc)
        ctx2 = ctx._replace(
            src=new_src,
            dst=jax.tree.map(lambda x: x[receivers], vdata))
        new_e = program.edge_out(ctx2, new_src, src_acc)
        wmask = jnp.logical_and(mask[senders], emask)
        prio_bump = edge_residual_bump(graph.edge_data, new_e, wmask,
                                       receivers, emask, n, tolerance,
                                       dtype=residual_dtype)
        edata = masked_update(graph.edge_data, new_e, wmask)
        graph = graph.replace(edge_data=edata)

    with jax.named_scope("graphlab.apply"):
        residual = jnp.where(mask, residual.astype(residual_dtype), 0.0)
    return graph, residual, edges_touched, prio_bump


def edge_residual_bump(old_e: Pytree, new_e: Pytree, wmask: jnp.ndarray,
                       receivers: jnp.ndarray, emask: jnp.ndarray,
                       n: int, tolerance: float,
                       dtype=jnp.float32) -> jnp.ndarray:
    """Per-receiver priority contribution of adjacent-edge writes: the
    largest component change of each written edge, maxed into the vertex
    that reads it, thresholded at the tolerance.

    ``max`` rather than sum, and sub-tolerance changes dropped entirely:
    a re-executed vertex recomputes messages that differ by a few f32
    ulps, and summing that jitter across components/in-edges would push
    it past the tolerance and ping-pong forever.  Super-tolerance changes
    (a delta edge's message jumping off its init value) pass through and
    re-schedule the reader exactly once per real change."""
    delta = jnp.zeros(wmask.shape[0], dtype)
    for o, v in zip(jax.tree.leaves(old_e), jax.tree.leaves(new_e)):
        d = jnp.abs(v.astype(dtype) - o.astype(dtype))
        delta = jnp.maximum(delta, d.reshape(d.shape[0], -1).max(axis=1))
    delta = jnp.where(delta > tolerance, delta, 0.0)
    recv_idx = jnp.where(emask, receivers, n)
    return jnp.maximum(jax.ops.segment_max(
        jnp.where(wmask, delta, 0.0), recv_idx, n + 1), 0.0)[:n]


# Back-compat name: the reschedule rule now lives in the scheduler
# subsystem (core/scheduler.py, DESIGN.md §3.8).
schedule_phase = reschedule_prio


class Engine:
    """Base: an engine is a scheduler plus the shared phase loop.

    ``_step`` runs ``scheduler.num_phases`` select → apply → reschedule
    phases (``step`` is its jitted form); subclasses choose the scheduler —
    pass one via ``scheduler=`` or override ``_make_scheduler`` — and may
    override ``_phase_edge_sets`` to hand each phase its own prepared
    ``EdgeSet``s (the chromatic per-color edge ranges).

    ``use_fused`` selects the fused GAS gather⊕combine path (DESIGN.md §3.5)
    for programs that declare registry gathers: None (default) auto-enables
    it when the program qualifies, False forces the seed dense path, True
    requests it but still falls back when the program is non-fuseable (the
    LBP case).  ``gas_interpret`` threads the Pallas interpret flag to the
    kernel — tests use it to exercise the real kernel body on CPU.

    ``stream_tables`` (DESIGN.md §3.11, built by ``stream/ingest.py``)
    switches the engine to *dynamic structure* mode: ``graph`` must be the
    capacity-padded data graph of a ``StreamingGraph``, the edge arrays
    flow through the jitted step as traced arguments instead of baked
    constants, and ``apply_delta`` patches their values in place — zero
    recompilations until ``regrow()``.
    """

    def __init__(
        self,
        program: VertexProgram,
        graph: DataGraph,
        tolerance: float = 1e-3,
        sync_ops: Sequence[SyncOp] = (),
        *,
        scheduler: Optional[Scheduler] = None,
        use_fused: Optional[bool] = None,
        gas_interpret: Optional[bool] = None,
        stream_tables: Optional[Dict[str, Any]] = None,
        residual_dtype=None,
        obs=None,
    ):
        self.program = program
        self.structure = graph.structure
        self.tolerance = float(tolerance)
        self.sync_ops = tuple(sync_ops)
        self.residual_dtype = (jnp.float32 if residual_dtype is None
                               else residual_dtype)
        fusable = supports_fused_gather(program)
        self.use_fused = fusable if use_fused is None \
            else bool(use_fused) and fusable
        self.gas_interpret = gas_interpret
        self.scheduler = (scheduler if scheduler is not None
                          else self._make_scheduler())
        self._tables: Optional[Dict[str, jnp.ndarray]] = None
        self._stream_fused_meta = None
        self._stream_colors: Optional[np.ndarray] = None
        if stream_tables is not None:
            if not isinstance(self.scheduler, SweepScheduler):
                raise UnsupportedStreamingError(
                    "streaming supports sweep-scheduled local engines; "
                    "dynamic/prioritized schedules stream through the dist "
                    "engines (arbitration there reads the dynamic tables)")
            self._stream_colors = np.asarray(self.scheduler.colors, np.int32)
            self.set_stream_tables(stream_tables)
            if self.use_fused:
                self._stream_fused_meta = self._build_stream_fused()
        # Telemetry (DESIGN §3.15): pure host-side config — nothing below
        # reads it while building ``_step``, so the jaxpr is byte-identical
        # with obs on/off (tests/test_obs.py asserts it).
        if obs is None:
            from repro.obs.config import ObsConfig
            obs = ObsConfig()
        self.obs = obs
        # per-graph arrays ride the jitted step as arguments — the fused
        # path's prepared edge sets and a sweep's coloring.  Baked in as
        # constants, a 10⁷-edge graph would inflate the program, its compile
        # time and its compile-cache key, and XLA would fold a mask constant
        # per color-step out of the coloring.
        static = stream_tables is None
        self._phase_groups, gas = ([], None)
        # per phase group, the gather set's map from its edges into the
        # full edge arrays (None where it gathers over all of them): the
        # fused weights of a color's edges are gathered through it once
        # per edge data (``prepare_weights``), so the step carries no perm
        self._gas_perms: list = []
        if self.use_fused and static:
            with span("graphlab.edge_sets"):
                self._phase_groups, gas = self._phase_edge_sets()
            self._gas_perms = [sets["gather"].perm for sets in gas]
            gas = [dict(sets, gather=dataclasses.replace(sets["gather"],
                                                         perm=None))
                   for sets in gas]
        self._prepares_weights = any(p is not None for p in self._gas_perms)
        self._jit_weights = jax.jit(self._gas_weights)
        self._consts = {
            "gas": gas,
            "colors": (self.scheduler.colors
                       if static and isinstance(self.scheduler,
                                                SweepScheduler) else None)}
        self._trace_count = 0  # bumped at trace time; delta tests assert 0 new
        self._jit_step = jax.jit(self._step_unweighted)

    def _make_scheduler(self) -> Scheduler:
        """Default schedule when none is passed: a single-color sweep
        (execute everything scheduled — the BSP/vertex-consistency case)."""
        return SweepScheduler(self.program, self.structure, self.tolerance)

    # -- streaming (dynamic structure) ---------------------------------------
    def set_stream_tables(self, tables: Dict[str, Any]) -> None:
        """(Re)loads the dynamic structure tables after a delta batch.  The
        treedef/shapes/dtypes never change between ``regrow()``s, so the
        jitted step's cache entry keeps hitting.  The live coloring rides
        along as a table so incremental color repair (DESIGN.md §3.12)
        never retraces either."""
        self._tables = {k: jnp.asarray(v) for k, v in tables.items()}
        if self._stream_colors is not None:
            self._tables["colors"] = jnp.asarray(self._stream_colors)

    def set_stream_colors(self, colors) -> None:
        """Swaps in a repaired coloring (values only — same shape/dtype)."""
        self._stream_colors = np.asarray(colors, np.int32)
        if self._tables is not None:
            self._tables["colors"] = jnp.asarray(self._stream_colors)

    def _build_stream_fused(self):
        """Static GAS metadata of the capacity layout: slot reservation per
        receiver keeps the receiver array frozen, so the kernel grid is
        computed once, here."""
        leaves, treedef = fused_gather_leaves(self.program)
        st = self.structure
        recv = np.asarray(self._tables["receivers"])
        e_cap = recv.shape[0]
        e_pad = max(-(-e_cap // EDGE_BLOCK), 1) * EDGE_BLOCK
        pad_r = np.int32(st.n_vertices + ROW_BLOCK)
        recv_p = np.pad(recv, (0, e_pad - e_cap), constant_values=pad_r)
        step_rb, step_eb = csr_steps(recv_p, st.n_vertices)
        return (leaves, treedef, jnp.asarray(step_rb), jnp.asarray(step_eb),
                int(e_pad))

    def _phase_edge_sets(self) -> Tuple[List[Tuple[int, int]], list]:
        """Prepared EdgeSets of the fused path, per phase: ``gather`` the
        edges the phase gathers over, ``scatter`` the edges its fused
        reschedule deposits along (every out-edge of a vertex the phase
        may execute).  Returns ``(groups, stacked)``: ``groups`` lists
        ``(first phase, phase count)`` runs of consecutive phases whose
        sets share shapes, ``stacked`` the run's sets stacked on a leading
        axis — one entry per phase, or a single entry the run's phases
        share.  The base engine shares the full structure across all its
        phases; the chromatic engine restricts each to its color."""
        st = self.structure
        full = EdgeSet.build(st.senders, st.receivers, st.n_vertices)
        return ([(0, self.scheduler.num_phases)],
                [stack_edge_sets([{"gather": full, "scatter": full}])])

    def _gas_weights(self, edge_data, perms, src_deg):
        """Every fused leaf's edge weights, evaluated on the full edge data
        and gathered into each color-step's edge order: per phase group,
        per leaf, the group's ``[phases, E_pad]`` rows laid end to end in
        one f32 vector (() for a group that gathers over all edges).  Flat,
        because the TPU tiles a 2-D array's rows in eights: a group of one
        phase would take eight times its bytes."""
        leaves, _ = fused_gather_leaves(self.program)
        full = [fused_edge_weight(leaf, edge_data, self.structure.n_edges,
                                  src_deg) for leaf in leaves]
        return tuple(() if perm is None else
                     tuple(w[perm].reshape(-1) for w in full)
                     for perm in perms)

    def prepare_weights(self, state: EngineState) -> EngineState:
        """``state`` with ``gas_weights`` prepared from its edge data, where
        the engine's fused phases gather over a subset of the edges and the
        state has none.  The fused path runs only programs that never
        write edges, so the weights hold until ``graph.edge_data`` is
        replaced (``EngineState.replace`` then drops them)."""
        if not self._prepares_weights or state.gas_weights:
            return state
        src_deg = _source_degrees(self.structure,
                                  fused_gather_leaves(self.program)[0])
        with span("graphlab.weights"):
            weights = jax.block_until_ready(self._jit_weights(
                state.graph.edge_data, self._gas_perms, src_deg))
        return state.replace(gas_weights=weights)

    def _scatter_ctx(self, tables, sets) -> Optional[ScatterCtx]:
        """ScatterCtx for the fused reschedule (DESIGN.md §3.14), or None
        to keep the dense scatter.  Gated on f32 priorities: the f64
        residual opt-in keeps the dense path rather than silently
        downcasting through the f32 kernel."""
        if not (self.use_fused and self.program.schedule_neighbors):
            return None
        if self.residual_dtype != jnp.float32:
            return None
        if tables is None:
            return ScatterCtx(edges=sets["scatter"],
                              interpret=self.gas_interpret)
        if self._stream_fused_meta is None:
            return None
        # dynamic structure: the capacity EdgeSet streams through the
        # trace (values change, shapes never do); slack slots carry real
        # receiver ids, so the live edge mask must ride as the weights —
        # otherwise a reserved self-loop would bump its own receiver
        _, _, step_rb, step_eb, e_pad = self._stream_fused_meta
        n = self.structure.n_vertices
        e_cap = tables["senders"].shape[0]
        es = EdgeSet(
            n_vertices=n,
            senders=jnp.pad(tables["senders"], (0, e_pad - e_cap)),
            receivers=jnp.pad(tables["receivers"], (0, e_pad - e_cap),
                              constant_values=n + ROW_BLOCK),
            step_rb=step_rb, step_eb=step_eb)
        w = jnp.pad(tables["edge_mask"].astype(jnp.float32),
                    (0, e_pad - e_cap))
        return ScatterCtx(edges=es, weights=w,
                          interpret=self.gas_interpret)

    def _step(self, state: EngineState, tables=None,
              consts=None) -> EngineState:
        self._trace_count += 1
        if consts is None:
            consts = self._consts
        select_tables = tables
        if tables is None and consts["colors"] is not None:
            select_tables = {"colors": consts["colors"]}
        prev_vdata = state.graph.vertex_data
        glob = state.globals_
        gas_weights = state.gas_weights
        if self._prepares_weights and not gas_weights:
            raise ValueError("the state carries no prepared edge weights: "
                             "pass it through Engine.prepare_weights")

        def run_phase(phase, sets, carry, weights=None):
            graph, prio, sched, count, total, edges_t = carry
            with jax.named_scope("graphlab.select"):
                mask, sched = self.scheduler.select(sched, prio, phase,
                                                    tables=select_tables)
            if tables is None:
                graph, residual, et = apply_phase(
                    self.program, graph, mask, glob,
                    edges=sets["gather"] if sets else None,
                    weights=weights, interpret=self.gas_interpret,
                    residual_dtype=self.residual_dtype)
            else:
                graph, residual, et, bump = stream_apply_phase(
                    self.program, graph, mask, glob, tables,
                    fused_meta=self._stream_fused_meta,
                    interpret=self.gas_interpret, tolerance=self.tolerance,
                    residual_dtype=self.residual_dtype)
            with jax.named_scope("graphlab.reschedule"):
                prio, sched = self.scheduler.reschedule(
                    sched, prio, mask, residual, tables=tables,
                    scatter=self._scatter_ctx(tables, sets))
                if tables is not None and bump is not None:
                    prio = prio + bump
            with jax.named_scope("graphlab.apply"):
                count = count + mask.astype(jnp.int32)
                total = total + jnp.sum(mask.astype(jnp.int32))
            with jax.named_scope("graphlab.gather"):
                edges_t = edges_t + et
            return graph, prio, sched, count, total, edges_t

        carry = (state.graph, state.prio, state.sched, state.update_count,
                 state.total_updates, state.edges_touched)
        # One phase per color for the chromatic sweep.  Consecutive phases
        # whose prepared edge sets share a shape run as one loop over the
        # stacked sets, so a sweep with hundreds of colors traces, lowers
        # and compiles a handful of phase bodies; the sync op runs safely
        # between phases.
        if consts["gas"] is None:
            for phase in range(self.scheduler.num_phases):
                carry = run_phase(phase, None, carry)
        else:
            for g, ((first, n), stacked) in enumerate(
                    zip(self._phase_groups, consts["gas"])):
                shared = jax.tree.leaves(stacked)[0].shape[0] == 1
                group_w = gas_weights[g] if gas_weights else ()

                def body(phase, c, first=first, stacked=stacked,
                         shared=shared, group_w=group_w):
                    i = 0 if shared else phase - first
                    with jax.named_scope("graphlab.edge_sets"):
                        sets = jax.tree.map(lambda x: x[i], stacked)
                    e_pad = sets["gather"].senders.shape[0]
                    with jax.named_scope("graphlab.edge_weight"):
                        weights = [jax.lax.dynamic_slice_in_dim(
                            w, i * e_pad, e_pad) for w in group_w] or None
                    return run_phase(phase, sets, c, weights)

                if n == 1:
                    carry = body(first, carry)
                else:
                    carry = jax.lax.fori_loop(first, first + n, body, carry)

        graph, prio, sched, count, total, edges_t = carry
        # the carry's edge data are new tracers: keep the weights explicitly
        state = state.replace(
            graph=graph, prio=prio, sched=sched, update_count=count,
            total_updates=total, edges_touched=edges_t,
            step_index=state.step_index + 1, gas_weights=gas_weights)
        return self._run_syncs(state, prev_vdata)

    def _step_unweighted(self, state: EngineState, tables=None,
                         consts=None) -> EngineState:
        """``_step`` without ``gas_weights`` in its output: they pass
        through unchanged, and as an output of the compiled step they
        would be copied every step.  ``step`` puts the input's back."""
        return self._step(state, tables, consts).replace(gas_weights=())

    # -- shared driver --------------------------------------------------------
    def init(self, graph: DataGraph, initial_prio=None) -> EngineState:
        with span("graphlab.upload"):
            state = init_state(self.program, graph, initial_prio,
                               self.sync_ops, scheduler=self.scheduler)
            if self.residual_dtype != jnp.float32:
                state = state.replace(
                    prio=state.prio.astype(self.residual_dtype))
            state = jax.block_until_ready(state)
        return self.prepare_weights(state)

    def step(self, state: EngineState) -> EngineState:
        state = self.prepare_weights(state)
        out = self._jit_step(state, self._tables, self._consts)
        return out.replace(gas_weights=state.gas_weights)

    def compile(self, state: EngineState):
        """Compiles the step ahead of time for ``state``'s shapes; ``step``
        and ``run`` then call that executable.  Returns it, for its
        ``as_text()`` and ``memory_analysis()``."""
        state = self.prepare_weights(state)
        with span("graphlab.lower"):
            lowered = self._jit_step.lower(state, self._tables, self._consts)
        with span("graphlab.compile"):
            self._jit_step = lowered.compile()
        return self._jit_step

    def _run_syncs(self, state: EngineState, prev_vdata) -> EngineState:
        if not self.sync_ops:
            return state
        with jax.named_scope("graphlab.sync"):
            g = run_syncs(self.sync_ops, state.graph.vertex_data, prev_vdata,
                          self.structure.n_vertices)
        return state.replace(globals_=g)

    def run(
        self,
        state: EngineState,
        max_steps: int = 100,
        trace_fn: Optional[Callable[[EngineState], Dict[str, float]]] = None,
        *,
        trace_every: Optional[int] = None,
        supervisor=None,
        session=None,
    ) -> Tuple[EngineState, List[Dict[str, float]]]:
        """Host loop: step until the scheduler reports itself empty
        (default: max prio ≤ tol).

        Termination here is the bulk-synchronous collapse of the paper's
        distributed consensus algorithm [26]: "all schedulers empty" is a
        global reduction evaluated at the step barrier (DESIGN.md §3.7).

        Trace rows follow the canonical schema (obs.metrics.METRICS_SCHEMA
        — ``step``/``updates``/``edges_touched``/``residual_max``/
        ``backlog`` plus structurally-zero traffic fields); ``trace_fn``
        extras are merged on top.  Rows are recorded lazily as device
        scalars and fetched with **one** host transfer every
        ``trace_every`` steps (default: ``obs.trace_every``, i.e. 1 — the
        pre-§3.15 behavior forced a blocking sync per step to ``int()``
        each field).  A ``supervisor`` (obs.Supervisor) observes after
        every step — for a ``WorkStealingScheduler`` it fires
        ``steal_backlog`` when per-queue update counters skew; a
        ``session`` (obs.ObsSession) additionally receives rows, events,
        and timeline spans.  Host spans (``obs.span``): ``graphlab.run``
        over the call, and per step ``graphlab.done`` (the scheduler's
        check, which blocks on the device) and ``graphlab.dispatch``
        (``step``).  A state that lacks its prepared edge weights gets
        them first (``prepare_weights``, span ``graphlab.weights``).
        """
        from repro.obs.metrics import RowCollector, lazy_local_row
        every = int(trace_every) if trace_every is not None \
            else self.obs.trace_every
        want_rows = (trace_fn is not None or self.obs.enabled
                     or session is not None)
        col = RowCollector(every, session=session)
        state = self.prepare_weights(state)
        with span("graphlab.run", session=session, track="local"):
            for _ in range(max_steps):
                with span("graphlab.done", session=session, track="local"):
                    done = bool(self.scheduler.done(state.sched, state.prio))
                if done:
                    break
                with span("graphlab.dispatch", session=session,
                          track="local"):
                    state = self.step(state)
                if supervisor is not None:
                    _, state = supervisor.observe(self, state)
                if want_rows:
                    row = lazy_local_row(state, self.tolerance,
                                         self.obs.residual_quantiles)
                    row["backlog"] = self.scheduler.backlog(state.sched,
                                                            state.prio)
                    col.push(row, extra=dict(trace_fn(state))
                             if trace_fn else None)
            col.drain()
        return state, col.rows

    def run_while(self, state: EngineState, max_steps: int = 100) -> EngineState:
        """Fully-jitted driver (used for lowering / production runs).

        In streaming mode the current tables are baked into this trace —
        a later delta needs a fresh ``run_while`` call (``run``/``step``
        stay retrace-free; they thread the tables as arguments)."""

        def cond(s):
            with jax.named_scope("graphlab.done"):
                return jnp.logical_and(
                    s.step_index < max_steps,
                    jnp.logical_not(self.scheduler.done(s.sched, s.prio)))

        return jax.lax.while_loop(
            cond, lambda s: self._step(s, self._tables, self._consts),
            self.prepare_weights(state))
