"""Delta ingestion: splice mutations into *running* engines (DESIGN §3.11).

``apply_delta(engine, state, batch)`` is the subsystem's contract:

  1. the ``StreamingGraph`` assigns slots (host bookkeeping, no engine
     involvement);
  2. engine state rows are spliced — new vertex/edge data, and on the
     distributed engines the ghost caches + versioned send tables are
     patched incrementally (a cross-machine edge claims a slab slot from
     the per-peer slack and warms the cache with the owner's current row —
     no layout rebuild, no retrace);
  3. scheduler priority is re-seeded for exactly the touched scopes — the
     distance-1 closed neighborhoods of mutated vertices
     (``core/scheduler.py:reseed_scopes``, the paper's Sec. 3.2 dynamic
     computation: reschedule the scopes whose data changed, nothing else).

Every patch is a value write into same-shaped arrays, so the jitted step's
cache entry keeps hitting: applying a delta within capacity slack performs
**zero recompilations** (asserted by tests/test_stream.py via the engines'
trace counters).  When slack runs out, ``CapacityError`` escapes and
``regrow_engine`` compacts the live state and rebuilds through the
existing two-phase atom path (``core/partition.py``) — the paper's elastic
placement, reused for growth.

Deletion (DESIGN §3.12) is the inverse splice: ``DelEdge`` frees a slot
back to the inert self-loop of the slack layout (swap-with-last keeps the
receiver region contiguous, so the data row of at most one surviving edge
moves), ``DelVertex`` cascades over its incident edges and returns the
slot to spare capacity, and the *former* distance-1 neighborhood is
re-seeded so stale contributions drain.

Quantized wire (DESIGN §3.14) is fully supported: under a lossy
``WireConfig`` every splice patches the owner-side error-feedback mirrors
in lockstep with the ghost caches — a fresh cache line, its ``vref``/
``aref`` mirror row and every *existing* line of the same vertex warm with
the **encoded-then-decoded** owner row (owner and all cachers stay
bit-identical; the residual against the exact owner value rides the
pending delta and ships next step), deletions zero the mirror rows, data
writes put the exact value on the owner and the wire image on caches and
mirrors, and ghost-slab growth re-lays the ``aghost`` mirror together with
the cache slabs.  ``regrow_engine`` re-seeds the scopes of rows with
nonzero pending residual, so deferred top-k deltas are never orphaned by
a rebuild.  Same-color delta edges are
repaired at apply time (``_repair_colors``) instead of degrading to
Jacobi reads.  ``apply_delta`` is fenced against a live Chandy-Lamport
marker wave (``SnapshotInFlightError``), and when a ``DeltaJournal`` is
attached every committed batch is appended under a monotone offset — the
event log that snapshot cuts anchor to (``stream/recovery.py``).

Layering: stream/ imports core/ and dist/, never models/.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.chromatic import ChromaticEngine
from repro.core.coloring import coloring_for
from repro.core.engine_base import Engine, EngineState
from repro.core.graph import DataGraph
from repro.core.scheduler import reseed_scopes
from repro.dist.engine import (DistState, DistributedEngine,
                               ShardEngineBase, _expand_slabs)
from repro.dist.wire import encdec_rows
from repro.stream.delta import (AddEdge, AddVertex, DelEdge, DeltaBatch,
                                DeltaJournal, DelVertex, SetEdgeData,
                                SetVertexData)
from repro.stream.mutable import (CapacityError, SlackConfig, StreamingGraph,
                                  pad_edge_data, pad_vertex_data)

Pytree = Any


class SnapshotInFlightError(RuntimeError):
    """``apply_delta`` was called while a Chandy-Lamport marker wave is
    live (``DistState.snap is not None``).  Splicing rows mid-wave would
    mix pre- and post-delta values into one "consistent" cut silently;
    drain the wave first (step until ``snapshot_complete``, save, then
    ``clear_snapshot``) or abort it with ``clear_snapshot``."""


# ---------------------------------------------------------------------------
# small helpers
# ---------------------------------------------------------------------------

def _host(tree: Pytree) -> Pytree:
    return jax.tree.map(lambda x: np.asarray(x).copy(), tree)


def _leaf_rows(data, n_leaves: int) -> Optional[List[np.ndarray]]:
    """Normalizes a command's row payload to the flattened-leaf list."""
    if data is None:
        return None
    if isinstance(data, (list, tuple)):
        rows = list(data)
    else:
        rows = jax.tree.flatten(data)[0]
    if len(rows) != n_leaves:
        raise ValueError(
            f"delta row has {len(rows)} leaves, graph data has {n_leaves}")
    return [np.asarray(r) for r in rows]


def _write_row(leaves: List[np.ndarray], row: int,
               rows: Optional[List[np.ndarray]]) -> None:
    if rows is None:
        return
    for leaf, val in zip(leaves, rows):
        leaf[row] = val


def _masked_initial_prio(program, sgraph: StreamingGraph) -> np.ndarray:
    prio = np.asarray(program.initial_priority(sgraph.n_cap), np.float32)
    return np.where(sgraph.vertex_active, prio, 0.0)


# ---------------------------------------------------------------------------
# incremental color repair (DESIGN §3.12)
# ---------------------------------------------------------------------------

def _sg_neighbors(sg: StreamingGraph, v: int) -> Set[int]:
    nbrs = {int(s) for s in sg.senders[sg.in_slots(v)]}
    nbrs.update(int(sg.receivers[sl]) for sl in sg.out_slots.get(v, ()))
    nbrs.discard(v)
    return nbrs


def _ball_colors(sg: StreamingGraph, colors: np.ndarray, v: int,
                 radius: int) -> Set[int]:
    """Colors used within distance <= radius of ``v`` (excluding v)."""
    seen, frontier, used = {v}, {v}, set()
    for _ in range(radius):
        nxt = set()
        for u in frontier:
            for w in _sg_neighbors(sg, u):
                if w not in seen:
                    seen.add(w)
                    nxt.add(w)
                    used.add(int(colors[w]))
        frontier = nxt
    return used


def _conflict_pairs(sg: StreamingGraph, radius: int, s: int, r: int):
    pairs = [(s, r)]
    if radius >= 2:  # full consistency: distance-2 coloring
        pairs += [(s, u) for u in _sg_neighbors(sg, r) if u != s]
        pairs += [(r, u) for u in _sg_neighbors(sg, s) if u != r]
    return pairs


def _repair_colors(sg: StreamingGraph, colors: np.ndarray, num_colors: int,
                   radius: int, new_pairs) -> List[Tuple[int, int]]:
    """Greedy incremental recoloring: for every delta edge whose endpoints
    (or, at radius 2, whose distance-2 pairs) collide, move the lower-
    degree vertex to a color unused within its exclusion ball.  The sweep
    palette is static under zero-recompile streaming, so when every color
    is occupied this raises ``CapacityError`` — regrow recolors from
    scratch.  Mutates ``colors`` in place; returns the (vid, color)
    changes."""
    changes: List[Tuple[int, int]] = []
    for s, r in new_pairs:
        if s == r:
            continue
        for a, b in _conflict_pairs(sg, radius, s, r):
            if int(colors[a]) != int(colors[b]):
                continue  # an earlier repair already separated them
            done = False
            for v in sorted((a, b), key=lambda u: len(_sg_neighbors(sg, u))):
                used = _ball_colors(sg, colors, v, radius)
                for c in range(num_colors):
                    if c not in used:
                        colors[v] = c
                        changes.append((v, c))
                        done = True
                        break
                if done:
                    break
            if not done:
                raise CapacityError(
                    f"color palette ({num_colors} colors) exhausted "
                    f"repairing delta edge ({a}, {b})")
    return changes


def _wants_color_repair(engine) -> bool:
    radius = engine.program.consistency.exclusion_radius
    return radius >= 1 and getattr(engine, "num_colors", 1) > 1


# ---------------------------------------------------------------------------
# engine builders (record their own recipe so regrow can replay it)
# ---------------------------------------------------------------------------

def make_local_engine(
    program,
    graph: DataGraph,
    *,
    engine_cls=Engine,
    tolerance: float = 1e-3,
    slack: SlackConfig = SlackConfig(),
    sync_ops: Sequence = (),
    use_fused: Optional[bool] = None,
    gas_interpret: Optional[bool] = None,
    initial_prio: Optional[np.ndarray] = None,
    in_capacity: Optional[np.ndarray] = None,
    n_cap: Optional[int] = None,
) -> Tuple[Engine, EngineState]:
    """A streaming shared-memory engine over ``graph``.

    ``engine_cls`` picks the sweep flavour: ``Engine`` (single-color BSP
    sweep) or ``ChromaticEngine`` (Gauss-Seidel color sweep — required for
    message-passing programs like LBP whose Jacobi cold start stalls).
    ``in_capacity`` sizes per-vertex in-edge regions beyond the uniform
    slack (the ingress side usually knows the degrees its journals will
    deliver — power-law hubs overflow a uniform minimum)."""
    sg, init_perm = StreamingGraph.build(graph.structure, slack,
                                         n_cap=n_cap,
                                         in_capacity=in_capacity)
    padded = DataGraph(
        vertex_data=jax.tree.map(jnp.asarray,
                                 pad_vertex_data(graph.vertex_data,
                                                 sg.n_cap)),
        edge_data=jax.tree.map(jnp.asarray,
                               pad_edge_data(graph.edge_data, sg,
                                             init_perm)),
        structure=sg.capacity_structure())
    ekw = {}
    if issubclass(engine_cls, ChromaticEngine):
        # palette headroom for incremental color repair (DESIGN §3.12)
        ekw["spare_colors"] = slack.color_slack
    engine = engine_cls(program, padded, tolerance=tolerance,
                        sync_ops=sync_ops, use_fused=use_fused,
                        gas_interpret=gas_interpret,
                        stream_tables=sg.tables(), **ekw)
    prio0 = _masked_initial_prio(program, sg)
    if initial_prio is not None:
        prio0[:len(initial_prio)] = np.asarray(initial_prio, np.float32)
        prio0 = np.where(sg.vertex_active, prio0, 0.0)
    state = engine.init(padded, initial_prio=jnp.asarray(prio0))
    engine._stream_graph = sg
    engine._stream_config = dict(
        kind="local", engine_cls=engine_cls, program=program,
        tolerance=tolerance, slack=slack, sync_ops=tuple(sync_ops),
        use_fused=use_fused, gas_interpret=gas_interpret)
    engine._stream_patcher = None
    return engine, state


def make_dist_engine(
    program,
    graph: DataGraph,
    mesh,
    *,
    engine_cls=DistributedEngine,
    tolerance: float = 1e-3,
    slack: SlackConfig = SlackConfig(),
    sync_ops: Sequence = (),
    initial_prio: Optional[np.ndarray] = None,
    in_capacity: Optional[np.ndarray] = None,
    n_cap: Optional[int] = None,
    **kw,
) -> Tuple[ShardEngineBase, DistState]:
    """A streaming distributed engine (sweep or locking) over ``graph``.

    The capacity structure's slack slots are inert self-loops, so the
    two-phase atom placement, the ghost slabs and (for the sweep engine)
    the coloring are all computed over the real edges plus reserved room.
    """
    sg, init_perm = StreamingGraph.build(graph.structure, slack,
                                         n_cap=n_cap,
                                         in_capacity=in_capacity)
    cap_st = sg.capacity_structure()
    padded = DataGraph(
        vertex_data=jax.tree.map(jnp.asarray,
                                 pad_vertex_data(graph.vertex_data,
                                                 sg.n_cap)),
        edge_data=jax.tree.map(jnp.asarray,
                               pad_edge_data(graph.edge_data, sg,
                                             init_perm)),
        structure=cap_st)
    if engine_cls is DistributedEngine and "colors" not in kw:
        # color the *real* structure (capacity self-loops would confuse a
        # proper coloring); inactive vertices take color 0
        colors = np.zeros(sg.n_cap, np.int32)
        colors[: graph.structure.n_vertices] = coloring_for(
            graph.structure, program.consistency)
        kw["colors"] = colors
        # palette headroom for incremental color repair (DESIGN §3.12)
        kw.setdefault("spare_colors", slack.color_slack)
    engine = engine_cls(
        program, padded, mesh, tolerance=tolerance, sync_ops=sync_ops,
        stream_real_edges=sg.edge_mask.copy(),
        ghost_slack=slack.ghost_slack, eghost_slack=slack.eghost_slack,
        **kw)
    prio0 = _masked_initial_prio(program, sg)
    if initial_prio is not None:
        prio0[:len(initial_prio)] = np.asarray(initial_prio, np.float32)
        prio0 = np.where(sg.vertex_active, prio0, 0.0)
    state = engine.init(initial_prio=prio0)
    engine._stream_graph = sg
    engine._stream_config = dict(
        kind="dist", program=program, tolerance=tolerance, slack=slack,
        sync_ops=tuple(sync_ops), mesh=mesh, engine_cls=engine_cls,
        kwargs={k: v for k, v in kw.items() if k != "colors"})
    engine._stream_patcher = None
    return engine, state


# ---------------------------------------------------------------------------
# the local patcher
# ---------------------------------------------------------------------------

class _LocalPatcher:
    def __init__(self, engine: Engine):
        self.engine = engine
        self.sg: StreamingGraph = engine._stream_graph

    def _drop_edge(self, src: int, dst: int,
                   eleaves: List[np.ndarray]) -> None:
        """Frees a slot and mirrors the swap-with-last in the data rows:
        the moved edge's row fills the hole, the vacated tail row zeroes
        (inert self-loops must carry no stale contribution)."""
        slot, moved_from = self.sg.del_edge(src, dst)
        if moved_from is not None:
            for leaf in eleaves:
                leaf[slot] = leaf[moved_from]
        vacated = moved_from if moved_from is not None else slot
        for leaf in eleaves:
            leaf[vacated] = 0

    def apply(self, state: EngineState, batch: DeltaBatch) -> EngineState:
        sg, engine = self.sg, self.engine
        cp = _snapshot_sg(sg)
        vleaves, vdef = jax.tree.flatten(_host(state.graph.vertex_data))
        eleaves, edef = jax.tree.flatten(_host(state.graph.edge_data))
        touched = np.zeros(sg.n_cap, bool)
        new_pairs: List[Tuple[int, int]] = []
        colors = None
        try:
            for cmd in batch:
                if isinstance(cmd, AddVertex):
                    vid = sg.add_vertex(cmd.vid)
                    _write_row(vleaves, vid,
                               _leaf_rows(cmd.data, len(vleaves)))
                    touched[vid] = True
                elif isinstance(cmd, AddEdge):
                    slot = sg.add_edge(cmd.src, cmd.dst)
                    _write_row(eleaves, slot,
                               _leaf_rows(cmd.data, len(eleaves)))
                    touched[cmd.src] = touched[cmd.dst] = True
                    new_pairs.append((int(cmd.src), int(cmd.dst)))
                elif isinstance(cmd, SetVertexData):
                    _write_row(vleaves, int(cmd.vid),
                               _leaf_rows(cmd.data, len(vleaves)))
                    touched[int(cmd.vid)] = True
                elif isinstance(cmd, SetEdgeData):
                    slot = sg.slot_of(cmd.src, cmd.dst)
                    _write_row(eleaves, slot,
                               _leaf_rows(cmd.data, len(eleaves)))
                    touched[cmd.src] = touched[cmd.dst] = True
                elif isinstance(cmd, DelEdge):
                    touched[int(cmd.src)] = touched[int(cmd.dst)] = True
                    self._drop_edge(int(cmd.src), int(cmd.dst), eleaves)
                elif isinstance(cmd, DelVertex):
                    vid = int(cmd.vid)
                    # the *former* neighborhood reseeds: its scopes lose a
                    # contribution and must drain the stale value
                    ins = [int(s) for s in sg.senders[sg.in_slots(vid)]]
                    outs = [int(sg.receivers[sl])
                            for sl in sg.out_slots.get(vid, [])]
                    touched[vid] = True
                    for u in ins + outs:
                        touched[u] = True
                    for u in ins:
                        if (u, vid) in sg.edge_slot:
                            self._drop_edge(u, vid, eleaves)
                    for u in outs:
                        if (vid, u) in sg.edge_slot:
                            self._drop_edge(vid, u, eleaves)
                    sg.del_vertex(vid)
                    for leaf in vleaves:
                        leaf[vid] = 0
                else:
                    raise TypeError(f"unknown delta command {cmd!r}")
            if new_pairs and _wants_color_repair(engine) \
                    and engine._stream_colors is not None:
                colors = engine._stream_colors.copy()
                if not _repair_colors(
                        sg, colors, engine.num_colors,
                        engine.program.consistency.exclusion_radius,
                        new_pairs):
                    colors = None  # nothing collided
        except BaseException:
            _restore_sg(sg, cp)  # a batch applies atomically or not at all
            raise

        prio, _ = reseed_scopes(
            jnp.asarray(np.asarray(state.prio)), touched, sg.senders,
            sg.receivers, sg.edge_mask, sg.n_cap,
            _masked_initial_prio(engine.program, sg))
        prio = jnp.where(jnp.asarray(sg.vertex_active), prio, 0.0)
        if colors is not None:
            engine.set_stream_colors(colors)
        engine.set_stream_tables(sg.tables())
        graph = state.graph.replace(
            vertex_data=jax.tree.unflatten(
                vdef, [jnp.asarray(x) for x in vleaves]),
            edge_data=jax.tree.unflatten(
                edef, [jnp.asarray(x) for x in eleaves]))
        # the fused weights were prepared from the old tables
        return state.replace(graph=graph, prio=prio, gas_weights=())


# ---------------------------------------------------------------------------
# the distributed patcher
# ---------------------------------------------------------------------------

def _snapshot_sg(sg: StreamingGraph) -> dict:
    return dict(
        vertex_active=sg.vertex_active.copy(), fill=sg.fill.copy(),
        out_deg=sg.out_deg.copy(), senders=sg.senders.copy(),
        edge_mask=sg.edge_mask.copy(), rev_idx=sg.rev_idx.copy(),
        edge_slot=dict(sg.edge_slot),
        out_slots={k: list(v) for k, v in sg.out_slots.items()},
        next_vid=sg._next_vid)


def _restore_sg(sg: StreamingGraph, cp: dict) -> None:
    sg.vertex_active[:] = cp["vertex_active"]
    sg.fill[:] = cp["fill"]
    sg.out_deg[:] = cp["out_deg"]
    sg.senders[:] = cp["senders"]
    sg.edge_mask[:] = cp["edge_mask"]
    sg.rev_idx[:] = cp["rev_idx"]
    sg.edge_slot = cp["edge_slot"]
    sg.out_slots = cp["out_slots"]
    sg._next_vid = cp["next_vid"]


def _relay_slab_rows(x: np.ndarray, S: int, b: int, nb: int) -> np.ndarray:
    """Re-lays a ``[S*S*b, ...]`` slab-shaped state/mirror array to the
    per-pair budget ``nb`` (new slots zero) — the host twin of the layout's
    ``_pad_slab`` for row-batched state leaves."""
    a = x.reshape((S * S, b) + x.shape[1:])
    out = np.zeros((S * S, nb) + x.shape[1:], x.dtype)
    out[:, :b] = a
    return out.reshape((S * S * nb,) + x.shape[1:])


class _DistPatcher:
    """Incremental layout surgery for the shard_map engines.

    Keeps host-side maps of the ghost slabs (which (machine, vertex) pairs
    hold a cache line, which slots are free) so a delta edge can claim a
    slot without scanning — the device tables and state rows are patched
    to match and re-uploaded once per batch.

    Under a lossy wire the §3.14 error-feedback mirrors (``vref``/``cpend``
    /``alast``/``aref``/``aghost``/``eref``) ride the same host pass
    (``self._wire``, flattened per component) and every splice patches them
    in lockstep with the caches — see the module docstring for the
    protocol.  When a (dest, owner) pair runs out of slack cache lines the
    slabs grow in place (``_grow_slabs``) instead of failing the batch; the
    per-batch checkpoint covers budgets, so a later failure in the same
    batch rolls the expansion back with everything else.
    """

    def __init__(self, engine: ShardEngineBase):
        self.engine = engine
        self.sg: StreamingGraph = engine._stream_graph
        lay = engine.layout
        self.S, self.B, self.EB = lay.n_machines, lay.budget, lay.e_budget
        self.n_loc, self.e_loc = lay.n_loc, lay.e_loc
        # slab maps: (dest machine, gid) -> slot b; free slots per pair
        self.ghost_slot: Dict[Tuple[int, int], int] = {}
        self.ghost_rows: Dict[int, List[int]] = {}
        self.ghost_free: Dict[Tuple[int, int], List[int]] = {}
        self._scan_slab(lay.ghost_gid, self.B, self.ghost_slot,
                        self.ghost_rows, self.ghost_free)
        self.eghost_slot: Dict[Tuple[int, int], int] = {}
        self.eghost_rows: Dict[int, List[int]] = {}
        self.eghost_free: Dict[Tuple[int, int], List[int]] = {}
        if lay.has_rev:
            self._scan_slab(lay.eghost_gid, self.EB, self.eghost_slot,
                            self.eghost_rows, self.eghost_free)
        if engine._use_fused:
            self.e_pad = lay.tables["gas_send"].size // self.S
        self.changed: Set[str] = set()
        # per-apply() scratch: flattened host leaves of the state slabs and
        # of the §3.14 wire mirrors (None between batches / default wire)
        self._leaves: Optional[Dict[str, List[np.ndarray]]] = None
        self._wire: Optional[Dict[str, tuple]] = None
        self._expanded = False

    def _scan_slab(self, slab_gid, budget, slot_map, rows_map, free_map):
        S = self.S
        g = slab_gid.reshape(S, S, budget)
        for d in range(S):
            for o in range(S):
                for b in range(budget):
                    gid = int(g[d, o, b])
                    if gid >= 0:
                        slot_map[(d, gid)] = b
                        rows_map.setdefault(gid, []).append(
                            d * (S * budget) + o * budget + b)
                    else:
                        free_map.setdefault((d, o), []).append(b)

    def _checkpoint(self):
        lay = self.engine.layout
        return (
            _snapshot_sg(self.sg),
            {k: v.copy() for k, v in lay.tables.items()},
            lay.ghost_gid.copy(), lay.eghost_gid.copy(),
            dict(self.ghost_slot),
            {k: list(v) for k, v in self.ghost_rows.items()},
            {k: list(v) for k, v in self.ghost_free.items()},
            dict(self.eghost_slot),
            {k: list(v) for k, v in self.eghost_rows.items()},
            {k: list(v) for k, v in self.eghost_free.items()},
            (lay.budget, lay.e_budget),
        )

    def _restore(self, cp):
        lay = self.engine.layout
        (sgcp, tables, gg, egg, gs, gr, gf, egs, egr, egf, budgets) = cp
        _restore_sg(self.sg, sgcp)
        lay.tables = tables
        lay.ghost_gid = gg
        lay.eghost_gid = egg
        self.ghost_slot, self.ghost_rows, self.ghost_free = gs, gr, gf
        self.eghost_slot, self.eghost_rows, self.eghost_free = egs, egr, egf
        # roll back any in-batch slab expansion: the checkpointed tables
        # and gid maps already carry the old shapes, only the budgets (and
        # their cached copies) need resetting — the device tables were
        # never touched (refresh happens on success only)
        lay.budget, lay.e_budget = budgets
        self.B, self.EB = lay.budget, lay.e_budget

    # -- in-batch slab growth -------------------------------------------------
    def _grow_slabs(self, extra_b: int, extra_eb: int) -> None:
        """Grows every (dest, owner) ghost slab in place instead of failing
        the batch: routes through ``_expand_slabs`` (the same remap path
        construction-time slack uses), re-lays the slab-shaped state leaves
        and the ``aghost`` wire mirror, and rebuilds the slab maps.  Shapes
        change, so the jitted step retraces once on success — within-slack
        batches stay zero-recompile."""
        lay = self.engine.layout
        S = self.S
        old_b, old_eb = lay.budget, lay.e_budget
        _expand_slabs(lay, int(extra_b), int(extra_eb))
        if extra_b > 0:
            nb = lay.budget
            vgh = self._leaves["vghost"]
            for i, x in enumerate(vgh):
                vgh[i] = _relay_slab_rows(x, S, old_b, nb)
            if self._wire is not None and "aghost" in self._wire:
                agh = self._wire["aghost"][0]
                for i, x in enumerate(agh):
                    agh[i] = _relay_slab_rows(x, S, old_b, nb)
            self.B = nb
            self.ghost_slot, self.ghost_rows, self.ghost_free = {}, {}, {}
            self._scan_slab(lay.ghost_gid, nb, self.ghost_slot,
                            self.ghost_rows, self.ghost_free)
        if extra_eb > 0 and lay.has_rev:
            neb = lay.e_budget
            egh = self._leaves["eghost"]
            for i, x in enumerate(egh):
                egh[i] = _relay_slab_rows(x, S, old_eb, neb)
            self.EB = neb
            self.eghost_slot, self.eghost_rows, self.eghost_free = {}, {}, {}
            self._scan_slab(lay.eghost_gid, neb, self.eghost_slot,
                            self.eghost_rows, self.eghost_free)
        self._expanded = True

    # -- §3.14 mirror splicing ------------------------------------------------
    def _enc1(self, val) -> np.ndarray:
        """One row's wire image: exactly what a receiver decodes from the
        wire for this row (``encdec_rows`` on a single row)."""
        x = np.asarray(val, np.float32)
        return encdec_rows(x[None], self.engine.wire.codec)[0]

    # -- slab allocation -----------------------------------------------------
    def _vertex_ghost(self, dest: int, vid: int, vown, vghost) -> int:
        """Local index (within dest's own+ghost rows) of vertex ``vid``
        cached at machine ``dest``; claims a slack cache line on first
        use and warms it with the owner's current row."""
        lay = self.engine.layout
        owner = int(lay.machine_of[vid])
        key = (dest, vid)
        if key not in self.ghost_slot:
            free = self.ghost_free.get((dest, owner), [])
            if not free:
                # slack exhausted: grow the slabs in place (one retrace on
                # success) instead of failing the whole batch
                self._grow_slabs(max(1, self.B), 0)
                free = self.ghost_free.get((dest, owner), [])
                if not free:  # pragma: no cover - growth always adds slots
                    raise CapacityError(
                        f"ghost slab ({dest} <- {owner}) vertex cache lines")
            b = free.pop(0)
            self.ghost_slot[key] = b
            S, B = self.S, self.B
            row = dest * (S * B) + owner * B + b
            lay.ghost_gid[row] = vid
            self.ghost_rows.setdefault(vid, []).append(row)
            send_row = owner * (S * B) + dest * B + b
            lay.tables["send_idx"][send_row] = \
                int(lay.row_of[vid]) - owner * self.n_loc
            lay.tables["send_mask"][send_row] = True
            self.changed.update(("send_idx", "send_mask"))
            own_row = int(lay.row_of[vid])
            if self._wire is not None:
                # §3.14 mirror splice: warm the new line AND re-anchor the
                # owner mirror + every existing cache line of ``vid`` at
                # the wire image of the owner row, so owner and all cachers
                # agree bit-identically; the residual vs. the exact owner
                # value rides the pending delta and ships next step
                rows = self.ghost_rows[vid]
                first = len(rows) == 1
                vref = self._wire["vref"][0]
                for gleaf, oleaf, rleaf in zip(vghost, vown, vref):
                    x = self._enc1(oleaf[own_row])
                    rleaf[own_row] = x
                    for rw in rows:
                        gleaf[rw] = x
                if first:
                    # no cacher accumulated contribs while unmapped; a
                    # stale residual from a long-gone cacher must not be
                    # delivered to the new one
                    self._wire["cpend"][0][0][own_row] = 0.0
                if "alast" in self._wire:
                    for al, ar, ag in zip(self._wire["alast"][0],
                                          self._wire["aref"][0],
                                          self._wire["aghost"][0]):
                        a = self._enc1(al[own_row])
                        ar[own_row] = a
                        for rw in rows:
                            ag[rw] = a
            else:
                for gleaf, oleaf in zip(vghost, vown):
                    gleaf[row] = oleaf[own_row]
        b = self.ghost_slot[key]
        return self.n_loc + int(lay.machine_of[vid]) * self.B + b

    def _edge_ghost(self, dest: int, slot: int, edata, eghost) -> int:
        """Local index of edge ``slot``'s row cached at ``dest`` (reverse-
        message reads); claims + warms an eghost line on first use."""
        lay = self.engine.layout
        owner = int(lay.machine_of[self.sg.receivers[slot]])
        key = (dest, slot)
        if key not in self.eghost_slot:
            free = self.eghost_free.get((dest, owner), [])
            if not free:
                self._grow_slabs(0, max(1, self.EB))
                free = self.eghost_free.get((dest, owner), [])
                if not free:  # pragma: no cover - growth always adds slots
                    raise CapacityError(
                        f"ghost slab ({dest} <- {owner}) edge cache lines")
            b = free.pop(0)
            self.eghost_slot[key] = b
            S, EB = self.S, self.EB
            row = dest * (S * EB) + owner * EB + b
            lay.eghost_gid[row] = slot
            self.eghost_rows.setdefault(slot, []).append(row)
            send_row = owner * (S * EB) + dest * EB + b
            lrow = int(lay.erow_of[slot])
            lay.tables["esend_idx"][send_row] = lrow - owner * self.e_loc
            lay.tables["esend_mask"][send_row] = True
            self.changed.update(("esend_idx", "esend_mask"))
            if self._wire is not None and "eref" in self._wire:
                # edge mirror splice: same bit-identical warm as vertices
                rows = self.eghost_rows[slot]
                for gleaf, oleaf, rleaf in zip(eghost, edata,
                                               self._wire["eref"][0]):
                    x = self._enc1(oleaf[lrow])
                    rleaf[lrow] = x
                    for rw in rows:
                        gleaf[rw] = x
            else:
                for gleaf, oleaf in zip(eghost, edata):
                    gleaf[row] = oleaf[lrow]
        b = self.eghost_slot[key]
        return self.e_loc + owner * self.EB + b

    # -- per-command surgery -------------------------------------------------
    def _splice_edge(self, slot: int, vown, vghost, edata, eghost) -> None:
        sg, lay = self.sg, self.engine.layout
        s, r = int(sg.senders[slot]), int(sg.receivers[slot])
        m = int(lay.machine_of[r])
        p = int(lay.machine_of[s])
        lrow = int(lay.erow_of[slot])
        if p == m:
            sl = int(lay.row_of[s]) - p * self.n_loc
        else:
            sl = self._vertex_ghost(m, s, vown, vghost)
        lay.tables["senders_local"][lrow] = sl
        lay.tables["edge_mask"][lrow] = True
        self.changed.update(("senders_local", "edge_mask"))
        if self.engine._use_fused:
            gas_row = (lrow // self.e_loc) * self.e_pad + lrow % self.e_loc
            lay.tables["gas_send"][gas_row] = sl
            self.changed.add("gas_send")
        # reverse linking (adjacent-edge writes read the twin's message)
        twin = int(sg.rev_idx[slot])
        if lay.has_rev and 0 <= twin != slot:
            trow = int(lay.erow_of[twin])
            q = int(lay.machine_of[sg.receivers[twin]])  # twin's machine
            lay.tables["rev_local"][lrow] = (
                trow - q * self.e_loc if q == m
                else self._edge_ghost(m, twin, edata, eghost))
            lay.tables["rev_local"][trow] = (
                lrow - m * self.e_loc if m == q
                else self._edge_ghost(q, slot, edata, eghost))
            self.changed.add("rev_local")

    # -- deletion surgery ----------------------------------------------------
    def _free_edge_ghosts(self, slot: int) -> None:
        """Releases every cache line holding ``slot``'s row (its reverse
        twin on another machine read it there)."""
        lay = self.engine.layout
        S, EB = self.S, self.EB
        for row in self.eghost_rows.pop(slot, []):
            d, rem = divmod(row, S * EB)
            o, b = divmod(rem, EB)
            lay.eghost_gid[row] = -1
            del self.eghost_slot[(d, slot)]
            self.eghost_free.setdefault((d, o), []).append(b)
            send_row = o * (S * EB) + d * EB + b
            lay.tables["esend_mask"][send_row] = False
            self.changed.add("esend_mask")

    def _free_eghost_line(self, dest: int, slot: int) -> None:
        """Releases ``slot``'s cache line at machine ``dest`` if present —
        each line has exactly one reader (the reverse pairing is unique),
        so deleting that reader frees the line.  Call while ``slot`` is
        still live (its receiver machine is looked up)."""
        key = (dest, slot)
        if key not in self.eghost_slot:
            return
        lay = self.engine.layout
        b = self.eghost_slot.pop(key)
        owner = int(lay.machine_of[self.sg.receivers[slot]])
        S, EB = self.S, self.EB
        row = dest * (S * EB) + owner * EB + b
        lay.eghost_gid[row] = -1
        rows = self.eghost_rows.get(slot)
        if rows is not None:
            rows.remove(row)
            if not rows:
                del self.eghost_rows[slot]
        self.eghost_free.setdefault((dest, owner), []).append(b)
        send_row = owner * (S * EB) + dest * EB + b
        lay.tables["esend_mask"][send_row] = False
        self.changed.add("esend_mask")

    def _rekey_edge_ghosts(self, old_slot: int, new_slot: int) -> None:
        """The swap-with-last moved an edge's home row; its cache lines
        keep their physical (dest, owner, b) position — only the gid map
        and the owner's send index change."""
        lay = self.engine.layout
        S, EB = self.S, self.EB
        rows = self.eghost_rows.pop(old_slot, [])
        if not rows:
            return
        new_lrow = int(lay.erow_of[new_slot])
        for row in rows:
            d, rem = divmod(row, S * EB)
            o, b = divmod(rem, EB)
            lay.eghost_gid[row] = new_slot
            self.eghost_slot[(d, new_slot)] = self.eghost_slot.pop(
                (d, old_slot))
            send_row = o * (S * EB) + d * EB + b
            lay.tables["esend_idx"][send_row] = new_lrow - o * self.e_loc
            self.changed.add("esend_idx")
        self.eghost_rows[new_slot] = rows

    def _clear_edge_row(self, slot: int, edata) -> None:
        """Resets a freed slot to the inert self-loop of the slack layout
        (sender = receiver, masked out, its own reverse) and zeroes its
        data row so no stale contribution survives a later re-splice."""
        sg, lay = self.sg, self.engine.layout
        dst = int(sg.receivers[slot])
        m = int(lay.machine_of[dst])
        lrow = int(lay.erow_of[slot])
        sl = int(lay.row_of[dst]) - m * self.n_loc
        lay.tables["senders_local"][lrow] = sl
        lay.tables["edge_mask"][lrow] = False
        self.changed.update(("senders_local", "edge_mask"))
        if lay.has_rev:
            lay.tables["rev_local"][lrow] = lrow - m * self.e_loc
            self.changed.add("rev_local")
        if self.engine._use_fused:
            gas_row = (lrow // self.e_loc) * self.e_pad + lrow % self.e_loc
            lay.tables["gas_send"][gas_row] = sl
            self.changed.add("gas_send")
        for leaf in edata:
            leaf[lrow] = 0
        if self._wire is not None and "eref" in self._wire:
            for rleaf in self._wire["eref"][0]:
                rleaf[lrow] = 0

    def _remove_edge(self, src: int, dst: int, vown, vghost, edata,
                     eghost) -> None:
        sg, lay = self.sg, self.engine.layout
        slot = sg.slot_of(src, dst)
        twin = int(sg.rev_idx[slot])
        m = int(lay.machine_of[dst])
        if lay.has_rev:
            self._free_edge_ghosts(slot)
            if 0 <= twin != slot:
                # the twin loses its reverse: unlink it and release the
                # cache line this edge held of the twin's row
                self._free_eghost_line(m, twin)
                trow = int(lay.erow_of[twin])
                lay.tables["rev_local"][trow] = -1
                self.changed.add("rev_local")
        _, moved_from = sg.del_edge(src, dst)
        lrow = int(lay.erow_of[slot])
        if moved_from is not None:
            mrow = int(lay.erow_of[moved_from])
            for leaf in edata:
                leaf[lrow] = leaf[mrow]
            if self._wire is not None and "eref" in self._wire:
                # the EF mirror row moves with its data row
                for rleaf in self._wire["eref"][0]:
                    rleaf[lrow] = rleaf[mrow]
            if lay.has_rev:
                lay.tables["rev_local"][lrow] = -1  # splice re-links twins
                self.changed.add("rev_local")
                self._rekey_edge_ghosts(moved_from, slot)
            self._splice_edge(slot, vown, vghost, edata, eghost)
            if lay.has_rev and int(sg.rev_idx[slot]) == slot:
                # a real self-loop moved: it is its own reverse
                lay.tables["rev_local"][lrow] = lrow - m * self.e_loc
            self._clear_edge_row(moved_from, edata)
        else:
            self._clear_edge_row(slot, edata)

    def _remove_vertex(self, vid: int, vown, vghost, edata, eghost,
                       touched: np.ndarray) -> None:
        sg, lay = self.sg, self.engine.layout
        ins = [int(s) for s in sg.senders[sg.in_slots(vid)]]
        outs = [int(sg.receivers[sl]) for sl in sg.out_slots.get(vid, [])]
        touched[vid] = True
        for u in ins + outs:
            touched[u] = True
        for u in ins:
            if (u, vid) in sg.edge_slot:
                self._remove_edge(u, vid, vown, vghost, edata, eghost)
        for u in outs:
            if (vid, u) in sg.edge_slot:
                self._remove_edge(vid, u, vown, vghost, edata, eghost)
        sg.del_vertex(vid)
        own_row = int(lay.row_of[vid])
        for leaf in vown:
            leaf[own_row] = 0
        if self._wire is not None:
            # a dead vertex's mirrors reset to the engine-init zero: a
            # later re-add of this slot must not inherit stale pending
            # residual (it would be "delivered" to the wrong vertex)
            for rleaf in self._wire["vref"][0]:
                rleaf[own_row] = 0
            self._wire["cpend"][0][0][own_row] = 0.0
            if "alast" in self._wire:
                for al in self._wire["alast"][0]:
                    al[own_row] = 0
                for ar in self._wire["aref"][0]:
                    ar[own_row] = 0
        # release the dead vertex's remote cache lines
        S, B = self.S, self.B
        for grow in self.ghost_rows.pop(vid, []):
            d, rem = divmod(grow, S * B)
            o, b = divmod(rem, B)
            lay.ghost_gid[grow] = -1
            del self.ghost_slot[(d, vid)]
            self.ghost_free.setdefault((d, o), []).append(b)
            send_row = o * (S * B) + d * B + b
            lay.tables["send_mask"][send_row] = False
            self.changed.add("send_mask")
            for gleaf in vghost:
                gleaf[grow] = 0
            if self._wire is not None and "aghost" in self._wire:
                for ag in self._wire["aghost"][0]:
                    ag[grow] = 0

    def _refresh_degrees(self) -> None:
        sg, lay = self.sg, self.engine.layout
        rows = lay.erow_of
        lay.tables["src_deg_e"][rows] = sg.out_deg[sg.senders]
        lay.tables["dst_deg_e"][rows] = sg.fill[sg.receivers]
        self.changed.update(("src_deg_e", "dst_deg_e"))

    # -- the batch -----------------------------------------------------------
    def apply(self, state: DistState, batch: DeltaBatch) -> DistState:
        engine, sg = self.engine, self.sg
        lay = engine.layout
        cp = self._checkpoint()
        self.changed = set()
        self._expanded = False
        vown, vdef = jax.tree.flatten(_host(state.vown))
        vghost, _ = jax.tree.flatten(_host(state.vghost))
        edata, edef = jax.tree.flatten(_host(state.edata))
        eghost, egdef = jax.tree.flatten(_host(state.eghost))
        self._leaves = {"vghost": vghost, "eghost": eghost}
        # §3.14 mirror splicing: the EF mirrors ride the same host pass as
        # the caches and every splice patches both in lockstep
        self._wire = None
        if state.wire is not None and engine.wire.uses_delta:
            self._wire = {k: jax.tree.flatten(_host(v))
                          for k, v in state.wire.items()}
        prio = np.asarray(state.prio).copy()
        touched = np.zeros(sg.n_cap, bool)
        new_pairs: List[Tuple[int, int]] = []
        new_colors = None
        try:
            for cmd in batch:
                if isinstance(cmd, AddVertex):
                    vid = sg.add_vertex(cmd.vid)
                    rows = _leaf_rows(cmd.data, len(vown))
                    own_row = int(lay.row_of[vid])
                    _write_row(vown, own_row, rows)
                    if self._wire is not None and rows is not None:
                        for val, rleaf in zip(rows, self._wire["vref"][0]):
                            rleaf[own_row] = self._enc1(val)
                    touched[vid] = True
                elif isinstance(cmd, AddEdge):
                    slot = sg.add_edge(cmd.src, cmd.dst)
                    rows = _leaf_rows(cmd.data, len(edata))
                    lrow = int(lay.erow_of[slot])
                    _write_row(edata, lrow, rows)
                    if self._wire is not None and "eref" in self._wire \
                            and rows is not None:
                        for val, rleaf in zip(rows, self._wire["eref"][0]):
                            rleaf[lrow] = self._enc1(val)
                    self._splice_edge(slot, vown, vghost, edata, eghost)
                    touched[cmd.src] = touched[cmd.dst] = True
                    new_pairs.append((int(cmd.src), int(cmd.dst)))
                elif isinstance(cmd, SetVertexData):
                    vid = int(cmd.vid)
                    rows = _leaf_rows(cmd.data, len(vown))
                    own_row = int(lay.row_of[vid])
                    _write_row(vown, own_row, rows)
                    grows = self.ghost_rows.get(vid, ())
                    if self._wire is not None and rows is not None:
                        # owner takes the exact value; caches and the vref
                        # mirror take its wire image, so the residual ships
                        # as pending delta (never silently dropped)
                        for val, rleaf, gleaf in zip(
                                rows, self._wire["vref"][0], vghost):
                            x = self._enc1(val)
                            rleaf[own_row] = x
                            for grow in grows:
                                gleaf[grow] = x
                    else:
                        for grow in grows:
                            _write_row(vghost, grow, rows)
                    touched[vid] = True
                elif isinstance(cmd, SetEdgeData):
                    slot = sg.slot_of(cmd.src, cmd.dst)
                    rows = _leaf_rows(cmd.data, len(edata))
                    lrow = int(lay.erow_of[slot])
                    _write_row(edata, lrow, rows)
                    egrows = self.eghost_rows.get(slot, ())
                    if self._wire is not None and "eref" in self._wire \
                            and rows is not None:
                        for val, rleaf, gleaf in zip(
                                rows, self._wire["eref"][0], eghost):
                            x = self._enc1(val)
                            rleaf[lrow] = x
                            for grow in egrows:
                                gleaf[grow] = x
                    else:
                        for grow in egrows:
                            _write_row(eghost, grow, rows)
                    touched[cmd.src] = touched[cmd.dst] = True
                elif isinstance(cmd, DelEdge):
                    touched[int(cmd.src)] = touched[int(cmd.dst)] = True
                    self._remove_edge(int(cmd.src), int(cmd.dst), vown,
                                      vghost, edata, eghost)
                elif isinstance(cmd, DelVertex):
                    self._remove_vertex(int(cmd.vid), vown, vghost, edata,
                                        eghost, touched)
                else:
                    raise TypeError(f"unknown delta command {cmd!r}")
            if new_pairs and _wants_color_repair(engine):
                new_colors = np.asarray(engine.colors, np.int32).copy()
                changes = _repair_colors(
                    sg, new_colors, engine.num_colors,
                    engine.program.consistency.exclusion_radius, new_pairs)
                if changes:
                    for v, c in changes:
                        lay.tables["colors_own"][int(lay.row_of[v])] = c
                    self.changed.add("colors_own")
                else:
                    new_colors = None  # nothing collided
        except BaseException:
            self._restore(cp)  # a batch applies atomically or not at all
            raise
        finally:
            self._leaves = None
        if new_colors is not None:
            engine.colors = new_colors  # table rollback covers the rest
        self._refresh_degrees()
        # the has-cacher masks are derived tables (which owned rows some
        # remote machine caches — the delta wire's dirtiness gate reads
        # them); recompute whenever the send tables or slab strides moved
        if self._expanded or self.changed & {"send_idx", "send_mask"}:
            vhas = np.zeros(self.S * self.n_loc, bool)
            ent = np.nonzero(lay.tables["send_mask"])[0]
            vhas[(ent // (self.S * lay.budget)) * self.n_loc
                 + lay.tables["send_idx"][ent]] = True
            lay.tables["vhas_cacher"] = vhas
            self.changed.add("vhas_cacher")
        if lay.has_rev and (self._expanded
                            or self.changed & {"esend_idx", "esend_mask"}):
            ehas = np.zeros(self.S * self.e_loc, bool)
            ent = np.nonzero(lay.tables["esend_mask"])[0]
            ehas[(ent // (self.S * lay.e_budget)) * self.e_loc
                 + lay.tables["esend_idx"][ent]] = True
            lay.tables["ehas_cacher"] = ehas
            self.changed.add("ehas_cacher")

        # re-seed exactly the touched scopes, in global vertex space, then
        # map onto the machine-major priority rows
        prio_g = np.zeros(sg.n_cap, np.float32)
        ok = lay.own_gid >= 0
        prio_g[lay.own_gid[ok]] = prio[ok]
        prio_g2, _ = reseed_scopes(
            jnp.asarray(prio_g), touched, sg.senders, sg.receivers,
            sg.edge_mask, sg.n_cap,
            _masked_initial_prio(engine.program, sg))
        prio_host = np.where(sg.vertex_active, np.asarray(prio_g2),
                             0.0).astype(np.float32)
        prio[ok] = prio_host[lay.own_gid[ok]]

        if self._expanded:
            # slab shapes changed: re-upload every table and rebuild the
            # jitted step (one retrace); within-slack batches never get
            # here and stay zero-recompile
            engine._finalize()
        else:
            engine.refresh_tables(sorted(self.changed))
        put = lambda leaves, tdef: jax.tree.map(
            lambda x: jax.device_put(jnp.asarray(x), engine._shard),
            jax.tree.unflatten(tdef, leaves))
        out = state.replace(
            vown=put(vown, vdef), vghost=put(vghost, vdef),
            edata=put(edata, edef), eghost=put(eghost, egdef),
            prio=jax.device_put(jnp.asarray(prio), engine._shard))
        if self._wire is not None:
            out = out.replace(wire={
                k: put(lv, td) for k, (lv, td) in self._wire.items()})
            self._wire = None
        return out


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def apply_delta(engine, state, batch: DeltaBatch, *, record: bool = True):
    """Splices a delta batch into a running engine's state.

    Raises ``CapacityError`` (state unchanged) when the preallocated slack
    cannot hold the batch — call ``regrow_engine`` and re-apply — and
    ``SnapshotInFlightError`` (state unchanged) while a Chandy-Lamport
    marker wave is live: a splice mid-wave would leak post-delta rows into
    the in-flight cut.  Drain the wave (step until ``snapshot_complete``,
    save, ``clear_snapshot``) or abort it first.

    When a ``DeltaJournal`` is attached (``attach_journal``), every batch
    that commits is appended to the journal; ``record=False`` replays an
    already-journaled batch (recovery) without re-appending.
    """
    if getattr(engine, "_stream_graph", None) is None:
        raise ValueError("engine was not built by stream.ingest "
                         "(make_local_engine / make_dist_engine)")
    if getattr(state, "snap", None) is not None:
        raise SnapshotInFlightError(
            "a Chandy-Lamport marker wave is in flight; drain it "
            "(step until snapshot_complete, save_snapshot, clear_snapshot) "
            "or abort it with clear_snapshot before applying deltas")
    if engine._stream_patcher is None:
        engine._stream_patcher = (
            _DistPatcher(engine) if isinstance(engine, ShardEngineBase)
            else _LocalPatcher(engine))
    from repro.obs.session import engine_span
    with engine_span(engine, "graphlab.apply_delta", track="stream",
                     cat="delta", args={"commands": len(batch)}):
        new_state = engine._stream_patcher.apply(state, batch)
    journal = getattr(engine, "_stream_journal", None)
    if journal is not None and record:
        engine._stream_offset = journal.append(batch) + 1
    return new_state


def attach_journal(engine, journal: DeltaJournal) -> None:
    """Makes ``journal`` the authoritative event log of this engine's
    mutation stream: every batch that commits through ``apply_delta``
    appends under a monotone offset, and snapshot cuts anchor to
    ``engine._stream_offset`` — the journal prefix the cut reflects
    (``dist/snapshot.py:save_snapshot`` records it; recovery replays the
    suffix, see ``stream/recovery.py``).

    Attach at build time, before any un-journaled batch lands: the
    contract is that the engine's graph equals the base graph plus the
    journal prefix ``[0, engine._stream_offset)``.
    """
    engine._stream_journal = journal
    engine._stream_offset = journal.next_offset


def stream_colors(engine) -> Optional[np.ndarray]:
    """The live coloring in global vertex space, after any incremental
    repairs (None when the engine runs single-color)."""
    if isinstance(engine, ShardEngineBase):
        c = getattr(engine, "colors", None)
        return None if c is None else np.asarray(c, np.int32)
    c = getattr(engine, "_stream_colors", None)
    return None if c is None else np.asarray(c, np.int32)


def readback(engine, state) -> DataGraph:
    """The live *real* graph (padding stripped) as a receiver-sorted
    ``DataGraph`` — scratch-engine comparisons, checkpoints, regrow."""
    sg: StreamingGraph = engine._stream_graph
    if isinstance(engine, ShardEngineBase):
        lay = engine.layout
        vleaves, vdef = jax.tree.flatten(_host(state.vown))
        eleaves, edef = jax.tree.flatten(_host(state.edata))
        ok = lay.own_gid >= 0

        def vpad(x):
            out = np.zeros((sg.n_cap,) + x.shape[1:], x.dtype)
            out[lay.own_gid[ok]] = x[ok]
            return out

        vdata = jax.tree.unflatten(vdef, [vpad(x) for x in vleaves])
        edata = jax.tree.unflatten(
            edef, [x[lay.erow_of] for x in eleaves])
    else:
        vdata = _host(state.graph.vertex_data)
        edata = _host(state.graph.edge_data)
    return sg.compact(vdata, edata)


def stream_prio(engine, state) -> np.ndarray:
    """Current priority in global vertex space [n_cap]."""
    sg: StreamingGraph = engine._stream_graph
    if isinstance(engine, ShardEngineBase):
        lay = engine.layout
        prio = np.asarray(state.prio)
        out = np.zeros(sg.n_cap, np.float32)
        ok = lay.own_gid >= 0
        out[lay.own_gid[ok]] = prio[ok]
        return out
    return np.asarray(state.prio)


def total_updates(engine, state) -> int:
    if isinstance(engine, ShardEngineBase):
        return int(np.asarray(state.update_count).sum())
    return int(state.total_updates)


def _wire_pending_mask(engine, state) -> Optional[np.ndarray]:
    """Global-vid mask of rows whose §3.14 mirrors still carry nonzero
    pending residual (deltas owed to some cache: ``vown−vref``, ``cpend``,
    ``alast−aref``, and the endpoints of edges with ``edata−eref``
    pending).  A rebuild delivers the *data* exactly (init gathers owner
    rows into every cache), but the scheduling signal of the unshipped
    contribs would be silently lost — deferred top-k deltas must not be
    orphaned by a regrow, so their scopes re-seed."""
    if not isinstance(engine, ShardEngineBase) \
            or getattr(state, "wire", None) is None:
        return None
    sg, lay = engine._stream_graph, engine.layout
    w = jax.tree.map(np.asarray, state.wire)
    wtol = engine.wire.resolve_tol(engine.tolerance)

    def rows_gap(a, b):
        out = None
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            d = np.abs(np.asarray(x, np.float32)
                       - np.asarray(y, np.float32))
            d = d.reshape(len(d), -1).max(axis=1)
            out = d if out is None else np.maximum(out, d)
        return out

    dirty = rows_gap(jax.tree.map(np.asarray, state.vown), w["vref"]) > wtol
    dirty |= np.abs(w["cpend"]) > wtol
    if "alast" in w:
        dirty |= rows_gap(w["alast"], w["aref"]) > wtol
    mask = np.zeros(sg.n_cap, bool)
    sel = (lay.own_gid >= 0) & dirty
    mask[lay.own_gid[sel]] = True
    if "eref" in w:
        epend = rows_gap(jax.tree.map(np.asarray, state.edata),
                         w["eref"]) > wtol
        slots = lay.erow_gid[np.nonzero(epend)[0]]
        slots = slots[slots >= 0]
        mask[sg.senders[slots]] = True
        mask[sg.receivers[slots]] = True
    return mask & sg.vertex_active


def regrow_engine(engine, state, *, slack: Optional[SlackConfig] = None,
                  in_capacity: Optional[np.ndarray] = None,
                  n_cap: Optional[int] = None):
    """Compacts the live state and rebuilds the engine with fresh slack —
    re-partitioning through the existing atom path (``place_vertices``
    inside the dist engine constructor).  Converged priorities carry over,
    so reconvergence stays incremental across the rebuild; under a lossy
    wire the scopes of rows with pending (unshipped) residual re-seed, so
    deferred top-k deltas are never orphaned by the rebuild.

    Returns ``(engine, state)``; the old pair is dead.
    """
    from repro.obs.session import engine_span
    with engine_span(engine, "graphlab.regrow", track="stream",
                     cat="delta"):
        return _regrow_engine(engine, state, slack=slack,
                              in_capacity=in_capacity, n_cap=n_cap)


def _regrow_engine(engine, state, *, slack, in_capacity, n_cap):
    cfg = dict(engine._stream_config)
    graph = readback(engine, state)
    prio_full = stream_prio(engine, state)
    pend = _wire_pending_mask(engine, state)
    if pend is not None and pend.any():
        sg = engine._stream_graph
        bumped, _ = reseed_scopes(
            jnp.asarray(prio_full), pend, sg.senders, sg.receivers,
            sg.edge_mask, sg.n_cap,
            _masked_initial_prio(engine.program, sg))
        prio_full = np.where(sg.vertex_active, np.asarray(bumped),
                             0.0).astype(np.float32)
    prio = prio_full[: graph.structure.n_vertices]
    slack = slack or cfg["slack"]
    if cfg["kind"] == "local":
        new_engine, new_state = make_local_engine(
            cfg["program"], graph, engine_cls=cfg["engine_cls"],
            tolerance=cfg["tolerance"], slack=slack,
            sync_ops=cfg["sync_ops"], use_fused=cfg["use_fused"],
            gas_interpret=cfg["gas_interpret"], initial_prio=prio,
            in_capacity=in_capacity, n_cap=n_cap)
    else:
        new_engine, new_state = make_dist_engine(
            cfg["program"], graph, cfg["mesh"], engine_cls=cfg["engine_cls"],
            tolerance=cfg["tolerance"], slack=slack,
            sync_ops=cfg["sync_ops"], initial_prio=prio,
            in_capacity=in_capacity, n_cap=n_cap, **cfg["kwargs"])
    # the journal outlives the layout: the event log is engine-agnostic;
    # an attached telemetry session rides along the same way
    for attr in ("_stream_journal", "_stream_offset", "_obs_session"):
        if hasattr(engine, attr):
            setattr(new_engine, attr, getattr(engine, attr))
    return new_engine, new_state


def _batch_capacity_hint(engine, batch: DeltaBatch
                         ) -> Tuple[np.ndarray, int]:
    """What the regrown layout must hold: current in-degrees plus the
    batch's per-receiver arrivals, and enough vertex slots for its
    AddVertex commands (the ingress side reads its own journal)."""
    sg: StreamingGraph = engine._stream_graph
    n_new = batch.n_new_vertices
    explicit = [c.vid for c in batch
                if isinstance(c, AddVertex) and c.vid is not None]
    n_needed = max([sg.n_cap] + [v + 1 for v in explicit])
    n_needed = max(n_needed, sg.n_real + n_new + 1)
    indeg = np.zeros(n_needed, np.int64)
    indeg[: sg.n_cap] = sg.fill
    for c in batch:
        if isinstance(c, AddEdge):
            indeg[int(c.dst)] += 1
    return indeg, n_needed


def apply_delta_growing(engine, state, batch: DeltaBatch,
                        *, slack: Optional[SlackConfig] = None,
                        max_regrows: int = 4, record: bool = True):
    """``apply_delta`` with automatic regrow-and-retry on capacity
    exhaustion.  The regrown in-edge regions and vertex table are sized
    from the failed batch itself, so those exhaust at most once; ghost
    slab demand depends on the *new* placement and cannot be precomputed,
    so the per-peer slack escalates (doubles) across retries instead.

    Returns ``(engine, state, regrew: bool)``.
    """
    cur = slack or engine._stream_config["slack"]
    for attempt in range(max_regrows + 1):
        try:
            return (engine,
                    apply_delta(engine, state, batch, record=record),
                    attempt > 0)
        except CapacityError:
            if attempt == max_regrows:
                raise
            in_cap, n_needed = _batch_capacity_hint(engine, batch)
            engine, state = regrow_engine(engine, state, slack=cur,
                                          in_capacity=in_cap,
                                          n_cap=n_needed)
            cur = dataclasses.replace(
                cur,
                ghost_slack=max(2 * cur.ghost_slack, 4),
                eghost_slack=max(2 * cur.eghost_slack, 4))
