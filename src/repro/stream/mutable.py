"""Capacity-padded mutable graph over the static ``GraphStructure``
(DESIGN.md §3.11; paper Secs. 3.2 + 4.1).

Every engine in this repo jit-compiles against a frozen structure; real
deployments (paper Sec. 4.1 ingress, ASYMP) keep computing while edges
arrive.  ``StreamingGraph`` reconciles the two with *slot reservation per
receiver block*: each vertex owns a contiguous, pre-sized region of edge
slots for its in-edges, so

  - the receiver array is frozen at build time (slot ``i`` in vertex
    ``r``'s region always names receiver ``r``) and stays globally
    receiver-sorted — the GAS kernel's CSR block metadata is computed once;
  - an arriving edge claims the next free slot of its receiver's region:
    no shifting, no re-sort, no edge-data permutation — existing slots
    never move, so engine state patches are row writes;
  - free (slack) slots are inert **self-loops** (sender = receiver,
    reverse = themselves) with ``edge_mask == False``: they cost nothing
    through either the masked dense path or the zero-weight fused path,
    keep the structure symmetric, and never ghost across machines.

Vertex slack works the same way: the capacity structure holds ``n_cap``
vertices, of which only ``vertex_active`` are live; inactive vertices are
isolated, carry zero data and zero priority, and an ``AddVertex`` merely
activates one.

When a receiver's region (or the vertex table, or a distributed ghost
slab) fills, ``CapacityError`` fires and the caller re-partitions through
the existing atom path (``stream/ingest.py:regrow_engine``) — the paper's
elastic two-phase placement, now used for *growth* instead of restart.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.core.graph import DataGraph, GraphStructure
from repro.kernels.gas.gas import ROW_BLOCK

Pytree = Any


class CapacityError(RuntimeError):
    """Preallocated slack exhausted — the caller must ``regrow()``."""

    def __init__(self, what: str):
        super().__init__(
            f"streaming capacity exhausted ({what}); regrow() to "
            f"re-partition with fresh slack")
        self.what = what


@dataclasses.dataclass(frozen=True)
class SlackConfig:
    """How much room a freshly built ``StreamingGraph`` leaves for growth.

    ``edge_frac``/``edge_min`` size each vertex's in-edge region above its
    current in-degree; ``vertex_frac``/``vertex_min`` add inactive vertex
    slots; ``ghost_slack``/``eghost_slack`` add unmapped cache lines per
    (machine, peer) slab on the distributed engines; ``color_slack``
    reserves spare sweep phases (initially empty colors) so incremental
    color repair of delta edges (DESIGN §3.12) has palette headroom —
    an empty phase is one masked sweep of dead weight, a missing color
    is a regrow."""

    vertex_frac: float = 0.25
    vertex_min: int = 16
    edge_frac: float = 0.5
    edge_min: int = 2
    ghost_slack: int = 16
    eghost_slack: int = 16
    color_slack: int = 2


class StreamingGraph:
    """Host-side bookkeeping of the capacity layout.

    Data rows live in engine state, not here: this object only decides
    *where* a delta lands (slots, reverse links, degrees) and hands the
    engines their dynamic tables (``tables()``).
    """

    def __init__(self, n_cap: int, slot_start: np.ndarray,
                 slack: SlackConfig):
        self.n_cap = int(n_cap)
        self.slack = slack
        self.slot_start = slot_start.astype(np.int64)      # [n_cap + 1]
        e_cap = int(slot_start[-1])
        self.e_cap = e_cap
        self.vertex_active = np.zeros(n_cap, bool)
        self.fill = np.zeros(n_cap, np.int32)              # in-degree
        self.out_deg = np.zeros(n_cap, np.int32)
        self.senders = np.zeros(e_cap, np.int32)
        self.receivers = np.repeat(
            np.arange(n_cap, dtype=np.int32),
            np.diff(slot_start).astype(np.int64))
        self.edge_mask = np.zeros(e_cap, bool)
        self.rev_idx = np.arange(e_cap, dtype=np.int32)    # slack: self
        # slack slots are inert self-loops: sender = receiver
        self.senders[:] = self.receivers
        self.edge_slot: Dict[Tuple[int, int], int] = {}
        self.out_slots: Dict[int, List[int]] = {}
        self._next_vid = 0

    # -- construction --------------------------------------------------------
    @staticmethod
    def build(structure: GraphStructure,
              slack: SlackConfig = SlackConfig(),
              *,
              n_cap: Optional[int] = None,
              in_capacity: Optional[np.ndarray] = None,
              ) -> Tuple["StreamingGraph", np.ndarray]:
        """Builds the capacity layout around an existing structure.

        Returns ``(sgraph, init_perm)`` where ``init_perm[i]`` is the
        capacity slot of the structure's (receiver-sorted) edge ``i`` —
        use it to place existing edge data (``pad_edge_data``).

        ``in_capacity`` overrides the per-vertex in-edge region sizes
        (journal replay into an initially empty layout: the ingress side
        knows the degrees its atoms will deliver)."""
        n = structure.n_vertices
        if n_cap is None:
            n_cap = n + max(slack.vertex_min,
                            int(np.ceil(slack.vertex_frac * n)))
        n_cap = max(int(n_cap), n)
        indeg = np.zeros(n_cap, np.int64)
        indeg[:n] = structure.in_degree
        if in_capacity is not None:
            hint = np.zeros(n_cap, np.int64)
            k = min(len(in_capacity), n_cap)
            hint[:k] = np.asarray(in_capacity[:k], np.int64)
            indeg = np.maximum(indeg, hint)
        cap = indeg + np.maximum(
            slack.edge_min, np.ceil(slack.edge_frac * indeg).astype(np.int64))
        slot_start = np.concatenate([[0], np.cumsum(cap)])
        sg = StreamingGraph(n_cap, slot_start, slack)

        sg.vertex_active[:n] = True
        sg._next_vid = n
        # lay existing edges into their receivers' regions, preserving the
        # receiver-sorted order (edges of r are contiguous in the source)
        offs = structure.receiver_offsets().astype(np.int64)
        E = structure.n_edges
        init_perm = np.zeros(E, np.int64)
        if E:
            pos = np.arange(E, dtype=np.int64) - offs[structure.receivers]
            init_perm = sg.slot_start[structure.receivers] + pos
            sg.senders[init_perm] = structure.senders
            sg.edge_mask[init_perm] = True
            sg.fill[:n] = structure.in_degree
            sg.out_deg[:n] = structure.out_degree
            rev = structure.reverse_perm
            has = rev >= 0
            sg.rev_idx[init_perm[has]] = init_perm[rev[has]]
            sg.rev_idx[init_perm[~has]] = -1
            sg.edge_slot = dict(zip(
                zip(structure.senders.tolist(), structure.receivers.tolist()),
                init_perm.tolist()))
            # out_slots grouped by sender at C speed (regrow is a serving-
            # path operation; a per-edge Python loop is too slow there)
            order = np.argsort(structure.senders, kind="stable")
            uniq, starts = np.unique(structure.senders[order],
                                     return_index=True)
            slots_by_sender = np.split(init_perm[order], starts[1:])
            sg.out_slots = {int(s): list(map(int, sl))
                            for s, sl in zip(uniq, slots_by_sender)}
        return sg, init_perm

    # -- mutation ------------------------------------------------------------
    @property
    def n_real(self) -> int:
        return int(self.vertex_active.sum())

    @property
    def n_real_edges(self) -> int:
        return int(self.edge_mask.sum())

    def add_vertex(self, vid: Optional[int] = None) -> int:
        """Activates a vertex slot.  Sequential ids by default; explicit
        ``vid`` supports atom-journal replay (any inactive id < n_cap)."""
        if vid is None:
            while self._next_vid < self.n_cap and \
                    self.vertex_active[self._next_vid]:
                self._next_vid += 1
            vid = self._next_vid
        vid = int(vid)
        if vid >= self.n_cap:
            raise CapacityError(f"vertex slots (vid {vid} >= {self.n_cap})")
        if self.vertex_active[vid]:
            raise ValueError(f"vertex {vid} already active")
        self.vertex_active[vid] = True
        return vid

    def add_edge(self, src: int, dst: int) -> int:
        """Claims the next free slot of ``dst``'s region.  Returns the
        capacity slot; links the reverse edge when its twin is present."""
        src, dst = int(src), int(dst)
        if not (0 <= src < self.n_cap and 0 <= dst < self.n_cap):
            raise ValueError(f"edge ({src}, {dst}) outside capacity "
                             f"{self.n_cap}")
        if (src, dst) in self.edge_slot:
            raise ValueError(f"edge ({src}, {dst}) already present")
        slot = int(self.slot_start[dst]) + int(self.fill[dst])
        if slot >= int(self.slot_start[dst + 1]):
            raise CapacityError(f"in-edge region of vertex {dst}")
        self.senders[slot] = src
        self.edge_mask[slot] = True
        self.fill[dst] += 1
        self.out_deg[src] += 1
        self.edge_slot[(src, dst)] = slot
        self.out_slots.setdefault(src, []).append(slot)
        twin = self.edge_slot.get((dst, src))
        if twin is not None:  # a real self-loop is its own reverse
            self.rev_idx[slot] = twin
            self.rev_idx[twin] = slot
        else:
            self.rev_idx[slot] = -1
        return slot

    def del_edge(self, src: int, dst: int) -> Tuple[int, Optional[int]]:
        """Removes edge ``src -> dst``, keeping ``dst``'s region contiguous
        by swapping the region's last occupied slot into the hole.

        Returns ``(slot, moved_from)``: the freed slot and, when a swap
        happened, the slot the region's tail edge vacated (its data row
        must move ``moved_from -> slot``; ``None`` when the deleted edge
        *was* the tail).  The vacated slot reverts to the inert self-loop
        of the slack layout.
        """
        src, dst = int(src), int(dst)
        slot = self.slot_of(src, dst)
        twin = int(self.rev_idx[slot])
        # unhook the deleted edge
        del self.edge_slot[(src, dst)]
        outs = self.out_slots[src]
        outs.remove(slot)
        if not outs:
            del self.out_slots[src]
        self.fill[dst] -= 1
        self.out_deg[src] -= 1
        if 0 <= twin != slot:   # the twin loses its reverse link
            self.rev_idx[twin] = -1
        tail = int(self.slot_start[dst]) + int(self.fill[dst])
        moved_from: Optional[int] = None
        if tail != slot:
            # swap-with-last-occupied: the tail edge (msrc -> dst) moves
            # into the hole; its reverse links follow it
            msrc = int(self.senders[tail])
            self.senders[slot] = msrc
            self.edge_mask[slot] = True
            self.edge_slot[(msrc, dst)] = slot
            mouts = self.out_slots[msrc]
            mouts[mouts.index(tail)] = slot
            mtwin = int(self.rev_idx[tail])
            if mtwin == tail:        # a real self-loop is its own reverse
                self.rev_idx[slot] = slot
            elif mtwin >= 0:
                self.rev_idx[slot] = mtwin
                self.rev_idx[mtwin] = slot
            else:
                self.rev_idx[slot] = -1
            moved_from = tail
        vacated = tail if moved_from is not None else slot
        self.senders[vacated] = dst            # inert self-loop again
        self.edge_mask[vacated] = False
        self.rev_idx[vacated] = vacated
        return slot, moved_from

    def del_vertex(self, vid: int) -> int:
        """Deactivates ``vid``.  All incident edges must already be gone
        (``stream/ingest.py`` cascades ``DelEdge`` first); the slot becomes
        spare capacity and its id is reusable by a later ``AddVertex``."""
        vid = int(vid)
        if not (0 <= vid < self.n_cap) or not self.vertex_active[vid]:
            raise ValueError(f"vertex {vid} not active")
        if int(self.fill[vid]) or int(self.out_deg[vid]):
            raise ValueError(
                f"vertex {vid} still has incident edges "
                f"(in={int(self.fill[vid])}, out={int(self.out_deg[vid])})")
        self.vertex_active[vid] = False
        self._next_vid = min(self._next_vid, vid)
        return vid

    def slot_of(self, src: int, dst: int) -> int:
        try:
            return self.edge_slot[(int(src), int(dst))]
        except KeyError:
            raise KeyError(f"no edge ({src}, {dst})") from None

    def in_slots(self, dst: int) -> np.ndarray:
        """Occupied slots of ``dst``'s region (its real in-edges)."""
        return np.arange(self.slot_start[dst],
                         self.slot_start[dst] + self.fill[dst])

    # -- engine-facing views -------------------------------------------------
    def capacity_structure(self) -> GraphStructure:
        """A frozen snapshot of the capacity layout as a ``GraphStructure``
        (receiver-sorted by construction; slack slots are self-loops with
        themselves as reverse, keeping symmetry checks honest).  Degrees
        are the *real* degrees — engines read the dynamic tables for the
        live values, this snapshot seeds layout building only."""
        ind = np.zeros(self.n_cap, np.int32)
        ind[:len(self.fill)] = self.fill
        return GraphStructure(
            n_vertices=self.n_cap,
            senders=self.senders.copy(),
            receivers=self.receivers,            # frozen by construction
            reverse_perm=self.rev_idx.copy(),
            in_degree=ind,
            out_degree=self.out_deg.astype(np.int32).copy())

    def tables(self) -> Dict[str, np.ndarray]:
        """The dynamic structure tables of the local streaming engine
        (``core/engine_base.py``)."""
        nblk = max(-(-self.n_cap // ROW_BLOCK), 1)
        real_recv = self.receivers[self.edge_mask]
        block_counts = np.bincount(
            real_recv // ROW_BLOCK, minlength=nblk).astype(np.int32)
        return {
            "senders": self.senders.astype(np.int32).copy(),
            "receivers": self.receivers,
            "edge_mask": self.edge_mask.copy(),
            "rev_idx": self.rev_idx.astype(np.int32).copy(),
            "in_deg": self.fill.astype(np.int32).copy(),
            "out_deg": self.out_deg.astype(np.int32).copy(),
            "block_counts": block_counts,
        }

    # -- compaction (the regrow read-side) -----------------------------------
    def compact(self, vertex_data: Pytree, edge_data: Pytree
                ) -> DataGraph:
        """Strips the padding: the current *real* graph as a fresh
        receiver-sorted ``DataGraph`` (scratch-engine comparisons, regrow)."""
        n = int(np.max(np.nonzero(self.vertex_active)[0])) + 1 \
            if self.vertex_active.any() else 0
        slots = np.nonzero(self.edge_mask)[0]
        st, perm = GraphStructure.from_edges(
            self.senders[slots], self.receivers[slots], max(n, 1))

        def vtake(x):
            return np.asarray(x)[:max(n, 1)]

        def etake(x):
            return np.asarray(x)[slots][perm]

        return DataGraph(
            vertex_data=jax.tree.map(vtake, vertex_data),
            edge_data=jax.tree.map(etake, edge_data),
            structure=st)


def pad_vertex_data(vertex_data: Pytree, n_cap: int) -> Pytree:
    """Zero-pads each vertex leaf to the capacity row count (inactive
    vertices carry zeros, so linear sync folds stay exact)."""

    def one(x):
        x = np.asarray(x)
        out = np.zeros((n_cap,) + x.shape[1:], x.dtype)
        out[: x.shape[0]] = x
        return out

    return jax.tree.map(one, vertex_data)


def pad_edge_data(edge_data: Pytree, sgraph: StreamingGraph,
                  init_perm: np.ndarray) -> Pytree:
    """Scatters (receiver-sorted) edge rows into their capacity slots."""

    def one(x):
        x = np.asarray(x)
        out = np.zeros((sgraph.e_cap,) + x.shape[1:], x.dtype)
        out[init_perm] = x
        return out

    return jax.tree.map(one, edge_data)
