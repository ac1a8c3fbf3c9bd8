"""Sharded vertex-program engines under ``shard_map`` (DESIGN §3.7).

Where ``core/distributed.py`` *models* the paper's cluster (real values,
simulated time), this module *is* the cluster on a device mesh: vertices are
placed with the two-phase atom partitioner (``core/partition.py``), each
mesh slice along the ``data`` axis plays one machine, and ghosts — boundary
vertices a machine reads but does not own — live in a versioned remote
cache refreshed by explicit ``all_to_all`` exchanges.

``ShardEngineBase`` owns everything schedule-independent: the partition
layout, the versioned ghost exchange, and the **phase update** (local
gather⊕combine → apply → exchange → reschedule → adjacent-edge writes) for
one caller-supplied active mask.  The engines are scheduler choices over
it, mirroring the shared-memory layer (core/scheduler.py, DESIGN §3.8):

  ``DistributedEngine``         chromatic sweep (Sec. 4.2.1): one step
                                sweeps the colors; same-color vertices are
                                non-adjacent, so the fixed point matches
                                ``ChromaticEngine`` to float tolerance.
  ``dist/locking.py``           the pipelined locking engine (Sec. 4.2.2):
                                per-machine top-p selection with ghost-rank
                                arbitration.

Versioned ghost exchange (Sec. 5.1: "each machine receives each modified
vertex data at most once"): the send tables enumerate (owner row, caching
machine) pairs once; at each exchange a row ships only if its vertex
updated this phase.  Unchanged ghosts keep their cached value; per-machine
counters account the rows actually shipped, which is the quantity the
paper's Fig. 6(c) network curves measure.

Adjacent-edge writes (LBP messages) ride the same machinery: an edge lives
with its receiver's machine, its reverse edge may live elsewhere, so edge
data has its own ghost cache + send tables, refreshed with the same
changed-only discipline (an edge changes exactly when its source vertex
updates).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.coloring import coloring_for
from repro.core.engine_base import edge_residual_bump
from repro.core.graph import DataGraph, segment_combine
from repro.core.scheduler import sweep_mask
from repro.core.snapshot import SnapshotState, stitch_rows
from repro.dist.snapshot import (assemble_snapshot as _assemble_snapshot,
                                 init_dist_snapshot, make_marker_phase,
                                 mark_stale)
from repro.core.partition import (atom_meta_index, overpartition,
                                  place_atoms)
from repro.core.sync_op import SyncOp, run_syncs
from repro.core.update import (EdgeCtx, VertexProgram, fused_edge_weight,
                               fused_gather_leaves, masked_update,
                               supports_fused_gather)
from repro.dist.wire import (WireConfig, decode_payload, encode_payload,
                             encode_rows, payload_row_nbytes,
                             tree_add_where, tree_rows_maxabs, tree_sub)
from repro.kernels.gas.gas import EDGE_BLOCK, ROW_BLOCK, csr_steps
from repro.kernels.gas.ops import (EdgeSet, active_row_blocks, color_runs,
                                   gather_combine, scatter_reschedule,
                                   size_class, split_by_color)
from repro.obs.timeline import span

Pytree = Any


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DistState:
    """Sharded engine state: leading dims are ``S * per_machine`` blocks,
    machine m owns block m (sharded over the mesh ``data`` axis)."""

    vown: Pytree            # [S*n_loc, ...] owned vertex data (padded)
    vghost: Pytree          # [S*(S*B), ...] ghost vertex cache
    edata: Pytree           # [S*e_loc, ...] owned edge data
    eghost: Pytree          # [S*(S*EB), ...] ghost edge cache ({} if unused)
    prio: jnp.ndarray       # [S*n_loc] scheduler T (pad rows 0)
    update_count: jnp.ndarray  # [S*n_loc] i32
    traffic_v: jnp.ndarray  # [S] i32 — ghost vertex rows actually shipped
    traffic_e: jnp.ndarray  # [S] i32 — ghost edge rows actually shipped
    traffic_r: jnp.ndarray  # [S] i32 — arbitration rank rows shipped
    traffic_bytes_v: jnp.ndarray  # [S] i32 — payload bytes of those rows
    traffic_bytes_e: jnp.ndarray  # [S] i32
    traffic_bytes_r: jnp.ndarray  # [S] i32
    step_index: jnp.ndarray  # scalar i32
    snap: Pytree = None     # DistSnapshotState while a snapshot is live
    globals_: Pytree = ()   # sync-op outputs (replicated), DESIGN §3.9
    beats: Pytree = None    # [S] i32 heartbeat counters (DESIGN §3.13)
    wire: Pytree = None     # quantized-wire mirrors (DESIGN §3.14) or None

    def replace(self, **kw) -> "DistState":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass
class _Layout:
    """Host-side partition layout: static index tables for the device step."""

    n_machines: int
    n_loc: int          # owned vertex rows per machine (padded)
    budget: int         # ghost vertex rows per (machine, peer) pair
    e_loc: int          # edge rows per machine (padded)
    e_budget: int       # ghost edge rows per (machine, peer) pair
    has_rev: bool       # reverse-edge ghost machinery built?
    machine_of: np.ndarray   # [N]
    own_gid: np.ndarray      # [S*n_loc] global vertex id or -1
    row_of: np.ndarray       # [N] global row of each vertex
    erow_gid: np.ndarray     # [S*e_loc] global edge id or -1
    erow_of: np.ndarray      # [E] machine-major global row of each edge
                             #     (local row = erow_of[e] - machine*e_loc)
    ghost_gid: np.ndarray    # [S*(S*B)] global vertex id cached here or -1
    eghost_gid: np.ndarray   # [S*(S*EB)] global edge id cached here or -1
    tables: Dict[str, np.ndarray]   # device tables (see _build_layout)


def _slab_tables(dest: np.ndarray, owner: np.ndarray, gid: np.ndarray,
                 S: int, row_in_owner: np.ndarray, domain: int):
    """Ghost slab assignment, vectorized.

    Each unique (dest machine, owner machine, gid) triple gets a slot
    ``b < budget`` in dest's per-owner slab.  Returns
    ``(budget, slab_gid [S*S*budget], send_idx, send_mask, ukey, bslot)``
    where (ukey, bslot) label arbitrary (dest, owner, gid) queries via
    searchsorted — used to localize edge endpoints.
    """
    if dest.size == 0:
        z = np.zeros(S * S, np.int64)
        return (1, np.full(S * S, -1, np.int64), z, np.zeros(S * S, bool),
                np.zeros(0, np.int64), np.zeros(0, np.int64))
    key = (dest.astype(np.int64) * S + owner) * domain + gid
    ukey = np.unique(key)
    pair = ukey // domain                    # dest * S + owner, sorted
    ugid = ukey % domain
    starts = np.searchsorted(pair, np.arange(S * S))
    bslot = np.arange(ukey.size) - starts[pair]
    budget = max(int(bslot.max()) + 1, 1)
    d, o = pair // S, pair % S
    slab_gid = np.full(S * S * budget, -1, np.int64)
    slab_gid[d * (S * budget) + o * budget + bslot] = ugid
    send_idx = np.zeros(S * S * budget, np.int64)
    send_mask = np.zeros(S * S * budget, bool)
    # owner o ships its local row of gid to machine d's slab slot
    send_idx[o * (S * budget) + d * budget + bslot] = row_in_owner[ugid]
    send_mask[o * (S * budget) + d * budget + bslot] = True
    return budget, slab_gid, send_idx, send_mask, ukey, bslot


def _slab_lookup(ukey: np.ndarray, bslot: np.ndarray, dest, owner, gid,
                 S: int, domain: int) -> np.ndarray:
    """Slot of each (dest, owner, gid) query in its slab (must exist)."""
    key = (dest.astype(np.int64) * S + owner) * domain + gid
    return bslot[np.searchsorted(ukey, key)]


def _build_layout(graph: DataGraph, machine_of: np.ndarray,
                  n_machines: int, build_rev: bool) -> _Layout:
    st = graph.structure
    N, S = st.n_vertices, int(n_machines)

    # --- owned vertex rows: [machine-major, id-minor], padded to n_loc ----
    counts = np.bincount(machine_of, minlength=S)
    n_loc = max(int(counts.max()), 1)
    order = np.argsort(machine_of, kind="stable")
    slot = np.zeros(N, np.int64)
    offs = np.concatenate([[0], np.cumsum(counts)])
    slot[order] = np.arange(N) - offs[machine_of[order]]
    row_of = machine_of.astype(np.int64) * n_loc + slot
    own_gid = np.full(S * n_loc, -1, np.int64)
    own_gid[row_of] = np.arange(N)

    # --- owned edge rows (an edge lives with its receiver's machine) ------
    E = st.n_edges
    e_machine = machine_of[st.receivers]
    ecounts = np.bincount(e_machine, minlength=S)
    e_loc = max(int(ecounts.max()), 1)
    eorder = np.argsort(e_machine, kind="stable")
    epos = np.zeros(E, np.int64)
    eoffs = np.concatenate([[0], np.cumsum(ecounts)])
    epos[eorder] = np.arange(E) - eoffs[e_machine[eorder]]
    erow_of = e_machine.astype(np.int64) * e_loc + epos
    erow_gid = np.full(S * e_loc, -1, np.int64)
    erow_gid[erow_of] = np.arange(E)

    # --- ghost vertex slabs: machine m ghosts v iff some edge it owns has
    # remote sender v; slot assignment is a vectorized group-rank ----------
    s_machine = machine_of[st.senders]
    cut = s_machine != e_machine
    budget, ghost_gid, send_idx, send_mask, vkey, vslot = _slab_tables(
        e_machine[cut], s_machine[cut], st.senders[cut], S, slot, max(N, 1))

    senders_local = np.zeros(S * e_loc, np.int64)
    senders_local[erow_of[~cut]] = slot[st.senders[~cut]]
    if cut.any():
        gslot = _slab_lookup(vkey, vslot, e_machine[cut], s_machine[cut],
                             st.senders[cut], S, max(N, 1))
        senders_local[erow_of[cut]] = \
            n_loc + s_machine[cut].astype(np.int64) * budget + gslot
    receivers_local = np.zeros(S * e_loc, np.int64)
    receivers_local[erow_of] = slot[st.receivers]
    edge_mask = np.zeros(S * e_loc, bool)
    edge_mask[erow_of] = True
    src_deg_e = np.zeros(S * e_loc, np.int32)
    src_deg_e[erow_of] = st.out_degree[st.senders]
    dst_deg_e = np.zeros(S * e_loc, np.int32)
    dst_deg_e[erow_of] = st.in_degree[st.receivers]

    # --- ghost edge slabs (reverse-edge reads: ctx.rev_edata) -------------
    e_budget = 1
    rev_local = np.full(S * e_loc, -1, np.int64)
    eghost_gid = np.full(S * S, -1, np.int64)
    esend_idx = np.zeros(S * S, np.int64)
    esend_mask = np.zeros(S * S, bool)
    if build_rev:
        has = st.reverse_perm >= 0
        e_ids = np.nonzero(has)[0]
        re = st.reverse_perm[e_ids].astype(np.int64)
        m, p = e_machine[e_ids], e_machine[re]
        ecut = m != p
        e_budget, eghost_gid, esend_idx, esend_mask, ekey, eslot = \
            _slab_tables(m[ecut], p[ecut], re[ecut], S, epos, max(E, 1))
        rev_local[erow_of[e_ids[~ecut]]] = epos[re[~ecut]]
        if ecut.any():
            gslot = _slab_lookup(ekey, eslot, m[ecut], p[ecut], re[ecut],
                                 S, max(E, 1))
            rev_local[erow_of[e_ids[ecut]]] = \
                e_loc + p[ecut].astype(np.int64) * e_budget + gslot

    tables = {
        "senders_local": senders_local.astype(np.int32),
        "receivers_local": receivers_local.astype(np.int32),
        "edge_mask": edge_mask,
        "src_deg_e": src_deg_e,
        "dst_deg_e": dst_deg_e,
        "own_mask": (own_gid >= 0),
        "send_idx": send_idx.astype(np.int32),
        "send_mask": send_mask,
        "rev_local": rev_local.astype(np.int32),
        "esend_idx": esend_idx.astype(np.int32),
        "esend_mask": esend_mask,
    }
    return _Layout(
        n_machines=S, n_loc=n_loc, budget=budget, e_loc=e_loc,
        e_budget=e_budget, has_rev=build_rev, machine_of=machine_of,
        own_gid=own_gid, row_of=row_of, erow_gid=erow_gid, erow_of=erow_of,
        ghost_gid=ghost_gid, eghost_gid=eghost_gid, tables=tables)


def _pad_slab(arr: np.ndarray, S: int, budget: int, new_budget: int, fill):
    """Re-lays a flattened [S*S*budget] slab array to a larger per-pair
    budget, filling the new slots with ``fill`` (works for both slab
    orientations — the last axis is the per-pair slot either way)."""
    a = arr.reshape(S * S, budget)
    out = np.full((S * S, new_budget), fill, a.dtype)
    out[:, :budget] = a
    return out.reshape(-1)


def _expand_slabs(lay: _Layout, extra_b: int, extra_eb: int) -> None:
    """Streaming slack (DESIGN §3.11): grows every (dest machine, owner
    machine) ghost slab by ``extra_b`` vertex / ``extra_eb`` edge slots so a
    delta edge that spans machines can claim a cache line without a layout
    rebuild.  New slots start unmapped (gid -1, send_mask False)."""
    S, B = lay.n_machines, lay.budget
    if extra_b > 0:
        nb = B + extra_b
        lay.ghost_gid = _pad_slab(lay.ghost_gid, S, B, nb, -1)
        lay.tables["send_idx"] = _pad_slab(
            lay.tables["send_idx"], S, B, nb, 0)
        lay.tables["send_mask"] = _pad_slab(
            lay.tables["send_mask"], S, B, nb, False)
        # senders_local ghost references use the per-owner slab stride:
        # local index n_loc + o*B + b becomes n_loc + o*nb + b
        sl = lay.tables["senders_local"].astype(np.int64)
        is_ghost = sl >= lay.n_loc
        off = sl - lay.n_loc
        lay.tables["senders_local"] = np.where(
            is_ghost, lay.n_loc + (off // B) * nb + off % B,
            sl).astype(np.int32)
        # the fused kernel's sender table holds the same local indices
        # (present only on live expansion — at construction the GAS
        # metadata is built after the slack expansion)
        if "gas_send" in lay.tables:
            gs = lay.tables["gas_send"].astype(np.int64)
            is_ghost = gs >= lay.n_loc
            off = gs - lay.n_loc
            lay.tables["gas_send"] = np.where(
                is_ghost, lay.n_loc + (off // B) * nb + off % B,
                gs).astype(np.int32)
        lay.budget = nb
    EB = lay.e_budget
    if extra_eb > 0 and lay.has_rev:
        neb = EB + extra_eb
        lay.eghost_gid = _pad_slab(lay.eghost_gid, S, EB, neb, -1)
        lay.tables["esend_idx"] = _pad_slab(
            lay.tables["esend_idx"], S, EB, neb, 0)
        lay.tables["esend_mask"] = _pad_slab(
            lay.tables["esend_mask"], S, EB, neb, False)
        # rev_local entries pointing into eghost slabs shift with the
        # per-owner stride: slot e_loc + p*EB + b becomes e_loc + p*neb + b
        rl = lay.tables["rev_local"].astype(np.int64)
        is_ghost = rl >= lay.e_loc
        off = rl - lay.e_loc
        rl2 = np.where(is_ghost,
                       lay.e_loc + (off // EB) * neb + off % EB, rl)
        lay.tables["rev_local"] = rl2.astype(np.int32)
        lay.e_budget = neb


def _rows_where(m: jnp.ndarray, new: jnp.ndarray,
                old: jnp.ndarray) -> jnp.ndarray:
    """Row-masked replace with a cast to the stored dtype."""
    mm = m.reshape((-1,) + (1,) * (old.ndim - 1))
    return jnp.where(mm, new.astype(old.dtype), old)


def _take_rows(tree: Pytree, idx: np.ndarray) -> Pytree:
    """Gathers global rows by id (pad ids < 0 -> zero rows)."""

    def one(x):
        x = np.asarray(x)
        out = np.zeros((idx.size,) + x.shape[1:], x.dtype)
        ok = idx >= 0
        out[ok] = x[idx[ok]]
        return out

    return jax.tree.map(one, tree)


class ShardEngineBase:
    """Schedule-independent half of a sharded engine: partition layout,
    versioned ghost exchange, and the per-phase local update.

    One mesh slice along ``axis`` = one paper machine.  Subclasses build
    ``_make_step`` from ``_make_phase_helpers`` — each phase executes one
    caller-chosen active mask — and finish ``__init__`` with
    ``_finalize()``.

    Sync ops (paper Sec. 3.5, DESIGN §3.9) evaluate at the shard_map step
    barrier: each machine folds ``map_fn`` over its owned rows, the partial
    sums meet in a cross-machine ``psum``, and ``finalize`` runs replicated
    — every machine reads identical globals next step, the paper's
    atomic-consistency readback.  Inconsistent ops see the previous
    barrier's data (a background sync racing with updates), exactly as the
    host-loop engines do.

    Streaming mode (DESIGN §3.11, driven by ``stream/ingest.py``): ``graph``
    is a capacity-padded data graph, ``stream_real_edges`` marks which
    capacity slots currently hold real edges (slack slots are inert
    receiver-owned self-loops), and ``ghost_slack``/``eghost_slack`` reserve
    unmapped cache lines per machine pair so delta edges that span machines
    splice in with table patches only — the jitted step never retraces
    until ``regrow()``.
    """

    def __init__(
        self,
        program: VertexProgram,
        graph: DataGraph,
        mesh,
        *,
        axis: str = "data",
        k_atoms: Optional[int] = None,
        method: str = "hash",
        tolerance: float = 1e-3,
        seed: int = 0,
        sync_ops: Sequence[SyncOp] = (),
        use_fused: Optional[bool] = None,
        gas_interpret: Optional[bool] = None,
        wire: Optional[WireConfig] = None,
        overlap: bool = False,
        stream_real_edges: Optional[np.ndarray] = None,
        ghost_slack: int = 0,
        eghost_slack: int = 0,
        atom_of: Optional[np.ndarray] = None,
        atom_placement: Optional[np.ndarray] = None,
        machine_of: Optional[np.ndarray] = None,
        obs=None,
    ):
        self.program = program
        self.graph = graph
        self.mesh = mesh
        self.axis = axis
        self.tolerance = float(tolerance)
        self.sync_ops = tuple(sync_ops)
        st = graph.structure

        if axis not in mesh.shape:
            raise ValueError(
                f"mesh has no {axis!r} axis (axes: {tuple(mesh.shape)}); "
                f"pass axis=<name> for the machine dimension")
        S = int(mesh.shape[axis])
        k_atoms = k_atoms or max(4 * S, 32)
        # two-phase placement, with every intermediate overridable so
        # migration (dist/migrate.py) can rebuild on an explicit placement
        if machine_of is None:
            if atom_of is None:
                atom_of = overpartition(st, k_atoms, method=method,
                                        seed=seed)
            atom_of = np.asarray(atom_of, np.int32)
            if atom_placement is None:
                atom_placement = place_atoms(atom_meta_index(st, atom_of), S)
            atom_placement = np.asarray(atom_placement, np.int32)
            machine_of = atom_placement[atom_of]
        else:
            machine_of = np.asarray(machine_of, np.int32)
            if atom_of is not None:
                atom_of = np.asarray(atom_of, np.int32)
            if atom_placement is not None:
                atom_placement = np.asarray(atom_placement, np.int32)
        self.atom_of = atom_of
        self.atom_placement = atom_placement
        # reverse-edge ghost machinery only when the program reads
        # ctx.rev_edata (declared, defaulting to has_edge_out)
        use_rev = (program.reads_rev_edata
                   if program.reads_rev_edata is not None
                   else program.has_edge_out)
        # place_atoms may leave a machine empty on tiny graphs; the layout
        # pads every machine to the same shapes, so that is fine.
        self.layout = _build_layout(
            graph, np.asarray(machine_of, np.int32), S, use_rev)
        self.streaming = stream_real_edges is not None
        if self.streaming or ghost_slack or eghost_slack:
            _expand_slabs(self.layout, int(ghost_slack), int(eghost_slack))
        # membership stall flags (DESIGN §3.13): a stalled machine executes
        # no updates, ships nothing, and stops beating — the watchdog's
        # silent-failure model (dist/faults.py sets these).
        self.layout.tables["stall"] = np.zeros(S, bool)
        self._trace_count = 0  # bumped at trace time; delta tests assert 0
        # Telemetry (DESIGN §3.15): host-side only — never read while
        # building ``_make_step``, so the step jaxpr is byte-identical
        # with obs on/off (tests/test_obs.py asserts the strings).
        if obs is None:
            from repro.obs.config import ObsConfig
            obs = ObsConfig()
        self.obs = obs

        # Quantized wire (DESIGN §3.14): codec + top-k residual shipping.
        # Streaming engines are fully supported: stream/ingest.py patches
        # the error-feedback mirrors in lockstep with every ghost splice.
        self.wire = wire if wire is not None else WireConfig()
        # Double-buffered phase overlap (DESIGN §3.14): defer each phase's
        # encoded ship one phase, so the all_to_all of color c-1's rows is
        # issued before — and carries no data dependency into — color c's
        # local gather⊕combine.  Merges are delayed one phase, never
        # dropped; the last phase of a step always flushes synchronously.
        self.overlap = bool(overlap)
        # has-cacher masks: rows some remote machine caches (the only rows
        # dirtiness can ever drain for — interior rows never ship).  Derived
        # from the final (post-slack) send tables: entry o*(S*B)+d*B+b ships
        # owner o's local row send_idx[entry].
        lay = self.layout
        vhas = np.zeros(S * lay.n_loc, bool)
        ent = np.nonzero(lay.tables["send_mask"])[0]
        vhas[(ent // (S * lay.budget)) * lay.n_loc
             + lay.tables["send_idx"][ent]] = True
        lay.tables["vhas_cacher"] = vhas
        ehas = np.zeros(S * lay.e_loc, bool)
        if lay.has_rev:
            ent = np.nonzero(lay.tables["esend_mask"])[0]
            ehas[(ent // (S * lay.e_budget)) * lay.e_loc
                 + lay.tables["esend_idx"][ent]] = True
        lay.tables["ehas_cacher"] = ehas

        # Fused GAS local compute (DESIGN.md §3.5): per-machine kernel grid
        # schedule over the *local* edge rows, padded to one shared length.
        # Within a machine the real edge rows keep the global receiver-sorted
        # order and local receiver ids are monotone in global ids, so the
        # local receiver array is sorted; pad rows route past every row
        # block.  Same knobs as the
        # shared-memory engines: use_fused=False forces the seed dense
        # shard_map body, gas_interpret=True runs the kernel body on CPU.
        fusable = supports_fused_gather(program)
        self._use_fused = fusable if use_fused is None \
            else bool(use_fused) and fusable
        self._gas_interpret = gas_interpret
        # per-color local edge sets of the sweep engine (_build_color_sets)
        self._color_sets = self._color_groups = None
        if self._use_fused:
            self._gas_leaves, self._gas_treedef = fused_gather_leaves(program)
            lay = self.layout
            e_loc, n_loc = lay.e_loc, lay.n_loc
            e_pad = max(-(-e_loc // EDGE_BLOCK), 1) * EDGE_BLOCK
            rl = lay.tables["receivers_local"].reshape(S, e_loc)
            em = lay.tables["edge_mask"].reshape(S, e_loc)
            sl = lay.tables["senders_local"].reshape(S, e_loc)
            pad_r = np.int32(n_loc + ROW_BLOCK)
            rk = np.pad(np.where(em, rl, pad_r).astype(np.int32),
                        ((0, 0), (0, e_pad - e_loc)), constant_values=pad_r)
            sk = np.pad(np.where(em, sl, 0).astype(np.int32),
                        ((0, 0), (0, e_pad - e_loc)))
            for m in range(S):
                assert (np.diff(rk[m]) >= 0).all(), \
                    "local receivers must be sorted for the GAS kernel"
            n_steps = max(csr_steps(rk[m], n_loc)[0].size for m in range(S))
            steps = [csr_steps(rk[m], n_loc, n_steps) for m in range(S)]
            lay.tables["gas_send"] = sk.reshape(-1)
            lay.tables["gas_recv"] = rk.reshape(-1)
            lay.tables["gas_step_rb"] = np.concatenate([a for a, _ in steps])
            lay.tables["gas_step_eb"] = np.concatenate([b for _, b in steps])

        if self.streaming:
            # The GAS metadata above was built over the *allocated* capacity
            # slots (slack included — their reserved receivers pin the
            # static block ranges); the live edge_mask is the real-edge
            # mask, patched by apply_delta as slots fill.
            real = np.asarray(stream_real_edges, bool)
            if real.shape[0] != st.n_edges:
                raise ValueError("stream_real_edges must be [n_edge_slots]")
            em_rows = np.zeros(S * self.layout.e_loc, bool)
            em_rows[self.layout.erow_of[np.nonzero(real)[0]]] = True
            self.layout.tables["edge_mask"] = em_rows

        self._shard = NamedSharding(mesh, P(axis))
        self._rep = NamedSharding(mesh, P())

    def _finalize(self) -> None:
        """Device-put the (possibly subclass-extended) tables and jit the
        step.  Subclasses call this at the end of ``__init__``."""
        self._tables = {
            k: jax.device_put(jnp.asarray(v), self._shard)
            for k, v in self.layout.tables.items()}
        self._jit_step = jax.jit(self._make_step())

    def refresh_tables(self, keys: Optional[Sequence[str]] = None) -> None:
        """Re-uploads (patched) host tables to the device — the streaming
        delta path (stream/ingest.py): values change, shapes never do, so
        the jitted step's cache entry keeps hitting."""
        for k in (keys if keys is not None else self.layout.tables):
            self._tables[k] = jax.device_put(
                jnp.asarray(self.layout.tables[k]), self._shard)

    # -- live migration hooks (dist/migrate.py; DESIGN §3.13) -----------------
    def _clone_kwargs(self) -> dict:
        """Constructor kwargs that reproduce this engine's configuration on
        a new mesh/placement; subclasses extend with their own knobs."""
        return dict(tolerance=self.tolerance, sync_ops=self.sync_ops,
                    use_fused=self._use_fused,
                    gas_interpret=self._gas_interpret, wire=self.wire,
                    overlap=self.overlap, obs=self.obs)

    def clone_for_placement(self, graph: DataGraph, mesh,
                            machine_of: np.ndarray, *,
                            atom_of: Optional[np.ndarray] = None,
                            atom_placement: Optional[np.ndarray] = None):
        """A new engine of the same type and configuration over an explicit
        vertex→machine placement: the live-migration rebuild.  Same
        program, new layout tables, one jit retrace — survivor state is
        carried by the caller via ``init(initial_prio=...)``."""
        return type(self)(self.program, graph, mesh, axis=self.axis,
                          machine_of=np.asarray(machine_of, np.int32),
                          atom_of=atom_of, atom_placement=atom_placement,
                          **self._clone_kwargs())

    # -- state ---------------------------------------------------------------
    def init(self, graph: Optional[DataGraph] = None,
             initial_prio: Optional[np.ndarray] = None) -> DistState:
        graph = graph or self.graph
        if graph.structure is not self.graph.structure and not (
                graph.structure.n_vertices == self.graph.structure.n_vertices
                and np.array_equal(graph.structure.senders,
                                   self.graph.structure.senders)
                and np.array_equal(graph.structure.receivers,
                                   self.graph.structure.receivers)):
            raise ValueError(
                "init() graph structure differs from the one this engine "
                "was partitioned for; build a new engine")
        lay = self.layout
        S = lay.n_machines
        vdata = jax.tree.map(np.asarray, graph.vertex_data)
        edata = jax.tree.map(np.asarray, graph.edge_data)

        vown = _take_rows(vdata, lay.own_gid)
        vghost = _take_rows(vdata, lay.ghost_gid)
        edata_l = _take_rows(edata, lay.erow_gid)
        eghost = _take_rows(edata, lay.eghost_gid) if lay.has_rev else {}

        prio_g = (np.asarray(initial_prio, np.float32)
                  if initial_prio is not None else np.asarray(
                      self.program.initial_priority(
                          graph.structure.n_vertices), np.float32))
        prio = np.zeros(S * lay.n_loc, np.float32)
        ok = lay.own_gid >= 0
        prio[ok] = prio_g[lay.own_gid[ok]]

        # delta-wire mirrors (DESIGN §3.14): vref/eref start equal to every
        # cache (both sides gathered the same initial global rows), acc
        # mirrors start at the accumulator's zero, nothing is dirty
        wire_st = None
        if self.wire.uses_delta:
            wire_st = {
                "vref": _take_rows(vdata, lay.own_gid),
                "cpend": np.zeros(S * lay.n_loc, np.float32),
                "backlog": np.zeros(S, np.int32),
            }
            if self.program.has_edge_out:
                wire_st["alast"] = self._acc_zero_rows(S * lay.n_loc)
                wire_st["aref"] = self._acc_zero_rows(S * lay.n_loc)
                wire_st["aghost"] = self._acc_zero_rows(
                    S * (S * lay.budget))
            if lay.has_rev:
                wire_st["eref"] = _take_rows(edata, lay.erow_gid)

        put = lambda t: jax.tree.map(
            lambda x: jax.device_put(jnp.asarray(x), self._shard), t)
        return DistState(
            vown=put(vown), vghost=put(vghost), edata=put(edata_l),
            eghost=put(eghost), prio=put(prio),
            update_count=put(np.zeros(S * lay.n_loc, np.int32)),
            traffic_v=put(np.zeros(S, np.int32)),
            traffic_e=put(np.zeros(S, np.int32)),
            traffic_r=put(np.zeros(S, np.int32)),
            traffic_bytes_v=put(np.zeros(S, np.int32)),
            traffic_bytes_e=put(np.zeros(S, np.int32)),
            traffic_bytes_r=put(np.zeros(S, np.int32)),
            step_index=jax.device_put(jnp.zeros((), jnp.int32), self._rep),
            snap=None,
            beats=put(np.zeros(S, np.int32)),
            wire=None if wire_st is None else put(wire_st),
            globals_=jax.tree.map(
                lambda x: jax.device_put(jnp.asarray(x), self._rep),
                run_syncs(self.sync_ops, vdata, vdata,
                          graph.structure.n_vertices)))

    def _acc_zero_rows(self, rows: int) -> Pytree:
        """f32 zero rows shaped like the per-vertex gather accumulator
        (trailing dims of ``prog.gather``'s message tree) — the shape of
        the §3.14 acc mirrors, discovered by abstract evaluation."""
        prog = self.program
        vdata = jax.tree.map(np.asarray, self.graph.vertex_data)
        edata = jax.tree.map(np.asarray, self.graph.edge_data)
        row = lambda t: jax.tree.map(
            lambda x: jax.ShapeDtypeStruct((1,) + np.asarray(x).shape[1:],
                                           np.asarray(x).dtype), t)

        def g(src, dst, ed):
            deg = jnp.zeros(1, jnp.int32)
            ctx = EdgeCtx(edata=ed, rev_edata=ed, src=src, dst=dst,
                          src_deg=deg, dst_deg=deg)
            return prog.gather(ctx)

        msgs = jax.eval_shape(g, row(vdata), row(vdata), row(edata))
        return jax.tree.map(
            lambda m: np.zeros((rows,) + m.shape[1:], np.float32), msgs)

    # -- the shared phase machinery -------------------------------------------
    def _make_phase_helpers(self):
        """Builds ``(exchange, phase_update)`` closures for a shard_map body.

        ``exchange(payload, changed, send_idx, send_mask, budget)`` is the
        versioned all_to_all: ship only rows whose vertex/edge changed;
        returns (recv payload, recv changed, rows shipped).

        ``phase_update(tb, carry, active)`` executes one phase for the given
        active mask: local gather⊕combine → apply → versioned vdata/contrib
        exchange → reschedule (losers keep their priority untouched) →
        adjacent-edge writes with their own versioned exchange.  ``carry``
        is the dict {vown, vghost, edata, eghost, prio, count, tv, te,
        snap}; with a live snapshot attached, every phase also records
        which rows now carry post-snapshot data (``mark_stale`` —
        DESIGN.md §3.10's machine-checked consistency accounting).
        """
        lay, prog = self.layout, self.program
        S, n_loc, B = lay.n_machines, lay.n_loc, lay.budget
        e_loc, EB = lay.e_loc, lay.e_budget
        use_rev = lay.has_rev
        ax = self.axis
        streaming = getattr(self, "streaming", False)
        use_fused = self._use_fused
        if use_fused:
            gas_leaves, gas_treedef = self._gas_leaves, self._gas_treedef
            gas_interpret = self._gas_interpret
        wire_cfg = self.wire
        codec = wire_cfg.codec
        top_k = wire_cfg.top_k
        use_delta = wire_cfg.uses_delta
        wtol = wire_cfg.resolve_tol(self.tolerance)

        def exchange(payload, changed, send_idx, send_mask, budget):
            ship = jnp.logical_and(send_mask, changed[send_idx])

            def a2a(rows):
                rows = rows.reshape((S, budget) + rows.shape[1:])
                out = jax.lax.all_to_all(rows, ax, 0, 0, tiled=True)
                return out.reshape((S * budget,) + out.shape[2:])

            def one(x):
                rows = x[send_idx]
                m = ship.reshape((-1,) + (1,) * (rows.ndim - 1))
                return a2a(jnp.where(m, rows, jnp.zeros_like(rows)))

            recv = jax.tree.map(one, payload)
            recv_changed = a2a(ship)
            return recv, recv_changed, jnp.sum(ship, dtype=jnp.int32)

        def color_edge_sets(tb, color, group):
            """The color-step's local edge sets: gather over edges whose
            receiver has the color, deposit along edges whose sender has
            it (only those senders contribute), plus the gather subset's
            rows in the local edge arrays.  ``color`` may be traced: the
            colors of one group have equal sizes, laid end to end."""
            first = self._color_groups[group][0]
            cut = jax.lax.dynamic_slice_in_dim
            sets, perm = [], None
            for kind in ("g", "s"):
                e_base, size, t_base, n_steps = self._color_sets[kind][group]
                lo = e_base + (color - first) * size
                tlo = t_base + (color - first) * n_steps
                sets.append(EdgeSet(
                    n_vertices=n_loc,
                    senders=cut(tb[f"gasc_{kind}_send"], lo, size),
                    receivers=cut(tb[f"gasc_{kind}_recv"], lo, size),
                    step_rb=cut(tb[f"gasc_{kind}_srb"], tlo, n_steps),
                    step_eb=cut(tb[f"gasc_{kind}_seb"], tlo, n_steps)))
                if kind == "g":
                    perm = cut(tb["gasc_g_perm"], lo, size)
            return sets[0], sets[1], perm

        def phase_update(tb, carry, active, defer=False, color=None,
                         group=None):
            # a stalled machine (membership: dead or hung) executes no
            # updates — and, through the versioned exchange below, ships
            # nothing, so poisoned data never leaves it (DESIGN §3.13)
            live = jnp.logical_not(tb["stall"][0])
            active = jnp.logical_and(active, live)
            vown, vghost = carry["vown"], carry["vghost"]
            edata, eghost = carry["edata"], carry["eghost"]
            prio, count = carry["prio"], carry["count"]
            tv, te = carry["tv"], carry["te"]
            bv, be = carry["bv"], carry["be"]
            wire_st = dict(carry["wire"]) if use_delta else carry["wire"]

            # Double-buffered overlap (DESIGN §3.14): the previous phase
            # deferred its encoded rows into ``carry["pkt"]``; issue their
            # all_to_all here, before this phase's gather⊕combine.  Nothing
            # between here and the merge below reads the result, so the
            # collective carries no data dependency into the local compute
            # and XLA overlaps the two.  The recv merges after the compute
            # — delivery is delayed one phase, never dropped.
            pkt = carry.get("pkt")
            pkt_recv = pkt_ch = epkt_recv = epkt_ch = None
            if pkt is not None:
                pkt_recv, pkt_ch, shipped = exchange(
                    pkt["payload"], pkt["ship"], tb["send_idx"],
                    tb["send_mask"], B)
                tv = tv + shipped
                bv = bv + shipped * payload_row_nbytes(pkt["payload"])
                if "epayload" in pkt:
                    epkt_recv, epkt_ch, eshipped = exchange(
                        pkt["epayload"], pkt["eship"], tb["esend_idx"],
                        tb["esend_mask"], EB)
                    te = te + eshipped
                    be = be + eshipped * payload_row_nbytes(
                        pkt["epayload"])

            sl, rl = tb["senders_local"], tb["receivers_local"]
            emask = tb["edge_mask"]
            # masked edges aggregate into the dropped segment n_loc
            recv_idx = jnp.where(emask, rl, n_loc)

            v_all = jax.tree.map(
                lambda o, g: jnp.concatenate([o, g], 0), vown, vghost)

            if use_fused:
                # fused local compute: per-leaf feature table over
                # own+ghost rows, per-edge scalar weight, one GAS
                # gather⊕combine per leaf — no [e_loc, D] messages, and
                # row blocks with no scheduled own vertex are skipped.
                # ``es_dep`` is reused below by the fused reschedule
                # scatter.  A sweep's color-step streams only its color's
                # edges (see color_edge_sets); otherwise all local edges.
                es = EdgeSet(
                    n_vertices=n_loc,
                    senders=tb["gas_send"], receivers=tb["gas_recv"],
                    step_rb=tb["gas_step_rb"], step_eb=tb["gas_step_eb"])
                es_dep, perm = es, None
                if group is not None:
                    es, es_dep, perm = color_edge_sets(tb, color, group)
                blk_active = active_row_blocks(active)
                accs = []
                for leaf in gas_leaves:
                    feat = leaf.feature(v_all)
                    trailing = feat.shape[1:]
                    w = fused_edge_weight(leaf, edata, e_loc,
                                          tb["src_deg_e"])
                    w = jnp.where(tb["edge_mask"], w, 0.0)
                    if perm is not None:
                        w = w[perm]
                    a = gather_combine(
                        feat.reshape(feat.shape[0], -1), w, es,
                        block_active=blk_active,
                        interpret=gas_interpret)
                    accs.append(a.reshape((n_loc,) + trailing))
                acc = jax.tree.unflatten(gas_treedef, accs)
            else:
                if use_rev:
                    e_all = jax.tree.map(
                        lambda o, g: jnp.concatenate([o, g], 0), edata,
                        eghost)
                    rp = jnp.maximum(tb["rev_local"], 0)
                    has_rev = tb["rev_local"] >= 0

                    def _rev(x):
                        y = x[rp]
                        m = has_rev.reshape((-1,) + (1,) * (y.ndim - 1))
                        return jnp.where(m, y, jnp.zeros_like(y))

                    rev_edata = jax.tree.map(_rev, e_all)
                else:
                    # program declared it never reads ctx.rev_edata
                    rev_edata = jax.tree.map(jnp.zeros_like, edata)

                ctx = EdgeCtx(
                    edata=edata,
                    rev_edata=rev_edata,
                    src=jax.tree.map(lambda x: x[sl], v_all),
                    dst=jax.tree.map(lambda x: x[rl], vown),
                    src_deg=tb["src_deg_e"],
                    dst_deg=tb["dst_deg_e"])
                msgs = prog.gather(ctx)
                acc = segment_combine(msgs, recv_idx, n_loc,
                                      prog.combiner,
                                      indices_are_sorted=False)

            new_v, residual = prog.apply(vown, acc, carry.get("glob"))
            vown = masked_update(vown, new_v, active)
            contrib = jnp.where(
                active, prog.priority(residual.astype(jnp.float32)), 0.0)

            # versioned ghost exchange: vdata (+acc for edge writes,
            # +contrib for remote scheduling).  Default wire ships f32
            # rows of *changed* vertices; a non-default WireConfig ships
            # quantized rows — absolute (replace-merge) without error
            # feedback, else deltas against the owner-side mirror of what
            # every cache holds, with top-k residual selection (§3.14).
            pkt_out = None
            ghost_contrib = jnp.zeros(S * B, jnp.float32)
            merged_ch = jnp.zeros(S * B, bool)
            if use_delta:
                # contrib of cached rows accrues until a ship delivers it
                cpend = wire_st["cpend"] + jnp.where(
                    jnp.logical_and(active, tb["vhas_cacher"]), contrib,
                    0.0)
                if prog.has_edge_out:
                    # fused gather zeroes acc rows in inactive row blocks,
                    # so the shippable accumulator is the last *valid* one
                    alast = jax.tree.map(
                        lambda o, n: _rows_where(active, n, o),
                        wire_st["alast"], acc)
                vdelta = tree_sub(vown, wire_st["vref"])
                pend = tree_rows_maxabs(vdelta)
                if prog.has_edge_out:
                    adelta = tree_sub(alast, wire_st["aref"])
                    pend = jnp.maximum(pend, tree_rows_maxabs(adelta))
                dirty = jnp.logical_and(
                    jnp.logical_or(pend > wtol, jnp.abs(cpend) > wtol),
                    jnp.logical_and(tb["vhas_cacher"], live))
                if top_k is not None:
                    k = min(int(top_k), n_loc)
                    score = jnp.where(dirty, pend + jnp.abs(cpend),
                                      -jnp.inf)
                    _, tki = jax.lax.top_k(score, k)
                    in_top = jnp.zeros(n_loc, bool).at[tki].set(True)
                    ship_rows = jnp.logical_and(dirty, in_top)
                else:
                    ship_rows = dirty
                payload = {"v": encode_payload(vdelta, codec),
                           "contrib": encode_rows(cpend, codec)}
                if prog.has_edge_out:
                    payload["acc"] = encode_payload(adelta, codec)
                if defer:
                    # overlap: the ship rides the *next* phase's top-of-
                    # phase all_to_all; the owner folds now (below), so
                    # these rows are in flight, not pending
                    recv = recv_ch = None
                    pkt_out = {"payload": payload, "ship": ship_rows}
                else:
                    recv, recv_ch, shipped = exchange(
                        payload, ship_rows, tb["send_idx"],
                        tb["send_mask"], B)
                    tv = tv + shipped
                    bv = bv + shipped * payload_row_nbytes(payload)
                # owner-side error feedback: fold the decoded (= applied)
                # delta into the mirrors; the quantization residue stays
                # in vown − vref / cpend and re-ships until < wire_tol
                dec_own = decode_payload(payload, codec)
                wire_st["vref"] = tree_add_where(
                    wire_st["vref"], dec_own["v"], ship_rows)
                wire_st["cpend"] = jnp.where(
                    ship_rows, cpend - dec_own["contrib"], cpend)
                if prog.has_edge_out:
                    wire_st["aref"] = tree_add_where(
                        wire_st["aref"], dec_own["acc"], ship_rows)
                    wire_st["alast"] = alast
                # receiver side: additive delta merges — last phase's
                # deferred packet first, then this phase's own rows
                # (owner folded the identical decodes into its mirrors,
                # so caches track them; addition commutes anyway)
                for r_, ch_ in ((pkt_recv, pkt_ch), (recv, recv_ch)):
                    if r_ is None:
                        continue
                    d_ = decode_payload(r_, codec)
                    vghost = tree_add_where(vghost, d_["v"], ch_)
                    ghost_contrib = ghost_contrib + jnp.where(
                        ch_, d_["contrib"], 0.0)
                    if prog.has_edge_out:
                        wire_st["aghost"] = tree_add_where(
                            wire_st["aghost"], d_["acc"], ch_)
                    merged_ch = jnp.logical_or(merged_ch, ch_)
                recv_acc = wire_st["aghost"] if prog.has_edge_out \
                    else None
            else:
                raw = {"v": vown, "contrib": contrib}
                if prog.has_edge_out:
                    raw["acc"] = acc
                payload = raw if codec == "f32" \
                    else encode_payload(raw, codec)
                if defer:
                    recv = recv_ch = None
                    pkt_out = {"payload": payload, "ship": active}
                else:
                    recv, recv_ch, shipped = exchange(
                        payload, active, tb["send_idx"], tb["send_mask"],
                        B)
                    tv = tv + shipped
                    bv = bv + shipped * payload_row_nbytes(payload)
                # replace-merges, deferred packet first (a row ships at
                # most once per step here — one color per vertex — so the
                # two merges never collide)
                recv_acc = jax.tree.map(
                    lambda a: jnp.zeros((S * B,) + a.shape[1:], a.dtype),
                    acc) if prog.has_edge_out else None
                for r_, ch_ in ((pkt_recv, pkt_ch), (recv, recv_ch)):
                    if r_ is None:
                        continue
                    d_ = r_ if codec == "f32" else decode_payload(r_,
                                                                  codec)

                    def _merge(old, new, ch=ch_):
                        m = ch.reshape((-1,) + (1,) * (old.ndim - 1))
                        return jnp.where(m, new.astype(old.dtype), old)

                    vghost = jax.tree.map(_merge, vghost, d_["v"])
                    ghost_contrib = ghost_contrib + jnp.where(
                        ch_, d_["contrib"], 0.0)
                    if prog.has_edge_out:
                        recv_acc = jax.tree.map(_merge, recv_acc,
                                                d_["acc"])
                    merged_ch = jnp.logical_or(merged_ch, ch_)

            # live snapshot: record post-cut rows (updated-after-save own
            # rows, rows arriving from already-saved remote vertices)
            # BEFORE any later capture could read them
            snap = carry["snap"]
            if snap is not None:
                snap = mark_stale(snap, active, merged_ch)

            # T ← (T \ executed) ∪ T': winners consume their priority,
            # losers/remotes keep theirs (a still-queued lock request).
            # On the fused path consume + per-edge deposit run as one
            # scatter_reschedule — no [e_loc] float gather temp, no dense
            # [n_loc+1] scatter-add intermediate.
            if prog.schedule_neighbors:
                contrib_all = jnp.concatenate([contrib, ghost_contrib])
                if use_fused:
                    # a color subset holds only live edges: unit weights
                    prio = scatter_reschedule(
                        contrib_all, prio, active, es_dep,
                        emask.astype(jnp.float32) if es_dep is es else None,
                        interpret=gas_interpret)
                else:
                    prio = jnp.where(active, 0.0, prio)
                    vals = jnp.where(emask, contrib_all[sl], 0.0)
                    prio = prio + jax.ops.segment_sum(
                        vals, recv_idx, n_loc + 1)[:n_loc]
            else:
                prio = jnp.where(active, 0.0, prio)

            if prog.has_edge_out:
                v_all2 = jax.tree.map(
                    lambda o, g: jnp.concatenate([o, g], 0), vown,
                    vghost)
                acc_all = jax.tree.map(
                    lambda a, g: jnp.concatenate(
                        [a, g.astype(a.dtype)], 0), acc, recv_acc)
                changed_all = jnp.concatenate(
                    [active, merged_ch.astype(active.dtype)])
                ctx2 = ctx._replace(
                    src=jax.tree.map(lambda x: x[sl], v_all2),
                    dst=jax.tree.map(lambda x: x[rl], vown))
                new_src = jax.tree.map(lambda x: x[sl], v_all2)
                src_acc = jax.tree.map(lambda x: x[sl], acc_all)
                new_e = prog.edge_out(ctx2, new_src, src_acc)
                wmask = jnp.logical_and(changed_all[sl], emask)
                if streaming:
                    # Elidan-style message-residual scheduling (DESIGN
                    # §3.11): a delta edge's message jumps from its init
                    # value while the writer's own residual is zero, so
                    # the reader must be re-scheduled by the *edge*
                    # change.  Only the streaming engines add this —
                    # the frozen-structure engines keep their seed
                    # schedule bit-for-bit.
                    prio = prio + edge_residual_bump(
                        edata, new_e, wmask, rl, emask, n_loc,
                        self.tolerance)
                edata = masked_update(edata, new_e, wmask)

                if use_rev:  # refresh remote reverse-message caches
                    if use_delta:
                        # edge wire: same delta + error-feedback protocol,
                        # dirtiness-driven (re-ships quantization residue
                        # until < wire_tol); no top-k on edges
                        edelta = tree_sub(edata, wire_st["eref"])
                        edirty = jnp.logical_and(
                            tree_rows_maxabs(edelta) > wtol,
                            jnp.logical_and(tb["ehas_cacher"], live))
                        epayload = encode_payload(edelta, codec)
                        if defer:
                            erecv = erecv_ch = None
                            pkt_out["epayload"] = epayload
                            pkt_out["eship"] = edirty
                        else:
                            erecv, erecv_ch, eshipped = exchange(
                                epayload, edirty, tb["esend_idx"],
                                tb["esend_mask"], EB)
                            te = te + eshipped
                            be = be + eshipped * payload_row_nbytes(
                                epayload)
                        wire_st["eref"] = tree_add_where(
                            wire_st["eref"],
                            decode_payload(epayload, codec), edirty)
                        for r_, ch_ in ((epkt_recv, epkt_ch),
                                        (erecv, erecv_ch)):
                            if r_ is None:
                                continue
                            eghost = tree_add_where(
                                eghost, decode_payload(r_, codec), ch_)
                    else:
                        epayload = edata if codec == "f32" \
                            else encode_payload(edata, codec)
                        if defer:
                            erecv = erecv_ch = None
                            pkt_out["epayload"] = epayload
                            pkt_out["eship"] = wmask
                        else:
                            erecv, erecv_ch, eshipped = exchange(
                                epayload, wmask, tb["esend_idx"],
                                tb["esend_mask"], EB)
                            te = te + eshipped
                            be = be + eshipped * payload_row_nbytes(
                                epayload)
                        for r_, ch_ in ((epkt_recv, epkt_ch),
                                        (erecv, erecv_ch)):
                            if r_ is None:
                                continue
                            ed_ = r_ if codec == "f32" \
                                else decode_payload(r_, codec)

                            def _emerge(old, new, ch=ch_):
                                m = ch.reshape(
                                    (-1,) + (1,) * (old.ndim - 1))
                                return jnp.where(m, new.astype(old.dtype),
                                                 old)

                            eghost = jax.tree.map(_emerge, eghost, ed_)

            if use_delta:
                # backlog: rows still owed to some cache (top-k leftovers,
                # quantization residue) — run() refuses to terminate while
                # any machine's backlog is nonzero, so every deferred
                # delta is eventually delivered
                pend2 = tree_rows_maxabs(tree_sub(vown, wire_st["vref"]))
                if prog.has_edge_out:
                    pend2 = jnp.maximum(pend2, tree_rows_maxabs(
                        tree_sub(wire_st["alast"], wire_st["aref"])))
                vd = jnp.logical_and(
                    jnp.logical_or(pend2 > wtol,
                                   jnp.abs(wire_st["cpend"]) > wtol),
                    jnp.logical_and(tb["vhas_cacher"], live))
                nback = jnp.sum(vd, dtype=jnp.int32)
                if use_rev:
                    ed = jnp.logical_and(
                        tree_rows_maxabs(
                            tree_sub(edata, wire_st["eref"])) > wtol,
                        jnp.logical_and(tb["ehas_cacher"], live))
                    nback = nback + jnp.sum(ed, dtype=jnp.int32)
                wire_st["backlog"] = nback.reshape(1)

            count = count + active.astype(jnp.int32)
            return dict(vown=vown, vghost=vghost, edata=edata, eghost=eghost,
                        prio=prio, count=count, tv=tv, te=te, bv=bv, be=be,
                        wire=wire_st, snap=snap, glob=carry.get("glob"),
                        pkt=pkt_out)

        return exchange, phase_update

    def _wrap_step(self, body):
        """shard_map-wraps a ``body(state, tables) -> state`` and appends
        the replicated step-index bump.

        When a snapshot is live (``state.snap`` is a ``DistSnapshotState``
        rather than None — a trace-time distinction), the Chandy-Lamport
        marker phase runs first, as the paper's prioritized snapshot
        update (Alg. 5): scope + channel-state capture and the marker
        exchange all precede the step's regular phases, so captures read
        pre-step values and post-cut rows can never enter a saved scope.
        The ``snap=spec`` entry is a pytree prefix: zero leaves when snap
        is None, all machine-sharded rows otherwise."""
        spec = P(self.axis)
        marker_phase = make_marker_phase(
            self._make_phase_helpers()[0], self.layout.n_loc,
            self.layout.budget)
        sync_ops = self.sync_ops
        n_global = self.graph.structure.n_vertices
        ax = self.axis

        def dist_syncs(tb, vown, vown_prev):
            """The §3.9 step-barrier sync: per-machine masked map_fn fold,
            cross-machine psum, replicated finalize."""
            out = {}
            for op in sync_ops:
                data = vown if op.consistent else vown_prev
                mapped = op.map_fn(data)

                def _fold(m):
                    keep = tb["own_mask"].reshape(
                        (-1,) + (1,) * (m.ndim - 1))
                    return jax.lax.psum(
                        jnp.sum(jnp.where(keep, m, jnp.zeros_like(m)),
                                axis=0), ax)

                z = jax.tree.map(_fold, mapped)
                out[op.name] = op.finalize(z, n_global)
            return out

        def full_body(state: DistState, tb) -> DistState:
            vown_prev = state.vown
            beats = state.beats
            if beats is None:  # pre-§3.13 state (e.g. restored cut)
                beats = jnp.zeros((1,), jnp.int32)
            if state.snap is not None:
                state = state.replace(snap=marker_phase(
                    tb, state.snap, state.vown, state.edata,
                    state.step_index))
            state = body(state, tb)
            if sync_ops:
                state = state.replace(
                    globals_=dist_syncs(tb, state.vown, vown_prev))
            # heartbeat (DESIGN §3.13): one monotone beat per executed
            # step; a stalled machine stops beating, which is exactly the
            # signal the host Watchdog reads
            return state.replace(
                beats=beats + jnp.logical_not(tb["stall"]).astype(
                    jnp.int32))

        state_specs = DistState(
            vown=spec, vghost=spec, edata=spec, eghost=spec, prio=spec,
            update_count=spec, traffic_v=spec, traffic_e=spec,
            traffic_r=spec, traffic_bytes_v=spec, traffic_bytes_e=spec,
            traffic_bytes_r=spec, step_index=P(), snap=spec, globals_=P(),
            beats=spec, wire=spec)
        sharded = jax.shard_map(
            full_body, mesh=self.mesh,
            in_specs=(state_specs, spec), out_specs=state_specs,
            check_vma=False)

        def step(state: DistState, tables) -> DistState:
            self._trace_count += 1
            out = sharded(state, tables)
            return out.replace(step_index=state.step_index + 1)

        return step

    def _make_step(self):
        raise NotImplementedError

    # -- drivers --------------------------------------------------------------
    def step(self, state: DistState) -> DistState:
        return self._jit_step(state, self._tables)

    def compile(self, state: DistState):
        """Compiles the step ahead of time for ``state``'s shapes; ``step``
        and ``run`` then call that executable.  Returns it, for its
        ``as_text()`` and ``memory_analysis()``."""
        with span("graphlab.lower"):
            lowered = self._jit_step.lower(state, self._tables)
        with span("graphlab.compile"):
            self._jit_step = lowered.compile()
        return self._jit_step

    def run(self, state: DistState, max_steps: int = 100, *,
            trace_every: Optional[int] = None,
            supervisor=None,
            session=None) -> Tuple[DistState, "list[dict]"]:
        """Host driver loop.  Trace rows follow the canonical telemetry
        schema (obs.metrics.METRICS_SCHEMA): ``step``/``updates``/
        ``residual_max``/``backlog``/``wire_backlog``/
        ``traffic_{rows,bytes}_{v,e,r}``.  Rows are lazy device
        scalars, fetched with one host transfer per ``trace_every``
        steps (default ``obs.trace_every``); the per-step sync that
        remains is the NaN-safe termination check, which the control
        loop needs anyway.

        A ``supervisor`` (obs.Supervisor) observes after every step and
        may *rebuild* the engine (migrate_leave/join, shed_atoms) — the
        loop continues on the returned engine, the final one is at
        ``supervisor.engine``, and the loop keeps stepping a converged
        state while ``supervisor.pending_work()`` (e.g. an offered
        machine still to join).  A ``session`` (obs.ObsSession) receives
        rows, supervisor events, and timeline spans.  Host spans
        (``obs.span``): ``graphlab.run`` over the call, and per step
        ``graphlab.done`` (the termination check, which blocks on the
        device) and ``graphlab.dispatch`` (``step``; its args say whether
        a marker wave rode the step).
        """
        from repro.obs.metrics import RowCollector, lazy_dist_row
        eng = self
        every = int(trace_every) if trace_every is not None \
            else self.obs.trace_every
        col = RowCollector(every, session=session)
        quant = self.obs.residual_quantiles if self.obs.enabled else None
        track = type(self).__name__
        with span("graphlab.run", session=session, track=track):
            for _ in range(max_steps):
                # under a quantized wire, converged priorities are not
                # enough: deferred/top-k deltas still owed to remote caches
                # (the wire backlog) must drain first — deferral is never
                # a drop.  NaN residuals — a dead machine's poisoned shard
                # — must hold the loop open for the supervisor to heal, and
                # XLA's reduce_max does NOT reliably propagate NaN, so map
                # them to +inf before reducing
                with span("graphlab.done", session=session, track=track):
                    done = (float(jnp.max(jnp.where(
                        jnp.isnan(state.prio), jnp.inf, state.prio)))
                        <= eng.tolerance
                        and eng._wire_backlog(state) == 0
                        and (supervisor is None
                             or not supervisor.pending_work()))
                if done:
                    break
                with span("graphlab.dispatch", session=session, track=track,
                          args={"marker_wave": state.snap is not None}):
                    state = eng.step(state)
                if supervisor is not None:
                    eng, state = supervisor.observe(eng, state)
                col.push(lazy_dist_row(state, eng.tolerance, quant,
                                       beats=eng.obs.enabled))
            col.drain()
        return state, col.rows

    def _wire_backlog(self, state: DistState) -> int:
        if state.wire is None:
            return 0
        return int(np.asarray(state.wire["backlog"]).sum())

    # -- snapshots (paper Sec. 4.3; DESIGN.md §3.10) ---------------------------
    def start_snapshot(self, state: DistState,
                       initiators=(0,)) -> DistState:
        """Attaches a fresh Chandy-Lamport snapshot: the next ``step``
        runs the prioritized marker phase with the given initiator
        vertices' scopes as the first frontier.  Markers flood the
        sender→receiver direction of the local edge tables plus the ghost
        channels, so reaching every vertex requires a symmetrized
        structure (the reverse hop rides the reverse edge — same
        requirement, and same error, as the locking engine's
        arbitration)."""
        if state.snap is not None:
            raise ValueError("a snapshot is already in flight; clear or "
                             "complete it first")
        if not self.graph.structure.is_symmetric():
            raise ValueError(
                "distributed snapshot markers flood via reverse edges: "
                "the structure must be symmetrized (every edge's reverse "
                "present) or the wave cannot reach every vertex")
        lay = self.layout
        rows = lay.row_of[np.asarray(list(initiators), np.int64)]
        pending = np.zeros(lay.n_machines * lay.n_loc, bool)
        pending[rows] = True
        sg = getattr(self, "_stream_graph", None)
        if sg is not None:
            # markers flood real edges only, so isolated active vertices
            # (churn can strand them) must self-capture: their scope is
            # exactly themselves — seed them into the first frontier
            isolated = sg.vertex_active & (sg.fill == 0) & (sg.out_deg == 0)
            pending[lay.row_of[np.nonzero(isolated)[0]]] = True
        snap = init_dist_snapshot(
            jnp.asarray(pending), state.vown, state.edata,
            e_rows=lay.n_machines * lay.e_loc,
            g_rows=lay.n_machines * (lay.n_machines * lay.budget),
            n_machines=lay.n_machines)
        put = lambda t: jax.tree.map(
            lambda x: jax.device_put(x, self._shard), t)
        return state.replace(snap=put(snap))

    def clear_snapshot(self, state: DistState) -> DistState:
        """Detaches the snapshot state (after journaling a completed cut
        — or to abandon one); subsequent steps skip the marker phase."""
        return state.replace(snap=None)

    def _snapshot_need(self) -> np.ndarray:
        """Rows whose scope a complete cut must have saved: owned rows,
        minus capacity padding — under streaming, inactive (never-added
        or deleted) vertices carry no edges, so no marker can reach them
        and no cut needs them."""
        need = self.layout.tables["own_mask"].copy()
        sg = getattr(self, "_stream_graph", None)
        if sg is not None:
            ok = self.layout.own_gid >= 0
            need[ok] &= sg.vertex_active[self.layout.own_gid[ok]]
        return need

    def snapshot_complete(self, state: DistState) -> bool:
        """All owned vertex scopes saved (pad rows don't count)."""
        if state.snap is None:
            return False
        done = np.asarray(state.snap.done)
        return bool(np.all(done | ~self._snapshot_need()))

    def snapshot_done_frac(self, state: DistState) -> float:
        if state.snap is None:
            return 0.0
        need = self._snapshot_need()
        return float(np.asarray(state.snap.done)[need].mean())

    def snapshot_violations(self, state: DistState) -> int:
        """Post-snapshot rows read by a capture — 0 iff the saved cut is
        consistent (the machine-checked invariant)."""
        if state.snap is None:
            return 0
        return int(np.asarray(state.snap.violations).sum())

    def marker_rows_sent(self, state: DistState) -> int:
        """Marker rows shipped over the ghost channels; bounded by
        ``total_ghost_slots`` (each pair ships its marker at most once)."""
        if state.snap is None:
            return 0
        return int(np.asarray(state.snap.traffic_m).sum())

    def assemble_snapshot(self, state: DistState) -> SnapshotState:
        """The sharded cut stitched to a global ``SnapshotState`` —
        ``core.snapshot.restore_engine_state`` restarts any engine (any
        mesh shape) from it."""
        if state.snap is None:
            raise ValueError("no snapshot attached")
        st = self.graph.structure
        return _assemble_snapshot(self.layout, state.snap, st.n_vertices,
                                  st.n_edges)

    # -- readback -------------------------------------------------------------
    def vertex_data(self, state: DistState) -> Pytree:
        """Owned rows stitched back to global vertex order [N, ...]."""
        return stitch_rows(state.vown, self.layout.own_gid,
                           self.graph.structure.n_vertices)

    def ghost_rows_sent(self, state: DistState) -> int:
        return int(np.asarray(state.traffic_v).sum())

    def ghost_edge_rows_sent(self, state: DistState) -> int:
        return int(np.asarray(state.traffic_e).sum())

    def rank_rows_sent(self, state: DistState) -> int:
        """Arbitration rank rows shipped (the locking engine's lock-request
        traffic; always 0 for the sweep-scheduled engine)."""
        return int(np.asarray(state.traffic_r).sum())

    def ghost_bytes_sent(self, state: DistState) -> int:
        """Payload bytes of the vertex ghost rows shipped (per-row codec
        bytes × rows; the per-entry ship bitmap rides free either way and
        is excluded, matching the row counters)."""
        return int(np.asarray(state.traffic_bytes_v).sum())

    def ghost_edge_bytes_sent(self, state: DistState) -> int:
        return int(np.asarray(state.traffic_bytes_e).sum())

    def rank_bytes_sent(self, state: DistState) -> int:
        return int(np.asarray(state.traffic_bytes_r).sum())

    def total_ghost_slots(self) -> int:
        """Distinct (vertex, caching machine) pairs — the per-sweep upper
        bound on versioned traffic when every vertex updates."""
        return int(self.layout.tables["send_mask"].sum())


class DistributedEngine(ShardEngineBase):
    """The sweep-scheduled distributed engine (paper Sec. 4.2.1 under
    shard_map): ``step(state)`` is one chromatic sweep; within a color every
    machine updates its scheduled own vertices of that color.  Because a
    proper coloring makes same-color vertices non-adjacent, refreshing
    ghosts once per color-step reproduces the shared-memory engine's reads
    exactly, so the distributed fixed point matches ``ChromaticEngine`` to
    float tolerance (tests/test_dist_engine.py)."""

    def __init__(
        self,
        program: VertexProgram,
        graph: DataGraph,
        mesh,
        *,
        colors: Optional[np.ndarray] = None,
        spare_colors: int = 0,
        **kw,
    ):
        super().__init__(program, graph, mesh, **kw)
        st = graph.structure
        if colors is None:
            colors = coloring_for(st, program.consistency)
        colors = np.asarray(colors, np.int32)
        # spare colors: empty sweep phases reserved as palette headroom
        # for streaming color repair (value patches, never a retrace)
        self.num_colors = (int(colors.max()) + 1 if colors.size else 1) \
            + max(int(spare_colors), 0)
        self.colors = colors
        self._spare_colors = max(int(spare_colors), 0)

        colors_own = np.zeros(
            self.layout.n_machines * self.layout.n_loc, np.int32)
        ok = self.layout.own_gid >= 0
        colors_own[ok] = colors[self.layout.own_gid[ok]]
        self.layout.tables["colors_own"] = colors_own
        if self._use_fused and not self.streaming:
            self._build_color_sets(colors)
        self._finalize()

    def _build_color_sets(self, colors: np.ndarray) -> None:
        """Per-color local edge sets for the fused sweep (the dist twin of
        ``ChromaticEngine._phase_edge_sets``): without them every
        color-step streams all local edges, and a power-law graph has
        colors in the hundreds.  Each (kind, color) set is padded to a
        power-of-two block count shared by all machines; runs of
        consecutive colors of one size class (``_color_groups``) share a
        grid length too, so a sweep loops over them.  All sets lie end to
        end in one table per array; ``_color_sets[kind][group]`` keeps the
        group's static (edge base, set size, step base, steps).  Streaming
        engines keep the full set: color membership of edges goes stale
        as deltas land."""
        lay = self.layout
        S, n_loc, e_loc = lay.n_machines, lay.n_loc, lay.e_loc
        ghosts = lay.ghost_gid.reshape(S, -1)
        local_colors = np.concatenate(
            [lay.tables["colors_own"].reshape(S, n_loc),
             np.where(ghosts >= 0, colors[np.maximum(ghosts, 0)], -1)],
            axis=1)
        sl = lay.tables["senders_local"].reshape(S, e_loc)
        rl = lay.tables["receivers_local"].reshape(S, e_loc)
        em = lay.tables["edge_mask"].reshape(S, e_loc)
        k = self.num_colors
        subsets = {}
        for kind, key in (("g", np.take_along_axis(local_colors, rl, 1)),
                          ("s", np.take_along_axis(local_colors, sl, 1))):
            key = np.where(em, key, -1)       # padding rows join no color
            per_machine = [split_by_color(key[m], k) for m in range(S)]
            subsets[kind] = [[per_machine[m][c] for m in range(S)]
                             for c in range(k)]
        blocks = {kind: [size_class(max(i.size for i in idx))
                         for idx in per_color]
                  for kind, per_color in subsets.items()}
        self._color_groups = color_runs(blocks)
        self._color_sets = {}
        pad_r = n_loc + ROW_BLOCK
        for kind, per_color in subsets.items():
            cols = {f: [[] for _ in range(S)]
                    for f in ("send", "recv", "perm", "srb", "seb")}
            ranges, e_off, t_off = [], 0, 0
            for first, count in self._color_groups:
                size = blocks[kind][first] * EDGE_BLOCK
                recv = [[np.pad(rl[m][i], (0, size - i.size),
                                constant_values=pad_r)
                         for m, i in enumerate(per_color[c])]
                        for c in range(first, first + count)]
                n_steps = max(csr_steps(r, n_loc)[0].size
                              for rc in recv for r in rc)
                for j, c in enumerate(range(first, first + count)):
                    for m, i in enumerate(per_color[c]):
                        pad = (0, size - i.size)
                        cols["send"][m].append(np.pad(sl[m][i], pad))
                        cols["recv"][m].append(recv[j][m])
                        cols["perm"][m].append(np.pad(i, pad))
                        srb, seb = csr_steps(recv[j][m], n_loc, n_steps)
                        cols["srb"][m].append(srb)
                        cols["seb"][m].append(seb)
                ranges.append((e_off, size, t_off, n_steps))
                e_off += count * size
                t_off += count * n_steps
            self._color_sets[kind] = ranges
            for f, per_machine in cols.items():
                if kind == "s" and f == "perm":
                    continue
                lay.tables[f"gasc_{kind}_{f}"] = np.concatenate(
                    [np.concatenate(p) for p in per_machine]).astype(np.int32)

    def _clone_kwargs(self) -> dict:
        return dict(super()._clone_kwargs(), colors=self.colors,
                    spare_colors=self._spare_colors)

    def _make_step(self):
        _, phase_update = self._make_phase_helpers()
        num_colors, tol = self.num_colors, self.tolerance
        overlap = self.overlap
        groups = self._color_groups

        def body(state: DistState, tb: Dict[str, jnp.ndarray]) -> DistState:
            carry = dict(vown=state.vown, vghost=state.vghost,
                         edata=state.edata, eghost=state.eghost,
                         prio=state.prio, count=state.update_count,
                         tv=state.traffic_v, te=state.traffic_e,
                         bv=state.traffic_bytes_v,
                         be=state.traffic_bytes_e,
                         wire=state.wire,
                         snap=state.snap, glob=state.globals_,
                         pkt=None)
            # overlap is a trace-time choice, and it stands down while a
            # snapshot is live: the marker wave's channel accounting
            # assumes each phase's sends merge in-phase (§3.10).  The last
            # color never defers, so no packet outlives the step and the
            # run() termination check stays exact.
            defer_ok = overlap and state.snap is None

            def color_step(c, carry, group=None):
                active = jnp.logical_and(
                    tb["own_mask"],
                    sweep_mask(tb["colors_own"], carry["prio"], tol, c))
                return phase_update(tb, carry, active,
                                    defer=defer_ok and c < num_colors - 1,
                                    color=c, group=group)

            if groups is None or defer_ok:
                # (a deferred exchange changes the carry from phase to
                # phase, so overlapped sweeps stay unrolled)
                group_of = {c: g for g, (first, n) in enumerate(groups or [])
                            for c in range(first, first + n)}
                for c in range(num_colors):
                    carry = color_step(c, carry, group_of.get(c))
            else:
                # runs of same-sized colors loop over one traced body
                for g, (first, n) in enumerate(groups):
                    step_g = functools.partial(color_step, group=g)
                    carry = (step_g(first, carry) if n == 1 else
                             jax.lax.fori_loop(first, first + n, step_g,
                                               carry))
            return DistState(
                vown=carry["vown"], vghost=carry["vghost"],
                edata=carry["edata"], eghost=carry["eghost"],
                prio=carry["prio"], update_count=carry["count"],
                traffic_v=carry["tv"], traffic_e=carry["te"],
                traffic_r=state.traffic_r,
                traffic_bytes_v=carry["bv"], traffic_bytes_e=carry["be"],
                traffic_bytes_r=state.traffic_bytes_r,
                step_index=state.step_index, snap=carry["snap"],
                wire=carry["wire"], globals_=state.globals_)

        return self._wrap_step(body)


# ---------------------------------------------------------------------------
# overlap audit (DESIGN §3.14): jaxpr-level schedule assertion
# ---------------------------------------------------------------------------

def exchange_overlap_report(engine, state: Optional[DistState] = None
                            ) -> Dict[str, int]:
    """Traces one engine step and audits the exchange schedule at the
    jaxpr level — the §3.14 "collective issued before the dependent
    gather" assertion, in checkable form.

    Inside the shard_map body, equations are walked in program order.
    Collectives issued back-to-back (no ``gather`` between them) form one
    exchange *group* — the per-phase ship.  Every ``gather`` that follows
    a group is classified against the most recent group: *dependent* if
    it transitively consumes any of the group's outputs (it must wait for
    the wire), *independent* if it consumes none (XLA is free to run it
    concurrently with the in-flight collectives).

    In the sequential build each phase's exchange merges before the next
    color's local gather⊕combine, so those gathers are dependent.  The
    double-buffered overlap build issues color c−1's deferred packet at
    the top of phase c and merges it only *after* the local compute, so
    phase c's gathers are independent of the group in flight.  Compared
    at equal collective counts, overlap therefore strictly raises
    ``independent_gathers`` and strictly lowers ``dependent_gathers`` —
    that pairwise comparison is the assertion tests and the benchmark
    make (absolute counts vary with program and color count).

    Trace with ``use_fused=False`` engines: the fused path hides the
    local gather inside a ``pallas_call``.
    """
    if state is None:
        state = engine.init()
    closed = jax.make_jaxpr(engine._make_step())(state, engine._tables)

    def _subjaxprs(eqn):
        for v in eqn.params.values():
            vs = v if isinstance(v, (tuple, list)) else (v,)
            for w in vs:
                inner = getattr(w, "jaxpr", w)
                if hasattr(inner, "eqns"):
                    yield inner

    def _find_body(j):
        if any(e.primitive.name == "all_to_all" for e in j.eqns):
            return j
        for e in j.eqns:
            for sj in _subjaxprs(e):
                hit = _find_body(sj)
                if hit is not None:
                    return hit
        return None

    body = _find_body(closed.jaxpr)
    report = {"all_to_all": 0, "independent_gathers": 0,
              "dependent_gathers": 0}
    if body is None:
        return report
    deps: Dict[int, frozenset] = {}
    group: frozenset = frozenset()
    last_sig = None
    nid = 0
    for eqn in body.eqns:
        d = frozenset()
        for v in eqn.invars:
            d |= deps.get(id(v), frozenset())
        name = eqn.primitive.name
        if name == "all_to_all":
            if last_sig == "gather":
                group = frozenset()
            group |= frozenset([nid])
            d |= frozenset([nid])
            nid += 1
            report["all_to_all"] += 1
            last_sig = "a2a"
        elif name == "gather":
            if group:
                key = ("dependent_gathers" if d & group
                       else "independent_gathers")
                report[key] += 1
            last_sig = "gather"
        for v in eqn.outvars:
            deps[id(v)] = d
    return report
