"""Dispatch layer for the fused gather⊕combine (GAS) kernel.

``EdgeSet`` packages a (possibly color-restricted) receiver-sorted edge
subset with its padded device arrays and the kernels' grid schedule;
engines build them once per structure (or once per color) on host.
``gather_combine`` then dispatches one fused ``acc[v] = Σ w_e · feat[u]``:

    TPU            → Pallas kernel (gas.py)
    CPU, tests     → Pallas kernel in interpret mode (``interpret=True``)
    CPU, production→ jnp oracle (ref.py)

The active-block bitmap (``active_row_blocks`` of the scheduler mask) is
honored identically by both targets: inactive row blocks produce exact
zeros and — on the kernel path — cost no edge work.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.gas.gas import (EDGE_BLOCK, ROW_BLOCK, csr_steps,
                                   edge_major_fits,
                                   gas_gather_combine_pallas,
                                   kernel_takes_gather, kernel_takes_outer,
                                   gas_scatter_reschedule_pallas)
from repro.kernels.gas.ref import gather_combine_ref, scatter_reschedule_ref


@functools.partial(jax.tree_util.register_dataclass,
                   meta_fields=["n_vertices"],
                   data_fields=["senders", "receivers", "step_rb", "step_eb",
                                "block_counts"])
@dataclasses.dataclass(frozen=True, eq=False)
class EdgeSet:
    """A receiver-sorted edge subset prepared for the GAS kernel.

    A pytree (the arrays are leaves): engines pass their EdgeSets to the
    jitted step as arguments, so the edge arrays are never baked into the
    program as constants.

    Padded to a multiple of ``EDGE_BLOCK`` (always >= one block, so E == 0
    degenerates to one all-padding block): pad senders are 0, pad weights 0,
    pad receivers ``n_vertices + ROW_BLOCK`` (outside every row block).
    ``step_rb``/``step_eb`` are the kernels' flattened grid (``csr_steps``).
    ``block_counts[i]`` is the number of real subset edges whose receiver
    lies in row block i — the honest edges-touched accounting unit.
    """

    n_vertices: int
    senders: jnp.ndarray              # [E_pad] i32
    receivers: jnp.ndarray            # [E_pad] i32, non-decreasing
    step_rb: jnp.ndarray              # [T] i32 row block of each grid step
    step_eb: jnp.ndarray              # [T] i32 edge block (-1: no-op step)
    block_counts: Optional[jnp.ndarray] = None  # [n_row_blocks] i32

    @property
    def n_row_blocks(self) -> int:
        return max(-(-self.n_vertices // ROW_BLOCK), 1)

    @staticmethod
    def build(
        senders: np.ndarray,
        receivers: np.ndarray,
        n_vertices: int,
        min_blocks: int = 1,
        n_steps: Optional[int] = None,
    ) -> "EdgeSet":
        """``min_blocks`` pads the edge arrays to at least that many
        ``EDGE_BLOCK``s, ``n_steps`` the kernel grid to that many steps
        (``csr_steps``), so that sets of one size class stack."""
        senders = np.asarray(senders, np.int32)
        receivers = np.asarray(receivers, np.int32)
        assert senders.shape == receivers.shape and senders.ndim == 1
        if receivers.size:
            assert (np.diff(receivers) >= 0).all(), "receivers must be sorted"
        E = int(senders.size)
        e_pad = max(-(-E // EDGE_BLOCK), min_blocks, 1) * EDGE_BLOCK
        pad_r = np.int32(n_vertices + ROW_BLOCK)
        s = np.concatenate([senders, np.zeros(e_pad - E, np.int32)])
        r = np.concatenate([receivers, np.full(e_pad - E, pad_r, np.int32)])
        step_rb, step_eb = csr_steps(r, n_vertices, n_steps)
        nblk = max(-(-n_vertices // ROW_BLOCK), 1)
        counts = np.bincount(
            np.minimum(receivers // ROW_BLOCK, nblk - 1), minlength=nblk
        ).astype(np.int32) if E else np.zeros(nblk, np.int32)
        return EdgeSet(
            n_vertices=int(n_vertices),
            senders=jnp.asarray(s), receivers=jnp.asarray(r),
            step_rb=jnp.asarray(step_rb), step_eb=jnp.asarray(step_eb),
            block_counts=jnp.asarray(counts))


def stack_edge_sets(trees):
    """Stacks equally shaped pytrees of EdgeSets on a new leading axis
    (one entry per phase); a phase takes its sets back with ``x[i]``."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def split_by_color(key: np.ndarray, k: int) -> list:
    """Edge indices grouped by ``key`` (an edge's color; -1 joins none):
    ``k`` index arrays, each in edge order, so receiver-sorted edges stay
    sorted within a color."""
    order = np.argsort(key, kind="stable").astype(np.int32)
    bounds = np.searchsorted(key[order], np.arange(k + 1))
    return [order[bounds[c]:bounds[c + 1]] for c in range(k)]


def size_class(n_edges: int) -> int:
    """``EDGE_BLOCK``s for a set of ``n_edges``, rounded up to a power of
    two, so that the sets of hundreds of colors fall into a few shapes."""
    return 1 << max(-(-n_edges // EDGE_BLOCK) - 1, 0).bit_length()


def color_runs(blocks: dict) -> list:
    """``(first color, count)`` runs of consecutive colors whose sets have
    one size class for every kind: ``blocks[kind][c]`` is color ``c``'s
    ``size_class``.  A run's color-steps share shapes, so an engine stacks
    their sets and loops over them with one traced body."""
    runs = []
    for c in range(len(next(iter(blocks.values())))):
        if runs and all(b[c] == b[c - 1] for b in blocks.values()):
            runs[-1][1] += 1
        else:
            runs.append([c, 1])
    return [tuple(r) for r in runs]


def active_row_blocks(mask: jnp.ndarray,
                      row_block: int = ROW_BLOCK) -> jnp.ndarray:
    """[N] scheduler mask → [n_row_blocks] i32 bitmap (1 ⇔ any active)."""
    n = mask.shape[0]
    nblk = max(-(-n // row_block), 1)
    m = jnp.pad(mask.astype(jnp.int32), (0, nblk * row_block - n))
    return m.reshape(nblk, row_block).max(axis=1)


def gather_combine(
    feat: jnp.ndarray,             # [N, D] per-vertex source features
    weights: jnp.ndarray,          # [E] or [E_pad] per-edge scalars
    edges: EdgeSet,
    *,
    block_active: Optional[jnp.ndarray] = None,
    interpret: Optional[bool] = None,
    outer: bool = False,
) -> jnp.ndarray:
    """Fused ``acc[v] = Σ_{u→v} w_e · feat[u]`` over ``edges`` → [N, D];
    ``outer``: ``Σ_{u→v} w_e · feat[u] feat[u]ᵀ`` → [N, D²] (row major).

    ``interpret`` falsy (None/False) is the production dispatch: compiled
    kernel on TPU, oracle elsewhere.  ``interpret=True`` forces the kernel
    body through the Pallas interpreter on any backend (how tests validate
    it on CPU).  Either way a table the kernels do not take
    (``kernel_takes_gather``: an over-wide feature, or a scalar table too
    large for VMEM) goes to the oracle.  An outer gather runs in the
    row-block kernel from the ``D``-wide table where ``kernel_takes_outer``
    (under the scope ``graphlab.outer``); elsewhere, and off the kernel
    path, it gathers the ``[N, D²]`` table of outer products.
    """
    assert feat.ndim == 2, feat.shape
    e_pad = edges.senders.shape[0]
    with jax.named_scope("graphlab.edge_weight"):
        w = weights.astype(jnp.float32)
        if w.shape[0] != e_pad:
            w = jnp.pad(w, (0, e_pad - w.shape[0]))

    if block_active is None:
        block_active = jnp.ones((edges.n_row_blocks,), jnp.int32)
    on_kernel = bool(interpret) or jax.default_backend() == "tpu"
    n, d = feat.shape
    if outer and on_kernel and kernel_takes_outer(n, d):
        with jax.named_scope("graphlab.outer"), \
                jax.named_scope("graphlab.gather"):
            return gas_gather_combine_pallas(
                feat, w, edges.senders, edges.receivers, edges.n_vertices,
                edges.step_rb, edges.step_eb, block_active,
                interpret=bool(interpret), outer=True)

    with jax.named_scope("graphlab.gather"):
        if outer:
            feat = (feat[:, :, None] * feat[:, None, :]).reshape(n, d * d)
        if not on_kernel or not kernel_takes_gather(
                feat.shape[0], feat.shape[1], edges.n_vertices):
            return gather_combine_ref(
                feat, w, edges.senders, edges.receivers, edges.n_vertices,
                block_active)
        return gas_gather_combine_pallas(
            feat, w, edges.senders, edges.receivers, edges.n_vertices,
            edges.step_rb, edges.step_eb, block_active,
            interpret=bool(interpret))


@dataclasses.dataclass(frozen=True, eq=False)
class ScatterCtx:
    """How an engine wants its reschedule scatter fused: the prepared
    edge subset (the FULL out-edge structure — contributions target every
    neighbor, so per-color subsets are wrong here), optional per-edge
    weights (dynamic-structure engines pass the live edge mask; None means
    all real edges weigh 1), and the Pallas interpret flag."""

    edges: EdgeSet
    weights: Optional[jnp.ndarray] = None   # [E] or [E_pad]; None = ones
    interpret: Optional[bool] = None


def scatter_reschedule(
    contrib: jnp.ndarray,          # [N_src] per-source contribution
    prio: jnp.ndarray,             # [N] current priorities
    consume: jnp.ndarray,          # [N] bool — executed this phase
    edges: EdgeSet,
    weights: Optional[jnp.ndarray] = None,
    *,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Fused ``where(consume, 0, prio) + Σ_{u→v} w_e · contrib[u]`` → [N].

    The scheduler update of a GAS phase (T ← (T \\ executed) ∪ T') without
    the dense per-edge float gather + [N] scatter-add temp.  ``contrib``
    may be longer than ``edges.n_vertices`` (the dist engines index an
    own+ghost contribution table).  Dispatch mirrors ``gather_combine``:
    TPU → Pallas kernel (gas.py), CPU production → jnp oracle,
    ``interpret=True`` → kernel body through the Pallas interpreter.
    """
    e_pad = edges.senders.shape[0]
    if weights is None:
        w = jnp.ones((e_pad,), jnp.float32)   # pads drop via receivers >= n
    else:
        w = weights.astype(jnp.float32)
        if w.shape[0] != e_pad:
            w = jnp.pad(w, (0, e_pad - w.shape[0]))

    if (not interpret and jax.default_backend() != "tpu") or \
            not edge_major_fits(contrib.shape[0], edges.n_vertices, True):
        # off the TPU, and for tables too large to stay in VMEM, the
        # scatter is the jnp oracle (the XLA segment_sum)
        with jax.named_scope("graphlab.scatter"):
            return scatter_reschedule_ref(
                contrib, prio, consume, w, edges.senders, edges.receivers,
                edges.n_vertices)
    # edge-block activity: a block matters only if some edge in it has a
    # contributing source and nonzero weight — bool work, invisible to the
    # float-intermediate accounting the kernel path is measured by
    live = jnp.logical_and(contrib[edges.senders] != 0.0, w != 0.0)
    eblk_active = live.reshape(-1, EDGE_BLOCK).any(axis=1)
    with jax.named_scope("graphlab.scatter"):
        return gas_scatter_reschedule_pallas(
            contrib, prio, consume, w, edges.senders, edges.receivers,
            edges.n_vertices, eblk_active,
            interpret=bool(interpret))
