"""Update functions (paper Sec. 3.2) in gather/apply/scatter form.

A GraphLab update function ``f(v, S_v) -> (S_v, T)`` reads the scope of a
vertex, writes its own vertex data and adjacent edge data, and schedules
future work.  On TPU we decompose ``f`` structurally (DESIGN.md §3.1):

  gather   : per-edge message from (edge data, src vertex, dst vertex)
  combine  : ⊕ over in-edges (segment op)
  apply    : new vertex data + a scalar *residual* from (vertex, accumulator)
  edge_out : optional — new data for adjacent edges (LBP messages live here)
  priority : residual -> priority contribution scattered to neighbors (T')

The decomposition *enforces* the edge consistency model: writes are limited
to the central vertex and adjacent edges, reads to the scope.  Programs that
need full consistency declare it via ``consistency`` and the engines run
them under a distance-2 coloring / distance-2 exclusion instead.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.consistency import Consistency

Pytree = Any


# ---------------------------------------------------------------------------
# Fuseable gather registry (DESIGN.md §3.5)
# ---------------------------------------------------------------------------

#: The registry of gather shapes the fused GAS kernel can compute in-kernel.
#: Every kind reduces to ``acc[v] = Σ_{u→v} w_e · m(u)`` for a per-vertex
#: feature table and a per-edge scalar weight — the pieces the kernel
#: consumes without ever materializing the [E, D] messages:
#:   weighted_src_sum      m = feature(u), w_e = ``weight(edge_data)``
#:   src_copy              m = feature(u), w_e = 1
#:   degree_normalized_src m = feature(u), w_e = 1 / max(out_degree(u), 1)
#:   weighted_src_outer    m = feature(u) feature(u)ᵀ for an [N, d] feature
#:                         (accumulator [N, d, d]), w_e = ``weight(...)``
FUSED_GATHER_KINDS = ("weighted_src_sum", "src_copy", "degree_normalized_src",
                      "weighted_src_outer")
WEIGHTED_KINDS = ("weighted_src_sum", "weighted_src_outer")


class FusedGather(NamedTuple):
    """Declares one ``gather`` output leaf as a registry op.

    ``feature`` maps vertex data to a per-vertex array ``[N, ...]`` (any
    trailing shape — it is flattened for the kernel and restored on the
    accumulator; ``[N, d]`` for ``weighted_src_outer``); ``weight`` maps
    edge data to a per-edge scalar ``[E]`` (the ``WEIGHTED_KINDS`` only).
    The declaration must compute exactly what ``gather`` computes —
    engines fuse it, tests cross-check the two.
    """

    kind: str
    feature: Callable[[Pytree], jnp.ndarray]
    weight: Optional[Callable[[Pytree], jnp.ndarray]] = None


def fused_gather_leaves(program) -> Optional[Tuple[list, Any]]:
    """Flattens ``program.fused_gather()`` into (leaves, treedef), validating
    each leaf against the registry; None when the program stays dense."""
    spec = program.fused_gather()
    if spec is None:
        return None
    leaves, treedef = jax.tree.flatten(
        spec, is_leaf=lambda x: isinstance(x, FusedGather))
    for leaf in leaves:
        if not isinstance(leaf, FusedGather):
            raise TypeError(f"fused_gather leaves must be FusedGather, "
                            f"got {type(leaf).__name__}")
        if leaf.kind not in FUSED_GATHER_KINDS:
            raise ValueError(f"unknown fused gather kind {leaf.kind!r} "
                             f"(registry: {FUSED_GATHER_KINDS})")
        if leaf.kind in WEIGHTED_KINDS and leaf.weight is None:
            raise ValueError(f"{leaf.kind} needs a weight fn")
    return leaves, treedef


def supports_fused_gather(program) -> bool:
    """The fallback rule: a program runs the fused GAS path iff it declares
    registry gathers, ⊕ is sum, and it never writes adjacent edges (edge
    writes both mutate the weights' source data and need the dense ctx)."""
    return (program.combiner == "sum" and not program.has_edge_out
            and program.fused_gather() is not None)


def fused_edge_weight(leaf: FusedGather, edge_data: Pytree, n_edges: int,
                      src_deg: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Per-edge scalar weight [E] for a registry leaf (f32).

    ``src_deg`` (out-degree of each edge's source) is only consulted by
    ``degree_normalized_src`` — callers materialize it lazily so the common
    weighted/copy kinds never pay an O(E) degree gather."""
    if leaf.kind in WEIGHTED_KINDS:
        return leaf.weight(edge_data).astype(jnp.float32)
    if leaf.kind == "src_copy":
        return jnp.ones(n_edges, jnp.float32)
    if leaf.kind == "degree_normalized_src":
        assert src_deg is not None, "degree_normalized_src needs src_deg"
        return 1.0 / jnp.maximum(src_deg.astype(jnp.float32), 1.0)
    raise ValueError(leaf.kind)


class EdgeCtx(NamedTuple):
    """Per-edge context handed to ``gather`` / ``edge_out``."""

    edata: Pytree          # this directed edge's data
    rev_edata: Pytree      # reverse edge's data (or zeros if absent)
    src: Pytree            # source vertex data
    dst: Pytree            # destination vertex data
    src_deg: jnp.ndarray   # [E] out-degree of source
    dst_deg: jnp.ndarray   # [E] in-degree of destination


class ApplyOut(NamedTuple):
    vertex_data: Pytree     # new data for the central vertex
    residual: jnp.ndarray   # [N] — drives adaptive scheduling (|ΔR| etc.)


class VertexProgram:
    """Base class for GraphLab programs.  All methods are batched over arrays.

    Subclasses override the pieces they need; the defaults give an identity
    program.  ``combiner`` is the ⊕ of the paper's sync/gather semantics.
    """

    combiner: str = "sum"
    consistency: Consistency = Consistency.EDGE
    # When True the engines scatter each vertex's residual to its neighbors'
    # priorities (the adaptive "schedule neighbors on big change" pattern of
    # Alg. 1).  Programs can instead override ``schedule`` for custom T'.
    schedule_neighbors: bool = True

    # -- gather ---------------------------------------------------------------
    def gather(self, ctx: EdgeCtx) -> Pytree:
        """Per-edge message; combined with ``combiner`` into acc[dst]."""
        raise NotImplementedError

    def fused_gather(self) -> Optional[Pytree]:
        """Optional: declare ``gather`` as a pytree of ``FusedGather``
        registry ops (same tree structure as the gather output).  Engines
        then run the fused GAS kernel path — per-edge messages are computed
        inside the kernel and inactive row blocks are skipped — instead of
        materializing ``edge_ctx``.  None (default) keeps the dense path."""
        return None

    def zero_acc(self, vertex_data: Pytree) -> Pytree:
        """Accumulator for isolated vertices (segment_sum default: zeros)."""
        return None  # None -> engines use segment-op natural zero

    # -- apply ---------------------------------------------------------------
    def apply(self, vertex_data: Pytree, acc: Pytree,
              glob: Pytree = None) -> ApplyOut:
        """``glob`` carries the sync operation's global values (Sec. 3.5):
        update functions may *read* globals; only sync ops write them."""
        raise NotImplementedError

    # -- optional edge writes (adjacent-edge mutation, e.g. BP messages) -----
    has_edge_out: bool = False

    # Whether gather/edge_out read ``ctx.rev_edata``.  None (default) means
    # "if has_edge_out" — BP-style programs read the reverse message, pure
    # gather programs don't.  The distributed engine uses this to decide
    # whether reverse edges need ghost caches (dist/engine.py); a program
    # that reads rev_edata without declaring it gets zeros there, so
    # declare it.  Shared-memory engines always supply real rev_edata.
    reads_rev_edata: Optional[bool] = None

    def edge_out(self, ctx: EdgeCtx, new_src: Pytree, src_acc: Pytree) -> Pytree:
        """New data for edge (src -> dst), given src's freshly applied data
        and src's accumulator.  Only edges whose *source* vertex was updated
        are written back (the update at v owns its adjacent edges)."""
        raise NotImplementedError

    # -- scheduling -----------------------------------------------------------
    def priority(self, residual: jnp.ndarray) -> jnp.ndarray:
        """Priority contribution scattered to neighbors of updated vertices."""
        return residual

    # -- init -----------------------------------------------------------------
    def initial_priority(self, n_vertices: int) -> jnp.ndarray:
        return jnp.ones(n_vertices, jnp.float32)


def edge_ctx(graph) -> EdgeCtx:
    """Builds the per-edge context from a DataGraph (reads only)."""
    st = graph.structure
    s = jnp.asarray(st.senders)
    r = jnp.asarray(st.receivers)
    rp = jnp.asarray(st.reverse_perm)
    rp_safe = jnp.maximum(rp, 0)
    has_rev = (rp >= 0)

    def _rev(x):
        y = x[rp_safe]
        mask = has_rev.reshape((-1,) + (1,) * (y.ndim - 1))
        return jnp.where(mask, y, jnp.zeros_like(y))

    return EdgeCtx(
        edata=graph.edge_data,
        rev_edata=jax.tree.map(_rev, graph.edge_data),
        src=jax.tree.map(lambda x: x[s], graph.vertex_data),
        dst=jax.tree.map(lambda x: x[r], graph.vertex_data),
        src_deg=jnp.asarray(st.out_degree)[s],
        dst_deg=jnp.asarray(st.in_degree)[r],
    )


def masked_update(old: Pytree, new: Pytree, mask: jnp.ndarray) -> Pytree:
    """where(mask, new, old) broadcast over trailing dims of each leaf."""

    def _one(o, n):
        m = mask.reshape((-1,) + (1,) * (o.ndim - 1))
        return jnp.where(m, n.astype(o.dtype), o)

    return jax.tree.map(_one, old, new)
