"""The GraphLab data graph (paper Sec. 3.1), as JAX arrays.

The data graph ``G = (V, E, D)`` stores mutable user data on vertices and
edges over a *static* structure.  On TPU the structure is a pair of index
arrays (``senders``/``receivers``) kept sorted by receiver so that the
``⊕``-combine of gathered messages is a single ``segment_sum`` — the
TPU-native form of the paper's scope reads (DESIGN.md §3.1).

Structure arrays are built on host in numpy (graph ingress is host-side in
any real deployment, cf. paper Sec. 4.1) and handed to engines as device
arrays; they are static for the lifetime of the computation, exactly as the
paper requires ("while the graph data is mutable, the structure is static").
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Pytree = Any


# ---------------------------------------------------------------------------
# Static structure
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True, eq=False)
class GraphStructure:
    """Static directed-edge structure, receiver-sorted.

    ``eq=False``: as jit static metadata the structure compares by object
    identity (dataclass field equality on ndarrays raises in pytree
    metadata checks); engines hold one structure per graph.

    Attributes:
      n_vertices: |V|.
      senders:    [E] int32 — source vertex of each directed edge.
      receivers:  [E] int32 — destination vertex; **non-decreasing**.
      reverse_perm: [E] int32 — index of the reverse edge (r, s) for each
        edge (s, r), or -1 when the reverse edge does not exist.  Needed by
        update functions that write adjacent edges (e.g. LBP messages).
      in_degree / out_degree: [N] int32.
    """

    n_vertices: int
    senders: np.ndarray
    receivers: np.ndarray
    reverse_perm: np.ndarray
    in_degree: np.ndarray
    out_degree: np.ndarray

    @property
    def n_edges(self) -> int:
        return int(self.senders.shape[0])

    # -- constructors -------------------------------------------------------

    @staticmethod
    def from_edges(
        senders: np.ndarray,
        receivers: np.ndarray,
        n_vertices: Optional[int] = None,
        *,
        sort: bool = True,
    ) -> Tuple["GraphStructure", np.ndarray]:
        """Builds a structure from raw edge lists.

        Returns ``(structure, perm)`` where ``perm`` maps *input* edge order
        to the stored (receiver-sorted) order, so callers can permute edge
        data built in input order: ``edata_sorted = edata[perm]``.
        """
        senders = np.asarray(senders, dtype=np.int32)
        receivers = np.asarray(receivers, dtype=np.int32)
        if senders.shape != receivers.shape or senders.ndim != 1:
            raise ValueError("senders/receivers must be equal-length 1D arrays")
        if n_vertices is None:
            n_vertices = int(max(senders.max(initial=-1), receivers.max(initial=-1)) + 1)
        if senders.size and (senders.min() < 0 or receivers.min() < 0):
            raise ValueError("negative vertex ids")
        if senders.size and max(senders.max(), receivers.max()) >= n_vertices:
            raise ValueError("vertex id out of range")

        if sort:
            # receiver-major, sender-minor: receiver blocks are contiguous and
            # deterministic, which the Pallas kernels rely on.  One stable
            # argsort of the combined key orders exactly as
            # lexsort((senders, receivers)), in a fraction of the time.
            perm = np.argsort(
                receivers.astype(np.int64) * n_vertices + senders,
                kind="stable").astype(np.int32)
        else:
            perm = np.arange(senders.size, dtype=np.int32)
        s, r = senders[perm], receivers[perm]

        # Reverse-edge lookup: position of (r, s) among receiver-sorted keys.
        # The queries are searched in sorted order (sequential memory access
        # instead of E random binary searches), then put back.
        key = r.astype(np.int64) * n_vertices + s.astype(np.int64)
        rev_key = s.astype(np.int64) * n_vertices + r.astype(np.int64)
        order = np.argsort(rev_key)
        pos = np.empty(rev_key.size, np.int64)
        pos[order] = np.searchsorted(key, rev_key[order])
        pos = np.clip(pos, 0, max(key.size - 1, 0))
        has_rev = key.size > 0
        if has_rev:
            found = key[pos] == rev_key
            reverse_perm = np.where(found, pos, -1).astype(np.int32)
        else:
            reverse_perm = np.zeros(0, dtype=np.int32)

        in_degree = np.bincount(r, minlength=n_vertices).astype(np.int32)
        out_degree = np.bincount(s, minlength=n_vertices).astype(np.int32)
        return (
            GraphStructure(
                n_vertices=n_vertices,
                senders=s,
                receivers=r,
                reverse_perm=reverse_perm,
                in_degree=in_degree,
                out_degree=out_degree,
            ),
            perm,
        )

    @staticmethod
    def undirected(
        u: np.ndarray, v: np.ndarray, n_vertices: Optional[int] = None
    ) -> Tuple["GraphStructure", np.ndarray]:
        """Builds a symmetric structure from undirected pairs (u, v).

        Every pair is materialized as two directed edges.  The returned perm
        maps the concatenated ``[u→v ; v→u]`` input order to storage order.
        """
        u = np.asarray(u, dtype=np.int32)
        v = np.asarray(v, dtype=np.int32)
        s = np.concatenate([u, v])
        r = np.concatenate([v, u])
        return GraphStructure.from_edges(s, r, n_vertices)

    # -- derived quantities --------------------------------------------------

    def receiver_offsets(self) -> np.ndarray:
        """CSR-style row offsets over the receiver-sorted edge array."""
        return np.concatenate(
            [[0], np.cumsum(np.bincount(self.receivers, minlength=self.n_vertices))]
        ).astype(np.int32)

    def is_symmetric(self) -> bool:
        return bool(self.n_edges == 0 or (self.reverse_perm >= 0).all())

    def validate(self) -> None:
        assert (np.diff(self.receivers) >= 0).all(), "receivers must be sorted"
        assert self.in_degree.sum() == self.n_edges
        assert self.out_degree.sum() == self.n_edges
        ok = self.reverse_perm >= 0
        if ok.any():
            idx = np.nonzero(ok)[0]
            rp = self.reverse_perm[idx]
            assert (self.senders[rp] == self.receivers[idx]).all()
            assert (self.receivers[rp] == self.senders[idx]).all()

    def device_arrays(self) -> Dict[str, jnp.ndarray]:
        return {
            "senders": jnp.asarray(self.senders),
            "receivers": jnp.asarray(self.receivers),
            "reverse_perm": jnp.asarray(self.reverse_perm),
            "in_degree": jnp.asarray(self.in_degree),
            "out_degree": jnp.asarray(self.out_degree),
        }


# ---------------------------------------------------------------------------
# Data graph = structure + mutable data
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DataGraph:
    """Paper Sec. 3.1: ``G = (V, E, D)``.

    ``vertex_data``/``edge_data`` are pytrees whose leaves have leading dim
    |V| / |E| (edge leaves in receiver-sorted order).  The structure is
    metadata (static) so a ``DataGraph`` traces cleanly through jit.
    """

    vertex_data: Pytree
    edge_data: Pytree
    structure: GraphStructure = dataclasses.field(metadata=dict(static=True))

    @property
    def n_vertices(self) -> int:
        return self.structure.n_vertices

    @property
    def n_edges(self) -> int:
        return self.structure.n_edges

    def replace(self, **kw) -> "DataGraph":
        return dataclasses.replace(self, **kw)

    @staticmethod
    def build(
        structure: GraphStructure,
        vertex_data: Pytree,
        edge_data: Pytree = None,
        edge_perm: Optional[np.ndarray] = None,
    ) -> "DataGraph":
        """Builds a DataGraph, permuting edge data into storage order."""

        def _vchk(x):
            x = jnp.asarray(x)
            assert x.shape[0] == structure.n_vertices, (
                f"vertex leaf leading dim {x.shape[0]} != |V|={structure.n_vertices}")
            return x

        def _echk(x):
            x = jnp.asarray(x)
            assert x.shape[0] == structure.n_edges, (
                f"edge leaf leading dim {x.shape[0]} != |E|={structure.n_edges}")
            if edge_perm is not None:
                x = x[jnp.asarray(edge_perm)]
            return x

        vertex_data = jax.tree.map(_vchk, vertex_data)
        edge_data = jax.tree.map(_echk, edge_data) if edge_data is not None else {}
        return DataGraph(vertex_data=vertex_data, edge_data=edge_data,
                         structure=structure)


# ---------------------------------------------------------------------------
# Message-passing primitives (the system's segment ops — DESIGN.md §3.1)
# ---------------------------------------------------------------------------

def segment_combine(
    messages: Pytree,
    receivers: jnp.ndarray,
    n_vertices: int,
    combiner: str = "sum",
    indices_are_sorted: bool = True,
    receivers_np: Optional[np.ndarray] = None,
) -> Pytree:
    """``⊕``-combine per-edge messages into per-vertex accumulators.

    JAX has no CSR SpMM; this segment-op formulation *is* the system's sparse
    layer.  ``combiner`` ∈ {sum, mean, max, min}.  When the caller holds the
    *host* receiver array (static structure) and passes it as
    ``receivers_np``, a sorted 2-D sum whose width the segsum kernel takes
    (a multiple of 128 lanes: ``kernel_takes_width``) dispatches to the
    Pallas kernel on TPU (DESIGN.md §3.5); narrower sums, where the
    kernel's lane padding would multiply the traffic, and everything off
    the TPU stay a segment op.
    """

    def _one(m):
        if combiner == "sum":
            if (receivers_np is not None and indices_are_sorted
                    and m.ndim == 2 and jax.default_backend() == "tpu"):
                from repro.kernels.segsum.ops import (kernel_takes_width,
                                                      segment_sum_sorted)
                if kernel_takes_width(m.shape[1]):
                    return segment_sum_sorted(m, receivers_np, n_vertices)
            return jax.ops.segment_sum(
                m, receivers, n_vertices, indices_are_sorted=indices_are_sorted)
        if combiner == "mean":
            s = jax.ops.segment_sum(
                m, receivers, n_vertices, indices_are_sorted=indices_are_sorted)
            c = jax.ops.segment_sum(
                jnp.ones(m.shape[0], m.dtype), receivers, n_vertices,
                indices_are_sorted=indices_are_sorted)
            c = jnp.maximum(c, 1).reshape((-1,) + (1,) * (m.ndim - 1))
            return s / c
        if combiner == "max":
            return jax.ops.segment_max(
                m, receivers, n_vertices, indices_are_sorted=indices_are_sorted)
        if combiner == "min":
            return jax.ops.segment_min(
                m, receivers, n_vertices, indices_are_sorted=indices_are_sorted)
        raise ValueError(f"unknown combiner {combiner!r}")

    return jax.tree.map(_one, messages)


def gather_scope(
    graph: DataGraph,
) -> Tuple[Pytree, Pytree, Pytree]:
    """Materializes per-edge views of the scope: (edge, src vertex, dst vertex).

    This is the read half of the paper's scope ``S_v`` (Fig. 2(a)): an update
    at v may read its own data, adjacent edges and adjacent vertices.
    """
    s = jnp.asarray(graph.structure.senders)
    r = jnp.asarray(graph.structure.receivers)
    src_v = jax.tree.map(lambda x: x[s], graph.vertex_data)
    dst_v = jax.tree.map(lambda x: x[r], graph.vertex_data)
    return graph.edge_data, src_v, dst_v


def scatter_to_neighbors(
    values: jnp.ndarray,
    structure: GraphStructure,
    direction: str = "out",
) -> jnp.ndarray:
    """Scatters per-vertex scalars along edges to neighbors (scheduling ∪T').

    ``direction='out'``: each vertex v adds ``values[v]`` to every out-
    neighbor (paper: v schedules the vertices it points at);
    ``'in'`` uses in-edges; ``'both'`` uses the symmetrized structure.
    """
    s = jnp.asarray(structure.senders)
    r = jnp.asarray(structure.receivers)
    out = jnp.zeros(structure.n_vertices, values.dtype)
    if direction in ("out", "both"):
        out = out + jax.ops.segment_sum(values[s], r, structure.n_vertices,
                                        indices_are_sorted=True)
    if direction in ("in", "both"):
        out = out + jax.ops.segment_sum(values[r], s, structure.n_vertices)
    return out
