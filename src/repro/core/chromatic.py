"""The Chromatic Engine (paper Sec. 4.2.1).

Given a proper coloring of the data graph, executing all scheduled vertices
of one color simultaneously satisfies the edge consistency model; the sweep
over colors is a sequence of **color-steps** (the paper's analogy to BSP
super-steps).  Full consistency uses a distance-2 coloring, vertex
consistency a single color — we obtain all three by "simply changing how the
vertices are colored".

On TPU a color-step is a masked dense update of the vertex array; the
communication barrier between color-steps is XLA program order (ghost
exchange is the sharded all-gather XLA inserts — see launch/spmd path).
Within a color-step, updates read the freshest data (Gauss-Seidel across
colors), which is what buys the asynchronous convergence behaviour of
Fig. 1(a) relative to the Jacobi BSP engine.

Fused GAS path (DESIGN.md §3.5): for fuseable programs each color owns a
**per-color edge range** — the receiver-sorted edges whose receiver has
that color, precomputed on host — so a color-step streams only E_c edges
(Σ_c E_c = E per sweep) instead of gathering all E edges ``num_colors``
times, and the active-block bitmap prunes further as the scheduler drains.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax.numpy as jnp
import numpy as np

from repro.core.coloring import coloring_for, verify_coloring
from repro.core.engine_base import Engine
from repro.core.graph import DataGraph
from repro.core.scheduler import SweepScheduler
from repro.core.sync_op import SyncOp
from repro.core.update import VertexProgram
from repro.kernels.gas.gas import EDGE_BLOCK, ROW_BLOCK
from repro.kernels.gas.ops import (EdgeSet, color_runs, size_class,
                                   split_by_color, stack_edge_sets)
from repro.obs.timeline import span


def steps_bound(n_edges: int, n_vertices: int) -> int:
    """The longest kernel grid (``csr_steps``) of ``n_edges``
    receiver-sorted edges: a step per row block, and one more where an
    edge block crosses into the next row block.  A schedule is shorter
    by every row-block boundary that falls on an edge-block boundary,
    which depends on the vertex labels; padded to this length, every
    labelling of a graph gives the same step program."""
    return max(-(-n_vertices // ROW_BLOCK), 1) \
        + max(-(-n_edges // EDGE_BLOCK) - 1, 0)


class ChromaticEngine(Engine):
    """One engine step = one sweep, one ``SweepScheduler`` phase per color
    (paper: T is drained color by color; the sync operation runs safely
    between color-steps)."""

    def __init__(
        self,
        program: VertexProgram,
        graph: DataGraph,
        colors: Optional[np.ndarray] = None,
        tolerance: float = 1e-3,
        sync_ops: Sequence[SyncOp] = (),
        *,
        use_fused: Optional[bool] = None,
        gas_interpret: Optional[bool] = None,
        stream_tables=None,
        residual_dtype=None,
        spare_colors: int = 0,
    ):
        with span("graphlab.coloring"):
            if colors is None:
                colors = coloring_for(graph.structure, program.consistency)
            colors = np.asarray(colors, dtype=np.int32)
            radius = program.consistency.exclusion_radius
            if radius >= 1 and not verify_coloring(graph.structure, colors,
                                                   radius):
                raise ValueError(
                    f"coloring does not satisfy {program.consistency} "
                    f"(radius {radius})")
        super().__init__(
            program, graph, tolerance, sync_ops,
            scheduler=SweepScheduler(program, graph.structure, tolerance,
                                     colors, spare_colors=spare_colors),
            use_fused=use_fused, gas_interpret=gas_interpret,
            stream_tables=stream_tables, residual_dtype=residual_dtype)
        self.colors = self.scheduler.colors
        self.num_colors = self.scheduler.num_phases

    def _phase_edge_sets(self):
        """Per-color edge ranges (DESIGN.md §3.5).  A color-step gathers
        over the receiver-sorted edges whose *receiver* has that color, and
        its reschedule deposits along the edges whose *sender* has it: only
        that color's vertices execute, so every other sender contributes
        exactly zero.  Σ_c E_c = E for each, per sweep.  Each set is padded
        to a power-of-two block count, and runs of consecutive colors of
        one size class share a grid length, so they stack into one loop.
        A color's gather edges sit in the full edge arrays in another
        order: each run's perms into them (``[colors, E_pad]``, padded
        with 0) come back beside its sets, for ``prepare_weights``.

        Streaming mode has none: the dynamic-tables path streams the full
        capacity edge set each phase (the color mask gates the
        write-back), since color membership of *edges* goes stale as
        deltas land.  The live coloring rides the dynamic tables instead —
        delta edges joining same-colored vertices are repaired at
        apply_delta time (DESIGN §3.12), so edge consistency holds between
        regrows too."""
        st = self.structure
        n, k = st.n_vertices, self.scheduler.num_phases
        colors = np.asarray(self.scheduler.colors)
        subsets = {"gather": split_by_color(colors[st.receivers], k),
                   "scatter": split_by_color(colors[st.senders], k)}
        blocks = {kind: [size_class(i.size) for i in idx]
                  for kind, idx in subsets.items()}
        groups = color_runs(blocks)

        stacked, perms = [], []
        for first, count in groups:
            run = range(first, first + count)
            sets = [{} for _ in run]
            for kind, idx in subsets.items():
                nb = blocks[kind][first]
                n_steps = max(steps_bound(idx[c].size, n) for c in run)
                for j, c in enumerate(run):
                    sets[j][kind] = EdgeSet.build(
                        st.senders[idx[c]], st.receivers[idx[c]], n,
                        min_blocks=nb, n_steps=n_steps)
            stacked.append(stack_edge_sets(sets))
            idx = subsets["gather"]
            e_pad = sets[0]["gather"].senders.shape[0]
            perms.append(jnp.asarray(np.stack(
                [np.pad(idx[c], (0, e_pad - idx[c].size)) for c in run])))
        return groups, stacked, perms
