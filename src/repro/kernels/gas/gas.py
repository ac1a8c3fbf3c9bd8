"""Pallas TPU kernels: fused gather⊕combine (GAS) and scatter/reschedule.

The engines' hot path (paper Sec. 3.2/4.2) is ``acc[v] = ⊕_{u→v} w_e ·
f(u)``: gather a per-edge message from the source vertex, ⊕-combine into the
receiver.  The dense path materializes the ``[E, D]`` messages array in HBM;
these kernels form each message in VMEM and never write it out.  Two
layouts, chosen by the size of what must stay on chip:

  - **edge-major, scalar tables** (``D == 1`` — PageRank's ranks, every
    scheduler scatter — while table and output fit in
    ``RESIDENT_BYTES`` of VMEM).  Both are packed 128 vertices to a row
    (vertex ``u`` is lane ``u % 128`` of row ``u // 128``) and stay
    resident for the whole call; the grid walks the edge blocks once, and
    each edge is a dynamic one-row load of its sender's row, a lane
    select, and a read-modify-write of its receiver's row.  Cost is per
    edge, with nothing per row block: a color-step of the chromatic engine
    whose edges are spread thinly over all row blocks pays only for its
    edges.  Messages are formed and summed on the VPU in f32.
  - **row-block, wide tables** (``1 < D <= MAX_FEAT``).  Edges are
    receiver-sorted, so each ``ROW_BLOCK``-row output
    block owns a contiguous run of edge blocks; the grid is one step per
    (row block, edge block) pair of that run (``csr_steps``), so a
    power-law hub's run costs only its own steps.  The table is split into
    128-lane planes; each step stages its block's sender rows into VMEM —
    from a VMEM-resident table by dynamic one-row loads, or from HBM with
    every row DMA of the block in flight at once — scales them by the edge
    weights, and combines them with the one-hot matmul ``onehot[RB, EB] @
    msgs[EB, 128]`` at ``Precision.HIGHEST`` (the one-hot factor is exact,
    so f32 messages are summed without a bf16 rounding; at the default
    precision the v5e rounds them to bf16).
  - **row-block, outer products** (``outer=True``: ``acc[v] = Σ_{u→v}
    w_e · f(u) f(u)ᵀ`` from a ``[N, d]`` table, ALS's ``x xᵀ``).  The
    ``d``-wide table stays resident, one vertex to a row; each step
    stages its block's sender rows by dynamic one-row loads, expands
    them to ``x_i`` and ``x_j`` at every lane ``i·d + j`` of the
    ``d²``-wide message by two exact 0/1 matmuls (each in three bf16
    passes over an exact three-piece split of the rows), multiplies the
    two on the VPU (each product rounded once, as an ``[N, d²]`` table
    would hold it), and combines the messages with the same one-hot
    matmul, the edge weights folded into its columns.  The derived table is
    never built, and no row is fetched by DMA (``kernel_takes_outer``).

Scalar tables too large to stay resident, and features wider than
``MAX_FEAT``, keep the dense XLA path (``kernel_takes_gather``,
``edge_major_fits``).  Both entry points are jitted, so a step that calls
a kernel many times with one shape (the chromatic engine's color-steps,
whose edge sets are padded to power-of-two block counts) lowers and
compiles it once.  Both kernels stream each edge block's senders and
weights into SMEM as blocks, so SMEM holds a schedule and two edge
blocks, never an ``[E]`` array.  The gather skips work for row blocks
with no scheduled vertex (``block_active``) and returns exact zeros
there; the scatter skips edge blocks with no contributing source.
One-dimensional per-edge blocks are ``EDGE_BLOCK = 1024`` long, the TPU's
tile for 1-D arrays (``T(1024)``); ``ops.EdgeSet`` pads edge arrays to it.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROW_BLOCK = 128
EDGE_BLOCK = 1024
LANES = 128
MAX_FEAT = 1024                    # widest padded feature the kernel takes
RESIDENT_BYTES = 48 << 20          # VMEM a call may keep resident
_UNROLL = 8
_HIGHEST = jax.lax.Precision.HIGHEST


def csr_steps(receivers: np.ndarray, n_rows: int,
              n_steps: Optional[int] = None) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side flattened grid over receiver-sorted, ``EDGE_BLOCK``-padded
    edges: ``(step_rb, step_eb)``, one step per (row block, edge block)
    that can hold edges of that row block, in row-block order.

    Every row block gets at least one step (an empty one is pointed at a
    clamped edge block whose receivers all lie outside it), so every
    output row is written.  ``n_steps`` pads the schedule to a fixed
    length — the distributed engine shares one grid across machines —
    with steps that repeat the last row block and carry edge block -1,
    which the kernels skip."""
    receivers = np.asarray(receivers)
    n_eblk = max(-(-receivers.size // EDGE_BLOCK), 1)
    n_rb = max(-(-n_rows // ROW_BLOCK), 1)
    pos = np.searchsorted(receivers, np.arange(n_rb + 1) * ROW_BLOCK)
    start = np.minimum(pos[:-1] // EDGE_BLOCK, n_eblk - 1)
    end = np.minimum(np.maximum(-(-pos[1:] // EDGE_BLOCK), start + 1), n_eblk)
    count = end - start
    step_rb = np.repeat(np.arange(n_rb), count)
    first = np.repeat(np.cumsum(count) - count, count)
    step_eb = np.repeat(start, count) + np.arange(step_rb.size) - first
    if n_steps is not None:
        pad = n_steps - step_rb.size
        assert pad >= 0, (n_steps, step_rb.size)
        step_rb = np.concatenate([step_rb, np.full(pad, n_rb - 1)])
        step_eb = np.concatenate([step_eb, np.full(pad, -1)])
    return step_rb.astype(np.int32), step_eb.astype(np.int32)


def _round_up(x: int, m: int) -> int:
    return max(-(-x // m), 1) * m


def _pack_scalars(x: jnp.ndarray, rows: int) -> jnp.ndarray:
    """[N] → lane-dense [rows, 128]: entry u at row u // 128, lane
    u % 128."""
    x = x.reshape(-1)
    return jnp.pad(x, (0, rows * LANES - x.shape[0])).reshape(rows, LANES)


def _planes(feat: jnp.ndarray) -> jnp.ndarray:
    """[N, D] → [D_pad / 128, N_pad, 128]: one 128-lane plane per lane tile,
    so each staged piece of a vertex row is one whole row of a plane (a
    one-row slice across several lane tiles is not a legal DMA or dynamic
    load on the TPU).  D == 1 packs 128 vertices per row instead."""
    n, d = feat.shape
    feat = feat.astype(jnp.float32)
    if d == 1:
        return _pack_scalars(feat, _round_up(-(-n // LANES), 8))[None]
    d_pad = _round_up(d, LANES)
    feat = jnp.pad(feat, ((0, _round_up(n, 8) - n), (0, d_pad - d)))
    return feat.reshape(-1, d_pad // LANES, LANES).transpose(1, 0, 2)


def _outer_table(feat: jnp.ndarray) -> jnp.ndarray:
    """[N, d] → [1, N_pad, 128]: one vertex to a row, for the outer
    product path (also at d == 1, which ``_planes`` would pack)."""
    n, d = feat.shape
    return jnp.pad(feat.astype(jnp.float32),
                   ((0, _round_up(n, 8) - n), (0, LANES - d)))[None]


def _expansions(d: int, n_planes: int) -> np.ndarray:
    """[2, n_planes, 128, 128] 0/1 maps of a ``d``-wide row to the
    ``d²``-wide message: ``S @ E[0]`` puts ``x_i`` and ``S @ E[1]`` puts
    ``x_j`` at lane ``i·d + j`` (plane ``lane // 128``); lanes past ``d²``
    stay zero."""
    lane = np.arange(n_planes * LANES)
    real = lane < d * d
    e = np.zeros((2, LANES, n_planes * LANES), np.float32)
    e[0, (lane // d)[real], lane[real]] = 1.0
    e[1, (lane % d)[real], lane[real]] = 1.0
    return e.reshape(2, LANES, n_planes, LANES).transpose(0, 2, 1, 3)


def _row_block_kernel(*, n_planes: int, resident: bool, outer: bool = False):
    """Row-block gather⊕combine body over an ``n_planes``-plane table, or,
    ``outer``, over the outer products of a one-plane table's rows into
    ``n_planes`` planes."""

    def kernel(srb, seb, act,             # scalar prefetch [T], [T], [RB's]
               snd, w,                    # SMEM [EB]; w VMEM [EB] if outer
               recv,                      # VMEM block [EB]
               tab,                       # VMEM-resident or HBM table
               *refs):                    # [expansions,] out, scratch
        *expand, out, stage, acc, sem = refs
        t = pl.program_id(0)
        n_t = pl.num_programs(0)
        rb, eb = srb[t], seb[t]
        first = (t == 0) | (srb[jnp.maximum(t - 1, 0)] != rb)
        last = (t == n_t - 1) | (srb[jnp.minimum(t + 1, n_t - 1)] != rb)

        @pl.when(first)
        def _init():
            acc[...] = jnp.zeros_like(acc)

        def matmul(a, b):
            return jax.lax.dot_general(
                a, b, (((1,), (0,)), ((), ())),
                precision=_HIGHEST, preferred_element_type=jnp.float32)

        def onehot(value):
            # ⊕-combine matrix: padding edges carry receivers outside every
            # row block, so their column is all zero
            local = recv[...] - rb * ROW_BLOCK
            rows = jax.lax.broadcasted_iota(jnp.int32,
                                            (ROW_BLOCK, EDGE_BLOCK), 0)
            return jnp.where(rows == local[None, :], value, 0.0)

        def accumulate_outer():
            def load(q, carry):
                for u in range(_UNROLL):
                    r = q * _UNROLL + u
                    stage[0, pl.ds(r, 1), :] = tab[0, pl.ds(snd[r], 1), :]
                return carry

            jax.lax.fori_loop(0, EDGE_BLOCK // _UNROLL, load, 0)
            # x = hi + mid + lo exactly, 8 of f32's 24 significant bits a
            # piece: three bf16 passes against the 0/1 maps place x's
            # entries exactly (HIGHEST would take six)
            x = stage[0]
            hi = x.astype(jnp.bfloat16)
            rest = x - hi.astype(jnp.float32)
            mid = rest.astype(jnp.bfloat16)
            lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)

            def spread(e):
                def mm(piece):
                    return jax.lax.dot_general(
                        piece, e, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                return (mm(hi) + mm(mid)) + mm(lo)

            combine = onehot(w[...][None, :])     # the weights in its columns
            for c in range(n_planes):
                xi = spread(expand[0][0, c])
                xj = spread(expand[0][1, c])
                acc[c] += matmul(combine, xi * xj)

        @pl.when((eb >= 0) & (act[rb] > 0))
        def _accumulate():
            if outer:
                return accumulate_outer()
            if not resident:
                # every row DMA of the block in flight on one semaphore
                def copy(r, c):
                    return pltpu.make_async_copy(
                        tab.at[c, pl.ds(snd[r], 1), :],
                        stage.at[c, pl.ds(r, 1), :], sem.at[0])

                def start(r, carry):
                    for c in range(n_planes):
                        copy(r, c).start()
                    return carry

                def wait(r, carry):
                    for c in range(n_planes):
                        copy(r, c).wait()
                    return carry

                jax.lax.fori_loop(0, EDGE_BLOCK, start, 0)
                jax.lax.fori_loop(0, EDGE_BLOCK, wait, 0)

            # message formation: staged row × edge weight
            def form(q, carry):
                for u in range(_UNROLL):
                    r = q * _UNROLL + u
                    for c in range(n_planes):
                        if resident:
                            v = tab[c, pl.ds(snd[r], 1), :]
                        else:
                            v = stage[c, pl.ds(r, 1), :]
                        stage[c, pl.ds(r, 1), :] = v * w[r]
                return carry

            jax.lax.fori_loop(0, EDGE_BLOCK // _UNROLL, form, 0)

            combine = onehot(1.0)
            for c in range(n_planes):
                acc[c] += matmul(combine, stage[c])

        @pl.when(last)
        def _flush():
            for c in range(n_planes):
                out[:, c * LANES:(c + 1) * LANES] = acc[c]

    return kernel


def _row_block_call(table, weights, senders, receivers, step_rb, step_eb,
                    block_active, *, n_out_rows, expand=None, interpret=False):
    """Runs the row-block kernel over a prepared ``_planes`` table, or,
    given ``expand`` (``_expansions``), over the outer products of an
    ``_outer_table``'s rows."""
    e_pad, = weights.shape
    assert e_pad % EDGE_BLOCK == 0, (e_pad,)
    outer = expand is not None
    n_planes = expand.shape[1] if outer else table.shape[0]
    width = n_planes * LANES
    table_bytes = table.size * table.dtype.itemsize
    resident = table_bytes <= RESIDENT_BYTES
    assert resident or not outer, (table_bytes, "outer tables stay resident")

    def eblk(t, srb, seb, act):
        return (jnp.maximum(seb[t], 0),)

    smem = pl.BlockSpec((EDGE_BLOCK,), eblk, memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec((EDGE_BLOCK,), eblk)
    return pl.pallas_call(
        _row_block_kernel(n_planes=n_planes, resident=resident, outer=outer),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(step_rb.shape[0],),
            in_specs=[
                smem, vmem if outer else smem, vmem,
                pl.BlockSpec(memory_space=pltpu.VMEM if resident else pl.ANY),
            ] + [pl.BlockSpec(memory_space=pltpu.VMEM)] * outer,
            out_specs=pl.BlockSpec((ROW_BLOCK, width),
                                   lambda t, srb, seb, act: (srb[t], 0)),
            scratch_shapes=[
                pltpu.VMEM((table.shape[0], EDGE_BLOCK, LANES), jnp.float32),
                pltpu.VMEM((n_planes, ROW_BLOCK, LANES), jnp.float32),
                pltpu.SemaphoreType.DMA((1,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((n_out_rows, width), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=(table_bytes if resident else 0) + (24 << 20)),
        interpret=interpret,
    )(step_rb.astype(jnp.int32), step_eb.astype(jnp.int32),
      block_active.astype(jnp.int32), senders.astype(jnp.int32),
      weights.astype(jnp.float32), receivers.astype(jnp.int32), table,
      *([jnp.asarray(expand, jnp.bfloat16)] if outer else []))


def _edge_major_kernel(*, reschedule: bool):
    """Edge-major body over packed scalar tables, all VMEM-resident."""

    def kernel(act,                       # scalar prefetch [n edge blocks]
               snd, w, recv,              # SMEM blocks [EB]
               tab, *rest):               # resident [rows, 128] arrays
        out = rest[-1]

        @pl.when(pl.program_id(0) == 0)
        def _init():
            if reschedule:
                # T ← (T \ executed) ∪ T': winners consume their priority
                prio, cons = rest[0], rest[1]
                out[...] = jnp.where(cons[...] > 0, 0.0, prio[...])
            else:
                out[...] = jnp.zeros_like(out)

        @pl.when(act[pl.program_id(0)] > 0)
        def _edges():
            lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

            def body(q, carry):
                for u in range(_UNROLL):
                    r = q * _UNROLL + u
                    src, dst = snd[r], recv[r]
                    row = tab[pl.ds(src // LANES, 1), :]
                    msg = jnp.sum(jnp.where(lane == src % LANES, row, 0.0),
                                  axis=1, keepdims=True) * w[r]
                    here = pl.ds(dst // LANES, 1)
                    out[here, :] = out[here, :] + jnp.where(
                        lane == dst % LANES, msg, 0.0)
                return carry

            jax.lax.fori_loop(0, EDGE_BLOCK // _UNROLL, body, 0)

    return kernel


def _edge_major_rows(n_rows: int) -> int:
    """Packed output rows: every receiver, padding receivers
    (``n_rows + ROW_BLOCK``) included, rounded up to a whole tile."""
    return _round_up((n_rows + ROW_BLOCK) // LANES + 1, 8)


def _edge_major_call(table, weights, senders, receivers, block_act, *,
                     n_out_rows, reschedule=None, interpret=False):
    e_pad, = weights.shape
    assert e_pad % EDGE_BLOCK == 0, (e_pad,)
    resident = [table] + list(reschedule or ())
    nbytes = sum(x.size * 4 for x in resident) + n_out_rows * LANES * 4
    assert nbytes <= RESIDENT_BYTES, (nbytes, "too large to stay resident")
    eblk = pl.BlockSpec((EDGE_BLOCK,), lambda b, act: (b,),
                        memory_space=pltpu.SMEM)
    return pl.pallas_call(
        _edge_major_kernel(reschedule=reschedule is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(e_pad // EDGE_BLOCK,),
            in_specs=[eblk, eblk, eblk] + [
                pl.BlockSpec(memory_space=pltpu.VMEM)] * len(resident),
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        ),
        out_shape=jax.ShapeDtypeStruct((n_out_rows, LANES), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=nbytes + (16 << 20)),
        interpret=interpret,
    )(block_act.astype(jnp.int32), senders.astype(jnp.int32),
      weights.astype(jnp.float32), receivers.astype(jnp.int32), *resident)


def edge_major_fits(n_src: int, n_rows: int, reschedule: bool) -> bool:
    """Whether a scalar (``D == 1``) table of ``n_src`` rows and an output
    of ``n_rows`` stay resident for the edge-major kernel."""
    src_rows = _round_up(-(-n_src // LANES), 8)
    out_rows = _edge_major_rows(n_rows)
    return (src_rows + out_rows * (3 if reschedule else 1)) * LANES * 4 \
        <= RESIDENT_BYTES


def kernel_takes_outer(n_src: int, d: int) -> bool:
    """The dispatch rule of the fused outer-product gather: the row-block
    kernel forms the ``d²``-wide messages itself while the ``d``-wide
    table, one vertex to a row, stays resident and ``d²`` fits
    ``MAX_FEAT`` lanes; otherwise callers gather a ``[N, d²]`` table."""
    return (_round_up(d * d, LANES) <= MAX_FEAT
            and _round_up(n_src, 8) * LANES * 4 <= RESIDENT_BYTES)


def kernel_takes_gather(n_src: int, d: int, n_rows: int) -> bool:
    """The dispatch rule of the fused gather: scalar tables while the
    edge-major kernel keeps them resident, wider ones up to ``MAX_FEAT``
    lanes through the row-block kernel; anything else stays on the dense
    XLA path."""
    if d == 1:
        return edge_major_fits(n_src, n_rows, reschedule=False)
    return _round_up(d, LANES) <= MAX_FEAT


@functools.partial(jax.jit,
                   static_argnames=("n_rows", "interpret", "outer"))
def gas_gather_combine_pallas(
    feat: jnp.ndarray,         # [N, D] source-feature table
    weights: jnp.ndarray,      # [E_pad] f32, pad rows 0
    senders: jnp.ndarray,      # [E_pad] i32, pad rows 0
    receivers: jnp.ndarray,    # [E_pad] i32 sorted, pad rows >= n + ROW_BLOCK
    n_rows: int,
    step_rb: jnp.ndarray,      # [T] i32 (csr_steps)
    step_eb: jnp.ndarray,      # [T] i32
    block_active: jnp.ndarray,  # [n_row_blocks] i32 bitmap
    interpret: bool = False,
    outer: bool = False,
) -> jnp.ndarray:
    """``acc[v] = Σ_{u→v} w_e · feat[u]`` → [n_rows, D] (feat's dtype), or,
    ``outer``, ``Σ_{u→v} w_e · feat[u] feat[u]ᵀ`` → [n_rows, D²] (row
    major); rows of inactive row blocks are exact zeros.  Callers check
    ``kernel_takes_gather`` or ``kernel_takes_outer``."""
    N, D = feat.shape
    n_rb = max(-(-n_rows // ROW_BLOCK), 1)
    block_active = block_active.astype(jnp.int32)
    if outer:
        assert kernel_takes_outer(N, D), (N, D)
        out = _row_block_call(
            _outer_table(feat), weights, senders, receivers, step_rb,
            step_eb, block_active, n_out_rows=n_rb * ROW_BLOCK,
            expand=_expansions(D, _round_up(D * D, LANES) // LANES),
            interpret=interpret)
        return out[:n_rows, :D * D].astype(feat.dtype)
    assert kernel_takes_gather(N, D, n_rows), (N, D, n_rows)
    if D > 1:
        out = _row_block_call(_planes(feat), weights, senders, receivers,
                              step_rb, step_eb, block_active,
                              n_out_rows=n_rb * ROW_BLOCK,
                              interpret=interpret)
        return out[:n_rows, :D].astype(feat.dtype)
    # an edge block is live iff a row block its receivers span is: prefix
    # sums over the bitmap, indexed by the block's first and last receiver
    # (sorted) — O(row blocks + edge blocks), no per-edge gather
    live = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(block_active)])
    lo = jnp.minimum(receivers[::EDGE_BLOCK] // ROW_BLOCK, n_rb)
    hi = jnp.minimum(receivers[EDGE_BLOCK - 1::EDGE_BLOCK] // ROW_BLOCK,
                     n_rb - 1)
    block_act = live[jnp.maximum(hi + 1, lo)] - live[lo]
    out = _edge_major_call(_planes(feat)[0], weights, senders, receivers,
                           block_act, n_out_rows=_edge_major_rows(n_rows),
                           interpret=interpret)
    keep = jnp.repeat(block_active > 0, ROW_BLOCK)[:n_rows]
    out = jnp.where(keep, out.reshape(-1)[:n_rows], 0.0)
    return out[:, None].astype(feat.dtype)


@functools.partial(jax.jit, static_argnames=("n_rows", "interpret"))
def gas_scatter_reschedule_pallas(
    contrib: jnp.ndarray,      # [N_src] f32 source contributions
    prio: jnp.ndarray,         # [N] f32 current priorities
    consume: jnp.ndarray,      # [N] i32/bool — executed this phase
    weights: jnp.ndarray,      # [E_pad] f32, pad rows 0
    senders: jnp.ndarray,      # [E_pad] i32 into contrib, pad rows 0
    receivers: jnp.ndarray,    # [E_pad] i32 sorted, pads >= n + ROW_BLOCK
    n_rows: int,
    eblk_active: jnp.ndarray,  # [E_pad // EDGE_BLOCK] i32 bitmap
    interpret: bool = False,
) -> jnp.ndarray:
    """``where(consume, 0, prio) + Σ_{u→v} w_e · contrib[u]`` → [n_rows].

    The scheduler update of a GAS phase (paper Alg. 1): every executed
    vertex's priority contribution is scattered along its out-edges, and
    executed vertices consume their own.  Activity is per edge block — a
    property of the sources feeding the block.  Edge-major only: callers
    check ``edge_major_fits``."""
    rows = _edge_major_rows(n_rows)
    out = _edge_major_call(
        _planes(contrib.reshape(-1, 1))[0], weights, senders, receivers,
        eblk_active, n_out_rows=rows,
        reschedule=(_pack_scalars(prio.astype(jnp.float32), rows),
                    _pack_scalars(consume.astype(jnp.int32), rows)),
        interpret=interpret)
    return out.reshape(-1)[:n_rows]
