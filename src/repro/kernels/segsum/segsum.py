"""Pallas TPU kernel: receiver-sorted segment sum (blocked SpMM-style).

The ⊕-combine of the GraphLab engines' dense path and every GNN arch:
accumulate per-edge messages into per-vertex rows.  TPU-native design:

  - receivers are sorted (the data graph stores edges receiver-major), so
    the edges of a 128-row output block are a *contiguous* run of edge
    blocks; the grid walks the same flattened (row block, edge block)
    schedule as the GAS kernels (``kernels/gas/gas.py:csr_steps``), with
    the two per-step ids as scalar prefetch;
  - the in-block scatter is a one-hot MXU matmul at ``Precision.HIGHEST``:
    onehot[RB, EB] @ msgs[EB, 128] — scatter-by-matrix-multiply is the
    idiomatic way to feed the 128x128 systolic array an irregular reduce,
    and with an exact one-hot factor f32 messages are not rounded to bf16;
  - grid (feature block k, step t), t innermost and sequential: the VMEM
    accumulator of one (row block, feature block) pair is reset at the row
    block's first step and flushed at its last;
  - boundary edge blocks are shared by adjacent row blocks; the row-range
    mask makes each contribution exactly-once.

VMEM per step: msgs EB*128*4 + onehot RB*EB*4 + acc RB*128*4 ≈ 1.1 MB.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gas.gas import EDGE_BLOCK, ROW_BLOCK

FEAT_BLOCK = 128


def _kernel(srb, seb,                      # scalar prefetch [T], [T]
            msgs_ref, recv_ref,            # blocks [EB, BD], [EB]
            out_ref,                       # block [RB, BD]
            acc_ref):                      # VMEM scratch [RB, BD] f32
    t = pl.program_id(1)
    n_t = pl.num_programs(1)
    rb = srb[t]

    @pl.when((t == 0) | (srb[jnp.maximum(t - 1, 0)] != rb))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(seb[t] >= 0)
    def _accumulate():
        local = recv_ref[...] - rb * ROW_BLOCK
        rows = jax.lax.broadcasted_iota(jnp.int32, (ROW_BLOCK, EDGE_BLOCK), 0)
        onehot = jnp.where(rows == local[None, :], 1.0, 0.0)
        acc_ref[...] += jax.lax.dot_general(
            onehot, msgs_ref[...].astype(jnp.float32),
            (((1,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)

    @pl.when((t == n_t - 1) | (srb[jnp.minimum(t + 1, n_t - 1)] != rb))
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def segment_sum_sorted_pallas(
    msgs: jnp.ndarray,
    receivers: jnp.ndarray,
    n_rows: int,
    step_rb: jnp.ndarray,
    step_eb: jnp.ndarray,
    interpret: bool = False,
) -> jnp.ndarray:
    """msgs [E, D] (E % EDGE_BLOCK == 0), receivers [E] sorted (pads >=
    n_rows + ROW_BLOCK), (step_rb, step_eb) from ``csr_steps`` →
    [n_rows_padded, D]."""
    E, D = msgs.shape
    assert E % EDGE_BLOCK == 0, (E,)
    n_pad_rows = pl.cdiv(n_rows, ROW_BLOCK) * ROW_BLOCK
    d_pad = pl.cdiv(D, FEAT_BLOCK) * FEAT_BLOCK
    if d_pad != D:
        msgs = jnp.pad(msgs, ((0, 0), (0, d_pad - D)))
    eblk = lambda k, t, srb, seb: (jnp.maximum(seb[t], 0), k)

    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(d_pad // FEAT_BLOCK, step_rb.shape[0]),
            in_specs=[
                pl.BlockSpec((EDGE_BLOCK, FEAT_BLOCK), eblk),
                pl.BlockSpec((EDGE_BLOCK,),
                             lambda k, t, srb, seb: (jnp.maximum(seb[t], 0),)),
            ],
            out_specs=pl.BlockSpec((ROW_BLOCK, FEAT_BLOCK),
                                   lambda k, t, srb, seb: (srb[t], k)),
            scratch_shapes=[pltpu.VMEM((ROW_BLOCK, FEAT_BLOCK), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((n_pad_rows, d_pad), msgs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(step_rb.astype(jnp.int32), step_eb.astype(jnp.int32), msgs,
      receivers.astype(jnp.int32))
    return out[:, :D] if d_pad != D else out
