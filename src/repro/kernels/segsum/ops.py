"""jit'd wrapper for the sorted segment-sum kernel.

Dispatch rule (same as kernels/gas): TPU → compiled Pallas kernel;
``interpret=True`` → Pallas kernel through the interpreter (how tests
validate it on CPU); otherwise (CPU production) → the jnp oracle.

Width rule: the kernel streams 128-lane feature blocks, so a narrower sum
would be padded to 128 lanes and cost 128/D times its own traffic.  Callers
on the TPU send it only widths that are a multiple of 128
(``kernel_takes_width``); ``core/graph.py:segment_combine`` keeps narrower
sums (LBP's ``[E, K]`` messages, scalar sums) on XLA's segment_sum."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.gas.gas import EDGE_BLOCK, ROW_BLOCK, csr_steps
from repro.kernels.segsum import segsum as k
from repro.kernels.segsum.ref import segment_sum_sorted_ref


def kernel_takes_width(d: int) -> bool:
    """Whether a sorted ``[E, d]`` sum goes to the kernel on the TPU."""
    return d > 0 and d % k.FEAT_BLOCK == 0


def segment_sum_sorted(
    msgs: jnp.ndarray,
    receivers: np.ndarray,
    n_rows: int,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """msgs [E, D] with *host-known sorted* receivers [E] -> [n_rows, D].

    Receivers must be host (numpy) values: the kernel's grid schedule is
    scalar-prefetch data computed at trace time — the data-graph structure
    is static (paper Sec. 3.1), so this holds for every engine/GNN use.
    """
    receivers_np = np.asarray(receivers)
    if not interpret and jax.default_backend() != "tpu":
        # production CPU path: the oracle (interpret mode is for tests)
        return segment_sum_sorted_ref(msgs, jnp.asarray(receivers_np), n_rows)

    E, D = msgs.shape
    e_pad = max(-(-E // EDGE_BLOCK), 1) * EDGE_BLOCK
    if e_pad != E:
        msgs = jnp.pad(msgs, ((0, e_pad - E), (0, 0)))
        receivers_np = np.concatenate(
            [receivers_np, np.full(e_pad - E, n_rows + ROW_BLOCK, np.int32)])

    step_rb, step_eb = csr_steps(receivers_np, n_rows)
    out = k.segment_sum_sorted_pallas(
        msgs, jnp.asarray(receivers_np), n_rows, jnp.asarray(step_rb),
        jnp.asarray(step_eb), interpret=bool(interpret))
    return out[:n_rows]
