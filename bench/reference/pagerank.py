"""Plain reference for PageRank, and its lower-precision control.

PageRank's fixed point is ``r = T(r)`` with ``T(r)_v = alpha/n + (1 -
alpha) Σ_{u→v} r_u / outdeg(u)``.  ``check`` applies ``T`` once, in
float64 NumPy, to the ranks the program produced, and returns the L1 norm
of ``T(r) - r``.  Since ``T`` contracts by ``1 - alpha`` in L1, the
distance to the true fixed point is at most that residual over ``alpha``:
a wrong gather, a wrong apply and an early stop each show in it.  Nothing
here imports the program.

``control`` is the same PageRank solved in bfloat16 (the precision below
the configuration's float32) by Jacobi sweeps on the device, to the
configuration's tolerance or ``max_sweeps``, as the MXU does at its
default precision: ranks and weights stored in bfloat16, products summed
in float32, each sweep's ranks rounded back to bfloat16.  Its ranks go
through the same ``check``.
"""
from __future__ import annotations

import numpy as np


def _edges(inst):
    u, v = inst["u"].astype(np.int64), inst["v"].astype(np.int64)
    return np.concatenate([u, v]), np.concatenate([v, u])


def check(inst, cfg, answer) -> dict:
    n, alpha = inst["n"], cfg["alpha"]
    src, dst = _edges(inst)
    r = answer["rank"].astype(np.float64)
    outdeg = np.bincount(src, minlength=n)
    t = alpha / n + (1.0 - alpha) * np.bincount(
        dst, weights=r[src] / outdeg[src], minlength=n)
    return {"residual_l1": float(np.abs(t - r).sum())}


def control(inst, cfg, dtype="bfloat16", max_sweeps=100) -> dict:
    import jax
    import jax.numpy as jnp

    n, alpha = inst["n"], cfg["alpha"]
    tol = cfg["tolerance_per_vertex"] / n
    src, dst = _edges(inst)
    outdeg = np.bincount(src, minlength=n)
    dt = jnp.dtype(dtype)
    s, d = jnp.asarray(src, jnp.int32), jnp.asarray(dst, jnp.int32)
    w = jnp.asarray(1.0 / outdeg[src], dt)

    @jax.jit
    def sweep(r):
        f32 = jnp.float32
        acc = jax.ops.segment_sum(w.astype(f32) * r[s].astype(f32), d, n)
        nxt = (alpha / n + (1.0 - alpha) * acc).astype(dt)
        return nxt, jnp.max(jnp.abs(nxt.astype(f32) - r.astype(f32)))

    r = jnp.full((n,), 1.0 / n, dt)
    for i in range(max_sweeps):
        r, change = sweep(r)
        if float(change) <= tol:
            break
    return {"rank": np.asarray(r.astype(jnp.float32)), "sweeps": i + 1}
