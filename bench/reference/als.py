"""Plain reference for ALS-WR, and its lower-precision control.

ALS-WR (Zhou, Wilkinson, Schreiber & Pan, AAIM 2008, section 3) minimises
``Σ_train (x_u·x_m − r_um)² + λ (Σ_u n_u |x_u|² + Σ_m n_m |x_m|²)`` by
alternating exact solves: vertex v takes
``x_v = (Σ_{train u∈N(v)} x_u x_uᵀ + λ n_v I)⁻¹ Σ r_uv x_u``, with ``n_v`` its
number of training ratings (counted as one where it has none, so that its
factor solves to zero).  Nothing here imports the program.

``check`` reads the program's factors in float64 NumPy, in blocks of pairs:

- ``grad_rel``: ``‖g‖₂ / ‖b‖₂`` over all vertices, with
  ``g_v = Σ_{train u∈N(v)} (x_u·x_v − r_uv) x_u + λ n_v x_v`` (half the
  objective's gradient, the residual of v's normal equations) and
  ``b_v = Σ r_uv x_u``.  Near zero at the color that was updated last, and
  as large as the other color's last movement elsewhere: a wrong gather, a
  wrong regulariser, a dropped color-step and an early stop all show.
- ``test_rmse``: the root mean square error over the held-out pairs.

``control`` is the same ALS-WR on the device, colored and scheduled as the
program's chromatic engine does it (users, then movies; a vertex runs
while its priority, the sum of its neighbors' L1 factor changes since it
last ran, is above the configuration's tolerance), to that tolerance or
``max_sweeps``: factors stored in bfloat16 (the precision below the
configuration's float32), sums and solves in float32.
"""
from __future__ import annotations

import numpy as np

BLOCK = 1 << 20


def _counts(inst):
    nu, nm = inst["n_users"], inst["n_movies"]
    t = inst["train"].astype(np.float64)
    return (np.maximum(np.bincount(inst["users"], t, nu), 1.0),
            np.maximum(np.bincount(inst["movies"], t, nm), 1.0))


def check(inst, cfg, answer) -> dict:
    nu, nm = inst["n_users"], inst["n_movies"]
    x = answer["factor"].astype(np.float64)
    xu, xm = x[:nu], x[nu:]
    d = x.shape[1]
    lam = cfg["lambda"]
    gu, gm = np.zeros((nu, d)), np.zeros((nm, d))
    bu, bm = np.zeros((nu, d)), np.zeros((nm, d))
    sq, n_test = 0.0, 0
    for lo in range(0, inst["users"].size, BLOCK):
        sl = slice(lo, lo + BLOCK)
        u, m = inst["users"][sl], inst["movies"][sl]
        r = inst["rating"][sl].astype(np.float64)
        t = inst["train"][sl]
        pred = np.einsum("ed,ed->e", xu[u], xm[m])
        sq += float(((pred - r)[~t] ** 2).sum())
        n_test += int((~t).sum())
        e, rt = (pred - r) * t, r * t
        for k in range(d):
            gu[:, k] += np.bincount(u, e * xm[m, k], nu)
            gm[:, k] += np.bincount(m, e * xu[u, k], nm)
            bu[:, k] += np.bincount(u, rt * xm[m, k], nu)
            bm[:, k] += np.bincount(m, rt * xu[u, k], nm)
    nvu, nvm = _counts(inst)
    gu += lam * nvu[:, None] * xu
    gm += lam * nvm[:, None] * xm
    grad = np.sqrt((gu ** 2).sum() + (gm ** 2).sum())
    rhs = np.sqrt((bu ** 2).sum() + (bm ** 2).sum())
    return {"grad_rel": float(grad / rhs) if rhs > 0 else None,
            "test_rmse": float(np.sqrt(sq / n_test)) if n_test else None}


def control(inst, cfg, dtype="bfloat16", max_sweeps=40,
            block=1 << 19) -> dict:
    import jax
    import jax.numpy as jnp

    nu, nm = inst["n_users"], inst["n_movies"]
    d, lam, tol = int(cfg["d"]), float(cfg["lambda"]), float(cfg["tolerance"])
    dt = jnp.dtype(dtype)
    f32 = jnp.float32
    t = inst["train"]
    nvu, nvm = _counts(inst)

    def side(recv, send, n):
        """One side's training pairs, receiver-sorted, in blocks padded
        to ``block`` (pad receiver ``n``: dropped)."""
        order = np.argsort(recv[t], kind="stable")
        rv, sd = recv[t][order], send[t][order]
        r = inst["rating"][t][order]
        pad = -rv.size % block if rv.size else block
        rv = np.concatenate([rv, np.full(pad, n)]).reshape(-1, block)
        sd = np.concatenate([sd, np.zeros(pad, sd.dtype)]).reshape(-1, block)
        r = np.concatenate([r, np.zeros(pad)]).reshape(-1, block)
        return (jnp.asarray(rv, jnp.int32), jnp.asarray(sd, jnp.int32),
                jnp.asarray(r, f32))

    sides = [side(inst["users"], inst["movies"], nu),
             side(inst["movies"], inst["users"], nm)]
    reg = [jnp.asarray(lam * nvu, f32), jnp.asarray(lam * nvm, f32)]
    # every pair, for the priorities: a vertex's change reaches each
    # neighbor, held-out pairs too, as the program's reschedule does
    pu = jnp.asarray(inst["users"], jnp.int32)
    pm = jnp.asarray(inst["movies"], jnp.int32)

    def normal(recv, send, r, y, n):
        # the outer products laid out [block, d·d]: as [block, d, d] the
        # TPU would tile each d×d matrix into 8×128 and take 7.7× its bytes
        def body(i, ab):
            a, b = ab
            ys = y[send[i]].astype(f32)
            xxt = (ys[:, :, None] * ys[:, None, :]).reshape(-1, d * d)
            a = a + jax.ops.segment_sum(xxt, recv[i], n + 1,
                                        indices_are_sorted=True)
            b = b + jax.ops.segment_sum(r[i][:, None] * ys, recv[i], n + 1,
                                        indices_are_sorted=True)
            return a, b
        zero = (jnp.zeros((n + 1, d * d), f32), jnp.zeros((n + 1, d), f32))
        a, b = jax.lax.fori_loop(0, recv.shape[0], body, zero)
        return a[:n].reshape(n, d, d), b[:n]

    normal = jax.jit(normal, static_argnames="n")

    @jax.jit
    def update(x, a, b, reg, prio):
        with jax.default_matmul_precision("highest"):
            eye = jnp.eye(d, dtype=f32)
            new = jnp.linalg.solve(a + reg[:, None, None] * eye,
                                   b[..., None])[..., 0].astype(dt)
        sel = prio > tol
        res = jnp.where(sel, jnp.abs(new.astype(f32) - x.astype(f32)).sum(1),
                        0.0)
        return jnp.where(sel[:, None], new, x), jnp.where(sel, 0.0, prio), res

    x = jnp.asarray(inst["factor0"], f32).astype(dt)
    xs = [x[:nu], x[nu:]]
    prio = [jnp.ones(nu, f32), jnp.ones(nm, f32)]
    sweeps = 0
    for sweeps in range(1, max_sweeps + 1):
        for c in (0, 1):
            a, b = normal(*sides[c], xs[1 - c], n=(nu, nm)[c])
            xs[c], prio[c], res = update(xs[c], a, b, reg[c], prio[c])
            mine, theirs = (pu, pm) if c == 0 else (pm, pu)
            prio[1 - c] = prio[1 - c] + jax.ops.segment_sum(
                res[mine], theirs, (nm, nu)[c])
        if max(float(prio[0].max()), float(prio[1].max())) <= tol:
            break
    factor = jnp.concatenate(xs).astype(f32)
    return {"factor": np.asarray(factor), "sweeps": sweeps}
