"""Minimal irreducible-representation toolbox for E(3)-equivariant GNNs.

Everything the equivariant archs (NequIP, MACE, EquiformerV2) need, built
from scratch (no e3nn):

  real_sph_harm     batched real spherical harmonics Y_l, l <= LMAX, on unit
                    vectors — stable Cartesian recurrences (no poles).
  wigner_d          batched rotation matrices D^l(R) for real SH via the
                    Ivanic-Ruedenberg recursion (J. Phys. Chem. 100, 6342,
                    + erratum), driven entirely by D^1 = R in the (y,z,x)
                    basis.  Traced jnp — rotations are per-edge data.
  clebsch_gordan    real-basis coupling tensors C^{l1 l2 l3}, derived
                    *numerically* as the unique fixed point of the group
                    average  C <- E_R[ D1 C D2 D3 ]  (power iteration over
                    random rotations, float64).  By construction they are
                    exactly consistent with ``wigner_d`` — no Condon-Shortley
                    convention hazards.  Cached per triple.
  align_to_z        rotation matrices taking each edge direction to +z (for
                    the eSCN SO(2) convolution trick of EquiformerV2).

Conventions: within each l, components are ordered m = -l..l; l=1 is (y,z,x).
Equivariance of every piece is hypothesis-property-tested in
tests/test_irreps.py:  Y(R r) = D(R) Y(r),  D(R1 R2) = D(R1) D(R2),  and
TP(D1 x, D2 y) = D3 TP(x, y).
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

LMAX_SUPPORTED = 8


def irrep_dim(l: int) -> int:
    return 2 * l + 1


def irreps_dim(lmax: int) -> int:
    return (lmax + 1) ** 2


def slice_l(l: int) -> slice:
    """Slice of the l-block inside a flattened [..., (lmax+1)^2] feature."""
    return slice(l * l, (l + 1) * (l + 1))


# ---------------------------------------------------------------------------
# Real spherical harmonics (orthonormal, m = -l..l, l=1 -> (y,z,x))
# ---------------------------------------------------------------------------

def real_sph_harm(r: jnp.ndarray, lmax: int,
                  normalized_input: bool = False) -> jnp.ndarray:
    """Y: [..., (lmax+1)^2] on (optionally unnormalized) vectors r [..., 3].

    Stable Cartesian form: with C_m + i S_m = (x + iy)^m and
    Pbar_l^m = P_l^m / sin^m(theta) (a polynomial in z), the poles never
    divide by sin(theta).
    """
    assert lmax <= LMAX_SUPPORTED
    # dual-mode: numpy in -> numpy out (float64 precompute path, independent
    # of the jax_enable_x64 flag); jnp in -> traced jnp out (runtime path)
    xp = np if isinstance(r, np.ndarray) else jnp
    x, y, z = r[..., 0], r[..., 1], r[..., 2]
    if not normalized_input:
        n = xp.sqrt(x * x + y * y + z * z)
        n = xp.maximum(n, 1e-12)
        x, y, z = x / n, y / n, z / n

    # C_m + i S_m = (x + i y)^m by recurrence
    C = [xp.ones_like(x)]
    S = [xp.zeros_like(x)]
    for m in range(1, lmax + 1):
        C.append(C[m - 1] * x - S[m - 1] * y)
        S.append(C[m - 1] * y + S[m - 1] * x)

    # Pbar_l^m by recurrence (no Condon-Shortley phase)
    P: Dict[Tuple[int, int], jnp.ndarray] = {}
    P[(0, 0)] = xp.ones_like(z)
    for m in range(1, lmax + 1):
        P[(m, m)] = (2 * m - 1) * P[(m - 1, m - 1)]
    for m in range(0, lmax):
        P[(m + 1, m)] = (2 * m + 1) * z * P[(m, m)]
    # The sin^m(theta) factor lives in C_m/S_m (= Re/Im (x+iy)^m), so the
    # factored P-bar obeys the *plain* Legendre recurrence in z.
    for m in range(0, lmax + 1):
        for l in range(m + 2, lmax + 1):
            P[(l, m)] = ((2 * l - 1) * z * P[(l - 1, m)]
                         - (l - 1 + m) * P[(l - 2, m)]) / (l - m)

    out = []
    for l in range(lmax + 1):
        for m in range(-l, l + 1):
            am = abs(m)
            K = math.sqrt((2 * l + 1) / (4 * math.pi)
                          * math.factorial(l - am) / math.factorial(l + am))
            if m == 0:
                out.append(K * P[(l, 0)])
            elif m > 0:
                out.append(math.sqrt(2) * K * C[am] * P[(l, am)])
            else:
                out.append(math.sqrt(2) * K * S[am] * P[(l, am)])
    return xp.stack(out, axis=-1)


# ---------------------------------------------------------------------------
# Wigner rotations for real SH — anchor-point construction, batched & traced
# ---------------------------------------------------------------------------
#
# For each l, D^l(R) is the unique linear map with Y^l(R p) = D^l Y^l(p).
# Evaluate Y at K static anchor directions p_k: with B_l = [Y^l(p_k)]_k
# (static, pseudo-inverted once at import) and A_l = [Y^l(R p_k)]_k (per
# rotation), B_l D^T = A_l  =>  D^l = A_l^T pinv(B_l)^T.  Exact by
# construction (no Condon-Shortley/recursion convention hazards — the
# Ivanic-Ruedenberg recursion was tried first and retired after its l>=2
# convention could not be matched; see tests/test_irreps.py which pins the
# required properties).  Cost per rotation: K spherical-harmonic evals + one
# small static matmul per l — comparable to the recursion, fully batched.

@functools.lru_cache(maxsize=None)
def _anchor_data(lmax: int) -> Tuple[np.ndarray, Tuple[np.ndarray, ...]]:
    """(anchors [K,3], per-l pinv(B_l) [2l+1, K]) — float64 numpy statics
    (independent of the jax_enable_x64 flag)."""
    k = 2 * (2 * lmax + 1) + 3
    rng = np.random.default_rng(12345)
    p = rng.normal(size=(k, 3))
    p /= np.linalg.norm(p, axis=1, keepdims=True)
    yfull = real_sph_harm(p.astype(np.float64), lmax)  # numpy path
    pinvs = []
    for l in range(lmax + 1):
        B = yfull[:, l * l:(l + 1) * (l + 1)]
        pinvs.append(np.linalg.pinv(B))
        # guard conditioning: the anchors must span the irrep
        assert np.linalg.cond(B) < 1e3, (l, np.linalg.cond(B))
    return p, tuple(pinvs)


def wigner_d(R, lmax: int) -> List:
    """Returns [D^0, D^1, ..., D^lmax]; D^l has shape [..., 2l+1, 2l+1].
    Dual-mode like real_sph_harm: numpy in (f64 precompute) / jnp in."""
    xp = np if isinstance(R, np.ndarray) else jnp
    anchors, pinvs = _anchor_data(lmax)
    p = xp.asarray(anchors, dtype=R.dtype)                 # [K, 3]
    q = xp.einsum("...ij,kj->...ki", R, p)                 # [..., K, 3]
    yq = real_sph_harm(q, lmax, normalized_input=True)     # [..., K, dim]
    out: List = []
    for l in range(lmax + 1):
        A = yq[..., l * l:(l + 1) * (l + 1)]               # [..., K, 2l+1]
        Pb = xp.asarray(pinvs[l], dtype=R.dtype)           # [2l+1, K]
        out.append(xp.einsum("...ka,bk->...ab", A, Pb))
    return out


def wigner_d_block(R: jnp.ndarray, lmax: int) -> jnp.ndarray:
    """Block-diagonal D over the full [.., (lmax+1)^2, (lmax+1)^2] space."""
    Ds = wigner_d(R, lmax)
    dim = irreps_dim(lmax)
    out = jnp.zeros(R.shape[:-2] + (dim, dim), R.dtype)
    for l, D in enumerate(Ds):
        sl = slice_l(l)
        out = out.at[..., sl, sl].set(D)
    return out


def align_to_z(r: jnp.ndarray) -> jnp.ndarray:
    """Rotation matrices R with R @ r_hat = +z, batched.  Rodrigues about
    axis r_hat x z; the antipode r_hat = -z uses a pi-rotation about x."""
    x, y, z = r[..., 0], r[..., 1], r[..., 2]
    n = jnp.sqrt(x * x + y * y + z * z)
    n = jnp.maximum(n, 1e-12)
    x, y, z = x / n, y / n, z / n
    # axis v = r_hat x z = (y, -x, 0); cos = z
    eye = jnp.broadcast_to(jnp.eye(3, dtype=r.dtype), r.shape[:-1] + (3, 3))
    vx, vy = y, -x
    zero = jnp.zeros_like(x)
    K = jnp.stack([
        jnp.stack([zero, zero, vy], -1),
        jnp.stack([zero, zero, -vx], -1),
        jnp.stack([-vy, vx, zero], -1),
    ], -2)
    # 1 + cos; near the antipode 1 + z cancels, and the equal form
    # (x² + y²) / (1 - z) keeps full precision
    s2 = x * x + y * y
    one_plus_c = jnp.where(z >= 0, 1.0 + z,
                           s2 / jnp.maximum(1.0 - z, 1e-12))
    antipode = s2 <= 0
    R = eye + K + (K @ K) / jnp.where(antipode, 1.0, one_plus_c)[
        ..., None, None]
    # exact antipode: rotate pi about x: (x,y,z) -> (x,-y,-z)
    flip = jnp.asarray([[1., 0., 0.], [0., -1., 0.], [0., 0., -1.]], r.dtype)
    flip = jnp.broadcast_to(flip, R.shape)
    use_flip = (antipode & (z < 0))[..., None, None]
    return jnp.where(use_flip, flip, R)


# ---------------------------------------------------------------------------
# Clebsch-Gordan tensors: numeric invariant-subspace construction
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def clebsch_gordan(l1: int, l2: int, l3: int) -> np.ndarray:
    """Real-basis coupling tensor C [2l1+1, 2l2+1, 2l3+1], unit Frobenius
    norm, satisfying for every rotation R:

        einsum('ai,bj,ck,ijk->abc', D1, D2, D3, C) == C

    Built by power-iterating the group average with ``wigner_d`` itself, so
    consistency with our D matrices holds by construction.  Returns zeros if
    l3 is not in |l1-l2|..l1+l2 (no coupling).
    """
    shape = (irrep_dim(l1), irrep_dim(l2), irrep_dim(l3))
    if not (abs(l1 - l2) <= l3 <= l1 + l2):
        return np.zeros(shape)
    lmax = max(l1, l2, l3)
    rng = np.random.default_rng(f"{l1}-{l2}-{l3}".__hash__() & 0xFFFF)

    K = 24
    Rs = _random_rotations(K, rng)
    D_all = wigner_d(Rs.astype(np.float64), lmax)  # numpy f64 path
    D1, D2, D3 = D_all[l1], D_all[l2], D_all[l3]

    C = rng.normal(size=shape)
    for _ in range(120):
        # group-average projection step
        Cn = np.einsum("rai,rbj,rck,ijk->abc", D1, D2, D3, C) / K
        norm = np.linalg.norm(Cn)
        if norm < 1e-9:
            return np.zeros(shape)
        C = Cn / norm
    # final polish with a fresh rotation set to kill MC bias
    Rs2 = _random_rotations(K, rng)
    D_all2 = wigner_d(Rs2.astype(np.float64), lmax)
    E1, E2, E3 = (D_all2[l] for l in (l1, l2, l3))
    for _ in range(120):
        Cn = np.einsum("rai,rbj,rck,ijk->abc", E1, E2, E3, C) / K
        norm = np.linalg.norm(Cn)
        if norm < 1e-9:
            return np.zeros(shape)
        C = Cn / norm
    # deterministic sign: make the largest-magnitude entry positive
    flat = C.ravel()
    C = C * np.sign(flat[np.argmax(np.abs(flat))])
    return C


def _random_rotations(k: int, rng: np.random.Generator) -> np.ndarray:
    """Uniform SO(3) samples via quaternions."""
    q = rng.normal(size=(k, 4))
    q /= np.linalg.norm(q, axis=1, keepdims=True)
    w, x, y, z = q[:, 0], q[:, 1], q[:, 2], q[:, 3]
    R = np.stack([
        1 - 2 * (y * y + z * z), 2 * (x * y - z * w), 2 * (x * z + y * w),
        2 * (x * y + z * w), 1 - 2 * (x * x + z * z), 2 * (y * z - x * w),
        2 * (x * z - y * w), 2 * (y * z + x * w), 1 - 2 * (x * x + y * y),
    ], axis=-1).reshape(k, 3, 3)
    return R


def tensor_product(x: jnp.ndarray, y: jnp.ndarray, l1: int, l2: int,
                   l3: int) -> jnp.ndarray:
    """Couples x [..., 2l1+1] (x) y [..., 2l2+1] -> [..., 2l3+1]."""
    C = jnp.asarray(clebsch_gordan(l1, l2, l3), x.dtype)
    return jnp.einsum("...i,...j,ijk->...k", x, y, C)
