"""Polynomial evaluation / interpolation over Galois rings.

Host-side (``s_``-prefixed, exact python ints) variants are used for
setup-time constants (RMFE matrices, fixed evaluation points).  The jnp
variants are jit-traceable and are used for *runtime-dependent* point sets —
decoding from whichever R workers responded first.

TPU adaptation note: encode/decode are expressed as (block) matmuls with
Vandermonde / Lagrange-coefficient matrices rather than the O(N log^2 N)
subproduct-tree algorithms of [vzGathen&Gerhard]; for N <= 512 and matrix
blocks >> N this is strictly MXU-friendlier (see DESIGN.md §3.2).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, vmap

from .galois import Ring

# ---------------------------------------------------------------------------
# host-side exact versions
# ---------------------------------------------------------------------------


def s_vandermonde(ring: Ring, points: np.ndarray, K: int) -> np.ndarray:
    """V[i, k] = points[i]^k for k < K. Shape (n, K, D), object dtype."""
    n = points.shape[0]
    V = np.zeros((n, K, ring.D), dtype=object)
    for i in range(n):
        acc = ring.s_one()
        for k in range(K):
            V[i, k] = acc
            if k + 1 < K:
                acc = ring.s_mul(acc, points[i].astype(object))
    return V


def s_lagrange_coeff_matrix(ring: Ring, points: np.ndarray) -> np.ndarray:
    """M[k, i] = k-th coefficient of the i-th Lagrange basis polynomial.

    For values y_i at ``points``, the interpolating polynomial of degree < n
    has coefficients  c_k = sum_i M[k, i] * y_i.  Shape (n, n, D), object.
    """
    n = points.shape[0]
    pts = [points[i].astype(object) for i in range(n)]
    # full = prod (x - x_j): coefficients full[0..n], monic
    full = np.zeros((n + 1, ring.D), dtype=object)
    full[0] = ring.s_one()
    deg = 0
    for j in range(n):
        # multiply by (x - x_j)
        new = np.zeros_like(full)
        for k in range(deg, -1, -1):
            new[k + 1] = ring.s_add(new[k + 1], full[k])
            new[k] = ring.s_sub(new[k], ring.s_mul(full[k], pts[j]))
        full = new
        deg += 1
    M = np.zeros((n, n, ring.D), dtype=object)
    for i in range(n):
        # synthetic division: num_i = full / (x - x_i), degree n-1
        b = np.zeros((n, ring.D), dtype=object)
        b[n - 1] = full[n]
        for k in range(n - 1, 0, -1):
            b[k - 1] = ring.s_add(full[k], ring.s_mul(pts[i], b[k]))
        # lambda_i = 1 / num_i(x_i)
        val = ring.s_zero()
        for k in range(n - 1, -1, -1):
            val = ring.s_add(ring.s_mul(val, pts[i]), b[k])
        lam = ring.s_inv(val)
        for k in range(n):
            M[k, i] = ring.s_mul(lam, b[k])
    return M


def as_u32(obj_arr: np.ndarray) -> np.ndarray:
    return np.vectorize(int, otypes=[np.uint64])(obj_arr).astype(np.uint32)


# ---------------------------------------------------------------------------
# jnp traceable versions (runtime point sets)
#
# Jitted with the ring (and sizes) static, so an eager call compiles its scans
# once per (ring, shapes) rather than on every call.
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnums=(0, 2))
def vandermonde(ring: Ring, points: jnp.ndarray, K: int) -> jnp.ndarray:
    """V[i, k] = points[i]^k, shape (n, K, D); traceable scan over K."""
    n = points.shape[0]
    one = ring.ones((n,))

    def step(acc, _):
        nxt = ring.mul(acc, points)
        return nxt, acc

    _, cols = lax.scan(step, one, None, length=K)
    return jnp.moveaxis(cols, 0, 1)  # (n, K, D)


@partial(jax.jit, static_argnums=0)
def eval_poly_horner(ring: Ring, coeffs: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Evaluate sum_k coeffs[k] x^k; coeffs (K, ..., D), x (D,) -> (..., D)."""
    K = coeffs.shape[0]

    def step(acc, c):
        return ring.add(ring.mul(acc, x), c), None

    init = jnp.zeros_like(coeffs[0])
    out, _ = lax.scan(step, init, coeffs[::-1])
    return out


@partial(jax.jit, static_argnums=0)
def lagrange_coeff_matrix(ring: Ring, points: jnp.ndarray) -> jnp.ndarray:
    """Traceable M[k, i]: coefficients of Lagrange basis polys. (n, n, D).

    ``points`` (n, D) may be a runtime value (gathered from responsive
    workers); all pairwise differences must be units.
    """
    n = points.shape[0]
    D = ring.D

    # full product prod (x - x_j) via scan; buffer (n+1, D)
    def mul_linear(poly, xj):
        # poly * (x - xj): c'_k = c_{k-1} - xj c_k
        shifted = jnp.roll(poly, 1, axis=0).at[0].set(0)
        return ring.sub(shifted, ring.mul(poly, xj[None, :])), None

    init = jnp.zeros((n + 1, D), dtype=ring.dtype).at[0, 0].set(1)
    full, _ = lax.scan(mul_linear, init, points)

    def basis_for(xi):
        # synthetic division by (x - xi): b[n-1] = full[n]; b[k-1] = full[k] + xi b[k]
        def div_step(bk, fk):
            bkm1 = ring.add(fk, ring.mul(xi, bk))
            return bkm1, bk

        # iterate over full[n-1] .. full[1]; step emits the incoming carry b[k]
        # so outputs are b[n-1], ..., b[1] and the final carry is b[0]
        b_last = full[n]
        carry, bs = lax.scan(div_step, b_last, full[1:n][::-1])
        b = jnp.concatenate([carry[None], bs[::-1]], axis=0)  # b[0..n-1]
        # evaluate num_i at xi (Horner over b)
        def hstep(acc, c):
            return ring.add(ring.mul(acc, xi), c), None

        val, _ = lax.scan(hstep, jnp.zeros((D,), ring.dtype), b[::-1])
        lam = ring.inv(val)
        return ring.mul(lam[None, :], b)  # (n, D) coefficients of ell_i

    basis = vmap(basis_for)(points)  # (n_i, n_k, D)
    return jnp.moveaxis(basis, 0, 1)  # (k, i, D)


@partial(jax.jit, static_argnums=0)
def interpolate_coeffs(
    ring: Ring, points: jnp.ndarray, values: jnp.ndarray
) -> jnp.ndarray:
    """Coefficients (n, ..., D) of the unique deg<n poly through the points.

    values: (n, ..., D).
    """
    M = lagrange_coeff_matrix(ring, points)  # (n, n, D)
    batch = values.shape[1:-1]
    flat = values.reshape(values.shape[0], -1, ring.D)
    out = ring.matmul(M, flat)
    return out.reshape((M.shape[0],) + batch + (ring.D,))
