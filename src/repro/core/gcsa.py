"""CSA / GCSA batch codes for batch DMM over a Galois ring (paper Table 1).

Two executable members of the GCSA family:

* :class:`CSACode` — the (u, v, w) = (1, 1, 1), kappa = n point (the
  configuration GCSA uses for its best communication costs, and the one
  Table 1 contrasts most sharply with Batch-EP_RMFE: R_CSA = 2n-1 vs
  R_RMFE = uvw + w - 1).

* :class:`GCSACode` — the general (u, v, w, kappa) construction:
  Entangled-Polynomial inner partitioning (t/u x r/w and r/w x s/v
  blocks) composed with the CSA outer Cauchy structure over
  kappa-grouped batches, R = uvw(n + kappa - 1) + w - 1.

Construction (Jia-Jafar GCSA, ported to Galois rings with digit-lift
exceptional points so all beta_g - alpha_i differences are units).  The
n products are grouped into ell = n/kappa groups of kappa; with
x_g = beta_g - alpha_i and Delta_l = prod_{g in group l} x_g, worker i
receives per group l the EP-in-Cauchy evaluations

    A~_{l,i} = Delta_l^{uvw} sum_{g in l} sum_{e in Ef} A_g^(e) x_g^{e-uvw}
    B~_{l,i} =               sum_{g in l} sum_{e in Eg} B_g^(e) x_g^{e-uvw}

shipped as ONE pair of block-concatenated shares

    fa_i = [A~_{0,i} | ... | A~_{ell-1,i}]   (t/u, ell*r/w)
    gb_i = [B~_{0,i} ; ... ; B~_{ell-1,i}]   (ell*r/w, s/v)

so a worker's single plain ring matmul H_i = fa_i @ gb_i computes
sum_l A~_{l,i} B~_{l,i} — the same worker surface as every other scheme
(kernel substitution, contraction-axis streaming and at-worker encode
all apply unchanged).  Every EP exponent satisfies e <= uvw - 1, so H_i
decomposes into per-product pole terms of order 1..uvw at each beta_g
plus a polynomial of interference terms of degree
<= (kappa - 1) uvw + w - 2: any R responses form a generalized
Cauchy-Vandermonde system, solved on device by unit-pivot Gauss-Jordan
elimination (:func:`gr_solve`).  The recovered pole coefficients at
beta_g are a lower-triangular Toeplitz transform — with unit diagonal
prod_{g' != g} (beta_{g'} - beta_g)^{uvw} — of product g's EP
convolution coefficients; a precomputed truncated power-series inverse
undoes it, and the useful coefficients assemble C_g exactly as in
``EPCode.decode``.
"""
from __future__ import annotations

import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, vmap

from repro import settings

from .ep_codes import EPCosts
from .galois import Ring
from .polyops import as_u32, s_vandermonde

__all__ = ["CSACode", "GCSACode", "gcsa_cost_model", "gr_solve"]


def is_unit_mask(ring: Ring, x: jnp.ndarray) -> jnp.ndarray:
    """(…, D) -> (…,) bool: element is a unit iff some coeff != 0 mod p."""
    return jnp.any(x % jnp.uint32(ring.p) != 0, axis=-1)


def _raise_singular(ok) -> None:
    if not bool(ok):
        raise ValueError(
            "gr_solve: singular system detected at run time (some "
            "elimination column has no unit pivot)"
        )


def gr_solve(
    ring: Ring, M: jnp.ndarray, Y: jnp.ndarray, *, check: bool = True
) -> jnp.ndarray:
    """Solve M X = Y over the ring; M (n, n, D) invertible, Y (n, b, D).

    Unit-pivot Gauss-Jordan, traceable (n is static, pivot row is dynamic).

    ``check=True`` guards against silent garbage on singular systems: over
    a local ring M is invertible iff every elimination column holds a unit
    pivot, and ``jnp.argmax`` over the all-False unit mask of a singular
    column would silently select row 0 and "invert" a non-unit.  On eager
    (non-traced) calls the pivot masks are concrete and a singular system
    raises ``ValueError`` host-side.  Under jit every mask is a tracer, so
    the check degrades to an accumulated flag, raised from a
    ``jax.debug.callback`` at run time under ``REPRO_DEBUG_SOLVE=1`` (off
    by default: the callback has per-call cost).  The jitted ``decode_op``
    seam, whose live set is traced data, is instead covered by
    ``decode_op``'s host-side check of the index tuple: duplicates raise
    before the decode runs.
    """
    n = M.shape[0]
    ok = None
    for k in range(n):
        col = M[:, k]  # (n, D)
        units = is_unit_mask(ring, col) & (jnp.arange(n) >= k)
        if check:
            has = jnp.any(units)
            if isinstance(has, jax.core.Tracer):
                ok = has if ok is None else ok & has
            elif not bool(has):
                raise ValueError(
                    f"gr_solve: singular system over {ring}: no unit pivot "
                    f"in elimination column {k} (matrix not invertible mod "
                    f"p — e.g. a decode live set indexing dependent "
                    f"responses)"
                )
        j = jnp.argmax(units)
        perm = jnp.arange(n)
        perm = perm.at[k].set(j).at[j].set(k)
        M = M[perm]
        Y = Y[perm]
        inv = ring.inv(M[k, k])
        Mk = ring.mul(inv[None, :], M[k])  # (n, D)
        Yk = ring.mul(inv[None, :], Y[k])  # (b, D)
        M = M.at[k].set(Mk)
        Y = Y.at[k].set(Yk)
        factors = M[:, k].at[k].set(0)  # (n, D)
        M = ring.sub(M, ring.mul(factors[:, None, :], Mk[None, :, :]))
        Y = ring.sub(Y, ring.mul(factors[:, None, :], Yk[None, :, :]))
    if ok is not None and settings.get_bool("debug_solve"):
        jax.debug.callback(_raise_singular, ok)
    return Y


def _check_live_set(idx) -> None:
    """Host-side decode guard: duplicate worker indices make the decode
    system singular (repeated Cauchy-Vandermonde rows).  A concrete ``idx``
    (an eager decode, or one closed over as a constant under jit) raises
    here before tracing hides the pivot masks; traced live sets (the
    ``decode_op`` seam, which checks its index tuple itself) fall through
    to ``gr_solve``'s ``REPRO_DEBUG_SOLVE`` run-time guard."""
    if isinstance(idx, jax.core.Tracer):
        return
    ii = np.asarray(idx).ravel()
    if np.unique(ii).shape[0] != ii.shape[0]:
        raise ValueError(
            "decode: singular live set — duplicate worker indices "
            f"{sorted(ii.tolist())} (repeated responses carry no new "
            "information; the decode system is not invertible)"
        )


class CSACode:
    """Batch DMM of L products over ``ring`` with N workers, R = 2L-1."""

    def __init__(self, ring: Ring, L: int, N: int):
        self.ring = ring
        self.L, self.N = L, N
        self.R = 2 * L - 1
        if self.R > N:
            raise ValueError(f"R={self.R} > N={N}")
        if L + N > ring.p**ring.D:
            raise ValueError(
                f"need {L + N} exceptional points, |T| = {ring.p}^{ring.D}"
            )
        pts = ring.exceptional_points(L + N)
        fs, alphas = pts[:L], pts[L:]
        self.fs_np, self.alphas_np = fs, alphas

        # host precompute: cauchy terms, Delta(alpha), c_g
        cau = np.zeros((N, L, ring.D), dtype=object)  # 1/(f_g - a_i)
        delta = np.zeros((N, ring.D), dtype=object)
        for i in range(N):
            d = ring.s_one()
            for g in range(L):
                diff = ring.s_sub(fs[g].astype(object), alphas[i].astype(object))
                cau[i, g] = ring.s_inv(diff)
                d = ring.s_mul(d, diff)
            delta[i] = d
        cg = np.zeros((L, ring.D), dtype=object)
        for g in range(L):
            c = ring.s_one()
            for d_ in range(L):
                if d_ != g:
                    c = ring.s_mul(
                        c, ring.s_sub(fs[d_].astype(object), fs[g].astype(object))
                    )
            cg[g] = c
        self.cauchy = jnp.asarray(as_u32(cau))  # (N, L, D)
        self.enc_a = jnp.asarray(
            as_u32(
                np.array(
                    [[ring.s_mul(delta[i], cau[i, g]) for g in range(L)] for i in range(N)],
                    dtype=object,
                )
            )
        )  # (N, L, D): Delta(a_i)/(f_g - a_i)
        self.cg_inv = jnp.asarray(
            as_u32(np.array([ring.s_inv(cg[g]) for g in range(L)], dtype=object))
        )  # (L, D)
        V = s_vandermonde(ring, alphas, max(L - 1, 1))  # (N, L-1, D)
        self.vand = jnp.asarray(as_u32(V))
        self.points = jnp.asarray(alphas)

    # -- encode ---------------------------------------------------------------

    def encode_a(self, As: jnp.ndarray) -> jnp.ndarray:
        """As (L, t, r, D) -> (N, t, r, D)."""
        L, t, r, D = As.shape
        return self.ring.matmul(self.enc_a, As.reshape(L, t * r, D)).reshape(
            self.N, t, r, D
        )

    def encode_b(self, Bs: jnp.ndarray) -> jnp.ndarray:
        L, r, s, D = Bs.shape
        return self.ring.matmul(self.cauchy, Bs.reshape(L, r * s, D)).reshape(
            self.N, r, s, D
        )

    def encode_a_at(self, As: jnp.ndarray, i) -> jnp.ndarray:
        """Worker i's A~_i only (encode-at-worker; ``i`` may be a tracer)."""
        L, t, r, D = As.shape
        row = lax.dynamic_index_in_dim(self.enc_a, i, axis=0, keepdims=False)
        return self.ring.matmul(row[None], As.reshape(L, t * r, D))[0].reshape(
            t, r, D
        )

    def encode_b_at(self, Bs: jnp.ndarray, i) -> jnp.ndarray:
        L, r, s, D = Bs.shape
        row = lax.dynamic_index_in_dim(self.cauchy, i, axis=0, keepdims=False)
        return self.ring.matmul(row[None], Bs.reshape(L, r * s, D))[0].reshape(
            r, s, D
        )

    def worker_compute(self, FA, GB):
        return vmap(self.ring.matmul)(FA, GB)

    # -- decode -----------------------------------------------------------------

    def decode(self, H: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
        """H (R, t, s, D) from workers idx (R,) -> (L, t, s, D) products.

        Guarded against silent garbage: duplicate live-set indices raise
        host-side whenever ``idx`` is concrete, and ``gr_solve`` raises on
        any singular system when called eagerly.
        """
        _check_live_set(idx)
        ring = self.ring
        R, t, s, D = H.shape
        assert R == self.R
        cau = jnp.take(self.cauchy, idx, axis=0)  # (R, L, D)
        van = jnp.take(self.vand, idx, axis=0)  # (R, L-1, D)
        M = jnp.concatenate([cau, van], axis=1)  # (R, R, D)
        X = gr_solve(ring, M, H.reshape(R, t * s, D))  # (R, t*s, D)
        U = X[: self.L].reshape(self.L, t, s, D)
        C = ring.mul(self.cg_inv[:, None, None, :], U)
        return C

    def run(self, As, Bs, idx: Optional[jnp.ndarray] = None):
        FA, GB = self.encode_a(As), self.encode_b(Bs)
        H = self.worker_compute(FA, GB)
        if idx is None:
            idx = jnp.arange(self.R, dtype=jnp.int32)
        return self.decode(jnp.take(H, idx, axis=0), idx)

    def costs(self, spec, r: Optional[int] = None, s: Optional[int] = None,
              base: Optional[Ring] = None) -> EPCosts:
        """Analytic costs for a ProblemSpec (shared ``costs(spec)`` surface).

        The legacy positional form ``costs(t, r, s, base)`` still works but
        is deprecated.
        """
        if r is not None:
            warnings.warn(
                "CSACode.costs(t, r, s, base) is deprecated; pass a "
                "repro.cdmm.api.ProblemSpec instead",
                DeprecationWarning,
                stacklevel=2,
            )
            t = int(spec)
        else:
            t, r, s, base = spec.t, spec.r, spec.s, spec.ring
        return gcsa_cost_model(
            t, r, s, 1, 1, 1, self.L, self.L, self.N, self.ring.D / base.D
        )


def _trunc_pow_prod(ring: Ring, cs, e: int, K: int):
    """First K coefficients (in x) of prod_c (c + x)^e, object arithmetic."""
    poly = [ring.s_one()] + [ring.s_zero() for _ in range(K - 1)]
    for c in cs:
        for _ in range(e):
            nxt = []
            for j in range(K):
                term = ring.s_mul(poly[j], c)
                if j:
                    term = ring.s_add(term, poly[j - 1])
                nxt.append(term)
            poly = nxt
    return poly


def _series_inv(ring: Ring, rho, K: int):
    """sigma with sigma * rho = 1 mod x^K (rho[0] must be a unit)."""
    sigma = [ring.s_inv(rho[0])]
    for j in range(1, K):
        acc = ring.s_zero()
        for i in range(1, j + 1):
            acc = ring.s_add(acc, ring.s_mul(rho[i], sigma[j - i]))
        sigma.append(ring.s_sub(ring.s_zero(), ring.s_mul(sigma[0], acc)))
    return sigma


class GCSACode:
    """General-(u, v, w, kappa) GCSA: batch DMM of L products over ``ring``
    with N workers, R = uvw(L + kappa - 1) + w - 1 (see module docstring).

    ``kappa`` must divide L; ``kappa = L`` with u = v = w = 1 is the
    :class:`CSACode` point (bit-identical shares and decode), ``kappa = 1``
    is the per-product-poles end of the family (R = uvw L + w - 1), and
    L = 1 degenerates to a single EP execution (R = uvw + w - 1).
    Shapes are taken at encode time, so one instance serves any (t, r, s)
    divisible by the partition.
    """

    def __init__(
        self, ring: Ring, L: int, N: int, u: int = 1, v: int = 1,
        w: int = 1, kappa: Optional[int] = None,
    ):
        kappa = L if kappa is None else kappa
        if min(u, v, w, kappa) < 1:
            raise ValueError(
                f"partition (u={u}, v={v}, w={w}, kappa={kappa}) must be >= 1"
            )
        if L % kappa:
            raise ValueError(f"kappa={kappa} must divide the batch L={L}")
        self.ring = ring
        self.L, self.N = L, N
        self.u, self.v, self.w, self.kappa = u, v, w, kappa
        self.nl = L // kappa  # number of kappa-groups ("ell")
        uvw = u * v * w
        self.uvw = uvw
        self.R = uvw * (L + kappa - 1) + w - 1
        if self.R > N:
            raise ValueError(f"R={self.R} > N={N}")
        if L + N > ring.p**ring.D:
            raise ValueError(
                f"need {L + N} exceptional points, |T| = {ring.p}^{ring.D}"
            )
        pts = ring.exceptional_points(L + N)
        betas, alphas = pts[:L], pts[L:]
        self.betas_np, self.alphas_np = betas, alphas
        self.points = jnp.asarray(alphas)

        # EP exponent layout (same zero-based layout as EPCode)
        exp_f = [i * w + j for i in range(u) for j in range(w)]
        exp_g = [(w - 1 - k) + l * u * w for k in range(w) for l in range(v)]
        self.exp_c = np.array(
            [[i * w + (w - 1) + l * u * w for l in range(v)] for i in range(u)]
        )  # (u, v): exponents carrying the useful blocks, all <= uvw - 1

        # host precompute (exact object-int arithmetic): per-group encode
        # coefficient tensors + the pole half of the decode basis.  Column
        # order within a group is (k, EP-block) — matching the grouped
        # reshape of the split operand blocks in encode_*.
        Ea = np.zeros((N, self.nl, kappa * u * w, ring.D), dtype=object)
        Eb = np.zeros((N, self.nl, kappa * w * v, ring.D), dtype=object)
        pole = np.zeros((N, L * uvw, ring.D), dtype=object)
        for i in range(N):
            a_i = alphas[i].astype(object)
            for l in range(self.nl):
                xs = [
                    ring.s_sub(betas[l * kappa + k].astype(object), a_i)
                    for k in range(kappa)
                ]
                delta = ring.s_one()
                for x in xs:
                    delta = ring.s_mul(delta, x)
                dpow = ring.s_pow(delta, uvw)  # Delta_l^{uvw}
                for k in range(kappa):
                    g = l * kappa + k
                    xinv = ring.s_inv(xs[k])
                    xp = [None, xinv]  # xp[m] = (beta_g - alpha_i)^{-m}
                    for _ in range(uvw - 1):
                        xp.append(ring.s_mul(xp[-1], xinv))
                    for m in range(1, uvw + 1):
                        pole[i, g * uvw + (m - 1)] = xp[m]
                    # x^{e - uvw} = xinv^{uvw - e}; every EP exponent is
                    # <= uvw - 1, so the shifted power stays negative
                    for a, e in enumerate(exp_f):
                        Ea[i, l, k * u * w + a] = ring.s_mul(dpow, xp[uvw - e])
                    for b, e in enumerate(exp_g):
                        Eb[i, l, k * w * v + b] = xp[uvw - e]
        self.Ea = jnp.asarray(as_u32(Ea))  # (N, nl, kappa*u*w, D)
        self.Eb = jnp.asarray(as_u32(Eb))  # (N, nl, kappa*w*v, D)

        # decode basis: per product g the pole columns x_g^{-m} (m=1..uvw),
        # then a Vandermonde block absorbing the polynomial interference of
        # degree <= (kappa-1)uvw + w - 2 (absent when that is negative)
        polyK = (kappa - 1) * uvw + w - 1
        if polyK > 0:
            V = s_vandermonde(ring, alphas, polyK)  # (N, polyK, D)
            M = np.concatenate([pole, V], axis=1)
        else:
            M = pole
        assert M.shape[1] == self.R, (M.shape, self.R)
        self.M = jnp.asarray(as_u32(M))  # (N, R, D)

        # per-product Toeplitz recovery: the solved pole coefficients
        # Gamma'_{g,e} (e = uvw - pole order) relate to product g's EP
        # convolution coefficients h_d by Gamma'_e = sum_d rho_{e-d} h_d,
        # rho = coefficients of prod_{g' != g, same group}
        # ((beta_{g'} - beta_g) + x)^{uvw} — lower-triangular Toeplitz with
        # unit diagonal rho_0.  T[g] holds the truncated power-series
        # inverse sigma as T[d, e] = sigma_{d-e}, so h = T @ Gamma'.
        T = np.zeros((L, uvw, uvw, ring.D), dtype=object)
        for g in range(L):
            l, k = divmod(g, kappa)
            cs = [
                ring.s_sub(
                    betas[l * kappa + k2].astype(object), betas[g].astype(object)
                )
                for k2 in range(kappa)
                if k2 != k
            ]
            rho = _trunc_pow_prod(ring, cs, uvw, uvw)
            sigma = _series_inv(ring, rho, uvw)
            for d in range(uvw):
                for e in range(d + 1):
                    T[g, d, e] = sigma[d - e]
        self.Tinv = jnp.asarray(as_u32(T))  # (L, uvw, uvw, D)

    # -- partitioning ---------------------------------------------------------

    def _split_a(self, As: jnp.ndarray) -> jnp.ndarray:
        """(L, t, r, D) -> (L, uw, t/u, r/w, D), ordered to match exp_f."""
        L, t, r, D = As.shape
        u, w = self.u, self.w
        if L != self.L or t % u or r % w:
            raise ValueError(
                f"As {As.shape} not partitionable by (L={self.L}, u={u}, w={w})"
            )
        blocks = As.reshape(L, u, t // u, w, r // w, D)
        return blocks.transpose(0, 1, 3, 2, 4, 5).reshape(
            L, u * w, t // u, r // w, D
        )

    def _split_b(self, Bs: jnp.ndarray) -> jnp.ndarray:
        """(L, r, s, D) -> (L, wv, r/w, s/v, D), ordered to match exp_g."""
        L, r, s, D = Bs.shape
        w, v = self.w, self.v
        if L != self.L or r % w or s % v:
            raise ValueError(
                f"Bs {Bs.shape} not partitionable by (L={self.L}, w={w}, v={v})"
            )
        blocks = Bs.reshape(L, w, r // w, v, s // v, D)
        return blocks.transpose(0, 1, 3, 2, 4, 5).reshape(
            L, w * v, r // w, s // v, D
        )

    # -- encode ---------------------------------------------------------------

    def encode_a(self, As: jnp.ndarray) -> jnp.ndarray:
        """As (L, t, r, D) -> block-concat shares (N, t/u, nl * r/w, D)."""
        blocks = self._split_a(As)  # (L, uw, tb, rb, D)
        L, K, tb, rb, D = blocks.shape
        grp = blocks.reshape(self.nl, self.kappa * K, tb * rb, D)
        out = vmap(self.ring.matmul, in_axes=(1, 0))(self.Ea, grp)
        out = out.reshape(self.nl, self.N, tb, rb, D)
        return out.transpose(1, 2, 0, 3, 4).reshape(
            self.N, tb, self.nl * rb, D
        )

    def encode_b(self, Bs: jnp.ndarray) -> jnp.ndarray:
        """Bs (L, r, s, D) -> block-concat shares (N, nl * r/w, s/v, D)."""
        blocks = self._split_b(Bs)  # (L, wv, rb, sb, D)
        L, K, rb, sb, D = blocks.shape
        grp = blocks.reshape(self.nl, self.kappa * K, rb * sb, D)
        out = vmap(self.ring.matmul, in_axes=(1, 0))(self.Eb, grp)
        out = out.reshape(self.nl, self.N, rb, sb, D)
        return out.transpose(1, 0, 2, 3, 4).reshape(
            self.N, self.nl * rb, sb, D
        )

    def encode_a_at(self, As: jnp.ndarray, i) -> jnp.ndarray:
        """Worker i's fa_i only (``i`` may be a tracer)."""
        blocks = self._split_a(As)
        L, K, tb, rb, D = blocks.shape
        grp = blocks.reshape(self.nl, self.kappa * K, tb * rb, D)
        row = lax.dynamic_index_in_dim(self.Ea, i, axis=0, keepdims=False)
        out = vmap(lambda e, g: self.ring.matmul(e[None], g)[0])(row, grp)
        out = out.reshape(self.nl, tb, rb, D)
        return out.transpose(1, 0, 2, 3).reshape(tb, self.nl * rb, D)

    def encode_b_at(self, Bs: jnp.ndarray, i) -> jnp.ndarray:
        blocks = self._split_b(Bs)
        L, K, rb, sb, D = blocks.shape
        grp = blocks.reshape(self.nl, self.kappa * K, rb * sb, D)
        row = lax.dynamic_index_in_dim(self.Eb, i, axis=0, keepdims=False)
        out = vmap(lambda e, g: self.ring.matmul(e[None], g)[0])(row, grp)
        return out.reshape(self.nl * rb, sb, D)

    # -- worker ---------------------------------------------------------------

    def worker_compute(self, FA, GB):
        """(N, tb, nl*rb, D) x (N, nl*rb, sb, D) -> (N, tb, sb, D)."""
        return vmap(self.ring.matmul)(FA, GB)

    # -- decode ---------------------------------------------------------------

    def decode(self, H: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
        """H (R, t/u, s/v, D) from workers idx (R,) -> (L, t, s, D).

        Guarded like :meth:`CSACode.decode`: duplicate live-set indices
        raise host-side whenever ``idx`` is concrete, and ``gr_solve``
        raises on any singular system when called eagerly.
        """
        _check_live_set(idx)
        ring = self.ring
        R, tb, sb, D = H.shape
        assert R == self.R, (R, self.R)
        M = jnp.take(self.M, idx, axis=0)  # (R, R, D)
        X = gr_solve(ring, M, H.reshape(R, tb * sb, D))  # (R, tb*sb, D)
        P = X[: self.L * self.uvw].reshape(self.L, self.uvw, tb * sb, D)
        # P[g, m-1] is the coefficient of (beta_g - alpha)^{-m}; flipping m
        # gives Gamma'[g, e] (e = uvw - m), the Toeplitz image of the EP
        # convolution coefficients h — undone by the precomputed inverse
        h = vmap(ring.matmul)(self.Tinv, jnp.flip(P, axis=1))
        h = h.reshape(self.L, self.uvw, tb, sb, D)
        cb = jnp.take(h, jnp.asarray(self.exp_c.ravel()), axis=1)
        cb = cb.reshape(self.L, self.u, self.v, tb, sb, D)
        return cb.transpose(0, 1, 3, 2, 4, 5).reshape(
            self.L, self.u * tb, self.v * sb, D
        )

    # -- end to end -----------------------------------------------------------

    def run(self, As, Bs, idx: Optional[jnp.ndarray] = None):
        FA, GB = self.encode_a(As), self.encode_b(Bs)
        H = self.worker_compute(FA, GB)
        if idx is None:
            idx = jnp.arange(self.R, dtype=jnp.int32)
        return self.decode(jnp.take(H, idx, axis=0), idx)

    def costs(self, spec) -> EPCosts:
        return gcsa_cost_model(
            spec.t, spec.r, spec.s, self.u, self.v, self.w, self.L,
            self.kappa, self.N, self.ring.D / spec.ring.D,
        )


def gcsa_cost_model(
    t: int, r: int, s: int, u: int, v: int, w: int,
    n: int, kappa: int, N: int, m_eff: float,
) -> EPCosts:
    """Table-1 GCSA costs, per product, in base-ring elements.

    R = uvw(n + kappa - 1) + w - 1 with the batch grouped into
    ell = n/kappa groups of kappa.  Each worker holds ONE pair of
    block-concatenated shares fa (t/u, ell*r/w) and gb (ell*r/w, s/v) —
    see :class:`GCSACode` — so, per product (divide totals by n and use
    ell/n = 1/kappa):

      upload   N * (tb*rb + rb*sb) * m_eff / kappa
      download R * tb*sb * m_eff / n
      encode   N * (uw*tb*rb + wv*rb*sb) * m_eff^2      (kappa*ell = n)
      decode   R^2 * tb*sb * m_eff^2 / n                (one gr_solve)
      worker   tb*rb*sb * m_eff^2 / kappa

    (The pre-audit formulas scaled upload/encode/worker by n/kappa instead
    — double-counting the batch: at the kappa = n CSA point they priced
    the whole batch's upload per *product*.  Pinned against the
    executable code's true share shapes in tests/test_codes.py.)

    GCSA needs >= N + n exceptional points (vs N for Batch-EP_RMFE).
    """
    if n % kappa:
        raise ValueError(f"kappa={kappa} must divide the batch n={n}")
    R = u * v * w * (n + kappa - 1) + w - 1
    tb, rb, sb = t // u, r // w, s // v
    up = N * (tb * rb + rb * sb) * m_eff / kappa
    down = R * tb * sb * m_eff / n
    enc = N * (tb * rb * u * w + rb * sb * w * v) * m_eff**2
    dec = R * R * tb * sb * m_eff**2 / n
    worker = tb * rb * sb * m_eff**2 / kappa
    return EPCosts(N, R, m_eff, up, down, enc, dec, worker)
