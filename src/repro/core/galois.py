"""Galois ring arithmetic GR(p^e, d) and extension towers, in JAX.

Representation
--------------
An element of ``GR(p^e, d * m_1 * ... * m_L)`` is a flat coefficient vector of
length ``D = d * prod(m_k)`` with entries in ``Z_{p^e}`` (dtype uint32).  The
ring is built as a *tower*::

    Z_{p^e}[x]/(f)            -- degree d,   f irreducible mod p
      [y_1]/(g_1)             -- degree m_1, g_1 irreducible mod p, gcd(m_1, d)=1
        [y_2]/(g_2)           -- degree m_2, gcd(m_2, d*m_1)=1 ...

All moduli have coefficients in {0..p-1} (lifts of GF(p) polynomials).  A
degree-m polynomial irreducible over GF(p) stays irreducible over GF(p^D0)
iff gcd(m, D0) = 1, so every tower level only needs a *prime-field*
irreducibility search (Rabin test).  Because the moduli have scalar
coefficients, reduction never mixes tower levels and the reduction of a
product factorises as a Kronecker product of per-level power-reduction
matrices (``FOLD``).

Multiplication = multi-level coefficient convolution (positions ``CONVPOS``)
followed by the linear ``FOLD`` map.  Structure constants
``T[i,j,k] = FOLD[CONVPOS[i,j], k]`` are also materialised for scalar paths.

Exceptional sets
----------------
Instead of the Teichmuller set (needs a primitive root of GF(p^D)), we use
digit lifts: the i-th point is the base-p digit vector of i.  Two distinct
digit vectors differ in some coordinate by a value in {1..p-1}, which is
non-zero mod p, hence the difference is a unit.  This gives the same maximal
cardinality p^D used by the paper and is jit-constant.

Overflow discipline
-------------------
* p = 2, e <= 32: uint32 arithmetic wraps mod 2^32 and 2^e | 2^32, so all
  intermediate sums are exact; a single mask is applied at the end.
* general p^e <= 2^12: products fit uint32; contractions are chunked so that
  partial sums never exceed 2^32 before an explicit ``% q``.
"""
from __future__ import annotations

import math
from functools import lru_cache, partial
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

__all__ = [
    "Ring",
    "make_ring",
    "find_irreducible_gfp",
    "is_irreducible_gfp",
]

# ---------------------------------------------------------------------------
# GF(p)[x] utilities (host-side, numpy int64 coefficient arrays, index=degree)
# ---------------------------------------------------------------------------


def _poly_trim(a: np.ndarray) -> np.ndarray:
    nz = np.nonzero(a)[0]
    if len(nz) == 0:
        return a[:1] * 0
    return a[: nz[-1] + 1]


def _poly_mulmod(a: np.ndarray, b: np.ndarray, f: np.ndarray, p: int) -> np.ndarray:
    """(a*b) mod f over GF(p); f monic."""
    prod = np.convolve(a.astype(np.int64), b.astype(np.int64)) % p
    return _poly_mod(prod, f, p)


def _poly_mod(a: np.ndarray, f: np.ndarray, p: int) -> np.ndarray:
    a = a.astype(np.int64) % p
    d = len(f) - 1
    a = a.copy()
    for k in range(len(a) - 1, d - 1, -1):
        c = a[k]
        if c:
            a[k - d : k + 1] = (a[k - d : k + 1] - c * f) % p
    out = a[:d]
    if len(out) < d:
        out = np.pad(out, (0, d - len(out)))
    return out


def _poly_powmod(a: np.ndarray, n: int, f: np.ndarray, p: int) -> np.ndarray:
    result = np.zeros(len(f) - 1, dtype=np.int64)
    result[0] = 1
    base = _poly_mod(a, f, p)
    while n:
        if n & 1:
            result = _poly_mulmod(result, base, f, p)
        base = _poly_mulmod(base, base, f, p)
        n >>= 1
    return result


def _poly_gcd(a: np.ndarray, b: np.ndarray, p: int) -> np.ndarray:
    a, b = _poly_trim(a % p), _poly_trim(b % p)
    while len(b) > 1 or (len(b) == 1 and b[0] != 0):
        # make b monic
        inv_lead = pow(int(b[-1]), p - 2, p)
        bm = (b * inv_lead) % p
        # a mod bm
        r = a.astype(np.int64) % p
        db = len(bm) - 1
        r = r.copy()
        for k in range(len(r) - 1, db - 1, -1):
            c = r[k]
            if c:
                r[k - db : k + 1] = (r[k - db : k + 1] - c * bm) % p
        r = _poly_trim(r[:db] if db > 0 else r[:1] * 0)
        a, b = bm, r
    return a


def _prime_factors(n: int) -> Tuple[int, ...]:
    out = []
    d = 2
    while d * d <= n:
        while n % d == 0:
            if not out or out[-1] != d:
                out.append(d)
            n //= d
        d += 1
    if n > 1:
        out.append(n)
    return tuple(out)


def is_irreducible_gfp(f: np.ndarray, p: int) -> bool:
    """Rabin irreducibility test for a monic polynomial over GF(p)."""
    n = len(f) - 1
    if n <= 0:
        return False
    x = np.array([0, 1], dtype=np.int64)
    # x^(p^n) == x (mod f)
    xq = _poly_powmod(x, p**n, f, p)
    xx = _poly_mod(x, f, p)
    if not np.array_equal(xq, xx):
        return False
    for ell in _prime_factors(n):
        h = _poly_powmod(x, p ** (n // ell), f, p)
        diff = (h - xx) % p
        g = _poly_gcd(f.astype(np.int64), diff, p)
        if not (len(_poly_trim(g)) == 1 and _poly_trim(g)[0] != 0):
            return False
    return True


@lru_cache(maxsize=None)
def find_irreducible_gfp(p: int, d: int) -> Tuple[int, ...]:
    """Deterministic search for a monic degree-d irreducible over GF(p).

    Returns the coefficient tuple (len d+1, entries in 0..p-1, monic).
    """
    if d == 1:
        return (0, 1)  # x
    # iterate low coefficients as base-p counter; constant term must be != 0
    for c in range(p ** d):
        digits = []
        cc = c
        for _ in range(d):
            digits.append(cc % p)
            cc //= p
        if digits[0] == 0:
            continue
        f = np.array(digits + [1], dtype=np.int64)
        if is_irreducible_gfp(f, p):
            return tuple(int(v) for v in f)
    raise RuntimeError(f"no irreducible polynomial found for p={p}, d={d}")


# ---------------------------------------------------------------------------
# The Ring class
# ---------------------------------------------------------------------------


def _power_reduction_matrix(f: Sequence[int], q: int) -> np.ndarray:
    """Rows r = 0..2d-2: coefficients of x^r mod f, over Z_q. Shape (2d-1, d)."""
    f = np.array(f, dtype=object)
    d = len(f) - 1
    rows = np.zeros((2 * d - 1, d), dtype=object)
    cur = np.zeros(d, dtype=object)
    cur[0] = 1
    rows[0] = cur
    for r in range(1, 2 * d - 1):
        nxt = np.zeros(d, dtype=object)
        nxt[1:] = cur[: d - 1]
        top = cur[d - 1]
        if top:
            # x^d = -(f[0] + f[1] x + ... + f[d-1] x^{d-1}) mod q
            for i in range(d):
                nxt[i] = (nxt[i] - top * f[i]) % q
        nxt %= q
        rows[r] = nxt
        cur = nxt
    return rows


class Ring:
    """GR(p^e, D) with D = prod(degrees), tower representation (see module doc).

    All jnp methods are jit-traceable; ``s_*`` methods are host-side exact
    python-int mirrors used for setup-time constant computation.
    """

    def __init__(self, p: int, e: int, degrees: Tuple[int, ...]):
        degrees = tuple(int(d) for d in degrees if int(d) > 1)
        self.p = int(p)
        self.e = int(e)
        self.q = p**e
        self.degrees = degrees
        self.D = int(np.prod(degrees)) if degrees else 1
        self.p2fast = (p == 2 and e <= 32)
        if not self.p2fast and self.q > (1 << 12):
            raise NotImplementedError(
                f"general modulus q={self.q} > 2^12 needs wider accumulators; "
                "use p=2, e<=32 for the machine-word fast path"
            )
        self.dtype = jnp.uint32
        self._mask = np.uint32(2**e - 1) if (p == 2 and e < 32) else None

        # validate coprimality of tower degrees
        acc = 1
        self.moduli = []
        for m in degrees:
            if acc > 1 and math.gcd(m, acc) != 1:
                raise ValueError(
                    f"tower degree {m} not coprime with lower degrees (prod={acc}); "
                    "use Ring.extend() which auto-adjusts"
                )
            self.moduli.append(find_irreducible_gfp(p, m))
            acc *= m

        self._build_tables()

    # -- construction of CONVPOS / FOLD / T --------------------------------

    def _build_tables(self):
        q = self.q
        if not self.degrees:
            self.conv_shape = (1,)
            self.K = 1
            self.CONVPOS = np.zeros((1, 1), dtype=np.int32)
            self.FOLD = np.ones((1, 1), dtype=object)
        else:
            # Flat coefficient layout: innermost (base, degrees[0]) level is the
            # FASTEST-varying axis; the outermost extension is the slowest.
            shapes_rev = tuple(reversed(self.degrees))  # outer ... inner
            conv_shape = tuple(2 * m - 1 for m in shapes_rev)
            K = int(np.prod(conv_shape))
            D = self.D
            idx = np.arange(D)
            multis = np.stack(np.unravel_index(idx, shapes_rev), axis=-1)  # (D, L)
            conv_pos = np.zeros((D, D), dtype=np.int64)
            for i in range(D):
                summed = multis[i][None, :] + multis  # (D, L)
                conv_pos[i] = np.ravel_multi_index(
                    tuple(summed[:, k] for k in range(summed.shape[1])), conv_shape
                )
            self.conv_shape = conv_shape
            self.K = K
            self.CONVPOS = conv_pos.astype(np.int32)
            # FOLD = kron over levels, outermost first so innermost lands inner
            fold = np.ones((1, 1), dtype=object)
            for m, modulus in zip(shapes_rev, reversed(self.moduli)):
                red = _power_reduction_matrix(modulus, q)  # (2m-1, m)
                A0, B0 = fold.shape
                C0, D0 = red.shape
                newf = np.zeros((A0 * C0, B0 * D0), dtype=object)
                for a in range(A0):
                    for b in range(B0):
                        if fold[a, b]:
                            newf[a * C0 : (a + 1) * C0, b * D0 : (b + 1) * D0] = (
                                fold[a, b] * red
                            ) % q
                fold = newf
            assert fold.shape == (K, D), (fold.shape, K, D)
            self.FOLD = fold % q

        # structure constants T[i,j,k] = FOLD[CONVPOS[i,j], k]
        D = self.D
        T = np.zeros((D, D, D), dtype=object)
        for i in range(D):
            T[i] = self.FOLD[self.CONVPOS[i]]
        self.T = T

        # jnp constants
        self.FOLDJ = jnp.asarray(self.FOLD.astype(np.uint32))
        self.CONVJ = jnp.asarray(self.CONVPOS)
        self.TJ = jnp.asarray(T.astype(np.uint32))

        # chunking for general-q contractions
        if self.p2fast:
            self.max_terms = None
        else:
            self.max_terms = max(1, (2**32 - 1) // ((self.q - 1) ** 2))

    # -- basics -------------------------------------------------------------

    def __repr__(self):
        return f"GR({self.p}^{self.e}, {self.D}) degrees={self.degrees}"

    def __eq__(self, other):
        return (
            isinstance(other, Ring)
            and (self.p, self.e, self.degrees) == (other.p, other.e, other.degrees)
        )

    def __hash__(self):
        return hash((self.p, self.e, self.degrees))

    @property
    def size(self) -> int:
        return self.q**self.D

    def extend(self, m: int) -> "Ring":
        """Extension of degree >= m with the coprimality constraint auto-fixed."""
        if m <= 1:
            return self
        mm = m
        while math.gcd(mm, self.D) != 1:
            mm += 1
        return make_ring(self.p, self.e, self.degrees + (mm,))

    @property
    def ext_degree_of_top(self) -> int:
        return self.degrees[-1] if self.degrees else 1

    def base_ring(self) -> "Ring":
        if not self.degrees:
            return self
        return make_ring(self.p, self.e, self.degrees[:-1])

    # -- host-side exact scalar ops (python ints) ---------------------------

    def s_zero(self) -> np.ndarray:
        return np.zeros(self.D, dtype=object)

    def s_one(self) -> np.ndarray:
        z = self.s_zero()
        z[0] = 1
        return z

    def s_from_int(self, v: int) -> np.ndarray:
        z = self.s_zero()
        z[0] = v % self.q
        return z

    def s_add(self, a, b) -> np.ndarray:
        return (np.asarray(a, dtype=object) + np.asarray(b, dtype=object)) % self.q

    def s_sub(self, a, b) -> np.ndarray:
        return (np.asarray(a, dtype=object) - np.asarray(b, dtype=object)) % self.q

    def s_mul(self, a, b) -> np.ndarray:
        a = np.asarray(a, dtype=object)
        b = np.asarray(b, dtype=object)
        conv = np.zeros(self.K, dtype=object)
        for i in range(self.D):
            ai = a[i]
            if ai:
                pos = self.CONVPOS[i]
                for j in range(self.D):
                    bj = b[j]
                    if bj:
                        conv[pos[j]] += ai * bj
        out = np.zeros(self.D, dtype=object)
        for c in range(self.K):
            v = conv[c]
            if v:
                out = out + v * self.FOLD[c]
        return out % self.q

    def s_pow(self, a, n: int) -> np.ndarray:
        result = self.s_one()
        base = np.asarray(a, dtype=object) % self.q
        while n:
            if n & 1:
                result = self.s_mul(result, base)
            base = self.s_mul(base, base)
            n >>= 1
        return result

    def s_is_unit(self, a) -> bool:
        return any(int(v) % self.p for v in np.asarray(a).ravel())

    def s_inv(self, a) -> np.ndarray:
        """Inverse of a unit: Fermat inverse mod p + Hensel lifting."""
        if not self.s_is_unit(a):
            raise ZeroDivisionError("not a unit in " + repr(self))
        # inverse mod p via Fermat in GF(p^D)
        x = self.s_pow(a, self.p**self.D - 2)
        # Hensel: x <- x(2 - a x), doubling p-adic precision
        two = self.s_from_int(2)
        k = 1
        while k < self.e:
            ax = self.s_mul(a, x)
            x = self.s_mul(x, self.s_sub(two, ax))
            k *= 2
        return x % self.q

    def s_matmul(self, A: np.ndarray, B: np.ndarray) -> np.ndarray:
        """Host matmul of (t,r,D) x (r,s,D) object arrays."""
        t, r, _ = A.shape
        r2, s, _ = B.shape
        assert r == r2
        out = np.zeros((t, s, self.D), dtype=object)
        for i in range(t):
            for j in range(s):
                acc = self.s_zero()
                for k in range(r):
                    acc = self.s_add(acc, self.s_mul(A[i, k], B[k, j]))
                out[i, j] = acc
        return out

    # -- exceptional set -----------------------------------------------------

    def exceptional_points(self, count: int) -> np.ndarray:
        """First ``count`` digit-lift points; pairwise differences are units.

        Returns uint32 array (count, D).
        """
        if count > self.p**self.D:
            raise ValueError(
                f"need {count} exceptional points but |T| = {self.p}^{self.D}"
            )
        pts = np.zeros((count, self.D), dtype=np.uint32)
        for i in range(count):
            c = i
            for k in range(self.D):
                pts[i, k] = c % self.p
                c //= self.p
        return pts

    # -- device-side helpers --------------------------------------------------

    def _modq(self, x: jnp.ndarray) -> jnp.ndarray:
        if self.p2fast:
            if self._mask is not None:
                return x & self._mask
            return x
        return x % jnp.uint32(self.q)

    def mask_final(self, x: jnp.ndarray) -> jnp.ndarray:
        return self._modq(x)

    def _chunk_dot(self, X: jnp.ndarray, Y: jnp.ndarray) -> jnp.ndarray:
        """(a, b) @ (b, c) with overflow-safe accumulation, reduced output."""
        if self.p2fast:
            return lax.dot(X, Y, preferred_element_type=jnp.uint32)
        b = X.shape[-1]
        mt = self.max_terms
        if b <= mt:
            return lax.dot(X, Y, preferred_element_type=jnp.uint32) % jnp.uint32(self.q)
        nchunk = -(-b // mt)
        pad = nchunk * mt - b
        Xp = jnp.pad(X, ((0, 0), (0, pad)))
        Yp = jnp.pad(Y, ((0, pad), (0, 0)))
        Xc = Xp.reshape(X.shape[0], nchunk, mt)
        Yc = Yp.reshape(nchunk, mt, Y.shape[1])

        def body(carry, xy):
            xc, yc = xy
            d = lax.dot(xc, yc, preferred_element_type=jnp.uint32) % jnp.uint32(self.q)
            return (carry + d) % jnp.uint32(self.q), None

        init = jnp.zeros((X.shape[0], Y.shape[1]), dtype=jnp.uint32)
        out, _ = lax.scan(body, init, (jnp.moveaxis(Xc, 1, 0), Yc))
        return out

    # -- elementwise ops -------------------------------------------------------

    def zeros(self, shape: Tuple[int, ...]) -> jnp.ndarray:
        return jnp.zeros(tuple(shape) + (self.D,), dtype=self.dtype)

    def ones(self, shape: Tuple[int, ...]) -> jnp.ndarray:
        z = np.zeros(tuple(shape) + (self.D,), dtype=np.uint32)
        z[..., 0] = 1
        return jnp.asarray(z)

    def add(self, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
        return self._modq(a + b)

    def sub(self, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
        if self.p2fast:
            return self._modq(a - b)  # wraps correctly mod 2^e
        return (a + jnp.uint32(self.q) - b) % jnp.uint32(self.q)

    def neg(self, a: jnp.ndarray) -> jnp.ndarray:
        if self.p2fast:
            return self._modq(jnp.uint32(0) - a)
        return (jnp.uint32(self.q) - a) % jnp.uint32(self.q)

    # ``mul`` and ``matmul`` are jitted with the ring as a static argument
    # (rings hash by (p, e, degrees)): an eager call compiles once per
    # (ring, shapes, dtypes) instead of re-tracing its loop body every time,
    # and a call under an outer jit is inlined into the caller's program.

    @partial(jax.jit, static_argnums=0)
    def mul(self, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
        """Elementwise ring product; a, b broadcastable with trailing dim D."""
        a, b = jnp.broadcast_arrays(a, b)
        batch = a.shape[:-1]
        D, K = self.D, self.K
        conv = jnp.zeros(batch + (K,), dtype=jnp.uint32)

        def body(i, conv):
            ai = lax.dynamic_index_in_dim(a, i, axis=a.ndim - 1, keepdims=True)
            contrib = ai * b  # (..., D)
            if not self.p2fast:
                contrib = contrib % jnp.uint32(self.q)
            pos = self.CONVJ[i]
            return conv.at[..., pos].add(contrib)

        conv = lax.fori_loop(0, D, body, conv)
        conv = self._modq(conv)
        flat = conv.reshape(-1, K)
        out = self._chunk_dot(flat, self.FOLDJ)
        return self._modq(out.reshape(batch + (D,)))

    @partial(jax.jit, static_argnums=0)
    def matmul(self, A: jnp.ndarray, B: jnp.ndarray) -> jnp.ndarray:
        """Ring matmul: (t, r, D) x (r, s, D) -> (t, s, D)."""
        t, r, D = A.shape
        r2, s, D2 = B.shape
        assert r == r2 and D == D2 == self.D, (A.shape, B.shape, self.D)
        K = self.K
        Bf = B.reshape(r, s * D)
        conv = jnp.zeros((t, s, K), dtype=jnp.uint32)

        def body(i, conv):
            Ai = lax.dynamic_index_in_dim(A, i, axis=2, keepdims=False)  # (t, r)
            tmp = self._chunk_dot(Ai, Bf).reshape(t, s, D)
            pos = self.CONVJ[i]
            return conv.at[..., pos].add(tmp)

        conv = lax.fori_loop(0, D, body, conv)
        conv = self._modq(conv)
        out = self._chunk_dot(conv.reshape(t * s, K), self.FOLDJ)
        return self._modq(out.reshape(t, s, D))

    def pow(self, a: jnp.ndarray, n: int) -> jnp.ndarray:
        """Elementwise a**n for a python-int exponent (unrolled square&multiply)."""
        result = jnp.broadcast_to(self.ones(a.shape[:-1]), a.shape)
        base = a
        while n:
            if n & 1:
                result = self.mul(result, base)
            base = self.mul(base, base) if n > 1 else base
            n >>= 1
        return result

    def inv(self, a: jnp.ndarray) -> jnp.ndarray:
        """Elementwise inverse of units (traceable: Fermat mod p + Hensel)."""
        x = self.pow(a, self.p**self.D - 2)
        two = self.scale(self.ones(a.shape[:-1]), 2)
        k = 1
        while k < self.e:
            ax = self.mul(a, x)
            x = self.mul(x, self.sub(two, ax))
            k *= 2
        return x

    def scale(self, a: jnp.ndarray, c: int) -> jnp.ndarray:
        """Multiply by an integer scalar."""
        return self._modq(a * jnp.uint32(c % self.q))

    def random(self, rng: np.random.Generator, shape: Tuple[int, ...]) -> jnp.ndarray:
        arr = rng.integers(0, self.q, size=tuple(shape) + (self.D,), dtype=np.uint64)
        return jnp.asarray(arr.astype(np.uint32))

    def random_jax(self, key: jax.Array, shape: Tuple[int, ...]) -> jnp.ndarray:
        """Uniform ring elements from a ``jax.random`` key (traceable).

        This is the masked-randomness seam used by the secure (T-private)
        schemes: the same key yields the same mask coefficients whether the
        encode runs master-side (``encode_*``) or at-worker
        (``encode_*_at``), so every execution backend produces bit-identical
        codewords from identical keys.
        """
        full = tuple(shape) + (self.D,)
        if self.p == 2:
            # q = 2^e divides 2^32: masking uniform 32-bit words stays uniform
            return self._modq(jax.random.bits(key, full, dtype=jnp.uint32))
        return jax.random.randint(key, full, 0, self.q, dtype=jnp.int32).astype(
            jnp.uint32
        )

    def random_units(self, rng: np.random.Generator, shape: Tuple[int, ...]) -> jnp.ndarray:
        arr = rng.integers(0, self.q, size=tuple(shape) + (self.D,), dtype=np.uint64)
        arr = arr.astype(np.uint32)
        # force constant coefficient to be a unit in Z_q => element is a unit
        c0 = arr[..., 0]
        c0 = c0 - (c0 % self.p) + 1
        arr[..., 0] = c0
        return jnp.asarray(arr)

    # -- embeddings between tower and base ------------------------------------

    def embed_base(self, a: jnp.ndarray, base: "Ring") -> jnp.ndarray:
        """Embed elements of the base ring (trailing dim base.D) into self.

        self must be a tower over ``base`` (degrees prefix match); the image
        occupies the low coefficients.
        """
        assert self.degrees[: len(base.degrees)] == base.degrees
        batch = a.shape[:-1]
        out = jnp.zeros(batch + (self.D,), dtype=self.dtype)
        return out.at[..., : base.D].set(a)

    def tower_coeffs(self, a: jnp.ndarray, base: "Ring") -> jnp.ndarray:
        """View (…, D) as (…, D//base.D, base.D): coefficients over the base."""
        assert self.degrees[: len(base.degrees)] == base.degrees
        t = self.D // base.D
        return a.reshape(a.shape[:-1] + (t, base.D))

    def from_tower_coeffs(self, c: jnp.ndarray) -> jnp.ndarray:
        return c.reshape(c.shape[:-2] + (self.D,))


@lru_cache(maxsize=None)
def make_ring(p: int, e: int, degrees: Tuple[int, ...] = ()) -> Ring:
    return Ring(p, e, degrees)
