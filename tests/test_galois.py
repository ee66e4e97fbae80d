"""Unit tests for the Galois ring core (host + jnp paths)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.galois import (
    Ring,
    make_ring,
    find_irreducible_gfp,
    is_irreducible_gfp,
    _poly_mulmod,
)
from repro.core.polyops import lagrange_coeff_matrix, vandermonde
from repro.dist.chips import compile_counter

RINGS = [
    make_ring(2, 32, ()),          # Z_{2^32}
    make_ring(2, 32, (3,)),        # GR(2^32, 3)
    make_ring(2, 8, (4,)),         # GR(2^8, 4)
    make_ring(2, 32, (3, 5)),      # tower GR(2^32, 15)
    make_ring(3, 2, (2,)),         # GR(9, 2), odd p general path
    make_ring(5, 1, (3,)),         # GF(125): e=1 field case
]


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(0)


def test_find_irreducible():
    for p, d in [(2, 3), (2, 8), (3, 4), (5, 2), (2, 15)]:
        f = np.array(find_irreducible_gfp(p, d), dtype=np.int64)
        assert len(f) == d + 1 and f[-1] == 1
        assert is_irreducible_gfp(f, p)


def test_reducible_detected():
    # x^2 over GF(2) is reducible; x^2+1 = (x+1)^2 over GF(2) reducible
    assert not is_irreducible_gfp(np.array([0, 0, 1], dtype=np.int64), 2)
    assert not is_irreducible_gfp(np.array([1, 0, 1], dtype=np.int64), 2)
    # x^2+1 irreducible over GF(3)
    assert is_irreducible_gfp(np.array([1, 0, 1], dtype=np.int64), 3)


@pytest.mark.parametrize("ring", RINGS, ids=repr)
def test_ring_axioms_host(ring, rng):
    for _ in range(10):
        a = np.array(rng.integers(0, ring.q, ring.D), dtype=object)
        b = np.array(rng.integers(0, ring.q, ring.D), dtype=object)
        c = np.array(rng.integers(0, ring.q, ring.D), dtype=object)
        ab = ring.s_mul(a, b)
        ba = ring.s_mul(b, a)
        assert np.array_equal(ab, ba)
        assert np.array_equal(ring.s_mul(ab, c), ring.s_mul(a, ring.s_mul(b, c)))
        lhs = ring.s_mul(a, ring.s_add(b, c))
        rhs = ring.s_add(ring.s_mul(a, b), ring.s_mul(a, c))
        assert np.array_equal(lhs, rhs)
        assert np.array_equal(ring.s_mul(a, ring.s_one()), a % ring.q)


@pytest.mark.parametrize("ring", RINGS, ids=repr)
def test_jnp_matches_host_mul(ring, rng):
    a = ring.random(rng, (4, 3))
    b = ring.random(rng, (4, 3))
    out = np.asarray(ring.mul(a, b))
    an, bn = np.asarray(a), np.asarray(b)
    for i in range(4):
        for j in range(3):
            expect = ring.s_mul(
                an[i, j].astype(object), bn[i, j].astype(object)
            ).astype(np.uint64) % ring.q
            assert np.array_equal(out[i, j].astype(np.uint64), expect), (i, j)


@pytest.mark.parametrize("ring", RINGS, ids=repr)
def test_jnp_matmul_matches_host(ring, rng):
    t, r, s = 3, 4, 2
    A = ring.random(rng, (t, r))
    B = ring.random(rng, (r, s))
    C = np.asarray(ring.matmul(A, B)).astype(object)
    Ch = ring.s_matmul(np.asarray(A).astype(object), np.asarray(B).astype(object))
    assert np.array_equal(C % ring.q, Ch % ring.q)


def test_field_case_matches_poly_mulmod(rng):
    """For e=1 single-level rings, ring mult == GF(p)[x] mulmod (independent path)."""
    ring = make_ring(5, 1, (3,))
    f = np.array(ring.moduli[0], dtype=np.int64)
    for _ in range(20):
        a = rng.integers(0, 5, 3).astype(np.int64)
        b = rng.integers(0, 5, 3).astype(np.int64)
        expect = _poly_mulmod(a, b, f, 5)
        got = ring.s_mul(a.astype(object), b.astype(object)).astype(np.int64)
        assert np.array_equal(got, expect)


@pytest.mark.parametrize("ring", RINGS, ids=repr)
def test_inverse_host_and_jnp(ring, rng):
    a = ring.random_units(rng, (5,))
    ah = np.asarray(a).astype(object)
    one = ring.s_one()
    for i in range(5):
        inv = ring.s_inv(ah[i])
        assert np.array_equal(ring.s_mul(ah[i], inv), one)
    inv_j = ring.inv(a)
    prod = np.asarray(ring.mul(a, inv_j)).astype(np.uint64)
    expect = np.zeros((5, ring.D), dtype=np.uint64)
    expect[:, 0] = 1
    assert np.array_equal(prod % ring.q, expect)


@pytest.mark.parametrize("ring", RINGS, ids=repr)
def test_exceptional_points(ring):
    n = min(16, ring.p ** ring.D)
    pts = ring.exceptional_points(n)
    assert pts.shape == (n, ring.D)
    # all pairwise differences must be units (inverse exists)
    for i in range(n):
        for j in range(i):
            d = ring.s_sub(pts[i].astype(object), pts[j].astype(object))
            inv = ring.s_inv(d)  # raises if not a unit
            assert np.array_equal(ring.s_mul(d, inv), ring.s_one())


def test_exceptional_points_exhausted():
    ring = make_ring(2, 32, ())
    with pytest.raises(ValueError):
        ring.exceptional_points(3)  # |T| = 2 for Z_{2^e}


def test_embed_base_is_ring_hom(rng):
    base = make_ring(2, 32, (3,))
    ext = base.extend(4)
    assert ext.degrees == (3, 4)
    a = base.random(rng, (4,))
    b = base.random(rng, (4,))
    ea, eb = ext.embed_base(a, base), ext.embed_base(b, base)
    lhs = ext.mul(ea, eb)
    rhs = ext.embed_base(base.mul(a, b), base)
    assert np.array_equal(np.asarray(lhs), np.asarray(rhs))


def test_extend_coprime_adjustment():
    base = make_ring(2, 32, (3,))
    ext = base.extend(3)  # gcd(3,3)!=1 -> bumps to 4
    assert ext.degrees == (3, 4)
    ext2 = base.extend(5)
    assert ext2.degrees == (3, 5)


def test_tower_coeffs_roundtrip(rng):
    base = make_ring(2, 16, (3,))
    ext = base.extend(5)
    a = ext.random(rng, (2, 2))
    c = ext.tower_coeffs(a, base)
    assert c.shape == (2, 2, 5, 3)
    back = ext.from_tower_coeffs(c)
    assert np.array_equal(np.asarray(a), np.asarray(back))


def test_pow_scalar(rng):
    ring = make_ring(2, 32, (3,))
    a = ring.random(rng, (3,))
    a3 = ring.pow(a, 3)
    expect = ring.mul(ring.mul(a, a), a)
    assert np.array_equal(np.asarray(a3), np.asarray(expect))


def test_scale_and_sub(rng):
    ring = make_ring(3, 2, (2,))
    a = ring.random(rng, (4,))
    z = ring.sub(a, a)
    assert np.all(np.asarray(z) == 0)
    s = ring.scale(a, ring.q - 1)  # == -a
    assert np.array_equal(np.asarray(ring.add(s, a)), np.zeros_like(np.asarray(a)))


def test_jit_traceable(rng):
    ring = make_ring(2, 32, (3,))

    @jax.jit
    def f(a, b):
        return ring.matmul(a, b)

    A = ring.random(rng, (4, 4))
    B = ring.random(rng, (4, 4))
    out = f(A, B)
    assert np.array_equal(np.asarray(out), np.asarray(ring.matmul(A, B)))

    @jax.jit
    def g(a):
        return ring.inv(a)

    a = ring.random_units(rng, (3,))
    assert np.array_equal(np.asarray(g(a)), np.asarray(ring.inv(a)))


@pytest.mark.parametrize(
    "ring",
    [make_ring(2, 32, ()), make_ring(2, 16, (3,)), make_ring(3, 2, (2,))],
    ids=["Z_2^32", "GR(2^16,3)", "GR(9,2)"],
)
def test_repeated_eager_ops_compile_once(ring):
    """Eager ring ops and polyops helpers compile once per (ring, shapes):
    a second round on the same ring and shapes compiles nothing."""
    rng = np.random.default_rng(5)
    a, b = ring.random_units(rng, (4, 3)), ring.random(rng, (4, 3))
    A, B = ring.random(rng, (3, 4)), ring.random(rng, (4, 2))
    pts = jnp.asarray(ring.exceptional_points(2))

    def one_round():
        jax.block_until_ready((
            ring.mul(a, b), ring.matmul(A, B), ring.inv(a),
            lagrange_coeff_matrix(ring, pts), vandermonde(ring, pts, 3),
        ))

    one_round()
    compiles = compile_counter()
    one_round()
    assert compiles() == 0
