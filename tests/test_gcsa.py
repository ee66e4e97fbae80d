"""General-(u, v, w, kappa) GCSA: exactness across the parameter grid,
any-R recovery, bit-exact degenerations (kappa = n -> CSA, L = 1 -> EP
threshold), the singular-system decode guards, and the audited cost model
pinned against the executable code's true share shapes.

Separate module (not test_codes.py) on purpose: the eager decode paths
compile many programs and the suite-wide live-XLA-program bound is
enforced at module boundaries (see tests/conftest.py).
"""
import itertools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import (
    CSACode,
    GCSACode,
    gcsa_cost_model,
    gr_solve,
    make_ring,
)


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(7)


# ------------------------------------------------------------ general GCSA


def batch_ref(ring, As, Bs):
    return jax.vmap(ring.matmul)(As, Bs)


GCSA_CASES = [
    # (ring args, u, v, w, kappa, L, N) — R = uvw(L + kappa - 1) + w - 1
    ((2, 16, (4,)), 2, 2, 1, 1, 2, 10),   # R = 8: inner 2x2 split, per-product poles
    ((2, 16, (4,)), 1, 1, 2, 2, 2, 8),    # R = 7: MatDot inner, one kappa-group
    ((2, 16, (4,)), 2, 1, 2, 1, 2, 12),   # R = 9: asymmetric inner split
    ((2, 16, (4,)), 1, 1, 1, 2, 4, 6),    # R = 5: CSA point via the general path
    ((2, 8, (5,)), 2, 2, 2, 1, 2, 18),    # R = 17: full 3-axis inner split
    ((3, 2, (3,)), 2, 2, 1, 1, 2, 9),     # R = 8: odd p
]


@pytest.mark.parametrize("ringargs,u,v,w,kappa,L,N", GCSA_CASES)
def test_gcsa_general_exact(ringargs, u, v, w, kappa, L, N, rng):
    ring = make_ring(*ringargs)
    code = GCSACode(ring, L=L, N=N, u=u, v=v, w=w, kappa=kappa)
    assert code.R == u * v * w * (L + kappa - 1) + w - 1
    As = ring.random(rng, (L, 4, 4))
    Bs = ring.random(rng, (L, 4, 4))
    Cs = code.run(As, Bs)
    assert np.array_equal(np.asarray(Cs), np.asarray(batch_ref(ring, As, Bs)))


def test_gcsa_general_any_R_subset(rng):
    ring = make_ring(2, 16, (4,))
    code = GCSACode(ring, L=2, N=8, u=1, v=1, w=2, kappa=2)  # R = 7
    As = ring.random(rng, (2, 4, 4))
    Bs = ring.random(rng, (2, 4, 4))
    H = code.worker_compute(code.encode_a(As), code.encode_b(Bs))
    expect = np.asarray(batch_ref(ring, As, Bs))

    @jax.jit
    def dec(idx):
        return code.decode(jnp.take(H, idx, axis=0), idx)

    for subset in itertools.combinations(range(8), 7):
        Cs = dec(jnp.asarray(subset, dtype=jnp.int32))
        assert np.array_equal(np.asarray(Cs), expect), subset


def test_gcsa_general_encode_at_matches_master(rng):
    ring = make_ring(2, 16, (4,))
    code = GCSACode(ring, L=2, N=10, u=2, v=2, w=1, kappa=1)
    As = ring.random(rng, (2, 4, 4))
    Bs = ring.random(rng, (2, 4, 4))
    FA, GB = code.encode_a(As), code.encode_b(Bs)
    for i in range(code.N):
        assert np.array_equal(
            np.asarray(code.encode_a_at(As, i)), np.asarray(FA[i])
        ), i
        assert np.array_equal(
            np.asarray(code.encode_b_at(Bs, i)), np.asarray(GB[i])
        ), i


def test_gcsa_kappa_n_reduces_to_csa_bitwise(rng):
    """(u, v, w) = (1, 1, 1), kappa = n must BE the CSA code: identical
    shares and identical decodes, symbol for symbol."""
    ring = make_ring(2, 16, (4,))
    gen = GCSACode(ring, L=3, N=8, kappa=3)
    csa = CSACode(ring, L=3, N=8)
    assert gen.R == csa.R == 5
    As = ring.random(rng, (3, 4, 4))
    Bs = ring.random(rng, (3, 4, 4))
    FA, GB = gen.encode_a(As), gen.encode_b(Bs)
    assert np.array_equal(np.asarray(FA), np.asarray(csa.encode_a(As)))
    assert np.array_equal(np.asarray(GB), np.asarray(csa.encode_b(Bs)))
    H = gen.worker_compute(FA, GB)
    idx = jnp.asarray([0, 2, 3, 5, 7], dtype=jnp.int32)
    assert np.array_equal(
        np.asarray(gen.decode(jnp.take(H, idx, axis=0), idx)),
        np.asarray(csa.decode(jnp.take(H, idx, axis=0), idx)),
    )


def test_gcsa_degenerate_L1_is_single_ep(rng):
    """L = 1 collapses the outer Cauchy structure: R = uvw + w - 1, the EP
    threshold, and the single product still decodes exactly."""
    ring = make_ring(2, 16, (4,))
    code = GCSACode(ring, L=1, N=8, u=2, v=1, w=2, kappa=1)
    assert code.R == 5  # = R_EP(2, 1, 2)
    As = ring.random(rng, (1, 4, 4))
    Bs = ring.random(rng, (1, 4, 4))
    Cs = code.run(As, Bs)
    assert np.array_equal(np.asarray(Cs), np.asarray(batch_ref(ring, As, Bs)))


def test_gcsa_kappa_1_threshold(rng):
    """kappa = 1 is the per-product-poles end: R = uvw * L + w - 1."""
    ring = make_ring(2, 16, (4,))
    code = GCSACode(ring, L=4, N=8, kappa=1)
    assert code.R == 4
    As = ring.random(rng, (4, 3, 3))
    Bs = ring.random(rng, (4, 3, 3))
    Cs = code.run(As, Bs)
    assert np.array_equal(np.asarray(Cs), np.asarray(batch_ref(ring, As, Bs)))


def test_gcsa_validates_parameters():
    ring = make_ring(2, 16, (4,))
    with pytest.raises(ValueError, match="divide"):
        GCSACode(ring, L=4, N=16, kappa=3)
    with pytest.raises(ValueError, match="R="):
        GCSACode(ring, L=2, N=10, u=2, v=2, w=1, kappa=2)  # R = 12 > 10


# ------------------------------------------------- singular-system guards


def test_gr_solve_singular_raises(rng):
    """A system with no unit pivot must raise, not silently 'invert' a
    non-unit (argmax over an all-False mask selects row 0)."""
    ring = make_ring(2, 16, (3,))
    n = 3
    M = np.asarray(ring.random(rng, (n, n))).astype(np.uint32)
    for i in range(n):
        M[i, i, 0] |= 1
        for j in range(i + 1, n):
            M[i, j] = 0
    M[:, 1] = M[:, 0]  # duplicate column => singular mod p
    Y = ring.random(rng, (n, 2))
    with pytest.raises(ValueError, match="singular"):
        gr_solve(ring, jnp.asarray(M), Y)
    # all-even (non-unit) pivot column, still singular
    M2 = np.array(M)
    M2[:, 1] = 0
    M2[1, 1, 0] = 2
    with pytest.raises(ValueError, match="singular"):
        gr_solve(ring, jnp.asarray(M2), Y)


def test_decode_duplicate_live_set_raises(rng):
    """Duplicate worker indices make the decode system singular; both CSA
    and general-GCSA decode must raise — including under jit, where the
    live set is a concrete closure constant."""
    ring = make_ring(2, 16, (4,))
    csa = CSACode(ring, L=3, N=8)
    As = ring.random(rng, (3, 3, 3))
    Bs = ring.random(rng, (3, 3, 3))
    H = csa.worker_compute(csa.encode_a(As), csa.encode_b(Bs))
    bad = jnp.asarray([0, 0, 1, 2, 3], dtype=jnp.int32)
    with pytest.raises(ValueError, match="singular"):
        csa.decode(jnp.take(H, bad, axis=0), bad)
    with pytest.raises(ValueError, match="singular"):
        jax.jit(lambda h: csa.decode(h, bad))(jnp.take(H, bad, axis=0))
    gen = GCSACode(ring, L=2, N=8, u=1, v=1, w=2, kappa=2)  # R = 7
    As2 = ring.random(rng, (2, 4, 4))
    Bs2 = ring.random(rng, (2, 4, 4))
    Hg = gen.worker_compute(gen.encode_a(As2), gen.encode_b(Bs2))
    badg = jnp.asarray([0, 1, 2, 3, 4, 5, 5], dtype=jnp.int32)
    with pytest.raises(ValueError, match="singular"):
        gen.decode(jnp.take(Hg, badg, axis=0), badg)


# ------------------------------------------------------- GCSA cost model


def test_gcsa_cost_model_matches_true_share_shapes():
    """The audited formulas must agree with the executable code's actual
    share sizes: per worker one (tb, nl*rb) + one (nl*rb, sb) share, so
    per-product upload is N(tb*rb + rb*sb)/kappa base elements at
    m_eff = 1, and the worker contraction runs over nl*rb rows."""
    t = r = s = 8
    for (u, v, w, kappa, L) in [(2, 2, 1, 1, 2), (1, 1, 2, 2, 4), (1, 1, 1, 4, 4)]:
        nl = L // kappa
        tb, rb, sb = t // u, r // w, s // v
        N = u * v * w * (L + kappa - 1) + w - 1  # minimal N = R
        c = gcsa_cost_model(t, r, s, u, v, w, L, kappa, N, m_eff=1.0)
        per_worker_elems = tb * (nl * rb) + (nl * rb) * sb
        assert c.upload * L == N * per_worker_elems, (u, v, w, kappa)
        assert c.worker_ops * L == tb * (nl * rb) * sb, (u, v, w, kappa)
        assert c.download * L == c.R * tb * sb, (u, v, w, kappa)


def test_gcsa_cost_model_paper_points():
    """Pin R and the per-product costs at Table-1 comparison points.

    At (u=v=w=1, kappa=n) GCSA's per-product upload must equal the plain
    per-product upload (t*r + r*s scaled by N*m_eff/n) — the batch is
    amortized across the group, NOT paid once per product (the pre-audit
    formulas multiplied upload/encode/worker by an extra n/kappa)."""
    t = r = s = 64
    n, N, m = 4, 16, 4.0
    c = gcsa_cost_model(t, r, s, 1, 1, 1, n, n, N, m)
    assert c.R == 2 * n - 1
    assert c.upload == N * (t * r + r * s) * m / n
    assert c.encode_ops == N * (t * r + r * s) * m**2
    assert c.worker_ops == t * r * s * m**2 / n
    assert c.decode_ops == c.R**2 * t * s * m**2 / n
    # kappa = 1: per-product poles, R = uvw*n + w - 1, no group amortization
    c1 = gcsa_cost_model(t, r, s, 2, 2, 1, n, 1, N, m)
    assert c1.R == 4 * n
    tb, rb, sb = t // 2, r, s // 2
    assert c1.upload == N * (tb * rb + rb * sb) * m
    assert c1.worker_ops == tb * rb * sb * m**2
    with pytest.raises(ValueError, match="divide"):
        gcsa_cost_model(t, r, s, 1, 1, 1, 4, 3, N, m)


def test_gcsa_threshold_gap_vs_rmfe():
    """The paper's headline: R_GCSA ~ n * R_RMFE at matched partition."""
    from repro.core import ep_cost_model

    for n in (2, 4, 8):
        for (u, v, w) in [(1, 1, 1), (2, 2, 2)]:
            g = gcsa_cost_model(64, 64, 64, u, v, w, n, n, 64, 4.0)
            b = ep_cost_model(64, 64, 64, u, v, w, 64, 4.0, batch=n)
            uvw = u * v * w
            assert g.R == uvw * (2 * n - 1) + w - 1
            assert b.R == uvw + w - 1
            assert g.R / b.R >= n  # at least the 1/n headline factor
