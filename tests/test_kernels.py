"""Pallas gr_matmul kernel vs pure-jnp oracle: shape/ring sweeps + hypothesis.

hypothesis is optional: the deterministic sweeps always run; the
property-based tests skip cleanly when it is not installed.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - exercised on minimal installs
    HAVE_HYPOTHESIS = False

from repro.core.galois import make_ring
from repro.kernels import gr_matmul, gr_matmul_ref, kernel_supported

RINGS = [
    make_ring(2, 32, ()),      # Z_{2^32}, D=1
    make_ring(2, 32, (3,)),    # GR(2^32, 3) — paper's 8-worker ring
    make_ring(2, 32, (4,)),    # GR(2^32, 4) — paper's 16-worker ring
    make_ring(2, 16, (5,)),    # e<32 mask path
    make_ring(2, 8, (2, 3)),   # tower, D=6
]

SHAPES = [
    (8, 8, 8),
    (16, 32, 8),
    (128, 128, 128),
    (7, 13, 5),     # ragged -> exercises padding
    (1, 64, 1),
    (130, 17, 129),  # just past block boundaries
]


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(3)


@pytest.mark.parametrize("ring", RINGS, ids=repr)
@pytest.mark.parametrize("shape", SHAPES)
def test_kernel_matches_ref(ring, shape, rng):
    t, r, s = shape
    A = ring.random(rng, (t, r))
    B = ring.random(rng, (r, s))
    out = gr_matmul(A, B, ring, interpret=True)
    ref = gr_matmul_ref(A, B, ring)
    assert out.shape == ref.shape == (t, s, ring.D)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_kernel_block_sweep(rng):
    ring = make_ring(2, 32, (3,))
    A = ring.random(rng, (32, 64))
    B = ring.random(rng, (64, 16))
    ref = np.asarray(gr_matmul_ref(A, B, ring))
    for blocks in [(8, 8, 8), (16, 16, 64), (32, 16, 32), (8, 16, 64)]:
        out = gr_matmul(A, B, ring, blocks=blocks, interpret=True)
        np.testing.assert_array_equal(np.asarray(out), ref, err_msg=str(blocks))


def test_kernel_fallback_odd_p(rng):
    # outside the envelope there is no silent fallback to the reference:
    # the caller is told to use Ring.matmul
    ring = make_ring(3, 2, (2,))
    assert not kernel_supported(ring)
    A = ring.random(rng, (4, 4))
    B = ring.random(rng, (4, 4))
    with pytest.raises(ValueError, match="outside the kernel envelope"):
        gr_matmul(A, B, ring)


def test_kernel_jit(rng):
    ring = make_ring(2, 32, (3,))

    @jax.jit
    def f(A, B):
        return gr_matmul(A, B, ring, interpret=True)

    A = ring.random(rng, (16, 16))
    B = ring.random(rng, (16, 16))
    np.testing.assert_array_equal(
        np.asarray(f(A, B)), np.asarray(gr_matmul_ref(A, B, ring))
    )


if HAVE_HYPOTHESIS:

    @settings(max_examples=15, deadline=None)
    @given(
        t=st.integers(1, 40),
        r=st.integers(1, 40),
        s=st.integers(1, 40),
        ringix=st.integers(0, len(RINGS) - 1),
        seed=st.integers(0, 2**31 - 1),
    )
    def test_kernel_property(t, r, s, ringix, seed):
        ring = RINGS[ringix]
        g = np.random.default_rng(seed)
        A = ring.random(g, (t, r))
        B = ring.random(g, (r, s))
        out = gr_matmul(A, B, ring, interpret=True)
        ref = gr_matmul_ref(A, B, ring)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    @settings(max_examples=10, deadline=None)
    @given(
        seed=st.integers(0, 2**31 - 1),
        t=st.integers(1, 16),
        r=st.integers(1, 16),
        s=st.integers(1, 16),
    )
    def test_matmul_distributes_property(seed, t, r, s):
        """Hypothesis: ring matmul is bilinear — (A+A')B = AB + A'B."""
        ring = make_ring(2, 32, (3,))
        g = np.random.default_rng(seed)
        A, A2 = ring.random(g, (t, r)), ring.random(g, (t, r))
        B = ring.random(g, (r, s))
        lhs = gr_matmul(ring.add(A, A2), B, ring, interpret=True)
        rhs = ring.add(
            gr_matmul(A, B, ring, interpret=True),
            gr_matmul(A2, B, ring, interpret=True),
        )
        np.testing.assert_array_equal(np.asarray(lhs), np.asarray(rhs))

else:

    @pytest.mark.skip(reason="hypothesis not installed")
    def test_kernel_property():
        pytest.importorskip("hypothesis")

    @pytest.mark.skip(reason="hypothesis not installed")
    def test_matmul_distributes_property():
        pytest.importorskip("hypothesis")
