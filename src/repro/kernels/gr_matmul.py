"""Pallas TPU kernel: blocked Galois-ring matrix multiplication.

This is the CDMM hot loop: every worker computes f(alpha_i) @ g(alpha_i)
over GR(2^e, D) — and encode/decode are themselves ring matmuls against
Vandermonde / Lagrange matrices, so ONE kernel serves all three stages.

TPU adaptation (DESIGN.md §3.1): the paper's NTL implementation is a scalar
tower-field library.  Here a GR matmul is decomposed into D^2 *integer*
matmuls (coefficient outer-convolution) accumulated into a VMEM scratch of
K = prod(2m_l - 1) coefficient planes, folded once per output tile by the
precomputed linear reduction FOLD (K x D).  All matmul operands are laid out
*planar* — (D, t, r) — so the contraction dims are genuine matrix dims and
each partial product is an MXU-shaped ``dot``.

Constraints: p = 2, e <= 32 (uint32 wraparound arithmetic — the machine-word
case the paper targets); D <= MAX_D keeps the unrolled D^2 dot loop bounded.
``ops.gr_matmul`` falls back to the jnp reference outside this envelope.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.galois import Ring

MAX_D = 16  # unrolled D^2 dots per block; beyond this use the jnp reference


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _kernel(a_ref, b_ref, o_ref, acc_ref, *, ring: Ring, nsteps_r: int):
    """Grid (T/bt, S/bs, R/br); planar blocks.

    a_ref: (D, bt, br), b_ref: (D, br, bs), o_ref: (D, bt, bs)
    acc_ref: VMEM scratch (K, bt, bs) uint32 accumulator (conv coefficients).
    """
    D, K = ring.D, ring.K
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]
    b = b_ref[...]
    # coefficient outer-convolution: D^2 MXU dots
    for i in range(D):
        ai = a[i]
        for j in range(D):
            c = int(ring.CONVPOS[i, j])  # static conv plane
            acc_ref[c, :, :] += jax.lax.dot(
                ai, b[j], preferred_element_type=jnp.uint32
            )

    @pl.when(k == nsteps_r - 1)
    def _fold():
        acc = acc_ref[...]  # (K, bt, bs)
        fold = ring.FOLD.astype(np.uint32)  # (K, D) host constant
        out = jnp.zeros(o_ref.shape, dtype=jnp.uint32)
        for d in range(D):
            plane = jnp.zeros(o_ref.shape[1:], dtype=jnp.uint32)
            for c in range(K):
                f = int(fold[c, d])
                if f == 0:
                    continue
                if f == 1:
                    plane += acc[c]
                else:
                    plane += jnp.uint32(f) * acc[c]
            out = out.at[d].set(plane)
        if ring.e < 32:
            out = out & jnp.uint32(2**ring.e - 1)
        o_ref[...] = out


def gr_matmul_planar(
    A: jnp.ndarray,
    B: jnp.ndarray,
    ring: Ring,
    *,
    bt: int = 128,
    bs: int = 128,
    br: int = 128,
    interpret: bool = True,
) -> jnp.ndarray:
    """Planar GR matmul: A (D, T, R), B (D, R, S) -> (D, T, S).

    Block sizes need not divide the dims: oversized blocks are clamped to
    the 8-aligned dim and the operands are zero-padded up to block
    multiples (zeros contribute zero to the coefficient convolution), so
    autotuner candidates and odd-shaped CDMM tiles never crash the kernel
    path.  The output is sliced back to the input (T, S).
    """
    if ring.p != 2 or ring.e > 32:
        raise ValueError("kernel supports the machine-word case p=2, e<=32")
    if ring.D > MAX_D:
        raise ValueError(f"D={ring.D} > MAX_D={MAX_D}; use the jnp reference")
    D, T, R = A.shape
    _, R2, S = B.shape
    assert R == R2 and D == ring.D
    bt = min(bt, _round_up(T, 8))
    bs = min(bs, _round_up(S, 8))
    br = min(br, _round_up(R, 8))
    Tp, Sp, Rp = _round_up(T, bt), _round_up(S, bs), _round_up(R, br)
    if (Tp, Rp) != (T, R):
        A = jnp.pad(A, ((0, 0), (0, Tp - T), (0, Rp - R)))
    if (Rp, Sp) != (R, S):
        B = jnp.pad(B, ((0, 0), (0, Rp - R), (0, Sp - S)))
    grid = (Tp // bt, Sp // bs, Rp // br)

    kern = functools.partial(_kernel, ring=ring, nsteps_r=grid[2])
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((D, bt, br), lambda i, j, k: (0, i, k)),
            pl.BlockSpec((D, br, bs), lambda i, j, k: (0, k, j)),
        ],
        out_specs=pl.BlockSpec((D, bt, bs), lambda i, j, k: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct((D, Tp, Sp), jnp.uint32),
        scratch_shapes=[pltpu.VMEM((ring.K, bt, bs), jnp.uint32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(A, B)
    return out if (Tp, Sp) == (T, S) else out[:, :T, :S]
