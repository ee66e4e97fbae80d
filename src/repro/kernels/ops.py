"""Jit-ready wrappers around the Pallas Galois-ring matmul kernel.

Handles layout conversion (interleaved (t, r, D) <-> planar (D, t, r)),
padding to block multiples and block-size selection (autotuned cache first,
static heuristic as fallback).  A ring outside the kernel envelope (odd p,
e > 32 or D > MAX_D) is an error: callers that want the ring product for any
ring call ``Ring.matmul``.

The kernel is never chosen by backend name.  It does not compile for the
TPU v5e today (Mosaic refuses both its scatter and its 32-bit integer dot),
so every production path computes with XLA's ``Ring.matmul`` unless a caller
forces the kernel, and then the compiler's error is the caller's error.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.galois import Ring

from .autotune import cached_blocks
from .gr_matmul import MAX_D, _round_up, gr_matmul_planar


def pick_blocks(t: int, r: int, s: int) -> Tuple[int, int, int]:
    """MXU-aligned block sizes: multiples of 128 when the dim allows, else
    the (padded) dim itself."""

    def pick(d: int, target: int = 128) -> int:
        return target if d >= target else _round_up(d, 8)

    return pick(t), pick(s), pick(r)


def kernel_supported(ring: Ring) -> bool:
    return ring.p == 2 and ring.e <= 32 and ring.D <= MAX_D


def gr_matmul(
    A: jnp.ndarray,
    B: jnp.ndarray,
    ring: Ring,
    *,
    blocks: Optional[Tuple[int, int, int]] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Ring matmul (t, r, D) x (r, s, D) -> (t, s, D) via the Pallas kernel.

    On the CPU ``interpret`` defaults to True (kernel body runs in python
    for validation); on an accelerator it compiles to Mosaic or raises.
    """
    t, r, D = A.shape
    r2, s, D2 = B.shape
    assert r == r2 and D == D2 == ring.D
    if not kernel_supported(ring):
        raise ValueError(
            f"GR({ring.p}^{ring.e},{ring.D}) is outside the kernel envelope "
            f"(p=2, e<=32, D<={MAX_D}); use Ring.matmul"
        )
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    if blocks is None:
        # tuned schedule for this (device, ring, tile) when one is cached;
        # the static MXU heuristic otherwise
        blocks = cached_blocks(ring, t, r, s)
    bt, bs, br = blocks if blocks else pick_blocks(t, r, s)
    tp, rp, sp = _round_up(t, bt), _round_up(r, br), _round_up(s, bs)
    Ap = jnp.moveaxis(jnp.pad(A, ((0, tp - t), (0, rp - r), (0, 0))), -1, 0)
    Bp = jnp.moveaxis(jnp.pad(B, ((0, rp - r), (0, sp - s), (0, 0))), -1, 0)
    Cp = gr_matmul_planar(Ap, Bp, ring, bt=bt, bs=bs, br=br, interpret=interpret)
    return jnp.moveaxis(Cp, 0, -1)[:t, :s]


def coded_encode(
    V: jnp.ndarray, blocks_mat: jnp.ndarray, ring: Ring, **kw
) -> jnp.ndarray:
    """CDMM encode = ring matmul against a Vandermonde slice.

    V: (N, K, D); blocks_mat: (K, M, D) -> (N, M, D)."""
    return gr_matmul(V, blocks_mat, ring, **kw)
