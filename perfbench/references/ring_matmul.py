"""Plain reference: the exact product of two matrices over Z_2^e, with numpy alone.

Operands are uint32 words of shape (..., 1) (one coefficient: the ring
Z_2^e, e <= 32), as the benchmark draws them.  Each operand is lifted to
the integers (the signed representative in [-2^(e-1), 2^(e-1))) and split
into limbs small enough that every float64 dot product of limbs is an exact
integer (below 2^53); the limb products are recombined with wrapping uint64
arithmetic and reduced mod 2^e.  int8 data at the widths the benchmark runs
needs one limb, so one float64 matrix product per block of rows.

It imports nothing of the program under test and takes nothing it made.
"""
from __future__ import annotations

import math

import numpy as np

ROW_BLOCK = 512  # rows of A per block, so the float64 copies stay small


def _signed(x: np.ndarray, e: int) -> np.ndarray:
    v = (x.astype(np.uint64) & np.uint64((1 << e) - 1)).astype(np.int64)
    return np.where(v >= (1 << (e - 1)), v - (1 << e), v)


def _limbs(v: np.ndarray, width: int, count: int):
    """Split non-negative int64 values into ``count`` limbs of ``width``
    bits, lowest first."""
    mask = (1 << width) - 1
    return [((v >> (width * k)) & mask).astype(np.float64)
            for k in range(count)]


def matmul(A: np.ndarray, B: np.ndarray, e: int = 32) -> np.ndarray:
    """``A @ B`` over Z_2^e for A (t, r, 1), B (r, s, 1); returns uint32
    (t, s, 1)."""
    if A.shape[-1] != 1 or B.shape[-1] != 1:
        raise NotImplementedError(
            "this reference covers Z_2^e (one coefficient per entry); a "
            "Galois-ring reference comes with the first GR cell")
    if not 1 <= e <= 32:
        raise ValueError(f"e = {e} outside 1..32")
    a, b = _signed(A[..., 0], e), _signed(B[..., 0], e)
    r = a.shape[1]
    top = int(np.abs(a).max(initial=0)) * int(np.abs(b).max(initial=0)) * r
    mod = np.uint64((1 << e) - 1)
    out = np.empty((a.shape[0], b.shape[1], 1), np.uint32)
    if top < 2 ** 53:
        bf = b.astype(np.float64)
        for lo in range(0, a.shape[0], ROW_BLOCK):
            c = a[lo:lo + ROW_BLOCK].astype(np.float64) @ bf
            out[lo:lo + ROW_BLOCK, :, 0] = (
                c.astype(np.int64).astype(np.uint64) & mod)
        return out
    # general path: unsigned limbs whose pair products sum exactly in float64
    width = max(1, (53 - max(1, math.ceil(math.log2(r)))) // 2)
    count = math.ceil(e / width)
    ua, ub = a & ((1 << e) - 1), b & ((1 << e) - 1)
    lb = _limbs(ub, width, count)
    for lo in range(0, a.shape[0], ROW_BLOCK):
        la = _limbs(ua[lo:lo + ROW_BLOCK], width, count)
        acc = np.zeros((la[0].shape[0], b.shape[1]), np.uint64)
        for i in range(count):
            for j in range(count):
                shift = width * (i + j)
                if shift >= e:
                    continue
                part = (la[i] @ lb[j]).astype(np.uint64)
                acc += part << np.uint64(shift)
        out[lo:lo + ROW_BLOCK, :, 0] = acc & mod
    return out
