"""The share product's yardstick: its operations and bytes, and the chip's peaks.

The work of one share product ``h = fa @ gb`` over a Galois ring GR(2^e, D)
is counted the same way whatever implements it: the schoolbook product of
the two degree-D polynomials (D^2 coefficient products per matrix entry),
each coefficient product done as 8-bit limb products on the int8 path.  A
coefficient of e bits has ceil(e/8) limbs, and mod 2^e only the limb pairs
(i, j) with 8(i + j) < e survive: 10 pairs for e = 32, 3 for e = 16.  Each
limb product is one multiply and one add, so

    ops(share) = 2 * D^2 * t' * r' * s' * L(e)

The bytes are the share's two operands and its result as stored (32-bit
words, D coefficients each).  A product that does fewer coefficient or limb
products than this count (Karatsuba, say) would beat the count, and its
share could pass 100%: the count then has to be revisited by a change to the
benchmark, not by the change that adds such a product.

The least time of a share is the larger of ops over the int8 peak and bytes
over HBM bandwidth; the peaks come from ``peaks.json``, keyed by the device
kind JAX reports.
"""
from __future__ import annotations

import json
import math
from pathlib import Path
from typing import Dict, Tuple

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"
WORD_BYTES = 4  # rings up to e = 32 are stored in uint32 words


class UnknownDevice(KeyError):
    """The device kind has no row in the peaks table."""


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> Dict[str, float]:
    """The peaks of one chip of ``device_kind``; a kind that is not in the
    table is an error, never a default."""
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r} in {path} "
            f"(known: {sorted(table)})")
    return table[device_kind]


def limb_pairs(e: int, limb_bits: int = 8) -> int:
    """The limb pairs of a product of two e-bit words that survive mod 2^e."""
    limbs = math.ceil(e / limb_bits)
    return sum(1 for i in range(limbs) for j in range(limbs)
               if limb_bits * (i + j) < e)


def share_ops(t: int, r: int, s: int, D: int, e: int) -> int:
    """Integer operations of one share product (t, r, D) @ (r, s, D)."""
    return 2 * D * D * t * r * s * limb_pairs(e)


def share_bytes(t: int, r: int, s: int, D: int) -> int:
    """Bytes of the share's operands and result as stored."""
    return (t * r + r * s + t * s) * D * WORD_BYTES


def least_time(ops: float, nbytes: float,
               peak: Dict[str, float]) -> Tuple[float, str]:
    """(seconds, bound): the least time the chip could take, and whether the
    int8 compute peak (``"compute"``) or HBM bandwidth (``"memory"``) sets
    it."""
    t_ops = ops / peak["int8_ops_per_s"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
