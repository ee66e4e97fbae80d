"""Pallas TPU kernels for the CDMM hot paths (validated via interpret mode).

gr_matmul: blocked Galois-ring matmul (worker compute, encode, decode).
autotune: measured block-size search + persisted cache consulted by ops.
"""
# NB: the tuner entry point lives at repro.kernels.autotune.autotune —
# re-exporting the function here would shadow the submodule attribute
from .autotune import cached_blocks, candidate_blocks, tune_key
from .ops import (
    coded_encode,
    gr_matmul,
    kernel_supported,
    pick_blocks,
)
from .ref import gr_matmul_planar_ref, gr_matmul_ref

__all__ = [
    "gr_matmul",
    "coded_encode",
    "kernel_supported",
    "pick_blocks",
    "gr_matmul_ref",
    "gr_matmul_planar_ref",
    "cached_blocks",
    "candidate_blocks",
    "tune_key",
]
