"""Distributed CDMM runtime: the paper's master/worker protocol as SPMD.

Mapping (DESIGN.md §3.3): the N CDMM workers are the shards of a mesh axis.
Under ``shard_map`` each shard

  1. *encodes its own point*  — evaluates f(alpha_i), g(alpha_i) from the
     (replicated) partition blocks.  This is the "broadcast blocks, evaluate
     at the worker" variant: upload = one block broadcast, and the master
     never materialises N evaluations (the paper's master-side encode is the
     `master_encode=True` mode, a Vandermonde matmul sharded over workers).
  2. computes its block product with the Pallas gr_matmul kernel,
  3. all-gathers responses; decoding from the first R live workers happens
     replicated (every shard doubles as the master — in a real deployment
     only the master decodes; collective bytes are reported either way).

Straggler tolerance is a runtime boolean mask: dead workers contribute
garbage that the any-R Lagrange decode provably never reads.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.batch_rmfe import BatchEPRMFE
from repro.core.ep_codes import EPCode
from repro.core.galois import Ring
from repro.core.polyops import as_u32, s_vandermonde
from repro.core.straggler import select_workers
from repro.kernels import gr_matmul

__all__ = ["DistributedEP", "DistributedBatchRMFE", "cdmm_shard_map"]


def _take_rows(M: jnp.ndarray, i: jnp.ndarray) -> jnp.ndarray:
    return lax.dynamic_index_in_dim(M, i, axis=0, keepdims=False)


class DistributedEP:
    """SPMD execution of one EPCode over a mesh axis of size N."""

    def __init__(
        self,
        code: EPCode,
        axis_name: str,
        *,
        use_kernel: bool = False,
        master_encode: bool = False,
    ):
        self.code = code
        self.axis = axis_name
        self.use_kernel = use_kernel
        self.master_encode = master_encode

    # ---- per-shard body (call inside shard_map over the worker axis) ------

    def worker_body(
        self, A: jnp.ndarray, B: jnp.ndarray, mask: jnp.ndarray
    ) -> jnp.ndarray:
        """A (t, r, D), B (r, s, D), mask (N,) replicated -> C (t, s, D) replicated.

        Executes encode-at-worker, local block product, all-gather + any-R
        decode.  Must run inside shard_map with these args replicated.
        """
        code, ring = self.code, self.code.ring
        i = lax.axis_index(self.axis)
        blocks_a = code.split_a(A)  # (uw, tb, rb, D)
        blocks_b = code.split_b(B)  # (wv, rb, sb, D)
        Ka, tb, rb, D = blocks_a.shape
        Kb, _, sb, _ = blocks_b.shape
        # this worker's Vandermonde rows (encode-at-worker)
        vf = _take_rows(code.Vf, i)  # (uw, D)
        vg = _take_rows(code.Vg, i)  # (wv, D)
        fa = ring.matmul(vf[None], blocks_a.reshape(Ka, tb * rb, D))[0]
        gb = ring.matmul(vg[None], blocks_b.reshape(Kb, rb * sb, D))[0]
        fa = fa.reshape(tb, rb, D)
        gb = gb.reshape(rb, sb, D)
        # local block product — the hot kernel
        if self.use_kernel:
            h = gr_matmul(fa, gb, ring)
        else:
            h = ring.matmul(fa, gb)
        # gather responses; decode replicated from the first R live workers
        H = lax.all_gather(h, self.axis)  # (N, tb, sb, D)
        idx = select_workers(mask, code.R)
        return code.decode(jnp.take(H, idx, axis=0), idx)

    def master_encode_body(self, A, B, mask):
        """Alternative: master-side Vandermonde encode, sharded over workers."""
        code, ring = self.code, self.code.ring
        i = lax.axis_index(self.axis)
        FA = code.encode_a(A)
        GB = code.encode_b(B)
        fa, gb = _take_rows(FA, i), _take_rows(GB, i)
        if self.use_kernel:
            h = gr_matmul(fa, gb, ring)
        else:
            h = ring.matmul(fa, gb)
        H = lax.all_gather(h, self.axis)
        idx = select_workers(mask, code.R)
        return code.decode(jnp.take(H, idx, axis=0), idx)

    def __call__(self, A, B, mask):
        if self.master_encode:
            return self.master_encode_body(A, B, mask)
        return self.worker_body(A, B, mask)


class DistributedBatchRMFE:
    """SPMD Batch-EP_RMFE: pack (replicated) -> DistributedEP -> unpack."""

    def __init__(self, scheme: BatchEPRMFE, axis_name: str, **kw):
        self.scheme = scheme
        self.dep = DistributedEP(scheme.code, axis_name, **kw)

    def __call__(self, As: jnp.ndarray, Bs: jnp.ndarray, mask: jnp.ndarray):
        """As, Bs: (n, t, r, D0) / (n, r, s, D0) replicated -> (n, t, s, D0)."""
        A = self.scheme.pack(As)
        B = self.scheme.pack(Bs)
        C = self.dep(A, B, mask)
        return self.scheme.unpack(C)


def cdmm_shard_map(
    fn,
    mesh: Mesh,
    axis_name: str,
):
    """Wrap a per-shard CDMM body into a shard_map with replicated operands.

    The worker axis carries no data sharding — CDMM's redundancy is in the
    *computation*; inputs are replicated (broadcast upload) and the decoded
    product is replicated (download).  Other mesh axes may shard the batch
    outside this wrapper.
    """
    spec = P()  # replicated

    def mapped(*args):
        return jax.shard_map(
            fn,
            mesh=mesh,
            in_specs=tuple(spec for _ in args),
            out_specs=spec,
            check_vma=False,
        )(*args)

    return mapped
