"""Pluggable execution backends behind one entry point: ``coded_matmul``.

Every backend runs the same four-stage protocol against the unified
:class:`~repro.cdmm.api.CdmmScheme` surface — encode, worker compute,
response gather, any-R decode — so a Plan chosen by the planner executes
identically everywhere.  Because every registered scheme is integer-exact,
all three backends are bit-identical; they differ only in *when* the master
gets its answer:

===========  ===========================  ======================  ==============
backend      execution model              completion time         when to use
===========  ===========================  ======================  ==============
local        all N workers vmapped in     one XLA program (no     tests, small
             one process; straggler       straggler savings —     problems, any
             mask applied at decode       everyone computes)      machine
shard_map    SPMD over a mesh axis, one   barrier: all-gather     real meshes /
             device per worker; encode-   waits for the slowest   multi-device
             at-worker, all-gather,       of the N shards         runs
             decode from first R live
elastic      event-driven master loop     R-th fastest response:  straggler-y or
             (``repro.cdmm.elastic``);    stragglers are raced    elastic worker
             threaded per-worker          past, late joiners      pools; batch
             dispatch, decode fires on    admitted, leavers       streams that
             the R-th response            tolerated up to N - R   rescale
===========  ===========================  ======================  ==============

Determinism: ``local`` and ``shard_map`` always decode from the *first R
live* workers (stable order), so repeated calls are bitwise-reproducible.
``elastic`` decodes from the first R *arrivals* — a different-but-valid
subset per run under a randomized trace — and still returns the same bits,
because the any-R decode is exact for every subset (that invariant is
property-tested in tests/test_elastic.py).
"""
from __future__ import annotations

from typing import Callable, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.straggler import select_workers
from repro.kernels import gr_matmul

from .api import CdmmScheme
from .planner import Plan

__all__ = [
    "LocalSimBackend",
    "ShardMapBackend",
    "shard_worker_body",
    "coded_matmul",
    "get_backend",
    "register_backend",
    "live_indices",
    "encode_all",
    "decode_from",
]


# --------------------------------------------------------------------------
# shared protocol helpers (used by every backend, incl. cdmm.elastic)
# --------------------------------------------------------------------------


def live_indices(scheme: CdmmScheme, mask: Optional[jnp.ndarray]) -> jnp.ndarray:
    """First-R live worker indices under ``mask`` (all-live when None)."""
    if mask is None:
        return jnp.arange(scheme.R, dtype=jnp.int32)
    return select_workers(mask, scheme.R)


def encode_all(
    scheme: CdmmScheme,
    A: jnp.ndarray,
    B: jnp.ndarray,
    key: Optional[jax.Array] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Master-side encode of both operands: (N, ...) share stacks.

    ``key`` is the masked-randomness seam for secure schemes (they derive
    independent A/B-side masks from it internally); non-secure schemes
    ignore it.
    """
    return scheme.encode_a(A, key=key), scheme.encode_b(B, key=key)


def decode_from(
    scheme: CdmmScheme, H: jnp.ndarray, idx: jnp.ndarray
) -> jnp.ndarray:
    """Any-R decode from the responses of workers ``idx`` (rows of ``H``
    indexed by worker, i.e. the full (N, ...) response stack)."""
    return scheme.decode(jnp.take(H, idx, axis=0), idx)


class LocalSimBackend:
    """Simulate all N workers locally (vmapped); decode from the first R
    responsive workers under ``mask``."""

    name = "local"

    def __call__(
        self,
        scheme: CdmmScheme,
        A: jnp.ndarray,
        B: jnp.ndarray,
        mask: Optional[jnp.ndarray] = None,
        key: Optional[jax.Array] = None,
    ) -> jnp.ndarray:
        # same span schema as the pool path (repro.obs), so a "local"
        # trace reads like a pool trace with one worker lane per share
        from repro.obs import trace as obs

        ctx = obs.maybe_context("local")
        tracer = obs.tracer()
        with tracer.span(ctx, "encode", "local", scheme=scheme.name):
            FA, GB = encode_all(scheme, A, B, key=key)
        with tracer.span(ctx, "compute", "local", N=int(scheme.N)):
            H = scheme.worker_compute(FA, GB)
        with tracer.span(ctx, "decode", "local", scheme=scheme.name):
            return decode_from(scheme, H, live_indices(scheme, mask))


def shard_worker_body(
    scheme: CdmmScheme,
    axis: str,
    A: jnp.ndarray,
    B: jnp.ndarray,
    mask: jnp.ndarray,
    *,
    use_kernel: bool = False,
    key: Optional[jax.Array] = None,
) -> jnp.ndarray:
    """Per-shard master/worker protocol: call inside shard_map over ``axis``
    with all operands replicated.

    Each shard encodes only its own codeword pair (encode-at-worker: the
    broadcast-blocks upload model — no shard materialises all N shares),
    computes the local block product, then all-gathers responses and
    decodes from the first R live workers.
    ``use_kernel=True`` computes with the Pallas kernel (interpret mode on
    CPU; a ring outside its envelope raises); False uses XLA.
    ``key`` (replicated) feeds every shard the SAME mask randomness, so the
    secure codeword polynomial is consistent across workers.
    """
    i = lax.axis_index(axis)
    fa = scheme.encode_a_at(A, i, key=key)
    gb = scheme.encode_b_at(B, i, key=key)
    if use_kernel:
        h = gr_matmul(fa, gb, scheme.ring)
    else:
        h = scheme.worker_compute(fa[None], gb[None])[0]
    H = lax.all_gather(h, axis)  # (N, ...)
    idx = select_workers(mask, scheme.R)
    return scheme.decode(jnp.take(H, idx, axis=0), idx)


class ShardMapBackend:
    """Run the protocol SPMD over a mesh axis with one device per worker."""

    name = "shard_map"

    def __init__(
        self,
        mesh: Optional[Mesh] = None,
        axis: str = "workers",
        use_kernel: bool = False,
    ):
        # use_kernel=True: the Pallas kernel (see shard_worker_body)
        self.mesh, self.axis, self.use_kernel = mesh, axis, use_kernel

    def _mesh_for(self, N: int) -> Mesh:
        if self.mesh is not None:
            return self.mesh
        devs = jax.devices()
        if len(devs) < N:
            raise ValueError(
                f"ShardMapBackend needs {N} devices for N={N} workers, "
                f"have {len(devs)} (set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={N} to simulate)"
            )
        return Mesh(np.array(devs[:N]).reshape(N), (self.axis,))

    def __call__(
        self,
        scheme: CdmmScheme,
        A: jnp.ndarray,
        B: jnp.ndarray,
        mask: Optional[jnp.ndarray] = None,
        key: Optional[jax.Array] = None,
    ) -> jnp.ndarray:
        mesh = self._mesh_for(scheme.N)
        if mask is None:
            mask = jnp.ones(scheme.N, dtype=bool)
        spec = P()  # CDMM redundancy is in the computation: operands replicated
        # the key rides in as a closure constant, replicated to every shard
        f = jax.shard_map(
            lambda a, b, m: shard_worker_body(
                scheme, self.axis, a, b, m,
                use_kernel=self.use_kernel, key=key,
            ),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=False,
        )
        return f(A, B, mask)


_BACKENDS: dict = {
    "local": LocalSimBackend,
    "shard_map": ShardMapBackend,
}

# backends registered by modules that are deliberately not imported at
# repro.cdmm import time: name -> module whose import registers it.  "pool"
# spawns threads/subprocess machinery, so it only loads on first use —
# which is what keeps coded_matmul(..., backend="pool") a one-line switch
# without a mandatory `import repro.dist`.
_LAZY_BACKENDS: dict = {
    "pool": "repro.dist",
}


def register_backend(name: str, factory: Callable[[], object]) -> None:
    """Register a backend factory under ``name`` (used by coded_matmul)."""
    _BACKENDS[name] = factory


def get_backend(backend: Union[None, str, object]):
    """Normalize a backend argument: instance, name, or None (local)."""
    if backend is None:
        return LocalSimBackend()
    if isinstance(backend, str):
        if backend not in _BACKENDS and backend in _LAZY_BACKENDS:
            import importlib

            importlib.import_module(_LAZY_BACKENDS[backend])
        try:
            return _BACKENDS[backend]()
        except KeyError:
            raise ValueError(
                f"unknown backend {backend!r}; one of "
                f"{sorted(set(_BACKENDS) | set(_LAZY_BACKENDS))}"
            ) from None
    return backend


def coded_matmul(
    A: jnp.ndarray,
    B: jnp.ndarray,
    plan: Union[Plan, CdmmScheme],
    *,
    backend: Union[None, str, object] = None,
    mask: Optional[jnp.ndarray] = None,
    key: Optional[jax.Array] = None,
    pool_config=None,
) -> jnp.ndarray:
    """Execute a planned coded matmul: ``C = A @ B`` over ``plan.spec.ring``.

    ``plan`` is a :class:`Plan` from :func:`repro.cdmm.planner.plan` (its
    best candidate is instantiated and memoized) or an already-built scheme.
    Shapes follow the scheme's arity: single schemes take ``(t, r, D0)`` x
    ``(r, s, D0)``; batch schemes take ``(n, t, r, D0)`` x ``(n, r, s, D0)``.
    ``mask`` is an (N,)-bool liveness vector; dead workers' responses are
    provably never read by the any-R decode.

    ``key`` is a ``jax.random`` key feeding the masked-randomness seam of
    secure (``privacy_t > 0``) schemes — REQUIRED for them, ignored by the
    rest.  The same key yields bit-identical codewords (hence decodes) on
    every backend; privacy requires a fresh key per call.

    ``pool_config`` (a :class:`repro.dist.PoolConfig`) shapes the worker
    pool when ``backend="pool"``: worker count/hostfile, wire codec and
    compression, streaming chunk size, timeouts.  The pool it implies is
    brought up for this call and torn down after — callers that issue many
    requests should build a ``PoolBackend(config=...)`` (or a pool +
    ``PoolBackend(pool)``) once and pass it as ``backend`` instead.
    """
    scheme = plan.instantiate() if isinstance(plan, Plan) else plan
    if pool_config is not None:
        if not (backend is None or backend == "pool"):
            raise ValueError(
                f"pool_config= only applies to backend='pool', "
                f"got backend={backend!r}"
            )
        from repro.dist import PoolBackend

        be = PoolBackend(config=pool_config)
        try:
            if key is None:
                return be(scheme, A, B, mask)
            return be(scheme, A, B, mask, key=key)
        finally:
            be.close()
    be = get_backend(backend)
    if key is None:
        # keep the pre-keyed-encode 4-argument backend protocol working:
        # externally registered backends that never learned ``key=`` still
        # serve every non-secure call
        return be(scheme, A, B, mask)
    return be(scheme, A, B, mask, key=key)
