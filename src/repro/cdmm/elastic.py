"""Elastic event-driven execution backend: decode at the R-th response.

The synchronous backends (``local``, ``shard_map``) run encode -> compute-all
-> gather -> decode behind a barrier, so a single straggler costs wall-clock
even though any R of N responses suffice.  :class:`ElasticBackend` is the
repo's first execution path whose completion time depends on R rather than N
— the paper's recovery-threshold claim made operational:

  * the master encodes per-worker shares (``encode_*_at``) and dispatches
    each worker's compute to a thread pool the moment that worker is
    scheduled, so later encodes overlap earlier computes;
  * worker results land on a response queue; the any-R decode fires the
    moment the R-th response arrives, through the scheme's decode operator
    (jitted once per scheme, the live set passed as data — see
    ``CdmmScheme.decode_op``);
  * membership is a :class:`~repro.core.straggler.WorkerTrace`: workers may
    join late, leave mid-batch (never responding) or run slow; the master
    races past anything outside the R fastest responders;
  * :class:`ElasticStream` scales the model to batch workloads that rescale
    mid-stream: the live pool is carved into groups of ``group_size``
    workers, each group runs one coded execution per wave, and on every
    membership change the per-group batch is re-chunked via
    ``repro.runtime.elastic.replan_batch`` and the planner re-ranks schemes
    for the new batch size.

Determinism: the decoded subset varies with the trace (first R *arrivals*,
not first R indices), but every registered scheme's any-R decode is
integer-exact, so the output is bit-identical to ``LocalSimBackend`` for
every valid trace — property-tested in tests/test_elastic.py.
"""
from __future__ import annotations

import queue
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.galois import Ring
from repro.core.straggler import WorkerTrace
from repro.kernels import gr_matmul
from repro.runtime.elastic import replan_batch

from .api import CdmmScheme, ProblemSpec
from .backends import encode_all, register_backend
from .planner import plan

__all__ = [
    "ElasticBackend",
    "ElasticStats",
    "ElasticStream",
    "NotEnoughResponders",
    "decode_responses",
    "share_compute",
    "share_encoders",
]


class NotEnoughResponders(RuntimeError):
    """Raised when a trace/mask leaves fewer than R workers ever responding:
    the any-R decode is mathematically impossible, and decoding from repeated
    indices would return garbage silently."""


@dataclass(frozen=True)
class ElasticStats:
    """Per-call accounting of one elastic execution (virtual-time model)."""

    fast_path: bool  # all-live vectorized path, no thread pool
    dispatched: Tuple[int, ...]  # workers whose compute was launched
    live_idx: Tuple[int, ...]  # the R-subset actually decoded from
    n_responders: int  # workers whose response would eventually land
    time_to_R_ms: float  # virtual arrival of the R-th response
    time_to_all_ms: float  # virtual arrival of the last response (inf if
    #                         any worker never responds — the sync barrier)
    wall_ms: float  # measured master wall-clock for the call


def _response_order(resp_ms: np.ndarray) -> np.ndarray:
    """Worker indices sorted by virtual arrival (ties -> lower index)."""
    return np.lexsort((np.arange(len(resp_ms)), resp_ms))


def decode_responses(
    scheme: CdmmScheme, got: Dict[int, jnp.ndarray]
) -> jnp.ndarray:
    """The shared response-ordering/decode tail of every any-R master.

    ``got`` maps worker index -> response for (at least) R workers.  The
    live set is canonicalized to sorted order — the any-R decode is
    subset-order agnostic as long as rows match ``idx``, and a canonical
    order makes the same responders decode the same way every time.
    Both the in-process elastic master and the multi-process pool master
    (``repro.dist.master``) decode through here, so they are bit-identical
    by construction.
    """
    if len(got) < scheme.R:
        raise NotEnoughResponders(
            f"{scheme.name}: decode needs R={scheme.R} responses, "
            f"have {len(got)}"
        )
    idx = tuple(sorted(int(i) for i in got))[: scheme.R]
    return scheme.decode_op(idx)(jnp.stack([got[i] for i in idx]))


def share_encoders(scheme: CdmmScheme, keyed: bool = False):
    """Jitted ``(encode_a_at, encode_b_at)`` of one share each, cached per
    scheme instance so repeated calls never re-trace.  The share index is a
    traced scalar (one compilation covers all N shares).  Apart, a caller
    can encode A alone and take B's share from elsewhere (the pool
    master's weight-share cache).  ``keyed`` selects the keyed variants
    (the masked-randomness seam: the PRNG key is a traced last argument so
    rekeying never re-compiles); both halves take the same key."""
    ops = scheme.__dict__.setdefault("_elastic_ops", {})
    suffix = "_keyed" if keyed else ""
    if "encode_a" + suffix not in ops:
        if keyed:
            ops["encode_a_keyed"] = jax.jit(
                lambda a, i, k: scheme.encode_a_at(a, i, key=k))
            ops["encode_b_keyed"] = jax.jit(
                lambda b, i, k: scheme.encode_b_at(b, i, key=k))
        else:
            ops["encode_a"] = jax.jit(lambda a, i: scheme.encode_a_at(a, i))
            ops["encode_b"] = jax.jit(lambda b, i: scheme.encode_b_at(b, i))
    return ops["encode_a" + suffix], ops["encode_b" + suffix]


def share_compute(scheme: CdmmScheme, use_kernel: bool = False):
    """Jitted ``compute(fa, gb)`` of one worker's share product, cached per
    scheme instance.  Worker shares are donated to the compute (single-use
    buffers; donation is a warn-only no-op on CPU).  ``use_kernel=True``
    routes the block product through the tuned Pallas kernel (False: XLA)
    — every registered scheme's ``worker_compute`` is exactly the ring
    matmul of its two shares, so the substitution is scheme-agnostic and
    exact."""
    ops = scheme.__dict__.setdefault("_elastic_ops", {})
    cname = "compute_kernel" if use_kernel else "compute"
    if cname not in ops:
        if use_kernel:
            body = lambda fa, gb: gr_matmul(fa, gb, scheme.ring)  # noqa: E731
        else:
            body = lambda fa, gb: (  # noqa: E731
                scheme.worker_compute(fa[None], gb[None])[0]
            )
        ops[cname] = jax.jit(
            body,
            donate_argnums=() if jax.default_backend() == "cpu" else (0, 1),
        )
    return ops[cname]


class ElasticBackend:
    """Event-driven elastic execution of one coded matmul.

    ``trace`` fixes the membership realization (default: everyone live and
    instant — the fast path).  An (N,)-bool ``mask`` passed at call time is
    composed with the trace (masked-out workers never respond).
    ``simulate_ms_scale > 0`` makes worker threads sleep
    ``response_ms * scale / 1000`` seconds so *real* wall-clock exhibits the
    race past stragglers (benchmarks); leave at 0 for tests.
    """

    name = "elastic"

    def __init__(
        self,
        trace: Optional[WorkerTrace] = None,
        max_threads: Optional[int] = None,
        simulate_ms_scale: float = 0.0,
        use_kernel: bool = False,
    ):
        self.trace = trace
        self.max_threads = max_threads
        self.simulate_ms_scale = simulate_ms_scale
        # True: workers use the Pallas kernel (see share_compute)
        self.use_kernel = use_kernel
        self.last_stats: Optional[ElasticStats] = None
        self._pool: Optional[ThreadPoolExecutor] = None
        self._pool_size = 0

    def _worker_pool(self, n: int) -> ThreadPoolExecutor:
        # one pool per backend instance: repeated calls (serving loops,
        # ElasticStream waves) must not pay thread spawn per matmul.  Sized
        # to the scheme's worker count — a cap below N would serialize
        # dispatch and make simulated stragglers block fast workers' slots,
        # inflating wall-clock toward the t_N barrier the backend exists to
        # beat.  Grown (never shrunk) if a bigger scheme shows up.
        want = self.max_threads or max(n, 8)
        if self._pool is None or self._pool_size < want:
            if self._pool is not None:
                self._pool.shutdown(wait=False)
            self._pool = ThreadPoolExecutor(
                max_workers=want, thread_name_prefix="cdmm-elastic"
            )
            self._pool_size = want
        return self._pool

    def close(self) -> None:
        """Release the worker thread pool (idempotent).  In-flight straggler
        tasks are abandoned, not joined — ``done`` is already set by the time
        any caller closes."""
        if self._pool is not None:
            self._pool.shutdown(wait=False)
            self._pool = None
            self._pool_size = 0

    def __enter__(self) -> "ElasticBackend":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- protocol entry point ------------------------------------------------

    def __call__(
        self,
        scheme: CdmmScheme,
        A: jnp.ndarray,
        B: jnp.ndarray,
        mask: Optional[jnp.ndarray] = None,
        key: Optional[jax.Array] = None,
    ) -> jnp.ndarray:
        C, self.last_stats = self.run(scheme, A, B, mask, key=key)
        return C

    def run(
        self,
        scheme: CdmmScheme,
        A: jnp.ndarray,
        B: jnp.ndarray,
        mask: Optional[jnp.ndarray] = None,
        key: Optional[jax.Array] = None,
    ) -> Tuple[jnp.ndarray, ElasticStats]:
        t0 = time.perf_counter()
        if self.trace is None and mask is None:
            return self._run_all_live(scheme, A, B, t0, key)
        trace = self.trace or WorkerTrace.all_live(scheme.N)
        if trace.N != scheme.N:
            raise ValueError(
                f"trace has N={trace.N} workers, scheme needs N={scheme.N}"
            )
        if mask is not None:
            trace = trace.restrict(np.asarray(mask, dtype=bool))
        return self._run_traced(scheme, A, B, trace, t0, key)

    # -- all-live fast path --------------------------------------------------

    def _run_all_live(self, scheme, A, B, t0, key=None):
        """Everyone present and instant: one vmapped XLA program, but the
        decode still routes through the cached per-subset operator so the
        warm path shares compilations with the event loop."""
        from repro.obs import trace as obs

        ctx = obs.maybe_context("elastic")
        tracer = obs.tracer()
        with tracer.span(ctx, "encode", "elastic", scheme=scheme.name):
            FA, GB = encode_all(scheme, A, B, key=key)
        with tracer.span(ctx, "compute", "elastic", N=int(scheme.N)):
            H = scheme.worker_compute(FA, GB)
        idx = tuple(range(scheme.R))
        with tracer.span(ctx, "decode", "elastic", scheme=scheme.name):
            C = scheme.decode_op(idx)(H[: scheme.R])
        stats = ElasticStats(
            fast_path=True,
            dispatched=tuple(range(scheme.N)),
            live_idx=idx,
            n_responders=scheme.N,
            time_to_R_ms=0.0,
            time_to_all_ms=0.0,
            wall_ms=(time.perf_counter() - t0) * 1e3,
        )
        return C, stats

    # -- event-driven master loop --------------------------------------------

    def _run_traced(self, scheme, A, B, trace: WorkerTrace, t0, key=None):
        N, R = scheme.N, scheme.R
        resp = trace.response_ms()
        responders = np.flatnonzero(np.isfinite(resp))
        if len(responders) < R:
            raise NotEnoughResponders(
                f"{scheme.name}: only {len(responders)} of N={N} workers "
                f"ever respond, decode needs R={R}"
            )
        # the R virtually-fastest responders; the master is done at t_R and
        # never even dispatches workers that join after that
        order = _response_order(resp)
        fastR = order[:R]
        t_R = trace.time_to_kth_response(R)
        t_all = trace.time_to_kth_response(N)
        dispatch = [i for i in np.argsort(trace.join_ms, kind="stable")
                    if trace.join_ms[i] <= t_R]

        encode_a, encode_b = share_encoders(scheme, keyed=key is not None)
        compute = share_compute(scheme, use_kernel=self.use_kernel)

        from repro.obs import trace as obs

        ctx = obs.maybe_context("elastic")
        tracer = obs.tracer()

        q: "queue.Queue" = queue.Queue()
        scale = self.simulate_ms_scale
        done = threading.Event()  # master finished: stragglers stop early

        def worker_task(i: int, fa, gb):
            try:
                t_c = obs.now()
                h = compute(fa, gb)
                h.block_until_ready()
                tracer.add(ctx, "compute", "worker", t_c, obs.now(),
                           wid=int(i), share=int(i), simulated=True)
                if scale > 0.0 and np.isfinite(resp[i]):
                    # simulated latency; cut short the moment the master
                    # decodes so stragglers never block pool reuse or exit
                    done.wait(resp[i] * scale / 1e3)
                q.put((i, h, None))
            except Exception as e:  # surfaced on the master thread
                q.put((i, None, e))

        needed = set(int(i) for i in fastR)
        got: Dict[int, jnp.ndarray] = {}
        pool = self._worker_pool(len(dispatch))
        # dispatch in join order; encode of worker k overlaps the pool's
        # compute of workers < k (the master thread never blocks here)
        for i in dispatch:
            t_e = obs.now()
            args = (jnp.int32(i),) if key is None else (jnp.int32(i), key)
            fa, gb = encode_a(A, *args), encode_b(B, *args)
            tracer.add(ctx, "encode", "elastic", t_e, obs.now(),
                       share=int(i))
            pool.submit(worker_task, int(i), fa, gb)
        # response queue: consume until the R-th needed response lands;
        # straggler tasks drain into the dead queue after `done` fires
        t_w = obs.now()
        try:
            while needed - set(got):
                i, h, err = q.get()
                if err is not None:
                    raise err
                if i in needed:
                    got[i] = h
        finally:
            done.set()  # race past stragglers: wake any simulated sleeps
        tracer.add(ctx, "wait_R", "elastic", t_w, obs.now(),
                   R=int(R), responders=sorted(int(i) for i in got))

        t_d = obs.now()
        C = decode_responses(scheme, got)
        tracer.add(ctx, "decode", "elastic", t_d, obs.now(),
                   scheme=scheme.name)
        idx = tuple(sorted(int(i) for i in fastR))
        stats = ElasticStats(
            fast_path=False,
            dispatched=tuple(int(i) for i in dispatch),
            live_idx=idx,
            n_responders=len(responders),
            time_to_R_ms=t_R,
            time_to_all_ms=t_all,
            wall_ms=(time.perf_counter() - t0) * 1e3,
        )
        return C, stats


register_backend("elastic", ElasticBackend)


# --------------------------------------------------------------------------
# batch streams that rescale mid-stream
# --------------------------------------------------------------------------


class ElasticStream:
    """Run a stream of batch matmuls over a worker pool that rescales.

    The live pool is carved into ``live // group_size`` independent groups;
    each wave, every group executes one planner-chosen coded scheme over its
    chunk of the global batch.  On a membership change the per-group batch
    is re-chunked with :func:`repro.runtime.elastic.replan_batch` (ceil —
    the trailing chunk is zero-padded and trimmed after decode) and the
    planner re-ranks schemes for the new batch size.  Plans are memoized per
    chunk size, so oscillating pools don't re-pay scheme construction.
    """

    def __init__(
        self,
        t: int,
        r: int,
        s: int,
        ring: Ring,
        group_size: int = 8,
        objective: str = "latency",
        straggler_budget: int = 0,
        backend: Optional[ElasticBackend] = None,
    ):
        self.t, self.r, self.s, self.ring = t, r, s, ring
        self.group_size = group_size
        self.objective = objective
        self.straggler_budget = straggler_budget
        self.backend = backend or ElasticBackend()
        self._schemes: Dict[int, CdmmScheme] = {}
        self.last_replan: Optional[Tuple[int, int]] = None  # (groups, per)

    def _scheme_for(self, per: int) -> CdmmScheme:
        if per not in self._schemes:
            spec = ProblemSpec(
                self.t, self.r, self.s, n=per, ring=self.ring,
                N=self.group_size, straggler_budget=self.straggler_budget,
            )
            self._schemes[per] = plan(spec, objective=self.objective).instantiate()
        return self._schemes[per]

    def step(self, As: jnp.ndarray, Bs: jnp.ndarray, live: int) -> jnp.ndarray:
        """One wave: ``As (n, t, r, D0) @ Bs (n, r, s, D0)`` with ``live``
        workers currently in the pool.  Returns ``Cs (n, t, s, D0)``."""
        nprod = int(As.shape[0])
        groups = live // self.group_size
        if groups < 1:
            raise NotEnoughResponders(
                f"pool of {live} live workers cannot form one group of "
                f"{self.group_size}"
            )
        per = replan_batch(nprod, groups)
        self.last_replan = (groups, per)
        scheme = self._scheme_for(per)
        chunk = scheme.batch  # may exceed `per` (RMFE packs up, never down)

        outs = []
        for lo in range(0, nprod, chunk):
            Ac, Bc = As[lo : lo + chunk], Bs[lo : lo + chunk]
            pad = chunk - Ac.shape[0]
            if pad:
                Ac = jnp.concatenate([Ac, jnp.zeros((pad, *As.shape[1:]), As.dtype)])
                Bc = jnp.concatenate([Bc, jnp.zeros((pad, *Bs.shape[1:]), Bs.dtype)])
            if chunk == 1:
                outs.append(self.backend(scheme, Ac[0], Bc[0])[None])
            else:
                outs.append(self.backend(scheme, Ac, Bc))
        return jnp.concatenate(outs, axis=0)[:nprod]
