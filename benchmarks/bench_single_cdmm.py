"""Paper Figs 2-5: plain EP vs EP_RMFE-I vs EP_RMFE-II over Z_{2^32}.

Measures master encode/decode time, per-worker compute time (wall clock,
XLA-CPU uint32 matmuls) and counts upload/download volume (bytes), for the
paper's two regimes:
  * N=8  workers -> GR(2^32, 3), u=v=2, w=1, R=4
  * N=16 workers -> GR(2^32, 4), u=v=w=2, R=9
n = 2 for both optimized variants, exactly as in §V (type II uses the
paper's measured configuration: B packed via phi1, A embedded).

All three schemes run through the unified CdmmScheme surface
(encode_a/encode_b/worker_compute/decode + costs(spec)) — the volumes come
straight from the shared analytic cost model.
"""
from __future__ import annotations

import queue

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.cdmm.api import (
    EPRMFE1Adapter,
    EPRMFE2Adapter,
    PlainCDMMAdapter,
    ProblemSpec,
)
from repro.cdmm.elastic import worker_closures
from repro.core import make_ring

from .common import emit, timeit

WORD = 4  # bytes per Z_{2^32} element


def bench_one(N: int, uvw, sizes, iters: int = 3):
    u, v, w = uvw
    base = make_ring(2, 32, ())
    schemes = {
        "ep_plain": PlainCDMMAdapter(base, N, u, v, w),
        "ep_rmfe1": EPRMFE1Adapter(base, 2, N, u, v, w),
        "ep_rmfe2": EPRMFE2Adapter(base, 2, N, u, v, w),  # §V: split_a=False
    }
    rng = np.random.default_rng(0)

    for size in sizes:
        t = r = s = size
        A = base.random(rng, (t, r))
        B = base.random(rng, (r, s))
        spec = ProblemSpec(t=t, r=r, s=s, n=1, ring=base, N=N)
        for name, sch in schemes.items():
            m = sch.ring.D
            idx = jnp.arange(sch.R, dtype=jnp.int32)
            enc = jax.jit(lambda a, b, sch=sch: (sch.encode_a(a), sch.encode_b(b)))
            FA, GB = enc(A, B)
            worker = jax.jit(
                lambda fa, gb, sch=sch: sch.worker_compute(fa, gb)
            )
            H = sch.worker_compute(FA, GB)
            dec = jax.jit(lambda h, sch=sch, idx=idx: sch.decode(h, idx))
            e_us = timeit(enc, A, B, iters=iters)
            w_us = timeit(worker, FA[:1], GB[:1], iters=iters)
            d_us = timeit(dec, H[: sch.R], iters=iters)
            # master<->worker transfer proxy: host round-trip of the share
            # stack (memcpy bandwidth on this box) — the communication term
            # the calibration fit grounds its upload/download coefficient on
            comm = jax.jit(lambda fa: fa + jnp.uint32(0))
            c_us = timeit(lambda fa: np.asarray(comm(fa)), FA, iters=iters)
            c = sch.costs(spec)
            # every stage row carries its cost-model features + backend tag
            # so repro.cdmm.calibrate can fit wall-time coefficients from
            # the emitted JSON (backend="local": stages are the same jitted
            # calls the LocalSim/ShardMap masters run)
            emit(f"{name}_N{N}_s{size}_encode", e_us,
                 upload_B=int(c.upload * WORD), m=m,
                 encode_ops=c.encode_ops, backend="local")
            emit(f"{name}_N{N}_s{size}_worker", w_us, m=m,
                 worker_ops=c.worker_ops, backend="local")
            emit(f"{name}_N{N}_s{size}_decode", d_us,
                 download_B=int(c.download * WORD),
                 decode_ops=c.decode_ops, backend="local")
            emit(f"{name}_N{N}_s{size}_comm", c_us,
                 comm_elems=c.upload + c.download, backend="local")


def _bench_elastic_stages(N, schemes, size, spec, A, B, iters):
    """Stage rows through the elastic master's actual code path: the serial
    per-worker ``encode_*_at`` dispatch loop, one threaded worker's jitted
    compute closure, the any-R ``decode_op``, and the
    in-process response handoff (queue put/get of a share stack) — so
    ``repro.cdmm.calibrate`` fits the elastic backend its own coefficients
    instead of falling back to "local"."""
    for name, sch in schemes.items():
        m = sch.ring.D
        c = sch.costs(spec)
        encode_at, compute = worker_closures(sch)

        def enc_all(a, b, _enc=encode_at, _n=N):
            return [_enc(a, b, jnp.int32(i)) for i in range(_n)]

        FA = sch.encode_a(A)
        GB = sch.encode_b(B)
        H = sch.worker_compute(FA, GB)
        dec = sch.decode_op(tuple(range(sch.R)))
        e_us = timeit(enc_all, A, B, iters=iters)
        w_us = timeit(compute, FA[0], GB[0], iters=iters)
        d_us = timeit(dec, H[: sch.R], iters=iters)
        # the elastic "transfer" is an in-process queue handoff of the
        # response buffers (workers share the master's address space)
        q: "queue.Queue" = queue.Queue()

        def handoff(h, _q=q):
            _q.put(h)
            return _q.get()

        c_us = timeit(handoff, H, iters=iters)
        tag = f"{name}_N{N}_s{size}_elastic"
        emit(f"{tag}_encode", e_us, upload_B=int(c.upload * WORD), m=m,
             encode_ops=c.encode_ops, backend="elastic")
        emit(f"{tag}_worker", w_us, m=m, worker_ops=c.worker_ops,
             backend="elastic")
        emit(f"{tag}_decode", d_us, download_B=int(c.download * WORD),
             decode_ops=c.decode_ops, backend="elastic")
        emit(f"{tag}_comm", c_us, comm_elems=c.upload + c.download,
             backend="elastic")


def _bench_shard_map_stages(N, schemes, size, spec, A, B, iters):
    """Stage rows through real SPMD programs over an N-device mesh: encode
    runs at-worker (each shard computes its own codeword pair), compute is
    the per-shard block product, the transfer is the ``all_gather``
    collective the sync backend pays, and decode is the replicated master
    decode.  Skipped when the host exposes fewer than N devices."""
    if len(jax.devices()) < N:
        # never skip silently: a calibration refit from this run would
        # quietly lose the shard_map coefficients
        print(f"# shard_map stage rows SKIPPED: need {N} devices, have "
              f"{len(jax.devices())} (set XLA_FLAGS="
              f"--xla_force_host_platform_device_count={N})")
        return
    mesh = Mesh(np.array(jax.devices()[:N]).reshape(N), ("workers",))
    rep = P()
    shard = P("workers")
    for name, sch in schemes.items():
        m = sch.ring.D
        c = sch.costs(spec)

        def enc_body(a, b, _sch=sch):
            i = lax.axis_index("workers")
            return (_sch.encode_a_at(a, i)[None], _sch.encode_b_at(b, i)[None])

        enc = jax.shard_map(enc_body, mesh=mesh, in_specs=(rep, rep),
                            out_specs=(shard, shard), check_vma=False)

        def cmp_body(fa, gb, _sch=sch):
            return _sch.worker_compute(fa, gb)

        cmp = jax.shard_map(cmp_body, mesh=mesh, in_specs=(shard, shard),
                            out_specs=shard, check_vma=False)

        def gather_body(h):
            return lax.all_gather(h[0], "workers")

        gather = jax.shard_map(gather_body, mesh=mesh, in_specs=(shard,),
                               out_specs=rep, check_vma=False)

        FA, GB = jax.jit(enc)(A, B)
        H = sch.worker_compute(FA, GB)
        idx = jnp.arange(sch.R, dtype=jnp.int32)
        dec = jax.jit(lambda h, _sch=sch, _idx=idx: _sch.decode(h, _idx))
        e_us = timeit(jax.jit(enc), A, B, iters=iters)
        w_us = timeit(jax.jit(cmp), FA, GB, iters=iters)
        d_us = timeit(dec, H[: sch.R], iters=iters)
        c_us = timeit(jax.jit(gather), H, iters=iters)
        tag = f"{name}_N{N}_s{size}_shard_map"
        emit(f"{tag}_encode", e_us, upload_B=int(c.upload * WORD), m=m,
             encode_ops=c.encode_ops, backend="shard_map")
        emit(f"{tag}_worker", w_us, m=m, worker_ops=c.worker_ops,
             backend="shard_map")
        emit(f"{tag}_decode", d_us, download_B=int(c.download * WORD),
             decode_ops=c.decode_ops, backend="shard_map")
        emit(f"{tag}_comm", c_us, comm_elems=c.upload + c.download,
             backend="shard_map")


def _bench_pool_stages(pool, N, schemes, size, spec, A, B, iters):
    """Stage rows for the multi-process pool backend, with the comm term
    measured from REAL socket round-trips: ``Master.echo`` bounces a
    payload sized to the scheme's upload+download volume off a live worker
    through the negotiated wire codec, so the fitted ``comm`` coefficient
    prices what pool execution actually pays (framing, codec, kernel
    socket path) instead of a memcpy proxy.  Encode/decode/compute stages
    are the same jitted calls the pool master and workers run."""
    master = pool.master
    for name, sch in schemes.items():
        m = sch.ring.D
        c = sch.costs(spec)
        encode_at, compute = worker_closures(sch)

        def enc_all(a, b, _enc=encode_at, _n=N):
            return [_enc(a, b, jnp.int32(i)) for i in range(_n)]

        FA = sch.encode_a(A)
        GB = sch.encode_b(B)
        H = sch.worker_compute(FA, GB)
        dec = sch.decode_op(tuple(range(sch.R)))
        e_us = timeit(enc_all, A, B, iters=iters)
        w_us = timeit(compute, FA[0], GB[0], iters=iters)
        d_us = timeit(dec, H[: sch.R], iters=iters)
        nbytes = max(int((c.upload + c.download) * WORD), 4)
        rtts = [master.echo(nbytes)["rtt_s"] for _ in range(max(iters, 2))]
        c_us = float(np.median(rtts) * 1e6)
        tag = f"{name}_N{N}_s{size}_pool"
        emit(f"{tag}_encode", e_us, upload_B=int(c.upload * WORD), m=m,
             encode_ops=c.encode_ops, backend="pool")
        emit(f"{tag}_worker", w_us, m=m, worker_ops=c.worker_ops,
             backend="pool")
        emit(f"{tag}_decode", d_us, download_B=int(c.download * WORD),
             decode_ops=c.decode_ops, backend="pool")
        emit(f"{tag}_comm", c_us, comm_elems=c.upload + c.download,
             backend="pool")


def bench_backends(N: int, uvw, sizes, iters: int = 3):
    """Per-backend calibration rows (shard_map / elastic / pool), mirroring
    ``bench_one``'s scheme grid so every backend's coefficients are fitted
    from the same problem family."""
    from repro.dist import LocalPool, PoolConfig

    u, v, w = uvw
    base = make_ring(2, 32, ())
    schemes = {
        "ep_plain": PlainCDMMAdapter(base, N, u, v, w),
        "ep_rmfe1": EPRMFE1Adapter(base, 2, N, u, v, w),
        "ep_rmfe2": EPRMFE2Adapter(base, 2, N, u, v, w),
    }
    rng = np.random.default_rng(0)
    # one real worker pool for the socket-measured comm rows (echo probes
    # need a live worker, not a full execute, so 2 workers suffice)
    with LocalPool(config=PoolConfig(workers=2)) as pool:
        for size in sizes:
            t = r = s = size
            A = base.random(rng, (t, r))
            B = base.random(rng, (r, s))
            spec = ProblemSpec(t=t, r=r, s=s, n=1, ring=base, N=N)
            _bench_elastic_stages(N, schemes, size, spec, A, B, iters)
            _bench_shard_map_stages(N, schemes, size, spec, A, B, iters)
            _bench_pool_stages(pool, N, schemes, size, spec, A, B, iters)


def run(full: bool = False):
    sizes = [128, 256, 512] if not full else [256, 512, 1024, 2048]
    bench_one(8, (2, 2, 1), sizes)
    bench_one(16, (2, 2, 2), sizes)
    # per-backend stage rows so calibrate.py fits shard_map/elastic their
    # own coefficients (the ROADMAP follow-up from the calibration PR);
    # N=8 keeps the mesh inside the CI host-device simulation
    bench_backends(8, (2, 2, 1), sizes)
