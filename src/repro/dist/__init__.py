"""repro.dist: a real multi-process worker-pool runtime behind coded_matmul.

Every earlier backend (LocalSim, ShardMap, Elastic) *simulates* the paper's
master/worker protocol inside one process — stragglers are ``WorkerTrace``
fictions.  This package runs it for real:

  * :mod:`repro.dist.config` — :class:`PoolConfig`/:class:`Endpoint`, the
    unified pool + transport configuration every entry point accepts
    (worker counts, hostfiles, wire codec, compression level, streaming
    chunk size, heartbeat/request timeouts);
  * :mod:`repro.dist.protocol` — length-prefixed framed RPC (msgpack header
    + array payloads) over TCP or Unix-domain sockets, with per-connection
    negotiated wire codecs: bit-packing to the ring's true bit-width plus
    optional zlib/zstd framing, so Z_{2^k} shares stop shipping dead carrier
    bits (raw vs. on-wire bytes are counted end to end);
  * :mod:`repro.dist.worker` — the worker-process entrypoint
    (``python -m repro.dist.worker --connect ...``): registers with a
    capability handshake (platform, device kind and count, ring-arithmetic
    envelope, wire codecs, autotune cache coverage), computes jitted
    ``Ring.matmul`` block products spread over its local devices, and
    accumulates chunked shares into partial products so transfer and
    compute overlap;
  * :mod:`repro.dist.chips` — where pool processes run on a TPU host: one
    chip-holding worker per host, the master pinned to the CPU, and the
    persistent compile cache;
  * :mod:`repro.dist.master` — the master: accepts workers, tracks
    heartbeats and membership (``core.straggler.MembershipEvents``),
    dispatches per-worker ``encode_*_at`` shares (pipelined in
    contraction-axis chunks when they are large), re-dispatches the shares
    of workers that die mid-request, and fires the LRU-cached any-R
    ``decode_op`` at the R-th response; plus :class:`LocalPool`, which
    spawns a local master + N worker OS processes in one call;
  * :mod:`repro.dist.launch` — the multi-host launcher
    (``python -m repro.dist.launch --hostfile hosts.txt``): hostfile or
    SPMD-style env rank-wiring, per-host worker counts, TCP endpoints;
    :class:`LocalPool` is its single-host specialization;
  * :mod:`repro.dist.scheduler` — a serving scheduler (bounded queue,
    admission control, per-spec plan cache) so one pool serves many
    concurrent matmul requests;
  * :mod:`repro.dist.pool_backend` — :class:`PoolBackend`, registered as
    ``coded_matmul(A, B, plan, backend="pool")``.

Importing this package registers the ``"pool"`` backend; ``cdmm.backends``
also lazy-imports it on first use, so the one-line switch works without an
explicit ``import repro.dist``.

Determinism: encode runs master-side (same process, same bits as
LocalSim), worker compute is exact integer ring arithmetic (bit-identical
across processes; chunked partial products accumulate with exact ring
addition), and the decode subset is the canonical sorted first-R
arrival set — so a fixed encode key gives bit-identical results to
``LocalSimBackend`` even under real worker deaths (property-tested in
tests/test_conformance.py and tests/test_dist.py).
"""
from repro.cdmm.backends import register_backend

from .config import Endpoint, HostSpec, PoolConfig, parse_hostfile
from .launch import HostPool, launch_pool, spawn_local_workers
from .master import LocalPool, Master, PoolStats, WorkerDied
from .pool_backend import PoolBackend, default_pool, shutdown_default_pool
from .protocol import recv_msg, send_msg
from .scheduler import PoolScheduler, SchedulerSaturated

register_backend("pool", PoolBackend)

__all__ = [
    "Endpoint",
    "HostPool",
    "HostSpec",
    "LocalPool",
    "Master",
    "PoolBackend",
    "PoolConfig",
    "PoolScheduler",
    "PoolStats",
    "SchedulerSaturated",
    "WorkerDied",
    "default_pool",
    "launch_pool",
    "parse_hostfile",
    "shutdown_default_pool",
    "spawn_local_workers",
    "recv_msg",
    "send_msg",
]
