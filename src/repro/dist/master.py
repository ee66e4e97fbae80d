"""Pool master: real workers, heartbeats, death detection, any-R decode.

:class:`Master` listens on a socket, accepts worker registrations (the
``hello`` capability handshake, which now negotiates a wire codec per
connection — see :mod:`repro.dist.protocol`), and executes coded matmuls
against the pool: the master encodes per-worker shares with the jitted
``encode_*_at`` closures of :func:`repro.cdmm.elastic.share_encoders`
(B's shares come from a cache while the same weight comes back), ships each
share to a live worker process (chunked along the contraction axis when
``stream_chunk_bytes`` says the share is big enough to pipeline — the
worker accumulates partial products, so transfer and compute overlap),
and fires the any-R ``decode_op`` the moment the R-th
response lands — through :func:`repro.cdmm.elastic.decode_responses`,
the exact decode tail of the in-process elastic master, so the two paths
are bit-identical by construction.

Failure model.  A worker is dead when its socket drops (SIGKILL, crash,
network) or its heartbeat goes silent past ``heartbeat_timeout``.  Death
mid-request re-dispatches the worker's unanswered shares to surviving
workers (any process can compute any share — the share index, not the
process, is the paper's "worker"), so a request completes as long as one
process survives and R distinct shares can still be computed.  Membership
is tracked by :class:`repro.core.straggler.MembershipEvents`, so the
observed join/leave/response history is available as a real
:class:`~repro.core.straggler.WorkerTrace` (``Master.trace()``) and plugs
into everything built on trace semantics.

Shares are multiplexed: a pool of W processes serves schemes with any N
(round-robin assignment), decoupling pool size from the code's worker
count.  Requests are multiplexed too — every task carries a request id and
responses are routed to per-request queues — which is what lets the
serving scheduler (:mod:`repro.dist.scheduler`) keep several requests in
flight over one pool.

Bandwidth accounting: every connection counts pre-codec (raw) vs. on-wire
bytes; per-request totals land on :class:`PoolStats` and cumulative
totals (plus latency histograms in the shared ``repro.stats`` schema) on
``Master.stats()``.

:class:`LocalPool` spawns a master plus N ``python -m repro.dist.worker``
OS processes on a Unix-domain socket (TCP fallback) in one call, with
``kill()`` for failure injection and clean shutdown on ``close()`` — it
is the single-host specialization of :func:`repro.dist.launch.launch_pool`
and accepts the same :class:`~repro.dist.config.PoolConfig`.
"""
from __future__ import annotations

import math
import os
import queue
import signal
import socket
import subprocess
import tempfile
import threading
import time
import weakref
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro import settings
from repro.cdmm.elastic import NotEnoughResponders, decode_responses, share_encoders
from repro.core.straggler import MembershipEvents
from repro.obs import http as obs_http
from repro.obs import trace as obs
from repro.obs.health import DISPATCH_THRESHOLD, HealthTracker
from repro.obs.metrics import MetricsRegistry
from repro.stats import StatsSnapshot

from . import chips
from .config import Endpoint, PoolConfig, warn_deprecated_once
from .protocol import Channel, ProtocolError, listen, negotiate

__all__ = ["LocalPool", "Master", "PoolStats", "WorkerDied"]


def _shutdown_socket(sock: socket.socket) -> None:
    """Force-wake any thread blocked reading ``sock``, then close it.
    ``close()`` alone leaves a blocked ``recv`` sleeping forever;
    ``shutdown(SHUT_RDWR)`` delivers EOF first."""
    try:
        sock.shutdown(socket.SHUT_RDWR)
    except OSError:
        pass
    try:
        sock.close()
    except OSError:
        pass


class WorkerDied(RuntimeError):
    """A request became impossible: too few live workers remain to compute
    R distinct shares (every surviving share was already re-dispatched)."""


@dataclass(frozen=True)
class PoolStats:
    """Accounting of one pool execution (real wall-clock, real processes)."""

    dispatched: Tuple[int, ...]  # share indices shipped to workers
    live_idx: Tuple[int, ...]  # the R-subset actually decoded from
    workers: Tuple[int, ...]  # pool worker ids that served shares
    redispatched: int  # shares re-shipped after a worker death
    wall_ms: float  # master wall-clock for the call
    time_to_R_ms: float  # wall-clock until the R-th response landed
    hedged: int = 0  # shares speculatively re-shipped past their deadline
    batch: int = 1  # products the scheme packs per codeword (RMFE slots)
    fill: int = 1  # slots carrying real requests (rest were zero padding)
    # bandwidth accounting (shared schema: raw = pre-codec payload bytes,
    # bytes_* = what actually crossed the socket, framing included)
    raw_bytes_out: int = 0  # share payloads before the wire codec
    bytes_out: int = 0  # what the master actually sent
    raw_bytes_in: int = 0  # result payloads before the wire codec
    bytes_in: int = 0  # what the master actually received
    codecs: Tuple[str, ...] = ()  # negotiated codecs of the workers used


class _WorkerHandle:
    def __init__(self, wid: int, chan: Channel, caps: Dict):
        self.wid = wid
        self.chan = chan
        self.sock = chan.sock
        self.caps = caps
        self.codec = chan.codec
        self.name = caps.get("name", f"worker-{wid}")
        self.alive = True
        self.last_seen = time.time()
        self.send_lock = threading.Lock()
        # worker-published load figures (heartbeat piggyback)
        self.tasks_done = 0
        self.busy_us = 0.0
        self.compiles = 0  # XLA compiles in the worker process
        self.device_tasks: List[int] = []  # shares computed per local device
        # shares computed per product implementation ("xla" / "pallas")
        self.matmul_tasks: Dict[str, int] = {}
        self.tasks_sent = 0  # task headers this master sent the worker

    def send(self, header: Dict, arrays=None,
             codec: Optional[str] = None) -> Tuple[int, int]:
        with self.send_lock:
            return self.chan.send(header, arrays, codec=codec)


class _Request:
    """Routing state of one in-flight coded matmul."""

    def __init__(self, rid: int, R: int,
                 trace: Optional[obs.TraceContext] = None):
        self.rid = rid
        self.R = R
        self.trace = trace
        self.events: "queue.Queue" = queue.Queue()
        self.lock = threading.Lock()
        # task_id -> (share index, fa, gb, assigned wid, t_sent)
        self.pending: Dict[
            int, Tuple[int, np.ndarray, np.ndarray, int, float]
        ] = {}
        self.redispatched = 0
        self.satisfied: set = set()  # share indices already answered
        self.hedged_shares: set = set()  # shares hedged (at most once each)
        self.hedged = 0
        self.done = False
        # per-request bandwidth accounting (summed into PoolStats)
        self.raw_out = 0
        self.wire_out = 0
        self.raw_in = 0
        self.wire_in = 0
        self.codecs: set = set()


class _Weight:
    """One cached B operand: an owned read-only copy and the B shares
    encoded from it so far (share index -> read-only host array)."""

    def __init__(self, operand: np.ndarray):
        self.operand = operand
        self.shares: Dict[int, np.ndarray] = {}


class _WeightCache:
    """The B operands a pool multiplied by lately, with their encoded
    shares, per scheme instance.

    A served layer multiplies every request by one weight, so codeword
    after codeword stacks the same B and encodes the same N B shares.
    That holds while the pool serves one weight per scheme instance; the
    serving engine keeps one scheme per ``ProblemSpec``, so layers of one
    shape that share a pool (a model's stacked projections, its routed
    experts) mix weights in one scheme and miss.  ``lookup`` hits only
    when a stored operand has the same shape, dtype and elements: a caller
    may change its weight in place between calls, and that misses.  A
    strided sample of the elements is compared first, so a different
    weight misses without a full pass.  Each scheme keeps its
    ``PER_SCHEME`` most recently used operands: two, a full batch and a
    padded one of one weight.  A missed operand is stored once its first
    share is encoded, so a codeword that fails before that leaves nothing
    behind.  Shares are encoded from the owned copy, never from the
    caller's array, and stored read-only, because requests in flight keep
    references to them for redispatch and hedging.  Lookups and inserts
    take one lock; two concurrent misses may both encode, and the first
    stored entry is kept.
    """

    PER_SCHEME = 2
    SAMPLE = 4096  # elements compared before the full comparison

    def __init__(self):
        self._lock = threading.Lock()
        self._by_scheme: "weakref.WeakKeyDictionary" = (
            weakref.WeakKeyDictionary()
        )

    @classmethod
    def _match(cls, entries, B: np.ndarray) -> Optional[_Weight]:
        for w in entries:
            a = w.operand
            if a.shape != B.shape or a.dtype != B.dtype:
                continue
            step = max(1, a.size // cls.SAMPLE)
            if (np.array_equal(a.reshape(-1)[::step], B.reshape(-1)[::step])
                    and np.array_equal(a, B)):
                return w
        return None

    def lookup(self, scheme, B) -> Tuple[_Weight, bool]:
        """The stored entry equal to ``B``, made the scheme's most recent
        (a hit); else a new entry holding a copy of ``B``, stored by
        :meth:`share` once it holds a share."""
        B = np.asarray(B)
        with self._lock:
            seen = list(self._by_scheme.get(scheme, ()))
        # compared outside the lock: stored operands never change
        w = self._match(seen, B)
        if w is None:
            operand = np.array(B, copy=True)
            operand.setflags(write=False)
            return _Weight(operand), False
        with self._lock:
            entries = self._by_scheme.get(scheme, [])
            if w in entries:
                entries.remove(w)
                entries.append(w)
        return w, True

    def share(self, scheme, w: _Weight, i: int,
              encode) -> Tuple[np.ndarray, bool]:
        """B share ``i`` of ``w``, encoded by ``encode(operand, i)`` when
        missing; also whether it was there already.  Stores ``w`` for
        ``scheme`` with its first share, unless a concurrent miss stored an
        equal operand first."""
        with self._lock:
            gb = w.shares.get(i)
        if gb is not None:
            return gb, True
        gb = np.asarray(encode(w.operand, i))
        gb.setflags(write=False)
        with self._lock:
            gb = w.shares.setdefault(i, gb)
            entries = self._by_scheme.setdefault(scheme, [])
            if w not in entries and self._match(entries, w.operand) is None:
                entries.append(w)
                del entries[:-self.PER_SCHEME]
        return gb, False

    def clear(self) -> None:
        with self._lock:
            self._by_scheme.clear()


class Master:
    """Accept workers, track membership, execute coded matmuls on the pool.

    The master keeps the encoded B shares of the last two B operands of
    each scheme (see :class:`_WeightCache`): a codeword whose B equals one
    of them, byte for byte, encodes only A.  A keyed (secure) codeword
    neither reads nor fills it.  It pays while the pool multiplies by one
    weight per scheme instance; weights that take turns in one scheme miss.
    A miss pays a sampled comparison with each stored operand and one host
    copy of B, and the cache holds up to two operands with their N shares
    per scheme: for a 4096x4096 weight over GR(2^32,3) at batch 2, about
    0.4 GB of shares and 134 MB of operand per entry, until :meth:`close`.
    """

    def __init__(
        self,
        address: Optional[str] = None,
        heartbeat_timeout: Optional[float] = None,
        use_kernel: Optional[bool] = None,
        config: Optional[PoolConfig] = None,
    ):
        if chips.chip_bound():
            # encode/decode run here; the chips belong to the one worker
            chips.pin_host_to_cpu()
        cfg = config or PoolConfig()
        if heartbeat_timeout is not None:
            cfg = cfg.with_(heartbeat_timeout=heartbeat_timeout)
        if use_kernel is not None:
            cfg = cfg.with_(use_kernel=use_kernel)
        if address is not None:
            cfg = cfg.with_(endpoint=Endpoint.parse(address))
        self.config = cfg
        listen_addr = (
            cfg.endpoint.address if cfg.endpoint else "tcp:127.0.0.1:0"
        )
        self._listener, self.address = listen(listen_addr)
        self.heartbeat_timeout = cfg.heartbeat_timeout
        # True forces the Pallas kernel pool-wide; otherwise workers
        # compute with XLA's ring.matmul
        self.use_kernel = cfg.use_kernel
        self.transport = cfg.transport
        self.compression_level = cfg.compression_level
        self.stream_chunk_bytes = cfg.stream_chunk_bytes
        self.membership = MembershipEvents()
        self._weights = _WeightCache()
        self._workers: Dict[int, _WorkerHandle] = {}
        self._requests: Dict[int, _Request] = {}
        self._lock = threading.Lock()
        self._joined = threading.Condition(self._lock)
        self._next_wid = 0
        self._next_rid = 0
        self._next_task = 0
        self._next_echo = 0
        self._echo_waiters: Dict[int, Tuple[threading.Event, List]] = {}
        self._rr = 0  # round-robin cursor for share -> worker assignment
        self._closed = False
        # telemetry knobs: explicit config wins, else the settings registry
        # (REPRO_HEDGE_FACTOR / REPRO_HEALTH_EWMA / REPRO_OBS_HTTP_PORT /
        # REPRO_OBS_RETENTION)
        self.hedge_factor = float(
            cfg.hedge_factor if cfg.hedge_factor is not None
            else (settings.get_float("hedge_factor") or 0.0)
        )
        health_ewma = float(
            cfg.health_ewma if cfg.health_ewma is not None
            else (settings.get_float("health_ewma") or 0.2)
        )
        retention_s = float(settings.get_float("obs_retention") or 300.0)
        # cumulative accounting: a live MetricsRegistry the dispatch and
        # result paths record into inline; stats() reads it (shared
        # repro.stats schema, pool_-prefixed)
        self.metrics = MetricsRegistry("pool", retention_s=retention_s)
        for name, doc in (
            ("requests", "coded-matmul requests started on this pool"),
            ("completed", "requests decoded successfully"),
            ("failed", "requests that raised"),
            ("redispatched", "shares re-shipped after a worker death"),
            ("share_errors", "error replies: shares whose compute raised "
                             "on a worker"),
            ("hedged", "shares speculatively re-shipped past the hedge "
                       "deadline"),
            ("hedge_wasted", "hedged shares whose extra reply lost the "
                             "race (duplicate discarded)"),
            ("raw_bytes_out", "share payload bytes before the wire codec"),
            ("bytes_out", "bytes actually sent on the wire"),
            ("raw_bytes_in", "result payload bytes before the wire codec"),
            ("bytes_in", "bytes actually received on the wire"),
            ("heartbeats", "worker heartbeat messages received"),
            ("weight_lookups", "unkeyed codewords whose B operand was "
                               "looked up in the weight-share cache"),
            ("weight_hits", "lookups that found the same B operand and "
                            "reused its encoded shares"),
        ):
            self.metrics.counter(name, doc)
        self._wall_hist = self.metrics.histogram(
            "wall_ms", "request wall-clock (ms)"
        )
        self._time_to_R_hist = self.metrics.histogram(
            "time_to_R_ms", "dispatch -> R-th response (ms)"
        )
        self.metrics.gauge("workers_live", "live workers in the pool")
        self.metrics.gauge(
            "worker_health",
            "per-worker health score in (0, 1]: EWMA share round-trip "
            "and heartbeat jitter vs the pool median",
            label="wid",
        )
        self.metrics.gauge(
            "worker_tasks_done", "tasks completed, as self-reported on "
            "the worker's last heartbeat", label="wid",
        )
        # per-worker health: share round-trips land in _route_result,
        # heartbeat jitter in _reader_loop; dispatch ordering and the
        # hedge deadline both read it
        self.health = HealthTracker(
            alpha=health_ewma, retention_s=retention_s
        )
        # the admin HTTP plane: source/resolver registration is
        # unconditional (cheap, lets an externally started server see this
        # master); the server itself starts only when a port is configured
        self._obs_source = obs_http.register_source("pool", self.stats)
        obs_http.register_trace_resolver(self._resolve_trace)
        self._obs_server = None
        obs_port = (
            cfg.obs_http_port if cfg.obs_http_port is not None
            else settings.get_int("obs_http_port")
        )
        if obs_port is not None:
            self._obs_server = obs_http.start_server(obs_port)
        # rid -> trace_id of recently finished traced requests, so spans
        # from stragglers that answer after the any-R decode still land
        # on the right timeline (bounded: oldest entries roll off)
        self._done_traces: "Dict[int, str]" = {}
        self._done_traces_cap = 256
        # failure injection: per-worker-id compute delay stamped into task
        # headers (tests/CI park a victim's compute so SIGKILL lands mid-task)
        self.task_delay_ms: Dict[int, float] = {}
        # error injection: these workers raise instead of computing, which
        # exercises the bounded share-retry path without corrupting state
        self.task_fail_wids: set = set()
        self._accept_thread = threading.Thread(
            target=self._accept_loop, name="pool-accept", daemon=True
        )
        self._accept_thread.start()
        self._monitor_thread = threading.Thread(
            target=self._monitor_loop, name="pool-monitor", daemon=True
        )
        self._monitor_thread.start()

    # -- membership --------------------------------------------------------

    def _accept_loop(self) -> None:
        while not self._closed:
            try:
                sock, _ = self._listener.accept()
            except OSError:
                return  # listener closed
            threading.Thread(
                target=self._register, args=(sock,), daemon=True
            ).start()

    def _register(self, sock: socket.socket) -> None:
        try:
            chan = Channel(sock, level=self.compression_level)
            caps, _, _, _ = chan.recv()
        except (ProtocolError, OSError):
            sock.close()
            return
        if caps.get("type") != "hello":
            sock.close()
            return
        # per-connection codec: the strongest the peer decodes, or the
        # pinned transport when both sides support it; a v0 worker that
        # advertises nothing gets raw frames (full interop)
        chan.codec = negotiate(caps.get("codecs"), prefer=self.transport)
        with self._lock:
            wid = self._next_wid
            self._next_wid += 1
            handle = _WorkerHandle(wid, chan, caps)
            self._workers[wid] = handle
            self._joined.notify_all()
        self.membership.record_join(wid, time.time())
        threading.Thread(
            target=self._reader_loop, args=(handle,),
            name=f"pool-reader-{wid}", daemon=True,
        ).start()

    def _reader_loop(self, handle: _WorkerHandle) -> None:
        try:
            while True:
                header, arrays, raw, wire = handle.chan.recv()
                handle.last_seen = time.time()
                kind = header.get("type")
                if kind == "result":
                    self._account(raw_bytes_in=raw, bytes_in=wire)
                    self._route_result(handle, header, arrays, raw, wire)
                elif kind == "heartbeat":
                    # heartbeat inter-arrival jitter is a health signal:
                    # a stuttering worker is struggling long before it
                    # trips the death deadline
                    self.health.record_heartbeat(handle.wid)
                    handle.tasks_done = int(header.get("tasks_done", 0))
                    handle.busy_us = float(header.get("busy_us", 0.0))
                    handle.compiles = int(header.get("compiles", 0))
                    handle.device_tasks = list(
                        header.get("device_tasks", ())
                    )
                    handle.matmul_tasks = dict(
                        header.get("matmul_tasks", {})
                    )
                    self._account(heartbeats=1)
                elif kind == "echo_reply":
                    with self._lock:
                        waiter = self._echo_waiters.pop(
                            header.get("seq"), None
                        )
                    if waiter is not None:
                        event, slot = waiter
                        slot.append((raw, wire))
                        event.set()
        except (ProtocolError, OSError):
            self._on_death(handle)

    def _monitor_loop(self) -> None:
        while not self._closed:
            time.sleep(min(self.heartbeat_timeout / 4.0, 0.5))
            deadline = time.time() - self.heartbeat_timeout
            with self._lock:
                stale = [
                    h for h in self._workers.values()
                    if h.alive and h.last_seen < deadline
                ]
            for h in stale:
                # shutdown() (not close()) is what actually wakes a reader
                # thread blocked in recv with EOF, tripping its death path
                _shutdown_socket(h.sock)

    def _on_death(self, handle: _WorkerHandle) -> None:
        with self._lock:
            if not handle.alive:
                return
            handle.alive = False
            self._workers.pop(handle.wid, None)
            requests = list(self._requests.values())
        self.membership.record_leave(handle.wid, time.time())
        self.health.forget(handle.wid)
        _shutdown_socket(handle.sock)
        for req in requests:
            self._redispatch(req, handle.wid)

    def _route_result(
        self, handle: _WorkerHandle, header: Dict, arrays: Dict,
        raw: int = 0, wire: int = 0,
    ) -> None:
        rid = header.get("req")
        with self._lock:
            req = self._requests.get(rid)
            done_tid = self._done_traces.get(rid) if req is None else None
        if req is None:
            # request already decoded (straggler / duplicate) — but a
            # traced request still wants the late responder on its
            # timeline, tagged so the viewer can tell it lost the race
            if done_tid is not None:
                self._collect_worker_spans(
                    done_tid, handle, header, wire, late=True
                )
            return
        if req.trace is not None:
            self._collect_worker_spans(
                req.trace.trace_id, handle, header, wire, late=False
            )
        with req.lock:
            entry = req.pending.pop(header.get("task"), None)
            req.raw_in += raw
            req.wire_in += wire
        if entry is not None and header.get("ok"):
            # master-observed send->result round-trip: the health signal
            # covering comm + compute in one number (hedged duplicates
            # measure too — both round-trips really happened)
            self.health.record_share(
                handle.wid, (time.perf_counter() - entry[4]) * 1e3
            )
        self.membership.record_response(
            handle.wid, float(header.get("wall_us", 0.0)) / 1e3
        )
        if header.get("ok"):
            req.events.put(("result", int(header["i"]), arrays.get("h")))
        else:
            req.events.put(
                ("error", int(header["i"]), (handle.wid, header.get("err")))
            )

    def _collect_worker_spans(
        self, trace_id: str, handle: _WorkerHandle, header: Dict,
        wire: int, late: bool,
    ) -> None:
        """Land a result frame's compute span on the request's timeline.

        Tracing-capable workers piggyback their span on the reply
        (``spans`` header field); a v0 peer sends none, so the master
        synthesizes one from the ``wall_us`` it already reports, ending
        at receipt time — same schema either way, tagged so readers know
        which clock produced it.
        """
        entries = header.get("spans")
        tags = {
            "wid": handle.wid, "worker": handle.name,
            "share": header.get("i"), "wire_bytes": wire,
        }
        if late:
            tags["late"] = True
        tracer = obs.tracer()
        if entries:
            for span in obs.spans_from_wire(entries, trace_id, **tags):
                tracer.record(span)
        else:
            t1 = obs.now()
            wall_s = float(header.get("wall_us", 0.0)) / 1e6
            tracer.record(obs.Span(
                trace_id=trace_id, name="compute", component="worker",
                t_start=t1 - wall_s, t_end=t1,
                tags={**tags, "synthesized": True,
                      "ok": bool(header.get("ok"))},
            ))

    # -- introspection -----------------------------------------------------

    def live_workers(self) -> List[int]:
        with self._lock:
            return sorted(w for w, h in self._workers.items() if h.alive)

    def worker_caps(self) -> Dict[int, Dict]:
        with self._lock:
            return {w: dict(h.caps) for w, h in self._workers.items()}

    def worker_reports(self) -> Dict[int, Dict]:
        """Per live worker: tasks sent to it, and the figures of its last
        heartbeat (tasks done, compute wall, XLA compiles, shares computed
        on each of its devices and by each product implementation)."""
        with self._lock:
            return {
                w: {
                    "tasks_sent": h.tasks_sent,
                    "tasks_done": h.tasks_done,
                    "busy_us": h.busy_us,
                    "compiles": h.compiles,
                    "device_tasks": list(h.device_tasks),
                    "matmul_tasks": dict(h.matmul_tasks),
                }
                for w, h in self._workers.items() if h.alive
            }

    def worker_codecs(self) -> Dict[int, str]:
        """Negotiated wire codec per live worker."""
        with self._lock:
            return {w: h.codec for w, h in self._workers.items()}

    def trace(self):
        """The observed membership history as a real WorkerTrace."""
        return self.membership.trace()

    def _account(self, **deltas) -> None:
        for k, v in deltas.items():
            self.metrics.counter(k).inc(v)

    def stats(self) -> StatsSnapshot:
        """Cumulative master accounting in the shared ``repro.stats``
        snapshot schema (``pool_``-prefixed keys): counters,
        ``pool_bytes_in/out`` vs ``pool_raw_bytes_in/out`` (on-wire vs
        pre-codec), ``pool_wall_ms``/``pool_time_to_R_ms`` histograms
        with p50/p99/sum, and the live gauges (``pool_workers_live``,
        per-worker ``pool_worker_health_by_wid`` scores).  Legacy
        unprefixed keys still resolve (with one DeprecationWarning per
        key)."""
        with self._lock:
            live = {
                w: h for w, h in self._workers.items() if h.alive
            }
        self.metrics.gauge("workers_live").set(len(live))
        scores = self.health.scores()
        health_gauge = self.metrics.gauge("worker_health")
        tasks_gauge = self.metrics.gauge("worker_tasks_done")
        health_gauge.clear_labels(keep=list(live))
        tasks_gauge.clear_labels(keep=list(live))
        for wid, handle in live.items():
            health_gauge.set(round(scores.get(wid, 1.0), 4), key=wid)
            tasks_gauge.set(handle.tasks_done, key=wid)
        return self.metrics.snapshot()

    def _resolve_trace(self, key: str):
        """Map a ``/trace/<key>`` request id to its merged Timeline (or
        None when this master never saw it).  Accepts the pool's integer
        request id; raw trace-id strings fall through to the process
        tracer inside :mod:`repro.obs.http`."""
        try:
            rid = int(key)
        except ValueError:
            return None
        with self._lock:
            req = self._requests.get(rid)
            tid = (
                req.trace.trace_id
                if req is not None and req.trace is not None
                else self._done_traces.get(rid)
            )
        if tid is None:
            return None
        timeline = obs.tracer().timeline(tid)
        return timeline if timeline.spans else None

    def wait_for_workers(self, n: int, timeout: float = 60.0) -> None:
        deadline = time.time() + timeout
        with self._joined:
            while len(self._workers) < n:
                remaining = deadline - time.time()
                if remaining <= 0 or not self._joined.wait(remaining):
                    raise TimeoutError(
                        f"pool has {len(self._workers)}/{n} workers after "
                        f"{timeout:.0f}s"
                    )

    # -- calibration probe -------------------------------------------------

    def echo(
        self, nbytes: int, wid: Optional[int] = None,
        timeout: float = 30.0, codec: Optional[str] = None,
    ) -> Dict[str, float]:
        """Time one real round-trip of an ``nbytes`` share-shaped payload
        to a worker and back (the calibration probe behind the pool
        backend's measured comm coefficients).  Returns seconds and byte
        counts: ``{"rtt_s", "raw_bytes", "wire_bytes"}``."""
        with self._lock:
            handle = (
                self._workers.get(wid) if wid is not None
                else next(iter(sorted(self._workers.items())), (None, None))[1]
            )
        if handle is None or not handle.alive:
            raise WorkerDied("no live worker for echo probe")
        payload = np.arange(max(1, nbytes // 4), dtype=np.uint32)
        with self._lock:
            seq = self._next_echo
            self._next_echo += 1
            event, slot = threading.Event(), []
            self._echo_waiters[seq] = (event, slot)
        t0 = time.perf_counter()
        use = handle.codec if codec is None else codec
        raw, wire = handle.send(
            {"type": "echo", "seq": seq, "codec": use},
            {"x": payload}, codec=use,
        )
        if not event.wait(timeout):
            with self._lock:
                self._echo_waiters.pop(seq, None)
            raise TimeoutError(f"echo probe {seq} got no reply in {timeout}s")
        rtt = time.perf_counter() - t0
        raw_back, wire_back = slot[0]
        return {
            "rtt_s": rtt,
            "raw_bytes": float(raw + raw_back),
            "wire_bytes": float(wire + wire_back),
        }

    # -- dispatch ----------------------------------------------------------

    def _pick_worker(self, exclude: Tuple[int, ...] = ()) -> _WorkerHandle:
        # health read happens before the dispatch lock (the tracker has
        # its own lock and never takes this one: no ordering cycle)
        scores = self.health.scores()
        with self._lock:
            live = [
                h for w, h in sorted(self._workers.items())
                if h.alive and w not in exclude
            ]
            if not live:
                live = [h for _, h in sorted(self._workers.items()) if h.alive]
            if not live:
                raise WorkerDied("pool has no live workers")
            # health-aware ordering: round-robin over the healthy subset;
            # known-slow workers (score < threshold) only serve when no
            # healthier worker is available.  With no health data every
            # score is 1.0 and this is exactly the old pure round-robin.
            healthy = [
                h for h in live
                if scores.get(h.wid, 1.0) >= DISPATCH_THRESHOLD
            ]
            pool = healthy or live
            self._rr += 1
            return pool[self._rr % len(pool)]

    def _stream_chunks(self, fa: np.ndarray, gb: np.ndarray) -> int:
        """How many chunks to pipeline this share in (1 = single message).
        Only 3-D planar block shares with a shared contraction axis are
        chunkable: ``fa (t,r,D) @ gb (r,s,D)`` splits along r exactly."""
        if self.stream_chunk_bytes <= 0:
            return 1
        if (
            getattr(fa, "ndim", 0) != 3 or getattr(gb, "ndim", 0) != 3
            or fa.shape[1] != gb.shape[0]
        ):
            return 1
        r = int(fa.shape[1])
        total = int(fa.nbytes) + int(gb.nbytes)
        if total <= self.stream_chunk_bytes:
            return 1
        return max(1, min(r, math.ceil(total / self.stream_chunk_bytes)))

    def _send_task(
        self,
        req: _Request,
        scheme,
        i: int,
        fa: np.ndarray,
        gb: np.ndarray,
        exclude: Tuple[int, ...] = (),
        redispatch: bool = False,
        hedge: bool = False,
    ) -> int:
        tried = set(exclude)
        while True:
            handle = self._pick_worker(tuple(tried))
            with self._lock:
                task = self._next_task
                self._next_task += 1
            header = {
                "type": "task",
                "req": req.rid,
                "task": task,
                "i": i,
                "codec": handle.codec,
                "ring": {
                    "p": scheme.ring.p,
                    "e": scheme.ring.e,
                    "degrees": list(scheme.ring.degrees),
                },
            }
            # trace_id rides the task header only when this worker's hello
            # advertised tracing — a v0 peer never sees the field and the
            # master synthesizes its compute span from wall_us instead
            if req.trace is not None and handle.caps.get("tracing"):
                header["trace"] = req.trace.trace_id
            header["use_kernel"] = bool(self.use_kernel)
            delay = self.task_delay_ms.get(handle.wid, 0.0)
            if delay > 0.0:
                header["delay_ms"] = delay
            if handle.wid in self.task_fail_wids:
                header["inject_fail"] = True
            with req.lock:
                req.pending[task] = (
                    i, fa, gb, handle.wid, time.perf_counter()
                )
            with self._lock:
                handle.tasks_sent += 1
            try:
                t_send = obs.now()
                chunks = self._stream_chunks(fa, gb)
                if chunks <= 1:
                    raw, wire = handle.send(header, {"fa": fa, "gb": gb})
                else:
                    # pipelined transfer: ship the share as contraction-
                    # axis slices so the worker computes partial products
                    # while later chunks are still in flight.  The header
                    # must promise exactly the number of chunk messages
                    # that follow (ceil(r/step) can undershoot the chunk
                    # target when step rounds up), or the worker's
                    # accumulator waits forever on a phantom chunk.
                    r = fa.shape[1]
                    step = math.ceil(r / chunks)
                    starts = range(0, r, step)
                    header["stream"] = len(starts)
                    raw, wire = handle.send(header)
                    for seq, lo in enumerate(starts):
                        cfa = fa[:, lo:lo + step, :]
                        cgb = gb[lo:lo + step, :, :]
                        short = step - cfa.shape[1]
                        if short:
                            # zero-pad the tail chunk (zero rows add zero
                            # to the product): every chunk of a share has
                            # one shape, so the worker compiles it once
                            cfa = np.pad(cfa, ((0, 0), (0, short), (0, 0)))
                            cgb = np.pad(cgb, ((0, short), (0, 0), (0, 0)))
                        craw, cwire = handle.send(
                            {
                                "type": "chunk", "req": req.rid,
                                "task": task, "seq": seq,
                            },
                            {
                                "fa": np.ascontiguousarray(cfa),
                                "gb": np.ascontiguousarray(cgb),
                            },
                        )
                        raw += craw
                        wire += cwire
                with req.lock:
                    req.raw_out += raw
                    req.wire_out += wire
                    req.codecs.add(handle.codec)
                self._account(raw_bytes_out=raw, bytes_out=wire)
                # the send span IS the dead worker's footprint when it
                # never answers: timeline evidence the share went there
                obs.tracer().add(
                    req.trace, "send", "pool", t_send, obs.now(),
                    wid=handle.wid, share=i, task=task,
                    raw_bytes=raw, wire_bytes=wire, chunks=chunks,
                    codec=handle.codec, redispatch=redispatch,
                    hedge=hedge,
                )
                return handle.wid
            except OSError:
                # the send found the corpse; retry on another worker (the
                # death path would skip this task if _on_death already ran)
                with req.lock:
                    req.pending.pop(task, None)
                tried.add(handle.wid)
                self._on_death(handle)

    def _redispatch(self, req: _Request, dead_wid: int) -> None:
        """Re-ship the dead worker's unanswered shares to survivors."""
        with req.lock:
            if req.done:
                return
            orphans = [
                (task, i, fa, gb)
                for task, (i, fa, gb, wid, _t) in req.pending.items()
                # a share already satisfied (its hedge or twin answered)
                # has nothing left to recover
                if wid == dead_wid and i not in req.satisfied
            ]
            for task, *_ in orphans:
                req.pending.pop(task, None)
        for _, i, fa, gb in orphans:
            try:
                self._send_task(req, req.scheme, i, fa, gb,
                                exclude=(dead_wid,), redispatch=True)
                with req.lock:
                    req.redispatched += 1
                self._account(redispatched=1)
            except WorkerDied as e:
                req.events.put(("dead", -1, str(e)))
                return

    def _maybe_hedge(self, req: _Request, scheme, got) -> Optional[float]:
        """Speculative re-dispatch sweep: any share outstanding past the
        health-derived deadline (p95 of recent round-trips x
        ``hedge_factor``) is re-shipped once to a different live worker
        — *before* the heartbeat timeout would declare its holder dead.
        First valid reply wins; the duplicate is discarded idempotently
        in ``execute``.  Returns seconds until the next share becomes
        hedge-due (None when hedging is off/armed with no evidence), so
        the wait loop knows how long it may block.
        """
        deadline_ms = self.health.hedge_deadline_ms(self.hedge_factor)
        if deadline_ms is None:
            return None
        now_pc = time.perf_counter()
        due: List[Tuple[int, np.ndarray, np.ndarray, int]] = []
        next_due: Optional[float] = None
        with req.lock:
            for task, (i, fa, gb, wid, t_sent) in req.pending.items():
                if i in got or i in req.satisfied or i in req.hedged_shares:
                    continue
                age_ms = (now_pc - t_sent) * 1e3
                if age_ms >= deadline_ms:
                    due.append((i, fa, gb, wid))
                else:
                    remain = (deadline_ms - age_ms) / 1e3
                    if next_due is None or remain < next_due:
                        next_due = remain
        for i, fa, gb, wid in due:
            # hedging needs a genuinely spare worker: another live
            # process besides the one still holding the share
            # (_pick_worker's exclude falls back to everyone otherwise)
            if not (set(self.live_workers()) - {wid}):
                continue
            with req.lock:
                if i in req.satisfied or i in req.hedged_shares:
                    continue
                req.hedged_shares.add(i)
            try:
                self._send_task(
                    req, scheme, i, fa, gb, exclude=(wid,), hedge=True
                )
            except WorkerDied:
                continue  # the original dispatch may still answer
            with req.lock:
                req.hedged += 1
            self._account(hedged=1)
        return next_due

    def _encode_share(self, scheme, encoders, A, B, i: int, key,
                      weight: Optional[_Weight]):
        """Share ``i``'s host ``(fa, gb)``, and whether ``gb`` came from the
        weight cache.  With ``weight`` (an unkeyed codeword's cache entry)
        B's share is the cached one, encoded from the entry's own copy of
        B on first use; without, both halves are encoded here."""
        import jax.numpy as jnp

        encode_a, encode_b = encoders
        args = (jnp.int32(i),) if key is None else (jnp.int32(i), key)
        fa = np.asarray(encode_a(A, *args))
        if weight is None:
            return fa, np.asarray(encode_b(B, *args)), False
        gb, hit = self._weights.share(
            scheme, weight, i, lambda b, j: encode_b(b, jnp.int32(j)))
        return fa, gb, hit

    # -- protocol entry point ----------------------------------------------

    def execute(
        self,
        scheme,
        A,
        B,
        mask=None,
        key=None,
        timeout: Optional[float] = None,
        batch_fill: Optional[int] = None,
        trace: Optional[obs.TraceContext] = None,
    ) -> Tuple[np.ndarray, PoolStats]:
        """Run one coded matmul on the pool; returns (C, PoolStats).

        ``mask`` is the usual (N,)-bool share-liveness vector: masked-out
        share indices are never dispatched (the test seam for simulating
        straggler budgets deterministically).  ``key`` feeds the keyed
        encode of secure schemes — encode runs master-side, so workers
        only ever see masked shares, and never touches the weight cache
        (fresh masks per batch); without it, B's shares come from the
        cache when B equals a recent operand of ``scheme`` byte for byte.
        ``batch_fill`` is observability from
        a coalescing caller: how many of the scheme's batch slots carry
        real requests (the rest are padding), surfaced on PoolStats.
        ``trace`` carries an upstream :class:`repro.obs.TraceContext`
        (scheduler/serving); when tracing is enabled and none is passed, a
        fresh one is opened so direct ``Master.execute`` calls trace too.
        """
        t0 = time.perf_counter()
        if trace is None:
            trace = obs.maybe_context("pool")
        tracer = obs.tracer()
        N, R = scheme.N, scheme.R
        shares = list(range(N))
        if mask is not None:
            m = np.asarray(mask, dtype=bool)
            if len(m) != N:
                raise ValueError(f"mask has {len(m)} entries, scheme N={N}")
            shares = [i for i in shares if m[i]]
        if len(shares) < R:
            raise NotEnoughResponders(
                f"{scheme.name}: mask leaves {len(shares)} shares, "
                f"decode needs R={R}"
            )
        encoders = share_encoders(scheme, keyed=key is not None)

        with self._lock:
            rid = self._next_rid
            self._next_rid += 1
            req = _Request(rid, R, trace=trace)
            req.scheme = scheme
            self._requests[rid] = req
        self._account(requests=1)
        deadline = time.perf_counter() + timeout if timeout else None
        workers_used: List[int] = []
        ok = False
        try:
            # an unkeyed codeword takes B's shares from the weight cache;
            # the lookup is timed in the first share's encode span
            weight = None
            for i in shares:
                t_enc = obs.now()
                if key is None and weight is None:
                    weight, found = self._weights.lookup(scheme, B)
                    self._account(weight_lookups=1, weight_hits=int(found))
                fa, gb, hit = self._encode_share(scheme, encoders, A, B, i,
                                                 key, weight)
                tracer.add(trace, "encode", "pool", t_enc, obs.now(),
                           share=i, scheme=scheme.name, hit=hit)
                wid = self._send_task(req, scheme, i, fa, gb)
                workers_used.append(wid)
            t_wait = obs.now()

            got: Dict[int, np.ndarray] = {}
            errors: Dict[int, int] = {}  # share -> failed compute attempts
            t_R = None
            while len(got) < R:
                wait = None
                if deadline is not None:
                    wait = deadline - time.perf_counter()
                    if wait <= 0:
                        raise TimeoutError(
                            f"pool request {rid}: {len(got)}/{R} responses "
                            f"after {timeout}s"
                        )
                poll = wait
                if self.hedge_factor > 0:
                    # hedge sweep, then bound the blocking get by the next
                    # share's hedge deadline so overdue shares re-ship
                    # promptly instead of waiting out the request timeout
                    next_due = self._maybe_hedge(req, scheme, got)
                    if next_due is None:
                        next_due = 0.25  # deadline not armed yet: re-check
                    poll = max(
                        1e-3,
                        next_due if poll is None else min(poll, next_due),
                    )
                try:
                    kind, i, payload = req.events.get(timeout=poll)
                except queue.Empty:
                    if self.hedge_factor > 0:
                        continue  # hedge wakeup; loop top re-checks deadline
                    raise TimeoutError(
                        f"pool request {rid}: {len(got)}/{R} responses "
                        f"after {timeout}s"
                    ) from None
                if kind == "result":
                    if i in got:
                        # duplicate reply (a hedge twin, or an error-retry
                        # racing its original): first valid reply already
                        # won — discard idempotently
                        if i in req.hedged_shares:
                            self._account(hedge_wasted=1)
                    else:
                        got[i] = payload
                        with req.lock:
                            req.satisfied.add(i)
                elif kind == "error":
                    # a compute error is a worker failure, not a request
                    # failure: retry the share ONCE on a different worker,
                    # then write it off — the any-R decode only needs R of
                    # the remaining shares
                    bad_wid, err = payload
                    self._account(share_errors=1)
                    errors[i] = errors.get(i, 0) + 1
                    healthy = [
                        s for s in shares
                        if s in got or errors.get(s, 0) < 2
                    ]
                    if len(healthy) < R:
                        raise RuntimeError(
                            f"pool request {rid}: share {i} failed "
                            f"{errors[i]}x and only {len(healthy)} viable "
                            f"shares remain (R={R}); last error: {err}"
                        )
                    if errors[i] < 2 and i not in got:
                        t_enc = obs.now()
                        fa, gb, hit = self._encode_share(
                            scheme, encoders, A, B, i, key, weight)
                        tracer.add(trace, "encode", "pool", t_enc,
                                   obs.now(), share=i, retry=True, hit=hit)
                        self._send_task(
                            req, scheme, i, fa, gb,
                            exclude=(bad_wid,), redispatch=True,
                        )
                else:  # "dead": no live workers remain for a re-dispatch
                    raise WorkerDied(
                        f"pool request {rid}: {payload} with {len(got)}/{R} "
                        f"responses collected"
                    )
            t_R = (time.perf_counter() - t0) * 1e3
            with req.lock:
                req.done = True
            # the any-R race: dispatch done -> R-th response landed
            tracer.add(trace, "wait_R", "pool", t_wait, obs.now(),
                       R=R, responders=sorted(got),
                       redispatched=req.redispatched, hedged=req.hedged)
            t_dec = obs.now()
            C = decode_responses(scheme, got)
            tracer.add(trace, "decode", "pool", t_dec, obs.now(),
                       live_idx=sorted(got)[:R], scheme=scheme.name)
            wall_ms = (time.perf_counter() - t0) * 1e3
            stats = PoolStats(
                dispatched=tuple(shares),
                live_idx=tuple(sorted(got))[:R],
                workers=tuple(sorted(set(workers_used))),
                redispatched=req.redispatched,
                wall_ms=wall_ms,
                time_to_R_ms=t_R,
                hedged=req.hedged,
                batch=int(getattr(scheme, "batch", 1)),
                fill=(int(batch_fill) if batch_fill is not None
                      else int(getattr(scheme, "batch", 1))),
                raw_bytes_out=req.raw_out,
                bytes_out=req.wire_out,
                raw_bytes_in=req.raw_in,
                bytes_in=req.wire_in,
                codecs=tuple(sorted(req.codecs)),
            )
            ok = True
            self._account(completed=1)
            self._wall_hist.observe(wall_ms)
            self._time_to_R_hist.observe(t_R)
            return C, stats
        finally:
            if not ok:
                self._account(failed=1)
            with self._lock:
                self._requests.pop(rid, None)
                if trace is not None:
                    # keep routing late responders' spans to this timeline
                    self._done_traces[rid] = trace.trace_id
                    while len(self._done_traces) > self._done_traces_cap:
                        self._done_traces.pop(next(iter(self._done_traces)))

    # -- lifecycle ---------------------------------------------------------

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self._weights.clear()
        obs_http.unregister_source(self._obs_source)
        obs_http.unregister_trace_resolver(self._resolve_trace)
        with self._lock:
            handles = list(self._workers.values())
            self._workers.clear()
        for h in handles:
            try:
                h.send({"type": "shutdown"})
            except OSError:
                pass
            _shutdown_socket(h.sock)
        try:
            self._listener.close()
        except OSError:
            pass

    def __enter__(self) -> "Master":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


# --------------------------------------------------------------------------
# local pools: master + N worker OS processes in one call
# --------------------------------------------------------------------------


def _worker_env() -> Dict[str, str]:
    """Child env: inherit, but make sure the repro package resolves."""
    import repro

    env = dict(os.environ)
    # repro may be a namespace package (no __init__.py): __path__ still
    # points at the package directory; its parent is the import root
    pkg_dir = (repro.__file__ and os.path.dirname(repro.__file__)) or list(
        repro.__path__
    )[0]
    src = os.path.dirname(os.path.abspath(pkg_dir))
    parts = [src] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    env["PYTHONPATH"] = os.pathsep.join(dict.fromkeys(parts))
    return env


_LEGACY_POOL_ARGS = (
    "workers", "address", "heartbeat_s", "heartbeat_timeout", "use_kernel",
    "spawn_timeout",
)


class LocalPool:
    """A master plus N local worker OS processes (the zero-config pool).

    The single-host specialization of the launcher
    (:func:`repro.dist.launch.launch_pool`): prefers a Unix-domain socket
    under a private tempdir, falls back to loopback TCP.  ``kill(k)``
    SIGKILLs k workers (failure injection); ``close()`` shuts the master
    down and reaps every child.

    Preferred construction is ``LocalPool(config=PoolConfig(...))``;
    keyword arguments (``workers=``, ``address=``, ...) remain supported
    and override the config.  Positional arguments are deprecated (one
    ``DeprecationWarning`` per process) but keep working.
    """

    def __init__(self, *args, config: Optional[PoolConfig] = None, **kwargs):
        if args:
            warn_deprecated_once(
                "LocalPool-positional",
                "positional LocalPool arguments are deprecated; pass "
                "LocalPool(config=PoolConfig(workers=...)) or keyword "
                "arguments",
            )
            for name, val in zip(_LEGACY_POOL_ARGS, args):
                if name in kwargs:
                    raise TypeError(
                        f"LocalPool got multiple values for {name!r}"
                    )
                kwargs[name] = val
        unknown = set(kwargs) - set(_LEGACY_POOL_ARGS)
        if unknown:
            raise TypeError(f"LocalPool got unexpected {sorted(unknown)}")
        cfg = config or PoolConfig()
        if "address" in kwargs and kwargs["address"] is not None:
            cfg = cfg.with_(endpoint=Endpoint.parse(kwargs["address"]))
        for name in ("workers", "heartbeat_s", "heartbeat_timeout",
                     "use_kernel", "spawn_timeout"):
            if name in kwargs:
                cfg = cfg.with_(**{name: kwargs[name]})
        self.config = cfg
        self._tmpdir = None
        if cfg.endpoint is None:
            if hasattr(socket, "AF_UNIX"):
                self._tmpdir = tempfile.mkdtemp(prefix="repro-pool-")
                cfg = cfg.with_(endpoint=Endpoint.unix(
                    os.path.join(self._tmpdir, "pool.sock")
                ))
            else:  # pragma: no cover - non-POSIX fallback
                cfg = cfg.with_(endpoint=Endpoint.tcp("127.0.0.1", 0))
        self.master = Master(config=cfg)
        # the launcher owns process spawning; LocalPool is its local case
        from .launch import spawn_local_workers

        self.procs: List[subprocess.Popen] = []
        try:
            self.procs = spawn_local_workers(
                self.master.address, cfg.workers,
                heartbeat_s=cfg.heartbeat_s, name_prefix="local",
            )
            # one on a TPU host, whatever cfg.workers says
            self._wait_for_workers(len(self.procs), cfg.spawn_timeout)
        except BaseException:
            self.close()
            raise

    def _wait_for_workers(self, n: int, timeout: float) -> None:
        """Wait for ``n`` hellos; a worker process that exits first (a
        device that failed to start, a bad import) fails the wait at once
        instead of at the timeout."""
        deadline = time.time() + timeout
        while True:
            try:
                self.master.wait_for_workers(
                    n, timeout=max(0.0, min(0.5, deadline - time.time()))
                )
                return
            except TimeoutError:
                for proc in self.procs:
                    if proc.poll() is not None:
                        raise RuntimeError(
                            f"worker pid {proc.pid} exited with code "
                            f"{proc.returncode} before joining the pool"
                        ) from None
                if time.time() >= deadline:
                    raise

    @property
    def address(self) -> str:
        return self.master.address

    def execute(self, scheme, A, B, mask=None, key=None, timeout=None,
                batch_fill=None, trace=None):
        return self.master.execute(scheme, A, B, mask=mask, key=key,
                                   timeout=timeout, batch_fill=batch_fill,
                                   trace=trace)

    def stats(self) -> Dict[str, object]:
        """Cumulative pool accounting (shared repro.stats schema)."""
        return self.master.stats()

    def kill(self, k: int = 1, sig: int = signal.SIGKILL) -> List[int]:
        """SIGKILL ``k`` live worker processes; returns the killed pids."""
        killed = []
        for proc in self.procs:
            if len(killed) >= k:
                break
            if proc.poll() is None:
                os.kill(proc.pid, sig)
                killed.append(proc.pid)
        for pid in killed:  # reap promptly so poll() reflects reality
            for proc in self.procs:
                if proc.pid == pid:
                    proc.wait(timeout=30)
        return killed

    def alive_count(self) -> int:
        return sum(1 for p in self.procs if p.poll() is None)

    def close(self) -> None:
        self.master.close()
        for proc in self.procs:
            if proc.poll() is None:
                proc.terminate()
        for proc in self.procs:
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:  # pragma: no cover
                proc.kill()
                # a worker holding four TPU chips took over 20 s to exit
                # while its devices were released
                proc.wait(timeout=120)
        if self._tmpdir:
            try:
                sock = os.path.join(self._tmpdir, "pool.sock")
                if os.path.exists(sock):
                    os.unlink(sock)
                os.rmdir(self._tmpdir)
            except OSError:  # pragma: no cover
                pass

    def __enter__(self) -> "LocalPool":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
