"""repro.dist: the multi-process worker-pool runtime, tested for real.

Covers the wire protocol (frame/codec round-trips), the membership
bookkeeping (``MembershipEvents`` -> ``WorkerTrace``), and — against a
real pool of worker OS processes shared across the module — pool-vs-local
bit-identicality, share multiplexing (scheme.N > pool size), the serving
scheduler (concurrency, plan cache, admission control), and the headline
failure-injection property: SIGKILL N - R workers MID-REQUEST and the
any-R decode still returns the oracle product bit for bit.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import socket
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.cdmm import ProblemSpec, coded_matmul, plan
from repro.core import make_ring
from repro.core.straggler import MembershipEvents
from repro.dist import (
    LocalPool,
    PoolBackend,
    PoolScheduler,
    SchedulerSaturated,
)
from repro.dist.protocol import (
    ProtocolError,
    connect,
    listen,
    parse_address,
    recv_msg,
    send_msg,
)

# multi-process pool smokes dominate tier-1 wall time; deselected by
# `tools/ci.sh --fast` (see tests/conftest.py for the marker)
pytestmark = pytest.mark.slow

Z32 = make_ring(2, 32, ())
KEY = jax.random.PRNGKey(7)
POOL_WORKERS = 4


# --------------------------------------------------------------------------
# protocol (no processes involved)
# --------------------------------------------------------------------------


def _socketpair():
    a, b = socket.socketpair()
    return a, b


def test_protocol_roundtrip_arrays():
    a, b = _socketpair()
    arrays = {
        "fa": np.arange(12, dtype=np.uint32).reshape(3, 4),
        "gb": np.zeros((2, 2, 5), dtype=np.uint32),
    }
    send_msg(a, {"type": "task", "i": 3, "nested": {"x": [1, 2]}}, arrays)
    header, got = recv_msg(b)
    assert header["type"] == "task" and header["i"] == 3
    assert header["nested"] == {"x": [1, 2]}
    assert sorted(got) == ["fa", "gb"]
    for name in arrays:
        assert got[name].dtype == arrays[name].dtype
        np.testing.assert_array_equal(got[name], arrays[name])
    a.close(), b.close()


def test_protocol_empty_arrays_and_many_messages():
    a, b = _socketpair()
    for k in range(5):
        send_msg(a, {"k": k})
    for k in range(5):
        header, got = recv_msg(b)
        assert header["k"] == k and got == {}
    a.close(), b.close()


def test_protocol_peer_hangup_raises():
    a, b = _socketpair()
    a.sendall(b"\x00\x00\x01\x00partial")  # 256-byte frame, 7 bytes sent
    a.close()
    with pytest.raises(ProtocolError):
        recv_msg(b)
    b.close()


def test_parse_address():
    assert parse_address("tcp:127.0.0.1:80") == ("tcp", ("127.0.0.1", 80))
    assert parse_address("unix:/tmp/x.sock") == ("unix", "/tmp/x.sock")
    for bad in ("http:x", "tcp:nohost", "unix:", "tcp:h:p", "tcp:h:-1"):
        with pytest.raises(ValueError):
            parse_address(bad)


def test_listen_connect_tcp_ephemeral():
    listener, addr = listen("tcp:127.0.0.1:0")
    assert addr.startswith("tcp:127.0.0.1:") and not addr.endswith(":0")
    results = {}

    def _accept():
        sock, _ = listener.accept()
        results["header"], _ = recv_msg(sock)
        sock.close()

    t = threading.Thread(target=_accept)
    t.start()
    client = connect(addr, timeout=10)
    send_msg(client, {"hello": True})
    t.join(10)
    assert results["header"]["hello"] is True
    client.close(), listener.close()


# --------------------------------------------------------------------------
# membership bookkeeping
# --------------------------------------------------------------------------


def test_membership_events_to_trace():
    ev = MembershipEvents()
    t0 = 100.0
    ev.record_join("a", t0)
    ev.record_join("b", t0 + 0.05)
    ev.record_response("a", 12.0)
    ev.record_leave("b", t0 + 0.2)
    assert ev.live() == ("a",)
    assert ev.seen() == ("a", "b")
    tr = ev.trace()
    assert tr.N == 2
    assert tr.join_ms[0] == 0.0 and tr.join_ms[1] == pytest.approx(50.0)
    # "a" responded (12 ms), "b" left before ever responding
    assert tr.mask().tolist() == [True, False]
    # rejoin clears the leave
    ev.record_join("b", t0 + 0.3)
    assert set(ev.live()) == {"a", "b"}


# --------------------------------------------------------------------------
# real worker processes (one pool for the whole module)
# --------------------------------------------------------------------------


@pytest.fixture(scope="module")
def pool():
    with LocalPool(workers=POOL_WORKERS) as p:
        yield p


def _tasks_sent(master) -> int:
    return sum(r["tasks_sent"] for r in master.worker_reports().values())


def _problem(scheme, rng):
    if scheme.batch > 1:
        A = scheme.base.random(rng, (scheme.batch, 8, 8))
        B = scheme.base.random(rng, (scheme.batch, 8, 8))
    else:
        A = scheme.base.random(rng, (8, 8))
        B = scheme.base.random(rng, (8, 8))
    return A, B


def test_capability_handshake(pool):
    ndev = len(jax.local_devices())  # 8 (XLA_FLAGS above), as workers see
    caps = pool.master.worker_caps()
    assert len(caps) >= 1
    for c in caps.values():
        # the devices as the worker's JAX reports them: the test suite runs
        # on the CPU, and workers inherit this process's XLA_FLAGS
        assert c["platform"] == "cpu"
        assert c["device_kind"] == "cpu"
        assert c["device_count"] == ndev
        assert c["rings"]["p2_max_e"] == 32
        assert "entries" in c["autotune"]
    # a CPU worker computes every share on its first device (shares spread
    # over accelerator devices only), and reports what it computed
    spec = ProblemSpec(t=8, r=8, s=8, n=1, ring=Z32, N=8, straggler_budget=2)
    scheme = plan(spec).instantiate()
    A, B = _problem(scheme, np.random.default_rng(11))
    pool.execute(scheme, A, B, timeout=120)
    deadline = time.time() + 30
    while True:
        reports = pool.master.worker_reports()
        if all(r["tasks_done"] == r["tasks_sent"] for r in reports.values()):
            break
        assert time.time() < deadline, reports
        time.sleep(0.05)
    assert [len(r["device_tasks"]) for r in reports.values()] == [1] * len(
        reports)
    assert sum(r["device_tasks"][0] for r in reports.values()) >= scheme.N
    # ... and by which implementation: XLA's Ring.matmul for every share
    for r in reports.values():
        assert r["matmul_tasks"] == (
            {"xla": r["device_tasks"][0]} if r["device_tasks"][0] else {})


def test_worker_spreads_shares_over_its_devices():
    """On an accelerator host the one worker computes share i on local
    device i mod count; this module's 8 virtual CPU devices stand in for
    the chips (in-process, no socket traffic)."""
    from repro.dist.worker import WorkerRuntime

    rt = WorkerRuntime(sock=None)
    rt._devices = jax.local_devices()
    x = np.arange(4, dtype=np.uint32).reshape(2, 2)
    for i in range(2 * len(rt._devices)):
        fa, gb = rt._place({"i": i}, {"fa": x, "gb": x})
        want = rt._devices[i % len(rt._devices)]
        assert fa.devices() == gb.devices() == {want}


def test_pool_matches_local_and_multiplexes_shares(pool):
    # N=8 scheme over a 4-process pool: shares multiplex round-robin
    spec = ProblemSpec(t=8, r=8, s=8, n=1, ring=Z32, N=8, straggler_budget=2)
    scheme = plan(spec).instantiate()
    assert scheme.N > POOL_WORKERS
    rng = np.random.default_rng(0)
    A, B = _problem(scheme, rng)
    be = PoolBackend(pool, timeout=120)
    C = coded_matmul(A, B, scheme, backend=be)
    C_local = coded_matmul(A, B, scheme, backend="local")
    np.testing.assert_array_equal(np.asarray(C), np.asarray(C_local))
    stats = be.last_stats
    assert stats.dispatched == tuple(range(scheme.N))
    assert len(stats.live_idx) == scheme.R
    assert set(stats.workers) <= set(range(POOL_WORKERS))


def test_pool_respects_mask_subset(pool):
    spec = ProblemSpec(t=8, r=8, s=8, n=1, ring=Z32, N=8, straggler_budget=4)
    scheme = plan(spec).instantiate()
    rng = np.random.default_rng(1)
    A, B = _problem(scheme, rng)
    live = rng.choice(scheme.N, size=scheme.R, replace=False)
    mask = np.zeros(scheme.N, dtype=bool)
    mask[live] = True
    be = PoolBackend(pool, timeout=120)
    C = coded_matmul(A, B, scheme, backend=be, mask=jnp.asarray(mask))
    C_local = coded_matmul(A, B, scheme, backend="local")
    np.testing.assert_array_equal(np.asarray(C), np.asarray(C_local))
    assert be.last_stats.dispatched == tuple(sorted(int(i) for i in live))


def test_pool_secure_scheme_keyed_bit_identical(pool):
    spec = ProblemSpec(t=8, r=8, s=8, n=1, ring=Z32, N=8, privacy_t=1)
    scheme = plan(spec).instantiate()
    rng = np.random.default_rng(2)
    A, B = _problem(scheme, rng)
    be = PoolBackend(pool, timeout=120)
    C = coded_matmul(A, B, scheme, backend=be, key=KEY)
    C_local = coded_matmul(A, B, scheme, backend="local", key=KEY)
    np.testing.assert_array_equal(np.asarray(C), np.asarray(C_local))


def test_scheduler_concurrent_requests_and_plan_cache(pool):
    spec = ProblemSpec(t=8, r=8, s=8, n=1, ring=Z32, N=6, straggler_budget=2)
    rng = np.random.default_rng(3)
    with PoolScheduler(pool.master, max_queue=16, max_inflight=3) as sched:
        futs, oracles = [], []
        for _ in range(6):
            A = Z32.random(rng, (8, 8))
            B = Z32.random(rng, (8, 8))
            oracles.append(np.asarray(Z32.matmul(A, B)))
            futs.append(sched.submit(A, B, spec=spec))
        for fut, want in zip(futs, oracles):
            np.testing.assert_array_equal(np.asarray(fut.result(120)), want)
        assert sched.stats.completed == 6
        assert sched.stats.plan_cache_misses == 1
        assert sched.stats.plan_cache_hits == 5


def test_scheduler_admission_control(pool, wait_until):
    spec = ProblemSpec(t=8, r=8, s=8, n=1, ring=Z32, N=4)
    scheme = plan(spec).instantiate()
    rng = np.random.default_rng(4)
    A, B = _problem(scheme, rng)
    oracle = np.asarray(Z32.matmul(A, B))
    # park the workers so the queue actually fills
    for wid in pool.master.live_workers():
        pool.master.task_delay_ms[wid] = 150.0
    try:
        with PoolScheduler(pool.master, max_queue=1, max_inflight=1) as sched:
            f1 = sched.submit(A, B, scheme=scheme)
            wait_until(f1.running, "the dispatcher picking f1 up")
            f2 = sched.submit(A, B, scheme=scheme)
            with pytest.raises(SchedulerSaturated):
                sched.submit(A, B, scheme=scheme)
                sched.submit(A, B, scheme=scheme)
            assert sched.stats.rejected >= 1
            np.testing.assert_array_equal(np.asarray(f1.result(120)), oracle)
            np.testing.assert_array_equal(np.asarray(f2.result(120)), oracle)
    finally:
        pool.master.task_delay_ms.clear()


def test_submit_arg_validation(pool):
    spec = ProblemSpec(t=8, r=8, s=8, n=1, ring=Z32, N=4)
    with PoolScheduler(pool.master) as sched:
        with pytest.raises(ValueError):
            sched.submit(None, None)
        with pytest.raises(ValueError):
            sched.submit(None, None, spec=spec, scheme=object())


# --------------------------------------------------------------------------
# failure injection: real SIGKILL, mid-request (dedicated pool — it shrinks)
# --------------------------------------------------------------------------


def test_sigkill_n_minus_r_workers_mid_request_still_decodes(wait_until):
    workers = 5
    spec = ProblemSpec(t=8, r=8, s=8, n=1, ring=Z32, N=workers,
                       straggler_budget=2)
    p = plan(spec, objective="threshold")
    rank = max(range(len(p.candidates)),
               key=lambda i: p.candidates[i].costs.R)
    scheme = p.instantiate(rank)
    kill = scheme.N - scheme.R
    assert kill >= 1, "need a scheme with R < N for the kill to matter"
    rng = np.random.default_rng(5)
    A, B = _problem(scheme, rng)
    oracle = np.asarray(coded_matmul(A, B, scheme, backend="local"))
    with LocalPool(workers=workers) as fresh:
        be = PoolBackend(fresh, timeout=120)
        # warm round: workers jit their ring matmul before the race
        np.testing.assert_array_equal(
            np.asarray(coded_matmul(A, B, scheme, backend=be)), oracle
        )
        for wid in fresh.master.live_workers():
            fresh.master.task_delay_ms[wid] = 400.0
        result = {}

        def _request():
            try:
                result["C"] = np.asarray(coded_matmul(A, B, scheme, backend=be))
            except Exception as e:  # pragma: no cover - surfaced in assert
                result["err"] = e

        sent = _tasks_sent(fresh.master)
        t = threading.Thread(target=_request)
        t.start()
        wait_until(lambda: _tasks_sent(fresh.master) >= sent + scheme.N,
                   "every share dispatched")  # and every worker parked
        assert len(fresh.kill(kill)) == kill
        t.join(timeout=120)
        assert not t.is_alive(), "request hung after SIGKILL"
        assert "err" not in result, f"request failed: {result.get('err')!r}"
        np.testing.assert_array_equal(result["C"], oracle)
        assert fresh.alive_count() == workers - kill
        # the membership log saw the deaths as real leave events
        assert len(fresh.master.live_workers()) == workers - kill


def test_worker_compute_error_is_retried_not_fatal(pool):
    """An ok=False worker reply is a worker failure, not a request failure:
    the share is retried once on a different worker and the request still
    decodes exactly (strictly-less-severe than SIGKILL must not be worse)."""
    spec = ProblemSpec(t=8, r=8, s=8, n=1, ring=Z32, N=6, straggler_budget=2)
    scheme = plan(spec).instantiate()
    rng = np.random.default_rng(8)
    A, B = _problem(scheme, rng)
    oracle = np.asarray(coded_matmul(A, B, scheme, backend="local"))
    bad = pool.master.live_workers()[0]
    pool.master.task_fail_wids.add(bad)
    try:
        C, stats = pool.master.execute(scheme, A, B, timeout=120)
        np.testing.assert_array_equal(np.asarray(C), oracle)
    finally:
        pool.master.task_fail_wids.clear()


def test_heartbeat_timeout_detects_stalled_worker():
    """A SIGSTOPped worker keeps its socket open — only the heartbeat
    timeout can unmask it.  The monitor must wake the blocked reader
    (socket shutdown, not close), record the leave, and re-dispatch the
    stalled worker's shares so the request completes."""
    import signal as _signal

    with LocalPool(workers=3, heartbeat_s=0.2, heartbeat_timeout=1.5) as fresh:
        spec = ProblemSpec(t=8, r=8, s=8, n=1, ring=Z32, N=3,
                           straggler_budget=0)
        # zero slack (R == N): the planner's default pick at N=3 is plain
        # replication with R=1, which completes off the two healthy
        # workers without ever needing the stall unmasked — this test is
        # only meaningful when the stalled worker's share is required
        p = plan(spec, objective="threshold")
        rank = max(range(len(p.candidates)),
                   key=lambda i: p.candidates[i].costs.R)
        scheme = p.instantiate(rank)
        assert scheme.R == scheme.N == 3
        rng = np.random.default_rng(9)
        A, B = _problem(scheme, rng)
        oracle = np.asarray(coded_matmul(A, B, scheme, backend="local"))
        # warm round so the stall is the only slow thing left
        fresh.execute(scheme, A, B, timeout=120)
        # blind round-robin again: health-aware dispatch skips a worker
        # that the warm round's compile or heartbeat jitter scored low, and
        # a victim holding no share is never waited on, so never unmasked
        fresh.master.health.reset_scores()
        victim = fresh.procs[0]
        os.kill(victim.pid, _signal.SIGSTOP)
        try:
            C, stats = fresh.execute(scheme, A, B, timeout=120)
            np.testing.assert_array_equal(np.asarray(C), oracle)
            # the stall was detected as a real leave event
            assert len(fresh.master.live_workers()) == 2
        finally:
            os.kill(victim.pid, _signal.SIGCONT)


def test_pool_trace_reflects_membership():
    with LocalPool(workers=2) as fresh:
        spec = ProblemSpec(t=8, r=8, s=8, n=1, ring=Z32, N=2)
        scheme = plan(spec).instantiate()
        rng = np.random.default_rng(6)
        A, B = _problem(scheme, rng)
        fresh.execute(scheme, A, B, timeout=120)
        tr = fresh.master.trace()
        assert tr.N == 2
        assert tr.mask().all()  # both responded
        fresh.kill(1)
        deadline = time.time() + 30
        while len(fresh.master.live_workers()) > 1:
            assert time.time() < deadline, "death never detected"
            time.sleep(0.05)
        tr = fresh.master.trace()
        assert np.isfinite(tr.leave_ms).sum() == 1
