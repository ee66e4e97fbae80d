"""The live telemetry plane: metrics registry, health scores, HTTP
admin endpoints, and the hedged re-dispatch they drive.

Fast sections exercise the in-process pieces (instruments, EWMA health
scoring, the Prometheus exporter/parser pair, the stdlib HTTP server,
the settings knobs).  The ``slow``-marked section runs a real worker
pool and proves the hedging plane's correctness properties: duplicate
replies are discarded idempotently, hedged secure decodes stay
bit-identical to the local keyed oracle, and a SIGKILLed-then-hedged
worker still satisfies the pool-smoke oracle.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import json
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from repro.obs import parse_prometheus, to_prometheus
from repro.obs.health import DISPATCH_THRESHOLD, HealthTracker
from repro.obs.metrics import Counter, Gauge, MetricsRegistry, Series

SRC = os.path.join(os.path.dirname(__file__), os.pardir, "src")


# --------------------------------------------------------------------------
# instruments
# --------------------------------------------------------------------------


def test_counter_accumulates_and_keeps_ints():
    c = Counter("requests")
    for _ in range(3):
        c.inc()
    c.inc(2)
    assert c.value == 5 and isinstance(c.value, int)
    c.inc(0.5)
    assert c.value == 5.5


def test_gauge_plain_and_labeled_snapshots():
    g = Gauge("mean_fill")
    g.set(3.5)
    assert g.snapshot_items() == {"mean_fill": 3.5}
    h = Gauge("worker_health", label="wid")
    h.set(1.0, key=0)
    h.set(0.25, key=3)
    assert h.snapshot_items() == {
        "worker_health_by_wid": {"0": 1.0, "3": 0.25}
    }
    h.clear_labels(keep=[3])
    assert h.snapshot_items() == {"worker_health_by_wid": {"3": 0.25}}
    with pytest.raises(ValueError):
        g.set(1.0, key=7)  # no label declared


def test_series_retention_capacity_quantile_and_clear():
    s = Series("rtt", retention_s=5.0)
    now = time.monotonic()
    s.add(1.0, t=now - 10.0)  # outside the window: pruned on next touch
    s.add(2.0, t=now)
    assert s.values() == [2.0]
    small = Series("rtt", retention_s=1e6, capacity=4)
    for v in range(6):
        small.add(float(v))
    assert len(small) == 4 and small.values() == [2.0, 3.0, 4.0, 5.0]
    assert small.quantile(0.0) == 2.0
    assert small.quantile(0.95) == 5.0
    small.clear()
    assert len(small) == 0 and small.quantile(0.5) is None


def test_registry_snapshot_prefixes_types_docs_and_extras():
    reg = MetricsRegistry("pool")
    reg.counter("requests", doc="requests accepted").inc(4)
    reg.gauge("oddness", doc="an unsuffixed gauge").set(4.2)
    reg.gauge("worker_health", label="wid").set(0.5, key=1)
    reg.histogram("wall_ms").observe(2.0)
    series = reg.series("share_ms")
    for v in range(10):
        series.add(float(v))
    assert reg.counter("requests") is reg.counter("requests")  # idempotent
    snap = reg.snapshot(extra={"derived": 7})
    assert snap["pool_requests"] == 4
    assert snap["pool_derived"] == 7
    assert snap["pool_worker_health_by_wid"] == {"1": 0.5}
    assert snap["pool_share_ms_window_count"] == 10
    assert snap["pool_share_ms_window_p50"] == 5.0
    assert snap._types["pool_requests"] == "counter"
    assert snap._types["pool_oddness"] == "gauge"
    assert "requests accepted" in snap._docs["pool_requests"]
    # the _types annotation overrides the exporter's suffix heuristic:
    # "oddness" has no gauge-ish suffix yet exports as a gauge
    text = to_prometheus(snap)
    assert "# TYPE repro_pool_oddness gauge" in text
    assert "# HELP repro_pool_requests requests accepted" in text
    parse_prometheus(text)  # and the whole exposition is strictly valid


# --------------------------------------------------------------------------
# exporter / parser (the satellite fixes: escaping, collisions, strictness)
# --------------------------------------------------------------------------


def test_prometheus_label_values_escape_and_roundtrip():
    weird = 'we"ird\\wid\nx'
    text = to_prometheus({"pool_worker_health_by_wid": {weird: 0.5}})
    fams = parse_prometheus(text)
    ((_, labels, value),) = fams["repro_pool_worker_health"]["samples"]
    assert labels["wid"] == weird and value == 0.5


def test_prometheus_collision_guard_keeps_first_key():
    # "wall.ms" and "wall_ms" both sanitize to repro_wall_ms; the first
    # (sorted) key wins and the exposition stays parseable
    text = to_prometheus({"wall.ms": 1, "wall_ms": 2})
    assert text.count("# TYPE repro_wall_ms ") == 1
    assert "collision" in text
    fams = parse_prometheus(text)
    assert [s[2] for s in fams["repro_wall_ms"]["samples"]] == [1.0]


def test_prometheus_histograms_are_cumulative():
    snap = {
        "pool_wall_ms_hist": {"<=1": 1, "<=5": 2, "inf": 3},
        "pool_wall_ms_sum": 12.5,
    }
    fams = parse_prometheus(to_prometheus(snap))
    fam = fams["repro_pool_wall_ms"]
    assert fam["type"] == "histogram"
    buckets = {
        labels["le"]: v for n, labels, v in fam["samples"]
        if n.endswith("_bucket")
    }
    assert buckets == {"1": 1.0, "5": 3.0, "+Inf": 6.0}
    by_name = {n: v for n, labels, v in fam["samples"] if not labels}
    assert by_name["repro_pool_wall_ms_sum"] == 12.5
    assert by_name["repro_pool_wall_ms_count"] == 6.0


@pytest.mark.parametrize("bad", [
    'dup 1\ndup 2\n',                                    # duplicate sample
    '# TYPE h histogram\nh_bucket{le="1"} 1\nh_count 1\n',  # no +Inf
    '# TYPE h histogram\nh_bucket{le="1"} 5\n'
    'h_bucket{le="+Inf"} 3\n',                           # not cumulative
    '# TYPE h histogram\nh_bucket{le="+Inf"} 3\nh_count 4\n',  # count drift
    'metric{l="unterminated} 1\n',                       # bad label block
    'metric nope\n',                                     # unparsable value
])
def test_parse_prometheus_rejects_malformed(bad):
    with pytest.raises(ValueError):
        parse_prometheus(bad)


# --------------------------------------------------------------------------
# health scoring + hedge deadline
# --------------------------------------------------------------------------


def test_health_scores_normalize_rtt_against_pool_median():
    ht = HealthTracker()
    for _ in range(5):
        ht.record_share(0, 10.0)
        ht.record_share(1, 100.0)
    s = ht.scores()
    assert s[0] == 1.0  # at/below the median: healthy
    assert s[1] == pytest.approx(55.0 / 100.0)
    assert s[1] > 0 and s[1] > DISPATCH_THRESHOLD


def test_health_heartbeat_jitter_lowers_score():
    ht = HealthTracker(alpha=0.5)
    t = 100.0
    for k in range(12):  # perfectly steady 0.5 s heartbeats
        ht.record_heartbeat(0, t=t + 0.5 * k)
    stutter = 100.0
    for k in range(12):  # alternating 0.1 / 0.9 s inter-arrivals
        stutter += 0.1 if k % 2 else 0.9
        ht.record_heartbeat(1, t=stutter)
    s = ht.scores()
    assert s[0] == 1.0
    assert s[1] < s[0]


def test_health_reset_scores_keeps_share_window():
    ht = HealthTracker()
    for _ in range(10):
        ht.record_share(0, 10.0)
    assert ht.scores()
    ht.reset_scores()
    assert ht.scores() == {}
    assert ht.score(0) == 1.0  # innocent until measured again
    assert len(ht.share_ms) == 10  # the pooled window survives


def test_hedge_deadline_gating_and_floor():
    ht = HealthTracker(min_hedge_samples=8)
    assert ht.hedge_deadline_ms(2.0) is None  # no evidence
    for _ in range(7):
        ht.record_share(0, 10.0)
    assert ht.hedge_deadline_ms(2.0) is None  # under min samples
    ht.record_share(0, 10.0)
    time.sleep(0.06)  # past the deadline quantile's staleness TTL
    assert ht.hedge_deadline_ms(0.0) is None  # hedging off
    assert ht.hedge_deadline_ms(2.0) == pytest.approx(20.0)
    ht.clear_window()
    assert ht.hedge_deadline_ms(2.0) is None  # window (and cache) gone
    for _ in range(8):
        ht.record_share(0, 1e-4)
    time.sleep(0.06)
    assert ht.hedge_deadline_ms(2.0) == 1.0  # min_ms floor


# --------------------------------------------------------------------------
# settings knobs + HTTP plane
# --------------------------------------------------------------------------


def test_settings_cli_lists_telemetry_knobs():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-m", "repro.settings"],
        capture_output=True, text=True, env=env, check=True, timeout=120,
    ).stdout
    for knob in ("REPRO_OBS_HTTP_PORT", "REPRO_HEDGE_FACTOR",
                 "REPRO_HEALTH_EWMA", "REPRO_OBS_RETENTION"):
        assert knob in out, f"{knob} missing from settings listing"


def _get(url: str):
    with urllib.request.urlopen(url, timeout=10) as resp:
        return resp.read().decode()


def test_http_endpoints_serve_registered_sources():
    from repro import obs
    from repro.obs import http as obs_http

    reg = MetricsRegistry("unit")
    reg.counter("requests").inc(3)
    reg.gauge("workers_live").set(2)
    name = obs_http.register_source("unit", reg.snapshot)
    dup = obs_http.register_source("unit", reg.snapshot)
    assert dup == "unit#2"  # second registrant deduplicates, both scrape
    obs_http.unregister_source(dup)

    obs.set_enabled(True)
    ctx = obs.TraceContext.new("unit")
    t0 = obs.now()
    obs.tracer().add(ctx, "compute", "worker", t0, obs.now(), wid=0)
    timeline = obs.tracer().timeline(ctx.trace_id)

    def resolver(key):
        return timeline if key == "42" else None

    obs_http.register_trace_resolver(resolver)
    srv = obs_http.start_server(port=0)
    try:
        assert obs_http.start_server(port=0) is srv  # process singleton
        fams = parse_prometheus(_get(f"{srv.url}/metrics"))
        assert "repro_unit_requests" in fams
        healthz = json.loads(_get(f"{srv.url}/healthz"))
        assert healthz["ok"] and name in healthz["sources"]
        stats = json.loads(_get(f"{srv.url}/stats"))
        assert stats["unit_requests"] == 3
        doc = json.loads(_get(f"{srv.url}/trace/42"))
        assert doc["spans"] and doc["spans"][0]["name"] == "compute"
        chrome = json.loads(_get(f"{srv.url}/trace/42?format=chrome"))
        assert any(e.get("ph") == "X" for e in chrome["traceEvents"])
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(f"{srv.url}/trace/no-such-request")
        assert ei.value.code == 404
    finally:
        obs.set_enabled(None)
        obs_http.stop_server()
        obs_http.unregister_source(name)
        obs_http.unregister_trace_resolver(resolver)


def test_top_renders_rates_and_worker_table():
    from repro.obs import top

    snap0 = {
        "pool_requests": 100, "pool_workers_live": 2, "pool_hedged": 1,
        "pool_worker_health_by_wid": {"0": 1.0, "1": 0.25},
        "pool_worker_tasks_done_by_wid": {"0": 9, "1": 3},
    }
    first = top.render(snap0, prev=None, now=1000.0)
    assert "req/s -" in first  # no rate on the first frame
    snap1 = dict(snap0, pool_requests=150)
    frame = top.render(snap1, prev=(1000.0, snap0), now=1010.0)
    assert "req/s 5.0" in frame
    assert "hedged=1" in frame
    lines = [ln for ln in frame.splitlines() if ln.strip().startswith(("0", "1"))]
    assert len(lines) == 2 and "#" in lines[0]


# --------------------------------------------------------------------------
# hedging correctness against a real pool (slow: worker OS processes)
# --------------------------------------------------------------------------

POOL_WORKERS = 4
SIZE = 32


def _zero_slack(workers: int, size: int = SIZE):
    from repro.cdmm import ProblemSpec, coded_matmul, plan
    from repro.core import make_ring

    Z32 = make_ring(2, 32, ())
    spec = ProblemSpec(
        t=size, r=size, s=size, n=1, ring=Z32, N=workers,
        straggler_budget=0,
    )
    p = plan(spec, objective="threshold")
    rank = max(range(len(p.candidates)),
               key=lambda i: p.candidates[i].costs.R)
    scheme = p.instantiate(rank)
    assert scheme.R == scheme.N == workers
    rng = np.random.default_rng(0)
    A = Z32.random(rng, (size, size))
    B = Z32.random(rng, (size, size))
    oracle = np.asarray(coded_matmul(A, B, scheme, backend="local"))
    return scheme, A, B, oracle


def _warm_and_seed(master, scheme, A, B):
    """Jit-warm the workers, then purge the compile-era round-trips and
    re-seed the hedge window with steady-state samples (>= 8 needed)."""
    master.hedge_factor = 0.0
    for _ in range(3):
        master.execute(scheme, A, B, timeout=120)
    master.health.clear_window()
    for _ in range(2):
        master.execute(scheme, A, B, timeout=120)


@pytest.fixture(scope="module")
def hedge_pool():
    from repro.dist import LocalPool

    with LocalPool(workers=POOL_WORKERS) as p:
        scheme, A, B, oracle = _zero_slack(POOL_WORKERS)
        _warm_and_seed(p.master, scheme, A, B)
        yield p, scheme, A, B, oracle


@pytest.mark.slow
def test_aggressive_hedging_discards_duplicates_idempotently(hedge_pool):
    """Every worker parked + an aggressive factor: every share hedges,
    and both replies (original + replica) eventually arrive for every
    share.  Each decode must stay bit-identical and the master must come
    out clean — the duplicate-discard paths ran dozens of times."""
    pool, scheme, A, B, oracle = hedge_pool
    master = pool.master
    before = master.stats()
    try:
        for _ in range(3):
            # each race poisons the share window with parked round-trips
            # (they dwarf the park of the NEXT race), so re-seed per race
            _warm_and_seed(master, scheme, A, B)
            for wid in master.live_workers():
                master.task_delay_ms[wid] = 150.0
            master.hedge_factor = 1.05
            C, st = master.execute(scheme, A, B, timeout=120)
            master.hedge_factor = 0.0
            master.task_delay_ms.clear()
            np.testing.assert_array_equal(np.asarray(C), oracle)
            assert st.hedged >= 1
    finally:
        master.hedge_factor = 0.0
        master.task_delay_ms.clear()
    time.sleep(0.8)  # let every late twin land and be discarded
    after = master.stats()
    assert after["pool_hedged"] >= before["pool_hedged"] + 3
    assert after["pool_hedge_wasted"] >= 0
    # the pool is not poisoned: a clean request still decodes exactly
    C, st = master.execute(scheme, A, B, timeout=120)
    np.testing.assert_array_equal(np.asarray(C), oracle)
    assert st.hedged == 0
    master.health.clear_window()
    _warm_and_seed(master, scheme, A, B)  # re-seed for the next test


@pytest.mark.slow
def test_hedged_secure_decode_bit_identical_under_fixed_key(hedge_pool):
    """Secure scheme, fixed key, every worker parked so shares hedge:
    the replica re-ships the SAME keyed encoding, so the decode must
    equal the local keyed oracle bit for bit despite duplicate replies
    taking different worker paths."""
    import jax

    from repro.cdmm import ProblemSpec, coded_matmul, plan
    from repro.core import make_ring
    from repro.dist import PoolBackend

    pool = hedge_pool[0]
    master = pool.master
    Z32 = make_ring(2, 32, ())
    spec = ProblemSpec(t=8, r=8, s=8, n=1, ring=Z32, N=8, privacy_t=1)
    scheme = plan(spec).instantiate()
    rng = np.random.default_rng(2)
    A = Z32.random(rng, (8, 8))
    B = Z32.random(rng, (8, 8))
    key = jax.random.PRNGKey(7)
    be = PoolBackend(pool, timeout=120)
    C_local = np.asarray(coded_matmul(A, B, scheme, backend="local", key=key))
    # unhedged pool run (also jit-warms the 8x8 keyed path), then the
    # hedged run with every worker parked past the deadline
    C_plain = np.asarray(coded_matmul(A, B, scheme, backend=be, key=key))
    np.testing.assert_array_equal(C_plain, C_local)
    # that first pool run compiled the keyed path worker-side; purge its
    # round-trips and re-seed so the hedge deadline arms at steady state
    master.health.clear_window()
    for _ in range(2):
        np.testing.assert_array_equal(
            np.asarray(coded_matmul(A, B, scheme, backend=be, key=key)),
            C_local,
        )
    for wid in master.live_workers():
        master.task_delay_ms[wid] = 150.0
    try:
        master.hedge_factor = 1.05
        C_hedged = np.asarray(
            coded_matmul(A, B, scheme, backend=be, key=key)
        )
    finally:
        master.hedge_factor = 0.0
        master.task_delay_ms.clear()
    np.testing.assert_array_equal(C_hedged, C_local)
    assert be.last_stats.hedged >= 1


@pytest.mark.slow
def test_sigkilled_then_hedged_worker_still_satisfies_oracle(wait_until):
    """A worker is SIGKILLed after its share was already speculatively
    hedged: the replica (or the death re-dispatch, whichever lands
    first) must complete the zero-slack decode bit-identically."""
    from repro.dist import LocalPool

    with LocalPool(workers=POOL_WORKERS, heartbeat_s=0.5,
                   heartbeat_timeout=30.0) as fresh:
        scheme, A, B, oracle = _zero_slack(POOL_WORKERS)
        master = fresh.master
        _warm_and_seed(master, scheme, A, B)
        for wid in master.live_workers():
            master.task_delay_ms[wid] = 400.0
        result = {}

        def _request():
            try:
                C, result["stats"] = master.execute(scheme, A, B, timeout=120)
                result["C"] = np.asarray(C)
            except Exception as e:  # surfaced below
                result["err"] = e

        master.hedge_factor = 2.0
        hedged0 = master.stats()["pool_hedged"]
        t = threading.Thread(target=_request)
        t.start()
        wait_until(lambda: master.stats()["pool_hedged"] > hedged0,
                   "an overdue share hedged")
        assert len(fresh.kill(1)) == 1
        t.join(timeout=120)
        master.hedge_factor = 0.0
        master.task_delay_ms.clear()
        assert not t.is_alive(), "request hung after SIGKILL"
        assert "err" not in result, f"request failed: {result.get('err')!r}"
        np.testing.assert_array_equal(result["C"], oracle)
        assert result["stats"].hedged >= 1
        # the hedge plane resolved the race long before the 30 s
        # heartbeat deadline could have
        assert result["stats"].wall_ms < 20_000
        assert fresh.alive_count() == POOL_WORKERS - 1
