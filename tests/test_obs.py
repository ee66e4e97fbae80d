"""repro.obs: per-request tracing, exporters, and the unified stats schema.

Fast unit coverage of the span model (Span/TraceContext/Tracer/Timeline),
the exporters (Chrome trace_event, Prometheus text, schema validation),
the repro.settings registry, and the StatsSnapshot legacy-key aliases —
then real-pool integration: a SIGKILLed worker mid-request must leave its
footprint (a send span to the dead worker AND a re-dispatched send span)
on the same merged timeline as the surviving responders' compute spans,
a v0 peer (no "tracing" capability) must still yield a synthesized
compute span without ever seeing a trace header, and results must be
bit-identical with tracing on vs. off.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import json
import threading
import time
import warnings

import numpy as np
import pytest

from repro import obs, settings
from repro.obs.trace import Span, Timeline, TraceContext, Tracer
from repro.stats import StatsSnapshot, merge_snapshots, namespaced

Z32 = None  # built lazily in the pool section (keeps unit tests jax-free)


@pytest.fixture(autouse=True)
def _reset_tracing():
    """Every test starts with tracing off and a clean ring buffer."""
    obs.set_enabled(None)
    obs.tracer().clear()
    yield
    obs.set_enabled(None)
    obs.tracer().clear()


# --------------------------------------------------------------------------
# span model
# --------------------------------------------------------------------------


def test_span_and_timeline_json_roundtrip():
    s = Span("t-1", "compute", "worker", 10.0, 10.5, {"wid": 3, "ok": True})
    assert s.duration_s == pytest.approx(0.5)
    assert Span.from_json(json.loads(json.dumps(s.to_json()))) == s
    tl = Timeline("t-1", [s])
    doc = json.loads(json.dumps(tl.to_json()))
    back = Timeline.from_json(doc)
    assert back.trace_id == "t-1" and back.spans == [s]
    assert tl.wall_s == pytest.approx(0.5)
    assert tl.by_component("worker") == [s]
    assert tl.by_component("pool") == []


def test_trace_ids_are_process_unique():
    ids = {obs.new_trace_id("x") for _ in range(100)}
    assert len(ids) == 100
    assert all(i.startswith("x-") for i in ids)


def test_now_is_monotone_and_epoch_aligned():
    a = obs.now()
    b = obs.now()
    assert b >= a
    assert abs(a - time.time()) < 1.0  # anchored to the epoch


def test_tracer_ring_buffer_bounded_and_filtered():
    tr = Tracer(capacity=4)
    ctx_a = TraceContext.new("a")
    ctx_b = TraceContext.new("b")
    for i in range(6):
        ctx = ctx_a if i % 2 == 0 else ctx_b
        tr.add(ctx, f"s{i}", "pool", float(i), float(i) + 0.1)
    assert len(tr) == 4  # oldest two evicted
    got = tr.spans(ctx_a.trace_id)
    assert all(s.trace_id == ctx_a.trace_id for s in got)
    merged = tr.timeline(ctx_a.trace_id, ctx_b.trace_id)
    assert len(merged.spans) == 4
    starts = [s.t_start for s in merged.spans]
    assert starts == sorted(starts)


def test_tracer_add_none_ctx_is_noop():
    tr = Tracer(capacity=8)
    assert tr.add(None, "x", "pool", 0.0, 1.0) is None
    assert len(tr) == 0


def test_span_contextmanager_nesting_sets_parent_tag():
    tr = Tracer(capacity=8)
    ctx = TraceContext.new("t")
    with tr.span(ctx, "outer", "pool"):
        with tr.span(ctx, "inner", "pool") as tags:
            tags["extra"] = 7
    spans = {s.name: s for s in tr.spans(ctx.trace_id)}
    assert spans["inner"].tags["parent"] == "outer"
    assert spans["inner"].tags["extra"] == 7
    assert "parent" not in spans["outer"].tags
    assert spans["outer"].t_start <= spans["inner"].t_start
    assert spans["inner"].t_end <= spans["outer"].t_end
    assert ctx.stack == []  # fully unwound


def test_enablement_gates_context_creation():
    obs.set_enabled(False)
    assert obs.maybe_context("t") is None
    obs.set_enabled(True)
    ctx = obs.maybe_context("t", request_id=5)
    assert ctx is not None and ctx.request_id == 5
    obs.set_enabled(None)  # fall back to the (unset) env setting
    assert obs.maybe_context("t") is None


def test_wire_roundtrip_restamps_trace_id_and_tags():
    spans = [Span("ignored", "compute", "worker", 1.0, 2.0, {"pid": 42})]
    wire = obs.spans_to_wire(spans)
    assert "trace_id" not in wire[0]
    back = obs.spans_from_wire(wire, "t-9", wid=3, share=1)
    assert back[0].trace_id == "t-9"
    assert back[0].tags == {"pid": 42, "wid": 3, "share": 1}
    assert back[0].t_start == 1.0 and back[0].t_end == 2.0


# --------------------------------------------------------------------------
# exporters
# --------------------------------------------------------------------------


def _sample_timeline():
    return Timeline("t-1", [
        Span("t-1", "encode", "pool", 0.0, 0.1, {"share": 0}),
        Span("t-1", "send", "pool", 0.1, 0.2, {"wid": 0, "share": 0}),
        Span("t-1", "compute", "worker", 0.2, 0.6, {"wid": 0}),
        Span("t-1", "compute", "worker", 0.25, 0.7, {"wid": 1}),
        Span("t-1", "decode", "pool", 0.7, 0.8, {}),
    ])


def test_chrome_trace_export_structure():
    doc = json.loads(obs.to_chrome_trace(_sample_timeline()))
    events = doc["traceEvents"]
    xs = [e for e in events if e["ph"] == "X"]
    assert len(xs) == 5
    for e in xs:
        assert e["ts"] >= 0 and e["dur"] > 0  # relative microseconds
    # worker spans land in per-worker lanes; metadata names them
    names = [e for e in events if e["ph"] == "M"]
    assert any(e["name"] == "process_name" for e in names)
    worker_tids = {e["tid"] for e in xs if e["name"] == "compute"}
    assert len(worker_tids) == 2


def test_prometheus_export_counters_hist_gauges():
    snap = namespaced("pool", {
        "requests": 3,
        "wall_ms_hist": {"<=1": 1, "<=5": 2, "inf": 3},
        "wall_ms_p50": 2.0,
        "wall_ms_p99": 5.0,
        "transport": "pack",  # non-numeric: skipped
    })
    text = obs.to_prometheus(snap)
    assert "# HELP repro_pool_requests" in text
    assert "# TYPE repro_pool_requests counter" in text
    assert "repro_pool_requests 3" in text
    assert 'le="1"' in text and 'le="+Inf"' in text
    # the histogram family keeps the snapshot's base name (no doubled
    # unit suffix) and carries the full cumulative triple
    assert "# TYPE repro_pool_wall_ms histogram" in text
    assert "repro_pool_wall_ms_bucket" in text
    assert "repro_pool_wall_ms_count 6" in text
    assert "repro_pool_wall_ms_p50 2.0" in text
    assert "transport" not in text
    # the exposition parses strictly (the CI scrape oracle)
    obs.parse_prometheus(text)


def test_validate_timeline_accepts_good_rejects_bad():
    good = _sample_timeline().to_json()
    assert obs.validate_timeline(
        good, min_workers=2, require_components=("pool", "worker")
    ) == []
    assert obs.validate_timeline({"trace_id": "t", "spans": []})
    backwards = {"trace_id": "t", "spans": [
        {"trace_id": "t", "name": "x", "component": "pool",
         "t_start": 2.0, "t_end": 1.0, "tags": {}},
    ]}
    assert any(
        "ends before" in p for p in obs.validate_timeline(backwards)
    )
    assert any(
        "worker" in p
        for p in obs.validate_timeline(good, min_workers=3)
    )
    assert any(
        "serve" in p
        for p in obs.validate_timeline(good, require_components=("serve",))
    )


# --------------------------------------------------------------------------
# repro.settings
# --------------------------------------------------------------------------


def test_settings_defaults_and_parsing():
    assert settings.get("trace", env={}) is False
    assert settings.get_bool("trace", env={"REPRO_TRACE": "yes"}) is True
    assert settings.get_bool("trace", env={"REPRO_TRACE": "0"}) is False
    assert settings.get_int("trace_buffer", env={}) == 8192
    assert settings.get_int(
        "trace_buffer", env={"REPRO_TRACE_BUFFER": "16"}
    ) == 16
    assert settings.get("calibration", env={}) is None


def test_settings_legacy_fallback_warns_once():
    settings._WARNED.discard("REPRO_POOL_WORKERS")
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        assert settings.get_int(
            "dist_workers", env={"REPRO_POOL_WORKERS": "5"}
        ) == 5
        assert settings.get_int(
            "dist_workers", env={"REPRO_POOL_WORKERS": "5"}
        ) == 5
    deps = [w for w in rec if issubclass(w.category, DeprecationWarning)]
    assert len(deps) == 1
    assert "REPRO_POOL_WORKERS" in str(deps[0].message)
    # the new variable wins when both are set
    assert settings.get_int("dist_workers", env={
        "REPRO_POOL_WORKERS": "5", "REPRO_DIST_WORKERS": "7",
    }) == 7


def test_settings_describe_lists_every_knob():
    text = settings.describe()
    for s in settings.SETTINGS.values():
        assert s.env in text
    assert "REPRO_POOL_WORKERS" in text  # legacy shims are documented too


# --------------------------------------------------------------------------
# unified stats schema
# --------------------------------------------------------------------------


def test_namespaced_prefixes_and_aliases():
    snap = namespaced("serve", {"submitted": 3, "wait_ms_p50": 1.0})
    assert snap["serve_submitted"] == 3
    settings._WARNED.discard("stats:submitted")
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        assert snap["submitted"] == 3  # legacy key resolves
        assert snap["submitted"] == 3
    deps = [w for w in rec if issubclass(w.category, DeprecationWarning)]
    assert len(deps) == 1
    assert "submitted" in snap and "serve_submitted" in snap
    assert snap.get("nope", 9) == 9
    with pytest.raises(KeyError):
        snap["serve_nope"]


def test_namespaced_is_idempotent():
    once = namespaced("pool", {"requests": 1})
    twice = namespaced("pool", once)
    assert dict(twice) == {"pool_requests": 1}


def test_merge_snapshots_preserves_aliases():
    merged = merge_snapshots(
        namespaced("serve", {"submitted": 2}),
        namespaced("pool", {"requests": 1}),
    )
    assert isinstance(merged, StatsSnapshot)
    assert merged["serve_submitted"] == 2 and merged["pool_requests"] == 1
    assert merged["requests"] == 1  # legacy alias survives the merge


# --------------------------------------------------------------------------
# calibration rows from measured spans
# --------------------------------------------------------------------------


def test_rows_from_timeline_feeds_fit_rows():
    from repro.cdmm.calibrate import fit_rows, rows_from_timeline
    from repro.core.ep_codes import EPCosts

    costs = EPCosts(N=4, R=3, m_eff=1.0, upload=100.0, download=50.0,
                    encode_ops=1000.0, decode_ops=500.0, worker_ops=2000.0)
    tl = Timeline("t-1", [
        Span("t-1", "encode", "pool", 0.0, 0.010, {}),
        Span("t-1", "encode", "pool", 0.010, 0.030, {}),
        Span("t-1", "send", "pool", 0.030, 0.040, {"wid": 0}),
        Span("t-1", "compute", "worker", 0.04, 0.24, {"wid": 0}),
        Span("t-1", "compute", "worker", 0.05, 0.29, {"wid": 1}),
        Span("t-1", "decode", "pool", 0.30, 0.35, {}),
        Span("t-1", "wait_R", "pool", 0.04, 0.30, {}),  # not a fit stage
    ])
    rows = rows_from_timeline(tl, costs, backend="pool")
    by_name = {}
    for r in rows:
        by_name.setdefault(r["name"], []).append(r)
    # serial stages pool into one row; each worker compute is its own
    assert len(by_name["trace_pool_encode"]) == 1
    assert by_name["trace_pool_encode"][0]["us"] == pytest.approx(3e4)
    assert len(by_name["trace_pool_worker"]) == 2
    assert by_name["trace_pool_decode"][0]["derived"]["decode_ops"] == 500.0
    assert "trace_pool_wait_R" not in by_name
    cal = fit_rows(rows)
    assert "pool" in cal.backends
    # the fitted compute slope reproduces the mean observed span
    coef = cal.backends["pool"].coef["compute"]
    assert coef * costs.worker_ops == pytest.approx(225_000, rel=0.15)


# --------------------------------------------------------------------------
# real worker processes (tracing through the pool and the serve engine)
# --------------------------------------------------------------------------

pool_tests = pytest.mark.slow


@pytest.fixture(scope="module")
def pool():
    from repro.dist import LocalPool

    with LocalPool(workers=4) as p:
        yield p


def _scheme_and_problem(N=4, budget=1, size=8, seed=0):
    from repro.cdmm import ProblemSpec, plan
    from repro.core import make_ring

    ring = make_ring(2, 32, ())
    spec = ProblemSpec(t=size, r=size, s=size, n=1, ring=ring, N=N,
                       straggler_budget=budget)
    scheme = plan(spec).instantiate()
    rng = np.random.default_rng(seed)
    A = ring.random(rng, (size, size))
    B = ring.random(rng, (size, size))
    return ring, scheme, A, B


@pool_tests
def test_pool_trace_covers_every_stage_and_responder(pool):
    ring, scheme, A, B = _scheme_and_problem()
    obs.set_enabled(True)
    ctx = TraceContext.new("test")
    C, stats = pool.master.execute(scheme, A, B, trace=ctx, timeout=120)
    np.testing.assert_array_equal(
        np.asarray(C), np.asarray(ring.matmul(A, B))
    )
    tl = obs.tracer().timeline(ctx.trace_id)
    assert obs.validate_timeline(
        tl.to_json(), min_workers=scheme.R,
        require_components=("pool", "worker"),
    ) == []
    names = {s.name for s in tl.spans}
    assert {"encode", "send", "compute", "wait_R", "decode"} <= names
    computes = [s for s in tl.spans if s.name == "compute"]
    assert len({s.tags["wid"] for s in computes}) >= scheme.R
    # none synthesized: every live worker advertised the tracing capability
    assert not any(s.tags.get("synthesized") for s in computes)
    # serial master-side stage time fits inside the request wall clock
    serial = sum(
        s.duration_s for s in tl.spans
        if s.component == "pool" and s.name in ("encode", "send", "decode")
    )
    assert serial <= tl.wall_s + 1e-9


@pool_tests
def test_pool_trace_bit_identical_on_vs_off(pool):
    ring, scheme, A, B = _scheme_and_problem(seed=3)
    obs.set_enabled(False)
    C_off, _ = pool.master.execute(scheme, A, B, timeout=120)
    obs.set_enabled(True)
    C_on, _ = pool.master.execute(
        scheme, A, B, trace=TraceContext.new("test"), timeout=120
    )
    np.testing.assert_array_equal(np.asarray(C_off), np.asarray(C_on))
    assert len(obs.tracer()) > 0  # tracing actually recorded


@pool_tests
def test_pool_trace_v0_peer_interop_synthesizes_spans(pool):
    # strip the "tracing" capability from every handle: the master must
    # never stamp a trace header (a v0 worker would reject unknown
    # semantics) and must synthesize compute spans from wall_us instead
    master = pool.master
    removed = {}
    for wid, h in master._workers.items():
        if "tracing" in h.caps:
            removed[wid] = h.caps.pop("tracing")
    try:
        ring, scheme, A, B = _scheme_and_problem(seed=5)
        obs.set_enabled(True)
        ctx = TraceContext.new("test")
        C, _ = master.execute(scheme, A, B, trace=ctx, timeout=120)
        np.testing.assert_array_equal(
            np.asarray(C), np.asarray(ring.matmul(A, B))
        )
        tl = obs.tracer().timeline(ctx.trace_id)
        computes = [s for s in tl.spans if s.name == "compute"]
        assert len(computes) >= scheme.R
        assert all(s.tags.get("synthesized") for s in computes)
        assert all(s.t_end >= s.t_start for s in computes)
    finally:
        for wid, v in removed.items():
            if wid in master._workers:
                master._workers[wid].caps["tracing"] = v


@pool_tests
def test_pool_trace_sigkill_leaves_dead_worker_footprint(pool, wait_until):
    # a kill-resilient scheme on a dedicated pool: SIGKILL one worker
    # mid-request; the merged timeline must show the send to the dead
    # worker AND the re-dispatched replacement share AND >= R compute
    # spans from the survivors — the full story of the any-R race
    from repro.dist import LocalPool

    ring, scheme, A, B = _scheme_and_problem(N=4, budget=1, size=16)
    oracle = np.asarray(ring.matmul(A, B))
    with LocalPool(workers=scheme.N) as victim_pool:
        master = victim_pool.master
        warm, _ = master.execute(scheme, A, B, timeout=120)  # jit before the race
        np.testing.assert_array_equal(np.asarray(warm), oracle)
        # blind round-robin, so the worker killed below holds a share
        # however the warm round scored it
        master.health.reset_scores()
        for wid in master.live_workers():
            master.task_delay_ms[wid] = 300.0
        obs.set_enabled(True)
        ctx = TraceContext.new("test")
        result = {}

        def _request():
            result["C"], result["stats"] = master.execute(
                scheme, A, B, trace=ctx, timeout=120
            )

        def sent():
            return sum(r["tasks_sent"]
                       for r in master.worker_reports().values())

        sent0 = sent()
        t = threading.Thread(target=_request)
        t.start()
        # every share dispatched and every worker parked, so the kill
        # strands a share that has to be re-dispatched
        wait_until(lambda: sent() >= sent0 + scheme.N, "every share dispatched")
        killed = victim_pool.kill(1)
        assert killed
        t.join(timeout=120)
        assert not t.is_alive()
    np.testing.assert_array_equal(np.asarray(result["C"]), oracle)
    assert result["stats"].redispatched >= 1
    tl = obs.tracer().timeline(ctx.trace_id)
    assert obs.validate_timeline(
        tl.to_json(), min_workers=scheme.R,
        require_components=("pool", "worker"),
    ) == []
    sends = [s for s in tl.spans if s.name == "send"]
    assert any(s.tags.get("redispatch") for s in sends)
    # every share's original dispatch is on the timeline, so the dead
    # worker's send span is the evidence of the share it never finished
    assert len(sends) >= scheme.N
    computes = [s for s in tl.spans if s.name == "compute"]
    assert len({s.tags["wid"] for s in computes}) >= scheme.R


@pool_tests
def test_serve_trace_merges_request_and_carrier(pool):
    from repro.cdmm import ProblemSpec
    from repro.core import make_ring
    from repro.serve import CoalescePolicy, ServeScheduler

    ring = make_ring(2, 32, ())
    spec = ProblemSpec(t=16, r=16, s=16, n=1, ring=ring, N=4,
                       straggler_budget=1)
    rng = np.random.default_rng(0)
    pairs = [
        (ring.random(rng, (16, 16)), ring.random(rng, (16, 16)))
        for _ in range(4)
    ]
    obs.set_enabled(True)
    with ServeScheduler(
        pool.master, CoalescePolicy(target_batch_n=4, max_wait_ms=100.0),
        max_queue=8, seed=0,
    ) as sched:
        futs = [sched.submit(A, B, spec=spec) for A, B in pairs]
        for fut, (A, B) in zip(futs, pairs):
            np.testing.assert_array_equal(
                np.asarray(fut.result(120)),
                np.asarray(ring.matmul(A, B)),
            )
        for fut in futs:
            tl = sched.trace(fut)
            comps = {s.component for s in tl.spans}
            # every request's merged timeline reaches through the carrier
            # to the pool and worker spans of its batch
            assert {"serve", "pool", "worker"} <= comps
            assert any(s.name == "coalesce_wait" for s in tl.spans)
            assert any(s.name == "decode" for s in tl.spans)
        with pytest.raises(KeyError):
            sched.trace(10**9)
    obs.set_enabled(False)
    with pytest.raises(ValueError):
        sched.trace(futs[0])


@pool_tests
def test_scheduler_trace_by_future(pool):
    from repro.dist import PoolScheduler

    ring, scheme, A, B = _scheme_and_problem(seed=7)
    obs.set_enabled(True)
    sched = PoolScheduler(pool.master, max_inflight=2)
    try:
        fut = sched.submit(A, B, scheme=scheme)
        np.testing.assert_array_equal(
            np.asarray(fut.result(120)),
            np.asarray(ring.matmul(A, B)),
        )
        tl = sched.trace(fut)
        names = {s.name for s in tl.spans}
        assert "queue_wait" in names and "decode" in names
    finally:
        sched.close()
