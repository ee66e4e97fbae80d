"""Serving throughput: coalesced continuous batching vs per-request dispatch.

The acceptance measurement of ``repro.serve``: the same stream of
concurrent same-shape requests over the same worker pool, served two ways —

  serving_unbatched  PoolScheduler, one single-CDMM job per request
  serving_batched    ServeScheduler, amortized-planned RMFE batch coalescing

Rows carry requests/s, per-request latency p50/p99 (submit-to-result,
futures timed individually) and the engine's mean batch fill.  The row's
``us`` is wall-clock per request across the whole stream — the regression
gate therefore tracks serving throughput history directly.  A third row,
``serving_traced``, re-runs the batched mode with ``repro.obs`` span
tracing enabled and reports its overhead against the untraced row (the
acceptance bound is <5%).

Two hedging rows measure the speculative re-dispatch plane:

  serving_hedged     the batched stream with ``hedge_factor=2`` and NO
                     stragglers — its ``overhead_pct`` against the
                     unhedged batched row is the <5% acceptance bound
                     (a healthy pool must never trip the hedge path);
  serving_straggler  a zero-slack scheme (R == N, every share needed) with
                     one worker's compute parked: time-to-R with hedging
                     off vs on, same request, scores reset so round-robin
                     re-offers the straggler a share each race.  The row's
                     ``us`` is the hedged time; ``unhedged_ms`` and
                     ``speedup`` carry the margin, and both decodes are
                     asserted bit-identical to the local sync backend.

Warmup matters more here than in the jit benches: the first stream of each
mode compiles the encode, worker and decode programs of every scheme.  Each mode runs ``WARM_STREAMS``
full streams to reach the steady state the row claims to measure, then
takes the median of ``iters`` measured streams.
"""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from .common import emit

WARM_STREAMS = 2  # first stream compiles decode subsets; second settles


def _stream(submit, pairs) -> Dict:
    """Submit every pair at once, record submit->result latency per
    request and the stream's total wall-clock."""
    t0 = time.perf_counter()
    futs = [submit(A, B) for A, B in pairs]
    done_at: List[float] = []
    for f in futs:
        f.result(timeout=600)
        done_at.append(time.perf_counter() - t0)
    # result() is collected in submit order, so each request's true
    # completion is bounded by when its future resolved; with every future
    # resolved well before the loop reaches it, done_at converges to the
    # resolution times (the loop only blocks on stragglers)
    wall = time.perf_counter() - t0
    return {"wall_s": wall, "lat_s": done_at}


def run(full: bool = False) -> None:
    from repro.cdmm import ProblemSpec
    from repro.core import make_ring
    from repro.dist import LocalPool, PoolScheduler
    from repro.serve import CoalescePolicy, ServeScheduler

    workers = 6
    requests = 32 if full else 16
    size = 128 if full else 64
    iters = 3
    Z32 = make_ring(2, 32, ())
    spec = ProblemSpec(
        t=size, r=size, s=size, n=1, ring=Z32, N=workers,
        straggler_budget=1,
    )
    rng = np.random.default_rng(0)
    pairs = [
        (Z32.random(rng, (size, size)), Z32.random(rng, (size, size)))
        for _ in range(requests)
    ]

    with LocalPool(workers=workers) as pool:
        # -- unbatched baseline: PoolScheduler, one job per request -------
        with PoolScheduler(
            pool.master, max_queue=requests, max_inflight=4,
        ) as sched:
            for _ in range(WARM_STREAMS):
                _stream(lambda A, B: sched.submit(A, B, spec=spec), pairs)
            runs = [
                _stream(lambda A, B: sched.submit(A, B, spec=spec), pairs)
                for _ in range(iters)
            ]
        r = sorted(runs, key=lambda x: x["wall_s"])[len(runs) // 2]
        lat = np.asarray(r["lat_s"]) * 1e3
        emit(
            f"serving_unbatched_{requests}x{size}",
            r["wall_s"] * 1e6 / requests,
            rps=round(requests / r["wall_s"], 2),
            p50_ms=round(float(np.percentile(lat, 50)), 1),
            p99_ms=round(float(np.percentile(lat, 99)), 1),
            mean_fill=1.0,
            workers=workers,
        )

        # -- coalesced: ServeScheduler, amortized RMFE batching -----------
        with ServeScheduler(
            pool.master,
            CoalescePolicy(target_batch_n=8, max_wait_ms=50.0),
            max_queue=requests, max_inflight=4, seed=0,
        ) as sched:
            for _ in range(WARM_STREAMS):
                _stream(lambda A, B: sched.submit(A, B, spec=spec), pairs)
            runs = [
                _stream(lambda A, B: sched.submit(A, B, spec=spec), pairs)
                for _ in range(iters)
            ]
            snap = sched.stats.snapshot()
        r = sorted(runs, key=lambda x: x["wall_s"])[len(runs) // 2]
        lat = np.asarray(r["lat_s"]) * 1e3
        batched_wall = r["wall_s"]
        emit(
            f"serving_batched_{requests}x{size}",
            r["wall_s"] * 1e6 / requests,
            rps=round(requests / r["wall_s"], 2),
            p50_ms=round(float(np.percentile(lat, 50)), 1),
            p99_ms=round(float(np.percentile(lat, 99)), 1),
            mean_fill=round(snap["serve_mean_fill"], 2),
            workers=workers,
        )

        # -- traced: same batched mode under repro.obs span recording -----
        from repro import obs

        obs.set_enabled(True)
        try:
            with ServeScheduler(
                pool.master,
                CoalescePolicy(target_batch_n=8, max_wait_ms=50.0),
                max_queue=requests, max_inflight=4, seed=0,
            ) as sched:
                _stream(lambda A, B: sched.submit(A, B, spec=spec), pairs)
                runs = [
                    _stream(
                        lambda A, B: sched.submit(A, B, spec=spec), pairs
                    )
                    for _ in range(iters)
                ]
        finally:
            obs.set_enabled(None)
            obs.tracer().clear()
        r = sorted(runs, key=lambda x: x["wall_s"])[len(runs) // 2]
        emit(
            f"serving_traced_{requests}x{size}",
            r["wall_s"] * 1e6 / requests,
            rps=round(requests / r["wall_s"], 2),
            overhead_pct=round(
                (r["wall_s"] / batched_wall - 1.0) * 100.0, 2
            ),
            workers=workers,
        )

        # -- hedged, no stragglers: the overhead acceptance row -----------
        # a healthy pool must not pay for the hedge plane: the sweep runs
        # every poll but the p95-derived deadline should never fire
        pool.master.hedge_factor = 2.0
        try:
            with ServeScheduler(
                pool.master,
                CoalescePolicy(target_batch_n=8, max_wait_ms=50.0),
                max_queue=requests, max_inflight=4, seed=0,
            ) as sched:
                _stream(lambda A, B: sched.submit(A, B, spec=spec), pairs)
                runs = [
                    _stream(
                        lambda A, B: sched.submit(A, B, spec=spec), pairs
                    )
                    for _ in range(iters)
                ]
        finally:
            pool.master.hedge_factor = 0.0
        hedged_total = int(pool.master.stats()["pool_hedged"])
        r = sorted(runs, key=lambda x: x["wall_s"])[len(runs) // 2]
        emit(
            f"serving_hedged_{requests}x{size}",
            r["wall_s"] * 1e6 / requests,
            rps=round(requests / r["wall_s"], 2),
            overhead_pct=round(
                (r["wall_s"] / batched_wall - 1.0) * 100.0, 2
            ),
            hedged=hedged_total,
            workers=workers,
        )

        # -- straggler race: hedged vs unhedged time-to-R -----------------
        _straggler_race(pool, workers=workers, full=full)


def _straggler_race(pool, workers: int, full: bool) -> None:
    """One parked worker on a zero-slack (R == N) scheme: without hedging
    the request waits out the injected delay; with hedging the overdue
    share re-ships to a spare worker at ~p95 x factor.  Emits the hedged
    time with the unhedged margin, after asserting both decodes equal the
    local sync backend bit for bit."""
    from repro.cdmm import ProblemSpec, coded_matmul, plan
    from repro.core import make_ring

    size = 48  # divisible by workers=6: zero-slack partitions exist
    delay_ms = 400.0
    Z32 = make_ring(2, 32, ())
    spec = ProblemSpec(
        t=size, r=size, s=size, n=1, ring=Z32, N=workers,
        straggler_budget=0,
    )
    p = plan(spec, objective="threshold")
    # zero slack: the candidate with the LARGEST R (== N) — every share
    # is needed, so one parked worker stalls the whole decode
    rank = max(
        range(len(p.candidates)), key=lambda i: p.candidates[i].costs.R
    )
    scheme = p.instantiate(rank)
    assert scheme.R == scheme.N == workers, (scheme.R, scheme.N)
    rng = np.random.default_rng(0)
    A = Z32.random(rng, (size, size))
    B = Z32.random(rng, (size, size))
    oracle = np.asarray(coded_matmul(A, B, scheme, backend="local"))

    master = pool.master
    master.hedge_factor = 0.0
    # warm: jit every worker's ring matmul for this scheme's shard shape
    for _ in range(3):
        master.execute(scheme, A, B)
    # those rounds carry jit-compile round-trips (seconds) that would make
    # the p95-derived hedge deadline dwarf the injected delay; purge them,
    # then re-seed the window with steady-state rounds (6 shares each; the
    # deadline needs >= 8 samples before it arms)
    master.health.clear_window()
    for _ in range(2):
        master.execute(scheme, A, B)

    victim = master.live_workers()[0]
    master.task_delay_ms[victim] = delay_ms
    try:
        # hedged race FIRST: the victim's slow reply lands after the
        # request closes, so it never pollutes the share-ms window the
        # hedge deadline quantile reads
        master.health.reset_scores()  # cold: round-robin is blind again
        master.hedge_factor = 2.0
        C_hedged, st_hedged = master.execute(scheme, A, B)
        master.hedge_factor = 0.0

        master.health.reset_scores()
        C_plain, st_plain = master.execute(scheme, A, B)
    finally:
        master.hedge_factor = 0.0
        master.task_delay_ms.pop(victim, None)

    assert np.array_equal(np.asarray(C_hedged), oracle), "hedged != oracle"
    assert np.array_equal(np.asarray(C_plain), oracle), "unhedged != oracle"
    emit(
        f"serving_straggler_{size}x{size}",
        st_hedged.time_to_R_ms * 1e3,
        unhedged_ms=round(st_plain.time_to_R_ms, 1),
        hedged_ms=round(st_hedged.time_to_R_ms, 1),
        speedup=round(
            st_plain.time_to_R_ms / max(st_hedged.time_to_R_ms, 1e-9), 2
        ),
        hedged=st_hedged.hedged,
        delay_ms=delay_ms,
        workers=workers,
        bit_identical=True,
    )
