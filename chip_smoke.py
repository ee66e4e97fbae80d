#!/usr/bin/env python3
"""Serve coded matmuls on the TPU: one smoke run of the served path.

    python chip_smoke.py                # one chip
    python chip_smoke.py --chips 4      # one worker spreading over 4 chips
    python chip_smoke.py --rehearse     # the same phases on the CPU, small

The path is the one a user calls: ``ServeScheduler`` -> ``Master`` -> the
one worker process that holds the host's chips -> XLA's ``Ring.matmul``.
This process (pool, master, encode and decode) stays on the host CPU.  All
requests use N = 8 shares on that one worker process, straggler budget 2,
and the engine's ``"amortized"`` plans:

- four concurrent Z_2^32 requests at t = 2048, r = s = 4096 (an int8
  4096-wide projection of a 2048-token batch, lifted to Z_2^32), which the
  engine coalesces into ``batch_ep_rmfe`` codewords of fill 2;
- one GR(2^16,3) request at 1024^3;
- two Z_2^32 requests at 256^3, coalesced into one ``batch_ep_rmfe``
  codeword (fill 2).

Full-width results are checked by a Freivalds test on the host: C x ==
A (B x) in the ring for 32 random vectors, which a wrong product passes
with probability at most 2^-32.  The 256^3 results are checked bit for bit
against ``Ring.matmul`` on the host.  The Z_2^32 operands are int8 values
lifted to Z_2^32; the GR(2^16,3) operands are uniform.  The run fails when
a check fails, the worker reports a platform other than ``tpu`` or a share
computed by anything but XLA's ``Ring.matmul``, or any share errored, was
retried, re-dispatched or hedged.

``--chips 4`` runs only the Z_2^32 requests: once on a worker that spreads
shares over all four chips, once on a worker that sees one chip.  The
results must be bit-identical, and every chip must have computed shares.

Timings printed here are those of a smoke run, not a benchmark.  The last
line of stdout is ``{"ok": true, "device": {...}}``, taken from the
chip-holding worker's report; a failure exits non-zero without it.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

N, BUDGET, VECTORS = 8, 2, 32
SIZES = {  # (t, r, s)
    "full": {"z32": (2048, 4096, 4096), "gr": (1024, 1024, 1024),
             "small": (256, 256, 256)},
    "rehearse": {"z32": (64, 128, 128), "gr": (32, 32, 32),
                 "small": (16, 16, 16)},
}
# libtpu's per-process chip bounds; the CPU device count for rehearsals
ONE_DEVICE_ENV = {
    "TPU_VISIBLE_CHIPS": "0",
    "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
    "TPU_PROCESS_BOUNDS": "1,1,1",
}


class SmokeFailure(Exception):
    """A check of the smoke failed."""


def check_worker(caps: dict, platform: str = "tpu") -> dict:
    """The worker's device report, or :class:`SmokeFailure` when it is not
    on ``platform``."""
    if caps.get("platform") != platform:
        raise SmokeFailure(
            f"worker pid {caps.get('pid')} reports platform "
            f"{caps.get('platform')!r}, not {platform!r}"
        )
    return {"platform": caps["platform"], "kind": caps["device_kind"],
            "count": caps["device_count"]}


def check_products(report: dict) -> None:
    """Fail unless XLA's ``Ring.matmul`` computed every share the worker
    reports, and it reports some."""
    impls, shares = report["matmul_tasks"], sum(report["device_tasks"])
    if not shares or impls != {"xla": shares}:
        raise SmokeFailure(
            f"worker computed {shares} shares, by implementation {impls}")


def check_counters(pool: dict, serve: dict) -> None:
    """Fail on any share error, retry, re-dispatch or hedge, and on any
    request the engine did not complete."""
    bad = {k: pool[k] for k in ("pool_share_errors", "pool_redispatched",
                                "pool_hedged", "pool_failed") if pool[k]}
    bad.update({k: serve[k] for k in ("serve_failed", "serve_timed_out",
                                      "serve_rejected") if serve[k]})
    if bad:
        raise SmokeFailure(f"counters not zero: {bad}")


def _settle(master, timeout: float = 120.0) -> dict:
    """The worker's report once it has answered every task it was sent."""
    deadline = time.time() + timeout
    while True:
        reports = master.worker_reports()
        if reports and all(r["tasks_done"] == r["tasks_sent"]
                           for r in reports.values()):
            (report,) = reports.values()
            return report
        if time.time() > deadline:
            raise SmokeFailure(f"worker did not settle: {reports}")
        time.sleep(0.1)


def _freivalds(ring, A, B, C, rng) -> bool:
    import jax

    mm = jax.jit(ring.matmul)
    X = ring.random(rng, (B.shape[1], VECTORS))
    return bool((mm(C, X) == mm(A, mm(B, X))).all())


class Smoke:
    """One pool with one worker, and the requests served through it."""

    def __init__(self, args, chips):
        from repro.cdmm import ProblemSpec
        from repro.core import make_ring

        self.args = args
        self.parent_compiles = chips.compile_counter()
        self.platform = "cpu" if args.rehearse else "tpu"
        self.sizes = SIZES["rehearse" if args.rehearse else "full"]
        self.rings = {"z32": make_ring(2, 32, ()), "gr": make_ring(2, 16, (3,)),
                      "small": make_ring(2, 32, ())}
        self.specs = {
            k: ProblemSpec(t=t, r=r, s=s, n=1, ring=self.rings[k], N=N,
                           straggler_budget=BUDGET)
            for k, (t, r, s) in self.sizes.items()
        }

    def data(self, kind: str, count: int, seed: int):
        """``count`` operand pairs: int8 values lifted to Z_2^32 (a
        quantized projection), uniform elements of GR(2^16,3)."""
        import numpy as np

        ring, (t, r, s) = self.rings[kind], self.sizes[kind]
        rng = np.random.default_rng([seed, list(self.sizes).index(kind)])

        def draw(shape):
            if ring.D > 1:
                return np.asarray(ring.random(rng, shape))
            return rng.integers(-128, 128, shape + (1,)).astype(ring.dtype)

        return [(draw((t, r)), draw((r, s))) for _ in range(count)]

    def check(self, kind: str, A, B, C, rng) -> None:
        import numpy as np

        ring = self.rings[kind]
        if kind == "small":
            ok = np.array_equal(C, np.asarray(ring.matmul(A, B)))
        else:
            ok = _freivalds(ring, A, B, C, rng)
        if not ok:
            raise SmokeFailure(f"{kind} {self.sizes[kind]}: wrong product")

    def serve(self, requests, warm: bool):
        """Run ``requests`` [(kind, A, B)] concurrently through a fresh pool
        and engine, after a warm-up when ``warm``.  Returns the results,
        the worker's device report and its last heartbeat report, the
        phase timings, and the batches the engine ran for the requests."""
        from repro.dist import LocalPool, PoolConfig
        from repro.serve import CoalescePolicy, ServeScheduler

        timings = {}
        t0 = time.perf_counter()
        cfg = PoolConfig(
            workers=1, spawn_timeout=600.0,
            # a smoke choice, not a cell's traffic: encoded Z_2^32 shares
            # are full 32-bit words, so bit-packing saves nothing, and
            # pack_bits expands each element to 64 bytes on the way
            transport="raw",
            # rehearsals stream too: their shares are far below 1 MiB
            **({"stream_chunk_bytes": 1 << 12} if self.args.rehearse else {}),
        )
        with LocalPool(config=cfg) as pool:
            (caps,) = pool.master.worker_caps().values()
            device = check_worker(caps, self.platform)
            timings["setup_s"] = time.perf_counter() - t0
            print(f"worker pid {caps['pid']}: jax {caps['jax_version']}, "
                  f"{caps['device_count']} x {caps['device_kind']} "
                  f"({caps['platform']}); pool up in "
                  f"{timings['setup_s']:.1f} s")
            policy = CoalescePolicy(target_batch_n=8, max_wait_ms=2000.0)
            with ServeScheduler(pool.master, policy, max_queue=16,
                                seed=self.args.seed) as sched:
                if warm:
                    # one request per spec, padded to its batch shape:
                    # every program the requests need compiles here
                    t0 = time.perf_counter()
                    seen = {}
                    for kind, A, B in requests:
                        seen.setdefault(kind, (A, B))
                    futs = {k: sched.submit(A, B, spec=self.specs[k])
                            for k, (A, B) in seen.items()}
                    timings["warm"] = {k: f.result(timeout=900)
                                       for k, f in futs.items()}
                    timings["warmup_s"] = time.perf_counter() - t0
                    timings["batches_warm"] = len(
                        sched.stats.snapshot()["serve_recent_batches"])
                compiles0 = self.parent_compiles()
                worker0 = _settle(pool.master)["compiles"]
                t0 = time.perf_counter()
                done = {}
                futs = []
                for j, (kind, A, B) in enumerate(requests):
                    f = sched.submit(A, B, spec=self.specs[kind])
                    f.add_done_callback(
                        lambda _f, j=j: done.setdefault(j, time.perf_counter()))
                    futs.append(f)
                results = [f.result(timeout=900) for f in futs]
                timings["requests_s"] = time.perf_counter() - t0
                timings["request_ms"] = [
                    round((done[j] - t0) * 1e3, 1) for j in range(len(futs))]
                report = _settle(pool.master)
                check_products(report)
                timings["compiles_in_requests"] = {
                    "parent": self.parent_compiles() - compiles0,
                    "worker": report["compiles"] - worker0}
                serve_snap = sched.stats.snapshot()
            check_counters(pool.master.stats(), serve_snap)
        batches = serve_snap["serve_recent_batches"][
            timings.get("batches_warm", 0):]
        return results, device, report, timings, batches

    def one_chip(self) -> dict:
        import numpy as np

        reqs = ([("z32",) + p for p in self.data("z32", 4, self.args.seed)]
                + [("gr",) + p for p in self.data("gr", 1, self.args.seed)]
                + [("small",) + p for p in self.data("small", 2,
                                                     self.args.seed)])
        results, device, report, timings, batches = self.serve(reqs, True)
        walls = ", ".join(f"{k} {ms} ms" for (k, *_), ms in
                          zip(reqs, timings["request_ms"]))
        print(f"smoke timings (not a benchmark): warm-up incl. compile "
              f"{timings['warmup_s']:.1f} s; {len(reqs)} concurrent requests "
              f"in {timings['requests_s']:.2f} s ({walls})")
        print(f"shares computed per implementation: "
              f"{report['matmul_tasks']}")
        print(f"compiles during the requests after warm-up: "
              f"{timings['compiles_in_requests']}")
        print(f"batches: {batches}")
        # 4 + 2 same-spec requests at cap 2: three batch_ep_rmfe codewords,
        # each carrying two requests
        pairs = [b for b in batches if b["spec"].startswith("batch_ep_rmfe")]
        if len(pairs) != 3 or any(b["fill"] != 2 for b in pairs):
            raise SmokeFailure(f"engine did not coalesce pairs: {batches}")
        rng = np.random.default_rng([self.args.seed, 99])
        for (kind, A, B), C in zip(reqs, results):
            self.check(kind, A, B, C, rng)
        # each warm-up request repeats the first request of its kind
        for kind, C in timings["warm"].items():
            first = next(j for j, (k, *_) in enumerate(reqs) if k == kind)
            if not np.array_equal(C, results[first]):
                raise SmokeFailure(f"{kind}: warm-up result differs")
        print(f"checks passed: {len(reqs)} products "
              f"(Freivalds x{VECTORS} at full width, bit for bit at "
              f"{self.sizes['small']})")
        return device

    def four_chips(self) -> dict:
        import numpy as np

        reqs = [("z32",) + p for p in self.data("z32", 4, self.args.seed)]
        spread, device, report, _, _ = self.serve(reqs, False)
        per_device = report["device_tasks"]
        print(f"shares per device, spread: {per_device}; per "
              f"implementation: {report['matmul_tasks']}")
        # a CPU worker (rehearsal) computes on its first device only
        used = self.args.chips if self.platform == "tpu" else 1
        if (device["count"] != self.args.chips or len(per_device) != used
                or min(per_device) < 1):
            raise SmokeFailure(
                f"{device['count']} devices, shares per device {per_device}")
        saved = {k: os.environ.get(k) for k in (*ONE_DEVICE_ENV, "XLA_FLAGS")}
        os.environ.update(ONE_DEVICE_ENV)
        os.environ["XLA_FLAGS"] = re.sub(
            r"--xla_force_host_platform_device_count=\d+", "",
            os.environ.get("XLA_FLAGS", "")
        ) + " --xla_force_host_platform_device_count=1"
        try:
            single, one, report1, _, _ = self.serve(reqs, False)
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        print(f"shares per device, one device: {report1['device_tasks']}")
        if one["count"] != 1:
            raise SmokeFailure(f"one-device worker sees {one['count']}")
        for j, (a, b) in enumerate(zip(spread, single)):
            if not np.array_equal(a, b):
                raise SmokeFailure(f"request {j}: spread != one device")
        rng = np.random.default_rng([self.args.seed, 99])
        for (kind, A, B), C in zip(reqs, spread):
            self.check(kind, A, B, C, rng)
        print(f"checks passed: {len(reqs)} products bit-identical spread "
              f"over {device['count']} devices and on one; Freivalds "
              f"x{VECTORS}")
        return device


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run on the CPU at small sizes (no chip needed)")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: the repository's sources are not next to this "
              f"script (no {ROOT / 'src' / 'repro'}); nothing was run",
              file=sys.stderr)
        return 2
    # deterministic plans: rank by the analytic cost model, not a host fit
    os.environ.setdefault("REPRO_CALIBRATION", "off")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.dist import chips

    if chips.chip_bound():
        # before anything here touches JAX: the chips are the worker's
        chips.pin_host_to_cpu()
    elif not args.rehearse:
        print(f"chip_smoke: JAX finds no TPU on this host (JAX_PLATFORMS="
              f"{os.environ.get('JAX_PLATFORMS', '')!r}); nothing was run",
              file=sys.stderr)
        return 1
    print(f"compile cache: {chips.enable_compile_cache()}")
    smoke = Smoke(args, chips)
    try:
        device = smoke.four_chips() if args.chips == 4 else smoke.one_chip()
    except SmokeFailure as e:
        print(f"FAIL: {e}")
        return 1
    line = {"ok": True, "device": device}
    if args.rehearse:
        line["rehearsal"] = True
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
