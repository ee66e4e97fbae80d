"""One run of one cell on the served path, and its result line.

The run, in order:

1. set-up: the pool as a user starts it (``LocalPool`` with one
   chip-holding worker, then ``ServeScheduler``), the operands from the
   seed, and one full codeword of the cell's own shape, which compiles
   (or loads from the cache) every program the window uses;
2. the window: a closed loop of the traffic's clients for ``--seconds``;
   every request sent before the close is waited for (a minute at most),
   so the window ends at its last answer;
3. after the window: the worker's device memory (and, with ``--trace 1``,
   the profiler trace of the worker) is read, the pool is closed, and
   every answer of the window is compared with the plain reference;
4. the metrics of the cell, each from its own reader, and the line.

``setup_s`` runs from the start of the process to the first timed request.
A run that finds no TPU fails before it starts a pool and prints no line;
``--rehearse`` runs the same steps on the CPU at a small shape for the
benchmark's own checks, and reports no metric.
"""
from __future__ import annotations

import json
import os
import resource
import shutil
import signal
import sys
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from . import cells, devtrace, loadgen, yardstick

HERE, ROOT = cells.HERE, cells.ROOT
OUT = ROOT / ".perfbench_out"  # the run's scratch files, listed in .gitignore
HOOK = HERE / "hook"
CACHE = ROOT / ".jax_compile_cache"
ANSWER_WAIT_S = 60.0  # how long past the close an answer is waited for
SIGNAL_WAIT_S = 300.0  # how long the worker may take to stop the profiler
SETTLE_S = 300.0  # how long the worker may take to finish late shares
CHECKED = {"pool_share_errors": "share_errors",
           "pool_redispatched": "redispatched", "pool_hedged": "hedged",
           "pool_failed": "pool_failed"}


class RunFailure(Exception):
    """The run cannot give a result: no chip, or a worker that fails."""


@dataclass
class Run:
    """Everything a metric reader may read about one run."""

    cell: cells.Cell
    shape: tuple  # (t, r, s) of a request
    share: Dict  # t, r, s, D, e of one share product
    setup_s: float
    window: loadgen.Window
    spans: List[Dict]
    pool: Dict  # master counters: {"before": ..., "after": ...}
    serve: Dict  # engine counters
    worker: Dict  # the worker's heartbeat report
    device: Dict
    trace: Optional[devtrace.Reduction] = None
    peaks: Optional[Dict] = None
    extra: Dict = field(default_factory=dict)

    @property
    def answered(self) -> List[loadgen.Request]:
        return [q for q in self.window.requests if q.answered]

    @property
    def macs_per_request(self) -> int:
        t, r, s = self.shape
        return t * r * s


def prepare_env() -> None:
    """Environment for this process and the worker it starts, set before
    anything imports JAX: the compile cache inside the checkout, no TPU
    logs outside it, and the worker hook."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    # no size cap: JAX's capped (LRU) cache fails its writes when the
    # parent and the worker share the directory
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ["TPU_LOG_DIR"] = "disabled"  # libtpu logs to /tmp otherwise
    os.environ.pop("REPRO_TRACE", None)
    os.environ["REPRO_TRACE_BUFFER"] = str(1 << 20)
    os.environ["PERFBENCH_WORKER_OUT"] = str(OUT / "worker")
    path = [p for p in os.environ.get("PYTHONPATH", "").split(os.pathsep)
            if p and p != str(HOOK)]
    os.environ["PYTHONPATH"] = os.pathsep.join(path + [str(HOOK)])
    CACHE.mkdir(exist_ok=True)
    shutil.rmtree(OUT / "worker", ignore_errors=True)
    (OUT / "worker").mkdir(parents=True)


def apply_calibration(config: Dict) -> None:
    """The planner's calibration as the configuration states it:
    ``default`` loads the repo's committed fit, as a user's process does
    when ``REPRO_CALIBRATION`` is unset; ``off`` plans by the analytic
    model."""
    if config["planner_calibration"] == "default":
        os.environ.pop("REPRO_CALIBRATION", None)
    else:
        os.environ["REPRO_CALIBRATION"] = "off"


def check_worker(caps: Dict, platform: str, chips: Optional[int]) -> None:
    """Raise RunFailure unless the worker runs on ``platform`` and holds
    exactly ``chips`` devices (None: any number)."""
    if caps.get("platform") != platform:
        raise RunFailure(f"worker pid {caps.get('pid')} runs on "
                         f"{caps.get('platform')!r}, not {platform}")
    if chips is not None and caps["device_count"] != chips:
        raise RunFailure(f"the worker holds {caps['device_count']} chips; "
                         f"the cell runs on {chips}")


def _signal_worker(pid: int, sig: int, marker: str) -> Dict:
    """Signal the worker's hook and wait for the file it writes back."""
    path = OUT / "worker" / marker
    path.unlink(missing_ok=True)
    os.kill(pid, sig)
    deadline = time.time() + SIGNAL_WAIT_S
    while not path.exists():
        if time.time() > deadline:
            raise RunFailure(f"worker pid {pid} wrote no {marker}")
        time.sleep(0.05)
    return json.loads(path.read_text())


def _settle(master) -> Dict:
    """The worker's report once it has answered every task it was sent."""
    deadline = time.time() + SETTLE_S
    while True:
        reports = master.worker_reports()
        if len(reports) == 1:
            (report,) = reports.values()
            if report["tasks_done"] == report["tasks_sent"]:
                return report
        elif not reports:
            raise RunFailure("the worker left the pool")
        if time.time() > deadline:
            raise RunFailure(f"worker did not settle: {reports}")
        time.sleep(0.05)


def _share_shape(scheme, t: int, r: int, s: int, batched: bool) -> Dict:
    import jax
    import jax.numpy as jnp

    lead = (scheme.batch,) if batched else ()
    a = jax.ShapeDtypeStruct(lead + (t, r, 1), jnp.uint32)
    b = jax.ShapeDtypeStruct(lead + (r, s, 1), jnp.uint32)
    fa, gb = jax.eval_shape(
        lambda x, y: (scheme.encode_a_at(x, 0), scheme.encode_b_at(y, 0)),
        a, b)
    return {"t": fa.shape[0], "r": fa.shape[1], "s": gb.shape[1],
            "D": scheme.ring.D, "e": scheme.ring.e}


def compare(window: loadgen.Window, expected: Dict[int, "object"]) -> Dict:
    """Wrong elements over every answer of the window, and the requests
    that got no answer."""
    import numpy as np

    wrong = 0
    for q in window.requests:
        if not q.answered:
            continue
        want = expected[q.operand]
        got = np.asarray(q.result)
        if got.shape != want.shape:
            wrong += int(want.size)
        else:
            wrong += int(np.count_nonzero(got != want))
    return {"wrong_elements": wrong,
            "unanswered": sum(1 for q in window.requests if not q.answered)}


def expected_products(cell: cells.Cell, As, B, window) -> Dict:
    """The reference's product for every activation set the window
    answered, from one product of the sets stacked by rows."""
    import numpy as np

    ref = cells.reference(cell.config)
    used = sorted({q.operand for q in window.requests if q.answered})
    if not used:
        return {}
    C = ref.matmul(np.concatenate([As[k] for k in used]), B,
                   int(cell.config["ring"]["e"]))
    t = As[0].shape[0]
    return {k: C[i * t:(i + 1) * t] for i, k in enumerate(used)}


class Bench:
    def __init__(self, cell: cells.Cell, args, t0: float):
        self.cell, self.args, self.t0 = cell, args, t0

    def run(self) -> Dict:
        from repro.cdmm import ProblemSpec
        from repro.core import make_ring
        from repro.dist import LocalPool, PoolConfig, chips
        from repro.obs import trace as obs
        from repro.serve import CoalescePolicy, ServeScheduler

        cell, args = self.cell, self.args
        cfg, traffic = cell.config, cell.traffic
        trace = bool(args.trace)
        t, r, s = loadgen.shape(cfg, traffic, args.rehearse)
        B, As = loadgen.operands(args.seed, t, r, s,
                                 int(traffic["operand_sets"]))
        ring = make_ring(int(cfg["ring"]["p"]), int(cfg["ring"]["e"]),
                         tuple(cfg["ring"]["degrees"]))
        spec = ProblemSpec(t=t, r=r, s=s, n=1, ring=ring, N=int(cfg["N"]),
                           straggler_budget=int(cfg["straggler_budget"]),
                           privacy_t=int(cfg["privacy_t"]))
        obs.set_enabled(trace)
        parent_compiles = chips.compile_counter()
        pool_cfg = PoolConfig(
            workers=1, transport=cfg["transport"], spawn_timeout=600.0,
            # rehearsal shares are far below the default chunk: stream them
            **({"stream_chunk_bytes": 1 << 12} if args.rehearse else {}))
        platform = "cpu" if args.rehearse else "tpu"
        with LocalPool(config=pool_cfg) as pool:
            master = pool.master
            (caps,) = master.worker_caps().values()
            check_worker(caps, platform, None if args.rehearse else cell.chips)
            device = {"platform": caps["platform"],
                      "kind": caps["device_kind"],
                      "count": caps["device_count"]}
            policy = CoalescePolicy(**cfg["coalesce"])
            with ServeScheduler(master, policy,
                                max_queue=4 * int(traffic["clients"]) + 16,
                                max_inflight=int(cfg["max_inflight"]),
                                objective=cfg["objective"],
                                seed=loadgen.seed_entropy(args.seed) % (1 << 31)
                                ) as sched:
                entry = sched.entry_for(spec)

                def submit(A, Bm):
                    return sched.submit(A, Bm, spec=spec)

                # set-up: one full codeword of the cell's own shape
                warm = [submit(As[k % len(As)], B) for k in range(entry.cap)]
                for f in warm:
                    f.result(timeout=1200)
                worker0 = _settle(master)
                pool0, serve0 = master.stats(), sched.stats.snapshot()
                compiles0 = parent_compiles()
                if trace:
                    _signal_worker(caps["pid"], signal.SIGUSR1, "started.json")
                obs.tracer().clear()
                setup_s = time.perf_counter() - self.t0
                window = loadgen.closed_loop(
                    submit, As, B, int(traffic["clients"]), args.seconds,
                    ANSWER_WAIT_S)
                worker1 = _settle(master)
                pool1, serve1 = master.stats(), sched.stats.snapshot()
                compiles1 = parent_compiles()
                ids = [q.trace_id for q in window.requests if q.trace_id]
                spans = [sp.to_json() for sp in obs.tracer().spans(*ids)]
                t_stop = time.perf_counter()
                stopped = _signal_worker(caps["pid"], signal.SIGUSR2,
                                         "stopped.json")
                run_extra = {"stop_s": time.perf_counter() - t_stop,
                             "plan": f"{entry.label} cap {entry.cap} "
                                     f"R {entry.scheme.R}"}
                share = _share_shape(entry.scheme, t, r, s, entry.cap > 1)
        run = Run(
            cell=cell, shape=(t, r, s), share=share, setup_s=setup_s,
            window=window, spans=spans,
            pool={"before": pool0, "after": pool1},
            serve={"before": serve0, "after": serve1},
            worker={"before": worker0, "after": worker1},
            device=device,
            extra={"parent_compiles": compiles1 - compiles0, **run_extra})
        peaks = [m["stats"].get("peak_bytes_in_use", 0)
                 for m in stopped["memory"] if m.get("stats")]
        if peaks:
            device["memory_peak_bytes"] = max(peaks)
        if trace:
            path = devtrace.find_xplane(OUT / "worker" / "trace")
            run.extra["trace_bytes"] = path.stat().st_size
        if trace and not args.rehearse:
            run.peaks = yardstick.peaks(device["kind"])
            t_read = time.perf_counter()
            red = devtrace.reduce(devtrace.read(path), stopped["window_ns"],
                                  used=range(device["count"]))
            run.extra["reduce_s"] = time.perf_counter() - t_read
            run.trace = red
            device["busy_s"] = red.mean_busy_s
            device["window_s"] = red.window_s
        expected = expected_products(cell, As, B, window)
        checks = self.checks(run, compare(window, expected))
        line = {
            "correct": all(c["value"] <= c["limit"] for c in checks.values()),
            "attempted": len(window.requests),
            "failed": checks["unanswered"]["value"],
            "metrics": {} if args.rehearse else self.metrics(run, trace),
            "device": device,
        }
        if args.rehearse:
            line["rehearsal"] = True
        if run.trace is not None:
            line["breakdown"] = {
                "device_ops": sorted(run.trace.op_s.items(),
                                     key=lambda kv: -kv[1])[:10],
                "idle_gaps": run.trace.gaps[:10],
            }
        line["checks"] = checks
        self.diagnose(run)
        return line

    @staticmethod
    def checks(run: Run, compared: Dict) -> Dict:
        """Each number compared, with its limit (a run is correct when no
        number exceeds its limit)."""
        after = run.pool["after"]
        report = run.worker["after"]
        out = {
            "wrong_elements": compared["wrong_elements"],
            "unanswered": compared["unanswered"],
        }
        for key, name in CHECKED.items():
            out[name] = int(after.get(key, 0) or 0)
        out["unaccounted_shares"] = abs(
            sum(report["device_tasks"]) - sum(report["matmul_tasks"].values()))
        out["idle_devices"] = sum(1 for n in report["device_tasks"] if n == 0)
        return {k: {"value": v, "limit": 0} for k, v in out.items()}

    def metrics(self, run: Run, trace: bool) -> Dict:
        out = {}
        kind = "per_layer" if trace else "end_to_end"
        for m in self.cell.metrics_of(kind):
            value = cells.reader(m.name)(run)
            if value is None:
                if kind == "end_to_end":
                    raise RunFailure(f"no value for end-to-end metric "
                                     f"{m.name}")
                continue
            out[m.name] = {"value": value, "unit": m.unit}
        return out

    def diagnose(self, run: Run) -> None:
        """What the line does not carry, for whoever reads the run's log."""
        w = run.window
        report = run.worker["after"]
        print(f"perfbench: cell {self.cell.name}, request {run.shape}, "
              f"share {run.share}; {len(w.requests)} requests, "
              f"{len(run.answered)} answered; window "
              f"{w.end - w.start:.3f} s (close at {w.close - w.start:.3f} s)"
              f"; worker shares per device {report['device_tasks']}, by "
              f"implementation {report['matmul_tasks']}; compiles in the "
              f"window: worker {report['compiles'] - run.worker['before']['compiles']}"
              f", parent {run.extra['parent_compiles']}", file=sys.stderr)
        lat = sorted(round(q.latency_s * 1e3) for q in run.answered)
        print(f"perfbench: times to result (ms, sorted): {lat}",
              file=sys.stderr)
        print(f"perfbench: peak RSS (KiB) parent "
              f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss}, worker "
              f"{resource.getrusage(resource.RUSAGE_CHILDREN).ru_maxrss}; "
              + ", ".join(f"{k} {v}" for k, v in run.extra.items()),
              file=sys.stderr)
        if run.trace is not None:
            print(f"perfbench: device busy {run.trace.busy_s} s of "
                  f"{run.trace.window_s} s", file=sys.stderr)


def main(args, t0: float) -> int:
    try:
        cell = cells.find_cell(args.workload)
    except cells.CellError as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 2
    prepare_env()
    apply_calibration(cell.config)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    from repro.dist import chips

    if not args.rehearse:
        if not chips.chip_bound():
            print(f"perfbench: JAX finds no TPU here (JAX_PLATFORMS="
                  f"{os.environ.get('JAX_PLATFORMS', '')!r}); nothing was "
                  f"run", file=sys.stderr)
            return 1
        # before anything here touches JAX: the chips are the worker's
        chips.pin_host_to_cpu()
    try:
        line = Bench(cell, args, t0).run()
    except RunFailure as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 1
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(line))
    return 0
