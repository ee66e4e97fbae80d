"""Planted faults: each one has to turn a run's ``correct`` false.

    python perfbench/checks/faults.py

drives the whole run (``perfbench/run.py --rehearse``: pool, engine,
worker, window, comparison) on the CPU at the rehearsal's shape, once as
it is and once with each fault that a cell of this benchmark can have
planted underneath the timed path:

- ``answer``: the worker alters one element of every share result it
  produces (a wrong answer where it is produced);
- ``half_batch``: the master is handed the codeword with its second half
  of requests zeroed, so half of the batch is left out;
- ``stale``: the engine answers each request with the product of the
  request before it, as a step that leaves its state unchanged would.

No cell has an exchange between chips to leave out: each share's product
goes from its chip to the master over the socket.  Exits 0 when the clean
run is correct and every faulty one is not.
"""
from __future__ import annotations

import contextlib
import io
import json
import os
import sys
import time
from argparse import Namespace
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
FAULTHOOK = Path(__file__).resolve().parent / "faulthook"
WORKLOAD = "oproj4096.prefill"


def run_once(seed: int, seconds: float = 3.0) -> dict:
    from perfbench import harness

    args = Namespace(workload=WORKLOAD, seed=seed, seconds=seconds, trace=0,
                     rehearse=True)
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = harness.main(args, time.perf_counter())
    if rc != 0:
        raise RuntimeError(f"run exited {rc}")
    return json.loads(buf.getvalue().strip().splitlines()[-1])


@contextlib.contextmanager
def planted(fault: str):
    from repro.dist.master import Master
    from repro.serve.engine import ServeScheduler

    saved = {k: os.environ.get(k) for k in ("PYTHONPATH", "PERFBENCH_FAULT")}
    execute, submit = Master.execute, ServeScheduler.submit
    try:
        if fault == "answer":
            os.environ["PERFBENCH_FAULT"] = "answer"
            os.environ["PYTHONPATH"] = os.pathsep.join(
                [str(FAULTHOOK)] + [p for p in (saved["PYTHONPATH"] or "")
                                    .split(os.pathsep) if p])
        elif fault == "half_batch":
            def half(self, scheme, A, B, *a, **kw):
                A = A.copy()
                A[A.shape[0] // 2:] = 0
                return execute(self, scheme, A, B, *a, **kw)
            Master.execute = half
        elif fault == "stale":
            last = {}

            def stale(self, A, B, spec, key=None):
                fut = submit(self, A, B, spec, key=key)
                prev = last.get("fut")
                last["fut"] = fut
                if prev is None:
                    return fut
                from concurrent.futures import Future
                out = Future()
                out.trace_id = getattr(fut, "trace_id", None)
                fut.add_done_callback(lambda f: out.set_result(prev.result()))
                return out
            ServeScheduler.submit = stale
        yield
    finally:
        Master.execute, ServeScheduler.submit = execute, submit
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def main() -> int:
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    os.environ["JAX_PLATFORMS"] = "cpu"
    ok = True
    for i, fault in enumerate(("none", "answer", "half_batch", "stale")):
        with planted(fault):
            line = run_once(seed=4000 + i)
        want = fault == "none"
        ok &= line["correct"] is want
        print(json.dumps({"fault": fault, "correct": line["correct"],
                          "attempted": line["attempted"],
                          "checks": {k: v["value"] for k, v in
                                     line["checks"].items()}}), flush=True)
    print("faults: every planted fault failed the run" if ok else
          "faults: a planted fault went unseen, or the clean run failed")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
