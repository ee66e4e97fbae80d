"""Benchmark hook loaded by the chip-holding worker process at start-up.

The benchmark puts this directory on the worker's ``PYTHONPATH`` (the pool
passes its environment on to the worker), so Python imports this module
before the worker's own code, and the worker is left as it is.  It does
nothing unless ``PERFBENCH_WORKER_OUT`` names a directory; then:

- ``SIGUSR1`` starts JAX's profiler in this process, the only one that can
  trace the chip, writing under ``$PERFBENCH_WORKER_OUT/trace``, and writes
  ``started.json``;
- ``SIGUSR2`` stops the profiler if it runs (``stopped.json`` then holds
  the traced window's length on the host clock), reads every local
  device's memory statistics, and writes ``stopped.json``.

The trace holds the devices alone: host tracing (PjRt's per-task events
and the Python tracer) runs to about a million events a second on a TPU
host serving streamed shares, which took 27 GB of the worker's memory and
75 s to write for a 10 s window.

The handlers run in the worker's main thread between two of its Python
steps.
"""
import json
import os
import signal
import time

_OUT = os.environ.get("PERFBENCH_WORKER_OUT")
_state = {"t0": None}  # the traced window's start, perf_counter_ns


def _write(name, obj):
    path = os.path.join(_OUT, name)
    with open(path + ".tmp", "w") as f:
        json.dump(obj, f)
    os.replace(path + ".tmp", path)


def _start(_signum, _frame):
    import jax.profiler

    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 0
    opts.python_tracer_level = 0
    jax.profiler.start_trace(os.path.join(_OUT, "trace"),
                             profiler_options=opts)
    _state["t0"] = time.perf_counter_ns()
    _write("started.json", {"pid": os.getpid()})


def _stop(_signum, _frame):
    out = {"pid": os.getpid()}
    if _state["t0"] is not None:
        import jax.profiler

        out["window_ns"] = time.perf_counter_ns() - _state["t0"]
        jax.profiler.stop_trace()
        _state["t0"] = None
    import jax

    out["memory"] = [
        {"id": d.id, "kind": d.device_kind, "stats": d.memory_stats()}
        for d in jax.local_devices()
    ]
    _write("stopped.json", out)


if _OUT:
    signal.signal(signal.SIGUSR1, _start)
    signal.signal(signal.SIGUSR2, _stop)
