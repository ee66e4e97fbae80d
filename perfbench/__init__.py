"""The chip benchmark of the coded-matmul service: ``python perfbench/run.py``.

It drives the served path as a user does (``ServeScheduler`` -> ``Master``
-> the chip-holding worker) with a closed loop of callers, checks every
answer against a plain reference, and reads the program's spans and
counters and a profiler trace of the worker.  See ``run.py`` and
``BENCHMARK.json``.
"""
