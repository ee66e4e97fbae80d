#!/usr/bin/env python3
"""Run one cell of the chip benchmark once and print its result line.

    python perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python perfbench/run.py --workload <cell> --seed <n> --seconds <s> --rehearse

The cells, their configurations, traffic and metrics are listed in
``BENCHMARK.json`` at the root of the checkout.  With ``--trace 0`` the line
carries the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics, read from the program's spans and counters and from a profiler
trace of the chip-holding worker.  The run needs a TPU: without one it
exits 1 and prints no line.  ``--rehearse`` runs the same steps on the CPU
at a small shape and reports no metric; it is there for the benchmark's own
checks.  The last line of stdout is the result; the numbers compared with
the reference, each with its limit, are the last lines of stderr.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="the CPU at a small shape; reports no metric")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"perfbench: no program next to the benchmark (no "
              f"{ROOT / 'src' / 'repro'}); nothing was run", file=sys.stderr)
        return 2
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from perfbench import harness

    return harness.main(args, T0)


if __name__ == "__main__":
    sys.exit(main())
