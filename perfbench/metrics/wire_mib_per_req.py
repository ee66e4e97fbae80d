"""Bytes on the wire per request answered, both ways, in MiB (the master's
``pool_bytes_out`` and ``pool_bytes_in`` counters over the window)."""
from perfbench import stats


def read(run):
    b, a = run.pool["before"], run.pool["after"]
    n = len(run.answered)
    if not n:
        return None
    moved = (stats.delta(a, b, "pool_bytes_out")
             + stats.delta(a, b, "pool_bytes_in"))
    return moved / n / 2 ** 20
