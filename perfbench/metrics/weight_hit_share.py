"""Share of the window's codewords whose B operand the master found in its
weight-share cache, in % (100 × the ``pool_weight_hits`` over the
``pool_weight_lookups`` counted in the window); nothing where the window
made no lookup (a keyed scheme, or a master without the cache)."""
from perfbench import stats


def read(run):
    b, a = run.pool["before"], run.pool["after"]
    lookups = stats.delta(a, b, "pool_weight_lookups")
    if not lookups:
        return None
    return 100.0 * stats.delta(a, b, "pool_weight_hits") / lookups
