"""90th-percentile time to result, in ms, over every request answered in
the window (client side, host clock).  Listed only for cells whose window
holds at least a hundred requests, so that ten lie beyond it."""
from perfbench import stats


def read(run):
    lat = [q.latency_s for q in run.answered]
    return stats.quantile(lat, 0.9) * 1e3 if lat else None
