"""Median time to result, in ms, over every request answered in the window
(client side of ``ServeScheduler.submit``, host clock)."""
from perfbench import stats


def read(run):
    lat = [q.latency_s for q in run.answered]
    return stats.quantile(lat, 0.5) * 1e3 if lat else None
