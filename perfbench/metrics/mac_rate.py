"""Ring multiply-accumulates of the users' products (t * r * s per request)
answered in the window, over the whole window, in Gmac/s.  The window runs
from the first timed request to the last answer: every request sent before
the close is waited for, so the rate counts all the work and all the time
of the window."""
from perfbench import stats


def read(run):
    w = run.window
    macs = [run.macs_per_request for _ in run.answered]
    return stats.rate(macs, w.start, w.end) / 1e9 if macs else None
