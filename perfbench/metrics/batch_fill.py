"""Mean requests per codeword over the window (``ServeStats`` counters
``serve_total_fill`` / ``serve_batches``)."""
from perfbench import stats


def read(run):
    b, a = run.serve["before"], run.serve["after"]
    batches = stats.delta(a, b, "serve_batches")
    return stats.delta(a, b, "serve_total_fill") / batches if batches else None
