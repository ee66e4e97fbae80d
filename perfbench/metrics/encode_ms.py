"""Self time of the master's ``encode`` spans per codeword, in ms (summed
over the codeword's spans, mean over the codewords of the window)."""
from perfbench import stats


def read(run):
    v = stats.mean(stats.per_trace_sum(run.spans, "encode"))
    return None if v is None else v * 1e3
