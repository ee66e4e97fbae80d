"""Mean self time of the engine's ``coalesce_wait`` span per request, in ms:
admission to dispatch of the request's codeword."""
from perfbench import stats


def read(run):
    waits = [st for s, st in zip(run.spans, stats.self_times(run.spans))
             if s["name"] == "coalesce_wait"]
    v = stats.mean(waits)
    return None if v is None else v * 1e3
