"""Mean compute time per share, in ms, from the worker's ``compute`` spans:
for a streamed share the span's ``busy_us`` (the compute of its chunks,
without the wait for them), else the span's duration."""
from perfbench import stats


def read(run):
    ms = []
    for s in run.spans:
        if s["name"] != "compute":
            continue
        busy = s["tags"].get("busy_us")
        ms.append(busy / 1e3 if busy is not None
                  else (s["t_end"] - s["t_start"]) * 1e3)
    return stats.mean(ms)
