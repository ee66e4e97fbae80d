"""Share of the traced window in which no operation ran on the device, in
%, averaged over the chips the worker holds: 1 - (union of the device's
operation intervals) / window, from the profiler trace of the worker."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share
