"""The share product's share of its roofline, in %: the least time of the
window's share products (``perfbench.yardstick``: int8 limb operations over
the int8 peak, or bytes over HBM bandwidth, whichever is larger) over the
time in which any operation ran on the worker's devices, from its trace.
The cells' devices run nothing but share products, so that time holds
every program that computes them, whatever their names.  Nothing to read
when the trace shows no device time or the window computed no share."""
from perfbench import yardstick


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    busy = sum(run.trace.busy_s)
    b, a = run.worker["before"], run.worker["after"]
    shares = sum(a["device_tasks"]) - sum(b["device_tasks"])
    if busy <= 0 or shares <= 0:
        return None
    sh = run.share
    ops = yardstick.share_ops(sh["t"], sh["r"], sh["s"], sh["D"], sh["e"])
    nbytes = yardstick.share_bytes(sh["t"], sh["r"], sh["s"], sh["D"])
    least, _ = yardstick.least_time(ops, nbytes, run.peaks)
    return 100.0 * shares * least / busy
