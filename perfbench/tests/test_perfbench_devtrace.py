"""The reduction from a profiler trace to device metrics, on a small trace
recorded on a TPU v5e through the benchmark's worker hook: three rounds of
two (256, 512, 3) @ (512, 128, 3) share products over GR(2^32, 3) and
their ring add, 20 ms of sleep before each round and after the last."""
import json
from pathlib import Path

import pytest

from perfbench import devtrace, yardstick

DATA = Path(__file__).resolve().parent / "data"
TRACE = DATA / "worker_tiny.xplane.pb"
WINDOW_NS = json.loads((DATA / "worker_tiny.stopped.json").read_text())[
    "window_ns"]
# read off this trace once, by hand and by the reduction
BUSY_S = 215.225e-6
TOP_OP, TOP_OP_S = "jit_matmul/while.2", 153.092e-6


@pytest.fixture(scope="module")
def devices():
    return devtrace.read(TRACE)


@pytest.fixture(scope="module")
def red(devices):
    return devtrace.reduce(devices, WINDOW_NS)


def test_the_trace_holds_one_device(devices):
    assert [d.name for d in devices] == ["/device:TPU:0"]
    (dev,) = devices
    assert [m[2].split("(")[0] for m in dev.modules] == (
        ["jit_matmul", "jit_matmul", "jit_add"] * 3)


def test_busy_and_idle_share(devices, red):
    ops = [(s, e) for s, e, _ in devices[0].ops]
    assert red.window_s == pytest.approx(WINDOW_NS / 1e9)
    assert red.busy_s == [pytest.approx(devtrace.busy_ns(ops) / 1e9)]
    assert red.busy_s[0] == pytest.approx(BUSY_S)
    assert red.idle_share == pytest.approx(1 - BUSY_S / red.window_s)
    assert 0.9 < red.idle_share < 1


def test_operation_and_program_time(devices, red):
    top = max(red.op_s, key=red.op_s.get)
    assert top == TOP_OP
    assert red.op_s[top] == pytest.approx(TOP_OP_S)
    assert {n.split("/")[0] for n in red.op_s} == {"jit_matmul", "jit_add"}
    assert sum(red.op_s.values()) >= red.busy_s[0]
    # every operation lies inside a run of its program
    mods = devices[0].modules
    assert busy_in(mods) >= red.busy_s[0]


def test_idle_gaps_are_named_by_the_programs_around_them(red):
    # the three sleeps: after one round's add, before the next products
    assert [name for name, _ in red.gaps[:2]] == ["jit_add -> jit_matmul"] * 2
    assert red.gaps[0][1] > 0.015
    assert sum(s for _, s in red.gaps) <= red.window_s - red.busy_s[0]


def test_roofline_of_the_recorded_products(red):
    ops = yardstick.share_ops(256, 512, 128, 3, 32)
    nbytes = yardstick.share_bytes(256, 512, 128, 3)
    least, bound = yardstick.least_time(ops, nbytes,
                                        yardstick.peaks("TPU v5 lite"))
    assert bound == "compute"
    share = 6 * least / sum(red.busy_s)
    assert share == pytest.approx(6 * ops / 393e12 / BUSY_S)
    assert 0 < share < 1


def busy_in(events):
    return devtrace.busy_ns([(s, e) for s, e, _ in events]) / 1e9


def test_interval_arithmetic():
    iv = [(0, 10), (5, 20), (30, 40), (35, 36)]
    assert devtrace.merge(iv) == [(0, 20), (30, 40)]
    assert devtrace.busy_ns(iv) == 30
    ops = [(0, 10, "a/x"), (5, 20, "a/y"), (30, 40, "b/z"), (35, 36, "b/w"),
           (50, 60, "a/x")]
    assert devtrace.gaps(ops) == [("a -> b", 10), ("b -> a", 10)]
