"""Suite-wide fixtures.

``_time_limit`` bounds every test: a test that runs past
``TEST_TIME_LIMIT_S`` fails with a clear message, and faulthandler prints
every thread's stack at the same moment (also when the main thread is stuck
in native code, where the alarm's Python handler cannot run).  A hang then
costs one failure, not the whole run.  xdist runs each test on its worker's
main thread, so SIGALRM reaches it.

No jit cache is cleared between modules.  Eager ring ops, decodes and the
shard_map backend compile once per shape, so a worker's count of live
programs stays bounded; a test that compiles on every call can bring back
the pinned jaxlib's crash in ``backend_compile`` that once showed near
~125 live programs in one process.
"""
import faulthandler
import signal
import time

import pytest

TEST_TIME_LIMIT_S = 300


def pytest_configure(config):
    # no pytest.ini/pyproject in this repo, so the marker registers here.
    # `tools/ci.sh --fast` deselects `slow` (the inline dist/serve smokes)
    # to keep a sub-5-minute local gate; bare `python -m pytest -x -q`
    # remains the full tier-1 run.
    config.addinivalue_line(
        "markers", "slow: multi-process / serving smokes skipped by ci.sh --fast"
    )


@pytest.fixture(autouse=True)
def _time_limit(request):
    def expired(signum, frame):
        pytest.fail(
            f"{request.node.nodeid} ran past the {TEST_TIME_LIMIT_S} s "
            "per-test limit (stacks of every thread on stderr)"
        )

    previous = signal.signal(signal.SIGALRM, expired)
    signal.alarm(TEST_TIME_LIMIT_S)
    faulthandler.dump_traceback_later(TEST_TIME_LIMIT_S)
    try:
        yield
    finally:
        signal.alarm(0)
        faulthandler.cancel_dump_traceback_later()
        signal.signal(signal.SIGALRM, previous)


@pytest.fixture
def wait_until():
    """``wait_until(cond, what)`` polls ``cond()`` until it holds, so a test
    that needs another thread or process to reach a step waits for that
    step itself, not for a sleep that a loaded machine can outrun."""

    def wait(cond, what, timeout=60.0):
        deadline = time.monotonic() + timeout
        while not cond():
            assert time.monotonic() < deadline, f"{what}: not after {timeout} s"
            time.sleep(0.005)

    return wait
