"""The plain reference is exact, the comparison sees a wrong element, and
the control fails the comparison (at a size a test run can hold)."""
import numpy as np
import pytest

from perfbench import cells, harness, loadgen
from perfbench.checks import control
from perfbench.references import ring_matmul


def _exact(A, B, e):
    a = [[int(x) for x in row] for row in A[..., 0]]
    b = [[int(x) for x in row] for row in B[..., 0]]
    out = np.zeros((len(a), len(b[0]), 1), np.uint32)
    for i, row in enumerate(a):
        for j in range(len(b[0])):
            out[i, j, 0] = sum(x * b[k][j] for k, x in enumerate(row)) % (1 << e)
    return out


@pytest.mark.parametrize("e", [32, 16])
@pytest.mark.parametrize("full_words", [False, True])
def test_reference_is_exact(e, full_words):
    rng = np.random.default_rng(e + full_words)
    if full_words:  # uniform words: the limb path
        A = rng.integers(0, 1 << e, (5, 37, 1), dtype=np.uint64).astype(np.uint32)
        B = rng.integers(0, 1 << e, (37, 4, 1), dtype=np.uint64).astype(np.uint32)
    else:  # int8 lifted: one float64 product
        A = loadgen.draw_int8(rng, (5, 37))
        B = loadgen.draw_int8(rng, (37, 4))
    assert np.array_equal(ring_matmul.matmul(A, B, e), _exact(A, B, e))


def _window(n):
    return loadgen.Window(start=0.0, close=1.0, requests=[
        loadgen.Request(0, k, k, t_submit=0.0, t_done=1.0) for k in range(n)])


def test_compare_counts_wrong_elements_and_lost_answers():
    w = _window(3)
    want = {k: np.full((2, 2, 1), k, np.uint32) for k in range(3)}
    for q in w.requests:
        q.result = want[q.operand].copy()
    w.requests[1].result[0, 0, 0] += 1
    w.requests[2].result = w.requests[2].result[:1]  # wrong shape: all wrong
    w.requests.append(loadgen.Request(1, 0, 0, t_submit=0.5, error="lost"))
    assert harness.compare(w, want) == {"wrong_elements": 5, "unanswered": 1}


def test_the_control_fails_the_comparison():
    """The reference over Z_2^16 in the program's place, at the prefill's
    contraction width with few rows: int8 sums of 4096 terms leave 16 bits."""
    cell = cells.find_cell("oproj4096.prefill")
    ref = cells.reference(cell.config)
    rng = np.random.default_rng(1)
    A, B = loadgen.draw_int8(rng, (4, 4096)), loadgen.draw_int8(rng, (4096, 8))
    want = {0: ref.matmul(A, B, 32)}
    w = _window(1)
    w.requests[0].result = control.z16_control(ref, A, B)
    got = harness.compare(w, want)
    assert got["wrong_elements"] > 0
    # the same answers from the exact reference pass
    w.requests[0].result = want[0].copy()
    assert harness.compare(w, want)["wrong_elements"] == 0
