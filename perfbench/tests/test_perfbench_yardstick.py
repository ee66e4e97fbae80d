"""The share product's yardstick and the peaks table."""
import pytest

from perfbench import yardstick


def test_limb_pairs_that_survive():
    assert yardstick.limb_pairs(32) == 10
    assert yardstick.limb_pairs(16) == 3
    assert yardstick.limb_pairs(8) == 1


def test_prefill_share_is_compute_bound():
    # one share of the oproj4096.prefill codeword: (2048, 4096, 3) @
    # (4096, 1024, 3) over GR(2^32, 3)
    ops = yardstick.share_ops(2048, 4096, 1024, 3, 32)
    assert ops == 2 * 9 * 2048 * 4096 * 1024 * 10
    nbytes = yardstick.share_bytes(2048, 4096, 1024, 3)
    assert nbytes == (2048 * 4096 + 4096 * 1024 + 2048 * 1024) * 3 * 4
    peak = yardstick.peaks("TPU v5 lite")
    least, bound = yardstick.least_time(ops, nbytes, peak)
    assert bound == "compute"
    assert least == pytest.approx(ops / 393e12)


def test_a_thin_share_is_memory_bound():
    peak = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}
    ops = yardstick.share_ops(1, 4096, 1024, 1, 8)
    nbytes = yardstick.share_bytes(1, 4096, 1024, 1)
    least, bound = yardstick.least_time(ops, nbytes, peak)
    assert bound == "memory"
    assert least == pytest.approx(nbytes / 819e9)


def test_unknown_device_is_an_error():
    with pytest.raises(yardstick.UnknownDevice):
        yardstick.peaks("cpu")
