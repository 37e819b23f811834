"""The least-bytes function and the peak table, on hand-computed shapes."""

import pytest

from harness import roofline


def test_least_bytes_by_hand():
    # 10 successors probe 8 bytes each; 4 unique states of 3 lanes are
    # written and read back (12 bytes each way) and their fingerprints
    # written (8 bytes).
    assert roofline.least_bytes(10, 4, 3) == 10 * 8 + 4 * (12 + 12 + 8)


def test_least_bytes_at_the_paxos3_golden():
    # 2,420,477 generated, 1,194,428 unique, 21 lanes.
    b = roofline.least_bytes(2_420_477, 1_194_428, 21)
    assert b == 2_420_477 * 8 + 1_194_428 * (84 + 84 + 8)
    assert 229e6 < b < 230e6


def test_peaks_refuse_an_unknown_device():
    assert roofline.peak_bytes_per_s("TPU v5 lite") == 819e9
    with pytest.raises(ValueError):
        roofline.peak_bytes_per_s("cpu")
