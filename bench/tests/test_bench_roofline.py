"""The peaks table and the quorum kernel's operation and byte count."""

import pytest

import roofline


def test_quorum_commit_cost_from_shape():
    assert roofline.quorum_commit_cost(1, 5) == (5 * 25 + 8 * 5, 2 * 4 * 5 + 16)
    f, b = roofline.quorum_commit_cost(1000, 9)
    assert (f, b) == (1000 * (5 * 81 + 72), 1000 * (72 + 16))


def test_peaks_are_keyed_by_device_kind():
    p = roofline.peaks("TPU v5 lite")
    assert p["flops_s"] == 197e12 and p["hbm_bytes_s"] == 819e9
    with pytest.raises(ValueError, match="no peaks"):
        roofline.peaks("cpu")
