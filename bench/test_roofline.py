"""Checks of the byte counts and the peaks table."""
import pytest

from bench import peaks, roofline


def test_spmv_bytes_by_hand():
    # 3 rows, 5 nonzeros: 5 * (4 + 4) + 4 * 4 + 3 * 4 + 3 * 4
    assert roofline.spmv_bytes(3, 5) == 40 + 16 + 12 + 12


def test_bfs_bytes_by_hand():
    # 4 vertices, 6 edges among the reached: 6 * 4 + 5 * 4 + 4 * 4
    assert roofline.bfs_bytes(4, 6) == 24 + 20 + 16


def test_v5e_peaks_and_unknown_device():
    p = peaks.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
