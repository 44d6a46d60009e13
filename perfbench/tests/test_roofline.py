"""Peaks table and the grid sweep's work count."""
import numpy as np
import pytest

from perfbench import roofline


def test_peaks_of_v5e_and_unknown_kind_is_an_error():
    pk = roofline.peaks("TPU v5 lite")
    assert pk["flops_per_s"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


def test_crms_grid_work_matches_a_hand_count_at_m4():
    # two candidate rows of four apps with 3, 5, 2, 7 and 4, 4, 1, 6 containers
    n = np.array([[3, 5, 2, 7], [4, 4, 1, 6]])
    flops, bytes_ = roofline.crms_grid_work(n)
    # 8 cells x 35 FLOPs, plus 6 FLOPs for each of the 32 Erlang terms
    assert flops == 8 * 35 + 6 * 32
    # 8 cells x (n, c, m in, the term out) x 4 bytes, plus 4 apps x 5 values x 4 bytes
    assert bytes_ == 8 * 16 + 4 * 20


def test_roofline_share_names_its_bound():
    share, bound = roofline.roofline_share(1e6, 819e3, 1e-6, "TPU v5 lite")
    assert bound == "memory" and share == pytest.approx(100.0)
    share, bound = roofline.roofline_share(197e6, 1.0, 2e-6, "TPU v5 lite")
    assert bound == "compute" and share == pytest.approx(50.0)
