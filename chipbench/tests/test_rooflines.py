"""Least work from shapes, on shapes worked out by hand."""

import pytest

from chipbench import rooflines
from chipbench.rooflines import reduce

V5E = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def test_peaks_table_and_unknown_kind():
    assert rooflines.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        rooflines.peaks("TPU v9")
    with pytest.raises(KeyError):
        rooflines.peaks("_source")


def test_reduce_is_one_read_per_result():
    cfg = {"resident_shape": [1572864, 1024], "small_shape": [1000, 1000]}
    per_op = reduce.per_op
    big = per_op(cfg, {"operand": "resident"}, 6, V5E)
    assert big["bytes"] == 6 * 1572864 * 1024 * 4 and big["bound"] == "hbm"
    assert big["seconds"] == pytest.approx(0.04719744, rel=1e-5)
    assert per_op(cfg, {"operand": "small"}, 6, V5E)["bytes"] == 6 * 4e6
    assert per_op(cfg, {"operand": "resident"}, 3, V5E)["seconds"] == pytest.approx(big["seconds"] / 2)
    assert big["flops"] == 6 * 3 * 1572864 * 1024


def test_least_names_the_bound_that_applies():
    assert rooflines.least(819e9, 1.0, V5E) == {"bytes": 819e9, "flops": 1.0, "seconds": 1.0, "bound": "hbm"}
    assert rooflines.least(1.0, 2 * 197e12, V5E)["bound"] == "compute"
    assert rooflines.least(1.0, 2 * 197e12, V5E)["seconds"] == pytest.approx(2.0)
