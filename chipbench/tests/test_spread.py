"""The spread statistic the bounds are set from, on values worked out by hand."""

import json

import pytest

from chipbench import spread


def test_spread_is_interquartile_distance_over_the_median():
    # statistics.quantiles' default (exclusive) method on 6 values: q1 at 1.75, q3 at 5.25
    v = [10.0, 11.0, 12.0, 13.0, 14.0, 20.0]
    assert spread.spread(v) == pytest.approx((15.5 - 10.75) / 12.5)
    # the run farthest from the median (20) goes; of the five left q1 = 10.5, q3 = 13.5
    assert spread.trimmed(v) == pytest.approx(3.0 / 12.0)


def test_of_lines_reads_every_metric_of_the_result_lines():
    lines = [json.dumps({"correct": True, "metrics": {"ops_per_s": {"value": x, "unit": "ops/s"}}}) for x in (1.0, 2.0, 3.0)]
    mid, full, cut, values = spread.of_lines(lines)["ops_per_s"]
    assert (mid, values) == (2.0, [1.0, 2.0, 3.0]) and full == pytest.approx(1.0)
