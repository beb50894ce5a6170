"""The trace reduction: interval arithmetic on made-up intervals, and the
whole reduction on a small trace recorded on a v5e (3 ``moments_trial`` ops on
the 6.44 GB operand, PR 26's first chip call)."""

import os

import numpy as np
import pytest

from chipbench import trace

RECORDED = os.path.join(os.path.dirname(__file__), "data", "scan_3ops.xplane.pb")


def test_union_counts_nested_and_overlapping_once():
    s, e = np.array([0.0, 1.0, 5.0, 5.5]), np.array([4.0, 2.0, 6.0, 7.0])
    assert trace.union_length(s, e) == pytest.approx(4.0 + 2.0)
    ms, me = trace.merge(s, e)
    assert list(ms) == [0.0, 5.0] and list(me) == [4.0, 7.0]


def test_covered_by_disjoint_intervals():
    merged = (np.array([0.0, 5.0]), np.array([4.0, 7.0]))
    got = trace.covered(merged, np.array([-1.0, 3.0, 4.5, 6.0]), np.array([1.0, 5.5, 4.8, 9.0]))
    assert list(got) == pytest.approx([1.0, 1.5, 0.0, 1.0])


def test_self_time_takes_children_out():
    own = trace.self_times([0.0, 1.0, 2.0, 10.0], [8.0, 5.0, 3.0, 11.0], ["while", "body", "k", "k"])
    assert own == pytest.approx({"while": 4.0, "body": 3.0, "k": 2.0})


def test_short_name():
    hlo = "%fusion.1 = (bf16[8]{0:T(1024)(128)(2,1)}, s32[8]{0:T(1024)}) fusion(f32[8,16]{1,0:T(8,128)} %p), kind=kLoop"
    assert trace.short_name(hlo) == "fusion.1:fusion"
    assert trace.short_name("%all-reduce.3 = f32[16,8]{1,0:T(8,128)} all-reduce(f32[16,8] %x)") == "all-reduce.3:all-reduce"


def test_no_device_operation_is_an_error():
    with pytest.raises(ValueError):
        trace.Trace({0: ((), (), ())}, {"bench.op": ([0.0], [1.0])})


def test_synthetic_window_idle_and_gaps():
    t = trace.Trace(
        {0: ((1.0, 3.0), (2.0, 4.0), ("%a = f32[] add(x)", "%all-reduce.1 = f32[] all-reduce(x)"))},
        {"bench.op": ([0.0, 5.0], [4.5, 6.0]), "bench.force": ([2.0], [3.5])},
    )
    assert t.window_s == pytest.approx(6.0) and t.n_ops == 2
    assert t.busy_s == pytest.approx(2.0) and t.idle_pct() == pytest.approx(100 * 4 / 6)
    assert t.busy_in_ops_per_op() == pytest.approx(1.0)
    gaps = dict(t.breakdown()["idle_gaps"])
    assert gaps["bench.force"] == pytest.approx(1.0)  # 2..3 of the force span is idle
    assert gaps["bench.between_ops"] == pytest.approx(0.5)
    assert gaps["bench.op_other"] == pytest.approx(1.0 + 0.5 + 1.0)  # 0..1, 4..4.5, 5..6


def test_recorded_trace():
    t = trace.from_profile(RECORDED)
    assert t.n_ops == 3 and sorted(t.devices) == [0]
    assert t.window_s == pytest.approx(0.2535895, rel=1e-5)
    assert t.busy_s == pytest.approx(0.2339281, rel=1e-5)
    assert t.idle_pct() == pytest.approx(7.7532, rel=1e-4)
    assert t.busy_in_ops_per_op() == pytest.approx(0.0779760, rel=1e-5)
    assert t.span_mean_s("bench.record") == pytest.approx(8.6667e-5, rel=1e-3)
    assert t.span_mean_s("bench.force") == pytest.approx(0.01398733, rel=1e-5)
    assert t.span_mean_s("bench.nothing") is None
    b = t.breakdown()
    assert b["device_ops"][0][0] == "multiply_reduce_fusion:fusion" and len(b["device_ops"]) <= 10
    assert b["idle_gaps"][0][0] == "bench.force"
    assert sum(v for _, v in b["idle_gaps"]) == pytest.approx(t.window_s - t.busy_s, rel=1e-6)
