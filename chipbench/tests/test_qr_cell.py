"""The ``qr_tall_f32`` configuration and its cell at a CPU size: a traced run
through ``run.main`` ends in a correct line with the two new per-layer
metrics and one force an op, the control comes out not correct, a planted
fault is caught (a column of Q left at zero), the roofline counts the
committed configuration's bytes and FLOP, and both new readers return
``None`` on a run without the counters. The CPU profile has no device plane,
so the traced run is handed a trace whose devices are busy for the length of
each ``bench.qr`` span, and the v5e's peaks.

The tiny sizes are set here, on import, because ``chipbench/conftest.py`` and
``tests/tiny.py`` are another PR's to edit: collected together with
``test_rehearsal.py`` and ``test_benchmark_json.py`` (``python3 -m pytest
chipbench/tests -q``) they are in place before any test runs."""

import glob
import json
import os
import types

import jax
import numpy as np
import pytest

from chipbench import control, rooflines, run, spec, trace
from chipbench.rooflines import qr as qr_roofline
from chipbench.tests import tiny

# 64 columns keep a decade of column scales meaningful; 256 rows a device give CholeskyQR2 its m >= 2 n
tiny.TINY.setdefault("qr_tall_f32", {"rows": {"1": 1024, "4": 1024}, "columns": 64})

CELL = "qr_tall_1c"
V5E = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
NEW_METRICS = {"qr_roofline", "qr_host_ms"}


@pytest.fixture()
def bench(tmp_path):
    return tiny.bench(tmp_path)


def host_spans_as_a_trace(directory):
    (path,) = glob.glob(os.path.join(directory, "plugins", "profile", "*", "*.xplane.pb"))
    spans, names = {}, set()
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    names.add(e.name)
                    if e.name.startswith("bench."):
                        s, en = spans.setdefault(e.name, ([], []))
                        s.append(e.start_ns * 1e-9)
                        en.append((e.start_ns + e.duration_ns) * 1e-9)
    assert {"heat.qr", "heat.qr.prepare", "heat.qr.dispatch", "heat.qr.sync", "heat.qr.wrap", "heat.force"} <= names
    s, e = (np.asarray(v) for v in spans["bench.qr"])
    return trace.Trace({0: (s, e, ["%fusion.8 = f32[8,8] fusion(x), kind=kOutput"] * len(s))}, spans)


def last_line(capsys, bench, trace_on, seed=2147483999):
    run.main(["--workload", CELL, "--seed", str(seed), "--seconds", "1", "--trace", str(trace_on)],
             bench=bench, devices=jax.devices())
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_traced_rehearsal_is_correct_and_reports_the_new_metrics(capsys, bench, monkeypatch):
    monkeypatch.setattr(trace, "load", host_spans_as_a_trace)
    monkeypatch.setattr(rooflines, "peaks", lambda kind: V5E)
    line = last_line(capsys, bench, 1)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["compared"]) >= {"r_gap", "q_gap", "recon_gap", "split_wrong", "qr_path_wrong"}
    want = {m["name"] for m in spec.Cell(CELL, bench).per_layer}
    assert NEW_METRICS | {"fusion_forces_per_op", "force_host_us"} <= want == set(line["metrics"])
    assert line["metrics"]["fusion_forces_per_op"]["value"] == 1.0  # Q, R and the probe: one multi-output node, one force
    assert line["metrics"]["qr_host_ms"]["value"] > 0 and line["metrics"]["qr_roofline"]["value"] > 0
    assert line["metrics"]["compiles_in_window"]["value"] == 0.0


def test_untraced_run_leaves_the_qr_counters_alone(capsys, bench):
    from heat_tpu.core import fusion

    before = fusion.cache_stats()
    line = last_line(capsys, bench, 0)
    after = fusion.cache_stats()
    assert line["correct"] is True
    assert set(line["metrics"]) == {"ops_per_s", "op_ms_p95", "setup_s"}
    assert all(after[key] == before[key] for key in after if key.startswith("phase_qr_"))


def test_control_is_not_correct(bench):
    out = control.control(CELL, 7, 2, bench=bench, devices=jax.devices())
    assert out["correct"] is False and out["control"] == "bfloat16"
    assert out["compared"]["q_gap"][0] > out["compared"]["q_gap"][1]
    assert out["compared"]["recon_gap"][0] > out["compared"]["recon_gap"][1]
    assert out["compared"]["split_wrong"][0] == 0 and out["compared"]["qr_path_wrong"][0] == 0


def test_fault_one_column_of_q_left_at_zero(capsys, bench, monkeypatch):
    op_mod = spec.Cell(CELL, bench).op_module()
    honest = op_mod.Op._trial

    def forgetful(self, a, trial):
        answer = honest(self, a, trial)
        answer["rows"] = answer["rows"] * (np.arange(answer["rows"].shape[1]) != 3)
        return answer

    real = spec.load_module
    monkeypatch.setattr(spec, "load_module", lambda *parts: op_mod if parts[-1] == "qr_trial.py" else real(*parts))
    monkeypatch.setattr(op_mod.Op, "_trial", forgetful)
    line = last_line(capsys, bench, 0)
    assert line["correct"] is False
    assert all(line["compared"][k][0] > line["compared"][k][1] for k in ("q_gap", "recon_gap"))
    assert line["compared"]["r_gap"][0] <= line["compared"]["r_gap"][1]


def test_a_householder_answer_is_another_path(capsys, bench, monkeypatch):
    """``qr_path_wrong`` reads R's diagonal: ``method="tsqr"`` (Householder)
    leaves mixed signs, and a correct factorisation otherwise."""
    cfg_path = next(c["file"] for c in bench["configs"] if c["name"] == "qr_tall_f32")
    with open(cfg_path) as fh:
        cfg = json.load(fh)
    cfg["method"] = "tsqr"
    with open(cfg_path, "w") as fh:
        json.dump(cfg, fh)
    line = last_line(capsys, bench, 0)
    assert line["correct"] is False and line["compared"]["qr_path_wrong"] == [1, 0]
    assert all(line["compared"][k][0] <= line["compared"][k][1] for k in ("r_gap", "q_gap", "recon_gap", "split_wrong"))


def test_roofline_counts_the_rows_read_once_and_q_written_once():
    cfg = spec.Cell(CELL).config
    least = qr_roofline.per_op(cfg, 1, V5E)
    assert least["bytes"] == 2 * 1_250_000 * 512 * 4 + 512 * 512 * 4 == 5_120_000_000 + 1_048_576
    assert least["flops"] == 2 * 1_250_000 * 512 * 512 == 655_360_000_000
    assert least["bound"] == "hbm" and least["seconds"] == pytest.approx(5.121048576e9 / 819e9)
    assert least["flops"] / V5E["bf16_flops_per_s"] == pytest.approx(3.3267e-3, rel=1e-3)


def test_both_readers_read_nothing_on_a_program_without_the_counters():
    """The parent commit has no ``phase_qr_*`` key: ``qr_host_ms`` reads
    ``None``; ``qr_roofline`` needs only the trace and reads ``None`` when no
    device time lies inside the op spans."""
    counters = {"fusion": {"forces": 3, "phase_forces": 3}}
    s, e = np.array([0.0, 1.0]), np.array([0.4, 1.4])
    idle_in_ops = trace.Trace({0: (e, e + 0.1, ["%fusion.1 = f32[8,8] fusion(x)"] * 2)}, {"bench.op": (s, e + 0.2)})
    idle_in_ops.busy_in_ops_per_op = lambda: 0.0
    run_ = types.SimpleNamespace(
        counters={"before": counters, "after": counters}, trace=idle_in_ops,
        config=spec.Cell(CELL).config, chips=1, device_kind="TPU v5 lite",
    )
    assert spec.load_module("layer_metrics", "qr_host_ms.py").read(run_) is None
    assert spec.load_module("layer_metrics", "qr_roofline.py").read(run_) is None


def test_the_configuration_states_what_the_cell_checks():
    cfg = spec.Cell(CELL).config
    assert (cfg["dtype"], cfg["multiplication"], cfg["accumulation"]) == ("float32",) * 3
    assert cfg["reduced"] == ["rows"] and cfg["columns"] == 512 and cfg["rows"] == {"1": 1_250_000}
    assert cfg["rows_published"] == cfg["rows"]["1"] * cfg["chips_that_share_the_rows"] == 10_000_000
    assert (cfg["method"], cfg["calc_q"], cfg["check"]["control_cast"]) == ("auto", True, "bfloat16")
    assert 3 * cfg["rows"]["1"] * cfg["columns"] * 4 == 7_680_000_000  # A, Q1 and Q: 48 % of 16e9 B
    entry = next(c for c in spec.benchmark()["configs"] if c["name"] == "qr_tall_f32")
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200 and entry["reduced"] == cfg["reduced"]
    cell = next(w for w in spec.benchmark()["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and spec.Cell(CELL).traffic["check_rows"] == 256
