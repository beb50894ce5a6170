"""The ``lasso_f32`` configuration and its cell at a CPU size: a traced run
through ``run.main`` ends in a correct line with the four new per-layer
metrics and thirty reads a fit, an untraced run moves no ``phase_lasso_*``
key, the control comes out NOT correct, planted faults are caught (a
coefficient left at zero, a sweep left out, a fit in residual mode, a theta
that is not finite), the generator's columns are off centre, correlated and of
unit mean square, a program that cannot say how it multiplies is refused at
once, the roofline counts the committed
configuration's bytes and FLOP, and the four readers return ``None`` on a run
without the counters. The CPU profile has no device plane, so the traced run
is handed a trace whose device runs one ``while`` for the length of each
``bench.fit`` span, and the v5e's peaks.

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

from chipbench import control, design, rooflines, run, spec, trace
from chipbench.rooflines import lasso as lasso_roofline
from chipbench.tests import tiny

# 64 features over 2048 rows (512 a device): Gram mode, six of the 63 penalised coefficients in the truth, the
# configuration's own columns ((1 + Z B) / sqrt(2): unit mean square, which upstream's step takes for granted).
# The limits are the CPU size's own: rounding to bfloat16 averages out over the rows, so at 2048 of them the
# control is further off than at 3 145 728
_CFG = spec.Cell("lasso_1c").config
TINY_DATA = dict(_CFG["data"], nonzero=6, block_rows=512)
TINY_CHECK = dict(_CFG["check"], theta_gap=1e-4, objective_gap=5e-6)
tiny.TINY.setdefault("lasso_f32", {"rows": {"1": 2048, "4": 2048}, "features": 64, "data": TINY_DATA, "check": TINY_CHECK})

CELL = "lasso_1c"
V5E = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
NEW_METRICS = {"lasso_roofline", "cd_step_us", "lasso_syncs_per_op", "lasso_host_ms"}
GAPS = ("theta_gap", "support_wrong", "objective_gap", "lasso_path_wrong")
# the sweep's loop as a v5e's trace names it (the compiled program's instruction, 64 and 512 features)
WHILE = ("%while.5 = (s32[]{:T(128)}, f32[64]{0:T(128)S(1)}, f32[64,1]{0,1:T(1,128)S(1)}, f32[64,64]{1,0:T(8,128)}, f32[]{:T(128)}, "
         "/*index=5*/f32[]{:T(128)}, s32[]{:T(128)}) while(%tuple.41), condition=%wide.wide.region_2.5.clone, body=%wide.wide.region_0.4.clone")
WHILE_512 = WHILE.replace("f32[64]{0:T(128)S(1)}", "f32[512]{0:T(512)S(1)}").replace("f32[64,", "f32[512,").replace(",64]", ",512]")
GRAM_WHILE = ("%while.3 = (s32[]{:T(128)}, f32[512,512]{1,0:T(8,128)S(1)}, f32[512,512]{1,0:T(8,128)S(1)}, f32[512]{0:T(512)S(1)}, "
              "f32[512]{0:T(512)S(1)}, /*index=5*/f32[3145728,512]{1,0:T(8,128)}, f32[3145728,1]{0,1:T(1,128)}, s32[]{:T(128)}) "
              "while(%tuple.32), condition=%wide.region_2.8.clone, body=%wide.region_0.7.clone")


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
    assert {"heat.lasso.fit"} | {f"heat.lasso.fit.{p}" for p in ("prepare", "gram", "dispatch", "sync", "copy", "wrap")} <= names
    s, e = (np.asarray(v) for v in spans["bench.fit"])
    return trace.Trace({0: (s, e, [WHILE] * len(s))}, spans)


def last_line(capsys, bench, trace_on, seed=2147483999):
    run.main(["--workload", CELL, "--seed", str(seed), "--seconds", "1", "--trace", str(trace_on)],
             bench=bench, devices=jax.devices())
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_traced_rehearsal_is_correct_and_reports_the_new_metrics(capsys, bench, monkeypatch):
    monkeypatch.setattr(trace, "load", host_spans_as_a_trace)
    monkeypatch.setattr(rooflines, "peaks", lambda kind: V5E)
    line = last_line(capsys, bench, 1)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["compared"]) >= set(GAPS)
    want = {m["name"] for m in spec.Cell(CELL, bench).per_layer}
    assert NEW_METRICS | {"device_idle_pct", "compiles_in_window"} == want == set(line["metrics"])
    assert line["metrics"]["lasso_syncs_per_op"]["value"] == 30.0  # one read a sweep, every sweep runs
    assert line["metrics"]["lasso_host_ms"]["value"] > 0 and 0 < line["metrics"]["lasso_roofline"]["value"]
    assert line["metrics"]["compiles_in_window"]["value"] == 0.0
    # the stand-in device runs its loop for the whole of each bench.fit: 30 sweeps of 64 steps share it
    assert 0 < line["metrics"]["cd_step_us"]["value"] < 1e6 * line["window_s"] / (30 * 64)


def test_untraced_window_leaves_the_lasso_counters_alone(capsys, bench):
    """No ``phase_lasso_*`` key moves in an untraced window. After the window
    the check asks the program which mode a fit takes: ONE fit with telemetry
    on, and that fit's own count is all the process's keys move by."""
    from heat_tpu.core import fusion

    before = fusion.cache_stats()
    line = last_line(capsys, bench, 0)
    after = fusion.cache_stats()
    assert line["correct"] is True and line["ops_timed"] > 1
    assert set(line["metrics"]) == {"ops_per_s", "op_ms_p95", "setup_s"}
    lasso_keys = [key for key in after if key.startswith("phase_lasso_")]
    assert len(lasso_keys) == 9
    moved = {key[len("phase_lasso_"):]: after[key] - before[key] for key in lasso_keys if not key.endswith("_ns")}
    assert moved == {"fits": 1, "sweeps": 30, "syncs": 30}


@pytest.mark.parametrize("seed", [7, 2147483999])
def test_control_is_not_correct(bench, seed):
    out = control.control(CELL, seed, 2, bench=bench, devices=jax.devices())
    assert out["correct"] is False and out["control"] == "bfloat16"
    assert out["compared"]["theta_gap"][0] > out["compared"]["theta_gap"][1]
    assert out["compared"]["lasso_path_wrong"][0] == 0  # the control takes the same path, one precision lower


def planted(monkeypatch, bench, change):
    """The cell's op kind with ``change(self, answer)`` applied to every answer."""
    op_mod = spec.Cell(CELL, bench).op_module()
    honest = op_mod.Op._fit

    def faulty(self, x):
        return change(self, honest(self, x))

    real = spec.load_module
    monkeypatch.setattr(spec, "load_module", lambda *parts: op_mod if parts[-1] == "lasso_fit.py" else real(*parts))
    monkeypatch.setattr(op_mod.Op, "_fit", faulty)
    return op_mod


def test_fault_one_coefficient_left_at_zero(capsys, bench, monkeypatch):
    def forgetful(self, answer):
        theta = np.array(answer["theta"])
        theta[int(np.argmax(np.abs(theta[1:]))) + 1] = 0.0
        return dict(answer, theta=theta)

    planted(monkeypatch, bench, forgetful)
    line = last_line(capsys, bench, 0)
    assert line["correct"] is False
    assert all(line["compared"][k][0] > line["compared"][k][1] for k in ("theta_gap", "support_wrong", "objective_gap"))
    assert line["compared"]["lasso_path_wrong"] == [0, 0]


def test_fault_a_sweep_left_out(capsys, bench, monkeypatch):
    """29 sweeps: another iterate (coordinate descent on correlated columns is
    far from its fixed point after 30) and another count."""
    cfg_path = next(c["file"] for c in bench["configs"] if c["name"] == "lasso_f32")
    with open(cfg_path) as fh:
        cfg = json.load(fh)

    def short(self, answer):
        import heat_tpu as ht

        est = ht.regression.Lasso(lam=cfg["lam"], max_iter=cfg["max_iter"] - 1, tol=cfg["tol"]).fit(self.x, self.y)
        return dict(answer, theta=est.theta.larray, n_iter=est.n_iter)

    planted(monkeypatch, bench, short)
    line = last_line(capsys, bench, 0)
    assert line["correct"] is False and line["compared"]["lasso_path_wrong"] == [1, 0]
    assert line["compared"]["theta_gap"][0] > line["compared"]["theta_gap"][1]


def test_fault_a_fit_that_takes_residual_mode(capsys, bench, monkeypatch):
    """The mode is what the fit reports, not the shape rule recomputed: a
    program whose rule sends this operand to the residual sweep returns the
    same theta and fails ``lasso_path_wrong``."""
    op_mod = spec.Cell(CELL, bench).op_module()
    monkeypatch.setattr(op_mod.lasso, "_GRAM_MAX_ELEMENTS", 0)
    line = last_line(capsys, bench, 0)
    assert line["correct"] is False and line["compared"]["lasso_path_wrong"] == [1, 0]
    assert line["compared"]["theta_gap"][0] <= line["compared"]["theta_gap"][1]


def test_fault_a_theta_that_is_not_finite(capsys, bench, monkeypatch):
    """What upstream's step leaves on columns of mean square 2 after thirty
    sweeps (PR 39's cell: ``tests/test_lasso_f32.py`` pins the growth): NaN
    would lose every ``max()``, so such a theta reads infinite gaps and the
    run is not correct."""
    def overflowed(self, answer):
        return dict(answer, theta=np.full_like(np.asarray(answer["theta"]), np.nan))

    planted(monkeypatch, bench, overflowed)
    line = last_line(capsys, bench, 0, seed=7)
    assert line["correct"] is False and line["failed"] == 0
    assert line["compared"]["theta_gap"][0] == float("inf") == line["compared"]["objective_gap"][0]
    assert line["compared"]["lasso_path_wrong"] == [0, 0]


def test_a_program_that_cannot_say_how_it_multiplies_is_refused(bench, monkeypatch):
    """The parent commit's ``regression/lasso.py`` has no ``mxu_precision``:
    the run ends before any input is made, with an exit code and no line."""
    op_mod = spec.Cell(CELL, bench).op_module()
    monkeypatch.delattr(op_mod.lasso, "mxu_precision")
    with pytest.raises(SystemExit) as exc:
        op_mod.require_stated_multiplication(spec.Cell(CELL, bench).config)
    assert "does not offer it" in str(exc.value.code)


def test_design_is_seeded_off_centre_correlated_and_of_unit_mean_square():
    """The configuration's columns, letter for letter: (1 + Z B) / sqrt(2), of
    mean 0.707, variance 0.5 and mean square 1, neighbours correlated 0.9."""
    data = _CFG["data"]
    assert (data["loc"], data["rho"], data["noise"]) == (1.0, 0.9, 0.1)
    theta = np.zeros(16, np.float32)
    theta[[0, 3]] = 0.5, -1.0
    x, y = design.correlated_design(2147483999 + 2**31, (8192, 16), data["loc"], data["rho"], theta, data["noise"], 1024)
    again, _ = design.correlated_design(2147483999 + 2**31, (8192, 16), data["loc"], data["rho"], theta, data["noise"], 1024)
    other, _ = design.correlated_design(5, (8192, 16), data["loc"], data["rho"], theta, data["noise"], 1024)
    x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
    assert np.array_equal(x, np.asarray(again)) and not np.array_equal(x, np.asarray(other))
    assert np.array_equal(x[:, 0], np.ones(8192))
    assert np.abs(x[:, 1:].mean(axis=0) - np.sqrt(0.5)).max() < 0.05  # off-centre
    assert np.abs(x[:, 1:].var(axis=0) - 0.5).max() < 0.05
    assert abs((x[:, 1:] ** 2).mean() - 1.0) < 0.02 and np.abs((x[:, 1:] ** 2).mean(axis=0) - 1.0).max() < 0.08  # what upstream's step takes for granted
    corr = np.corrcoef(x[:, 1:], rowvar=False)
    assert np.abs(np.diagonal(corr, 1) - 0.9).max() < 0.02 and np.abs(np.diagonal(corr, 2) - 0.81).max() < 0.03
    assert np.abs((y[:, 0] - x @ theta.astype(np.float64)).std() - 0.1) < 0.01
    with pytest.raises(ValueError):
        design.correlated_design(5, (1000, 16), 1.0, 0.9, theta, 0.1, 512)


def test_roofline_counts_the_rows_read_once_and_the_gram():
    cfg = spec.Cell(CELL).config
    least = lasso_roofline.per_op(cfg, 1, V5E)
    assert least["bytes"] == 3_145_728 * 512 * 4 + 3_145_728 * 4 == 6_442_450_944 + 12_582_912
    assert least["flops"] == 2 * 3_145_728 * 512 * 512 + 2 * 30 * 512 * 512
    assert least["bound"] == "compute" and least["seconds"] == pytest.approx(8.371e-3, rel=1e-3)
    assert least["bytes"] / V5E["hbm_bytes_per_s"] == pytest.approx(7.882e-3, rel=1e-3)


def test_readers_read_nothing_on_a_program_without_the_counters():
    """The parent commit has no ``phase_lasso_*`` key: the three readers of
    counters read ``None``; ``lasso_roofline`` needs only the trace and reads
    ``None`` when no device time lies inside the op spans; ``cd_step_us``
    reads ``None`` too where the counters moved and the device ran no loop."""
    counters = {"fusion": {"forces": 3, "phase_forces": 3}}
    s, e = np.array([0.0, 1.0]), np.array([0.4, 1.4])
    no_loop = trace.Trace({0: (e, e + 0.1, ["%fusion.1 = f32[8,8] fusion(x)"] * 2)}, {"bench.op": (s, e + 0.2)})
    run_ = types.SimpleNamespace(
        counters={"before": counters, "after": counters}, trace=no_loop,
        config=spec.Cell(CELL).config, chips=1, device_kind="TPU v5 lite",
    )
    for name in ("cd_step_us", "lasso_syncs_per_op", "lasso_host_ms"):
        assert spec.load_module("layer_metrics", name + ".py").read(run_) is None
    moved = {"fusion": dict(counters["fusion"], phase_lasso_fits=2, phase_lasso_sweeps=60)}
    zero = {"fusion": dict(counters["fusion"], phase_lasso_fits=0, phase_lasso_sweeps=0)}
    run_.counters = {"before": zero, "after": moved}
    assert spec.load_module("layer_metrics", "cd_step_us.py").read(run_) is None
    no_loop.busy_in_ops_per_op = lambda: 0.0
    assert spec.load_module("layer_metrics", "lasso_roofline.py").read(run_) is None


def test_cd_step_reads_the_sweeps_loops_and_no_other():
    """Two fits, each a Gram (a loop over chunks of the rows), thirty loops of
    512 steps and, between the sweeps, a loop the reader was never told of (a
    convergence test on the device, say): the sweeps' loops alone, found by
    what they carry, per fit, over 30 x 512 steps."""
    reader = spec.load_module("layer_metrics", "cd_step_us.py")
    other = "%while.9 = (s32[]{:T(128)}, f32[]{:T(128)}, f32[512,1]{0,1:T(1,128)}) while(%tuple.7), condition=%c, body=%b"
    assert reader.is_sweep_loop(WHILE_512, 512) and reader.is_sweep_loop(WHILE, 64) and not reader.is_sweep_loop(WHILE, 512)
    assert not reader.is_sweep_loop(GRAM_WHILE, 512) and not reader.is_sweep_loop(other, 512)
    assert not reader.is_sweep_loop(WHILE_512.replace(" while(", " fusion("), 512)
    names, starts, ends = [], [], []
    for fit in range(2):
        t = 10.0 * fit
        names += [GRAM_WHILE, "%fusion.9 = f32[128,512] fusion(%X.1), kind=kOutput"]
        starts += [t, t + 0.5]
        ends += [t + 2.0, t + 1.0]
        for sweep in range(30):
            lo = t + 2.0 + 0.1 * sweep
            names += [WHILE_512, "%fusion.3 = f32[1,512] fusion(%g), kind=kLoop", other]  # the loop, a body operation nested in it, the stranger
            starts += [lo, lo + 0.01, lo + 0.06]
            ends += [lo + 0.0512, lo + 0.02, lo + 0.09]
    t = trace.Trace({0: (starts, ends, names)}, {"bench.op": ([0.0, 10.0], [6.0, 16.0])})
    before = {"fusion": {"phase_lasso_fits": 5, "phase_lasso_sweeps": 150}}
    after = {"fusion": {"phase_lasso_fits": 7, "phase_lasso_sweeps": 210}}
    run_ = types.SimpleNamespace(counters={"before": before, "after": after}, trace=t, config=spec.Cell(CELL).config, chips=1)
    assert reader.read(run_) == pytest.approx(100.0)  # 51.2 ms a loop of 512 steps


def test_the_configuration_states_what_the_cell_checks():
    cfg = spec.Cell(CELL).config
    assert (cfg["dtype"], cfg["multiplication"], cfg["accumulation"]) == ("float32",) * 3
    assert cfg["reduced"] == [] and cfg["features"] == 512 and cfg["rows"] == {"1": 3_145_728}
    assert (cfg["max_iter"], cfg["tol"], cfg["lam"], cfg["lasso_mode"]) == (30, -1.0, 0.1, "gram")
    assert cfg["rows"]["1"] * cfg["features"] * 4 == 6_442_450_944  # 40.3 % of 16e9 B
    data = cfg["data"]
    assert cfg["rows"]["1"] % data["block_rows"] == 0 and (data["loc"], data["rho"], data["noise"]) == (1.0, 0.9, 0.1)
    assert (data["intercept"], data["nonzero"], data["block_rows"]) == (0.5, 32, 32768) and "(1 + Z B) / sqrt(2)" in data["columns_are"]
    assert set(GAPS) | {"control_cast", "readings"} == set(cfg["check"]) and cfg["check"]["control_cast"] == "bfloat16"
    entry = next(c for c in spec.benchmark()["configs"] if c["name"] == "lasso_f32")
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200 and entry["reduced"] == cfg["reduced"]
    cell = next(w for w in spec.benchmark()["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "lasso_trials"
    traffic = spec.Cell(CELL).traffic
    assert (traffic["op"], traffic["warm_up_ops"], traffic["check_answers"], traffic["trace_seconds"]) == ("lasso_fit", 2, 2, 5.0)
