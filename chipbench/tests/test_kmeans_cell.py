"""The ``kmeans_f32_k8`` configuration and its cell at a CPU size (the tiny
stand-in of ``chipbench/conftest.py``): a traced run through ``run.main``
ends in a correct line with the three new per-layer metrics, the control
comes out not correct, a planted fault is caught, a program that cannot
multiply as the configuration states is refused at once, and the roofline
counts the committed configuration's bytes. The CPU profile has no device
plane, so the traced run is handed a trace whose one "device" is busy for
the length of each ``bench.fit`` span, and the v5e's peaks."""

import glob
import json
import os

import jax
import pytest

from chipbench import control, rooflines, run, spec, trace
from chipbench.rooflines import lloyd as lloyd_roofline
from chipbench.tests import tiny

CELL = "kmeans_fit_1c"
V5E = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
NEW_METRICS = {"lloyd_roofline", "fit_syncs_per_op", "fit_host_ms"}


@pytest.fixture()
def bench(tmp_path):
    return tiny.bench(tmp_path)


def host_spans_as_a_trace(directory):
    (path,) = glob.glob(os.path.join(directory, "plugins", "profile", "*", "*.xplane.pb"))
    spans = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        s, en = spans.setdefault(e.name, ([], []))
                        s.append(e.start_ns * 1e-9)
                        en.append((e.start_ns + e.duration_ns) * 1e-9)
    fits = spans["bench.fit"]
    return trace.Trace({0: (fits[0], fits[1], ["%lloyd = f32[] custom-call(x)"] * len(fits[0]))}, spans)


def last_line(capsys, bench, trace_on, seed=2147483999):
    run.main(["--workload", CELL, "--seed", str(seed), "--seconds", "1", "--trace", str(trace_on)],
             bench=bench, devices=jax.devices())
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_traced_rehearsal_is_correct_and_reports_the_new_metrics(capsys, bench, monkeypatch):
    monkeypatch.setattr(trace, "load", host_spans_as_a_trace)
    monkeypatch.setattr(rooflines, "peaks", lambda kind: V5E)
    line = last_line(capsys, bench, 1)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["compared"]) >= {"centers_gap", "inertia_gap", "labels_gap", "iters_short", "lloyd_path_wrong"}
    want = {m["name"] for m in spec.Cell(CELL, bench).per_layer}
    assert NEW_METRICS <= want == set(line["metrics"])
    assert "setup_compile_s" not in want
    assert line["metrics"]["fit_syncs_per_op"]["value"] == 5.0  # four chunks of the 30 iterations, and the inertia
    assert line["metrics"]["fit_host_ms"]["value"] > 0 and line["metrics"]["lloyd_roofline"]["value"] > 0
    assert any(name == "bench.fit" for name, _ in line["breakdown"]["idle_gaps"]) or line["breakdown"]["idle_gaps"]


def test_untraced_run_leaves_the_fit_counters_alone(capsys, bench):
    from heat_tpu.core import fusion

    before = fusion.cache_stats()
    line = last_line(capsys, bench, 0)
    after = fusion.cache_stats()
    assert line["correct"] is True
    assert set(line["metrics"]) == {"ops_per_s", "op_ms_p95", "setup_s"}
    assert all(after[key] == before[key] for key in after if key.startswith("phase_kmeans_"))


def test_control_is_not_correct(bench):
    out = control.control(CELL, 7, 2, bench=bench, devices=jax.devices())
    assert out["correct"] is False and out["control"] == "bfloat16"
    assert out["compared"]["centers_gap"][0] > out["compared"]["centers_gap"][1]


def test_fault_centres_of_one_cluster_altered(capsys, bench, monkeypatch):
    op_mod = spec.Cell(CELL, bench).op_module()
    honest = op_mod.Op._fit

    def altered(self, x, trial):
        answer = honest(self, x, trial)
        answer["centers"] = answer["centers"].at[0].multiply(1.01)
        return answer

    real = spec.load_module
    monkeypatch.setattr(spec, "load_module", lambda *parts: op_mod if parts[-1] == "kmeans_fit.py" else real(*parts))
    monkeypatch.setattr(op_mod.Op, "_fit", altered)
    line = last_line(capsys, bench, 0)
    assert line["correct"] is False and line["compared"]["centers_gap"][0] > line["compared"]["centers_gap"][1]
    assert line["compared"]["labels_gap"][0] <= line["compared"]["labels_gap"][1]


def test_a_program_without_float32_products_is_refused_at_once(capsys, bench, monkeypatch):
    from heat_tpu.ops import lloyd

    monkeypatch.delattr(lloyd, "mxu_precision")
    with pytest.raises(SystemExit) as exc:
        last_line(capsys, bench, 0)
    assert exc.value.code not in (0, None) and capsys.readouterr().out == ""


def test_roofline_counts_one_read_per_iteration_and_the_labels():
    cfg = spec.Cell(CELL).config
    least = lloyd_roofline.per_op(cfg, {}, 1, V5E)
    assert least["bytes"] == 30 * 2**26 * 64 + 2**26 * 4
    assert least["flops"] == 30 * 4 * 2**26 * 16 * 8 and least["bound"] == "hbm"
    assert least["seconds"] == pytest.approx((30 * 2**26 * 64 + 2**26 * 4) / 819e9)
    assert least["bytes"] / V5E["hbm_bytes_per_s"] > 25 * least["flops"] / V5E["bf16_flops_per_s"]


def test_the_configuration_states_what_the_cell_checks():
    cfg = spec.Cell(CELL).config
    assert (cfg["dtype"], cfg["multiplication"], cfg["accumulation"]) == ("float32",) * 3
    assert cfg["reduced"] == [] and cfg["check"]["centers_gap"] <= 1e-3 and cfg["check"]["control_cast"] == "bfloat16"
    entry = next(c for c in spec.benchmark()["configs"] if c["name"] == "kmeans_f32_k8")
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
