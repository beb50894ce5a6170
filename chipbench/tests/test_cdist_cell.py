"""The ``cdist_f32`` configuration and its two cells at a CPU size: a traced
run through ``run.main`` ends in a correct line with the new per-layer
metrics, the control comes out not correct, a planted fault is caught (one
column tile left at zero), a program that cannot multiply as the
configuration states is refused at once, and the roofline counts the
committed configuration's bytes. The CPU profile has no device plane, so the
traced run is handed a trace whose devices are busy for the length of each
``bench.cdist`` span, one of them with a collective-permute inside it, and the
v5e's peaks.

The tiny sizes are set here, on import, because ``chipbench/conftest.py`` and
``tests/tiny.py`` are another PR's to edit: collected together with
``test_rehearsal.py`` and ``test_benchmark_json.py`` (``python3 -m pytest
chipbench/tests -q``) they are in place before any test runs."""

import glob
import json
import os

import jax
import numpy as np
import pytest

from chipbench import control, rooflines, run, spec, trace
from chipbench.rooflines import cdist as cdist_roofline
from chipbench.tests import tiny

tiny.TINY.setdefault("cdist_f32", {"rows": {"1": 96, "4": 192}})

CELLS = ["cdist_ring_4c", "cdist_50k_1c"]
V5E = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
NEW_METRICS = {"cdist_roofline", "cdist_host_ms"}


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
    assert {"heat.cdist", "heat.cdist.prepare", "heat.cdist.dispatch", "heat.cdist.place"} <= names
    s, e = (np.asarray(v) for v in spans["bench.cdist"])
    tile = ["%fusion.1 = f32[8,8] fusion(x), kind=kOutput"] * len(s)
    ring = ["%collective-permute-start = (f32[8,64]) collective-permute-start(y)"] * len(s)
    return trace.Trace({0: (s, e, tile), 1: (s, s + 0.25 * (e - s), ring)}, spans)


def last_line(capsys, bench, cell, trace_on, seed=2147483999):
    run.main(["--workload", cell, "--seed", str(seed), "--seconds", "1", "--trace", str(trace_on)],
             bench=bench, devices=jax.devices())
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_traced_rehearsal_is_correct_and_reports_the_new_metrics(capsys, bench, monkeypatch, cell):
    monkeypatch.setattr(trace, "load", host_spans_as_a_trace)
    monkeypatch.setattr(rooflines, "peaks", lambda kind: V5E)
    line = last_line(capsys, bench, cell, 1)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["compared"]) >= {"dist_gap", "diag_gap", "split_wrong"}
    want = {m["name"] for m in spec.Cell(cell, bench).per_layer}
    assert NEW_METRICS <= want and NEW_METRICS | {"compiles_in_window", "device_idle_pct"} <= set(line["metrics"])
    assert ("collective_ms_per_op" in want) == (cell == "cdist_ring_4c")
    assert line["metrics"]["cdist_host_ms"]["value"] > 0 and line["metrics"]["cdist_roofline"]["value"] > 0
    assert line["metrics"]["compiles_in_window"]["value"] == 0.0


def test_collective_reader_finds_the_ring_and_nothing_on_one_chip(capsys, bench, monkeypatch):
    read = spec.load_module("layer_metrics", "collective_ms_per_op.py").read
    s, e = np.array([0.0, 1.0]), np.array([0.4, 1.4])
    spans = {"bench.op": (s, e)}
    ring = ["%collective-permute-done = f32[8,64] collective-permute-done(t)", "%all-to-all.3 = f32[4] all-to-all(z)"]
    with_ring = trace.Trace({0: (s, s + 0.1, ring)}, spans)
    without = trace.Trace({0: (s, e, ["%fusion.1 = f32[8,8] fusion(x)"] * 2)}, spans)
    ns = lambda t: type("Run", (), {"trace": t})()  # noqa: E731
    assert read(ns(with_ring)) == pytest.approx(100.0) and read(ns(without)) is None


def test_untraced_run_leaves_the_cdist_counters_alone(capsys, bench):
    from heat_tpu.core import fusion

    before = fusion.cache_stats()
    line = last_line(capsys, bench, CELLS[0], 0)
    after = fusion.cache_stats()
    assert line["correct"] is True
    assert set(line["metrics"]) == {"ops_per_s", "op_ms_p95", "setup_s"}
    assert all(after[key] == before[key] for key in after if key.startswith("phase_cdist_"))


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(bench, cell):
    out = control.control(cell, 7, 2, bench=bench, devices=jax.devices())
    assert out["correct"] is False and out["control"] == "bfloat16"
    assert out["compared"]["dist_gap"][0] > out["compared"]["dist_gap"][1]
    assert out["compared"]["split_wrong"][0] == 0


def test_fault_one_column_tile_left_at_zero(capsys, bench, monkeypatch):
    """The tile program forgets a store: the first ``dynamic_update_slice``
    it traces (one visiting shard's tile) hands its buffer back zeroed."""
    from heat_tpu.spatial import distance

    honest = jax.lax.dynamic_update_slice
    seen = []

    def forgetful(out, part, at):
        seen.append(at)
        return out * 0.0 if len(seen) == 1 else honest(out, part, at)

    distance._tile_program.cache_clear()
    monkeypatch.setattr(distance.jax.lax, "dynamic_update_slice", forgetful)
    try:
        line = last_line(capsys, bench, CELLS[0], 0)
    finally:
        monkeypatch.undo()
        distance._tile_program.cache_clear()
    assert seen and line["correct"] is False
    assert line["compared"]["dist_gap"][0] > line["compared"]["dist_gap"][1]
    assert line["compared"]["split_wrong"][0] == 0


def test_a_program_without_float32_products_is_refused_at_once(capsys, bench, monkeypatch):
    from heat_tpu.spatial import distance

    monkeypatch.delattr(distance, "mxu_precision")
    with pytest.raises(SystemExit) as exc:
        last_line(capsys, bench, CELLS[0], 0)
    assert exc.value.code not in (0, None) and capsys.readouterr().out == ""


def test_roofline_counts_the_rows_of_the_result_held_here():
    cfg = spec.Cell(CELLS[0]).config
    four, one = cdist_roofline.per_op(cfg, 4, V5E), cdist_roofline.per_op(cfg, 1, V5E)
    assert four["bytes"] == 25_000 * 100_000 * 4 + 100_000 * 64 * 4
    assert one["bytes"] == 50_000 * 50_000 * 4 + 50_000 * 64 * 4
    assert four["flops"] == 2 * 25_000 * 100_000 * 64 and one["flops"] == 2 * 50_000 * 50_000 * 64
    assert four["bound"] == one["bound"] == "hbm"
    assert four["seconds"] == pytest.approx(10.0256e9 / 819e9) and 1 / four["seconds"] < 82


def test_the_configuration_states_what_the_cells_check():
    cfg = spec.Cell(CELLS[0]).config
    assert (cfg["dtype"], cfg["multiplication"], cfg["accumulation"]) == ("float32",) * 3
    assert cfg["reduced"] == [] and cfg["check"]["control_cast"] == "bfloat16" and cfg["quadratic_expansion"] is True
    assert cfg["rows"]["4"] ** 2 / 4 == cfg["rows"]["1"] ** 2  # upstream's weak-scaling rule
    assert cfg["check"]["dist_gap"] <= 1e-4 and cfg["check"]["diag_gap"] <= 1e-2
    entry = next(c for c in spec.benchmark()["configs"] if c["name"] == "cdist_f32")
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    cells = {w["name"]: w for w in spec.benchmark()["workloads"]}
    assert cells["cdist_ring_4c"]["chips"] == 4 and cells["cdist_50k_1c"]["chips"] == 1
