"""A tiny CPU rehearsal of every cell (4 virtual devices, sizes set here, in
the tiny configurations), the last line's schema, the control,
and the faults that ``correct`` has to catch. The look for a chip is skipped
(``devices=`` is handed in); the rest of a run is driven as the benchmark
drives it."""

import json

import jax
import pytest

from chipbench import control, run, spec
from chipbench.tests import tiny

CELLS = [w["name"] for w in spec.benchmark()["workloads"]]


def last_line(capsys, cell, bench, seed=2147483999, seconds="1"):
    run.main(["--workload", cell, "--seed", str(seed), "--seconds", seconds, "--trace", "0"],
             bench=bench, devices=jax.devices())
    captured = capsys.readouterr()
    return json.loads(captured.out.strip().splitlines()[-1]), captured.err


@pytest.fixture()
def bench(tmp_path):
    return tiny.bench(tmp_path)


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_and_last_line(capsys, bench, cell):
    line, err = last_line(capsys, cell, bench)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    want = {m["name"] for m in spec.Cell(cell, bench).end_to_end}
    assert set(line["metrics"]) == want and "setup_s" in want
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and isinstance(m["value"], float)
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert line["device"]["count"] == 4
    # every number compared stands beside its limit, on stderr's last lines too
    tail = err.strip().splitlines()[-len(line["compared"]):]
    assert all(t.startswith("compared ") and " limit " in t for t in tail)
    assert all(len(pair) == 2 for pair in line["compared"].values())


def test_no_chip_no_line(capsys):
    with pytest.raises(SystemExit) as exc:
        run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert exc.value.code == run.EXIT_NO_CHIP and capsys.readouterr().out == ""


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(bench, cell):
    out = control.control(cell, 7, 2, bench=bench, devices=jax.devices())
    assert out["correct"] is False
    assert any(v > lim for v, lim in out["compared"].values())


def test_fault_answer_altered_moments(capsys, bench, monkeypatch):
    op_mod = spec.Cell("moments_scan_1c", bench).op_module()
    honest = op_mod.Op._trial

    def altered(self, x):
        answer = honest(self, x)
        answer["std_0"] = answer["std_0"] * (1 + 1e-4)
        return answer

    monkeypatch.setattr(spec, "load_module", _serving(op_mod, "moments_trial.py", spec.load_module))
    monkeypatch.setattr(op_mod.Op, "_trial", altered)
    line, _ = last_line(capsys, "moments_scan_1c", bench)
    assert line["correct"] is False and line["compared"]["std_gap"][0] > line["compared"]["std_gap"][1]


@pytest.mark.parametrize("cell", CELLS)
def test_fault_half_of_the_rows_left_out(capsys, bench, monkeypatch, cell):
    """``mean`` and ``std`` taken over the first half of the rows only (axis
    None and 0, where the result keeps its shape)."""
    import heat_tpu as ht

    def over_half(fn):
        return lambda x, axis=None: fn(x if axis == 1 else x[: x.shape[0] // 2], axis=axis)

    monkeypatch.setattr(ht, "mean", over_half(ht.mean))
    monkeypatch.setattr(ht, "std", over_half(ht.std))
    line, _ = last_line(capsys, cell, bench)
    assert line["correct"] is False
    assert all(line["compared"][k][0] > line["compared"][k][1] for k in ("mean_gap", "std_gap"))


def test_fault_op_raises_counts_as_failed(capsys, bench, monkeypatch):
    op_mod = spec.Cell("moments_small_1c", bench).op_module()
    honest = op_mod.Op.run

    def flaky(self, trial):
        if trial == 3:
            raise RuntimeError("planted")
        return honest(self, trial)

    monkeypatch.setattr(spec, "load_module", _serving(op_mod, "moments_trial.py", spec.load_module))
    monkeypatch.setattr(op_mod.Op, "run", flaky)
    line, _ = last_line(capsys, "moments_small_1c", bench)
    assert line["failed"] == 1 and line["correct"] is False


def _serving(module, filename, real):
    """``spec.load_module`` that hands back the patched op-kind module."""
    return lambda *parts: module if parts[-1] == filename else real(*parts)
