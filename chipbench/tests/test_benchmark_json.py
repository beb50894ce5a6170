"""``BENCHMARK.json`` against the contract's rules that a file can be held to,
and every name in it against the files it has to lead to."""

import os
import re

import pytest

from chipbench import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH = spec.benchmark()
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def one_line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"] and 1 <= BENCH["run_seconds"] <= 51
    assert all(one_line(w) for w in BENCH["command"]) and len(BENCH["command"]) <= 32
    assert os.path.getsize(os.path.join(spec.ROOT, "BENCHMARK.json")) <= 64 * 1024
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda e: e["name"])
def test_config_entry(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"]) and one_line(entry["source"]) and one_line(entry["why"])
    assert entry["file"] == f"chipbench/configs/{entry['name']}.json"
    assert os.path.exists(os.path.join(spec.ROOT, entry["file"]))
    assert os.path.exists(os.path.join(spec.HERE, "references", entry["name"] + ".py"))
    assert len(entry["reduced"]) <= 16 and all(NAME.match(k) for k in entry["reduced"])
    assert any(w["config"] == entry["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("entry", BENCH["workloads"], ids=lambda e: e["name"])
def test_cell_entry(entry):
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(entry["name"]) and NAME.match(entry["traffic"]) and one_line(entry["why"])
    assert entry["chips"] in (1, 4)
    cell = spec.Cell(entry["name"])
    assert os.path.exists(os.path.join(spec.HERE, "ops", cell.op_kind + ".py"))
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in e2e, (m["name"], "moves a metric this cell does not report")
        assert callable(cell.reader("layer_metrics", m["name"]))
    for m in cell.end_to_end:
        assert callable(cell.reader("end_to_end", m["name"]))


def test_cells_are_distinct_and_few_take_four_chips():
    cells = BENCH["workloads"]
    assert len({c["name"] for c in cells}) == len(cells) == len({(c["config"], c["traffic"]) for c in cells})
    assert sum(c["chips"] == 4 for c in cells) <= max(1, len(cells) // 4)


@pytest.mark.parametrize("metric", BENCH["end_to_end"] + BENCH["per_layer"], ids=lambda m: m["name"])
def test_metric_entry(metric):
    e2e = metric in BENCH["end_to_end"]
    want = {"name", "unit", "better", "source"} | ({"bound"} if e2e else {"layer", "moves"})
    assert want <= set(metric) <= want | {"workloads"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"]) and metric["better"] in ("lower", "higher")
    assert metric["source"] in (SOURCES if not e2e else {"host_clock", "device_trace"})
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    if e2e:
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        assert one_line(metric["layer"]) and metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
        if metric["name"].endswith("_roofline") or "mfu" in metric["name"]:
            assert metric["unit"] == "%"


def test_names_are_unique_and_setup_is_there():
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(set(names)) == len(names) and "setup_s" in names
