"""The readers of the read's two parts (ISSUE 37) on a made-up run: the mean
per read, ``None`` on a count that did not move or on a program without the
keys (the parent of the PR that brought them), that the two add up to
``host_read_us.eager`` less what lies between the spans, and the three
entries of ``BENCHMARK.json``."""

import types

import pytest

from chipbench import spec

# nanoseconds the made-up window added over 3 reads: the wait, the copy, the whole heat.read
GREW = {"phase_reads": 3, "phase_read_ns": 1_500_000, "phase_read_ready_ns": 600_000, "phase_read_copy_ns": 870_000}
MEANS_US = {"read_ready_us.eager": 200.0, "read_copy_us.eager": 290.0, "read_copy_us": 290.0}
CELLS = {"read_ready_us.eager": "moments_small_1c", "read_copy_us.eager": "moments_small_1c", "read_copy_us": "moments_scan_1c"}


def reader(name):
    return spec.load_module("layer_metrics", name + ".py").read


def made_up(grew=GREW, keys=tuple(GREW)):
    before = {k: 500 + i for i, k in enumerate(keys)}
    after = {k: v + grew.get(k, 0) for k, v in before.items()}
    return types.SimpleNamespace(counters={"before": {"fusion": before}, "after": {"fusion": after}})


@pytest.mark.parametrize("name", sorted(MEANS_US))
def test_mean_per_read(name):
    assert reader(name)(made_up()) == pytest.approx(MEANS_US[name])


@pytest.mark.parametrize("name", sorted(MEANS_US))
def test_none_when_no_read_was_counted(name):
    assert reader(name)(made_up(dict(GREW, phase_reads=0))) is None


@pytest.mark.parametrize("name", sorted(MEANS_US))
def test_none_on_a_program_without_the_keys(name):
    assert reader(name)(made_up(keys=("phase_reads", "phase_read_ns"))) is None


def test_the_two_parts_lie_inside_the_whole_read():
    run = made_up()
    parts = reader("read_ready_us.eager")(run) + reader("read_copy_us.eager")(run)
    whole = reader("host_read_us.eager")(run)
    assert whole == pytest.approx(500.0) and 0.0 <= whole - parts == pytest.approx(10.0)


@pytest.mark.parametrize("name", sorted(MEANS_US))
def test_the_entry(name):
    entries = {m["name"]: m for m in spec.benchmark()["per_layer"]}
    cell = spec.Cell(CELLS[name])
    assert entries[name]["workloads"] == [CELLS[name]]
    assert (entries[name]["unit"], entries[name]["better"], entries[name]["source"]) == ("us", "lower", "program_span")
    assert entries[name]["moves"] in {m["name"] for m in cell.end_to_end}
    assert callable(cell.reader("layer_metrics", name))
