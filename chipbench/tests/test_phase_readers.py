"""The phase readers on a made-up run: counters before and after the window
and a ``trace.Trace`` with ``bench.force`` spans. The arithmetic, ``None`` on
a count that did not move or a program without the counters, and that the
remainder, the phases and the reads add up to the mean ``bench.force``."""

import types

import pytest

from chipbench import spec, trace

PHASES = spec.load_module("layer_metrics", "_phases.py")
# nanoseconds the made-up window added, over 4 forced results, 2 of them read
GREW = {
    "phase_forces": 4, "phase_admit_ns": 8_000, "phase_walk_ns": 120_000, "phase_lookup_ns": 40_000,
    "phase_dispatch_ns": 600_000, "phase_install_ns": 60_000, "phase_places": 4, "phase_place_ns": 72_000,
    "phase_reads": 2, "phase_read_ns": 900_000, "records": 66, "forces": 4,
}
MEANS_US = {
    "force_admit_us.eager": 2.0, "force_walk_us.eager": 30.0, "force_lookup_us.eager": 10.0,
    "force_dispatch_us.eager": 150.0, "force_install_us.eager": 15.0, "place_us.eager": 18.0,
    "host_read_us.eager": 450.0, "force_host_us": 225.0,
}
NEW = sorted(MEANS_US) + ["force_unattributed_us.eager", "fusion_records_per_op.eager"]


def reader(name):
    return spec.load_module("layer_metrics", name + ".py").read


def made_up(grew=GREW, counters=True):
    before = {k: 1000 + i for i, k in enumerate(GREW)} if counters else {"forces": 7}
    after = {k: v + grew.get(k, 0) for k, v in before.items()}
    # two ops of two forced results each; bench.force 500, 900, 700, 1100 us
    starts = [0.0010, 0.0020, 0.0110, 0.0120]
    spans = {
        "bench.op": ([0.0, 0.010], [0.004, 0.014]),
        "bench.force": (starts, [s + d for s, d in zip(starts, (500e-6, 900e-6, 700e-6, 1100e-6))]),
    }
    return types.SimpleNamespace(
        counters={"before": {"fusion": before}, "after": {"fusion": after}},
        attempted=2,
        trace=trace.Trace({0: ((0.0015,), (0.0016,), ("%r = f32[] reduce(x)",))}, spans),
    )


@pytest.mark.parametrize("name", sorted(MEANS_US))
def test_mean_per_count(name):
    assert reader(name)(made_up()) == pytest.approx(MEANS_US[name])


def test_records_per_op():
    assert reader("fusion_records_per_op.eager")(made_up()) == pytest.approx(33.0)
    run = made_up()
    run.attempted = 0
    assert reader("fusion_records_per_op.eager")(run) is None


def test_remainder_phases_and_reads_add_up_to_bench_force():
    run = made_up()
    bench_force_us = 1e6 * run.trace.span_mean_s("bench.force")
    assert bench_force_us == pytest.approx(800.0)
    rest = reader("force_unattributed_us.eager")(run)
    reads_per_force = GREW["phase_read_ns"] * 1e-3 / GREW["phase_forces"]
    assert rest == pytest.approx(800.0 - 225.0 - 225.0)
    assert rest + reader("force_host_us")(run) + reads_per_force == pytest.approx(bench_force_us)
    five = sum(reader(f"force_{p}_us.eager")(run) for p in PHASES.FORCE_PHASES)
    assert five + reader("place_us.eager")(run) == pytest.approx(reader("force_host_us")(run))


@pytest.mark.parametrize("name", sorted(MEANS_US) + ["force_unattributed_us.eager"])
def test_none_when_the_count_did_not_move(name):
    still = dict(GREW, phase_forces=0, phase_places=0, phase_reads=0)
    assert reader(name)(made_up(still)) is None


@pytest.mark.parametrize("name", NEW)
def test_none_on_a_program_without_the_counters(name):
    assert reader(name)(made_up(counters=False)) is None


def test_every_new_metric_is_an_entry_with_a_reader():
    entries = {m["name"]: m for m in spec.benchmark()["per_layer"]}
    for name in NEW:
        assert callable(reader(name))
        want = ["moments_scan_1c"] if name == "force_host_us" else ["moments_small_1c"]
        assert entries[name]["workloads"] == want and entries[name]["better"] == "lower"
