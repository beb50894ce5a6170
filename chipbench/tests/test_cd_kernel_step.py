"""``cd_kernel_step_us``, the device time of one coordinate step inside the
lasso's sweep kernel (``ops/cd.py``), on made-up traces: the kernels' custom
calls alone, per fit, over the steps of a fit; ``None`` on a program without
the counters or a trace without the kernel (the sweep as an XLA loop); and its
entry in ``BENCHMARK.json``."""

import types

import pytest

from chipbench import spec, trace

CELL = "lasso_1c"
KERNEL = ('%lasso_cd_sweep.5 = f32[4,128]{1,0:T(4,128)S(1)} custom-call(%get-tuple-element.205, %get-tuple-element.203, '
          '%subtract_bitcast_fusion.2, %bitcast.17), custom_call_target="tpu_custom_call"')
HEAD = ('%multiply_reduce_fusion.4 = f32[512]{0:T(512)S(1)} fusion(%get-tuple-element.201, %bitcast.16), kind=kLoop, '
        'metadata={op_name="jit(lasso_descent)/while/body/dot_general"}')
SWEEPS = ("%while.2 = (s32[]{:T(128)}, f32[512,1]{0,1:T(1,128)S(1)}, pred[]{:T(512)}, f32[]{:T(128)}) "
          "while(%tuple.7), condition=%c, body=%b")
GRAM = "%while.3 = (s32[]{:T(128)}, f32[512,512]{1,0:T(8,128)S(1)}) while(%tuple.32), condition=%c, body=%b"
LOOP = ("%while.5 = (s32[]{:T(128)}, f32[512]{0:T(512)S(1)}, f32[512,1]{0,1:T(1,128)S(1)}, f32[512,512]{1,0:T(8,128)}) "
        "while(%tuple.41), condition=%c, body=%b")


def reader():
    return spec.load_module("layer_metrics", "cd_kernel_step_us.py")


def run_of(names, starts, ends, ops, grew=True):
    before = {"phase_lasso_fits": 5, "phase_lasso_sweeps": 150}
    after = {"phase_lasso_fits": 7, "phase_lasso_sweeps": 210} if grew else before
    return types.SimpleNamespace(
        counters={"before": {"fusion": before}, "after": {"fusion": after}},
        trace=trace.Trace({0: (starts, ends, names)}, {"bench.op": ops}),
        config=spec.Cell(CELL).config, chips=1,
    )


def two_fits(kernel=KERNEL):
    """Two fits, each a Gram, then the loop over 30 sweeps, each a head
    product and a kernel of 0.1024 ms (0.2 us for each of 512 steps)."""
    names, starts, ends = [], [], []
    for fit in range(2):
        t = 10.0 * fit
        names += [GRAM, SWEEPS]
        starts += [t, t + 2.0]
        ends += [t + 2.0, t + 2.0 + 30 * 0.001]
        for sweep in range(30):
            lo = t + 2.0 + 0.001 * sweep
            names += [HEAD, kernel]
            starts += [lo, lo + 0.0002]
            ends += [lo + 0.0002, lo + 0.0002 + 0.1024e-3]
    return names, starts, ends, ([0.0, 10.0], [6.0, 16.0])


def test_names_the_kernel_and_nothing_else():
    is_kernel = reader().is_sweep_kernel
    assert is_kernel(KERNEL) and is_kernel(KERNEL.replace("lasso_cd_sweep.5 ", "lasso_cd_sweep "))
    assert not any(is_kernel(name) for name in (HEAD, SWEEPS, GRAM, LOOP))
    assert not is_kernel(KERNEL.replace("lasso_cd_sweep.5", "lasso_cd_sweep_head.5"))
    assert not is_kernel(KERNEL.replace(" custom-call(", " fusion("))


def test_reads_the_kernels_per_fit_over_the_steps_of_a_fit():
    """30 sweeps of 512 steps a fit, 0.1024 ms a kernel: 0.2 us a step; the
    Gram, the loop over the sweeps and the head products stay out."""
    assert reader().read(run_of(*two_fits())) == pytest.approx(0.2)


def test_reads_none_without_the_kernel_or_the_counters():
    """The parent's program (the sweep as an XLA loop) and a program without
    the counters read ``None``."""
    assert reader().read(run_of(*two_fits(kernel=LOOP))) is None
    assert reader().read(run_of(*two_fits(), grew=False)) is None
    bare = run_of(*two_fits())
    bare.counters = {"before": {"fusion": {"forces": 1}}, "after": {"fusion": {"forces": 1}}}
    assert reader().read(bare) is None


def test_entry_moves_ops_per_s_in_the_lasso_cell():
    (entry,) = [m for m in spec.benchmark()["per_layer"] if m["name"] == "cd_kernel_step_us"]
    assert entry == {"name": "cd_kernel_step_us", "unit": "us", "better": "lower", "source": "device_trace",
                     "layer": "kernels (Lasso's sweep kernel, ops/cd.py)", "moves": "ops_per_s", "workloads": [CELL]}
    assert callable(reader().read)
