"""What of the benchmark's ``bench.force`` span no program span covers,
microseconds per forced result: mean ``bench.force`` less the five force
phases and ``heat.place`` less the reads spread over all forced results.
The benchmark waits for vector results itself (``block_until_ready``), so
their completion wait has to show here, with the Python between the spans."""

from chipbench import spec

_phases = spec.load_module("layer_metrics", "_phases.py")


def read(run):
    bench_force_s = run.trace.span_mean_s("bench.force")
    host = _phases.mean_us(run, _phases.HOST_NS, "phase_forces")
    reads = _phases.mean_us(run, ["phase_read_ns"], "phase_forces")
    if bench_force_s is None or host is None or reads is None:
        return None
    return 1e6 * bench_force_s - host - reads
