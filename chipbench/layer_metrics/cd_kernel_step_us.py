"""Device time of one coordinate step inside the sweep kernel, microseconds:
the busiest device's time in the ``lasso_cd_sweep`` kernels (``ops/cd.py``:
one Pallas kernel a sweep, G held in VMEM) per fit over the steps of a fit,
``phase_lasso_sweeps`` per ``phase_lasso_fits`` times the configuration's
features. The ``XLA Ops`` line names an operation by its instruction: a
kernel is a ``custom-call`` named after it, ``%lasso_cd_sweep.<k> = ...
custom-call(``. The time is the union of those operations; each sweep's head
product and the loop over the sweeps stay out. A program without the
counters, or a trace without such an operation (the sweep as an XLA loop,
which ``cd_step_us`` reads), reads ``None``."""

import re

from chipbench import spec, trace

_phases = spec.load_module("layer_metrics", "_phases.py")
_KERNEL = re.compile(r"lasso_cd_sweep(\.\d+)?:custom-call")


def is_sweep_kernel(name: str) -> bool:
    """Whether the instruction ``name`` is a sweep kernel's custom call."""
    return bool(_KERNEL.fullmatch(trace.short_name(name)))


def read(run):
    fits, sweeps = _phases.delta(run, "phase_lasso_fits"), _phases.delta(run, "phase_lasso_sweeps")
    if not fits or not sweeps:
        return None
    starts, ends, names = run.trace.devices[run.trace.busiest]
    kernels = [i for i, name in enumerate(names) if is_sweep_kernel(name)]
    if not kernels:
        return None
    in_kernels = trace.union_length(starts[kernels], ends[kernels]) / run.trace.n_ops
    return 1e6 * in_kernels / ((sweeps / fits) * int(run.config["features"]))
