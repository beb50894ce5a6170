"""Blocking host reads per lasso fit (one a sweep: the iterates' change):
growth of ``phase_lasso_syncs`` over growth of ``phase_lasso_fits`` in the
window. The program counts them while the profiler records; a program
without the counters reads ``None``."""

from chipbench import spec

_phases = spec.load_module("layer_metrics", "_phases.py")


def read(run):
    fits, syncs = _phases.delta(run, "phase_lasso_fits"), _phases.delta(run, "phase_lasso_syncs")
    if not fits or syncs is None:
        return None
    return syncs / fits
