"""Mean of the program's ``heat.force.admit`` phase (the batch window, the
admission hook and the wait for the force lock), microseconds per forced
result (``phase_admit_ns`` / ``phase_forces``)."""

from chipbench import spec

read = spec.load_module("layer_metrics", "_phases.py").reader(["phase_admit_ns"])
