"""Mean of the program's ``heat.force.lookup`` phase (the quarantine and
program-cache lookup, where a miss builds the jit, and the memory gate),
microseconds per forced result (``phase_lookup_ns`` / ``phase_forces``)."""

from chipbench import spec

read = spec.load_module("layer_metrics", "_phases.py").reader(["phase_lookup_ns"])
