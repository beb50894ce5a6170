"""Mean of the program's ``heat.force.walk`` phase (the DAG walk, the batching of
other live roots and the signature), microseconds per forced result
(``phase_walk_ns`` / ``phase_forces``)."""

from chipbench import spec

read = spec.load_module("layer_metrics", "_phases.py").reader(["phase_walk_ns"])
