"""Mean of the program's ``heat.force.dispatch`` phase (the jitted call of the
fused program), microseconds per forced result (``phase_dispatch_ns`` /
``phase_forces``)."""

from chipbench import spec

read = spec.load_module("layer_metrics", "_phases.py").reader(["phase_dispatch_ns"])
