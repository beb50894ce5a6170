"""Mean of the program's ``heat.read`` span (the blocking device-to-host
read of ``item()``/``numpy()``, the payload already forced), microseconds per
read (``phase_read_ns`` / ``phase_reads``)."""

from chipbench import spec

read = spec.load_module("layer_metrics", "_phases.py").reader(["phase_read_ns"], "phase_reads")
