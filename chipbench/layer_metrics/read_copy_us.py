"""Mean of the program's ``heat.read.copy`` span (the device-to-host fetch of
a payload that is ready: ``.item()`` / ``device_get``), microseconds per read
(``phase_read_copy_ns`` / ``phase_reads``), in the cell that reports
``ops_per_s``. A program without the counter reads ``None``."""

from chipbench import spec

read = spec.load_module("layer_metrics", "_phases.py").reader(["phase_read_copy_ns"], "phase_reads")
