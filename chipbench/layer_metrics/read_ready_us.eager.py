"""Mean of the program's ``heat.read.ready`` span (the wait until the device
has made the forced payload, ``block_until_ready``, before the fetch),
microseconds per read (``phase_read_ready_ns`` / ``phase_reads``). A program
without the counter reads ``None``."""

from chipbench import spec

read = spec.load_module("layer_metrics", "_phases.py").reader(["phase_read_ready_ns"], "phase_reads")
