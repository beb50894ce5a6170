"""95th percentile of every trial's wall time in the window (the cell's
end-to-end metric is a rate; the tail stands beside it here)."""

from chipbench import spec

read = spec.load_module("end_to_end", "op_ms_p95.py").read
