"""``device_idle_pct`` for the cells that report ``eager_ops_per_s``."""

from chipbench import spec

read = spec.load_module("layer_metrics", "device_idle_pct.py").read
