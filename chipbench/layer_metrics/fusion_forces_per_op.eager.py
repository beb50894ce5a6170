"""``fusion_forces_per_op`` for the cells that report ``eager_ops_per_s``."""

from chipbench import spec

read = spec.load_module("layer_metrics", "fusion_forces_per_op.py").read
