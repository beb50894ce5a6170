"""``read_copy_us`` for the cells that report ``eager_ops_per_s``: with
``read_ready_us.eager`` it makes up ``host_read_us.eager``."""

from chipbench import spec

read = spec.load_module("layer_metrics", "read_copy_us.py").read
