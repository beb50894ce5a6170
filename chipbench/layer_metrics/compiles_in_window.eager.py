"""``compiles_in_window`` for the cells that report ``eager_ops_per_s``."""

from chipbench import spec

read = spec.load_module("layer_metrics", "compiles_in_window.py").read
