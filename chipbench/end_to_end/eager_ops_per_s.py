"""``ops_per_s`` of the host-bound traffic, under a name and a bound of its
own: its spread is host and runtime noise and must not set the device-bound
cells' bound."""

from chipbench import spec

read = spec.load_module("end_to_end", "ops_per_s.py").read
