"""Mean of the program's ``heat.place`` span (``DNDarray.parray`` after the
force: errstate check, placement under the split, ledger tag), microseconds
per placed result (``phase_place_ns`` / ``phase_places``)."""

from chipbench import spec

read = spec.load_module("layer_metrics", "_phases.py").reader(["phase_place_ns"], "phase_places")
