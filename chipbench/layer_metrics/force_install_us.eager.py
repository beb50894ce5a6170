"""Mean of the program's ``heat.force.install`` phase (installing the values on
the roots, the ledger tags and the notes), microseconds per forced result
(``phase_install_ns`` / ``phase_forces``)."""

from chipbench import spec

read = spec.load_module("layer_metrics", "_phases.py").reader(["phase_install_ns"])
