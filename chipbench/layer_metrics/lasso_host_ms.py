"""Host time of one ``Lasso.fit`` outside its waits, milliseconds: the
``prepare``, ``gram``, ``dispatch`` and ``wrap`` phases of ``heat.lasso.fit``
(``sync`` and ``copy``, each sweep's wait and read, stay out) over the fits
counted. A program without the counters reads ``None``."""

from chipbench import spec

_phases = spec.load_module("layer_metrics", "_phases.py")
_mean_us = _phases.reader([f"phase_lasso_{p}_ns" for p in ("prepare", "gram", "dispatch", "wrap")], "phase_lasso_fits")


def read(run):
    us = _mean_us(run)
    return None if us is None else 1e-3 * us
