"""Host time of one ``KMeans.fit`` outside its waits, milliseconds: the
``init``, ``prepare``, ``dispatch`` and ``wrap`` phases of ``heat.kmeans.fit``
(everything but ``sync``) over the fits counted."""

from chipbench import spec

_phases = spec.load_module("layer_metrics", "_phases.py")
_mean_us = _phases.reader([f"phase_kmeans_{p}_ns" for p in ("init", "prepare", "dispatch", "wrap")], "phase_kmeans_fits")


def read(run):
    us = _mean_us(run)
    return None if us is None else 1e-3 * us
