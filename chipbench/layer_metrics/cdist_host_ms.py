"""Host time of one ``ht.spatial.cdist`` call outside its wait, milliseconds:
the ``prepare``, ``dispatch`` and ``place`` phases of ``heat.cdist`` (the call
returns before the device is done; the benchmark waits outside it) over the
calls counted. A program without the counters reads ``None``."""

from chipbench import spec

_phases = spec.load_module("layer_metrics", "_phases.py")
_mean_us = _phases.reader([f"phase_cdist_{p}_ns" for p in ("prepare", "dispatch", "place")], "phase_cdist_calls")


def read(run):
    us = _mean_us(run)
    return None if us is None else 1e-3 * us
