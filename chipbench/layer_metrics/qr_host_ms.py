"""Host time of one ``ht.linalg.qr`` call outside its wait, milliseconds: the
``prepare``, ``dispatch`` and ``wrap`` phases of ``heat.qr`` (its ``sync``
phase is the probe's blocking read, which waits for the device) over the
calls counted. A program without the counters reads ``None``."""

from chipbench import spec

_phases = spec.load_module("layer_metrics", "_phases.py")
_mean_us = _phases.reader([f"phase_qr_{p}_ns" for p in ("prepare", "dispatch", "wrap")], "phase_qr_calls")


def read(run):
    us = _mean_us(run)
    return None if us is None else 1e-3 * us
