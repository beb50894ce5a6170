"""XLA label passes over the rows per fit (``ops/lloyd.py``'s epilogue, one
per fused Lloyd program before ISSUE 30, none since the kernel's last pass
writes the labels): growth of ``phase_kmeans_label_epilogues`` over growth of
``phase_kmeans_fits`` in the window. A program without the counter reads
``None``."""

from chipbench import spec

_phases = spec.load_module("layer_metrics", "_phases.py")


def read(run):
    fits, epilogues = _phases.delta(run, "phase_kmeans_fits"), _phases.delta(run, "phase_kmeans_label_epilogues")
    if not fits or epilogues is None:
        return None
    return epilogues / fits
