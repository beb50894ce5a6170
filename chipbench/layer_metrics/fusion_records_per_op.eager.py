"""DAG nodes the fusion engine's recorder made per op (delta of
``cache_stats()['records']``, counted in every run)."""

from chipbench import spec

_phases = spec.load_module("layer_metrics", "_phases.py")


def read(run):
    records = _phases.delta(run, "records")
    if records is None or not run.attempted:
        return None
    return records / run.attempted
