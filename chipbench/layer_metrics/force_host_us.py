"""Host time of the fusion engine and the forcing seam per forced result:
the five ``heat.force`` phases and ``heat.place``, microseconds."""

from chipbench import spec

_phases = spec.load_module("layer_metrics", "_phases.py")
read = _phases.reader(_phases.HOST_NS)
