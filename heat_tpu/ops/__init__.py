"""TPU pallas kernels for the hot ops.

The reference framework's compute kernels live in libtorch (reference
SURVEY.md vital stats: no native code in-repo, all kernels delegated). The
TPU-native analog is XLA for everything fusion can handle, plus hand-written
pallas kernels where the schedule matters. Current contents:

- :mod:`~heat_tpu.ops.lloyd` — single-pass fused Lloyd iteration for
  k-means (single-device and shard_map forms; the benchmark cell
  ``kmeans_fit_1c`` measures it through ``KMeans.fit``).
- :mod:`~heat_tpu.ops.symv` — the product of a symmetric float32 matrix with
  a vector from one read of its upper block triangle (each Lanczos step's
  product with the Laplacian on one TPU chip: ``core/linalg/solver.py``).
- :mod:`~heat_tpu.ops.cd` — one coordinate-descent sweep of the lasso as one
  kernel, the Gram matrix held in VMEM (each sweep of ``Lasso.fit``'s Gram
  mode on TPUs: ``regression/lasso.py``).

Each module's ``takes`` decides from the operand (its devices, shape, dtype,
split) whether its kernel runs; no estimator option picks a kernel. Off the
TPU a kernel runs only in Pallas' interpreter, where a test pretends
``takes``.
"""

from . import cd, lloyd, symv
from .lloyd import fused_lloyd_run, fused_lloyd_run_sharded

__all__ = [
    "lloyd",
    "symv",
    "cd",
    "fused_lloyd_run",
    "fused_lloyd_run_sharded",
]
