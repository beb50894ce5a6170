"""TPU pallas kernels for the hot ops.

The reference framework's compute kernels live in libtorch (reference
SURVEY.md vital stats: no native code in-repo, all kernels delegated). The
TPU-native analog is XLA for everything fusion can handle, plus hand-written
pallas kernels where the schedule matters. Current contents:

- :mod:`~heat_tpu.ops.flash` — flash attention with causal tile skipping
  (consumed by ``nn.attention`` on TPU).
- :mod:`~heat_tpu.ops.pairwise` — fused pairwise-distance tiles, an
  exact-numerics alternative to the broadcast expression with a guaranteed
  O(n·m + (n+m)·f) HBM footprint (see its docstring for the measured
  comparison against XLA's autofusion, which the default ``spatial.cdist``
  path uses).
- :mod:`~heat_tpu.ops.lloyd` — single-pass fused Lloyd iteration for
  k-means (single-device and shard_map forms; the benchmark cell
  ``kmeans_fit_1c`` measures it through ``KMeans.fit``).
"""

from . import flash, lloyd, pairwise
from .flash import flash_attention_tpu
from .lloyd import fused_lloyd_run, fused_lloyd_run_sharded
from .pairwise import pairwise_distance

__all__ = [
    "flash",
    "lloyd",
    "pairwise",
    "pairwise_distance",
    "flash_attention_tpu",
    "fused_lloyd_run",
    "fused_lloyd_run_sharded",
]
