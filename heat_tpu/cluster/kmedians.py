"""K-Medians clustering (reference: heat/cluster/kmedians.py).

Identical loop structure to KMeans but the centroid update is the
component-wise *median* of each cluster (reference kmedians.py:73-100) and
assignment uses the plain (non-squared) Euclidean metric.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Union

import jax
import jax.numpy as jnp

from ..core import types
from ..core.dndarray import DNDarray, _ensure_split
from ._kcluster import _KCluster
from .kmeans import _sq_dist

__all__ = ["KMedians"]


@partial(jax.jit, static_argnames=("k",))
def _median_step(data: jax.Array, centers: jax.Array, k: int):
    d2 = _sq_dist(data, centers)
    labels = jnp.argmin(d2, axis=1)

    def cluster_median(c):
        mask = labels == c
        vals = jnp.where(mask[:, None], data, jnp.nan)
        med = jnp.nanmedian(vals, axis=0)
        return jnp.where(jnp.any(mask), med, centers[c])

    new_centers = jax.vmap(cluster_median)(jnp.arange(k))
    inertia = jnp.sum(jnp.sqrt(jnp.take_along_axis(d2, labels[:, None], axis=1)))
    shift = jnp.sum((new_centers - centers) ** 2)
    return new_centers, labels, inertia, shift


@partial(jax.jit, static_argnames=("k", "n_steps"))
def _median_run(data: jax.Array, centers: jax.Array, k: int, n_steps: int):
    """``n_steps`` fused iterations in ONE XLA program: one dispatch per
    chunk instead of per step (``KMeans`` runs a whole fit as one
    program that checks convergence itself; no cell runs this estimator)."""

    def body(i, carry):
        centers, _, _, _ = carry
        return _median_step.__wrapped__(data, centers, k)

    # the first step seeds the carry with the exact output types
    first = _median_step.__wrapped__(data, centers, k)
    return jax.lax.fori_loop(1, n_steps, body, first)


class KMedians(_KCluster):
    """K-Medians clustering (reference kmedians.py:14-139)."""

    def __init__(
        self,
        n_clusters: int = 8,
        init: Union[str, DNDarray] = "random",
        max_iter: int = 300,
        tol: float = 1e-4,
        random_state: Optional[int] = None,
    ):
        if isinstance(init, str) and init in ("kmeans++", "k-means++"):
            init = "probability_based"
        super().__init__(
            metric=_sq_dist,  # module-level identity: kernels cache across instances
            n_clusters=n_clusters,
            init=init,
            max_iter=max_iter,
            tol=tol,
            random_state=random_state,
        )

    def fit(self, x: DNDarray) -> "KMedians":
        """Cluster ``x`` (reference kmedians.py:102-139)."""
        if not isinstance(x, DNDarray):
            raise ValueError(f"input needs to be a DNDarray, but was {type(x)}")
        if x.ndim != 2:
            raise ValueError(f"input needs to be 2D, but was {x.ndim}D")
        data = x.larray.astype(jnp.promote_types(x.dtype.jax_type(), jnp.float32))
        centers = self._initialize_cluster_centers(x)

        labels = inertia = None
        done = 0
        while done < self.max_iter:
            # fused chunks of up to 8 iterations per dispatch; convergence
            # checked at chunk boundaries
            chunk = min(8, self.max_iter - done)
            centers, labels, inertia, shift = _median_run(data, centers, self.n_clusters, chunk)
            done += chunk
            if float(shift) <= self.tol:
                break

        self._n_iter = done
        self._inertia = float(inertia) if inertia is not None else None
        self._cluster_centers = DNDarray(
            _ensure_split(centers, None, x.comm),
            tuple(centers.shape),
            types.canonical_heat_type(centers.dtype),
            None,
            x.device,
            x.comm,
        )
        self._labels = self._wrap_labels(labels, x)
        return self
