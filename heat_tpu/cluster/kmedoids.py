"""K-Medoids clustering (reference: heat/cluster/kmedoids.py).

As in the reference, the update computes the cluster median and then snaps it
to the nearest actual data point (reference kmedoids.py:73-105), so centroids
are always members of the dataset.
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

__all__ = ["KMedoids"]


@partial(jax.jit, static_argnames=("k",))
def _medoid_step(data: jax.Array, centers: jax.Array, k: int):
    d2 = _sq_dist(data, centers)
    labels = jnp.argmin(d2, axis=1)

    def cluster_medoid(c):
        mask = labels == c
        vals = jnp.where(mask[:, None], data, jnp.nan)
        med = jnp.nanmedian(vals, axis=0)
        # snap to the nearest member of the cluster
        dist_to_med = jnp.sum((data - med[None, :]) ** 2, axis=1)
        dist_to_med = jnp.where(mask, dist_to_med, jnp.inf)
        idx = jnp.argmin(dist_to_med)
        return jnp.where(jnp.any(mask), data[idx], centers[c])

    new_centers = jax.vmap(cluster_medoid)(jnp.arange(k))
    inertia = jnp.sum(jnp.sqrt(jnp.take_along_axis(d2, labels[:, None], axis=1)))
    shift = jnp.sum((new_centers - centers) ** 2)
    return new_centers, labels, inertia, shift


@partial(jax.jit, static_argnames=("k", "n_steps"))
def _medoid_run(data: jax.Array, centers: jax.Array, k: int, n_steps: int):
    """``n_steps`` fused iterations in ONE XLA program: one dispatch per
    chunk instead of per step (``KMeans`` runs a whole fit as one
    program that checks convergence itself; no cell runs this estimator)."""

    def body(i, carry):
        centers, _, _, _ = carry
        return _medoid_step.__wrapped__(data, centers, k)

    # the first step seeds the carry with the exact output types
    first = _medoid_step.__wrapped__(data, centers, k)
    return jax.lax.fori_loop(1, n_steps, body, first)


class KMedoids(_KCluster):
    """K-Medoids clustering (reference kmedoids.py:14-139)."""

    def __init__(
        self,
        n_clusters: int = 8,
        init: Union[str, DNDarray] = "random",
        max_iter: int = 300,
        random_state: Optional[int] = None,
    ):
        if isinstance(init, str) and init in ("kmeans++", "k-means++"):
            init = "probability_based"
        super().__init__(
            metric=_sq_dist,  # module-level identity: kernels cache across instances
            n_clusters=n_clusters,
            init=init,
            max_iter=max_iter,
            tol=0.0,
            random_state=random_state,
        )

    def fit(self, x: DNDarray) -> "KMedoids":
        """Cluster ``x`` (reference kmedoids.py:106-143)."""
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
            # checked at chunk boundaries. Medoids snap
            # to data points, so exact-zero shift is the fixed point.
            chunk = min(8, self.max_iter - done)
            centers, labels, inertia, shift = _medoid_run(data, centers, self.n_clusters, chunk)
            done += chunk
            if float(shift) == 0.0:
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
