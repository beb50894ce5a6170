"""Spectral clustering (reference: heat/cluster/spectral.py).

Pipeline identical to the reference (spectral.py:103-189): RBF/eNeighbour
affinity → normalized symmetric Laplacian → Lanczos eigen-embedding → KMeans
in the embedding space. The Lanczos dots ride sharded reductions; the small
(m×m) tridiagonal eigenproblem is solved replicated, as in the reference.

A fit holds one n×n buffer at a time: the affinity, which the Laplacian is
written over (``graph/laplacian.py``), and which Lanczos reads. Every product
of the embedding multiplies float32 in float32 (``ops/mxu.py``'s rule, from
the dtype alone: ``solver._matvec`` and :func:`_embed`).

While ``telemetry.tracing()`` a fit is a ``heat.spectral.fit`` span (stats
``n``, ``f``, ``k``, ``m``, ``p``, and ``product``: ``upper`` where each
Lanczos product read only L's upper block triangle, ``ops/symv.py``, else
``full``) whose children lie side by side:
``.affinity`` (the similarity's call; ``heat.cdist`` nests in it),
``.laplacian`` (the Laplacian program's call), ``.lanczos`` (the start vector
and the Lanczos program's call), ``.embed`` (the tridiagonal's eigenpairs and
the embedding's product; with ``n_clusters=None`` the read of the Ritz values
that picks it), ``.sync`` (the wait until the embedding is ready: the device
time of everything above), ``.assign`` (``KMeans.fit``, whose
``heat.kmeans.fit`` nests in it); the same intervals add to
``fusion.cache_stats()``'s ``phase_spectral_*`` keys, with the fit's
blocking host reads (``syncs``: the k-means initialisation's and its one read,
the eigengap's), its products with the Laplacian (``lanczos_steps``) and
those of them that read only its upper block triangle (``upper_steps``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..core import fusion, telemetry, types
from ..core.base import BaseEstimator, ClusteringMixin
from ..core.dndarray import DNDarray, _ensure_split
from ..core.linalg import solver
from ..graph import Laplacian
from ..ops.mxu import mxu_precision
from ..spatial import distance
from .kmeans import KMeans

__all__ = ["Spectral"]


def _embed(V: jax.Array, evecs: jax.Array, k: int) -> jax.Array:
    """The Ritz vectors of the ``k`` smallest Ritz values: V (n, m) times the
    tridiagonal's first ``k`` eigenvectors, float32 in float32."""
    return jnp.matmul(V, evecs[:, :k].astype(V.dtype), precision=mxu_precision(V.dtype))


class Spectral(ClusteringMixin, BaseEstimator):
    """Spectral clustering on the graph Laplacian's eigen-embedding
    (reference spectral.py:14-102 for the constructor contract). After a fit,
    ``eigenvalues_`` holds the ``n_lanczos`` Ritz values in ascending order
    and ``embedding_`` the rows the clusters were found in, the Ritz vectors
    of the ``n_clusters`` smallest (n, n_clusters), split as the data."""

    def __init__(
        self,
        n_clusters: Optional[int] = None,
        gamma: float = 1.0,
        metric: str = "rbf",
        laplacian: str = "fully_connected",
        threshold: float = 1.0,
        boundary: str = "upper",
        n_lanczos: int = 300,
        assign_labels: str = "kmeans",
        **params,
    ):
        self.n_clusters = n_clusters
        self.gamma = gamma
        self.metric = metric
        self.laplacian = laplacian
        self.threshold = threshold
        self.boundary = boundary
        self.n_lanczos = n_lanczos
        self.assign_labels = assign_labels

        if metric == "rbf":
            sigma = jnp.sqrt(1.0 / (2.0 * gamma))
            sim = functools.partial(distance.rbf, sigma=float(sigma), quadratic_expansion=True)
        elif metric == "euclidean":
            sim = functools.partial(distance.cdist, quadratic_expansion=True)
        else:
            raise NotImplementedError(f"Metric {metric} is currently not implemented")
        if laplacian == "fully_connected":
            self._laplacian = Laplacian(sim, definition="norm_sym", mode="fully_connected")
        elif laplacian == "eNeighbour":
            self._laplacian = Laplacian(
                sim,
                definition="norm_sym",
                mode="eNeighbour",
                threshold_key=boundary,
                threshold_value=threshold,
            )
        else:
            raise NotImplementedError(f"Laplacian {laplacian} is currently not implemented")
        if assign_labels != "kmeans":
            raise NotImplementedError(
                f"Assignment-method {assign_labels} is currently not implemented"
            )
        self._cluster = KMeans(
            n_clusters=n_clusters if n_clusters is not None else 8, **params
        )
        self._labels = None
        self._upper = False  # whether the last embedding's Lanczos read L's upper block triangle only
        self.eigenvalues_ = None
        self.embedding_ = None

    @property
    def labels_(self) -> DNDarray:
        return self._labels

    def _spectral_embedding(self, x: DNDarray, mark=telemetry.no_phase):
        """Lanczos eigen-embedding of the Laplacian (reference
        spectral.py:103-140): the Ritz values (ascending, on the device) and
        the Ritz vectors V @ evecs of the tridiagonal. Only the first
        ``n_clusters`` columns are formed; with ``n_clusters=None`` the Ritz
        values are read here to pick it (reference spectral.py:152-157)."""
        L = self._laplacian._construct(x, mark)
        mark("lanczos")
        m = min(self.n_lanczos, L.shape[0])
        self._upper = solver._reads_upper(L, jnp.float32)  # lanczos' start vector is float32
        V, T = solver.lanczos(L, m)
        del L
        mark("embed")
        evals, evecs = jnp.linalg.eigh(T.larray)  # ascending
        if self.n_clusters is None:
            import numpy as np

            self.n_clusters = int(np.argmax(np.diff(np.asarray(evals))) + 1)
            self._cluster.n_clusters = self.n_clusters
        emb = _ensure_split(_embed(V.larray, evecs, self.n_clusters), x.split, x.comm)
        embedding = DNDarray(
            emb, tuple(emb.shape), types.canonical_heat_type(emb.dtype), x.split, x.device, x.comm
        )
        return evals, embedding

    def fit(self, x: DNDarray) -> "Spectral":
        """Embed and cluster (reference spectral.py:141-170)."""
        if not isinstance(x, DNDarray):
            raise ValueError(f"input needs to be a DNDarray, but was {type(x)}")
        if x.split is not None and x.split != 0:
            raise NotImplementedError("Not implemented for other splitting-axes")
        if not telemetry.tracing():
            self._fit(x, telemetry.no_phase)
            return self
        m = min(self.n_lanczos, int(x.shape[0]))
        ph = telemetry.Phases(
            "heat.spectral.fit", n=int(x.shape[0]), f=int(x.shape[1]),
            k=self.n_clusters or 0, m=m, p=x.comm.size,
        )
        try:
            syncs = self._fit(x, ph.phase)
            ph.note(product="upper" if self._upper else "full")
        finally:
            ph.close()
        fusion.note_phases("spectral", ph.ns, fits=1, syncs=syncs, lanczos_steps=m, upper_steps=m * self._upper)
        return self

    def _fit(self, x: DNDarray, mark) -> int:
        """:meth:`fit` past its checks. ``mark(name)`` opens the fit's next
        phase (``telemetry.Phases.phase``; nothing when the fit is not
        traced). Returns the fit's blocking host reads."""
        picks = self.n_clusters is None
        evals, self.embedding_ = self._spectral_embedding(x, mark)
        evals = _ensure_split(evals, None, x.comm)
        self.eigenvalues_ = DNDarray(
            evals, tuple(evals.shape), types.canonical_heat_type(evals.dtype), None, x.device, x.comm
        )
        if mark is not telemetry.no_phase:
            mark("sync")
            jax.block_until_ready(self.embedding_.larray)
        mark("assign")
        self._cluster.fit(self.embedding_.balance_())
        self._labels = self._cluster.labels_
        return int(picks) + self._cluster._init_reads + 1  # the eigengap's, the init's, the Lloyd fit's one

    def predict(self, x: DNDarray) -> DNDarray:
        """Labels via embedding + trained KMeans (reference spectral.py:171-189)."""
        if not isinstance(x, DNDarray):
            raise ValueError(f"input needs to be a DNDarray, but was {type(x)}")
        _, eigenvectors = self._spectral_embedding(x)
        return self._cluster.predict(eigenvectors)
