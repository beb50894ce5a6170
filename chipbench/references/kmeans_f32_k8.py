"""Plain reference for the ``kmeans_f32_k8`` configuration: Lloyd's algorithm
in straightforward ``jax.numpy`` float32, by direct differences.

One iteration takes the rows in blocks. For a block, the squared distance of
every row to every centre is ``sum((x - c)**2)`` over the features (no
quadratic expansion, no matrix product: nothing the MXU could round); the
label is the argmin, the inertia the sum of the minima, and the block's
per-cluster sums and counts are masked sums. The host adds the blocks'
sums, counts and inertia in float64 and divides; a cluster without members
keeps its centre. ``labels`` and ``inertia`` are those of the last
assignment step, the one whose members' means are the returned centres.

``jax.default_matmul_precision("highest")`` is set around the whole of it as
the guide asks of a float32 reference on a TPU, though no product here goes
to the MXU. It imports nothing of the program and is handed only the rows
and the initial centres.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK_ROWS = 1 << 16


def _block(n: int) -> int:
    return BLOCK_ROWS if n % BLOCK_ROWS == 0 else n


@functools.partial(jax.jit, static_argnames=("block",))
def _assign(x, centers, block: int):
    """One assignment step over all rows: per block the (k, f) sums of its
    members, the (k,) counts and the inertia; per row the label."""
    n, f = x.shape
    k = centers.shape[0]

    def one(start):
        xb = jax.lax.dynamic_slice_in_dim(x, start, block, axis=0)  # (block, f)
        diff = xb[:, None, :] - centers[None, :, :]  # (block, k, f)
        d2 = jnp.sum(diff * diff, axis=-1)  # (block, k)
        label = jnp.argmin(d2, axis=1).astype(jnp.int32)
        member = label[:, None] == jnp.arange(k, dtype=jnp.int32)[None, :]  # (block, k)
        sums = jnp.sum(jnp.where(member[:, :, None], xb[:, None, :], 0.0), axis=0)  # (k, f)
        return sums, jnp.sum(member, axis=0, dtype=jnp.int32), jnp.sum(jnp.min(d2, axis=1)), label

    sums, counts, inertia, labels = jax.lax.map(one, jnp.arange(0, n, block, dtype=jnp.int32))
    return sums, counts, inertia, labels.reshape(n)


def lloyd(x: jax.Array, centers, max_iter: int):
    """``max_iter`` Lloyd iterations on the float32 rows ``x`` from the (k, f)
    ``centers``. Returns the centres (float64 numpy), the labels (int32, left
    on the device) and the inertia (float) of the last assignment step."""
    block = _block(x.shape[0])
    centers = np.asarray(centers, np.float64)
    labels, inertia = None, float("nan")
    with jax.default_matmul_precision("highest"):
        for _ in range(max_iter):
            sums, counts, parts, labels = _assign(x, jnp.asarray(centers, jnp.float32), block)
            sums = np.asarray(sums, np.float64).sum(axis=0)
            counts = np.asarray(counts, np.float64).sum(axis=0)
            inertia = float(np.asarray(parts, np.float64).sum())
            filled = counts > 0
            centers = np.where(filled[:, None], sums / np.maximum(counts, 1.0)[:, None], centers)
    return centers, labels, inertia
