"""Pairwise distance computation.

TPU-native re-design of reference heat/spatial/distance.py. The reference's
``_dist`` engine rotates the smaller operand's shards around an MPI ring:
each iteration sends the stationary shard to ``(rank+i) % size``, computes one
tile, and, for Y = X, exploits symmetry to halve the iteration count by
sending finished tiles to their transpose owners (distance.py:265-369
symmetric, :429-487 general). Here there is ONE tile program
(:func:`_tile_program`) for every row-split pair of operands on every mesh,
one device included: the stationary row block computes all p column tiles of
its rows, and only operand shards travel (``lax.ppermute``, shift 1, p - 1
times; none at p = 1). No collective carries anything of a result tile's
size. Upstream's symmetry halving pays on MPI + CPU, where a tile is dear and
a message cheap; on a TPU mesh a (n/p, n/p) float32 tile costs tens of
milliseconds over ICI and well under a millisecond to compute again for every
f below several thousand, so Y = X takes the same program.

Memory on a device is its rows of the result plus at most one column chunk of
a tile: the result buffer is born uninitialised (``lax.empty``) and every tile
is written into it in place, in column chunks of at most ``_CHUNK_BYTES``
(:func:`_write_tile`).

When one operand is replicated (reference distance.py:422-427) no ring is
needed: a single sharded jnp expression compiles to the local metric kernel.

While ``telemetry.tracing()`` a call is a ``heat.cdist`` span (stats ``mode``
= ``ring`` | ``replicated``, ``n``, ``m``, ``f``, ``p``, ``metric``) whose
children lie side by side: ``.prepare`` (promotion, padding, placing the
operands), ``.dispatch`` (the program's call), ``.place`` (slice,
``_ensure_split``, the ``DNDarray``); the same intervals add to
``fusion.cache_stats()``'s ``phase_cdist_*`` keys.
"""

from __future__ import annotations

from typing import Callable, Optional

import functools

import jax
import jax.numpy as jnp

from ..core import fusion, sanitation, telemetry, types
from ..core.communication import ppermute as _ppermute
from ..core.dndarray import DNDarray, _ensure_split
from ..ops.mxu import matmul as _matmul, mxu_precision  # noqa: F401  (mxu_precision: the rule this module multiplies by, for whoever asks)

__all__ = ["cdist", "manhattan", "rbf"]


# ----------------------------------------------------------------------------
# local metric kernels (reference distance.py:16-134)
# ----------------------------------------------------------------------------
def _euclidian(x: jax.Array, y: jax.Array) -> jax.Array:
    """Direct pairwise Euclidean distance (reference distance.py:16-37)."""
    diff = x[:, None, :] - y[None, :, :]
    return jnp.sqrt(jnp.sum(diff * diff, axis=-1))


def _sq_euclidian_fast(x: jax.Array, y: jax.Array) -> jax.Array:
    """Squared pairwise distance via quadratic expansion: |x|² + |y|² − 2x·yᵀ
    — one MXU matmul instead of an O(nmf) broadcast, the TPU fast path.
    float32 (or wider) rows multiply in float32 (``ops/mxu.py``'s rule, from
    the dtype alone): at the MXU's default the product is taken on
    bfloat16-rounded operands and the cancellation leaves distances 1e-2 off.
    Shared by cdist, rbf and the k-clustering assignment kernels."""
    xn = jnp.sum(x * x, axis=1, keepdims=True)
    yn = jnp.sum(y * y, axis=1, keepdims=True)
    return jnp.maximum(xn + yn.T - 2.0 * _matmul(x, y.T), 0.0)


def _euclidian_fast(x: jax.Array, y: jax.Array) -> jax.Array:
    """Quadratic-expansion Euclidean distance (reference distance.py:40-60)."""
    return jnp.sqrt(_sq_euclidian_fast(x, y))


def _manhattan(x: jax.Array, y: jax.Array) -> jax.Array:
    """Pairwise L1 distance (reference distance.py:95-115)."""
    return jnp.sum(jnp.abs(x[:, None, :] - y[None, :, :]), axis=-1)


def _gaussian(x: jax.Array, y: jax.Array, sigma: float = 1.0) -> jax.Array:
    """RBF kernel values (reference distance.py:63-92)."""
    d2 = jnp.sum((x[:, None, :] - y[None, :, :]) ** 2, axis=-1)
    return jnp.exp(-d2 / (2.0 * sigma * sigma))


def _gaussian_fast(x: jax.Array, y: jax.Array, sigma: float = 1.0) -> jax.Array:
    """RBF via quadratic expansion (reference distance.py:118-134)."""
    return jnp.exp(-_sq_euclidian_fast(x, y) / (2.0 * sigma * sigma))


def cdist(X: DNDarray, Y: Optional[DNDarray] = None, quadratic_expansion: bool = False) -> DNDarray:
    """Pairwise distance matrix (reference distance.py:136-175)."""
    metric = _euclidian_fast if quadratic_expansion else _euclidian
    return _dist(X, Y, metric)


def manhattan(X: DNDarray, Y: Optional[DNDarray] = None, expand: bool = False) -> DNDarray:
    """Pairwise L1 distance matrix (reference distance.py:176-207)."""
    return _dist(X, Y, _manhattan)


@functools.lru_cache(maxsize=32)
def _gaussian_metric(sigma: float, fast: bool) -> Callable:
    """One stable metric closure per (sigma, fast) — a fresh closure per rbf
    call would defeat the tile-program cache keyed on the metric object."""
    kernel = _gaussian_fast if fast else _gaussian

    def rbf_metric(x, y):
        return kernel(x, y, sigma)

    rbf_metric.__name__ = kernel.__name__
    return rbf_metric


def rbf(
    X: DNDarray,
    Y: Optional[DNDarray] = None,
    sigma: float = 1.0,
    quadratic_expansion: bool = False,
) -> DNDarray:
    """Pairwise RBF kernel matrix (reference distance.py:176-207)."""
    return _dist(X, Y, _gaussian_metric(float(sigma), bool(quadratic_expansion)))


def _dist(X: DNDarray, Y: Optional[DNDarray], metric: Callable) -> DNDarray:
    """Distance engine (reference distance.py:209-487): the checks, then
    :func:`_dist_checked`, timed by phase while ``telemetry.tracing()``."""
    sanitation.sanitize_in(X)
    if X.ndim != 2:
        raise NotImplementedError(f"X should be 2D, but was {X.ndim}D")
    if Y is None:
        Y = X
    elif Y is not X:
        sanitation.sanitize_in(Y)
        if Y.ndim != 2:
            raise NotImplementedError(f"Y should be 2D, but was {Y.ndim}D")
        if X.shape[1] != Y.shape[1]:
            raise ValueError("inputs must have the same number of features")
    ring = X.split == 0 and Y.split == 0
    if not telemetry.tracing():
        return _dist_checked(X, Y, metric, ring, telemetry.no_phase)
    p = X.comm.size
    ph = telemetry.Phases(
        "heat.cdist", mode="ring" if ring else "replicated", n=int(X.shape[0]),
        m=int(Y.shape[0]), f=int(X.shape[1]), p=p, metric=metric.__name__.lstrip("_"),
    )
    try:
        out = _dist_checked(X, Y, metric, ring, ph.phase)
    finally:
        ph.close()
    fusion.note_phases("cdist", ph.ns, calls=1, rotations=p - 1 if ring else 0)
    return out


def _dist_checked(X: DNDarray, Y: DNDarray, metric: Callable, ring: bool, mark) -> DNDarray:
    """:func:`_dist` past its checks. ``mark(name)`` opens the call's next
    phase (``telemetry.Phases.phase``; nothing when the call is not traced)."""
    mark("prepare")
    comm, p = X.comm, X.comm.size
    symmetric = Y is X
    promoted = types.promote_types(X.dtype, types.float32)
    if not symmetric:
        promoted = types.promote_types(promoted, Y.dtype)
    xl = X.larray.astype(promoted.jax_type())
    yl = xl if symmetric else Y.larray.astype(promoted.jax_type())
    n, m = xl.shape[0], yl.shape[0]

    if ring:
        # ragged row counts: pad to the next multiple of p and slice the
        # result — the reference's *v collectives have no XLA analog
        # (SURVEY.md §7), pad+mask is the balanced-only rendering
        n_pad, m_pad = (-n) % p, (-m) % p
        if n_pad:
            xl = jnp.pad(xl, ((0, n_pad), (0, 0)))
        if not symmetric and m_pad:
            yl = jnp.pad(yl, ((0, m_pad), (0, 0)))
        xl = _ensure_split(xl, 0, comm)
        yl = xl if symmetric else _ensure_split(yl, 0, comm)
        mark("dispatch")
        result = _tile_program(comm.mesh, comm.axis_name, p, metric)(xl, yl)
        mark("place")
        if n_pad or m_pad:
            result = result[:n, :m]
    else:
        # one operand replicated (reference distance.py:422-427) — or a layout
        # the ring does not cover: a single sharded expression, XLA schedules it
        mark("dispatch")
        result = metric(xl, yl)
        mark("place")

    split = 0 if X.split == 0 else None
    # a no-op for the tile program's result, which is born row-sharded
    result = _ensure_split(result, split, comm)
    return DNDarray(
        result, tuple(result.shape), types.canonical_heat_type(result.dtype), split, X.device, comm
    )


_CHUNK_BYTES = 1 << 29
"""The most one column chunk of a result tile may take (512 MiB): what a
device holds besides its rows of the result is a chunk's product and epilogue,
not a tile's (2.5 GB at 100 000 rows over four devices, 10 GB at 50 000 rows
on one)."""


def _column_chunks(rows: int, cols: int, itemsize: int):
    """``(starts, width)`` of the column chunks of ``cols`` columns (a
    multiple of 128) of ``rows`` rows: as few as keep a chunk within
    ``_CHUNK_BYTES``, all of one lane-aligned width, so the last one starts
    early and computes a few columns again (2.4 % at 100 000 rows) rather
    than be a ragged shape of its own."""
    most = max(128, _CHUNK_BYTES // (rows * itemsize) // 128 * 128)
    if cols <= most:
        return [0], cols
    count = -(-cols // most)
    width = -(-cols // (count * 128)) * 128
    return [min(k * width, cols - width) for k in range(-(-cols // width))], width


def _write_tile(metric: Callable, xs, ys_cur, out, lo: int):
    """``out`` with the tile of ``xs`` against ``ys_cur`` in its columns
    ``[lo, lo + len(ys_cur))``, every offset a constant. What XLA:TPU does
    with a store depends on where it lands (timed on a v5e, PERF.md PR 33): a
    chunk that starts on a lane tile (a multiple of 128) is stored where it is
    computed, product, epilogue and ``dynamic-update-slice`` one fusion;
    anywhere else, or at an offset known only at run time, the chunk is
    computed, then copied into place at a third of the speed, and a piece
    narrower than a lane tile turns the whole result column-major. So the
    columns between the first and the last multiple of 128 go in lane-aligned
    chunks (:func:`_column_chunks`), and what is left at either end, under
    128 columns, is merged into the lane tile it shares with the neighbouring
    tile: read, selected, written back. Tiles under three lane tiles (tests,
    wide meshes on small data) are one plain store."""
    rows, cols, total = xs.shape[0], ys_cur.shape[0], out.shape[1]
    hi = lo + cols

    def store(out, ys_cur, part, at):
        out = jax.lax.dynamic_update_slice(out, part, (0, at))
        # one piece at a time: without the barrier the scheduler may compute
        # every chunk of a tile before it stores the first
        return jax.lax.optimization_barrier((out, ys_cur))

    if cols < 3 * 128:
        return store(out, ys_cur, metric(xs, ys_cur), lo)[0]
    first, last = -(-lo // 128) * 128, hi // 128 * 128
    starts, width = _column_chunks(rows, last - first, jnp.dtype(out.dtype).itemsize)
    for start in starts:
        at = first + start
        out, ys_cur = store(out, ys_cur, metric(xs, ys_cur[at - lo : at - lo + width]), at)
    ends = ([first - 128] if lo < first else []) + ([min(last, total - 128)] if last < hi else [])
    for at in ends:
        col = at + jnp.arange(128)
        rows_of_y = jnp.take(ys_cur, jnp.clip(col - lo, 0, cols - 1), axis=0)
        mine = ((col >= lo) & (col < hi))[None, :]
        old = jax.lax.slice(out, (0, at), (rows, at + 128))
        out, ys_cur = store(out, ys_cur, jnp.where(mine, metric(xs, rows_of_y), old), at)
    return out


@functools.lru_cache(maxsize=64)
def _tile_program(mesh, axis: str, p: int, metric: Callable):
    """The cached jitted tile program of ``(mesh, metric)`` for X and Y split
    on rows (jit re-specializes per operand shape; tests ``.lower()`` it for
    collective-budget and memory assertions). On each device the stationary
    X shard computes its tile against the visiting Y shard, writes it into
    its rows of the result at the visiting shard's columns
    (:func:`_write_tile`), and passes the Y shard on (the reference's
    Send-to-(rank+i) schedule, distance.py:272-327, as a collective-permute
    ring): p tiles, p - 1 rotations in a ``fori_loop``, none at p = 1. Which
    shard visits is known only on the device; where its tile lands is not
    left to run time: one branch per visiting shard, each with its columns as
    constants (the collectives stay O(1) in p, the branches are p)."""
    from jax.sharding import PartitionSpec as P

    def kernel(xs, ys):
        rows, cols = xs.shape[0], ys.shape[0]  # one tile is (rows, cols)
        out = jax.lax.empty((rows, cols * p), jax.eval_shape(metric, xs[:1], ys[:1]).dtype)
        if p == 1:
            return _write_tile(metric, xs, ys, out, 0)
        out = jax.lax.pcast(out, (axis,), to="varying")
        tiles = [
            (lambda ys_cur, out, lo=j * cols: _write_tile(metric, xs, ys_cur, out, lo)) for j in range(p)
        ]
        rank = jax.lax.axis_index(axis)

        def step(i, carry):
            ys_cur, out = carry  # ys_cur holds the shard of device (rank + i) % p
            out = jax.lax.switch((rank + jnp.asarray(i, rank.dtype)) % p, tiles, ys_cur, out)
            return _ppermute(ys_cur, axis, p, shift=1), out

        # p-1 rotations; the last visiting shard's tile is written without re-sending it
        ys, out = jax.lax.fori_loop(0, p - 1, step, (ys, out))
        return jax.lax.switch((rank + p - 1) % p, tiles, ys, out)

    return jax.jit(
        jax.shard_map(
            kernel,
            mesh=mesh,
            in_specs=(P(axis, None), P(axis, None)),
            out_specs=P(axis, None),
        )
    )
