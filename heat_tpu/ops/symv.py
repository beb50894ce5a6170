"""Product of a symmetric float32 matrix with a vector, from one block triangle.

A Lanczos step (``core/linalg/solver.py::_lanczos_body``) is one product with
the stored Laplacian, and the product reads the matrix from HBM: 4 n^2 bytes
for a few FLOP a byte, so its time is its bytes. A symmetric matrix holds every
off-diagonal block twice. Cut into b x b blocks, the block L_IJ (I < J) gives
both halves of the product it takes part in,

    y_I += L_IJ v_J        (its rows)
    y_J += L_IJ^T v_I      (its columns),

so one read of the upper block triangle, the diagonal blocks included, is the
whole product: T (T + 1) / 2 of T^2 blocks, T = n / b (2 080 of 4 096 at n =
32 768, b = 512: 50.8 % of the bytes). Only those blocks are read. So the
result is the product with the matrix whose upper block triangle is ``a``'s
and whose lower one is its mirror, as ``numpy.linalg.eigh`` reads one
triangle (``UPLO``); for a symmetric ``a`` that is ``a @ v``.

The kernel walks a 1-D grid over the upper blocks in row-major order; the
block's (I, J) come from two scalar-prefetched tables, from which the index
map takes the block. ``v`` and ``y`` are whole in VMEM for the whole grid
(constant index maps), lane-dense as (n / 128, 128): y is written back once.
A block is walked 8 rows (one sublane tile) at a time:

- its rows: each lane tile times v_J's tile broadcast down the sublanes, added
  into a (b, 128) float32 accumulator of block row I, which is folded across
  lanes into y_I after the row's last block (a (128, 128) transpose, then a
  sum down the sublanes, so y_I's entries lie in lanes);
- its columns (I < J only): each lane tile times v_I's rows broadcast along
  the lanes (a (b, 128) table built once per block row, at its diagonal
  block), summed down the sublanes into J's (8, 128) tiles, carried through
  the walk in registers and added into y_J after the block.

Products and sums are float32 on the VPU, as the dtype asks (``ops/mxu.py``'s
rule): no MXU pass, no operand rounded to bfloat16. Two multiplies and two
adds per element read stay far under the VPU's rate at the HBM's, so the
product stays HBM-bound.

:func:`takes` says, from what can be observed of the operand, whether
:func:`upper_matvec` is its product: on a TPU, on one device, square,
float32, with a side that is a multiple of :data:`BLOCK`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["BLOCK", "takes", "upper_matvec"]

BLOCK = 512
"""Side of a square block of the matrix: 1 MB of float32, two in flight. On a
v5e at 32 768 rows a product took 2.90 ms at 512, 2.95 at 1 024 and 3.04 at
2 048 (PERF.md, section 6): the bytes fall with the block, the grid's steps hide
under each block's read down to 512."""

_LANE = 128
_UNROLL = 4


def takes(shape, dtype, sharding) -> bool:
    """Whether :func:`upper_matvec` is the product for a matrix of ``shape``
    and ``dtype`` laid out by ``sharding``: the process runs on a TPU, the
    matrix is on one device, square, float32, and its side is a multiple of
    :data:`BLOCK`."""
    return (
        jax.default_backend() == "tpu"
        and len(shape) == 2 and shape[0] == shape[1] and shape[0] % BLOCK == 0
        and jnp.dtype(dtype) == jnp.float32
        and len(sharding.device_set) == 1
    )


def _kernel(rows_ref, cols_ref, a_ref, v_ref, y_ref, acc_ref, vcol_ref, *, t: int, tiles: int):
    """One upper block (I, J) = (rows[k], cols[k]) of the matrix: its rows
    into block row I's accumulator, its columns (I < J) into y_J."""
    k = pl.program_id(0)
    i, j = rows_ref[k], cols_ref[k]
    groups = tiles * _LANE // (8 * _UNROLL)  # steps of the walk, each _UNROLL sublane tiles

    @pl.when(k == 0)
    def _():
        y_ref[:, :] = jnp.zeros_like(y_ref)

    @pl.when(i == j)  # the first block of block row I
    def _():
        acc_ref[:, :] = jnp.zeros_like(acc_ref)
        for c in range(tiles):
            vi = v_ref[pl.ds(i * tiles + c, 1), :]  # (1, 128): v_I's rows c*128 ..
            vcol_ref[c * _LANE : (c + 1) * _LANE, :] = jnp.transpose(jnp.broadcast_to(vi, (_LANE, _LANE)))

    vrow = [jnp.broadcast_to(v_ref[pl.ds(j * tiles + c, 1), :], (8, _LANE)) for c in range(tiles)]

    def rows_of(slab):
        part = slab[:, :_LANE] * vrow[0]
        for c in range(1, tiles):
            part = part + slab[:, c * _LANE : (c + 1) * _LANE] * vrow[c]
        return part

    def diagonal(g, carry):
        for u in range(_UNROLL):
            r = pl.multiple_of((g * _UNROLL + u) * 8, 8)
            acc_ref[pl.ds(r, 8), :] += rows_of(a_ref[pl.ds(r, 8), :])
        return carry

    def off_diagonal(g, cols):
        for u in range(_UNROLL):
            r = pl.multiple_of((g * _UNROLL + u) * 8, 8)
            slab = a_ref[pl.ds(r, 8), :]  # (8, b)
            acc_ref[pl.ds(r, 8), :] += rows_of(slab)
            vc = vcol_ref[pl.ds(r, 8), :]  # v_I's 8 rows, each along the lanes
            cols = tuple(cols[c] + slab[:, c * _LANE : (c + 1) * _LANE] * vc for c in range(tiles))
        return cols

    @pl.when(i == j)
    def _():
        jax.lax.fori_loop(0, groups, diagonal, 0)

    @pl.when(i < j)
    def _():
        zero = jnp.zeros((8, _LANE), jnp.float32)
        cols = jax.lax.fori_loop(0, groups, off_diagonal, (zero,) * tiles)
        for c in range(tiles):
            y_ref[pl.ds(j * tiles + c, 1), :] += jnp.sum(cols[c], axis=0, keepdims=True)

    @pl.when(j == t - 1)  # the last block of block row I
    def _():
        for c in range(tiles):
            folded = jnp.transpose(acc_ref[c * _LANE : (c + 1) * _LANE, :])  # row sums down the sublanes
            y_ref[pl.ds(i * tiles + c, 1), :] += jnp.sum(folded, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def upper_matvec(a: jax.Array, v: jax.Array, block: int = BLOCK, interpret: bool = False) -> jax.Array:
    """``y = M v`` for the (n, n) float32 ``a`` and the (n,) float32 ``v``,
    where M's upper block triangle (blocks of ``block`` x ``block``) is
    ``a``'s and its lower one is the mirror of it: ``a @ v`` for a symmetric
    ``a``, reading only the upper blocks, each once (module docstring). ``n``
    is a multiple of ``block``, and ``block`` of 128. ``a`` is read in place.
    The VMEM it holds (two blocks in flight, two (block, 128) tables, v and y)
    stays under a v5e's 16 MB of scoped VMEM up to ``block`` = 1 024."""
    n = a.shape[0]
    t, tiles = n // block, block // _LANE
    rows, cols = np.triu_indices(t)  # row-major: the diagonal block first in each row
    lanes = pl.BlockSpec((n // _LANE, _LANE), lambda k, rows, cols: (0, 0))
    y = pl.pallas_call(
        functools.partial(_kernel, t=t, tiles=tiles),
        out_shape=jax.ShapeDtypeStruct((n // _LANE, _LANE), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(len(rows),),
            in_specs=[pl.BlockSpec((block, block), lambda k, rows, cols: (rows[k], cols[k])), lanes],
            out_specs=lanes,
            scratch_shapes=[pltpu.VMEM((block, _LANE), jnp.float32), pltpu.VMEM((block, _LANE), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="lanczos_upper_matvec",
    )(jnp.asarray(rows, jnp.int32), jnp.asarray(cols, jnp.int32), a, v.reshape(n // _LANE, _LANE))
    return y.reshape(n)
