"""Fused pairwise-distance pallas kernel.

The exact (non-quadratic-expansion) metrics in reference
heat/spatial/distance.py:16-37 (L2) and :95-115 (L1) are computed there as a
broadcast ``|x[:,None,:] - y[None,:,:]|`` reduce — an O(n·m·f) intermediate
that is pure HBM traffic. On TPU that intermediate never needs to exist: this
kernel tiles the (n, m) output over a pallas grid, streams x/y row blocks
into VMEM once per tile, and reduces the feature axis on-chip, so HBM traffic
is O(n·m + (n+m)·f) — the lower bound — by construction.

Honest perf note (measured, v5e-1): XLA's own fusion of the broadcast
expression also avoids materializing the intermediate and currently beats
this kernel ~2-3x on VPU throughput for f ∈ [64, 256], so the default
``spatial.cdist`` path stays on the XLA expression ("don't hand-schedule
what the compiler already does"). The kernel is kept as (a) the template for
fused-tile pairwise patterns (ring attention tiles, flash-style reductions)
and (b) a guaranteed-VMEM-footprint variant whose memory behavior is
shape-predictable where XLA's fusion choices are not.

Layout: the feature axis is the TPU lane dimension (padded to 128), so the
per-step broadcast ``(ROWS, TN, F)`` lives entirely in VMEM and the feature
reduction is a lane reduction — no dynamic lane slicing (Mosaic requires
lane indices to be 128-aligned).

Numerics match the reference's exact path (difference first, then square/abs)
— NOT the quadratic expansion |x|²+|y|²−2x·yᵀ, which loses precision when
x≈y. This is the "exact but fast" option the reference cannot offer.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["pairwise_distance", "pallas_supported"]

_TM = 256  # output tile rows (x block)
_TN = 256  # output tile cols (y block)
_ROWS = 8  # x rows reduced per VPU step (one f32 sublane tile)
_LANE = 128  # feature padding quantum (lane width)
_MAX_F = 512  # above this the (ROWS, TN, F) step intermediate pressures VMEM


def pallas_supported(f: int) -> bool:
    """Whether the fused kernel can run here: TPU backend and a feature count
    whose VMEM footprint fits (step intermediate ROWS·TN·F·4B ≤ 4 MB)."""
    return jax.default_backend() == "tpu" and f <= _MAX_F


def _pairwise_kernel(x_ref, y_ref, o_ref, *, p: int, post_sqrt: bool):
    """One (TM, TN) output tile.

    x_ref: (TM, F) block, y_ref: (TN, F) block, o_ref: (TM, TN). F is padded
    to the lane width outside; zero features contribute nothing to L1/L2.
    """
    y = y_ref[:, :]  # (TN, F), resident for the whole tile

    def body(i, _):
        r = pl.multiple_of(i * _ROWS, _ROWS)
        xb = x_ref[pl.ds(r, _ROWS), :]  # (ROWS, F)
        diff = xb[:, None, :] - y[None, :, :]  # (ROWS, TN, F)
        if p == 1:
            part = jnp.sum(jnp.abs(diff), axis=-1)
        else:
            part = jnp.sum(diff * diff, axis=-1)
        o_ref[pl.ds(r, _ROWS), :] = jnp.sqrt(part) if post_sqrt else part
        return 0

    jax.lax.fori_loop(0, o_ref.shape[0] // _ROWS, body, 0)


@functools.partial(jax.jit, static_argnames=("p", "post", "interpret"))
def _pairwise_padded(x: jax.Array, y: jax.Array, p: int, post: bool, interpret: bool = False) -> jax.Array:
    """Grid-tiled pallas call over feature-padded, row-padded operands."""
    n, f = x.shape
    m = y.shape[0]
    kernel = functools.partial(_pairwise_kernel, p=p, post_sqrt=post)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n, m), x.dtype),
        grid=(n // _TM, m // _TN),
        in_specs=[
            pl.BlockSpec((_TM, f), lambda i, j: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((_TN, f), lambda i, j: (j, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((_TM, _TN), lambda i, j: (i, j), memory_space=pltpu.VMEM),
        interpret=interpret,
    )(x, y)


def pairwise_distance(
    x: jax.Array,
    y: Optional[jax.Array] = None,
    p: int = 2,
    squared: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """Exact pairwise Lp distance matrix ``(n, m)`` with fused feature
    reduction. ``p`` ∈ {1, 2}; ``squared=True`` skips the final sqrt (L2 only).

    Pads rows to the 256-tile and features to the lane width, then slices the
    result — zero-padding features is exact for both metrics; padded rows are
    discarded.
    """
    if y is None:
        y = x
    if p not in (1, 2):
        raise ValueError(f"p must be 1 or 2, got {p}")
    if x.ndim != 2 or y.ndim != 2:
        raise ValueError(f"x and y must be 2D, got {x.ndim}D and {y.ndim}D")
    if x.shape[1] != y.shape[1]:
        raise ValueError(f"feature counts differ: {x.shape[1]} != {y.shape[1]}")
    n, f = x.shape
    m = y.shape[0]
    if f > _MAX_F:
        raise ValueError(
            f"f={f} exceeds the kernel's VMEM budget (max {_MAX_F}); "
            "use the XLA broadcast expression for wide features"
        )
    dtype = jnp.promote_types(x.dtype, jnp.float32)
    x = x.astype(dtype)
    y = y.astype(dtype)

    f_pad = -f % _LANE
    n_pad = -n % _TM
    m_pad = -m % _TN
    if f_pad:
        x = jnp.pad(x, ((0, 0), (0, f_pad)))
        y = jnp.pad(y, ((0, 0), (0, f_pad)))
    if n_pad:
        x = jnp.pad(x, ((0, n_pad), (0, 0)))
    if m_pad:
        y = jnp.pad(y, ((0, m_pad), (0, 0)))

    out = _pairwise_padded(x, y, p, post=(p == 2 and not squared), interpret=interpret)
    if n_pad or m_pad:
        out = out[:n, :m]
    return out
