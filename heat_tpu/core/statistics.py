"""Statistical operations (reference: heat/core/statistics.py).

The reference's distributed machinery — custom MPI reduce ops carrying
(value, index) pairs for argmax/argmin (statistics.py:1335-1405), pairwise
moment merging for mean/var/std (``__merge_moments`` :1043-1113), Allgathered
bin counts for percentile (:1406-1675) — all collapses to sharded ``jnp``
reductions: XLA's psum is already deterministic and numerically stable at
these widths, so the merge choreography is not re-implemented.
"""

from __future__ import annotations

import builtins
import functools
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import factories, fusion, sanitation, telemetry, types
from ._operations import __binary_op as _binary_op
from ._operations import __local_op as _local_op
from ._operations import __reduce_op as _reduce_op
from .communication import sanitize_comm
from .dndarray import DNDarray, _ensure_split
from .stride_tricks import sanitize_axis

__all__ = [
    "argmax",
    "argmin",
    "average",
    "bincount",
    "bucketize",
    "cov",
    "digitize",
    "histc",
    "histogram",
    "kurtosis",
    "max",
    "maximum",
    "mean",
    "median",
    "mpi_argmax",
    "mpi_argmin",
    "min",
    "minimum",
    "percentile",
    "skew",
    "std",
    "var",
]


def _wrap(result: jax.Array, split, ref: DNDarray) -> DNDarray:
    if result.ndim == 0 or (split is not None and split >= result.ndim):
        split = None
    result = _ensure_split(result, split, ref.comm)
    return DNDarray(
        result, tuple(result.shape), types.canonical_heat_type(result.dtype), split, ref.device, ref.comm
    )


def argmax(x: DNDarray, axis: Optional[int] = None, out=None, **kwargs) -> DNDarray:
    """Indices of maximum values (reference statistics.py:37-116; the custom
    (value,index)-pair MPI op :1335-1405 is XLA's native sharded argmax)."""
    return _arg_reduce(jnp.argmax, x, axis, out)


def argmin(x: DNDarray, axis: Optional[int] = None, out=None, **kwargs) -> DNDarray:
    """Indices of minimum values (reference statistics.py:117-196)."""
    return _arg_reduce(jnp.argmin, x, axis, out)


@functools.lru_cache(maxsize=None)
def _arg_reduce_kernel(is_max: bool, axis: int, axis_name: str, block: int, size: int):
    """The split-crossing argmax/argmin shard_map kernel, cached per layout:
    a STABLE function identity (unlike a per-call closure) keys the fusion
    program cache and the retrace ledger correctly, so deferred argreduce
    chains hit compiled code in steady state."""
    from . import communication

    red = jnp.max if is_max else jnp.min
    arg = jnp.argmax if is_max else jnp.argmin
    combiner = mpi_argmax if is_max else mpi_argmin

    def kernel(xs):
        lv = red(xs, axis=axis)
        li = arg(xs, axis=axis) + jax.lax.axis_index(axis_name) * block
        _, gi = communication.allreduce((lv, li), axis_name, op=combiner, size=size)
        return gi

    kernel.__name__ = "argmax" if is_max else "argmin"
    return kernel


def _arg_reduce(op, x, axis, out):
    sanitation.sanitize_in(x)
    axis = sanitize_axis(x.shape, axis)
    # distributed schedule for a reduction ACROSS the split axis: local
    # (value, global-index) partials merged with the mpi_argmax/mpi_argmin
    # combiner through one allreduce — the reference's custom MPI reduce op
    # (reference statistics.py:1335-1405) riding MeshCommunication.allreduce.
    # Under collective-aware fusion the kernel records into the op-chain DAG
    # (fusion.defer_apply) instead of dispatching its own program, so
    # chain→argmax→chain compiles into ONE cached sharded program.
    if (
        isinstance(axis, int)
        and x.split == axis
        and not x.padded
        and x.comm.size > 1
    ):
        comm = x.comm
        block = x.shape[axis] // comm.size
        kernel = _arg_reduce_kernel(
            op is jnp.argmax, axis, comm.axis_name, block, comm.size
        )
        if out is None and fusion.active() and fusion.collectives_active():
            node = fusion.defer_apply(comm, kernel, (x,), (axis,), None)
            if node is not None:
                node = fusion.cast(node, types.index_dtype())
                return fusion.wrap_node(node, node.shape, None, x)
            # defer_apply left its own unfused breadcrumb: dispatch eagerly
        result = comm.apply(kernel, x.larray, in_splits=[axis], out_splits=None)
        result = result.astype(types.index_dtype())
        split = None
        ret = _wrap(result, split, x)
        if out is not None:
            sanitation.sanitize_out(out, ret.shape, ret.split, ret.device)
            out._replace(ret.larray.astype(out.dtype.jax_type()), ret.split)
            return out
        return ret
    result = op(x.larray, axis=axis).astype(types.index_dtype())
    if axis is None:
        split = None
    else:
        split = x.split
        if split is not None:
            if split == axis:
                split = None
            elif split > axis:
                split -= 1
    ret = _wrap(result, split, x)
    if out is not None:
        sanitation.sanitize_out(out, ret.shape, ret.split, ret.device)
        out._replace(ret.larray.astype(out.dtype.jax_type()), ret.split)
        return out
    return ret


def average(
    x: DNDarray, axis=None, weights: Optional[DNDarray] = None, returned: bool = False
):
    """Weighted average (reference statistics.py:197-316)."""
    sanitation.sanitize_in(x)
    if weights is None:
        result = mean(x, axis)
        if returned:
            cnt = np.prod(x.shape) if axis is None else _axis_count(x.shape, axis)
            wsum = factories.full_like(result, float(cnt))
            return result, wsum
        return result
    if weights.shape != x.shape:
        if axis is None or isinstance(axis, tuple):
            raise TypeError("Axis must be specified when shapes of x and weights differ.")
        if weights.ndim != 1:
            raise TypeError("1D weights expected when shapes of x and weights differ.")
        if weights.shape[0] != x.shape[axis]:
            raise ValueError("Length of weights not compatible with specified axis.")
        wl = weights.larray
        shape = [1] * x.ndim
        shape[axis] = -1
        wl = wl.reshape(shape)
    else:
        wl = weights.larray
    wsum = jnp.sum(jnp.broadcast_to(wl, x.shape), axis=axis)
    if bool(jnp.any(wsum == 0)):
        raise ZeroDivisionError("Weights sum to zero, can't be normalized")
    num = jnp.sum(x.larray * wl, axis=axis)
    result = num / wsum
    split = _reduced_split(x, axis)
    ret = _wrap(result, split, x)
    if returned:
        return ret, _wrap(jnp.broadcast_to(wsum, result.shape), split, x)
    return ret


def _axis_count(shape, axis):
    if isinstance(axis, tuple):
        out = 1
        for ax in axis:
            out *= shape[ax]
        return out
    return shape[axis]


def _reduced_split(x: DNDarray, axis, keepdims: bool = False):
    if x.split is None or axis is None:
        return None
    axes = (axis,) if isinstance(axis, int) else tuple(axis)
    axes = tuple(sanitize_axis(x.shape, a) for a in axes)
    if x.split in axes:
        return None
    if keepdims:
        return x.split
    return x.split - sum(1 for a in axes if a < x.split)


_ONEHOT_BINCOUNT_MAX = 1024


def _fast_bincount(idx: jax.Array, length: int, weights: Optional[jax.Array] = None) -> jax.Array:
    """Counting core shared by bincount/histc/histogram.

    XLA lowers ``.at[].add`` scatters on TPU to a sort-based expansion (its
    cost is not measured under the ledger); for a moderate number of bins
    the count is an MXU/VPU-shaped reduction instead: a one-hot compare that
    XLA fuses into the sum without materializing the (n, length) matrix.
    Falls back to the scatter path when bins are many or on CPU, where
    scatter-add is native.
    """
    use_onehot = length <= _ONEHOT_BINCOUNT_MAX and jax.default_backend() == "tpu"
    if not use_onehot:
        return jnp.bincount(idx, weights=weights, length=length)
    if weights is None:
        # int32 accumulation keeps counts exact past f32's 2^24 integer range
        oh = jax.nn.one_hot(idx, length, dtype=jnp.int32)
        return jnp.sum(oh, axis=0).astype(
            jnp.int64 if jax.config.read("jax_enable_x64") else jnp.int32
        )
    oh = jax.nn.one_hot(idx, length, dtype=weights.dtype)
    return weights @ oh  # (n,) @ (n, length): MXU


def bincount(x: DNDarray, weights: Optional[DNDarray] = None, minlength: int = 0) -> DNDarray:
    """Count occurrences of non-negative ints (reference statistics.py:317-374)."""
    sanitation.sanitize_in(x)
    if not types.heat_type_is_exact(x.dtype):
        raise TypeError(f"input must be integer type, got {x.dtype}")
    n = int(x.size)
    length = builtins.max(minlength, (int(jnp.max(x.larray)) + 1) if n else minlength)
    w = weights.larray.reshape(-1) if weights is not None else None
    result = _fast_bincount(x.larray.reshape(-1), length, w)
    if weights is None:
        result = result.astype(types.index_dtype())
    return _wrap(result, None, x)


def bucketize(
    input: DNDarray, boundaries, right: bool = False, out_int32: bool = False, out=None
) -> DNDarray:
    """Bucket index for each element (reference statistics.py:375-443)."""
    sanitation.sanitize_in(input)
    b = boundaries.larray if isinstance(boundaries, DNDarray) else jnp.asarray(boundaries)
    # torch semantics: right=False places v at the first boundary >= v
    # (numpy side='left'); right=True at the first boundary > v (side='right')
    side = "right" if right else "left"
    result = jnp.searchsorted(b, input.larray.reshape(-1), side=side).reshape(input.shape)
    result = result.astype(jnp.int32 if out_int32 else types.index_dtype())
    ret = _wrap(result, input.split, input)
    if out is not None:
        out._replace(ret.larray, ret.split)
        return out
    return ret


def cov(
    m: DNDarray, y: Optional[DNDarray] = None, rowvar: bool = True, bias: bool = False, ddof: Optional[int] = None
) -> DNDarray:
    """Covariance matrix estimate (reference statistics.py:444-525)."""
    if ddof is not None and not isinstance(ddof, int):
        raise TypeError("ddof must be integer")
    sanitation.sanitize_in(m)
    if m.ndim > 2:
        raise ValueError("m has more than 2 dimensions")
    x = m.larray.astype(jnp.promote_types(m.dtype.jax_type(), jnp.float32))
    if x.ndim == 1:
        x = x[None, :]
    if not rowvar and x.shape[0] != 1:
        x = x.T
    if y is not None:
        sanitation.sanitize_in(y)
        if y.ndim > 2:
            raise ValueError("y has more than 2 dimensions")
        yl = y.larray.astype(x.dtype)
        if yl.ndim == 1:
            yl = yl[None, :]
        if not rowvar and yl.shape[0] != 1:
            yl = yl.T
        x = jnp.concatenate([x, yl], axis=0)
    if ddof is None:
        ddof = 0 if bias else 1
    norm = x.shape[1] - ddof
    xm = x - jnp.mean(x, axis=1, keepdims=True)
    result = (xm @ jnp.conj(xm.T)) / norm
    return _wrap(jnp.squeeze(result), None, m)


def digitize(x: DNDarray, bins, right: bool = False) -> DNDarray:
    """Bin index for each element, numpy semantics (reference statistics.py:526-590)."""
    sanitation.sanitize_in(x)
    b = bins.larray if isinstance(bins, DNDarray) else jnp.asarray(bins)
    result = jnp.digitize(x.larray, b, right=right)
    return _wrap(result.astype(types.index_dtype()), x.split, x)


def histc(input: DNDarray, bins: int = 100, min: float = 0.0, max: float = 0.0, out=None) -> DNDarray:
    """Histogram with equal-width bins (reference statistics.py:591-651).

    The data-derived default range stays on device (traced scalars), so the
    op composes under ``jax.jit`` pipelines."""
    sanitation.sanitize_in(input)
    data = input.larray
    if sanitation.is_concrete(data):
        # eager: Python float64 range arithmetic (the degenerate ±1
        # expansion must not round away at large magnitudes — f32 ulp at
        # 1e8 is 8)
        lo, hi = float(min), float(max)
        if lo == 0.0 and hi == 0.0:
            lo = float(jnp.min(data))
            hi = float(jnp.max(data))
        if lo == hi:
            lo -= 1.0
            hi += 1.0
    else:
        # under a jit trace the data-derived range stays on device, in the
        # widest float the backend offers (f64 under x64, else f32 — the
        # degenerate expansion can round away at magnitudes ≥ 2^24 there)
        wdt = jnp.promote_types(data.dtype, jnp.float32)
        if float(min) == 0.0 and float(max) == 0.0:
            lo = jnp.min(data).astype(wdt)
            hi = jnp.max(data).astype(wdt)
        else:
            lo = jnp.asarray(float(min), wdt)
            hi = jnp.asarray(float(max), wdt)
        degenerate = lo == hi
        lo = jnp.where(degenerate, lo - 1.0, lo)
        hi = jnp.where(degenerate, hi + 1.0, hi)
    # torch.histc excludes out-of-range elements; bin index is direct
    # arithmetic on the equal-width grid, counted scatter-free
    data = data.reshape(-1)
    mask = (data >= lo) & (data <= hi)
    fdata = data.astype(jnp.float32) if not types.heat_type_is_inexact(input.dtype) else data
    idx = jnp.floor((fdata - lo) / (hi - lo) * bins).astype(jnp.int32)
    idx = jnp.clip(idx, 0, bins - 1)
    hist = _fast_bincount(idx, bins, mask.astype(fdata.dtype))
    ret = _wrap(hist.astype(input.dtype.jax_type()), None, input)
    if out is not None:
        out._replace(ret.larray, None)
        return out
    return ret


def histogram(a: DNDarray, bins: int = 10, range=None, normed=None, weights=None, density=None):
    """numpy-style histogram (reference statistics.py:652-699); counted via
    the scatter-free ``_fast_bincount`` on the searchsorted bin indices."""
    sanitation.sanitize_in(a)
    w = weights.larray.reshape(-1) if isinstance(weights, DNDarray) else (
        jnp.asarray(weights).reshape(-1) if weights is not None else None
    )
    data = a.larray.reshape(-1)
    if isinstance(bins, int) and bins <= _ONEHOT_BINCOUNT_MAX:
        edges = jnp.histogram_bin_edges(data, bins=bins, range=range)
        fdata = data.astype(edges.dtype)
        idx = jnp.clip(jnp.searchsorted(edges, fdata, side="right") - 1, 0, bins - 1)
        valid = (fdata >= edges[0]) & (fdata <= edges[-1])
        wv = valid.astype(edges.dtype) if w is None else jnp.where(valid, w, 0).astype(edges.dtype)
        hist = _fast_bincount(idx, bins, wv)
        if w is None:
            hist = hist.astype(types.index_dtype())
        if density:
            widths = jnp.diff(edges)
            hist = hist.astype(edges.dtype) / widths / jnp.sum(hist).astype(edges.dtype)
    else:
        hist, edges = jnp.histogram(data, bins=bins, range=range, weights=w, density=density)
    return _wrap(hist, None, a), _wrap(edges, None, a)


def kurtosis(x: DNDarray, axis: Optional[int] = None, unbiased: bool = True, Fischer: bool = True) -> DNDarray:
    """Kurtosis (4th central moment ratio) (reference statistics.py:700-784).

    ``unbiased`` applies the standard sample bias correction.
    """
    return _moment_stat(x, axis, order=4, unbiased=unbiased, fischer=Fischer)


def skew(x: DNDarray, axis: Optional[int] = None, unbiased: bool = True) -> DNDarray:
    """Skewness (3rd central moment ratio) (reference statistics.py:1860-1935)."""
    return _moment_stat(x, axis, order=3, unbiased=unbiased)


def _moment_stat(x, axis, order, unbiased, fischer=True):
    sanitation.sanitize_in(x)
    axis = sanitize_axis(x.shape, axis)
    if isinstance(axis, tuple):
        raise TypeError("axis must be None or an int")
    data = x.larray.astype(jnp.promote_types(x.dtype.jax_type(), jnp.float32))
    n = data.size if axis is None else data.shape[axis]
    mu = jnp.mean(data, axis=axis, keepdims=True)
    centered = data - mu
    m2 = jnp.mean(centered**2, axis=axis)
    mk = jnp.mean(centered**order, axis=axis)
    if order == 3:
        g = mk / jnp.power(m2, 1.5)
        if unbiased:
            g = g * jnp.sqrt(n * (n - 1)) / (n - 2)
    else:
        g = mk / (m2**2)
        if unbiased:
            g = ((n**2 - 1) * g - 3 * (n - 1) ** 2) / ((n - 2) * (n - 3)) + 3
        if fischer:
            g = g - 3
    return _wrap(jnp.asarray(g), _reduced_split(x, axis), x)


@functools.lru_cache(maxsize=None)
def _nan_propagating(op):
    """numpy max/min semantics: any NaN in the reduced window wins.

    XLA's *local* maximum propagates NaN, but the cross-device all-reduce
    combiner does not (C-max semantics — the reference's MPI.MAX has the
    identical hole), so a sharded reduce could silently drop NaN depending
    on the mesh size. One explicit isnan any-reduction restores the numpy
    contract deterministically; the pad-aware fast path stays safe because
    pad-slot NaNs only ever land in pad slots of the result.

    The wrapper is cached per ``op`` so its identity is stable call-to-call —
    the fusion engine's program cache keys on the operation object, and a
    fresh closure per ``ht.max`` call would force a retrace every time.
    """

    def fn(src, axis=None, keepdims=False, **kw):
        res = op(src, axis=axis, keepdims=keepdims, **kw)
        if jnp.issubdtype(src.dtype, jnp.floating):
            has_nan = jnp.any(jnp.isnan(src), axis=axis, keepdims=keepdims)
            res = jnp.where(has_nan, jnp.asarray(jnp.nan, res.dtype), res)
        return res

    return fn


def _reduction_crosses_split(x: DNDarray, axis) -> bool:
    if x.split is None:
        return False
    if axis is None:
        return True
    axes = (axis,) if isinstance(axis, int) else tuple(axis)
    ndim = x.ndim
    return any((a % ndim if ndim else a) == x.split for a in axes)


def max(x: DNDarray, axis=None, out=None, keepdims=False, keepdim=None) -> DNDarray:
    """Maximum along axis (reference statistics.py:785-901). ``keepdim`` is
    the reference's torch-style alias for ``keepdims``."""
    # XLA's local max propagates NaN; only the cross-device combine needs
    # the explicit pass (see _nan_propagating) — skip the extra traffic
    # for purely-local reductions
    op = _nan_propagating(jnp.max) if _reduction_crosses_split(x, axis) else jnp.max
    return _reduce_op(op, x, axis, out=out, keepdims=keepdims if keepdim is None else keepdim)


def maximum(x1: DNDarray, x2: DNDarray, out=None) -> DNDarray:
    """Elementwise maximum (reference statistics.py:902-940)."""
    return _binary_op(jnp.maximum, x1, x2, out=out)


def mean(x: DNDarray, axis=None, keepdims: bool = False) -> DNDarray:
    """Arithmetic mean (reference statistics.py:941-1007: local torch.mean +
    Allreduce of (mu, n) pairs with sequential merging; one sharded jnp.mean
    here). Routes through the L3 reduce engine, so under the fusion recorder
    a mean at the end of an op chain stays in the chain's single program."""
    if types.heat_type_is_exact(getattr(x, "dtype", types.float32)):
        x = x.astype(types.promote_types(x.dtype, types.float32))
    return _reduce_op(jnp.mean, x, axis, keepdims=keepdims)


def median(x: DNDarray, axis: Optional[int] = None, keepdims: bool = False, keepdim=None) -> DNDarray:
    """Median (reference statistics.py:1008-1042, via percentile's distributed
    bin protocol :1406-1675; a sharded sort-based kernel here)."""
    if keepdim is not None:
        keepdims = keepdim  # torch-style alias of the reference
    sanitation.sanitize_in(x)
    axis = sanitize_axis(x.shape, axis)
    if axis is None and x.split is not None and not x.padded:
        return percentile(x, 50.0, keepdims=keepdims)  # gather-free bisection
    data = x.larray
    if types.heat_type_is_exact(x.dtype):
        data = data.astype(types.promote_types(x.dtype, types.float32).jax_type())
    result = jnp.median(data, axis=axis, keepdims=keepdims)
    return _wrap(result, _reduced_split(x, axis, keepdims), x)


def min(x: DNDarray, axis=None, out=None, keepdims=False, keepdim=None) -> DNDarray:
    """Minimum along axis (reference statistics.py:1114-1230). ``keepdim`` is
    the reference's torch-style alias for ``keepdims``."""
    op = _nan_propagating(jnp.min) if _reduction_crosses_split(x, axis) else jnp.min
    return _reduce_op(op, x, axis, out=out, keepdims=keepdims if keepdim is None else keepdim)


def minimum(x1: DNDarray, x2: DNDarray, out=None) -> DNDarray:
    """Elementwise minimum (reference statistics.py:1231-1269)."""
    return _binary_op(jnp.minimum, x1, x2, out=out)


@jax.jit
def _order_stats_bisect(x: jax.Array, ranks: jax.Array) -> jax.Array:
    """Exact order statistics of the flat sharded array ``x`` by bisection on
    the VALUE space: each step counts ``x <= mid`` — a sharded reduction
    (local partial + psum), never a gather — and halves the bracket. The
    k-th order statistic is the smallest v with count(x <= v) >= k+1, which
    the upper bracket converges to within float precision. This is the TPU
    rendering of the reference's bin-count percentile protocol (reference
    statistics.py:1406-1675: Allgather of local bin counts + refinement);
    memory stays O(n/p) per device at any scale."""
    iters = 100 if x.dtype == jnp.float64 else 64
    lo = jnp.min(x)
    hi = jnp.max(x)
    los = jnp.full(ranks.shape, lo, x.dtype)
    his = jnp.full(ranks.shape, hi, x.dtype)

    def body(_, carry):
        los, his = carry
        mid = (los + his) * 0.5
        cnt = jnp.sum(x[None, :] <= mid[:, None], axis=1)
        ge = cnt >= ranks + 1
        return jnp.where(ge, los, mid), jnp.where(ge, mid, his)

    _, his = jax.lax.fori_loop(0, iters, body, (los, his))
    return his


def percentile(
    x: DNDarray,
    q,
    axis: Optional[int] = None,
    out=None,
    interpolation: str = "linear",
    keepdims: bool = False,
    keepdim=None,
) -> DNDarray:
    """q-th percentile (reference statistics.py:1406-1675: Allgather of local
    bin counts + refinement).

    Distributed flat percentiles (``axis=None`` over a split array) run the
    gather-free bisection kernel :func:`_order_stats_bisect`; other cases use
    one XLA quantile kernel over the logical array. ``keepdim`` is the
    reference's torch-style alias for ``keepdims``."""
    if keepdim is not None:
        keepdims = keepdim
    sanitation.sanitize_in(x)
    axis = sanitize_axis(x.shape, axis)
    if interpolation not in ("linear", "lower", "higher", "midpoint", "nearest"):
        raise ValueError(
            "interpolation must be 'linear', 'lower', 'higher', 'midpoint', or 'nearest'"
        )
    qa = jnp.asarray(q, dtype=jnp.float64 if jax.config.jax_enable_x64 else jnp.float32)
    data = x.larray
    if types.heat_type_is_exact(x.dtype):
        data = data.astype(types.promote_types(x.dtype, types.float32).jax_type())

    if axis is None and x.split is not None and not x.padded:
        n = x.size
        flat = data.reshape(-1)
        pos = qa / 100.0 * (n - 1)
        idt = jnp.int64 if jax.config.jax_enable_x64 else jnp.int32
        lower = jnp.floor(pos).astype(idt)
        upper = jnp.ceil(pos).astype(idt)
        ranks = jnp.concatenate([jnp.atleast_1d(lower).ravel(), jnp.atleast_1d(upper).ravel()])
        stats = _order_stats_bisect(flat, ranks)
        m = ranks.shape[0] // 2
        lo_v = stats[:m].reshape(jnp.shape(qa))
        hi_v = stats[m:].reshape(jnp.shape(qa))
        frac = (pos - jnp.floor(pos)).astype(data.dtype)
        if interpolation == "linear":
            result = lo_v + (hi_v - lo_v) * frac
        elif interpolation == "lower":
            result = lo_v
        elif interpolation == "higher":
            result = hi_v
        elif interpolation == "midpoint":
            result = (lo_v + hi_v) * 0.5
        else:  # nearest — numpy rounds half-to-even
            result = jnp.where(jnp.round(pos) <= jnp.floor(pos), lo_v, hi_v)
        if keepdims:
            result = result.reshape(jnp.shape(result) + (1,) * x.ndim)
        ret = _wrap(jnp.asarray(result), None, x)
    else:
        result = jnp.percentile(data, qa, axis=axis, method=interpolation, keepdims=keepdims)
        ret = _wrap(result, None, x)
    if out is not None:
        out._replace(ret.larray.astype(out.dtype.jax_type()), ret.split)
        return out
    return ret


def std(x: DNDarray, axis=None, ddof: int = 0, **kwargs) -> DNDarray:
    """Standard deviation (reference statistics.py:1936-1996): the root of :func:`var`.

    ``var`` reads the operand once and never returns a negative number, so the
    root is never NaN for finite data (see there for the algorithm and its
    error bound). The sqrt goes through the L3 local engine so var+sqrt stay
    one recorded chain, one program at the force."""
    v = var(x, axis, ddof=ddof, **kwargs)
    return _local_op(jnp.sqrt, v, no_cast=True)


# a shift taken from 1/64 of each reduced line bounds the one-pass formula's
# error amplification at 64 for any data (see _shifted_var)
_SLAB_SHARE = 64


@functools.partial(jax.jit, static_argnames=("axis", "keepdims", "ddof"))
def _shifted_var(src, axis=None, keepdims=False, ddof=0):
    """One-pass variance of a real floating array: ``jnp.var``'s calling
    convention, one read of ``src`` where ``jnp.var`` makes two.

    ``jnp.var`` reduces twice in sequence (the mean, then the centred
    squares), so an operand larger than the chip's fast memory streams from
    HBM twice. Here a shift ``c`` is taken from a leading slab of each
    reduced line (1/64 of the first reduced axis: the slab's mean, held
    inside the slab's ``[min, max]``), and ONE pass computes the sibling sums
    ``S1 = sum(x - c)`` and ``S2 = sum((x - c)^2)``, which XLA emits as one
    multi-output reduce fusion. ``var = max((S2 - S1^2/n) / (n - ddof), 0)``.

    Error: the shifted formula amplifies rounding over the two-pass one by
    ``kappa^2 = 1 + (mu - c)^2 / sigma^2``. With ``c`` the mean of a slab
    holding a share ``p`` of the line, the between-group variance of slab
    against rest is part of ``sigma^2``, so ``(mu - c)^2 / sigma^2 <=
    (1 - p) / p`` and ``kappa^2 <= 1/p <= 64`` for ANY data (met by a step:
    one level in the slab, another after it); i.i.d. data gives ``1 + 1/m``
    for a slab of ``m`` elements. The unshifted ``E[x^2] - E[x]^2`` has
    ``kappa^2 = 1 + mu^2 / sigma^2``, unbounded. The clamp keeps a
    rounding-negative difference from reaching ``std``'s root and lets NaN
    through. A constant line gives exactly 0: its slab's min and max hold
    ``c`` at the constant though a float mean of equal values may miss it.
    Sums accumulate in at least float32 (as ``jnp.var``); the result has
    ``src``'s dtype.
    """
    acc = jnp.promote_types(src.dtype, jnp.float32)
    if axis is None:
        axes = tuple(range(src.ndim))
    else:
        axes = tuple(a % src.ndim for a in ((axis,) if isinstance(axis, int) else axis))
    n = float(_axis_count(src.shape, axes))
    x = src.astype(acc)
    if n and axes:
        lead = axes[0]
        rows = -(-src.shape[lead] // _SLAB_SHARE)
        # the shift's value does not enter the derivative: var is invariant in c
        slab = jax.lax.stop_gradient(jax.lax.slice_in_dim(src, 0, rows, axis=lead)).astype(acc)
        # one variadic reduce: sibling jnp.sum/min/max split into two reads of
        # the slab when the minor axis is reduced (XLA:TPU, libtpu 0.0.34)
        total, lo, hi = jax.lax.reduce(
            (slab, slab, slab),
            (jnp.zeros((), acc), jnp.array(jnp.inf, acc), jnp.array(-jnp.inf, acc)),
            lambda a, b: (a[0] + b[0], jax.lax.min(a[1], b[1]), jax.lax.max(a[2], b[2])),
            axes,
        )
        # a rounded mean can leave [min, max]; inside it, constant lines shift to 0
        c = jnp.clip(total / (n * rows / src.shape[lead]), lo, hi)
        x = x - jnp.expand_dims(c, axes)
    s1 = jnp.sum(x, axis=axes, keepdims=keepdims)
    s2 = jnp.sum(x * x, axis=axes, keepdims=keepdims)
    # 0/0 = NaN where the line is empty or n - ddof <= 0, as jnp.var
    v = (s2 - s1 * (s1 / n)) / builtins.max(n - ddof, 0.0)
    return jnp.maximum(v, 0).astype(src.dtype)


def var(x: DNDarray, axis=None, ddof: int = 0, **kwargs) -> DNDarray:
    """Variance (reference statistics.py:2046-2126) in one read of the operand.

    The reference takes local moments once per rank and merges them pairwise
    (``__merge_moments`` :1043-1113). Here every real floating input records
    :func:`_shifted_var`, one-pass shifted-data moments whose two sums are
    siblings of one reduce fusion (GSPMD adds their psum across the split
    axis): ``var = max((S2 - S1^2/n) / (n - ddof), 0)`` with ``S1``, ``S2``
    the sums of ``x - c`` and its square and ``c`` the mean of the leading
    1/64 of each reduced line. The rounding error is at most ``kappa^2 = 1 +
    (mu - c)^2/sigma^2 <= 64`` times the two-pass formula's for any data
    (worst case a step between slab and rest; ``1 + 1/m`` for i.i.d. data
    and a slab of ``m`` elements). The clamp at 0 keeps :func:`std` from the
    root of a rounding-negative number, and a constant input gives exactly
    0. Exact dtypes are promoted to float first; complex input keeps the
    two-pass ``jnp.var``."""
    sanitation.sanitize_in(x)
    if not isinstance(ddof, int):
        raise TypeError(f"ddof must be integer, is {type(ddof)}")
    if ddof not in (0, 1):
        raise ValueError("Only ddof=0 or ddof=1 is supported")
    if kwargs.get("bessel") is not None:
        ddof = 1 if kwargs["bessel"] else 0
    keepdims = bool(kwargs.get("keepdims", False))
    if types.heat_type_is_exact(x.dtype):
        x = x.astype(types.promote_types(x.dtype, types.float32))
    onepass = not types.heat_type_is_complexfloating(x.dtype)
    if telemetry._MODE:
        telemetry.record_var_path("onepass" if onepass else "twopass")
    return _reduce_op(_shifted_var if onepass else jnp.var, x, axis, keepdims=keepdims, ddof=ddof)


def mpi_argmax(a, b):
    """Combiner merging two ``(values, indices)`` pairs to the elementwise max
    and its global index — the pure-JAX equivalent of the reference's custom
    MPI reduce op (reference statistics.py:1335-1370). Usable as the combine
    fn of a ``lax.psum``-style tree or ``jax.lax.reduce`` over shards."""
    av, ai = a
    bv, bi = b
    # NaN-aware (numpy argmax returns the first NaN's index): a NaN side
    # wins; both-NaN keeps the lower-index accumulator. No-op for ints.
    a_nan = jnp.isnan(av) if jnp.issubdtype(av.dtype, jnp.floating) else jnp.zeros_like(av, bool)
    b_nan = jnp.isnan(bv) if jnp.issubdtype(bv.dtype, jnp.floating) else jnp.zeros_like(bv, bool)
    take_b = ((bv > av) | b_nan) & ~a_nan
    return jnp.where(take_b, bv, av), jnp.where(take_b, bi, ai)


def mpi_argmin(a, b):
    """Elementwise-min combiner over ``(values, indices)`` pairs
    (reference statistics.py:1371-1405)."""
    av, ai = a
    bv, bi = b
    a_nan = jnp.isnan(av) if jnp.issubdtype(av.dtype, jnp.floating) else jnp.zeros_like(av, bool)
    b_nan = jnp.isnan(bv) if jnp.issubdtype(bv.dtype, jnp.floating) else jnp.zeros_like(bv, bool)
    take_b = ((bv < av) | b_nan) & ~a_nan
    return jnp.where(take_b, bv, av), jnp.where(take_b, bi, ai)
