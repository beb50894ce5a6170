"""Lasso regression (reference: heat/regression/lasso.py).

Coordinate descent with soft thresholding (reference lasso.py:90-176), the
first column of ``x`` the unpenalised intercept. A fit takes one of two
forms, chosen from the operand's shape alone:

* **Gram mode** (``m * m <= _GRAM_MAX_ELEMENTS`` and ``n >= m``: every tall
  operand up to 2 048 features). ONE program (``lasso_gram``) contracts the
  samples once, ``G = XᵀX`` and ``cy = Xᵀy``, from the rows AS THEY LIE,
  (n, m) split=0: axis 0 of the rows is contracted where it is, nothing is
  transposed and no operand-sized copy is made. The rows are read in chunks
  of ``_SUM_ROWS``: a chunk's Gram is the library's one tall Gram
  (``core/linalg/qr.py::tall_gram``, the product CholeskyQR2 takes twice:
  upper block triangle, mirrored, symmetric to the bit), its ``cy`` a
  multiply-reduce on the VPU, and the chunks add up in a compensated float32
  sum, because a sweep's ``c = cy - Gθ`` cancels and shows the sixth digit of
  either. It is one ``shard_map`` program on every mesh, of one chip or of
  p (``_gram_precompute``): rows are summed where they lie and cross the
  chips once; an operand that is not split=0 is resplit first, and the
  padding of a ragged split is read as zeros. Then ONE program
  (``lasso_descent``) runs every sweep: a sweep (``lasso_cd_sweep``) is a
  ``lax.fori_loop`` of ``m`` coordinate steps on the replicated m-vector
  ``c = cy - Gθ``, with no collective.
* **Residual mode** (wider operands): the incremental-residual sweep
  :func:`_cd_sweep` on a transposed float32 copy of the rows, all sweeps in
  one program too.

Both multiply by ``ops/mxu.py``'s rule, read off the dtype of the rows and
nothing else: float32 rows (and wider, carried as float32) multiply in
float32 (``Precision.HIGHEST``; left at XLA's default the MXU rounds both
operands to bfloat16), bfloat16 rows keep their one bfloat16 pass with
float32 accumulation (Gram mode streams them at their own width).

The loop over sweeps is the device's (:func:`_descend`, the one loop of both
modes): after each sweep it takes the iterates' root-mean-square change and
stops once that is under ``tol`` or ``max_iter`` sweeps have run (reference
lasso.py:166-171, whose loop and test are the host's). ``max_iter`` and
``tol`` are traced scalars, so one compiled program per shape serves every
value of them and of ``lam``. A fit is two dispatches (the Gram, the descent;
one in residual mode) and ONE blocking host read, of the sweeps run and the
last change, whatever ``max_iter``.

**The step's contract is upstream's: columns of unit mean square.** A
coordinate step sets ``θ_j = soft(rho_j, λ)`` with ``rho_j = mean(x_j · (y -
Xθ + θ_j x_j))`` and does NOT divide by the column's mean square ``G_jj / n``
(reference lasso.py:120-141; upstream's demo divides each column by its root
mean square first). That is the coordinate minimiser only where every
penalised column has mean square 1. On other columns it is another iteration:
under 1 it under-relaxes, and from about 2 on every step overshoots and the
iterates grow without bound (columns ``1 + Z`` of mean square 2, 512 of them
correlated 0.9: the largest coefficient 1e10 after 10 sweeps and past float32
after 26; ``tests/test_lasso_f32.py`` pins it, PERF.md section 6, PR 40). No
division is added here: normalise the columns, as upstream's users do.
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..core import factories, fusion, manipulations, telemetry, types
from ..core.base import BaseEstimator, RegressionMixin
from ..core.dndarray import DNDarray, _ensure_split
from ..core.linalg.qr import tall_gram
from ..ops.mxu import mxu_precision

__all__ = ["Lasso"]


def _soft_step(j, rho, lam):
    """Coordinate ``j``'s new value from its statistic ``rho``: the soft
    threshold, and for the intercept (``j == 0``) ``rho`` itself."""
    return jnp.where(j == 0, rho, jnp.sign(rho) * jnp.maximum(jnp.abs(rho) - lam, 0.0))


def _cd_sweep(XT: jax.Array, y: jax.Array, theta: jax.Array, lam: jnp.float32, precision=None):
    """One full coordinate-descent sweep over all features in the residual
    form (feature 0 is the unpenalized intercept, reference lasso.py:120-141).

    The residual is computed ONCE per sweep and updated incrementally after
    each coordinate step (``r -= Δθ_j · X_j``), so a sweep costs O(n·m)
    instead of the reference's O(n·m²) full ``X @ θ`` per feature — the
    same iterates up to rounding (the residual is refreshed from scratch
    every sweep, bounding drift). The operand arrives TRANSPOSED ((m, n),
    features in rows: the one copy of the rows a fit in this mode makes; Gram
    mode makes none) so each coordinate's slice is contiguous — a per-
    feature column gather out of the (n, m) layout costs ~stride-m reads
    per element and dominated the sweep. ``precision`` is what the rows'
    own dtype asks of the two contractions (``ops/mxu.py::mxu_precision``).
    The collective budget is unchanged: the per-feature ``rho`` contraction
    is the sweep's ONE row-axis all-reduce (the samples stay sharded on axis
    1 of the transpose), everything else is local to the shards."""
    m, n = XT.shape
    r = y.reshape(-1) - jnp.matmul(theta.reshape(-1), XT, precision=precision)

    def body(j, carry):
        r, th = carry
        X_j = jax.lax.dynamic_slice_in_dim(XT, j, 1, axis=0)[0]  # (n,) contiguous
        th_j = th[j, 0]
        rho = jnp.matmul(X_j, r + th_j * X_j, precision=precision) / n  # psum over the sharded samples
        new = _soft_step(j, rho, lam)
        r = r - (new - th_j) * X_j
        return r, th.at[j, 0].set(new)

    _, theta = jax.lax.fori_loop(0, m, body, (r, theta))
    return theta


# features-squared budget for replicating the Gram matrix (the same spirit as
# linalg.qr's _REPLICATED_MAX_ELEMENTS): above this, fit falls back to the
# incremental-residual sweep
_GRAM_MAX_ELEMENTS = 1 << 22


# rows of one sum on the MXU: a float32 product's accumulator there drops what
# each partial sum rounds off (it rounds towards zero), so a Gram of positive
# terms comes out low, the more rows the more: over 3 145 728 x 512 rows in one
# sum 7.4e-5 of itself in the mean and 2.1e-4 at most, which the cancelling
# c = cy - G theta of a sweep turns into 2.9e-3 of theta; in sums of 32 768
# rows 1.3e-6 and 5.1e-6 (theta 4.6e-5), of 8 192 rows 4.3e-7 at most (theta
# 4e-6), of 4 096 rows 8e-8, float32's own last bit (theta 2e-6, what the
# float32 sweeps alone leave). The chunks are added on the VPU. Smaller chunks
# are slower products: the precompute takes 45.8 ms in one sum, 66.9 at 8 192
# rows, 89.2 at 4 096, 103.2 at 2 048 (v5e, PERF.md PR 40).
_SUM_ROWS = 4096


def _two_sum(total, lost, term):
    """``total + term``, and ``lost`` plus what that addition rounded off
    (Knuth's TwoSum, exact whatever the magnitudes): a compensated sum's two
    float32 halves, elementwise."""
    t = total + term
    back = t - total
    return t, lost + ((total - (t - back)) + (term - back))


def lasso_gram(X: jax.Array, y: jax.Array, valid=None):
    """(G, cy) = (X'X, X'y) in float32 of the rows at hand, as they lie: ``X``
    (n, m) and ``y`` (n, 1); nothing is transposed and nothing operand-sized
    is made. The rows are taken in chunks of about ``_SUM_ROWS`` (a
    ``fori_loop`` of row slices read in place): a chunk's Gram is
    ``core/linalg/qr.py::tall_gram`` (the product CholeskyQR2 takes: upper
    block triangle, mirrored; under ``ops/mxu.py``'s rule by ``X``'s dtype),
    its ``cy`` a multiply-reduce on the VPU in float32 whatever the rows are
    (``y`` is never rounded), and the chunks add up in a compensated float32
    sum (:func:`_two_sum`), so both come out to float32's last bits however
    many rows there are. ``G`` is symmetric to the bit. ``valid`` (a traced
    count, or None for all) says how many leading rows are real: the rest, the
    padding of a ragged split whose content nobody promises, is read as
    zeros, chunk by chunk, and adds nothing to either sum."""
    n, m = X.shape
    chunks = max(1, n // _SUM_ROWS)
    rows = n // chunks

    def moments(start, xb, yb):
        if valid is not None:
            real = (start + jnp.arange(xb.shape[0]) < valid)[:, None]
            xb, yb = jnp.where(real, xb, 0), jnp.where(real, yb, 0)
        return tall_gram(xb), jnp.sum(xb.astype(jnp.float32) * yb, axis=0)

    def add(sums, terms):
        (g, g_lost, cy, cy_lost), (dg, dcy) = sums, terms
        return _two_sum(g, g_lost, dg) + _two_sum(cy, cy_lost, dcy)

    def chunk(i, sums):
        xb = jax.lax.dynamic_slice_in_dim(X, i * rows, rows, axis=0)
        yb = jax.lax.dynamic_slice_in_dim(y, i * rows, rows, axis=0)
        return add(sums, moments(i * rows, xb, yb))

    zeros = (jnp.zeros((m, m), jnp.float32),) * 2 + (jnp.zeros((m,), jnp.float32),) * 2
    sums = jax.lax.fori_loop(0, chunks, chunk, zeros)
    if n > chunks * rows:  # fewer rows than there are chunks
        sums = add(sums, moments(chunks * rows, X[chunks * rows:], y[chunks * rows:]))
    g, g_lost, cy, cy_lost = sums
    return g + g_lost, cy + cy_lost


@lru_cache(maxsize=None)
def _gram_precompute(mesh, axis: str, n: Optional[int] = None):
    """The fit's ONE Gram program: :func:`lasso_gram` of rows sharded over
    ``mesh``'s ``axis`` (a mesh of one chip too). Each device sums its own
    rows, then ONE all-reduce of (G, cy) together, the fit's only collective
    in Gram mode; both results are replicated. ``n`` is given for the physical
    rows of a ragged split (``DNDarray.parray``): rows from ``n`` on are
    padding and count as zeros."""

    def lasso_gram_rows(X, y):
        def local(xs, ys):
            valid = None if n is None else n - jax.lax.axis_index(axis) * xs.shape[0]
            return jax.lax.psum(lasso_gram(xs, ys, valid), axis)

        rows = P(axis, None)
        # check_vma off: the chunk loop's carry starts from constants, which carry no axis
        return jax.shard_map(local, mesh=mesh, in_specs=(rows, rows), out_specs=(P(), P()), check_vma=False)(X, y)

    lasso_gram_rows.__name__ = "lasso_gram"  # the program's name in a trace
    return jax.jit(lasso_gram_rows)


def lasso_cd_sweep(G: jax.Array, cy: jax.Array, theta: jax.Array, lam: jnp.float32, n: int):
    """One coordinate-descent sweep in the covariance-update form (sklearn's
    ``precompute=True``): with ``G = X'X`` and ``cy = X'y`` replicated, the
    per-feature statistic is ``rho_j = (cy_j - Σ_{i≠j} G_ji θ_i) / n`` and a
    coordinate step only touches the m-vector ``c = cy - G @ θ`` — the sweep
    is PURELY LOCAL (zero collectives; pinned by tests/test_mesh64_compile
    style HLO counting in tests/test_ml.py). Identical iterates to the
    residual form in exact arithmetic. ``G`` and ``θ`` are float32 whatever
    the rows were, so the one product here is taken in float32.

    The step is upstream's to the letter: ``rho`` is NOT divided by the
    column's mean square ``G_jj / n``, so it minimises over coordinate ``j``
    only where that is 1 (the module docstring: the contract, and what
    happens on columns of mean square 2)."""
    c = cy - jnp.matmul(G, theta.reshape(-1), precision=jax.lax.Precision.HIGHEST)

    def body(j, carry):
        c, th = carry
        th_j = th[j, 0]
        g_j = jax.lax.dynamic_slice_in_dim(G, j, 1, axis=0)[0]  # (m,)
        rho = (c[j] + th_j * g_j[j]) / n
        new = _soft_step(j, rho, lam)
        c = c - (new - th_j) * g_j
        return c, th.at[j, 0].set(new)

    _, theta = jax.lax.fori_loop(0, G.shape[0], body, (c, theta))
    return theta


def _descend(sweep, theta: jax.Array, max_iter, tol):
    """One fit's sweeps, the one loop of both modes. ``sweep(theta)`` is one
    coordinate-descent sweep. Sweeps run under a ``while_loop`` until the
    root-mean-square change of one is under ``tol`` or ``max_iter`` have run
    (reference lasso.py:166-171); the carry holds the outcome of ``diff <
    tol``, so a NaN change keeps sweeping as a host's ``float(diff) < tol``
    would. ``max_iter`` (int32) and ``tol`` (float32) are traced scalars.
    Returns ``(theta, n_iter, diff)``, the change the last sweep's (infinite
    when none ran)."""

    def body(carry):
        done, old, _, _ = carry
        new = sweep(old)
        diff = jnp.sqrt(jnp.mean((new - old) ** 2))
        return done + 1, new, diff < tol, diff

    done, theta, _, diff = jax.lax.while_loop(
        lambda carry: jnp.logical_and(carry[0] < max_iter, jnp.logical_not(carry[2])),
        body,
        (jnp.zeros((), jnp.int32), theta, jnp.zeros((), bool), jnp.full((), jnp.inf, jnp.float32)),
    )
    return theta, done, diff


@jax.jit
def lasso_descent(G: jax.Array, cy: jax.Array, lam: jnp.float32, n: int, max_iter, tol):
    """Gram mode's sweeps from θ = 0 in ONE program (:func:`_descend` over
    :func:`lasso_cd_sweep`), with no collective: each chip of a mesh runs it
    whole on its replicated ``G`` and ``cy``."""
    theta = jnp.zeros((G.shape[0], 1), jnp.float32)
    return _descend(lambda th: lasso_cd_sweep(G, cy, th, lam, n), theta, max_iter, tol)


@partial(jax.jit, static_argnames=("precision",))
def _descent_residual(XT: jax.Array, y: jax.Array, lam: jnp.float32, max_iter, tol, precision=None):
    """Residual mode's sweeps from θ = 0 in ONE program (:func:`_descend`
    over :func:`_cd_sweep`); each step's all-reduce sits inside the loops."""
    theta = jnp.zeros((XT.shape[0], 1), jnp.float32)
    return _descend(lambda th: _cd_sweep(XT, y, th, lam, precision), theta, max_iter, tol)


def _tol_operand(tol: Optional[float]):
    """``tol`` as the descent program's float32 operand: ``None`` never stops
    a fit (``-inf``), and a float64 is rounded towards +inf, so that ``diff <
    tol`` on the device decides as the host's comparison of the float32
    ``diff`` with the Python float did."""
    if tol is None:
        return np.float32(-np.inf)
    with np.errstate(over="ignore"):
        rounded = np.float32(tol)
    # float(): numpy would weigh a float32 against a Python float in float32
    return np.nextafter(rounded, np.float32(np.inf)) if float(rounded) < tol else rounded


class Lasso(RegressionMixin, BaseEstimator):
    """Least absolute shrinkage and selection operator (reference lasso.py:14-89).

    The first column of ``x`` is the intercept (all ones, not penalised; no
    column is prepended), and the others are expected at UNIT MEAN SQUARE, as
    upstream's coordinate step takes them: it divides by no column norm, so on
    columns of another scale the iterates are not the lasso's and may grow
    without bound (the module docstring has the numbers). ``fit`` multiplies
    float32 rows in float32 and bfloat16 rows in one bfloat16 pass with float32
    accumulation, read off ``x``'s dtype; tall operands up to 2 048 features
    are fitted from their Gram, summed over row chunks, with no transposed copy.

    Parameters
    ----------
    lam : float
        L1 penalty strength.
    max_iter : int
    tol : float
    """

    def __init__(self, lam: float = 0.1, max_iter: int = 100, tol: float = 1e-6):
        self.__lam = lam
        self.max_iter = max_iter
        self.tol = tol
        self.__theta = None
        self.n_iter = None

    @property
    def lam(self) -> float:
        return self.__lam

    @lam.setter
    def lam(self, arg: float):
        self.__lam = arg

    @property
    def coef_(self) -> Optional[DNDarray]:
        return None if self.__theta is None else self.__theta[1:]

    @property
    def intercept_(self) -> Optional[DNDarray]:
        return None if self.__theta is None else self.__theta[0]

    @property
    def theta(self):
        return self.__theta

    def fit(self, x: DNDarray, y: DNDarray) -> "Lasso":
        """Coordinate-descent fit (reference lasso.py:90-141).

        While ``telemetry.tracing()`` the fit is a ``heat.lasso.fit`` span
        (stats ``mode=gram|residual``, ``n``, ``m``, ``p``, ``sweeps``) whose
        children lie side by side: ``.prepare`` (casts and reshapes; in
        residual mode the transposed copy), ``.gram`` (the dispatch of the
        precompute, Gram mode only), ``.dispatch`` (the descent program's one
        call: every sweep and its convergence check), ``.sync`` (the wait
        until the device has run them all) and ``.copy`` (the fit's one read:
        the sweeps run and the last change, ``telemetry.ready_then``), then
        ``.wrap`` (θ into a ``DNDarray``), each once a fit; the same
        intervals add to ``fusion.cache_stats()``'s ``phase_lasso_*`` keys."""
        if not isinstance(x, DNDarray) or not isinstance(y, DNDarray):
            raise TypeError("x and y must be DNDarrays")
        if x.ndim != 2:
            raise ValueError(f"x needs to be 2D, but was {x.ndim}D")
        if y.ndim > 2:
            raise ValueError(f"y needs to be 1D or 2D, but was {y.ndim}D")
        n, m = (int(s) for s in x.shape)
        # Gram (covariance-update) mode whenever the (m, m) Gram replicates
        # cheaply: ALL sample-axis contractions happen once up front (one
        # matmul + one matvec, one all-reduce each) and every sweep is then
        # local m-vector work — the collective budget per fit drops from
        # m·iterations all-reduces to two. Falls back to the incremental-
        # residual sweep for very wide operands.
        gram_mode = m * m <= _GRAM_MAX_ELEMENTS and n >= m
        if not telemetry.tracing():
            self._fit(x, y, gram_mode, telemetry.no_phase)
            return self
        ph = telemetry.Phases(
            "heat.lasso.fit", mode="gram" if gram_mode else "residual", n=n, m=m, p=x.comm.size
        )
        try:
            sweeps = self._fit(x, y, gram_mode, ph.phase)
            ph.note(sweeps=sweeps)
        finally:
            ph.close()
        fusion.note_phases("lasso", ph.ns, fits=1, sweeps=sweeps, syncs=1)
        return self

    def _fit(self, x: DNDarray, y: DNDarray, gram_mode: bool, mark) -> int:
        """:meth:`fit` past its checks. ``mark(name)`` opens the fit's next
        phase (``telemetry.Phases.phase``; nothing when the fit is not
        traced). Returns the sweeps the device ran, which the fit's one
        blocking host read brought back."""
        mark("prepare")
        # as in the reference, the first column of x is treated as the
        # (unregularized) intercept feature — no ones column is prepended
        # (reference lasso.py:150-165)
        if gram_mode and x.split != 0 and x.comm.size > 1:
            x = manipulations.resplit(x, 0)  # the Gram program takes each chip's own rows
        rows = x.parray if gram_mode else x.larray  # the physical rows: a ragged split's padding is masked there
        precision = mxu_precision(rows.dtype)  # the rows' own dtype decides, before any cast
        if not (gram_mode and rows.dtype == jnp.bfloat16):
            rows = rows.astype(jnp.float32)  # float32 rows come back as they are
        yl = y.larray.astype(jnp.float32).reshape(-1, 1)
        n = int(x.shape[0])
        # operands of the descent program, which holds the loop over sweeps and the
        # rmse convergence criterion (reference lasso.py:166-171): no value of them compiles anew
        lam, max_iter, tol = np.float32(self.__lam), np.int32(self.max_iter), _tol_operand(self.tol)
        if gram_mode:
            mark("gram")
            comm, padding = x.comm, rows.shape[0] - n
            if padding:
                yl = jnp.pad(yl, ((0, padding), (0, 0)))
            G, cy = _gram_precompute(comm.mesh, comm.axis_name, n if padding else None)(rows, yl)
            mark("dispatch")
            theta, n_iter, diff = lasso_descent(G, cy, lam, n, max_iter, tol)
        else:
            XT = jnp.transpose(rows)  # one pass; every sweep slice is contiguous
            mark("dispatch")
            theta, n_iter, diff = _descent_residual(XT, yl, lam, max_iter, tol, precision=precision)
        # the fit's one read waits for the whole program: theta is final when it returns
        n_iter, _ = telemetry.ready_then(mark, (n_iter, diff), jax.device_get, "sync")
        self.n_iter = int(n_iter)
        mark("wrap")
        arr = _ensure_split(theta, None, x.comm)
        self.__theta = DNDarray(
            arr, tuple(arr.shape), types.canonical_heat_type(arr.dtype), None, x.device, x.comm
        )
        return self.n_iter

    def predict(self, x: DNDarray) -> DNDarray:
        """Linear prediction with learned coefficients (reference lasso.py:142-176)."""
        if self.__theta is None:
            raise RuntimeError("fit needs to be called before predict")
        rows = x.larray
        pred = jnp.matmul(rows.astype(jnp.float32), self.__theta.larray, precision=mxu_precision(rows.dtype))
        pred = _ensure_split(pred, x.split, x.comm)
        return DNDarray(
            pred, tuple(pred.shape), types.canonical_heat_type(pred.dtype), x.split, x.device, x.comm
        )
