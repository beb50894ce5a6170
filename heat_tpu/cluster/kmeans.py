"""K-Means clustering (reference: heat/cluster/kmeans.py).

The reference's Lloyd loop (kmeans.py:102-139) computes cdist against
replicated centroids, argmin-assigns, then per-cluster masked mean updates —
k Allreduces of (1, f) rows per iteration (kmeans.py:73-100). Here the whole
iteration is ONE jitted XLA program: the assignment is a quadratic-expansion
matmul (MXU), the update is a one-hot matmul (``onehotᵀ @ x`` — MXU again),
and the only collective is the psum GSPMD inserts for the row-sharded sums.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Union

import jax
import jax.numpy as jnp

from ..core import fusion, telemetry, types
from ..core.dndarray import DNDarray, _ensure_split
from ..ops import lloyd as _lloyd
from ..ops import mxu as _mxu
from ..spatial.distance import _sq_euclidian_fast as _sq_dist
from ._kcluster import _KCluster

__all__ = ["KMeans"]


@partial(jax.jit, static_argnames=("k",))
def _lloyd_run(data: jax.Array, centers: jax.Array, k: int, max_iter, tol):
    """One fit's Lloyd iterations in ONE XLA program, convergence check
    included (``ops/lloyd.py::_steps``, the loop and the rule the fused
    programs run by; ``max_iter`` and ``tol`` are traced scalars) — the
    reference pays an MPI round per iteration, here the host pays one dispatch
    and one read per *fit*. Returns ``(centers, labels, inertia, shift,
    n_iter)``, labels and inertia the last iteration's.

    The |x|² term of the quadratic-expansion distance is loop-invariant: the
    argmin over centers only sees −2x·cᵀ + |c|², and the inertia needs just
    the scalar Σ|x|². Hoisting it saves an (n, f) square+reduce — pure HBM
    bandwidth — per iteration."""
    xsq_sum = jnp.sum(data * data)

    def step(c, last):
        new_c, labels, inertia, shift = _lloyd_iter(data, c, k, xsq_sum)
        return (new_c, shift, labels, inertia) if last else (new_c, shift)

    return _lloyd._steps(step, centers, max_iter, tol)


def _lloyd_iter(data: jax.Array, centers: jax.Array, k: int, xsq_sum=None):
    if xsq_sum is None:
        xsq_sum = jnp.sum(data * data)
    # score = d² − |x|² (row-constant offset): same argmin, cheaper to form.
    # Both products go by ops/mxu.py's rule (float32 rows multiply in
    # float32), as the fused kernel's do
    score = jnp.sum(centers * centers, axis=1) - 2.0 * _mxu.matmul(data, centers.T)  # (n, k)
    labels = jnp.argmin(score, axis=1).astype(jnp.int32)
    onehot = jax.nn.one_hot(labels, k, dtype=data.dtype)  # (n, k)
    counts = jnp.sum(onehot, axis=0)  # (k,)
    # (k, f) — MXU; psum over the sharded rows
    sums = _mxu.matmul(onehot.T, data)
    new_centers = jnp.where(
        counts[:, None] > 0, sums / jnp.maximum(counts[:, None], 1.0), centers
    )
    # labels are the argmin, so the assigned distance is the row minimum —
    # a fused reduction instead of a gather (take_along_axis is ~100x slower
    # than the min on TPU for this shape); adding Σ|x|² restores true d²
    inertia = jnp.maximum(jnp.sum(jnp.min(score, axis=1)) + xsq_sum, 0.0)
    shift = jnp.sum((new_centers - centers) ** 2)
    return new_centers, labels, inertia, shift


class KMeans(_KCluster):
    """K-Means with Lloyd's algorithm (reference kmeans.py:14-139).

    Parameters mirror the reference: n_clusters=8, init='random',
    max_iter=300, tol=1e-4, random_state=None. ``use_fused`` (beyond the
    reference) selects the single-pass samples-in-lanes pallas Lloyd kernel
    (ops/lloyd.py): ``None`` auto-selects it on TPU backends, where it reads
    the operand once per iteration where the jnp path reads it twice, and
    the last pass of a fit writes the labels it assigned (``labels_`` is the
    assignment ``inertia_`` is summed over; no separate label pass);
    ``True`` forces it (interpret mode off-TPU — the testing path), ``False``
    pins the jnp oracle path. A kernel that fails to lower or run raises:
    there is no fallback from the fused path to the oracle.

    When a fit stops, on every path: iterations run until the shift of an
    iteration (the squared distance its centres moved, all clusters added
    up) is at most ``tol`` or ``max_iter - 1`` have run, and one more
    iteration then assigns ``labels_``, sums ``inertia_`` and moves the
    centres a last time; ``n_iter_`` counts it. That is the reference's
    per-iteration check plus one iteration, the same fixed point; a ``tol``
    the shift never reaches (a negative one) runs exactly ``max_iter``. The
    check runs on the device, inside the one program a fit dispatches
    (``ops/lloyd.py::_steps``): the host reads ``n_iter_`` and ``inertia_``
    once, when the fit is done.

    ``inertia_`` is the Σ d² of ``labels_`` to the centres that went into the
    last iteration. The fused path takes it from that pass's float32
    accumulators, ``Σ|x|² + Σ_k n_k·|c_k|² − 2 Σ_k c_k·s_k`` (``s_k`` the
    cluster's sum of rows), and sums no per-sample score. For bfloat16 rows
    it is therefore the distance to the centres as float32 holds them; before
    PR 32 it added up the kernel's scores, whose ``−2c`` is rounded to
    bfloat16, and read up to 7e-5 otherwise. On either path |x|² is added up
    in float32, so ``inertia_`` is good to a few roundings of Σ|x|² (6e-8 of
    it each): 2e-7 of itself on centred rows, 6e-5 where the rows lie 10
    standard deviations off the origin, 4e-3 at 100 and nothing at 1 000
    (``tests/test_lloyd_fused.py``); centre such rows before the fit.
    """

    def __init__(
        self,
        n_clusters: int = 8,
        init: Union[str, DNDarray] = "random",
        max_iter: int = 300,
        tol: float = 1e-4,
        random_state: Optional[int] = None,
        use_fused: Optional[bool] = None,
    ):
        if isinstance(init, str) and init in ("kmeans++", "k-means++"):
            init = "probability_based"
        self.use_fused = use_fused
        super().__init__(
            metric=_sq_dist,  # module-level identity: kernels cache across instances
            n_clusters=n_clusters,
            init=init,
            max_iter=max_iter,
            tol=tol,
            random_state=random_state,
        )

    def _fused_mode(self, x: DNDarray):
        """Resolve the Lloyd dispatch: ('single'|'sharded', interpret) or
        (None, False) for the jnp path."""
        n, f = int(x.shape[0]), int(x.shape[1])
        k = self.n_clusters
        if self.use_fused is False:
            return None, False
        if _lloyd.fused_supported(n, f, k):
            return "single", False
        if x.split == 0 and _lloyd.fused_sharded_supported(f, k):
            return "sharded", False
        if not self.use_fused:
            return None, False  # auto never interprets: jnp is faster off-TPU
        # forced off-TPU (the testing path): pallas interpret mode
        if x.split == 0 and f <= 512 and k <= 128:
            return "sharded", True
        if len(jax.devices()) == 1 and f <= 512 and k <= 128:
            return "single", True
        # use_fused=True could not be honored — say so loudly instead of
        # letting a test of the fused path pass vacuously on the jnp oracle
        import warnings

        warnings.warn(
            f"KMeans(use_fused=True) falling back to the jnp path: shape "
            f"(n={n}, f={f}, k={k}, split={x.split}) has no fused dispatch "
            "(needs f<=512, k<=128, and split=0 or a single device)",
            stacklevel=3,
        )
        return None, False

    def fit(self, x: DNDarray) -> "KMeans":
        """Cluster ``x`` (n_samples, n_features) (reference kmeans.py:102-139).

        While ``telemetry.tracing()`` the fit is a ``heat.kmeans.fit`` span
        (stats ``mode``, ``n``, ``f``, ``k``, and ``blocks`` / ``tail_blocks``:
        the grid steps of one pass of the fused kernel and those of them that
        take its masked body, 0 on the jnp path) whose children lie side by
        side: ``.init`` (the initial centres), ``.prepare`` (the dtype cast;
        no pass over the rows: the fused program reads them in place),
        ``.dispatch`` (the call of the fit's one Lloyd program, which stops
        by the class docstring's rule), ``.sync`` (the wait until the device
        has made ``n_iter_`` and ``inertia_``), ``.copy`` (the one read of the
        two together, ``telemetry.ready_then``), ``.wrap`` (centres and labels
        back into ``DNDarray``s); the same intervals add to ``fusion.cache_stats()``'s
        ``phase_kmeans_*`` keys."""
        if not isinstance(x, DNDarray):
            raise ValueError(f"input needs to be a DNDarray, but was {type(x)}")
        if x.ndim != 2:
            raise ValueError(f"input needs to be 2D, but was {x.ndim}D")
        mode, interpret = self._fused_mode(x)
        if not telemetry.tracing():
            self._fit(x, mode, interpret, telemetry.no_phase)
            return self
        ph = telemetry.Phases(
            "heat.kmeans.fit", mode=mode or "jnp",
            n=int(x.shape[0]), f=int(x.shape[1]), k=self.n_clusters,
        )
        try:
            counts = self._fit(x, mode, interpret, ph.phase)
            ph.note(blocks=counts["blocks"], tail_blocks=counts["tail_blocks"])
        finally:
            ph.close()
        fusion.note_phases("kmeans", ph.ns, fits=1, **counts)
        return self

    def _fit(self, x: DNDarray, mode, interpret: bool, mark):
        """:meth:`fit` past its checks, on the dispatch ``_fused_mode``
        resolved. ``mark(name)`` opens the fit's next phase
        (``telemetry.Phases.phase``; nothing when the fit is not traced).
        Returns the Lloyd programs dispatched and the blocking host reads
        made (one each: the fit is one program, which checks convergence
        itself) and the XLA label passes over the rows that the program ran
        (a fused program's labels are its last kernel pass's, the jnp
        program's its last iteration's: none), and ``blocks`` / ``tail_blocks``
        (``ops/lloyd.py::pass_blocks`` of one device's samples)."""
        k, n_global = self.n_clusters, int(x.shape[0])
        mark("init")
        centers = self._initialize_cluster_centers(x)
        mark("prepare")
        fdtype = jnp.promote_types(x.dtype.jax_type(), jnp.float32)
        # bfloat16 stays bfloat16 through the fused kernel (half the HBM
        # traffic of the f32 stream; accumulators are f32 inside) — the jnp
        # path and the centroids always compute in at-least-f32
        keep_bf16 = mode is not None and x.dtype.jax_type() == jnp.bfloat16
        ddtype = x.dtype.jax_type() if keep_bf16 else fdtype
        if mode == "sharded":
            # the kernel masks each device's share of the global pad itself,
            # so it consumes the PHYSICAL payload
            data = x.parray.astype(ddtype)
        else:
            data = x.larray.astype(ddtype)
        centers = jnp.asarray(centers, fdtype)

        mark("dispatch")
        max_iter, tol = int(self.max_iter), float(self.tol)
        if mode == "single":
            out = _lloyd.fused_lloyd_run(data, centers, k, max_iter, tol, interpret=interpret)
        elif mode == "sharded":
            out = _lloyd.fused_lloyd_run_sharded(
                data, centers, k, x.comm, n_global, max_iter, tol, interpret=interpret
            )
        else:
            out = _lloyd_run(data, centers, k, max_iter, tol)
        centers, labels, inertia, _, n_iter = out
        mark("sync")
        n_iter, inertia = telemetry.ready_then(mark, (n_iter, inertia), jax.device_get, "sync")
        self._n_iter, self._inertia = int(n_iter), float(inertia)
        mark("wrap")
        self._cluster_centers = DNDarray(
            _ensure_split(centers, None, x.comm),
            tuple(centers.shape),
            types.canonical_heat_type(centers.dtype),
            None,
            x.device,
            x.comm,
        )
        self._labels = self._wrap_labels(labels, x)
        epilogues = _lloyd.RUN_LABEL_EPILOGUES if mode else 0
        blocks = tail_blocks = 0
        if mode:
            # one device's samples, and the fewest valid among them any device
            # holds (the last one's): that device masks the most blocks
            rows = int(data.shape[0])
            local = rows // (x.comm.size if mode == "sharded" else 1)
            valid = min(max(n_global - (rows - local), 0), local)
            blocks, tail_blocks = _lloyd.pass_blocks(
                local, valid, int(x.shape[1]), k, jnp.dtype(ddtype).itemsize
            )
        return {
            "dispatches": 1, "syncs": 1, "label_epilogues": epilogues,
            "blocks": blocks, "tail_blocks": tail_blocks,
        }
