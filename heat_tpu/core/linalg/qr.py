"""QR decomposition.

TPU-native re-design of reference heat/core/linalg/qr.py:17-1042. The
reference implements tile-CAQR: per-tile-column local QRs with cross-process
Householder merges (qr.py:319-608 for split=0, :866-1042 for split=1) driven
by ``SquareDiagTiles``. The TPU rendering keeps a distributed schedule for
every split:

* **split=0, m >= n** — **TSQR**: each device QR-factors its row block, the
  small R factors are all-gathered and factored once more, and the final Q is
  one local matmul per device. Ragged row counts ride the runtime's pad+mask
  contract: zero row-blocks contribute zero R factors, so the padding rows of
  Q come out exactly zero and slice off (replaces reference qr.py:319-865).
* **split=1** — **blocked panel loop**: sequentially per device-panel, the
  owner QR-factors its (updated) panel, broadcasts the Q panel, and all later
  panels are orthogonalized against it with a two-pass block Gram-Schmidt
  (CGS2) update — the reference's ``__split1_qr_loop`` Bcast-per-panel
  schedule (qr.py:866-1042) with the Householder merge replaced by the
  TPU-friendlier panel-QR + reorthogonalized projection. Q and R come out
  column-split, like the reference's.
* **split=None / short-wide (m < n with ragged rows)** — one replicated XLA
  QR kernel; above ``_REPLICATED_MAX_ELEMENTS`` this emits a warning instead
  of silently gathering (the reference covers these shapes with its tile
  loops; the replicated fallback is explicit policy here, never silent).
"""

from __future__ import annotations

import collections
import functools
import warnings
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .. import factories, resilience, sanitation, telemetry, types
from ..communication import sanitize_comm
from ..dndarray import DNDarray, _ensure_split

__all__ = ["qr"]

# payload access here forces pending chains under the "collective" trigger,
# and each schedule declares its collective budget to the telemetry ledger
# (the schedule IS the algorithm — counts are per wrapper call)
_T_COLLECTIVE = telemetry.force_trigger("collective")

QR = collections.namedtuple("QR", "Q, R")

# replicated fallback (split=1 short-wide etc.) is explicit policy below this
# size and a loud warning above it — never a silent gather
_REPLICATED_MAX_ELEMENTS = 1 << 22


def qr(
    a: DNDarray,
    tiles_per_proc: int = 1,
    calc_q: bool = True,
    overwrite_a: bool = False,
    method: str = "auto",
) -> QR:
    """Reduced QR decomposition of a 2-D DNDarray (reference qr.py:17-179).

    ``tiles_per_proc``/``overwrite_a`` are accepted for API parity; the TSQR /
    panel schedules have no tile-count knob and never mutate their input.

    ``method``: ``"auto"`` (default), ``"tsqr"`` (Householder-based,
    unconditionally stable), or ``"cholqr2"``. CholeskyQR2 factors tall-skinny
    operands as R from ``chol(AᵀA)``, Q by triangular solve, repeated once
    for re-orthonormalization. Every FLOP is a matmul, so on TPU it runs on
    the MXU where Householder QR is mostly vector work; the price is a
    squared condition number in the first pass — safe for
    ``cond(A) ≲ 1/√ε`` (~3e3 f32 / ~7e7 f64). Breakdown detection is a
    single fused on-device probe (one host read): non-finite Cholesky OR
    first-pass orthogonality error ``‖Q1ᴴQ1 − I‖ >= 0.5`` — the latter
    catches operands near the ``1/√ε`` bound whose Gram Cholesky stays
    finite while Q silently degrades below Householder quality (the second
    pass only restores orthogonality while that error is < 1).
    ``method="cholqr2"`` raises on the probe rather than returning garbage.
    ``"auto"`` tries the MXU-native CholeskyQR2 first for genuinely
    tall-skinny operands (``m >= 2n``, Gram small enough to replicate,
    split != 1 — the panel path's split-1 R layout must not depend on
    conditioning) and falls back to TSQR on the same probe instead of
    raising — the all-matmul speed when conditioning allows, Householder
    stability when it does not. ``"auto"`` is the default because
    CholeskyQR2's tall work is all GEMMs (MXU), where Householder TSQR is
    mostly vector work: on one v5e chip, 1 250 000 x 512 float32, a call
    takes 62 ms (87 before its tall products left out the blocks that are
    a mirror or zero) against 1.66 s for ``method="tsqr"`` (there one
    replicated Householder ``jnp.linalg.qr``; PERF.md, PRs 35 and 36).

    While ``telemetry.tracing()`` a call is a ``heat.qr`` span (stats
    ``mode=cholqr2|tsqr|panel|replicated``, ``m``, ``n``, ``p``, ``calc_q``,
    ``blocks``: the column blocks the CholeskyQR2 program took its tall
    products in, 1 where they ran whole or no such program ran)
    whose children lie side by side: ``.prepare`` (sanitation, promotion),
    ``.dispatch`` (recording the multi-output node, or the eager jitted
    call), ``.sync`` (the probe's force, whose ``heat.force`` spans nest
    under it, and the wait until the device has made it), ``.copy`` (the
    probe's one read, ``telemetry.ready_then``), ``.wrap`` (the ``DNDarray``s); the
    same intervals add to ``fusion.cache_stats()``'s ``phase_qr_*`` keys,
    with the calls, their blocking reads, the probes that fell back and the
    calls whose CholeskyQR2 program took the blocked products.
    """
    if not telemetry.tracing():
        return _qr(a, calc_q, method, telemetry.no_phase)[0]
    from .. import fusion

    ph = telemetry.Phases("heat.qr", calc_q=int(bool(calc_q)))
    try:
        out, mode, syncs, fallbacks = _qr(a, calc_q, method, ph.phase)
        # the CholeskyQR2 program ran exactly where its probe was read
        blocks = _block_count(int(a.shape[1])) if syncs else 1
        ph.note(mode=mode, m=int(a.shape[0]), n=int(a.shape[1]), p=a.comm.size, blocks=blocks)
    finally:
        ph.close()
    fusion.note_phases(
        "qr", ph.ns, calls=1, syncs=syncs, fallbacks=fallbacks, blocked=int(blocks > 1)
    )
    return out


def _qr(a: DNDarray, calc_q: bool, method: str, mark) -> Tuple[QR, str, int, int]:
    """:func:`qr` itself. ``mark(name)`` opens the call's next phase
    (``telemetry.Phases.phase``; nothing when the call is not traced).
    Returns the factors, the path that made them (``cholqr2``, ``tsqr``,
    ``panel`` or ``replicated``), the blocking host reads made (the
    CholeskyQR2 probe's one) and the CholeskyQR2 attempts whose probe failed
    and fell to Householder (``auto`` only: ``method="cholqr2"`` raises)."""
    mark("prepare")
    sanitation.sanitize_in(a)
    if a.ndim != 2:
        raise ValueError(f"qr requires a 2-D array, got {a.ndim}-D")
    if method not in ("tsqr", "cholqr2", "auto"):
        raise ValueError(
            f"unknown qr method {method!r}: expected 'tsqr', 'cholqr2' or 'auto'"
        )
    if not types.heat_type_is_inexact(a.dtype) or a.dtype in (
        types.bfloat16,
        types.float16,
    ):
        # ints AND half floats factor in f32: XLA's qr/cholesky lowerings
        # have no half-precision kernels (factors return as float32)
        a = a.astype(types.promote_types(a.dtype, types.float32))

    m, n = a.shape
    comm = a.comm
    p = comm.size

    q_split = a.split
    r_split: Optional[int] = None
    q_arr = r_arr = None
    mode, syncs, fallbacks = "cholqr2", 0, 0
    if (
        method == "auto"
        # genuinely tall-skinny only: the probe factors a REPLICATED (n, n)
        # Gram, so a large square operand would silently replicate — the
        # exact degradation class warn_replicated polices. The aspect bound
        # also keeps the probe where CholeskyQR2's all-matmul profile wins.
        and m >= 2 * n
        and n * n <= _REPLICATED_MAX_ELEMENTS
        # split=1 stays on the panel path: its R is split=1 by contract, and
        # a conditioning-dependent layout flip (replicated R on probe
        # success) would break layout-dependent callers intermittently
        and a.split != 1
    ):
        # try the MXU-native CholeskyQR2, fall back to Householder on the
        # breakdown/conditioning probe (one host scalar read; the probe also
        # catches finite-but-degraded orthogonality, see _cholqr2_kernel).
        # Deferred-first: the passes record as a multi-output collective
        # node, the probe read forces Q/R/ok in ONE dispatch, and a pending
        # operand chain compiles into the same program.
        mark("dispatch")
        deferred = _cholqr2_deferred(a, calc_q, mark)
        syncs = 1
        if deferred is not None:
            q_d, r_d, ok_d = deferred
            if ok_d:
                return QR(q_d, r_d), mode, syncs, fallbacks
        else:
            with _T_COLLECTIVE:
                q_try, r_try, ok = _cholqr2_kernel(a.larray, calc_q)
            _record_cholqr2_collectives(a)  # the Gram psums ran either way
            mark("sync")
            if bool(ok):
                q_arr, r_arr = q_try, r_try
        fallbacks = int(r_arr is None)
    elif method == "cholqr2":
        if m < n:
            raise ValueError(f"cholqr2 requires a tall operand (m >= n), got {a.shape}")
        mark("dispatch")
        deferred = _cholqr2_deferred(a, calc_q, mark)
        syncs = 1
        if deferred is not None:
            q_d, r_d, ok_d = deferred
            if not ok_d:
                raise ValueError(_CHOLQR2_BREAKDOWN_MSG)
            return QR(q_d, r_d), mode, syncs, fallbacks
        with _T_COLLECTIVE:
            q_arr, r_arr, ok = _cholqr2_kernel(a.larray, calc_q)
        _record_cholqr2_collectives(a)
        mark("sync")
        if not bool(ok):
            raise ValueError(_CHOLQR2_BREAKDOWN_MSG)

    if r_arr is None:  # no CholeskyQR2 result: Householder dispatch
        mark("dispatch")
        # TSQR needs a full (n, n) R per block: block = ceil(m/p) >= n,
        # otherwise the R-tile all-gather would move p*block*n = the FULL
        # operand volume — exactly the silent gather the explicit fallback
        # policy exists to avoid
        if a.split == 0 and p > 1 and m >= n and -(-m // p) >= n:
            mode = "tsqr"
            deferred = _tsqr_deferred(a, comm)
            if deferred is not None:
                mark("wrap")
                q_d, r_d = deferred
                # calc_q=False: the unused Q pick is never walked into a
                # program, so XLA dead-code-eliminates the formation matmul
                return QR(q_d if calc_q else None, r_d), mode, syncs, fallbacks
            q_arr, r_arr = _tsqr(a, comm)
        elif a.split == 1 and p > 1 and m >= n:
            mode = "panel"
            q_arr, r_arr = _panel_qr_split1(a, comm)
            r_split = 1
        else:
            mode = "replicated"
            # replicated or short-wide: one XLA QR kernel over the gathered
            # operand — explicit policy with a size guard, never silent (the
            # shared warn_replicated helper so callers can filter one class)
            if a.is_distributed() and a.size > _REPLICATED_MAX_ELEMENTS:
                sanitation.warn_replicated(
                    "qr",
                    f"no gather-free distributed schedule for shape {a.shape} "
                    f"split={a.split} (short-wide, or row blocks narrower than "
                    "n); consider resplit or a transpose formulation",
                )
            q_arr, r_arr = jnp.linalg.qr(a.larray, mode="reduced")
            r_split = 1 if a.split == 1 else None

    mark("wrap")
    r = DNDarray(
        _ensure_split(r_arr, r_split, comm),
        tuple(r_arr.shape),
        types.canonical_heat_type(r_arr.dtype),
        r_split,
        a.device,
        comm,
    )
    if not calc_q or q_arr is None:
        return QR(None, r), mode, syncs, fallbacks
    q = DNDarray(
        _ensure_split(q_arr, q_split, comm),
        tuple(q_arr.shape),
        types.canonical_heat_type(q_arr.dtype),
        q_split,
        a.device,
        comm,
    )
    return QR(q, r), mode, syncs, fallbacks


def _record_cholqr2_collectives(a: DNDarray) -> None:
    """Declared CholeskyQR2 schedule: each of the two passes' Gram
    contractions psums its partial over the split axis, ONE all-reduce a pass
    (GSPMD inserts it when the operand rows are sharded; replicated operands
    move nothing): the (n, n) partial where the products run whole, the block
    rows of its upper block triangle (:func:`_gram_entries`) where blocked."""
    if a.split != 0 or not a.comm.is_distributed():
        return  # replicated operand: the Gram contractions move nothing
    if resilience._ARMED:
        # the declared schedule's fault site: fires exactly when the psums
        # will actually ride the dispatch, telemetry on or off
        resilience.check("collective.allreduce")
    if not telemetry._MODE:
        return
    n = int(a.shape[1])
    acc = jnp.result_type(a.larray.dtype, jnp.float32)
    telemetry.record_collective(
        "allreduce", a.comm.axis_name, _gram_entries(n) * jnp.dtype(acc).itemsize, str(acc), count=2
    )


_CHOLQR2_BREAKDOWN_MSG = (
    "cholqr2 broke down (non-finite Cholesky of the Gram matrix, or "
    "first-pass orthogonality error ‖Q1ᴴQ1 − I‖ >= 0.5): the operand "
    "is rank-deficient or too ill-conditioned (cond ≳ 1/√ε) for the "
    "squared-condition first pass — use method='tsqr'"
)


def _cholqr2_deferred(a: DNDarray, calc_q: bool, mark):
    """Record the CholeskyQR2 passes as a multi-output collective node: the
    Gram psums compile into the producing chain's program, and the breakdown
    probe's ONE host read forces Q/R/ok together (sibling batching — one
    dispatch, one blocking sync). Returns ``(q, r, ok)`` with Q/R as DNDarray
    wrappers (Q None when ``calc_q=False``), or None to decline (collectives
    off, tracer payloads, record failures → the eager jitted kernel).
    ``mark`` is the caller's (:func:`_qr`): the force and the wait for the
    device are its ``sync`` phase, the read of the ready probe its ``copy``."""
    from .. import fusion

    if not fusion.collectives_active():
        return None
    nodes = fusion.defer_multi(_cholqr2_op, (a,), calc_q=calc_q)
    if nodes is None:
        return None
    _record_cholqr2_collectives(a)  # the Gram psums ride the dispatch
    m, n = (int(s) for s in a.shape)
    if calc_q:
        qn, rn, okn = nodes
        q = fusion.wrap_node(qn, (m, n), a.split, a)
    else:
        rn, okn = nodes
        q = None
    r = fusion.wrap_node(rn, (n, n), None, a)
    mark("sync")
    with _T_COLLECTIVE:
        ok = fusion.force(okn)
    ok = telemetry.ready_then(mark, ok, bool, "sync")  # the call's one blocking read
    mark("wrap")
    return q, r, ok


@functools.lru_cache(maxsize=None)
def _tsqr_kernel(axis: str, block: int, n: int, p: int):
    """The TSQR reduction tree as an UNJITTED multi-output kernel for the
    deferred path — the same schedule as :func:`_tsqr_program`, handed to
    ``fusion.defer_apply`` so the R-stack all_gather compiles INTO the
    enclosing chain's program. Cached for one function identity per layout
    (one program-cache key)."""
    k1 = min(block, n)

    def kernel(xs):  # xs: (block, n) per device
        q1, r1 = jnp.linalg.qr(xs, mode="reduced")  # (block, k1), (k1, n)
        rs = jax.lax.all_gather(r1, axis)  # (p, k1, n) — the one ICI collective
        q2, r = jnp.linalg.qr(rs.reshape(p * k1, n), mode="reduced")
        idx = jax.lax.axis_index(axis)
        q2_block = jax.lax.dynamic_slice_in_dim(q2, idx * k1, k1, axis=0)
        return q1 @ q2_block, r

    kernel.__name__ = f"tsqr_b{block}_n{n}"
    return kernel


def _tsqr_deferred(a: DNDarray, comm):
    """Record TSQR as a multi-output collective node (Q row-split, R
    replicated) — pending operand chains stay pending and the allgather
    compiles into their program. Returns ``(q, r)`` DNDarray wrappers, or
    None to decline (collectives off, ragged rows → the eager pad+mask
    path, tracer payloads, record failures)."""
    from .. import fusion

    if not fusion.collectives_active() or a.padded:
        return None
    m, n = (int(s) for s in a.shape)
    p = comm.size
    block = m // p  # unpadded row split: m divides evenly
    nodes = fusion.defer_apply(
        comm,
        _tsqr_kernel(comm.axis_name, block, n, p),
        (a,),
        in_splits=(0,),
        out_split=(0, None),
        check_vma=False,
    )
    if nodes is None:
        return None
    if resilience._ARMED:
        # the declared schedule's fault site (one in-kernel all_gather) —
        # record time is dispatch time for deferred kernels
        resilience.check("collective.allgather")
    if telemetry._MODE:
        k1 = min(block, n)
        itemsize = jnp.dtype(a.dtype.jax_type()).itemsize
        telemetry.record_collective(
            "allgather", comm.axis_name, p * k1 * n * itemsize, str(a.dtype.jax_type())
        )
    return (
        fusion.wrap_node(nodes[0], (m, n), 0, a),
        fusion.wrap_node(nodes[1], (n, n), None, a),
    )


@functools.lru_cache(maxsize=None)
def _tsqr_program(mesh, axis: str, block: int, n: int, p: int, dtype_name: str):
    """Compiled TSQR kernel over the row-padded (p*block, n) operand."""
    from jax.sharding import PartitionSpec as P

    k1 = min(block, n)

    def kernel(xs):  # xs: (block, n) per device
        q1, r1 = jnp.linalg.qr(xs, mode="reduced")  # (block, k1), (k1, n)
        rs = jax.lax.all_gather(r1, axis)  # (p, k1, n) — the one ICI collective
        q2, r = jnp.linalg.qr(rs.reshape(p * k1, n), mode="reduced")
        idx = jax.lax.axis_index(axis)
        q2_block = jax.lax.dynamic_slice_in_dim(q2, idx * k1, k1, axis=0)
        return q1 @ q2_block, r

    return jax.jit(
        jax.shard_map(
            kernel,
            mesh=mesh,
            in_specs=P(axis, None),
            out_specs=(P(axis, None), P(None, None)),
            # R is replicated by construction (every device factors the same
            # gathered stack); the varying-axis checker cannot infer that.
            check_vma=False,
        )
    )


def _tsqr(a: DNDarray, comm) -> Tuple[jax.Array, jax.Array]:
    """Tall-skinny QR over the row-sharded array (reference qr.py:319-865).

    Schedule (the TSQR reduction tree):
      1. local QR of each (block, n) row block          — compute only
      2. all_gather of the p (k1, n) R factors          — one ICI collective
      3. QR of the stacked (p*k1, n) matrix (replicated)— small, redundant
      4. local Q1 @ Q2-block                            — compute only

    Ragged m rides pad+mask: zero row-blocks yield zero R factors, so the
    padded rows of Q are exactly zero and are sliced off.
    """
    m, n = a.shape
    p = comm.size
    with _T_COLLECTIVE:
        phys = a.parray  # (p*block, n), zero rows past m
    block = int(phys.shape[0]) // p
    k1 = min(block, int(n))
    if resilience._ARMED:
        # the declared schedule's fault site (one in-kernel all_gather) —
        # fires with the dispatch below, like the communication verbs
        resilience.check("collective.allgather")
    if telemetry._MODE:
        # declared schedule: ONE all_gather of the p (k1, n) R factors
        telemetry.record_collective(
            "allgather",
            comm.axis_name,
            p * k1 * int(n) * phys.dtype.itemsize,
            str(phys.dtype),
        )
    fn = _tsqr_program(comm.mesh, comm.axis_name, block, int(n), p, str(phys.dtype))
    q_pad, r = fn(phys)
    if a.padded:
        q_pad = q_pad[:m]  # zero padding rows slice off
    return q_pad, r


@functools.lru_cache(maxsize=None)
def _panel_program(mesh, axis: str, m: int, c: int, n: int, p: int, dtype_name: str):
    """Compiled split=1 blocked panel-QR kernel (reference qr.py:866-1042)."""
    from jax.sharding import PartitionSpec as P

    def bcast(v, root):
        idx = jax.lax.axis_index(axis)
        masked = jnp.where(idx == root, v, jnp.zeros_like(v))
        return jax.lax.psum(masked, axis)

    def kernel(a_loc):  # (m, c) per device
        idx = jax.lax.axis_index(axis)

        # fori_loop over the p panels (not an unrolled chain): program size
        # stays O(1) in the mesh size — tests/test_mesh64_compile
        def panel(d, carry):
            a_cur, q_loc, r_loc = carry
            # panel owner factors its (already orthogonalized) panel; every
            # device computes a QR but only the owner's is broadcast — the
            # XLA rendering of the reference's per-panel Bcast (qr.py:907-955)
            qd_own, rd_own = jnp.linalg.qr(a_cur, mode="reduced")
            qd = bcast(qd_own, d)  # (m, c)
            rdd = bcast(rd_own, d)  # (c, c)
            later = idx > d
            # two-pass block Gram-Schmidt (CGS2) of later panels against qd
            qdh = jnp.conjugate(qd).mT  # Q^H: correct for complex panels too
            coef1 = qdh @ a_cur  # (c, c)
            a_upd = a_cur - qd @ coef1
            coef2 = qdh @ a_upd
            a_upd = a_upd - qd @ coef2
            a_cur = jnp.where(later, a_upd, a_cur)
            # R rows d*c:(d+1)*c of this device's column block
            r_rows = jnp.where(
                idx == d, rdd, jnp.where(later, coef1 + coef2, jnp.zeros_like(rdd))
            )
            r_loc = jax.lax.dynamic_update_slice(r_loc, r_rows, (d * c, 0))
            q_loc = jnp.where(idx == d, qd, q_loc)
            return a_cur, q_loc, r_loc

        _, q_loc, r_loc = jax.lax.fori_loop(
            0, p, panel, (a_loc, jnp.zeros_like(a_loc), jnp.zeros((n, c), a_loc.dtype))
        )
        return q_loc, r_loc

    return jax.jit(
        jax.shard_map(
            kernel,
            mesh=mesh,
            in_specs=P(None, axis),
            out_specs=(P(None, axis), P(None, axis)),
            check_vma=False,
        )
    )


def _panel_qr_split1(a: DNDarray, comm) -> Tuple[jax.Array, jax.Array]:
    """Column-split blocked panel QR (reference qr.py:866-1042).

    Sequential over the p device panels: the owner QR-factors its panel,
    broadcasts the (m, c) Q panel, later panels run a CGS2 block update.
    Communication: p broadcasts of (m, c) = one full-operand volume — the
    same budget as the reference's panel Bcast schedule. Q and R come out
    column-split. Ragged n rides pad+mask: padding columns are a suffix of
    the last panel, their Q/R columns are sliced off at the end.
    """
    m, n = a.shape
    p = comm.size
    with _T_COLLECTIVE:
        phys = a.parray  # (m, p*c), zero columns past n
    c = int(phys.shape[1]) // p
    n_pad = c * p
    if resilience._ARMED:
        # the declared schedule's fault site (per-panel in-kernel bcasts)
        resilience.check("collective.bcast")
    if telemetry._MODE:
        # declared schedule: per panel, one (m, c) Q bcast + one (c, c) R bcast
        telemetry.record_collective(
            "bcast", comm.axis_name, int(m) * c * phys.dtype.itemsize, str(phys.dtype), count=p
        )
        telemetry.record_collective(
            "bcast", comm.axis_name, c * c * phys.dtype.itemsize, str(phys.dtype), count=p
        )
    fn = _panel_program(comm.mesh, comm.axis_name, int(m), c, n_pad, p, str(phys.dtype))
    q_pad, r_pad = fn(phys)
    if a.padded:
        # logical views; the DNDarray wrap re-pads along split=1
        q_pad = q_pad[:, :n]
        r_pad = r_pad[:n, :n]
    return q_pad, r_pad


_LANES = 128  # a lane tile: where XLA:TPU stores a column block in place
_MAX_BLOCKS = 8  # products per tall product: what a wider n adds is program, not work saved


def _block_width(n: int) -> int:
    """Width of the column blocks in which :func:`_cholqr2_body` takes its
    four tall products, read off the column count alone: ``n`` itself (whole
    products) unless ``n`` is a multiple of 128 with at least two lane tiles."""
    if n % _LANES or n < 2 * _LANES:
        return n
    return _LANES * -(-n // (_LANES * _MAX_BLOCKS))


def _col_blocks(n: int) -> list:
    """The ``(lo, hi)`` column blocks of an ``n``-column CholeskyQR2 (one
    block: whole products)."""
    width = _block_width(n)
    return [(lo, min(lo + width, n)) for lo in range(0, n, width)]


def _block_count(n: int) -> int:
    return len(_col_blocks(n))


def _gram_entries(n: int) -> int:
    """Entries of one pass's Gram that are computed, and all-reduced where the
    rows are sharded: the upper block triangle where blocked, all ``n * n``
    where whole (``analysis/dataflow.py`` prices the same figure)."""
    return sum((hi - lo) * (n - lo) for lo, hi in _col_blocks(n))


def _streams_half(dtype) -> bool:
    """Half-precision rows stream at their own width: one pass on the MXU,
    accumulated in float32. Everything else contracts at ``HIGHEST`` in its
    own dtype (``ops/mxu.py``'s rule)."""
    return dtype in (jnp.bfloat16, jnp.float16)


def _tall_product(a, b, contract):
    """One ``dot_general`` of tall rows, by ``a``'s dtype (:func:`_streams_half`)."""
    half = _streams_half(a.dtype)
    return jax.lax.dot_general(
        a, b, (contract, ((), ())),
        precision=None if half else jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32 if half else a.dtype,
    )


def tall_gram(x):
    """``xᴴx`` of tall rows ``x`` (m, n), contracting the (sharded) row axis:
    the ONE tall Gram of the library, CholeskyQR2's two (:func:`_cholqr2_body`)
    and ``regression/lasso.py``'s precompute, so that what a later PR does to
    it moves both. Traced inside the caller's program; under GSPMD the
    contraction ends in a psum over the split axis.

    Where :func:`_col_blocks` cuts the columns, the Gram is taken at its upper
    block triangle: block row ``i`` is ONE product ``x[:, block i]ᴴ @
    x[:, block i:]``, the diagonal block and what lies right of it, and the
    strictly lower block triangle is the conjugate mirror, so the result is
    Hermitian to the bit (its diagonal real) and every entry still contracts
    all rows in one product. Half-precision rows stream at their own width
    and accumulate in float32; float32 rows and wider multiply at
    ``HIGHEST`` (:func:`_tall_product`)."""
    n = x.shape[1]
    blocks = _col_blocks(n)
    if len(blocks) == 1:
        return _tall_product(jnp.conjugate(x), x, ((0,), (0,)))
    # block row i: the diagonal block and what lies right of it, one
    # product; what lies left of it is the mirror of a block above
    acc_t = jnp.float32 if _streams_half(x.dtype) else x.dtype
    u = jnp.zeros((n, n), acc_t)
    for lo, hi in blocks:
        row = _tall_product(jnp.conjugate(x[:, lo:hi]), x[:, lo:], ((0,), (0,)))
        u = jax.lax.dynamic_update_slice(u, row, (lo, lo))
    above = jnp.triu(u, 1)
    return above + jnp.conjugate(above).mT + jnp.diag(jnp.diagonal(u).real.astype(acc_t))


def _cholqr2_body(x, calc_q: bool = True):
    """Two CholeskyQR passes returning ``(q, r, ok)`` — the UNJITTED body
    shared by the eager jitted wrapper (:func:`_cholqr2_kernel`) and the
    deferred recording (:func:`_cholqr2_op`), so both paths run the exact
    same arithmetic.

    Everything tall is a matmul: the Gram contractions run on the MXU (and
    GSPMD turns them into psums over the split axis), and Q formation is
    ``x @ R⁻¹`` — R⁻¹ computed once per pass by an (n, n) triangular solve
    against the identity — instead of an (m, n) triangular solve. XLA lowers
    a big ``triangular_solve`` on TPU to a blocked substitution sweep that
    runs at a fraction of matmul rate; inverting the SMALL factor and
    substituting a GEMM keeps the m-dimensional work entirely on the MXU.
    Numerically the inverse of the small triangular factor is applied to the
    same operand the solve would see, and CholeskyQR2's second pass restores
    first-pass orthogonality loss either way.

    ``ok`` is the breakdown/conditioning probe, computed on-device so the
    caller pays ONE host scalar read: finite R AND ``max|Q1ᴴQ1 − I| < 0.5``.
    The second-pass Gram is exactly the first pass's orthogonality error, so
    this rejects not just NaN breakdown (rank deficiency) but the gradual
    degradation where a near-``1/√ε``-conditioned operand keeps the Cholesky
    finite while Q drifts from orthonormal: CholQR2
    theory restores full orthogonality only while ``‖Q1ᴴQ1 − I‖ < 1``.
    Hermitian Gram (``xᴴx``) so complex operands factor correctly. With
    ``calc_q=False`` the second (largest) formation matmul is skipped — R
    only needs the second pass's Cholesky factor.

    Half-precision operands STREAM at their own width: a bfloat16/float16
    ``x`` keeps its dtype on the big matmul operands (half the HBM bytes;
    Q comes back in the streamed dtype) while the Gram accumulates in f32
    (``preferred_element_type``) and the small Cholesky/inverse run f32 —
    XLA has no half-precision LAPACK kernels, and bf16 accumulation would
    be numerically void. The probe inherits the arithmetic honestly: the
    ~1e-2 bf16 quantization noise bounds the accepted conditioning far
    tighter than f32's (cond ≲ a few tens), which is the correct contract
    for a squared-condition algorithm on half-precision data. The public
    ``qr()`` never routes half dtypes here (it promotes to f32); this path
    serves callers that explicitly want the half-width stream.

    Full-width operands contract at ``Precision.HIGHEST``: the MXU's default
    f32 matmul rounds its operands to bf16 (~4e-3 relative), which caps Q's
    orthogonality at that level whatever the algorithm does — measured on a
    v5e at 1 250 000 x 512 f32, columns a decade apart (PERF.md, PR 35):
    ``‖QᵀQ − I‖_max`` 7.3e-3 at the default against 3.8e-6, and the probe's
    ``‖Q1ᵀQ1 − I‖_F`` 7.5e-2 against 1.0e-4 — and shrinks the safe
    conditioning range from ``1/√ε_f32`` to a few tens. At that shape a whole
    tall product takes 21 ms at ``HIGHEST`` (95 % of the MXU's bfloat16 rate
    over six passes), so the four tall products do only the blocks a triangle
    holds (below). What ``HIGHEST`` does not mend is the product's float32
    accumulator: over 1 250 000 rows the Gram's diagonal comes out 2.5e-5 low,
    so R's diagonal is 1.25e-5 low and Q's columns 1.25e-5 long (against a
    float64 Gram on the host: max ``|QᵀQ − I|`` 3.4e-5, off the diagonal
    2e-8); a Gram taken the same way has the same bias and reads 3.8e-6.

    **The four tall products run by column blocks** of :func:`_block_width`
    columns (read off ``n`` alone; ``n`` ragged or under 256 takes them whole,
    as one block). A Gram ``xᴴx`` is Hermitian: block row ``i`` is ONE product
    ``x[:, block i]ᴴ @ x[:, block i:]``, the diagonal block and what lies
    right of it, and the strictly lower block triangle is the conjugate mirror
    (so ``g`` is Hermitian to the bit; every entry still contracts all rows in
    one product, and on sharded rows the block rows are one all-reduce a
    pass). ``R⁻¹`` is upper triangular: column block ``j`` of ``x @ R⁻¹`` is
    ONE product that contracts down to the diagonal block and no further, the
    blocks below being zeros, stored at its constant column offset into a
    buffer born uninitialised, where XLA:TPU writes it in place (no list of
    column arrays to concatenate, no partial products to add: either costs a
    copy of the rows or re-reads an accumulator). Four blocks of 128 are ten
    block products of sixteen, 62.5 % of the FLOP: on a v5e at
    1 250 000 x 512 a Gram takes 15.2 ms and Q1 or Q 15.0 instead of 21 and
    a call 62 ms instead of 87; two blocks of 256 (75 %) take 16.3 and
    17.0 (PERF.md, PR 36)."""
    half = _streams_half(x.dtype)
    acc_t = jnp.float32 if half else x.dtype
    prec = None if half else jax.lax.Precision.HIGHEST
    n = x.shape[1]
    eye = jnp.eye(n, dtype=acc_t)
    blocks = _col_blocks(n)

    def gram_chol(x):
        # (n, n) — contracts the (sharded) row axis; psum under GSPMD
        g = tall_gram(x)
        return jnp.conjugate(jnp.linalg.cholesky(g)).mT, g  # upper factor

    def inv_upper(r):  # (n, n) solve against I: small, exact, off the hot path
        return jax.lax.linalg.triangular_solve(r, eye, left_side=False, lower=False)

    def form_q(x, r_inv):  # big GEMM; operands in the streamed dtype
        r_inv = r_inv.astype(x.dtype)
        # column block j contracts down to its diagonal block of R⁻¹ (below it
        # are zeros) and is stored where it stays: at a constant multiple of
        # 128 XLA:TPU writes it in place (spatial/distance.py::_write_tile)
        q = jax.lax.empty(x.shape, x.dtype)
        for lo, hi in blocks:
            cols = _tall_product(x[:, :hi], r_inv[:hi, lo:hi], ((1,), (0,))).astype(x.dtype)
            q = jax.lax.dynamic_update_slice(q, cols, (0, lo))
        return q

    r1, _ = gram_chol(x)
    q1 = form_q(x, inv_upper(r1))
    r2, g2 = gram_chol(q1)  # re-orthonormalization pass
    ok = _cholqr2_probe_ok(r1, r2, g2, eye)
    q2 = form_q(q1, inv_upper(r2)) if calc_q else None
    return q2, jnp.matmul(r2, r1, precision=prec), ok


_cholqr2_kernel = functools.partial(jax.jit, static_argnames=("calc_q",))(_cholqr2_body)


def _cholqr2_op(x, *, calc_q):
    """CholeskyQR2 as a recordable multi-output DAG op: the same body, with
    the calc_q=False tuple flattened (record_multi infers one aval per
    output, so None cannot ride the tuple). Under the fused program the
    Gram contractions see the sharded operand and GSPMD inserts the same
    psums the eager jitted kernel gets."""
    q, r, ok = _cholqr2_body(x, calc_q)
    if calc_q:
        return q, r, ok
    return r, ok


def _cholqr2_probe_ok(r1, r2, g2, eye):
    """The breakdown/conditioning acceptance scalar (see _cholqr2_kernel):
    both Cholesky factors finite AND first-pass orthogonality error
    ``‖Q1ᴴQ1 − I‖_F < 0.5``. The second pass provably restores
    orthonormality when the *spectral* norm ``‖Q1ᴴQ1 − I‖₂ < 1``; the
    Frobenius norm upper-bounds the spectral norm, so gating it at 0.5
    soundly implies the recovery condition (with margin) — unlike the
    element-wise max, which *lower*-bounds the spectral norm and could
    accept a matrix whose aggregate departure already exceeds 1
    (round-5 advisor finding)."""
    ok = jnp.isfinite(r2).all() & jnp.isfinite(r1).all()
    return ok & (jnp.linalg.norm(g2 - eye) < 0.5)
