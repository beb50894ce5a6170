"""Eager op-chain fusion engine with a sharded-program cache.

The reference HeAT design (and our port until this module) is eager op-by-op:
every operator call dispatches its own XLA program, so a 10-op elementwise
chain pays 10 dispatches and every reduction ends in its own device sync.
This module makes the L3 engines (``core/_operations.py``) *deferred*: chains
of elementwise / broadcast / cast / reduce ops are recorded as a small
expression DAG (:class:`LazyArray` nodes stored as the ``DNDarray`` payload)
instead of being executed, and the whole chain is materialized as ONE jitted,
sharding-aware XLA program at a *forcing point*:

* ``DNDarray.parray`` / ``.larray`` access (and everything built on them:
  ``numpy()``, ``item()``, printing, I/O, indexing, collectives, linalg,
  ``out=`` buffers, mixed eager fallbacks),
* flattening for ``jax.jit`` pytree pipelines (``_tree_flatten``).

Compiled programs live in an LRU keyed on (DAG structure — op identities,
topology, static kwargs — leaf logical shapes, dtypes, shardings i.e. split
axes + mesh), so steady-state loops hit compiled code with zero retraces.
Reductions record lazily too: a chain of k reductions feeding one consumer
costs ONE device sync at the forcing point instead of k.

Correctness stance
------------------
* *Always-correct, transparently-forced*: any access to the physical payload
  forces the chain; no user-visible API returns unmaterialized state.
* The pad+mask ragged contract is preserved: identical-layout chains compute
  on the *physical* (padded) payloads, so padding garbage stays in padding
  through fused programs; reductions across the split axis record an explicit
  un-pad slice so padding never enters the reduction (exactly the eager
  engines' rule).
* Anything the recorder cannot prove deferrable (``out=``/``where=`` buffers,
  unhashable kwargs, tracer payloads under an enclosing ``jax.jit``, padded
  broadcasts, shape-changing "local" ops) falls back to the eager engine
  unchanged.

``HEAT_TPU_FUSION=0`` is the escape hatch: it disables recording (the eager
engines run exactly as before); forcing of already-recorded nodes keeps
working regardless of the flag.

Guarded forcing (``core/resilience.py``): a fused program that fails to
trace/compile/execute does NOT abort the chain — ``force()`` degrades to
per-op eager dispatch (bitwise the eager engines' result), records a
``degraded`` telemetry event, and quarantines the DAG key so steady-state
loops skip the doomed compile from then on. The
``fusion.record``/``fusion.compile``/``fusion.execute`` injection sites
exist so tests can trigger exactly these failures deterministically.

Collective-aware fusion + asynchronous forcing
----------------------------------------------
The DAG also records **collective nodes**, so chains spanning communication
compile into the SAME cached program instead of fencing at each collective
(the GSPMD lesson: let XLA schedule the psums inside one partitioned
program):

* split-axis reductions already record through ``defer_reduce`` — the psum
  is GSPMD-inserted when the fused program compiles;
* ``defer_reshard`` records a redistribution (``resplit_`` / out-of-place
  ``resplit`` of a pending chain) as a ``with_sharding_constraint`` node
  (plus explicit un-pad/re-pad nodes for ragged splits);
* ``defer_apply`` records a ``MeshCommunication.apply``-style shard_map
  kernel (single-output) as a DAG node, so record→kernel→record chains stay
  one program.

Forcing is **asynchronous**: ``force()`` dispatches the fused program and
installs the resulting ``jax.Array`` futures without blocking or reading
device data — only genuine host boundaries (``float()``/``item()``,
``numpy()``, printing, I/O shard reads) synchronize. Independent DAG roots
alive at a forcing point (tracked in a weak registry of pending wrappers)
are batched into ONE multi-output jitted program when their results are
small (``HEAT_TPU_FUSION_BATCH_BYTES``), so e.g. ``mean``/``var``/``std``
of one operand cost one dispatch and at most one blocking sync.

Fault-site contract: ``collective.reshard``/``collective.apply`` fire at
*record* time (every deferral, before any metadata mutates); the in-kernel
``collective.<verb>`` sites fire whenever the kernel is actually traced
(first record of a signature, and the fused program's compile). Telemetry's
``collective_counts()`` therefore only sees collectives at trace time once
they ride fused programs — ``record_fused_collective`` counts the recorded
collective nodes and :func:`program_hlo` + ``telemetry.hlo_collective_counts``
cross-check the compiled program.

``HEAT_TPU_FUSION_COLLECTIVES=0`` is the escape hatch restoring
force-at-collective behavior (no collective nodes, no multi-root batching);
``HEAT_TPU_FUSION=0`` still disables recording entirely.

Memory observability (``core/memledger.py``)
--------------------------------------------
The dispatch seam here is also the memory seam: every force's results are
tagged into the live-buffer ledger (``fusion`` owner until a wrapper claims
them), ``_estimate_cost`` banks XLA's ``memory_analysis`` static peaks per
program, ``HEAT_TPU_MEMORY_BUDGET`` is checked before each dispatch
(``warn``/``raise``/``drain`` policies — drain blocking-syncs the other
outstanding async roots first), and a dispatch that dies of memory
exhaustion (injectable at the ``memory.exhausted`` site) produces a ranked
OOM forensic before degrading through the guarded path.
"""

from __future__ import annotations

import functools
import hashlib
import itertools
import os
import threading
import time
import weakref
from collections import OrderedDict
from contextlib import contextmanager
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import health_runtime, memledger, resilience, telemetry

__all__ = [
    "LazyArray",
    "ProgramCostWarning",
    "active",
    "collectives_active",
    "collectives_disabled",
    "cost_error_count",
    "disabled",
    "defer_apply",
    "defer_matmul",
    "defer_multi",
    "defer_op",
    "defer_reshard",
    "force",
    "phys_node",
    "record_multi",
    "is_deferred",
    "cache_stats",
    "clear_cache",
    "clear_quarantine",
    "program_audit_info",
    "program_costs",
    "program_hlo",
    "programs",
    "register_root",
    "wrap_node",
]


class ProgramCostWarning(UserWarning):
    """A cached program's cost estimate failed in the backend (the estimate
    carries ``cost["error"]``); failures are counted into
    ``report()["programs"]["cost_errors"]`` and warned once per session —
    never buried silently."""

_OFF_VALUES = ("0", "false", "off", "no")

# recording beyond this chain depth force-materializes the sub-chain first:
# unbounded deferral would otherwise grow the DAG (and the compiled program)
# without limit in loops that never hit a forcing point
_MAX_CHAIN = int(os.environ.get("HEAT_TPU_FUSION_MAX_CHAIN", "128"))
_CACHE_SIZE = int(os.environ.get("HEAT_TPU_FUSION_CACHE", "512"))
# DAG keys whose fused program failed once stay quarantined (replayed per-op
# eagerly, never re-jitted) up to this many keys — steady-state loops must
# not pay a doomed compile attempt every step
_QUARANTINE_SIZE = int(os.environ.get("HEAT_TPU_FUSION_QUARANTINE", "256"))


# the escape hatch is read ONCE at import (a per-op os.environ lookup is
# measurable on the record hot path); in-process toggling goes through
# set_enabled()/disabled(), cross-process through the env var
_ENABLED = os.environ.get("HEAT_TPU_FUSION", "1").lower() not in _OFF_VALUES

# collective-aware fusion escape hatch: with it off, collectives force the
# chain exactly as before this layer existed (resplit_/apply dispatch
# eagerly) and forcing points never batch independent roots
_COLLECTIVES = (
    os.environ.get("HEAT_TPU_FUSION_COLLECTIVES", "1").lower() not in _OFF_VALUES
)

# async multi-root forcing: at a forcing point, other live pending roots are
# dispatched in the SAME multi-output program when their results are small
# (batching a big intermediate would materialize an extra HBM write the
# chain's consumer never asked for); count-capped for program-size sanity
_BATCH_MAX = int(os.environ.get("HEAT_TPU_FUSION_BATCH", "16"))
_BATCH_BYTES = int(os.environ.get("HEAT_TPU_FUSION_BATCH_BYTES", "16384"))


def active() -> bool:
    """Whether the recorder is on (``HEAT_TPU_FUSION`` escape hatch, read at
    import; see :func:`set_enabled`/:func:`disabled` for in-process control)."""
    return _ENABLED


def set_enabled(flag: bool) -> bool:
    """Flip the recorder in-process; returns the previous state."""
    global _ENABLED
    prev, _ENABLED = _ENABLED, bool(flag)
    return prev


@contextmanager
def disabled():
    """Context manager running with fusion recording off (used by the parity
    tests and the fused-vs-unfused benchmark legs)."""
    prev = set_enabled(False)
    try:
        yield
    finally:
        set_enabled(prev)


def collectives_active() -> bool:
    """Whether collective nodes record into the DAG and forcing batches
    independent roots (``HEAT_TPU_FUSION_COLLECTIVES`` escape hatch)."""
    return _ENABLED and _COLLECTIVES


def set_collectives_enabled(flag: bool) -> bool:
    """Flip collective-aware fusion in-process; returns the previous state."""
    global _COLLECTIVES
    prev, _COLLECTIVES = _COLLECTIVES, bool(flag)
    return prev


@contextmanager
def collectives_disabled():
    """Context manager restoring force-at-collective behavior (the parity
    tests and the ``HEAT_TPU_FUSION_COLLECTIVES=0`` matrix leg)."""
    prev = set_collectives_enabled(False)
    try:
        yield
    finally:
        set_collectives_enabled(prev)


#: correlation-id source: every fresh chain takes the next id at record time;
#: nodes recorded onto a pending chain inherit it, so one fused DAG's whole
#: lifecycle (record -> dispatch -> blocking sync) shares a cid the trace
#: timeline can join on (doc/internals_distribution.md: the cid contract)
_CID_SEQ = itertools.count(1)


class LazyArray:
    """One recorded expression-DAG node.

    ``children`` entries are other ``LazyArray`` nodes, concrete arrays
    (``jax.Array`` / ``np.ndarray``) or Python scalars; ``kw`` is the sorted
    tuple of static keyword arguments baked into the program. ``shape`` /
    ``dtype`` describe the *physical* result (inferred abstractly at record
    time, never by executing the op). ``cid`` is the chain's correlation id
    (inherited from the first still-pending child, else fresh). ``program``
    is stamped at force time with the key of the fused program that
    produced the value (None while pending, or when the chain degraded to
    eager replay) — the provenance ``ht.errstate`` and the numerics lens
    report for a value gone bad.
    """

    __slots__ = ("fn", "children", "kw", "shape", "dtype", "depth", "cid",
                 "program", "session", "_value")

    def __init__(self, fn, children, kw, shape, dtype, depth, cid=0):
        self.fn = fn
        self.children = children
        self.kw = kw
        self.shape = shape
        self.dtype = dtype
        self.depth = depth
        self.cid = cid
        self.program = None
        # the serving session (name) this node was recorded under, or None —
        # cross-session batching preserves it per root so tracelens/SLO
        # histograms bill the right tenant even inside a shared dispatch
        self.session = None
        self._value = None

    @property
    def ndim(self) -> int:
        return len(self.shape)

    def astype(self, dtype) -> "LazyArray":
        """Deferred cast — keeps ``DNDarray.astype`` chains recorded."""
        return cast(self, dtype)

    def __repr__(self) -> str:  # debugging aid only
        state = "forced" if self._value is not None else f"depth={self.depth}"
        return f"LazyArray({getattr(self.fn, '__name__', self.fn)}, shape={self.shape}, dtype={self.dtype}, {state})"


# ----------------------------------------------------------------------
# recording
# ----------------------------------------------------------------------
def _astype_op(x, *, dtype):
    return jnp.asarray(x).astype(dtype)


def _unpad_op(x, *, axis, size):
    # the mask step of pad+mask: slice the suffix padding off the split dim
    # INSIDE the fused program, so cross-split reductions never see padding
    return jax.lax.slice_in_dim(x, 0, size, axis=axis)


def _pad_split_op(x, *, axis, pad):
    # the pad step of pad+mask, as a DAG node: zero-pad the new split dim to
    # p*ceil(n/p) inside the fused program (deferred reshard of ragged dims)
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _reshard_op(x, *, sharding):
    # a recorded redistribution: under a trace (the fused program, or the
    # record-time eval_shape) it is a sharding constraint GSPMD satisfies
    # with its own collective schedule; in the eager replay (guarded
    # forcing's degraded arm) it is the same device_put resplit_ used to do
    if isinstance(x, jax.core.Tracer):
        return jax.lax.with_sharding_constraint(x, sharding)
    return jax.device_put(x, sharding)


def _matmul_op(a, b, *, a_sharding, b_sharding, out_sharding):
    # the matmul case table as a DAG node: under a trace the operand/result
    # sharding constraints pin the same schedule _matmul_program's in/out
    # shardings pin (split-0 @ *: local contraction; * @ split-1: local;
    # both-split-1/split-0-rhs: GSPMD's psum/allgather), compiled INTO the
    # enclosing chain's program; the eager replay (guarded forcing's
    # degraded arm) is device_put + matmul — the same placement the pinned
    # program produces
    if isinstance(a, jax.core.Tracer):
        a = jax.lax.with_sharding_constraint(a, a_sharding)
    elif isinstance(a, jax.Array):
        a = jax.device_put(a, a_sharding)
    if isinstance(b, jax.core.Tracer):
        b = jax.lax.with_sharding_constraint(b, b_sharding)
    elif isinstance(b, jax.Array):
        b = jax.device_put(b, b_sharding)
    out = jnp.matmul(a, b)
    if isinstance(out, jax.core.Tracer):
        return jax.lax.with_sharding_constraint(out, out_sharding)
    return jax.device_put(out, out_sharding)


def _pick_op(t, *, i):
    # selector over a multi-output kernel node's result tuple: each output of
    # a record_multi parent is one _pick_op node, so the DAG stays
    # single-value per node while the kernel itself runs once per program
    return t[i]


def _aval(c) -> Tuple[Tuple[int, ...], np.dtype]:
    if isinstance(c, LazyArray):
        return c.shape, c.dtype
    if isinstance(c, (jax.Array, np.ndarray)):
        return tuple(c.shape), np.dtype(c.dtype)
    # python scalar: only ever feeds an _astype_op node, whose output aval
    # does not depend on the input dtype
    return (), np.result_type(type(c))


@functools.lru_cache(maxsize=8192)
def _infer_cached(fn, child_avals, kw):
    """Abstract (shape, dtype) of ``fn(*children, **kw)`` via one cached
    ``jax.eval_shape`` — the op is never executed at record time."""
    args = [jax.ShapeDtypeStruct(s, d) for s, d in child_avals]
    # kwargs are baked into the closure: eval_shape abstracts every argument
    # it is passed, and ops like jnp.sum need keepdims/axis static
    kw_d = dict(kw)
    out = jax.eval_shape(lambda *a: fn(*a, **kw_d), *args)
    return tuple(out.shape), np.dtype(out.dtype)


def record(fn, children, **kw) -> LazyArray:
    """Record ``fn(*children, **kw)`` as a DAG node without dispatching it.

    ``kw`` values must be hashable (callers pre-check); shape/dtype are
    inferred abstractly. Raises on inference failure — callers route the
    exception through ``resilience.record_recoverable`` and fall back to the
    eager engine, which reproduces the error eagerly.
    """
    if resilience._ARMED:
        resilience.check("fusion.record")
    kw_t = tuple(sorted(kw.items()))
    depth = 1 + max(
        (c.depth for c in children if isinstance(c, LazyArray) and c._value is None),
        default=0,
    )
    if depth > _MAX_CHAIN:
        children = tuple(
            force(c) if isinstance(c, LazyArray) and c._value is None else c
            for c in children
        )
        depth = 1
    cid = 0
    for c in children:
        if isinstance(c, LazyArray) and c._value is None:
            cid = c.cid  # join the pending chain's lifecycle
            break
    if not cid:
        cid = next(_CID_SEQ)
    if fn is _astype_op:
        shape = _aval(children[0])[0]
        dtype = np.dtype(kw["dtype"])
    elif fn is _unpad_op:
        shape = list(_aval(children[0])[0])
        shape[kw["axis"]] = kw["size"]
        shape = tuple(shape)
        dtype = _aval(children[0])[1]
    else:
        shape, dtype = _infer_cached(fn, tuple(_aval(c) for c in children), kw_t)
    if telemetry._MODE >= 2:
        telemetry.record_event(
            "record", op=getattr(fn, "__name__", str(fn)), cid=cid, depth=depth
        )
    node = LazyArray(fn, tuple(children), kw_t, shape, dtype, depth, cid)
    if _SESSION_OF is not None:
        node.session = _SESSION_OF()
    _STATS["records"] += 1
    return node


@functools.lru_cache(maxsize=8192)
def _infer_multi_cached(fn, child_avals, kw):
    """Abstract per-output (shape, dtype) of a tuple-returning ``fn`` via one
    cached ``jax.eval_shape`` — the multi-output analog of ``_infer_cached``."""
    args = [jax.ShapeDtypeStruct(s, d) for s, d in child_avals]
    kw_d = dict(kw)
    outs = jax.eval_shape(lambda *a: fn(*a, **kw_d), *args)
    return tuple((tuple(o.shape), np.dtype(o.dtype)) for o in outs)


def record_multi(fn, children, **kw) -> Tuple[LazyArray, ...]:
    """Record a tuple-returning kernel as ONE parent node plus one
    ``_pick_op`` selector node per output, and return the selector tuple.

    The parent stays interior to the DAG — forcing any selector runs the
    kernel once inside the fused program, and ``_gather_batch``'s sibling
    rule pulls the other selectors into the same dispatch so every output
    lands together (TSQR's Q/R, CholQR2's Q/R/ok, the halo pair)."""
    if resilience._ARMED:
        resilience.check("fusion.record")
    kw_t = tuple(sorted(kw.items()))
    depth = 1 + max(
        (c.depth for c in children if isinstance(c, LazyArray) and c._value is None),
        default=0,
    )
    if depth > _MAX_CHAIN:
        children = tuple(
            force(c) if isinstance(c, LazyArray) and c._value is None else c
            for c in children
        )
        depth = 1
    cid = 0
    for c in children:
        if isinstance(c, LazyArray) and c._value is None:
            cid = c.cid  # join the pending chain's lifecycle
            break
    if not cid:
        cid = next(_CID_SEQ)
    avals = _infer_multi_cached(fn, tuple(_aval(c) for c in children), kw_t)
    if telemetry._MODE >= 2:
        telemetry.record_event(
            "record", op=getattr(fn, "__name__", str(fn)), cid=cid, depth=depth
        )
    # the parent's own aval is never consumed (only _pick_op children refer
    # to it, with their own hand-assigned avals) — stamp the first output's
    session = _SESSION_OF() if _SESSION_OF is not None else None
    parent = LazyArray(fn, tuple(children), kw_t, avals[0][0], avals[0][1], depth, cid)
    parent.session = session
    picks = []
    for i, (shape, dtype) in enumerate(avals):
        pick = LazyArray(_pick_op, (parent,), (("i", i),), shape, dtype, depth + 1, cid)
        pick.session = session
        picks.append(pick)
    _STATS["records"] += 1 + len(picks)
    return tuple(picks)


def cast(c, jax_dtype) -> LazyArray:
    """A deferred dtype cast node (no-op passthrough when already right)."""
    dt = np.dtype(jax_dtype)
    if isinstance(c, (LazyArray, jax.Array, np.ndarray)) and np.dtype(_aval(c)[1]) == dt:
        return c
    return record(_astype_op, (c,), dtype=dt.name)


# ----------------------------------------------------------------------
# the sharded-program cache + materialization
# ----------------------------------------------------------------------
_PROGRAMS: "OrderedDict[tuple, callable]" = OrderedDict()
# quarantined DAG keys: signatures whose fused program failed to build or
# execute; forced via per-op eager replay from then on (guarded forcing)
_QUARANTINE: "OrderedDict[tuple, None]" = OrderedDict()
# per-program accounting: sig -> {key (stable digest), family, compiles,
# dispatches, roots}. Kept alongside _PROGRAMS (same LRU bound) so the
# telemetry trace can name the program a dispatch launched and the cost
# estimator can re-lower the signature on demand without holding operands.
_PROGRAM_INFO: "OrderedDict[tuple, dict]" = OrderedDict()
# memoized cost estimates keyed by program key (program_costs())
_COSTS: dict = {}
# program keys whose cost estimate failed in the backend: the failure used
# to be buried silently in cost["error"] — now it is counted into
# report()["programs"]["cost_errors"] and warned once per session
_COST_ERROR_KEYS: set = set()
_COST_ERROR_WARNED = False
# memoized everything-replicated cost estimates keyed by program key — the
# audit baseline: "what would this program cost per host if nothing were
# sharded" (heat_tpu/analysis/audit.py divides by the mesh size to get the
# sharded lower bound a replication blowup is measured against)
_REPL_COSTS: dict = {}
_STATS = {
    "compiles": 0,
    "hits": 0,
    "disk_hits": 0,
    "forces": 0,
    "evictions": 0,
    "degraded": 0,
    "quarantine_hits": 0,
    # DAG nodes recorded (record/record_multi), always counted. The add takes
    # no lock: recorders run outside _FORCE_LOCK, and under CPython 3.12's
    # GIL a dict-item += on an int is not interrupted
    "records": 0,
}
# where a forced result's host time goes, in nanoseconds of perf_counter_ns
# per phase; they grow only while telemetry.tracing() (telemetry on, or a
# profiler session recording), beside the heat.force / heat.place / heat.read
# spans of the same intervals. phase_forces counts the non-recursive forces
# timed; places/reads are DNDarray's seams (note_phase)
_FORCE_PHASES = ("admit", "walk", "lookup", "dispatch", "install")
_STATS.update({f"phase_{name}_ns": 0 for name in _FORCE_PHASES})
_STATS.update(
    phase_forces=0, phase_places=0, phase_place_ns=0, phase_reads=0, phase_read_ns=0,
    phase_read_ready_ns=0, phase_read_copy_ns=0,  # heat.read's two children: the wait, the copy
)
# an estimator's fit, a distance-matrix call and a QR factorisation are timed
# the same way, under the same switch (note_phases): ``phase_<prefix>_<phase>_ns``
# per phase of the heat.<prefix> span's children, and the region's own counts.
# cluster/kmeans.py (heat.kmeans.fit): fits, the programs they dispatched,
# their blocking host reads, the XLA label passes over the rows those programs
# ran, and the grid steps of one pass of the fused kernel with those of them
# that took the masked body (the blocks that do not lie wholly under n_valid;
# 0 and 0 on the jnp path). spatial/distance.py (heat.cdist): calls, and the
# operand rotations (collective-permutes of one operand shard) their tile
# programs made.
# core/linalg/qr.py (heat.qr): calls, their blocking host reads (the
# CholeskyQR2 probe's one), the CholeskyQR2 attempts whose probe failed and
# fell to Householder, and the calls whose CholeskyQR2 program took its tall
# products by column blocks (a multiple of 128 columns, at least 256).
# regression/lasso.py (heat.lasso.fit): fits, the coordinate-descent sweeps
# the device ran for them, and their blocking host reads (one a fit: the sweeps
# run and the last change; the loop over sweeps is the descent program's)
_KMEANS_PHASES = ("init", "prepare", "dispatch", "sync", "copy", "wrap")
_CDIST_PHASES = ("prepare", "dispatch", "place")
_QR_PHASES = ("prepare", "dispatch", "sync", "copy", "wrap")
_LASSO_PHASES = ("prepare", "gram", "dispatch", "sync", "copy", "wrap")
_STATS.update({f"phase_kmeans_{name}_ns": 0 for name in _KMEANS_PHASES})
_STATS.update({f"phase_cdist_{name}_ns": 0 for name in _CDIST_PHASES})
_STATS.update({f"phase_qr_{name}_ns": 0 for name in _QR_PHASES})
_STATS.update({f"phase_lasso_{name}_ns": 0 for name in _LASSO_PHASES})
_STATS.update(
    phase_kmeans_fits=0, phase_kmeans_dispatches=0, phase_kmeans_syncs=0,
    phase_kmeans_label_epilogues=0, phase_kmeans_blocks=0, phase_kmeans_tail_blocks=0,
    phase_cdist_calls=0, phase_cdist_rotations=0,
    phase_qr_calls=0, phase_qr_syncs=0, phase_qr_fallbacks=0, phase_qr_blocked=0,
    phase_lasso_fits=0, phase_lasso_sweeps=0, phase_lasso_syncs=0,
)
# place, read, a fit (k-means, lasso), a cdist and a qr are timed outside _FORCE_LOCK, from any serving thread:
# their adds take this lock, which only the traced path ever touches
_PHASE_LOCK = threading.Lock()


def note_phase(name: str, ns: int, parts: Optional[dict] = None) -> None:
    """Count one ``heat.place`` / ``heat.read`` interval of ``ns``
    nanoseconds (``DNDarray``'s forcing seam and host boundary, while
    ``telemetry.tracing()``), and the nanoseconds of each of its ``parts``
    (``telemetry.Phases.ns``: a read's ``ready`` and ``copy``) onto
    ``phase_<name>_<part>_ns``."""
    with _PHASE_LOCK:
        _STATS[f"phase_{name}s"] += 1
        _STATS[f"phase_{name}_ns"] += ns
        for part, took in (parts or {}).items():
            _STATS[f"phase_{name}_{part}_ns"] += took


def note_phases(prefix: str, ns: dict, **counts: int) -> None:
    """Count one traced region of the library above the engine (a
    ``heat.kmeans.fit``, a ``heat.cdist``, a ``heat.qr``, a ``heat.lasso.fit``; while
    ``telemetry.tracing()``): the
    nanoseconds of each phase it went through (``telemetry.Phases.ns``) onto
    ``phase_<prefix>_<phase>_ns`` and each of ``counts`` onto
    ``phase_<prefix>_<name>``."""
    with _PHASE_LOCK:
        for name, add in counts.items():
            _STATS[f"phase_{prefix}_{name}"] += add
        for name, took in ns.items():
            _STATS[f"phase_{prefix}_{name}_ns"] += took

# serving seams (core/serving.py installs these as module attributes — the
# telemetry ``_MEM_HOOK`` set-attribute pattern; each costs one ``is None``
# check per force when the serving layer is not in use):
_DISK_INDEX = None  # persistent program-key index: disk warm-start accounting
# token-bucket admission gate, composed BEFORE memledger's. Fires in force()
# BEFORE _FORCE_LOCK is taken: the `wait` policy sleeps until refill, and a
# rate-limited tenant sleeping under the force lock would convoy every other
# session's dispatches behind it. Called as _ADMIT_HOOK(cid) -> refund|None;
# the refund is invoked when the admitted dispatch never runs (a neighbour's
# batch materialized the node during the wait).
_ADMIT_HOOK = None
_SERVING_NOTE = None  # per-session incident/billing notes
_SESSION_OF = None  # resolves the calling thread's active Session id
# dispatch-ordering seam: maps a root's recording session name to a sort
# key (serving installs (tier_rank, deadline_ms)). When set, _gather_batch
# considers candidates in (priority, registration) order instead of pure
# registration order, so interactive/deadline-near roots win batch slots
# over batch-tier chains. Must be deterministic — candidate order feeds the
# program signature, and nondeterminism would churn the program cache.
# The hook may also return _BATCH_EXCLUDED to keep a root OUT of other
# sessions' gathered batches entirely (serving: shed-tier roots must not
# free-ride an interactive neighbour's dispatch while shedding is active).
_ROOT_PRIORITY = None
_BATCH_EXCLUDED = object()

# micro batch window (seconds): when serving arms this (>= 2 concurrent
# sessions), a top-level force sleeps this long BEFORE taking _FORCE_LOCK.
# The sleep releases the GIL so other client threads can register their own
# pending roots; the first thread to wake gathers every root registered in
# the window into ONE multi-output dispatch, and the absorbed threads find
# their value already installed without ever contending on the lock.
_BATCH_WINDOW_S = 0.0
_FORCE_TLS = threading.local()  # .held: this thread is inside force already

# force() serializes its walk/cache/dispatch critical section under ONE
# reentrant lock so concurrent serving clients never interleave signature
# accumulators or the program-cache LRU. RLock, not Lock: the memledger
# drain policy recursively forces OTHER roots from inside the gate.
_FORCE_LOCK = threading.RLock()


def _program_key(sig) -> str:
    """Stable short digest of a program signature: op names + topology +
    static kwargs + leaf shape/dtype/sharding — the *program key* correlating
    the trace timeline's ``dispatch`` events, ``cache_stats()["program_keys"]``
    and :func:`program_costs`. Function identities hash by name (not id), so
    the key is reproducible within and across processes up to sharding repr."""
    parts = []
    for e in sig:
        tag = e[0]
        if tag == "L":
            parts.append(f"L:{e[1]}:{e[2]}:{e[3]}")
        elif tag == "Ls":
            parts.append(f"Ls:{getattr(e[1], '__name__', e[1])}")
        elif tag == "R":
            parts.append(f"R:{e[1]}")
        else:
            fn, idxs, kw = e
            parts.append(f"O:{getattr(fn, '__name__', fn)}:{idxs}:{kw}")
    return hashlib.sha1("|".join(parts).encode()).hexdigest()[:16]


def _program_info(sig) -> dict:
    info = _PROGRAM_INFO.get(sig)
    if info is None:
        info = _PROGRAM_INFO[sig] = {
            "key": _program_key(sig),
            "family": "/".join(_family(sig)) or "<leaf>",
            "compiles": 0,
            "dispatches": 0,
            "roots": 0,
        }
        while len(_PROGRAM_INFO) > _CACHE_SIZE:
            _PROGRAM_INFO.popitem(last=False)
    else:
        # LRU like _PROGRAMS itself: a hot program's accounting must never
        # be the insertion-order eviction victim while its program stays
        # cached (the counters would silently restart from zero)
        _PROGRAM_INFO.move_to_end(sig)
    return info


def _leaf_sig(v):
    if isinstance(v, jax.Array):
        # the sharding carries both the split axes and the mesh, so a layout
        # or mesh-size change keys a fresh program (shardings and np dtypes
        # are hashable; no string derivation on the hot path)
        return ("L", v.shape, v.dtype, getattr(v, "sharding", None))
    if isinstance(v, np.ndarray):
        return ("L", v.shape, v.dtype, None)
    return ("Ls", type(v))


def _walk(root, entries, leaves, memo) -> None:
    """Postorder walk of one DAG root into the shared (entries, leaves,
    memo) accumulators — a subexpression shared with an earlier walk appears
    once and is referenced by index."""
    stack = [(root, False)]
    while stack:
        obj, expanded = stack.pop()
        oid = id(obj)
        if oid in memo:
            continue
        if not (isinstance(obj, LazyArray) and obj._value is None):
            val = obj._value if isinstance(obj, LazyArray) else obj
            memo[oid] = len(entries)
            leaves.append(val)
            entries.append(_leaf_sig(val))
            continue
        if not expanded:
            stack.append((obj, True))
            for c in obj.children:
                stack.append((c, False))
        else:
            memo[oid] = len(entries)
            entries.append((obj.fn, tuple(memo[id(c)] for c in obj.children), obj.kw))


def _signature(roots):
    """Structural signature + the leaf operands of one or more DAG roots.
    The final ``("R", ...)`` entry records each root's position —
    multi-output forcing's return order."""
    entries = []
    leaves = []
    memo = {}
    for root in roots:
        _walk(root, entries, leaves, memo)
    entries.append(("R", tuple(memo[id(r)] for r in roots)))
    return tuple(entries), leaves


def _build(sig):
    """The executable for a structural signature: replays the DAG from the
    leaf operands and returns the tuple of root values (one per ``("R",...)``
    position). One instance per signature, jitted once — steady-state calls
    with fresh same-shaped inputs reuse the compiled program."""

    def run(*leaves):
        vals = []
        li = 0
        for e in sig:
            if e[0] == "L" or e[0] == "Ls":
                vals.append(leaves[li])
                li += 1
            elif e[0] == "R":
                return tuple(vals[i] for i in e[1])
            else:
                fn, idxs, kw = e
                vals.append(fn(*(vals[i] for i in idxs), **dict(kw)))
        raise AssertionError("signature missing its root entry")  # pragma: no cover

    return run


def _family(sig) -> tuple:
    """The op identities of a signature, ignoring leaf shapes — the retrace
    detector's key: the same family missing under churning shapes is the
    recompile pathology worth warning about."""
    return tuple(
        getattr(e[0], "__name__", str(e[0]))
        for e in sig
        if e[0] not in ("L", "Ls", "R")
    )


def _leaf_key(sig) -> tuple:
    """The leaf (shape/dtype/sharding) part of a signature."""
    return tuple(e for e in sig if e[0] in ("L", "Ls"))


# ----------------------------------------------------------------------
# live-root registry: async multi-root forcing
# ----------------------------------------------------------------------
# weakrefs to DNDarray wrappers whose payload is (was) a pending LazyArray,
# in registration order: a forcing point batches the still-pending ones into
# one multi-output program. Entries die with their wrappers automatically;
# already-forced survivors are pruned during gathering.
_ROOT_SEQ = itertools.count()
_LIVE_ROOTS: "weakref.WeakValueDictionary[int, object]" = weakref.WeakValueDictionary()
# guards registry MUTATION and key snapshots: record()/register_root runs on
# client threads WITHOUT _FORCE_LOCK (the batch window exists precisely so
# other threads can register roots while a force is in flight), so an
# unsynchronized sorted(_LIVE_ROOTS.keys()) in the gather/drain loops could
# raise "dictionary changed size during iteration" mid-force
_ROOTS_LOCK = threading.Lock()


def register_root(wrapper) -> None:
    """Track a DNDarray whose payload is a pending recorded chain as an
    async-forcing batch candidate (every deferral site calls this). No-op
    with collective-aware fusion off — forcing then never batches."""
    if _COLLECTIVES:
        with _ROOTS_LOCK:
            _LIVE_ROOTS[next(_ROOT_SEQ)] = wrapper


def _live_root_keys() -> list:
    """Stable snapshot of the registry's keys, safe against concurrent
    ``register_root`` inserts from other client threads."""
    with _ROOTS_LOCK:
        return sorted(_LIVE_ROOTS.keys())


def _node_nbytes(node: LazyArray) -> int:
    size = 1
    for s in node.shape:
        size *= int(s)
    return size * np.dtype(node.dtype).itemsize


#: pending-node ids of the signature currently held at the admission gate:
#: while the drain policy recursively forces OTHER roots, neither the drain
#: loop nor those forces' own _gather_batch may touch any node of the gated
#: chain — batching it would dispatch the chain a second time when admit()
#: returns and the original force runs its already-built program
_DRAIN_EXCLUDE: frozenset = frozenset()


def _gather_batch(entries, leaves, memo, roots):
    """Select other live pending roots to dispatch alongside the triggering
    root, in stable registration order (nondeterministic ordering would
    churn the program cache), walking each selection into the shared
    signature accumulators as it is taken. ``memo`` is the signature walk so
    far: candidates already INTERIOR to a selected DAG are skipped — they
    would add an output write nothing asked for, and whether a caller
    happens to hold an intermediate must not change the program's cache key
    (a later read finds the whole-chain force already materialized the
    ancestor, and forces the held node with its own small program). Only
    small results batch (a big disjoint root keeps its own dispatch), and
    only candidates living on the SAME device set as the triggering root's
    leaves — one jitted program cannot span two meshes, and a mixed batch
    would dispatch-fail and spuriously degrade a perfectly valid chain.
    Roots recorded under DIFFERENT serving sessions batch together freely
    (the registry is global and the rules above are session-blind): each
    root carries its ``session`` stamp, so the shared dispatch still bills
    per tenant through the serving note and the timeline's ``sessions``."""
    device_set = None
    for leaf in leaves:
        if isinstance(leaf, jax.Array):
            sharding = getattr(leaf, "sharding", None)
            if sharding is not None:
                device_set = sharding.device_set
                break
    if device_set is None:
        return  # no placed operand to anchor the mesh: skip batching
    keys = _live_root_keys()
    prio = _ROOT_PRIORITY
    if prio is not None:
        # deadline/tier-aware ordering (serving's seam): candidates sort by
        # (priority, registration key) — deterministic for a given session
        # mix, so repeated steady-state batches keep one program signature.
        # _BATCH_EXCLUDED candidates (shed tiers) drop out entirely: a shed
        # root riding a neighbour's batch would dispatch work the overload
        # controller just refused.
        ranked = []
        for key in keys:
            wrapper = _LIVE_ROOTS.get(key)
            payload = getattr(wrapper, "_payload", None)
            try:
                p = prio(getattr(payload, "session", None))
            except Exception:  # noqa: BLE001 - ordering must never break a force
                p = None
            if p is _BATCH_EXCLUDED:
                continue
            ranked.append(((1, float("inf")) if p is None else p, key))
        ranked.sort()
        keys = [key for _, key in ranked]
    stale = []
    for key in keys:
        if len(roots) >= _BATCH_MAX:
            break
        wrapper = _LIVE_ROOTS.get(key)
        if wrapper is None:
            continue
        payload = wrapper._payload
        if not (isinstance(payload, LazyArray) and payload._value is None):
            stale.append(key)  # forced since registration: stop tracking
            continue
        if id(payload) in memo:
            continue  # interior to (or already selected by) this batch
        if _DRAIN_EXCLUDE and id(payload) in _DRAIN_EXCLUDE:
            continue  # part of the chain held at the admission gate
        if _node_nbytes(payload) > _BATCH_BYTES:
            # sibling outputs of one multi-output kernel ride along
            # regardless of size: their shared parent is already interior to
            # this batch's walk, so the kernel runs once either way and the
            # extra output write is free — leaving the sibling behind would
            # re-run the whole kernel at its own later force
            if not (
                payload.fn is _pick_op
                and payload.children
                and id(payload.children[0]) in memo
            ):
                continue
        if getattr(wrapper.comm, "device_set", None) != device_set:
            continue  # different comm/mesh: never fuse across device sets
        _walk(payload, entries, leaves, memo)
        roots.append(payload)
    with _ROOTS_LOCK:
        for key in stale:
            _LIVE_ROOTS.pop(key, None)


def _static_peak(key: str, leaves, roots) -> Tuple[int, str]:
    """The failing/candidate program's static per-host memory peak for the
    admission gate and OOM forensics: XLA's memoized ``memory_analysis``
    peak when :func:`program_costs` has computed it (``"static"``), else the
    cheap operand+result estimate (``"estimate"``) — the gate must never
    compile at dispatch time."""
    cost = _COSTS.get(key)
    if cost:
        peak = (cost.get("memory") or {}).get("peak_bytes")
        if peak:
            return int(peak), "static"
    est = 0
    for leaf in leaves:
        nbytes = getattr(leaf, "nbytes", None)
        if nbytes is not None:
            est += int(nbytes)
    for r in roots:
        est += _node_nbytes(r)
    return est, "estimate"


def _drain_pending_roots(exclude=()):
    """The ``drain`` admission policy's arm: force every OTHER live pending
    root and block until its value is on device — outstanding async futures
    stop being "outstanding", and their operand chains become collectable.
    ``exclude`` holds the ids of EVERY pending node of the gated signature
    (roots and interior nodes); it is also published as ``_DRAIN_EXCLUDE``
    so the recursive forces' own ``_gather_batch`` cannot pull the gated
    chain into another root's program — that would dispatch the chain twice
    once the gate admits the original force. Returns how many roots were
    drained; counted as ``drain`` blocking syncs so the async-forcing
    report shows what the gate cost."""
    global _DRAIN_EXCLUDE
    prev, _DRAIN_EXCLUDE = _DRAIN_EXCLUDE, _DRAIN_EXCLUDE | frozenset(exclude)
    drained = 0
    try:
        for key in _live_root_keys():
            wrapper = _LIVE_ROOTS.get(key)
            if wrapper is None:
                continue
            payload = wrapper._payload
            if not isinstance(payload, LazyArray) or id(payload) in _DRAIN_EXCLUDE:
                continue
            if payload._value is None:
                force(payload)
            value = payload._value
            if isinstance(value, jax.Array):
                token = None
                if telemetry._MODE:
                    token = telemetry.record_blocking_sync("drain", cid=payload.cid)
                with health_runtime.watch("sync:drain", cid=payload.cid):
                    value.block_until_ready()
                # close the event so the trace shows the drain's true host
                # wait as a duration, not a zero-width instant
                telemetry.end_blocking_sync(token)
                drained += 1
    finally:
        _DRAIN_EXCLUDE = prev
    return drained


def _quarantine(sig) -> None:
    _QUARANTINE[sig] = None
    while len(_QUARANTINE) > _QUARANTINE_SIZE:
        _QUARANTINE.popitem(last=False)


def _degrade(sig, leaves, exc, missed):
    """Guarded forcing's recovery arm: the fused program for ``sig`` failed
    to build (``missed``) or execute — drop it from the cache, quarantine the
    DAG key (later forces skip the doomed compile and replay eagerly), record
    a ``degraded`` telemetry event, warn once, and re-run the chain as per-op
    eager dispatch. The replay produces the exact eager result; if IT fails,
    the error surfaces with per-op locality — the reference's error model."""
    import warnings

    _PROGRAMS.pop(sig, None)
    _PROGRAM_INFO.pop(sig, None)  # a quarantined key is not a live program
    _quarantine(sig)
    _STATS["degraded"] += 1
    stage = "compile" if missed else "execute"
    family = _family(sig)
    if _SERVING_NOTE is not None:
        # contained per-session: only the tripping tenant's quarantine view
        # records the incident — a neighbor's programs stay undegraded
        _SERVING_NOTE("degraded", program=_program_key(sig), stage=stage)
    if telemetry._MODE:
        telemetry.record_degraded(family, stage, repr(exc))
    warnings.warn(
        resilience.DegradedDispatchWarning(
            f"fused program for op chain {'/'.join(family) or '<leaf>'} failed at "
            f"{stage} ({exc!r}); degraded to per-op eager dispatch and quarantined "
            "the DAG key (correct result, slower — fusion.clear_cache() lifts the "
            "quarantine)"
        ),
        stacklevel=4,
    )
    # black-box the failure: the ring holds the dispatches/collectives that
    # led here (throttled; no-op when the recorder is disarmed)
    health_runtime.auto_dump("degrade")
    return _build(sig)(*leaves)


def force(node):
    """Materialize a recorded DAG as one cached, jitted XLA program.

    ASYNCHRONOUS: the dispatch installs the resulting ``jax.Array`` futures
    and returns immediately — nothing here blocks on or reads device data,
    so only genuine host boundaries (``item()``, ``numpy()``, printing, I/O
    shard reads) synchronize. Under collective-aware fusion
    (:func:`collectives_active`), other live pending roots with small
    results (see :func:`_gather_batch`) ride the SAME multi-output program,
    so a later read of theirs finds the value already in flight.

    Under an active trace (an enclosing ``jax.jit``/``eval_shape``) the
    program executes into that trace, so the results may be tracers — they
    are then returned WITHOUT being cached on the nodes (caching a tracer
    would leak it past the trace's lifetime).

    GUARDED: a program that fails to trace/compile/execute (injectable at
    the ``fusion.compile``/``fusion.execute`` sites) degrades to per-op
    eager dispatch through :func:`_degrade` — one policy
    (``resilience.force_recoverable``) decides what degrades, the DAG key is
    quarantined, and the active ``ht.errstate`` policy is applied to the
    materialized value either way."""
    if not isinstance(node, LazyArray):
        return node
    if node._value is not None:
        return node._value
    if not telemetry.tracing():
        return _force(node, None)
    # the phases of this forced result: spans on the profiler's clock and
    # the phase_* counters (telemetry.Phases). A recursive force (the drain
    # policy) is one childless span and counts nothing: its time belongs to
    # the phase of the outer force that caused it
    recursive = {"recursive": 1} if getattr(_FORCE_TLS, "held", 0) else {}
    ph = telemetry.Phases(
        "heat.force", split=not recursive, cid=node.cid,
        trigger=telemetry.current_trigger(), **recursive,
    )
    try:
        return _force(node, ph)
    finally:
        ph.close()


def _force(node, ph):
    """:func:`force` past its early returns; ``ph`` is the force's
    ``telemetry.Phases`` while tracing, else None."""
    held = getattr(_FORCE_TLS, "held", 0)
    if ph is not None:
        ph.phase("admit")  # the time work waited: window, admission, the lock
    # micro batch window (serving arms it): sleep with the GIL released so
    # concurrent clients can register their roots, then re-check — a
    # neighbour's batch may have materialized this node during the window.
    # Skipped on recursive forces (drain policy) which already hold the lock.
    if _BATCH_WINDOW_S > 0.0 and not held:
        time.sleep(_BATCH_WINDOW_S)
        if node._value is not None:
            return node._value
    # local capture: a concurrent last-session exit may uninstall the hook
    # between the None check and the call
    admit = _ADMIT_HOOK
    if admit is not None and not held:
        # serving admission gate (core/serving.py): per-session + global
        # token buckets, BEFORE the force lock so a tenant blocked on refill
        # (`wait` policy) never convoys other sessions' dispatches behind
        # _FORCE_LOCK, and before memledger's headroom gate — cheap rate
        # math before ledger walks. A refusal surfaces AdmissionError with
        # the chain intact: still pending, never degraded, dispatchable once
        # tokens refill — exactly the admission_hold contract. Recursive
        # forces (the drain policy, already holding the lock) are exempt:
        # they dispatch on behalf of an already-admitted force, and gating
        # them would sleep under the lock.
        refund = admit(node.cid)
        if node._value is not None:
            # a neighbour's batch landed this node while we waited for
            # tokens: no dispatch happens, so the token goes back
            if refund is not None:
                refund()
            return node._value
    # one force at a time: concurrent serving clients serialize here (the
    # lock is reentrant for the drain policy's recursive forces). Re-check
    # after acquiring — another thread's batch may have materialized us.
    with _FORCE_LOCK:
        _FORCE_TLS.held = held + 1
        try:
            return _force_locked(node, ph)
        finally:
            _FORCE_TLS.held = held
            if ph is not None and not held:
                # fold this force's phases in under the lock (admit was
                # timed outside it and carried here: no update is lost)
                ph.phase(None)
                if "lookup" in ph.ns:  # past the walk: counted in "forces"
                    _STATS["phase_forces"] += 1
                    for name, ns in ph.ns.items():
                        _STATS[f"phase_{name}_ns"] += ns


def _force_locked(node, ph):
    if node._value is not None:
        return node._value
    if ph is not None:
        ph.phase("walk")
    roots = [node]
    entries = []
    leaves = []
    memo = {}
    _walk(node, entries, leaves, memo)
    if _COLLECTIVES and _ENABLED and _LIVE_ROOTS and jax.core.trace_ctx.is_top_level():
        # never batch while executing into an enclosing jit/eval_shape
        # trace: the extra roots would come back as tracers (uncacheable —
        # see below), tracing their subgraphs and baking their operands
        # into the user's compiled program as outputs nothing reads
        _gather_batch(entries, leaves, memo, roots)
    entries.append(("R", tuple(memo[id(r)] for r in roots)))
    sig = tuple(entries)
    _STATS["forces"] += 1
    if ph is not None:
        ph.phase("lookup")
    info = None  # per-program accounting; stays None for eager replays
    disk_warm = False  # this force's miss was served by the persistent index
    if _QUARANTINE and sig in _QUARANTINE:
        # known-bad DAG key: skip the failing compile, replay per-op
        _STATS["quarantine_hits"] += 1
        if _SERVING_NOTE is not None:
            _SERVING_NOTE(
                "quarantine_hit", program=_program_key(sig), cid=node.cid,
                sessions=[getattr(r, "session", None) for r in roots],
            )
        if telemetry._MODE:
            telemetry.record_force(
                telemetry.current_trigger(), node.depth, compiled=False, cid=node.cid
            )
        if ph is not None:
            ph.phase("dispatch")
        values = _build(sig)(*leaves)
    else:
        prog = _PROGRAMS.get(sig)
        missed = prog is None
        info = _program_info(sig)
        if missed:
            prog = jax.jit(_build(sig))
            _PROGRAMS[sig] = prog
            # a key already in the persistent index is a DISK hit, not a
            # recompile: jax's compilation cache (wired to the same
            # HEAT_TPU_PROGRAM_CACHE_DIR) serves the compiled binary, so a
            # warm-started process records zero compiles for seen signatures
            disk_warm = _DISK_INDEX is not None and _DISK_INDEX.has(info["key"])
            if disk_warm:
                _STATS["disk_hits"] += 1
            else:
                _STATS["compiles"] += 1
                info["compiles"] += 1
            if _DISK_INDEX is not None:
                _DISK_INDEX.note(info["key"], info["family"])
            while len(_PROGRAMS) > _CACHE_SIZE:
                _PROGRAMS.popitem(last=False)
                _STATS["evictions"] += 1
            if telemetry._MODE and not disk_warm:
                telemetry.record_retrace(_family(sig), _leaf_key(sig))
                # lands on the verbose timeline AND the flight ring (the
                # black box wants compiles next to the dispatches they cost)
                telemetry.record_event(
                    "compile",
                    program=info["key"], family=info["family"], cid=node.cid,
                )
        else:
            _PROGRAMS.move_to_end(sig)
            _STATS["hits"] += 1
        if telemetry._MODE:
            telemetry.record_force(
                telemetry.current_trigger(), node.depth, compiled=missed, cid=node.cid
            )
        if memledger._BUDGET_RAW is not None or memledger._HOLD is not None:
            # headroom admission gate (core/memledger.py): live ledger bytes
            # + this program's static peak against HEAT_TPU_MEMORY_BUDGET.
            # An elastic admission hold routes through the same seam even
            # with no budget armed — the supervisor's stop-the-world window.
            # Sits BEFORE the guarded try, so the `raise` policy surfaces to
            # the caller with the chain intact instead of degrading to an
            # eager replay that would dispatch the same bytes anyway.
            peak, peak_src = _static_peak(info["key"], leaves, roots)
            # every pending node of THIS signature (roots + interior): the
            # drain policy must not let another force's batch absorb any of
            # them — the program below is already built over this walk
            exclude = frozenset(memo)
            try:
                memledger.admit(
                    info["key"], info["family"], peak, peak_src,
                    drain_fn=lambda: _drain_pending_roots(exclude),
                )
            except memledger.MemoryBudgetExceeded:
                if _SERVING_NOTE is not None:
                    # the refusal is billed to the refused tenant only —
                    # containment: neighbors never see this session's gate
                    _SERVING_NOTE(
                        "mem_refused", program=info["key"], cid=node.cid,
                        sessions=[getattr(r, "session", None) for r in roots],
                    )
                raise
            if node._value is not None:  # pragma: no cover - belt and braces
                # some recursive path materialized this very chain while the
                # gate held it: the dispatch is done, do not run it again
                return node._value
        if ph is not None:
            ph.note(program=info["key"])
            ph.phase("dispatch")
        try:
            if resilience._ARMED:
                # jax.jit builds lazily, so the XLA compile happens inside the
                # first call — the injection sites model that split
                resilience.check("fusion.compile" if missed else "fusion.execute")
                # device OOM at dispatch time, as an injectable failure mode
                # (ISSUE 8): fires the same seam a real RESOURCE_EXHAUSTED
                # would, so the forensic + degrade path is testable
                resilience.check("memory.exhausted")
            if telemetry._MODE or health_runtime._WD_ACTIVE:
                # the fused-dispatch arming point: the watchdog knows the
                # in-flight program key + batched root cids at arm time, and
                # the health layer starts the dispatch→done clock (a fresh
                # build's call duration is the compile-time sample)
                cids = [r.cid for r in roots]
                with health_runtime.watch(
                    "dispatch", program=info["key"], cid=node.cid, cids=cids
                ):
                    values = prog(*leaves)
                if telemetry._MODE and ph is not None:
                    # the one timing of the jitted call: the dispatch phase
                    # (ph is None only if telemetry came on mid-force)
                    health_runtime.note_dispatch(
                        info["key"], cids, missed, ph.phase("install") * 1e-9
                    )
            else:
                values = prog(*leaves)
            info["dispatches"] += 1
            info["roots"] += len(roots)
        except Exception as exc:  # noqa: BLE001 - routed through ONE policy
            if memledger.is_oom(exc):
                # forensics BEFORE the degrade: rank the live buffers by
                # owner and name the failing program while the evidence is
                # still live — the eager replay below will churn it
                peak, _src = _static_peak(info["key"], leaves, roots)
                memledger.record_oom(
                    exc, program=info["key"], family=info["family"],
                    static_peak=peak,
                )
            if not resilience.force_recoverable(exc):
                raise
            values = _degrade(sig, leaves, exc, missed)
            info = None  # the eager replay is not a program dispatch
    if ph is not None:
        ph.phase("install")
    # under an enclosing trace the jit bind joins that trace and the values
    # are tracers even though every leaf is concrete (verified on jax
    # 0.4.37); caching is gated on each value's actual concreteness, not
    # ambient state (the errstate non-finite policy is applied at the
    # DNDarray.parray seam, which knows the logical extent — the padding
    # suffix of a ragged split holds unspecified garbage, never checked)
    for root, value in zip(roots, values):
        if not isinstance(value, jax.core.Tracer):
            root._value = value
            # provenance stamp: which fused program produced this value
            # (None on the degraded eager path) — read back at the errstate
            # seam so a nonfinite finding can name its producer
            root.program = None if info is None else info["key"]
            # drop the recorded graph: later forces of ancestors treat this
            # node as a leaf, and the chain's operand buffers become
            # collectable
            root.children = ()
            # ledger attribution: a dispatched-but-unclaimed async future is
            # "fusion" until a wrapper claims it at the parray seam. Tagged
            # BEFORE the dispatch event below, whose ledger sample must see
            # the in-flight futures attributed, not "unattributed"
            memledger.tag(value, "fusion")
    if telemetry._NUMLENS_HOOK is not None and info is not None:
        # numerics lens (core/numlens.py, HEAT_TPU_NUMLENS): sampled tensor
        # statistics + shadow-replay drift audit over the landed root
        # values — one attribute check when disarmed, and the hook itself
        # never raises and skips tracer values
        telemetry._NUMLENS_HOOK(sig, leaves, roots, values, info)
    sessions = None
    if _SESSION_OF is not None or any(
        getattr(r, "session", None) is not None for r in roots
    ):
        sessions = [getattr(r, "session", None) for r in roots]
    if _SERVING_NOTE is not None and info is not None:
        # per-tenant billing for a (possibly cross-session) shared dispatch:
        # each session is charged for ITS roots, and the compile (if any)
        # for the triggering session only. A disk warm-start is NOT billed
        # as a compile — session reports must agree with the global retrace
        # counter, which disk hits leave untouched.
        _SERVING_NOTE(
            "dispatch", program=info["key"], sessions=sessions,
            compiled=missed and not disk_warm,
            trigger=getattr(node, "session", None),
        )
    if telemetry._MODE:
        telemetry.record_async_dispatch(
            len(roots),
            cid=node.cid,
            cids=[r.cid for r in roots],
            program=None if info is None else info["key"],
            sessions=sessions,
        )
    return values[0]


def is_deferred(x) -> bool:
    """Whether a DNDarray currently carries an unmaterialized recorded chain."""
    payload = getattr(x, "_payload", x)
    return isinstance(payload, LazyArray) and payload._value is None


def cache_stats() -> dict:
    """Program-cache counters: ``compiles`` (the retrace count the
    compile-count tests pin — a disk warm-start is NOT a compile), ``hits``
    (in-memory), ``disk_hits`` (first force of a signature whose key was in
    the persistent ``HEAT_TPU_PROGRAM_CACHE_DIR`` index — the compiled
    binary comes from jax's compilation cache), ``forces``, ``misses``
    (``compiles + disk_hits`` — every cache-structure miss, however the
    binary was then obtained), ``evictions`` (LRU drops past
    ``HEAT_TPU_FUSION_CACHE``), the current cache ``size``, the
    ``program_keys`` of every cached program (the digests the trace
    timeline's ``dispatch`` events correlate to), plus the guarded-forcing
    counters: ``degraded`` (programs that failed and were replayed per-op),
    ``quarantine_hits`` (forces that skipped a known-bad compile) and
    ``quarantined`` (currently quarantined keys)."""
    return dict(
        _STATS,
        misses=_STATS["compiles"] + _STATS["disk_hits"],
        size=len(_PROGRAMS),
        quarantined=len(_QUARANTINE),
        program_keys=[info["key"] for info in _PROGRAM_INFO.values()],
    )


def clear_cache() -> None:
    """Drop every compiled program (and its accounting/cost memo), lift
    every quarantine, forget the live async-forcing root registry, and zero
    ALL counters coherently."""
    _PROGRAMS.clear()
    _PROGRAM_INFO.clear()
    _COSTS.clear()
    _REPL_COSTS.clear()
    _COST_ERROR_KEYS.clear()  # the once-per-session warn flag survives
    _QUARANTINE.clear()
    with _ROOTS_LOCK:
        _LIVE_ROOTS.clear()
    _STATS.update(dict.fromkeys(_STATS, 0))


def clear_quarantine() -> None:
    """Lift the quarantine only (keep compiled programs and counters): the
    next force of a previously-failing DAG key retries the fused compile."""
    _QUARANTINE.clear()


# ----------------------------------------------------------------------
# deferral front-ends for the L3 engines
# ----------------------------------------------------------------------
_SCALARS = (int, float, bool, complex, np.number, np.bool_)

# sibling-module handles resolved on first use (function-level `from . import`
# costs ~1µs of import machinery per call on the record hot path; a module
# top-level import would be circular — dndarray imports fusion)
DNDarray = None
_types = None
_broadcast_shape = None


def _resolve_siblings():
    global DNDarray, _types, _broadcast_shape
    from . import types as types_mod
    from .dndarray import DNDarray as dnd_cls
    from .stride_tricks import broadcast_shape as bshape

    DNDarray, _types, _broadcast_shape = dnd_cls, types_mod, bshape


def hashable_kwargs(kw: dict) -> bool:
    """Whether ``kw`` can be baked into a program-cache key. Only the
    ``hash()`` itself is guarded — the sort runs outside the ``try`` so a
    genuinely broken kwargs dict (unorderable keys) raises instead of being
    silently classified as "unhashable, use the eager engine"."""
    items = tuple(sorted(kw.items()))
    try:
        hash(items)
        return True
    except TypeError:  # an unhashable VALUE (list/array kwarg): eager path
        return False


def _unfused(engine: str, reason: str):
    """The one-line telemetry breadcrumb every eager-fallback site leaves,
    so ``telemetry.report()`` shows *why* a chain wasn't fused. Returns
    None — callers ``return _unfused(...)`` to decline deferral."""
    if telemetry._MODE:
        telemetry.record_unfused(engine, reason)
    return None


def _phys_node(x):
    """The DNDarray's physical payload as a recordable child, or None when it
    is a tracer (inside an enclosing jit/vmap trace, where deferral would
    nest programs — the enclosing trace already fuses)."""
    arr = x._payload
    if isinstance(arr, LazyArray):
        return arr if arr._value is None else arr._value
    if isinstance(arr, jax.core.Tracer):
        return None
    return arr


def _logical_node(x):
    n = _phys_node(x)
    if n is not None and x.padded:
        n = record(_unpad_op, (n,), axis=x.split, size=x.shape[x.split])
    return n


def _wrap(node, gshape, split, ref):
    """Wrap a recorded node as a DNDarray. Direct slot assembly (the
    ``_tree_unflatten`` pattern): the constructor's pad/validation logic is
    metadata-only for a LazyArray payload, and this sits on the per-op path."""
    if split is not None and (len(node.shape) == 0 or split >= len(gshape)):
        split = None
    obj = DNDarray.__new__(DNDarray)
    obj._DNDarray__gshape = gshape
    obj._DNDarray__dtype = _types.canonical_heat_type(node.dtype)
    obj._DNDarray__split = split
    obj._DNDarray__device = ref.device
    obj._DNDarray__comm = ref.comm
    obj._DNDarray__balanced = True
    obj._DNDarray__array = node
    if _COLLECTIVES:
        register_root(obj)
    return obj


def wrap_node(node: LazyArray, gshape, split, ref):
    """Public form of :func:`_wrap` for collective-node call sites outside
    this module (deferred ``apply`` consumers wrap their own metadata)."""
    if DNDarray is None:
        _resolve_siblings()
    return _wrap(node, tuple(int(s) for s in gshape), split, ref)


def defer_binary(operation, t1, t2, jt, fn_kwargs):
    """Record a binary elementwise/broadcast op; None = use the eager engine.

    Mirrors the eager engine's layout rules: identical-layout operands (or
    array⊗scalar) chain on the *physical* payloads so ragged padding stays in
    the padding; unpadded broadcasts follow split dominance. Padded operands
    with mismatched shapes are left to the eager engine.
    """
    if DNDarray is None:
        _resolve_siblings()
    if getattr(operation, "_no_fusion", False):
        # impure engine ops (closures reading other DNDarrays, e.g. where's
        # cond-alignment op) must not be traced abstractly or cached
        return _unfused("binary", "no_fusion_op")
    d1, d2 = isinstance(t1, DNDarray), isinstance(t2, DNDarray)
    ref = t1 if d1 else t2
    if d1 and d2:
        if t1.comm is not t2.comm:
            return _unfused("binary", "mixed_comm")
        if t1.split == t2.split and t1.shape == t2.shape:
            a, b = _phys_node(t1), _phys_node(t2)
            if a is None or b is None:
                return _unfused("binary", "tracer_payload")
            out_shape, out_split = t1.shape, t1.split
            expected_phys = _aval(a)[0]
        elif not t1.padded and not t2.padded:
            a, b = _phys_node(t1), _phys_node(t2)
            if a is None or b is None:
                return _unfused("binary", "tracer_payload")
            # shape check stays eager-identical (error parity)
            out_shape = _broadcast_shape(t1.shape, t2.shape)
            expected_phys = out_shape

            def _bcast_split(split, shape):
                return None if split is None else split + (len(out_shape) - len(shape))

            out_split = _bcast_split(t1.split, t1.shape)
            if out_split is None:
                out_split = _bcast_split(t2.split, t2.shape)
        else:
            return _unfused("binary", "padded_broadcast")
    elif d1 and isinstance(t2, _SCALARS):
        a, b = _phys_node(t1), t2
        if a is None:
            return _unfused("binary", "tracer_payload")
        out_shape, out_split = t1.shape, t1.split
        expected_phys = _aval(a)[0]
    elif d2 and isinstance(t1, _SCALARS):
        a, b = t1, _phys_node(t2)
        if b is None:
            return _unfused("binary", "tracer_payload")
        out_shape, out_split = t2.shape, t2.split
        expected_phys = _aval(b)[0]
    else:
        return _unfused("binary", "foreign_operand")  # np.ndarray / list / ...
    try:
        node = record(operation, (cast(a, jt), cast(b, jt)), **fn_kwargs)
    except Exception as exc:  # narrowed: ONE policy decides what falls back
        if not resilience.record_recoverable(exc):
            raise
        return _unfused("binary", "record_failed:" + type(exc).__name__)
    if node.shape != tuple(expected_phys):
        return _unfused("binary", "shape_changed")  # not elementwise after all
    return _wrap(node, out_shape, out_split, ref)


def defer_local(operation, x, promote_jt, kwargs):
    """Record a unary elementwise op on the physical payload (padding garbage
    stays in padding); None = use the eager engine."""
    if DNDarray is None:
        _resolve_siblings()
    if getattr(operation, "_no_fusion", False):
        return _unfused("local", "no_fusion_op")
    if not hashable_kwargs(kwargs):
        return _unfused("local", "unhashable_kwargs")
    n = _phys_node(x)
    if n is None:
        return _unfused("local", "tracer_payload")
    phys_shape = _aval(n)[0]
    try:
        # the promote cast records a node too — keep it inside the guard
        if promote_jt is not None:
            n = cast(n, promote_jt)
        node = record(operation, (n,), **kwargs)
    except Exception as exc:  # narrowed: ONE policy decides what falls back
        if not resilience.record_recoverable(exc):
            raise
        return _unfused("local", "record_failed:" + type(exc).__name__)
    if node.shape != phys_shape:
        return _unfused("local", "shape_changed")  # the eager engine's rare branch
    return _wrap(node, x.shape, x.split, x)


def defer_reduce(partial_op, x, axis, keepdims, out_split, dtype, kwargs):
    """Record a reduction; the chain (including the reduction) then costs one
    program + one sync at the forcing point. None = use the eager engine."""
    if DNDarray is None:
        _resolve_siblings()
    if getattr(partial_op, "_no_fusion", False):
        return _unfused("reduce", "no_fusion_op")
    if not hashable_kwargs(kwargs):
        return _unfused("reduce", "unhashable_kwargs")
    axes = None if axis is None else ((axis,) if isinstance(axis, int) else tuple(axis))
    padded_fast = x.padded and axes is not None and x.split not in axes
    ax_kw = axis if (axis is None or isinstance(axis, int)) else tuple(axis)
    try:
        # _logical_node records the un-pad slice, so it must sit INSIDE the
        # guarded region: a record-time failure there (including an injected
        # fusion.record fault) falls back to eager like any other
        child = _phys_node(x) if (padded_fast or not x.padded) else _logical_node(x)
        if child is None:
            return _unfused("reduce", "tracer_payload")
        node = record(partial_op, (child,), axis=ax_kw, keepdims=keepdims, **kwargs)
        if dtype is not None:
            node = cast(node, _types.canonical_heat_type(dtype).jax_type())
    except Exception as exc:  # narrowed: ONE policy decides what falls back
        if not resilience.record_recoverable(exc):
            raise
        return _unfused("reduce", "record_failed:" + type(exc).__name__)
    if padded_fast:
        gshape = list(x.shape)
        for a in sorted(axes, reverse=True):
            if keepdims:
                gshape[a] = 1
            else:
                del gshape[a]
        gshape = tuple(gshape)
    else:
        gshape = node.shape
    return _wrap(node, gshape, out_split, x)


def defer_cum(operation, x, axis, dtype):
    """Record a cumulative op (padding is a suffix, so any-axis scans leave
    the data region untouched); None = use the eager engine."""
    if DNDarray is None:
        _resolve_siblings()
    if getattr(operation, "_no_fusion", False):
        return _unfused("cum", "no_fusion_op")
    n = _phys_node(x)
    if n is None:
        return _unfused("cum", "tracer_payload")
    phys_shape = _aval(n)[0]
    try:
        node = record(operation, (n,), axis=axis)
        if dtype is not None:
            node = cast(node, _types.canonical_heat_type(dtype).jax_type())
    except Exception as exc:  # narrowed: ONE policy decides what falls back
        if not resilience.record_recoverable(exc):
            raise
        return _unfused("cum", "record_failed:" + type(exc).__name__)
    if node.shape != phys_shape:
        return _unfused("cum", "shape_changed")
    return _wrap(node, x.shape, x.split, x)


# ----------------------------------------------------------------------
# collective nodes: deferred reshard + deferred shard_map kernels
# ----------------------------------------------------------------------
def defer_reshard(payload: LazyArray, gshape, split, padded, axis, comm):
    """Record a redistribution of a pending chain to split ``axis`` as DAG
    nodes (un-pad the old split if ragged, re-pad the new one if ragged,
    then a ``with_sharding_constraint`` the fused program's partitioner
    satisfies with its own collective schedule). Returns the new payload
    node, or None when recording fails recoverably (callers then force and
    reshard eagerly — today's behavior).

    The ``collective.reshard`` fault site is the CALLER's to check before
    any metadata mutates (``resplit_`` does); this function only records.
    """
    if DNDarray is None:
        _resolve_siblings()
    try:
        node = payload
        if padded:
            node = record(_unpad_op, (node,), axis=split, size=int(gshape[split]))
        if axis is not None:
            n = int(gshape[axis])
            p = comm.size
            block = -(-n // p) if n else 0
            pad = block * p - n
            if pad:
                node = record(_pad_split_op, (node,), axis=axis, pad=pad)
        target = comm.sharding(len(node.shape), axis)
        node = record(_reshard_op, (node,), sharding=target)
    except Exception as exc:  # narrowed: ONE policy decides what falls back
        if not resilience.record_recoverable(exc):
            raise
        return _unfused("reshard", "record_failed:" + type(exc).__name__)
    if telemetry._MODE:
        detail = "replicated" if axis is None else f"split={int(axis)}"
        telemetry.record_fused_collective("reshard", cid=node.cid, detail=detail)
    return node


@functools.lru_cache(maxsize=512)
def _apply_fn(mesh, axis_name, kernel, in_splits, ndims, out_split, check_vma):
    """Cached shard_map rendering of a ``MeshCommunication.apply`` call — one
    stable function object per (mesh, kernel, layout) so the program cache
    and the retrace ledger key deferred kernels exactly like any other op."""
    from jax.sharding import PartitionSpec

    def spec(ndim, split):
        if split is None:
            return PartitionSpec()
        entries = [None] * ndim
        entries[split] = axis_name
        return PartitionSpec(*entries)

    def ospec(split):
        if split is None:
            return PartitionSpec()
        return PartitionSpec(*([None] * split), axis_name)

    if isinstance(out_split, tuple):
        # multi-output kernel: one spec per output (record_multi's picks)
        out_spec = tuple(ospec(s) for s in out_split)
    else:
        out_spec = ospec(out_split)
    fn = jax.shard_map(
        kernel,
        mesh=mesh,
        in_specs=tuple(spec(nd, s) for nd, s in zip(ndims, in_splits)),
        out_specs=out_spec,
        check_vma=check_vma,
    )

    def run(*args):
        return fn(*args)

    run.__name__ = "apply:" + getattr(kernel, "__name__", "kernel")
    return run


def defer_apply(comm, kernel, xs, in_splits, out_split, check_vma: bool = False):
    """Record a ``shard_map`` kernel over ``comm``'s mesh as DAG node(s), so
    record→kernel→record chains compile into ONE program (the deferred form
    of ``MeshCommunication.apply``). ``xs`` entries are DNDarrays (pending
    chains stay pending), already-recorded LazyArray nodes, or concrete
    arrays. With a scalar ``out_split`` the kernel is single-output and ONE
    LazyArray node is returned; a tuple/list ``out_split`` declares a
    multi-output kernel (one split entry per output) and a TUPLE of selector
    nodes comes back — one per output, all landing in the same dispatch via
    :func:`record_multi`'s sibling batching. Callers wrap their own global
    metadata via :func:`wrap_node`; None means declined (padded or tracer
    operands, record failures → the eager ``comm.apply`` path).

    The ``collective.apply`` fault site fires here at record time, every
    call; the in-kernel ``collective.<verb>`` sites and their telemetry
    fire whenever the kernel is actually traced (first record of a
    signature, and the fused program's compile) — steady-state collective
    accounting for deferred kernels lives in the compiled program
    (:func:`program_hlo` + ``telemetry.hlo_collective_counts``)."""
    if DNDarray is None:
        _resolve_siblings()
    if not (_ENABLED and _COLLECTIVES):
        return None
    multi = isinstance(out_split, (tuple, list))
    if multi:
        out_split = tuple(out_split)
    if getattr(kernel, "_no_fusion", False):
        return _unfused("apply", "no_fusion_op")
    children = []
    ndims = []
    for x in xs:
        if isinstance(x, DNDarray):
            if x.padded:
                return _unfused("apply", "padded_operand")
            child = _phys_node(x)
            if child is None:
                return _unfused("apply", "tracer_payload")
        elif isinstance(x, LazyArray):
            # a pre-recorded operand node (a cast/reshape the caller staged)
            child = x if x._value is None else x._value
        elif isinstance(x, (jax.Array, np.ndarray)):
            child = x
        else:
            return _unfused("apply", "foreign_operand")
        children.append(child)
        ndims.append(len(_aval(child)[0]))
    if resilience._ARMED:
        # record time IS dispatch time for the fault contract: the site
        # fires per call, exactly like the eager apply, and propagates
        resilience.check("collective.apply")
    try:
        fn = _apply_fn(
            comm.mesh,
            comm.axis_name,
            kernel,
            tuple(in_splits),
            tuple(ndims),
            out_split,
            check_vma,
        )
        if multi:
            nodes = record_multi(fn, tuple(children))
        else:
            nodes = record(fn, tuple(children))
    except Exception as exc:  # narrowed: ONE policy decides what falls back
        if not resilience.record_recoverable(exc):
            raise
        return _unfused("apply", "record_failed:" + type(exc).__name__)
    if telemetry._MODE:
        cid = nodes[0].cid if multi else nodes.cid
        telemetry.record_fused_collective(
            "apply:" + getattr(kernel, "__name__", "kernel"), cid=cid
        )
    return nodes


def phys_node(x):
    """Public :func:`_phys_node`: a DNDarray's physical payload as a
    recordable child (pending node or concrete array), or None for tracer
    payloads — deferral call sites stage casts/reshapes on it with
    :func:`record`/:func:`cast` before handing it to :func:`defer_apply`."""
    if DNDarray is None:
        _resolve_siblings()
    return _phys_node(x)


def _operand_children(engine, xs):
    """Shared operand intake for the global-view deferral front-ends: the
    LOGICAL node of each DNDarray (padding sliced off inside the program —
    global-view kernels see exactly ``larray``), pre-recorded nodes and
    concrete arrays as-is. Returns None (after the ``_unfused`` breadcrumb)
    when any operand cannot be recorded."""
    children = []
    for x in xs:
        if isinstance(x, DNDarray):
            child = _logical_node(x)
            if child is None:
                return _unfused(engine, "tracer_payload")
        elif isinstance(x, LazyArray):
            child = x if x._value is None else x._value
        elif isinstance(x, (jax.Array, np.ndarray)):
            child = x
        else:
            return _unfused(engine, "foreign_operand")
        children.append(child)
    return children


def defer_op(fn, xs, **kw):
    """Record a single-output global-view op over DNDarray/array operands
    (their LOGICAL views — padding is sliced off inside the program, GSPMD
    schedules any collectives the op implies). Returns the LazyArray node,
    or None to decline — the eager path's exact global-view semantics make
    this the deferral seam for jit-level kernels like CG's fused step."""
    if DNDarray is None:
        _resolve_siblings()
    if not (_ENABLED and _COLLECTIVES):
        return None
    if getattr(fn, "_no_fusion", False):
        return _unfused("op", "no_fusion_op")
    if not hashable_kwargs(kw):
        return _unfused("op", "unhashable_kwargs")
    try:
        # _logical_node records the un-pad slice: inside the guard, like
        # defer_reduce — a record-time failure there falls back to eager
        children = _operand_children("op", xs)
        if children is None:
            return None
        node = record(fn, tuple(children), **kw)
    except Exception as exc:  # narrowed: ONE policy decides what falls back
        if not resilience.record_recoverable(exc):
            raise
        return _unfused("op", "record_failed:" + type(exc).__name__)
    if telemetry._MODE:
        telemetry.record_fused_collective(
            "op:" + getattr(fn, "__name__", "op"), cid=node.cid
        )
    return node


def defer_multi(fn, xs, **kw):
    """Record a multi-output global-view op (a tuple-returning kernel like
    CholQR2's (Q, R, ok)) over DNDarray/array operands as selector nodes —
    :func:`defer_op`'s :func:`record_multi` form. Returns the tuple of
    LazyArray selectors, or None to decline."""
    if DNDarray is None:
        _resolve_siblings()
    if not (_ENABLED and _COLLECTIVES):
        return None
    if getattr(fn, "_no_fusion", False):
        return _unfused("multi", "no_fusion_op")
    if not hashable_kwargs(kw):
        return _unfused("multi", "unhashable_kwargs")
    try:
        # _logical_node records the un-pad slice: inside the guard, like
        # defer_reduce — a record-time failure there falls back to eager
        children = _operand_children("multi", xs)
        if children is None:
            return None
        nodes = record_multi(fn, tuple(children), **kw)
    except Exception as exc:  # narrowed: ONE policy decides what falls back
        if not resilience.record_recoverable(exc):
            raise
        return _unfused("multi", "record_failed:" + type(exc).__name__)
    if telemetry._MODE:
        telemetry.record_fused_collective(
            "multi:" + getattr(fn, "__name__", "op"), cid=nodes[0].cid
        )
    return nodes


def defer_matmul(a, b):
    """Record a 2-D ``a @ b`` as a collective DAG node: the nine
    split-combination schedules of the matmul case table
    (``linalg.basics._matmul_program``) become sharding constraints on a
    ``jnp.matmul`` node, so the contraction's psum/allgather compiles INTO
    the enclosing chain's program instead of forcing it. Pending operands
    stay pending. Returns the wrapped DNDarray at the case table's output
    split, or None to decline (N-D, padded or tracer operands, mixed comms,
    record failures → the eager pinned-program path).

    The ``collective.matmul`` fault site is the CALLER's (``matmul`` checks
    it before either path dispatches, like ``resplit_`` does for
    ``collective.reshard``); this function only records."""
    if DNDarray is None:
        _resolve_siblings()
    if not (_ENABLED and _COLLECTIVES):
        return None
    if a.ndim != 2 or b.ndim != 2:
        return _unfused("matmul", "non_2d")
    if a.comm is not b.comm:
        return _unfused("matmul", "mixed_comm")
    if a.padded or b.padded:
        return _unfused("matmul", "padded_operand")
    an, bn = _phys_node(a), _phys_node(b)
    if an is None or bn is None:
        return _unfused("matmul", "tracer_payload")
    # the case table: split-0 lhs keeps rows local (out split 0); split-1
    # rhs keeps cols local (out split 1); everything else contracts into a
    # replicated result
    if a.split == 0:
        out_split = 0
    elif b.split == 1:
        out_split = 1
    else:
        out_split = None
    comm = a.comm
    try:
        node = record(
            _matmul_op,
            (an, bn),
            a_sharding=comm.sharding(2, a.split),
            b_sharding=comm.sharding(2, b.split),
            out_sharding=comm.sharding(2, out_split),
        )
    except Exception as exc:  # narrowed: ONE policy decides what falls back
        if not resilience.record_recoverable(exc):
            raise
        return _unfused("matmul", "record_failed:" + type(exc).__name__)
    if telemetry._MODE:
        telemetry.record_fused_collective(
            "matmul",
            cid=node.cid,
            detail=f"{a.split}x{b.split}->{out_split}",
        )
    return _wrap(node, (int(a.shape[0]), int(b.shape[1])), out_split, a)


def programs() -> dict:
    """Per-cached-program accounting keyed by program key: op ``family``,
    ``compiles``, ``dispatches`` and total ``roots`` dispatched, with any
    memoized :func:`program_costs` estimate merged in as ``cost``. The
    record side of telemetry's ``report()["programs"]`` top-N block."""
    out = {}
    for info in _PROGRAM_INFO.values():
        rec = {k: v for k, v in info.items() if k != "key"}
        cost = _COSTS.get(info["key"])
        if cost is not None:
            # the raw HLO instruction lines are audit-only detail: merged
            # into report()/the metrics sink they would bloat every flush
            # with multi-hundred-char strings per program
            rec["cost"] = {k: v for k, v in cost.items() if k != "collective_lines"}
        out[info["key"]] = rec
    return out


def _replicated_like(sharding):
    """The fully-replicated sharding over the SAME mesh as ``sharding`` (the
    audit's everything-replicated lowering keeps per-host semantics exact by
    replicating over the identical device set), or None when the sharding
    type cannot express one."""
    if sharding is None:
        return None
    try:
        from jax.sharding import NamedSharding, PartitionSpec

        return NamedSharding(sharding.mesh, PartitionSpec())
    except Exception:  # noqa: BLE001 - non-Named shardings degrade to unsharded
        return None


def _leaf_placeholder(entry, replicated: bool = False):
    """An abstract stand-in for one signature leaf: sharded
    ``ShapeDtypeStruct`` for arrays, a zero of the recorded type for python
    scalars — enough to AOT-lower the program without any live operand.
    ``replicated=True`` swaps the recorded sharding for its fully-replicated
    form on the same mesh (the audit baseline)."""
    if entry[0] == "L":
        _, shape, dtype, sharding = entry
        if replicated:
            sharding = _replicated_like(sharding)
        try:
            return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
        except Exception:  # noqa: BLE001 - sharding kwarg availability varies
            return jax.ShapeDtypeStruct(shape, dtype)
    try:
        return entry[1](0)
    except Exception:  # noqa: BLE001 - exotic scalar types degrade to int
        return 0


def _estimate_cost(sig, replicated: bool = False) -> dict:
    """Best-effort cost estimate of one cached program, from its signature
    alone: logical operand/result bytes from the recorded avals, flops and
    bytes-accessed from XLA's post-compile cost analysis, and the in-program
    collective instruction counts parsed from the optimized HLO
    (``telemetry.hlo_collective_counts``). Re-lowers the signature from
    abstract specs — an extra compile, which is why callers memoize.
    ``replicated=True`` lowers with every array leaf fully replicated over
    its mesh instead — the denominator of the audit's replication check."""
    leaves = [e for e in sig if e[0] in ("L", "Ls")]
    specs = [_leaf_placeholder(e, replicated) for e in leaves]
    cost: dict = {
        "operand_bytes": 0,
        "result_bytes": None,
        "flops": None,
        "bytes_accessed": None,
        "collectives": {},
    }
    for e in leaves:
        if e[0] == "L":
            size = 1
            for s in e[1]:
                size *= int(s)
            cost["operand_bytes"] += size * np.dtype(e[2]).itemsize
    try:
        outs = jax.eval_shape(_build(sig), *specs)
        total = 0
        for o in jax.tree_util.tree_leaves(outs):
            size = 1
            for s in o.shape:
                size *= int(s)
            total += size * np.dtype(o.dtype).itemsize
        cost["result_bytes"] = total
    except Exception as exc:  # noqa: BLE001 - best-effort estimate
        cost["error"] = repr(exc)
        return cost
    try:
        compiled = jax.jit(_build(sig)).lower(*specs).compile()
        try:
            # XLA's post-compile memory accounting: the static per-host
            # peak (arguments + outputs + temps) the admission gate and
            # the resplit O(n/p) assertion surface read. Best-effort —
            # some backends return None or omit the analysis entirely.
            ma = compiled.memory_analysis()
            if ma is not None:
                arg_b = int(getattr(ma, "argument_size_in_bytes", 0))
                out_b = int(getattr(ma, "output_size_in_bytes", 0))
                tmp_b = int(getattr(ma, "temp_size_in_bytes", 0))
                cost["memory"] = {
                    "argument_bytes": arg_b,
                    "output_bytes": out_b,
                    "temp_bytes": tmp_b,
                    "alias_bytes": int(getattr(ma, "alias_size_in_bytes", 0)),
                    "generated_code_bytes": int(
                        getattr(ma, "generated_code_size_in_bytes", 0)
                    ),
                    "peak_bytes": arg_b + out_b + tmp_b,
                }
        except (AttributeError, RuntimeError, TypeError, ValueError):
            pass  # no memory analysis on this backend: flops/HLO still bank
        hlo_text = compiled.as_text()
        entries = telemetry.hlo_collectives(hlo_text)
        cost["collectives"] = {}
        for entry in entries:
            cost["collectives"][entry["op"]] = cost["collectives"].get(entry["op"], 0) + 1
        # the raw instruction lines carry the payload shapes — the audit's
        # bytes-on-wire estimate parses them (analysis/audit.py)
        cost["collective_lines"] = [entry["line"] for entry in entries]
        analysis = compiled.cost_analysis()
        if isinstance(analysis, (list, tuple)):
            analysis = analysis[0] if analysis else {}
        if isinstance(analysis, dict):
            if "flops" in analysis:
                cost["flops"] = float(analysis["flops"])
            if "bytes accessed" in analysis:
                cost["bytes_accessed"] = float(analysis["bytes accessed"])
    except Exception as exc:  # noqa: BLE001 - cost analysis is backend-dependent
        cost["error"] = repr(exc)
    return cost


def _note_cost_error(key: str, cost: dict) -> None:
    """Count a failed cost estimate (``cost["error"]``) into the per-key
    error ledger and warn once per session — a backend that cannot analyze
    programs must be visible, not silently averaged away."""
    global _COST_ERROR_WARNED
    if "error" not in cost:
        _COST_ERROR_KEYS.discard(key)
        return
    _COST_ERROR_KEYS.add(key)
    if not _COST_ERROR_WARNED:
        _COST_ERROR_WARNED = True
        import warnings

        warnings.warn(
            ProgramCostWarning(
                f"cost estimate failed for cached program {key} "
                f"({cost['error']}); further failures are counted into "
                "report()['programs']['cost_errors'] without re-warning"
            ),
            stacklevel=4,
        )


def cost_error_count() -> int:
    """How many cached programs currently hold a failed cost estimate
    (``report()["programs"]["cost_errors"]``)."""
    return len(_COST_ERROR_KEYS)


def program_costs(top: Optional[int] = None, refresh: bool = False) -> dict:
    """Cost estimates for the cached sharded programs, keyed by program key
    and ranked by dispatch count (``top`` limits how many are analyzed).
    Estimates come from :func:`_estimate_cost` and are memoized per key
    (``refresh=True`` recomputes); each entry also carries the program's
    ``family`` and ``dispatches`` so flops×dispatches ranks total spend,
    and (where the backend exposes ``memory_analysis``) a ``memory`` block
    with the static argument/output/temp/peak bytes per host. Backend
    estimate failures are never silent: they count into
    :func:`cost_error_count` and warn once per session.
    Never touches live data or forces a pending chain."""
    ranked = sorted(
        _PROGRAM_INFO.items(), key=lambda kv: kv[1]["dispatches"], reverse=True
    )
    if top is not None:
        ranked = ranked[:top]
    out = {}
    for sig, info in ranked:
        key = info["key"]
        cost = None if refresh else _COSTS.get(key)
        if cost is None:
            cost = _COSTS[key] = _estimate_cost(sig)
            _note_cost_error(key, cost)
        public = {k: v for k, v in cost.items() if k != "collective_lines"}
        out[key] = dict(
            public, family=info["family"], dispatches=info["dispatches"]
        )
    return out


def program_audit_info(top: Optional[int] = None, refresh: bool = False) -> dict:
    """Audit-grade introspection of every cached sharded program, keyed by
    program key (the AOT seam ``heat_tpu/analysis/audit.py`` reasons over):
    the op ``family``, ``dispatches``, the recorded ``leaves`` (shape/dtype/
    replicated flag), the leaf ``mesh_size`` and ``split_leaves`` count, the
    memoized :func:`_estimate_cost` under the recorded shardings (``cost``)
    and under everything-replicated shardings (``replicated_cost``) — the
    pair whose ratio exposes a replication blowup without depending on the
    chain's depth. Lowers from abstract specs only: never touches live data
    or forces a pending chain."""
    ranked = sorted(
        _PROGRAM_INFO.items(), key=lambda kv: kv[1]["dispatches"], reverse=True
    )
    if top is not None:
        ranked = ranked[:top]
    out = {}
    for sig, info in ranked:
        key = info["key"]
        cost = None if refresh else _COSTS.get(key)
        if cost is None:
            cost = _COSTS[key] = _estimate_cost(sig)
            _note_cost_error(key, cost)
        rcost = None if refresh else _REPL_COSTS.get(key)
        if rcost is None:
            rcost = _REPL_COSTS[key] = _estimate_cost(sig, replicated=True)
        leaves = []
        mesh_size = 1
        split_leaves = 0
        for e in sig:
            if e[0] != "L":
                continue
            sharding = e[3]
            replicated = True
            if sharding is not None:
                try:
                    mesh_size = max(mesh_size, len(sharding.device_set))
                    replicated = bool(sharding.is_fully_replicated)
                except Exception:  # noqa: BLE001 - sharding APIs vary by type
                    pass
            if not replicated:
                split_leaves += 1
            size = 1
            for s in e[1]:
                size *= int(s)
            leaves.append(
                {
                    "shape": tuple(int(s) for s in e[1]),
                    "dtype": str(np.dtype(e[2])),
                    "nbytes": size * np.dtype(e[2]).itemsize,
                    "replicated": replicated,
                }
            )
        out[key] = {
            "family": info["family"],
            "dispatches": info["dispatches"],
            "mesh_size": mesh_size,
            "split_leaves": split_leaves,
            "leaves": leaves,
            "cost": dict(cost),
            "replicated_cost": dict(rcost),
        }
    return out


def program_hlo(x, optimized: bool = True) -> str:
    """The (post-partitioning) HLO text of the program that would force
    ``x``'s pending chain — the compiled-side cross-check for collective
    accounting (``telemetry.hlo_collective_counts`` parses it). ``x`` is a
    DNDarray with a pending payload or a pending LazyArray; lowering here
    neither forces nor caches anything. ``optimized=False`` returns the
    pre-optimization StableHLO instead."""
    node = getattr(x, "_payload", x)
    if not (isinstance(node, LazyArray) and node._value is None):
        raise ValueError("program_hlo needs a pending recorded chain")
    sig, leaves = _signature([node])
    lowered = jax.jit(_build(sig)).lower(*leaves)
    if not optimized:
        return lowered.as_text()
    return lowered.compile().as_text()
