"""Runtime telemetry layer: collective accounting, forcing-point attribution,
retrace detection, scoped sessions and the trace timeline.

The reference framework ships no profiling subsystem (SURVEY.md §5) and — per
the Dask-MPI communication study (arxiv 2101.08878) and the array
redistribution work (arxiv 2112.01075) — per-collective counts and bytes
moved are the load-bearing metrics for diagnosing distributed array
performance. This module measures them natively at the three hot seams
instead of leaving tests and benches to infer them from HLO dumps:

* **Collectives** — every ``MeshCommunication`` verb (``allreduce`` /
  ``allgather`` / ``alltoall`` / ``ppermute`` / ``bcast`` / ``exscan`` /
  ``scan``) records op type, mesh axis, dtype and logical bytes moved
  (:func:`record_collective`, queried via :func:`collective_counts`).
  The explicitly-scheduled linalg kernels (TSQR, panel QR, blocked
  substitution) declare their schedule the same way. Counts are recorded at
  *Python call time*: for in-kernel (``shard_map``) use that is once per
  program trace; for the linalg wrappers it is once per wrapper call with
  the schedule's declared multiplicity.
* **Forcing points** — every ``fusion.force()`` is attributed to *what*
  triggered it (``parray``/``larray`` access, ``print``, ``indexing``,
  ``io``, ``collective``, ``pytree`` flatten) together with the chain depth
  forced, so blocking host reads become attributable instead of invisible
  (:func:`forcing_points`). Call sites scope themselves with
  :func:`force_trigger`; the outermost scope wins.
* **Compile/retrace tracking** — fusion-cache misses are keyed by *op
  family* (the DAG's op identities, ignoring shapes); when the same family
  keeps missing under different leaf shapes — shape churn defeating the
  sharded-program cache — a :class:`RetraceWarning` fires exactly once per
  family (:func:`record_retrace`). ``MeshCommunication.apply`` jit builds
  are counted per kernel name (:func:`record_compile`).

``HEAT_TPU_TELEMETRY={0,1,verbose}`` is the knob (read at import; in-process
control via :func:`set_mode`/:func:`enabled`). Disabled is the default and
costs one module-attribute check per instrumented site — the overhead guard
in tests/test_telemetry.py pins the telemetry-enabled eager-chain dispatch
rate at >= 0.9x the disabled rate.

The trace timeline
------------------
``verbose`` keeps a capped, monotonic-timestamped **event log** of typed
events (:func:`events`): ``record`` / ``compile`` / ``dispatch`` /
``blocking_sync`` / ``collective`` / ``fused_collective`` / ``force`` /
``degraded`` / ``fault`` / ``io_retry`` / ``io`` / ``checkpoint`` /
``checkpoint_phase`` / ``timer`` / ``span_begin`` / ``span_end`` /
``memory`` / ``memory_gate`` / ``memory_oom`` (the live-buffer ledger's
samples, gate decisions and OOM forensics — ``core/memledger.py``; the
exporter renders ``memory`` samples as per-host Perfetto counter tracks).
Events of
one fused chain's lifecycle share a **correlation id** (``cid``, assigned at
record time by ``core/fusion.py`` and inherited along the chain): the
``dispatch`` event lists every batched root's cid plus the sharded-program
key it launched (``fusion.cache_stats()["program_keys"]``), and the
``blocking_sync`` event that waited on it carries the same cid — see
doc/internals_distribution.md for the schema and the cid contract. The log
is a bounded deque: truncation is *visible* as ``events_dropped`` in
``report()["timeline"]`` (cap via ``HEAT_TPU_TELEMETRY_EVENTS``).

:func:`export_trace` renders the timeline as Chrome/Perfetto trace-event
JSON (load in ``ui.perfetto.dev`` or ``chrome://tracing``): spans and timers
as B/E duration pairs, dispatch→blocking-sync as async (``b``/``e``) pairs
keyed by cid, everything else as instants — one process row per host
(``multihost.process_index()``), with :func:`merge_traces` stitching
per-host files of a multihost run into one. ``python -m heat_tpu.telemetry``
pretty-prints / diffs ``report_json`` artifacts and validates trace files.

Scoped sessions
---------------
:func:`scope` opens a reentrant **telemetry session**: counters, spans and
events recorded inside are visible *isolated* through the query functions
(the innermost scope wins) while still rolling up into the enclosing scopes
and the global state live — the per-session surface ROADMAP item 4's
multi-tenant serving layer attaches to. Completed scopes are archived under
``report()["scopes"]`` (re-entering a path accumulates).

:func:`span` scopes all counters to a named region (spans nest —
``"fit/iter"`` paths) and integrates with ``utils/profiling.Timer``: timers
closing inside an active span are attributed to it, and every span records
its own wall time into the Timer registry under ``span:<path>``.

The forcing path's phases (:class:`Phases`, switched by :func:`tracing`:
telemetry on, or a ``jax.profiler`` session recording) are the one place the
program times itself on the profiler's clock: ``heat.force`` with its
``admit``/``walk``/``lookup``/``dispatch``/``install`` children,
``heat.place`` and ``heat.read`` are ``TraceAnnotation``s on the host line
of the session's ``.xplane.pb``, beside its device lines, and the same
intervals feed ``fusion.cache_stats()``'s ``phase_*`` keys. The timeline's
own timestamps (``perf_counter``) cannot be laid beside the profile's
events: the xplane's clock starts with its session. :func:`span` is such an
annotation too.

:func:`report` returns the whole picture as one structured dict — including
a ``memory`` block (``profiling.device_memory_stats``, live-buffer bytes,
the owner-attributed ledger + high watermark and the admission-gate state
from ``core/memledger.py``; best-effort, device stats empty off-TPU) and a
top-N ``programs`` block (per-cached-program dispatch counts with a
``cost_errors`` tally; :func:`program_costs` adds flops / bytes-accessed /
in-program collective estimates *and static memory peaks* from each
program's HLO, on demand because the estimate compiles). :func:`report_json` serializes it deterministically
(tuple keys are joined, sets sorted — never ``default=str`` drift);
``HEAT_TPU_METRICS=<path>`` streams it as JSON-lines periodically and at
exit (:func:`set_metrics_sink`) so long jobs are observable externally.

Event emission stays near-zero-cost when ``HEAT_TPU_TELEMETRY=0`` and never
forces a pending chain or adds a blocking sync of its own.

The module also owns the *compiled-program* side of collective accounting:
:func:`hlo_collectives` / :func:`hlo_collective_counts` parse an XLA HLO
dump into per-type collective instruction counts, and
:func:`collective_budget_excess` diffs them against a named budget — the
readable replacement for the hand-pinned ``len(coll) <= 7`` assertions the
linalg suites used to carry.
"""

from __future__ import annotations

import atexit
import json
import os
import re
import sys
import threading
import time
import warnings
from collections import deque
from contextlib import contextmanager
from typing import Any, Dict, List, Optional

import jax
from jax.profiler import TraceAnnotation as _Annotation

__all__ = [
    "Phases",
    "RetraceWarning",
    "TimelineDroppedWarning",
    "active",
    "async_forcing",
    "checkpoint_events",
    "collective_budget_excess",
    "collective_counts",
    "collectives",
    "current_trigger",
    "degraded",
    "degraded_counts",
    "dispatches",
    "enabled",
    "end_blocking_sync",
    "events",
    "export_trace",
    "fault_events",
    "force_trigger",
    "forcing_points",
    "fused_collectives",
    "hlo_collective_counts",
    "hlo_collectives",
    "io_retries",
    "merge_traces",
    "no_phase",
    "nonfinite_counts",
    "on_timer",
    "operand_bytes",
    "program_costs",
    "ready_then",
    "record_async_dispatch",
    "record_blocking_sync",
    "record_checkpoint",
    "record_collective",
    "record_collective_operand",
    "record_compile",
    "record_degraded",
    "record_dispatch",
    "record_event",
    "record_fault",
    "record_force",
    "record_fused_collective",
    "record_io_retry",
    "record_nonfinite",
    "record_retrace",
    "record_unfused",
    "record_var_path",
    "report",
    "report_json",
    "reset",
    "retraces",
    "scope",
    "scope_reports",
    "set_metrics_sink",
    "set_mode",
    "span",
    "spans",
    "trace_collective_parity",
    "trace_events",
    "tracing",
    "unfused_reasons",
    "validate_trace",
    "var_paths",
    "verbose",
]


class RetraceWarning(UserWarning):
    """An op family keeps missing the fusion program cache under different
    leaf shapes — shape churn is defeating the sharded-program cache and
    every miss pays a fresh XLA compile."""


class TimelineDroppedWarning(UserWarning):
    """The trace timeline hit its event cap and evicted the oldest events —
    the recorded window is now TRUNCATED, and any analysis over it
    (``tracelens.analyze``, exported traces) undercounts whatever happened
    before the surviving suffix. One-shot per :func:`reset`; raise
    ``HEAT_TPU_TELEMETRY_EVENTS`` to keep the whole window."""


_OFF_VALUES = ("", "0", "false", "off", "no")


def _parse_mode(value) -> int:
    if isinstance(value, bool):
        return 1 if value else 0
    if isinstance(value, int):
        return max(0, min(2, value))
    v = str(value).strip().lower()
    if v in _OFF_VALUES:
        return 0
    if v in ("2", "verbose", "debug"):
        return 2
    return 1


#: 0 = off, 1 = on, 2 = verbose. A module attribute (not a function) so the
#: instrumented hot paths can gate on ``telemetry._MODE`` with one attribute
#: read — the near-zero-overhead-when-disabled contract.
_MODE = _parse_mode(os.environ.get("HEAT_TPU_TELEMETRY", "0"))

#: distinct leaf-shape signatures a family may miss with before the one-shot
#: shape-churn warning fires. First-time compiles of a handful of fixed
#: shapes are normal warmup (each program stays cached afterwards); only a
#: family that keeps producing NEW shapes — paying a fresh XLA compile per
#: step — is the churn pathology, so the default sits well above warmup.
_RETRACE_WARN_AFTER = int(os.environ.get("HEAT_TPU_TELEMETRY_RETRACE_WARN", "8"))

#: trace-timeline event cap per state (global and per scope). Overflow drops
#: the OLDEST events and counts them (``report()["timeline"]["events_dropped"]``)
#: — truncation is visible, never silent.
_EVENT_CAP = int(os.environ.get("HEAT_TPU_TELEMETRY_EVENTS", "8192"))

#: one-shot latch for :class:`TimelineDroppedWarning` — the first cap
#: eviction after a :func:`reset` warns loudly; subsequent drops only count
_DROP_WARNED = False

#: programs shown in ``report()["programs"]`` (ranked by dispatch count)
_TOP_PROGRAMS = int(os.environ.get("HEAT_TPU_TELEMETRY_TOP_PROGRAMS", "5"))

#: memory-ledger sampling hook (``core/memledger.py`` installs its ``note``
#: here at import — set-attribute, not import, so this module stays
#: dependency-free). Called at the dispatch/force/collective/checkpoint
#: record seams so the live-buffer high watermark tracks the events that
#: change memory; None until the ledger module loads.
_MEM_HOOK = None

#: flight-recorder hook (``core/health_runtime.py`` installs its ring-buffer
#: append here at import — same set-attribute pattern as ``_MEM_HOOK``).
#: Called with every typed event dict from :func:`_note_event`, including at
#: plain ``HEAT_TPU_TELEMETRY=1`` where the verbose timelines stay empty —
#: the always-on black box costs one deque append per event. None until the
#: health module loads or when ``HEAT_TPU_FLIGHT=0``.
_FLIGHT_HOOK = None

#: blocking-sync completion hook (``core/health_runtime.py``): called as
#: ``_SYNC_HOOK(kind, cid, dur_s)`` whenever :func:`end_blocking_sync`
#: closes a token — feeds the host-wait latency histograms and resolves
#: dispatch→done durations without this module importing the health layer.
_SYNC_HOOK = None

#: elastic-supervisor stats hook (``core/elastic.py`` installs its ``stats``
#: snapshot here at import — same set-attribute pattern). ``report()`` calls
#: it to populate ``report()["elastic"]`` (preemptions survived, reforms,
#: downtime, steps replayed); None until the elastic module loads.
_ELASTIC_HOOK = None

#: autoscale-controller stats hook (``core/autoscale.py`` installs its
#: ``stats`` snapshot here at import — same set-attribute pattern).
#: ``report()`` joins it as ``report()["autoscale"]`` (controller state,
#: shed tiers, decision counters, mesh devices vs. baseline) and the
#: opsplane collector reads it for the ``heat_tpu_autoscale_*`` families;
#: None until the autoscale module loads.
_AUTOSCALE_HOOK = None

#: multi-process runtime stats hook (``core/multihost.py`` installs its
#: ``report_stats`` snapshot here at import — same set-attribute pattern).
#: ``report()`` joins it as ``report()["multihost"]`` (heartbeats, lost
#: peers, barrier waits/timeouts, abandoned barrier threads) and the
#: opsplane collector reads it for the ``heat_tpu_peers_*`` /
#: ``heat_tpu_barrier_*`` families; None until the multihost module loads.
_MULTIHOST_HOOK = None

#: numerics-lens sampling hook (``core/numlens.py`` installs its
#: ``_on_dispatch`` here via ``numlens.set_mode`` — same set-attribute
#: pattern). Called by ``fusion.force`` as ``_NUMLENS_HOOK(sig, leaves,
#: roots, values, info)`` after a fused program's root values land, so the
#: lens can sample streaming tensor statistics and shadow-replay drift
#: audits; None whenever ``HEAT_TPU_NUMLENS`` is off — the disabled hot
#: path pays exactly this one ``is None`` check.
_NUMLENS_HOOK = None


def active() -> bool:
    """Whether telemetry is recording (``HEAT_TPU_TELEMETRY`` knob)."""
    return _MODE > 0


def verbose() -> bool:
    """Whether the trace timeline is kept (``HEAT_TPU_TELEMETRY=verbose``)."""
    return _MODE >= 2


def set_mode(mode) -> int:
    """Set the telemetry mode in-process (0/off, 1/on, 2/'verbose');
    returns the previous mode. Accepts the same spellings as the env knob."""
    global _MODE
    prev, _MODE = _MODE, _parse_mode(mode)
    return prev


@contextmanager
def enabled(mode=1):
    """Context manager running with telemetry on (tests, bench legs)."""
    prev = set_mode(mode)
    try:
        yield
    finally:
        set_mode(prev)


def tracing() -> bool:
    """Whether the forcing path times its phases: telemetry is on, or a
    profiler session is recording (``jax.profiler.start_trace`` however it
    was started: ``utils/profiling.trace``, a TensorBoard capture, a
    benchmark's traced run). The one switch of :class:`Phases`; no knob of
    its own."""
    return _MODE > 0 or _Annotation.is_enabled()


def _annotate(name: str, **stats):
    """An entered ``jax.profiler.TraceAnnotation``, or None when no profiler
    session is recording. The span lands on ``/host:CPU`` of the session's
    ``.xplane.pb``, on the profiler's own clock: it starts with the session,
    so no timestamp taken here could be laid beside the profile's events.
    How closely the profiler aligns its device lines with its host lines is
    the profiler's affair (PERF.md, section 7)."""
    if not _Annotation.is_enabled():
        return None
    ann = _Annotation(name, **stats)
    ann.__enter__()
    return ann


class Phases:
    """One traced region of the forcing path, as the profiler and the
    counters see it: a parent span ``name`` whose children (``name.<phase>``)
    lie side by side, each closing where the next opens, and the same
    boundaries read once each from ``time.perf_counter_ns`` into :attr:`ns`
    (phase -> nanoseconds). Callers create one only while :func:`tracing`.
    ``split=False`` keeps the parent and the clock reads and opens no child
    span (a recursive force: its time belongs to the phase that caused it)."""

    __slots__ = ("name", "ns", "_split", "_span", "_child", "_cur", "_t0", "_t")

    def __init__(self, name: str, split: bool = True, **stats):
        self.name = name
        self.ns: Dict[str, int] = {}
        # every clock read lies outside the span it bounds (before it opens,
        # after it closes): a counted interval encloses its span
        self._t0 = self._t = time.perf_counter_ns()
        self._span = _annotate(name, **stats)
        self._split = split and self._span is not None
        self._child = self._cur = None

    def phase(self, name: str) -> int:
        """Close the running phase and open ``name`` (nothing to do when it
        is the running one); returns the nanoseconds the closed one took."""
        if name is not None and name == self._cur:
            return 0
        if self._child is not None:
            self._child.__exit__(None, None, None)
            self._child = None
        now = time.perf_counter_ns()
        took = 0
        if self._cur is not None:
            took = now - self._t
            self.ns[self._cur] = self.ns.get(self._cur, 0) + took
        self._cur, self._t = name, now
        if name is not None and self._split:
            self._child = _annotate(self.name + "." + name)
        return took

    def note(self, **stats) -> None:
        """Add keyword stats to the parent span (what was not known when it
        opened: the program key)."""
        if self._span is not None:
            self._span.set_metadata(**stats)

    def close(self) -> int:
        """Close the running phase and the parent; returns the nanoseconds
        from open to close."""
        self.phase(None)
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
            self._t = time.perf_counter_ns()
        return self._t - self._t0


def no_phase(name: str) -> int:
    """:meth:`Phases.phase` of a region that is not traced: what a function
    that marks its phases is handed when :func:`tracing` is off."""
    return 0


def ready_then(mark, value, fetch, ready: str = "ready", copy: str = "copy"):
    """``fetch(value)``, a blocking device-to-host read, with the wait for the
    device parted from the copy: phase ``ready`` of the region whose
    :meth:`Phases.phase` is ``mark`` lasts until the device has made
    ``value`` (``jax.block_until_ready``), phase ``copy`` is what is left of
    the fetch once the value is ready. The copy is asked for first
    (``copy_to_host_async``), as the fetch alone asks for it: the runtime
    starts it behind the program, not behind the host's wake-up, so the two
    phases add up to the read as it is when nobody looks (asked for after the
    wait, a scalar's copy is 90 us longer on a v5e: PERF.md, PR 37). For the
    four places where the program itself blocks on the device (``heat.read``,
    ``heat.kmeans.fit``, ``heat.qr``, ``heat.lasso.fit``). A region that is not
    traced (:func:`no_phase`) makes the fetch alone."""
    if mark is no_phase:
        return fetch(value)
    mark(ready)
    for leaf in jax.tree_util.tree_leaves(value):
        leaf.copy_to_host_async()
    jax.block_until_ready(value)
    mark(copy)
    return fetch(value)


# ----------------------------------------------------------------------
# counter state: one _State per telemetry session
# ----------------------------------------------------------------------
class _State:
    """One isolated set of telemetry counters + an event deque.

    The module keeps one shared global state (``_GLOBAL``) plus a
    THREAD-LOCAL stack of scope states: every active :func:`scope` pushes
    its own onto the entering thread's stack. Record functions write to the
    global state and every scope on the calling thread's stack (so scopes
    roll up live); query functions read the calling thread's INNERMOST
    scope (so concurrent sessions are isolated)."""

    __slots__ = (
        "path", "t0", "wall_s", "calls", "collectives", "forces", "retraces",
        "compiles", "dispatches", "degraded", "unfused", "var_paths", "nonfinite",
        "io_retries", "checkpoint", "fused_collectives", "async_", "blocking",
        "sync_wait", "faults", "spans", "events", "events_dropped",
    )

    def __init__(self, path: str = ""):
        self.path = path
        self.calls = 1
        self.wall_s = 0.0
        self.clear()

    def clear(self) -> None:
        self.t0 = time.perf_counter()
        self.collectives: Dict[str, Dict[str, Any]] = {}
        self.forces: Dict[str, Dict[str, Any]] = {}
        self.retraces: Dict[tuple, Dict[str, Any]] = {}
        self.compiles: Dict[str, int] = {}
        self.dispatches: Dict[str, Dict[str, int]] = {}
        self.degraded: Dict[str, Dict[str, Any]] = {}
        self.unfused: Dict[str, Dict[str, int]] = {}
        self.var_paths: Dict[str, int] = {}
        self.nonfinite: Dict[str, int] = {}
        self.io_retries: Dict[str, int] = {}
        self.checkpoint: Dict[str, int] = {}
        self.fused_collectives: Dict[str, int] = {}
        self.async_ = {"dispatches": 0, "roots": 0, "multi_root_batches": 0}
        self.blocking: Dict[str, int] = {}
        # true host-wait durations per trigger, aggregated in NON-verbose
        # mode too (the verbose timeline carries the per-event stamps)
        self.sync_wait: Dict[str, Dict[str, float]] = {}
        self.faults: Dict[str, int] = {}
        self.spans: Dict[str, Dict[str, Any]] = {}
        self.events: deque = deque(maxlen=_EVENT_CAP)
        self.events_dropped = 0

    def append_event(self, ev: dict) -> None:
        if self.events.maxlen is not None and len(self.events) == self.events.maxlen:
            self.events_dropped += 1
            global _DROP_WARNED
            if not _DROP_WARNED:
                _DROP_WARNED = True
                warnings.warn(
                    "trace timeline hit its event cap "
                    f"({self.events.maxlen}): oldest events are being dropped "
                    "and the recorded window is truncated — raise "
                    "HEAT_TPU_TELEMETRY_EVENTS to keep the whole window "
                    "(tracelens refuses truncated windows without "
                    "allow_partial)",
                    TimelineDroppedWarning,
                    stacklevel=3,
                )
        self.events.append(ev)


def _add_int(dst: Dict[str, int], src: Dict[str, int]) -> None:
    for k, v in src.items():
        dst[k] = dst.get(k, 0) + v


def _merge_state(dst: _State, src: _State) -> None:
    """Accumulate ``src`` into ``dst`` (the completed-scope rollup)."""
    for op, rec in src.collectives.items():
        d = dst.collectives.setdefault(op, {"count": 0, "bytes": 0, "axes": {}, "dtypes": {}})
        d["count"] += rec["count"]
        d["bytes"] += rec["bytes"]
        _add_int(d["axes"], rec["axes"])
        _add_int(d["dtypes"], rec["dtypes"])
    for trig, rec in src.forces.items():
        d = dst.forces.setdefault(trig, {"count": 0, "depth_total": 0, "max_depth": 0, "compiles": 0})
        d["count"] += rec["count"]
        d["depth_total"] += rec["depth_total"]
        d["max_depth"] = max(d["max_depth"], rec["max_depth"])
        d["compiles"] += rec["compiles"]
    for fam, rec in src.retraces.items():
        d = dst.retraces.setdefault(fam, {"misses": 0, "keys": set(), "warned": False})
        d["misses"] += rec["misses"]
        if not d["warned"]:
            # bounded union: the set exists to cross the warn threshold, so
            # archived scopes never need (and must never hold) more keys —
            # re-entered scopes under shape churn would otherwise leak
            for key in rec["keys"]:
                if len(d["keys"]) >= _RETRACE_WARN_AFTER:
                    d["warned"] = True
                    break
                d["keys"].add(key)
        d["warned"] = d["warned"] or rec["warned"]
    _add_int(dst.compiles, src.compiles)
    for eng, rec in src.dispatches.items():
        _add_int(dst.dispatches.setdefault(eng, {}), rec)
    for key, rec in src.degraded.items():
        d = dst.degraded.setdefault(key, {"count": 0, "stages": {}, "last_error": ""})
        d["count"] += rec["count"]
        _add_int(d["stages"], rec["stages"])
        d["last_error"] = rec["last_error"] or d["last_error"]
    for eng, rec in src.unfused.items():
        _add_int(dst.unfused.setdefault(eng, {}), rec)
    _add_int(dst.var_paths, src.var_paths)
    _add_int(dst.nonfinite, src.nonfinite)
    _add_int(dst.io_retries, src.io_retries)
    _add_int(dst.checkpoint, src.checkpoint)
    _add_int(dst.fused_collectives, src.fused_collectives)
    _add_int(dst.async_, src.async_)
    _add_int(dst.blocking, src.blocking)
    for kind, rec in src.sync_wait.items():
        d = dst.sync_wait.setdefault(kind, {"count": 0, "total_s": 0.0, "max_s": 0.0})
        d["count"] += rec["count"]
        d["total_s"] += rec["total_s"]
        d["max_s"] = max(d["max_s"], rec["max_s"])
    _add_int(dst.faults, src.faults)
    for path, rec in src.spans.items():
        d = dst.spans.setdefault(
            path, {"calls": 0, "total_s": 0.0, "collectives": {}, "forces": 0, "retraces": 0, "timers": {}}
        )
        d["calls"] += rec["calls"]
        d["total_s"] += rec["total_s"]
        d["forces"] += rec["forces"]
        d["retraces"] += rec["retraces"]
        _add_int(d["collectives"], rec["collectives"])
        for t, s in rec["timers"].items():
            d["timers"][t] = d["timers"].get(t, 0.0) + s
    for ev in src.events:
        dst.append_event(ev)
    dst.events_dropped += src.events_dropped
    dst.wall_s += src.wall_s
    dst.calls += src.calls


_GLOBAL = _State()
#: completed-scope accumulators, keyed by scope path (re-entry accumulates)
_SCOPES: Dict[str, _State] = {}

# Scope/span/trigger stacks are THREAD-LOCAL: each thread (a serving
# session, a client of `ht.serving`) resolves its own innermost scope, so a
# second thread entering a scope can never interleave with the first's
# stack. Records still roll up into the shared _GLOBAL state, queries read
# the calling thread's innermost scope, and the completed-scope archive is
# merged under _SCOPE_LOCK.
_TLS = threading.local()
#: the common fast path (no scope active on this thread) — one cached tuple,
#: no per-record allocation
_GLOBAL_ONLY = (_GLOBAL,)
#: every scope state currently active on ANY thread (reset() must clear all)
_ACTIVE_SCOPE_STATES: List[_State] = []
_SCOPE_LOCK = threading.Lock()


def _scope_stack() -> List[_State]:
    """This thread's active scope states, innermost last (created lazily)."""
    stack = getattr(_TLS, "scopes", None)
    if stack is None:
        stack = _TLS.scopes = []
    return stack


def _states():
    """Every state the calling thread records into: the shared global state
    plus this thread's own scope stack."""
    stack = getattr(_TLS, "scopes", None)
    if not stack:
        return _GLOBAL_ONLY
    return [_GLOBAL] + stack


def _span_stack() -> list:
    stack = getattr(_TLS, "spans", None)
    if stack is None:
        stack = _TLS.spans = []
    return stack


def _trigger_stack() -> List[str]:
    stack = getattr(_TLS, "triggers", None)
    if stack is None:
        stack = _TLS.triggers = []
    return stack


def _cur() -> _State:
    stack = getattr(_TLS, "scopes", None)
    return stack[-1] if stack else _GLOBAL


def reset() -> None:
    """Clear every counter, span, event and completed scope of every active
    state, and reset the ``utils/profiling`` timer registry, the
    ``core/memledger`` session state (watermark, gate counters, stored OOM
    report — the budget arming itself is configuration and survives) and the
    ``core/health_runtime`` session state (flight ring, latency histograms,
    SLO windows, stall log — knobs and watchdog arming survive) and the
    ``core/numlens`` session state (tensor stats, drift ledger, canary and
    training streams — the lens mode survives) with them:
    the report surfaces are joined — ``report()`` merges timers, the memory
    block and the health block in, so a reset that left any stale would
    mislabel the next bench's report. The mode is left untouched; active
    :func:`scope`/:func:`span` stacks keep recording."""
    global _DROP_WARNED
    _DROP_WARNED = False
    _GLOBAL.clear()
    with _SCOPE_LOCK:
        for st in list(_ACTIVE_SCOPE_STATES):  # every thread's active scopes
            st.clear()
        _SCOPES.clear()
    try:
        from ..utils import profiling

        profiling.reset()
    except Exception:  # pragma: no cover - import-order safety only
        pass
    try:
        from . import memledger

        memledger.reset()
    except Exception:  # pragma: no cover - import-order safety only
        pass
    try:
        from . import health_runtime

        health_runtime.reset()
    except Exception:  # pragma: no cover - import-order safety only
        pass
    try:
        from . import elastic

        elastic.reset()
    except Exception:  # pragma: no cover - import-order safety only
        pass
    try:
        from . import numlens

        numlens.reset()
    except Exception:  # pragma: no cover - import-order safety only
        pass
    try:
        from . import serving

        serving.reset()
    except Exception:  # pragma: no cover - import-order safety only
        pass
    try:
        from . import opsplane

        opsplane.reset()
    except Exception:  # pragma: no cover - import-order safety only
        pass
    try:
        from . import autoscale

        autoscale.reset()
    except Exception:  # pragma: no cover - import-order safety only
        pass


# ----------------------------------------------------------------------
# the trace timeline: typed, monotonic-timestamped events
# ----------------------------------------------------------------------
def _emit(kind: str, **fields) -> dict:
    """Append one typed event to every active state's timeline. Callers gate
    on ``_MODE >= 2``; the event carries a monotonic ``ts`` (perf_counter
    seconds — the exporter converts to trace microseconds) and the innermost
    scope path when a scope is active."""
    ev: Dict[str, Any] = {"kind": kind, "ts": time.perf_counter()}
    ev.update(fields)
    stack = getattr(_TLS, "scopes", None)
    if stack:
        ev["scope"] = stack[-1].path
    for st in _states():
        st.append_event(ev)
    return ev


def _note_event(kind: str, **fields) -> Optional[dict]:
    """Record one typed event on the joined timeline surfaces: the verbose
    per-state timelines (``_MODE >= 2``) and — even at plain
    ``HEAT_TPU_TELEMETRY=1`` — the always-on flight ring when
    ``core/health_runtime.py`` has installed ``_FLIGHT_HOOK``. Returns the
    (shared, mutable) event dict when anything recorded it, else None."""
    if _MODE >= 2:
        ev = _emit(kind, **fields)
        if _FLIGHT_HOOK is not None:
            _FLIGHT_HOOK(ev)
        return ev
    if _MODE and _FLIGHT_HOOK is not None:
        ev = {"kind": kind, "ts": time.perf_counter()}
        ev.update(fields)
        stack = getattr(_TLS, "scopes", None)
        if stack:
            ev["scope"] = stack[-1].path
        _FLIGHT_HOOK(ev)
        return ev
    return None


def record_event(kind: str, **fields) -> Optional[dict]:
    """Emit one typed trace-timeline event (no counter side effects). The
    public seam for subsystems with lifecycle phases worth a timestamp but
    no counter (checkpoint phases, io ingest milestones). Lands on the
    verbose timeline (``HEAT_TPU_TELEMETRY=verbose``) and on the flight ring
    (any active mode, flight armed); returns the (mutable) event dict, or
    None when nothing recorded it."""
    if not _MODE:
        return None
    return _note_event(kind, **fields)


def events() -> List[dict]:
    """The capped timeline of the innermost active state (empty unless
    ``HEAT_TPU_TELEMETRY=verbose``)."""
    return list(_cur().events)


# ----------------------------------------------------------------------
# scoped telemetry sessions
# ----------------------------------------------------------------------
@contextmanager
def scope(name: str):
    """Open an isolated telemetry session named ``name``.

    Counters/spans/events recorded inside are visible through the query
    functions as the scope's OWN (isolation) while also recording into every
    enclosing scope and the global state live (rollup) — so a multi-tenant
    server can meter one session without losing the fleet-wide picture.
    Scopes are reentrant and nest (paths join as ``outer/inner``); on exit
    the session is archived under ``report()["scopes"][path]``, re-entering
    the same path accumulates (``calls`` counts entries). The stack is
    THREAD-LOCAL: concurrent threads each resolve their own innermost scope
    (two threads entering scopes never interleave stacks), while the global
    rollup and the completed-scope archive stay shared. Yields the scope
    path, or None when telemetry is off."""
    if not _MODE:
        yield None
        return
    stack = _scope_stack()
    path = (stack[-1].path + "/" + str(name)) if stack else str(name)
    st = _State(path)
    stack.append(st)
    with _SCOPE_LOCK:
        _ACTIVE_SCOPE_STATES.append(st)
    try:  # the health layer scopes its histograms alongside (joined surface)
        from . import health_runtime

        health_runtime._push_scope(path)
    except Exception:  # pragma: no cover - import-order safety only
        pass
    try:
        yield path
    finally:
        st.wall_s = time.perf_counter() - st.t0
        # remove by identity: reset()/nesting must never pop the wrong frame
        for i in range(len(stack) - 1, -1, -1):
            if stack[i] is st:
                del stack[i]
                break
        with _SCOPE_LOCK:
            for i in range(len(_ACTIVE_SCOPE_STATES) - 1, -1, -1):
                if _ACTIVE_SCOPE_STATES[i] is st:
                    del _ACTIVE_SCOPE_STATES[i]
                    break
            acc = _SCOPES.get(path)
            if acc is None:
                acc = _SCOPES[path] = _State(path)
                acc.calls = 0
                acc.wall_s = 0.0
            _merge_state(acc, st)
        try:
            from . import health_runtime

            health_runtime._pop_scope(path)
        except Exception:  # pragma: no cover - import-order safety only
            pass


def _scope_doc(st: _State) -> Dict[str, Any]:
    """One archived scope rendered report-shaped."""
    return {
        "calls": st.calls,
        "wall_s": st.wall_s,
        "collectives": _render_collectives(st),
        "collective_counts": {op: rec["count"] for op, rec in st.collectives.items()},
        "fused_collectives": dict(st.fused_collectives),
        "async_forcing": _render_async(st),
        "forcing_points": _render_forces(st),
        "dispatches": {k: dict(v) for k, v in st.dispatches.items()},
        "unfused_reasons": {k: dict(v) for k, v in st.unfused.items()},
        "retraces": _render_retraces(st),
        "degraded": _render_degraded(st),
        "nonfinite": dict(st.nonfinite),
        "io_retries": dict(st.io_retries),
        "checkpoint": dict(st.checkpoint),
        "faults": dict(st.faults),
        "jit_compiles": dict(st.compiles),
        "spans": _render_spans(st),
        "timeline": {
            "events": len(st.events),
            "events_dropped": st.events_dropped,
            "cap": _EVENT_CAP,
        },
    }


def scope_reports() -> Dict[str, Dict[str, Any]]:
    """Every completed scope's archived counters, keyed by scope path."""
    return {path: _scope_doc(acc) for path, acc in _SCOPES.items()}


# ----------------------------------------------------------------------
# collectives
# ----------------------------------------------------------------------
def operand_bytes(x) -> int:
    """Logical payload bytes of a pytree of arrays as seen at the call site
    (per-participant shard bytes inside a ``shard_map`` kernel, global bytes
    outside). Tracers count via their abstract shape; shapeless leaves
    (python scalars) count zero."""
    import numpy as np

    total = 0
    try:
        import jax

        leaves = jax.tree_util.tree_leaves(x)
    except Exception:  # pragma: no cover - jax always importable here
        leaves = [x]
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            continue
        total += int(np.prod(shape, dtype=np.int64)) * np.dtype(dtype).itemsize
    return total


def record_collective_operand(op: str, axis: Optional[str], x, count: int = 1) -> None:
    """Record a collective whose payload is the pytree ``x``: one leaf walk
    derives both the logical bytes and the dtype (the communication verbs'
    single call site). No-op when telemetry is off."""
    if not _MODE:
        return
    import numpy as np

    try:
        import jax

        leaves = jax.tree_util.tree_leaves(x)
    except Exception:  # pragma: no cover - jax always importable here
        leaves = [x]
    total = 0
    dtype = None
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        dt = getattr(leaf, "dtype", None)
        if shape is None or dt is None:
            continue
        total += int(np.prod(shape, dtype=np.int64)) * np.dtype(dt).itemsize
        if dtype is None:
            dtype = str(dt)
    record_collective(op, axis, total, dtype, count)


def _in_trace() -> bool:
    """Whether a jax trace is active right now (verbose events only: a
    collective recorded from inside a ``shard_map`` kernel is stamped at
    TRACE time, not execution time — the timeline marks it so)."""
    import jax

    return not jax.core.trace_ctx.is_top_level()


#: sleep per recorded collective when the ``trace.hostdelay`` fault site is
#: armed on this host — the straggler-attribution test seam
_TRACE_DELAY_S = float(os.environ.get("HEAT_TPU_TRACE_DELAY_MS", "20")) / 1e3


def _maybe_host_delay() -> None:
    """Straggler test seam: when fault injection has armed the
    ``trace.hostdelay`` site on THIS host (``resilience.inject``), every
    collective record sleeps ``HEAT_TPU_TRACE_DELAY_MS`` before stamping its
    event — one simulated slow worker whose cumulative lag the tracelens
    straggler attribution must name. Looked up via ``sys.modules`` (never an
    import: resilience imports this module) and gated on its armed flag, so
    the cost is one dict probe when fault injection is idle."""
    res = sys.modules.get("heat_tpu.core.resilience")
    if res is None or not getattr(res, "_ARMED", False):
        return
    try:
        res.check("trace.hostdelay")
    except Exception:  # noqa: BLE001 - the raised fault IS the trigger
        time.sleep(_TRACE_DELAY_S)


def record_collective(
    op: str,
    axis: Optional[str] = None,
    nbytes: int = 0,
    dtype: Optional[str] = None,
    count: int = 1,
) -> None:
    """Record ``count`` logical collectives of type ``op`` moving ``nbytes``
    over mesh axis ``axis``. Called by the communication verbs and the
    declared linalg schedules; no-op when telemetry is off."""
    if not _MODE:
        return
    _maybe_host_delay()
    for st in _states():
        rec = st.collectives.get(op)
        if rec is None:
            rec = st.collectives[op] = {"count": 0, "bytes": 0, "axes": {}, "dtypes": {}}
        rec["count"] += count
        rec["bytes"] += int(nbytes) * count
        if axis is not None:
            rec["axes"][axis] = rec["axes"].get(axis, 0) + count
        if dtype is not None:
            rec["dtypes"][dtype] = rec["dtypes"].get(dtype, 0) + count
    if _MODE >= 2 or _FLIGHT_HOOK is not None:
        _note_event(
            "collective",
            op=op, axis=axis, bytes=int(nbytes), dtype=dtype, count=count,
            traced=_in_trace(),
        )
    for frame in _span_stack():
            frame.collectives[op] = frame.collectives.get(op, 0) + count
    if _MEM_HOOK is not None:
        _MEM_HOOK("collective")


def _render_collectives(st: _State) -> Dict[str, Dict[str, Any]]:
    return {
        op: {
            "count": rec["count"],
            "bytes": rec["bytes"],
            "axes": dict(rec["axes"]),
            "dtypes": dict(rec["dtypes"]),
        }
        for op, rec in st.collectives.items()
    }


def collective_counts() -> Dict[str, int]:
    """Per-type logical collective counts — the assertable surface for tests
    and benches: ``{"allreduce": 3, "allgather": 1, ...}``. Inside a
    :func:`scope` this is the scope's own isolated view."""
    return {op: rec["count"] for op, rec in _cur().collectives.items()}


def collectives() -> Dict[str, Dict[str, Any]]:
    """Full per-type accounting: count, bytes moved, per-axis and per-dtype
    breakdowns."""
    return _render_collectives(_cur())


def record_fused_collective(
    kind: str, cid: Optional[int] = None, detail: Optional[str] = None
) -> None:
    """Count one collective NODE recorded into the fusion DAG (a deferred
    split-crossing reduction's psum, a deferred ``reshard``, a deferred
    ``apply:<kernel>``). These collectives execute INSIDE fused programs, so
    :func:`collective_counts` does not see them at dispatch time — this
    ledger counts them at record time, and ``fusion.program_hlo`` +
    :func:`hlo_collective_counts` cross-check the compiled side. ``detail``
    rides the timeline event only (e.g. a reshard's target split axis — what
    the tracelens ping-pong detector keys on)."""
    if not _MODE:
        return
    _maybe_host_delay()
    for st in _states():
        st.fused_collectives[kind] = st.fused_collectives.get(kind, 0) + 1
    _note_event("fused_collective", op=kind, cid=cid, detail=detail)


def fused_collectives() -> Dict[str, int]:
    """Per-kind counts of collective nodes recorded into fusion DAGs."""
    return dict(_cur().fused_collectives)


# ----------------------------------------------------------------------
# asynchronous forcing: dispatches vs blocking syncs
# ----------------------------------------------------------------------
def record_async_dispatch(
    n_roots: int,
    cid: Optional[int] = None,
    cids=(),
    program: Optional[str] = None,
    sessions=None,
) -> None:
    """Count one asynchronous ``fusion.force`` dispatch covering ``n_roots``
    DAG roots (>1 = independent live roots batched into one multi-output
    program). Dispatches install device futures without blocking. ``cid`` is
    the triggering chain's correlation id, ``cids`` every batched root's,
    ``program`` the sharded-program key launched (None for degraded/
    quarantined replays) — the timeline event links the whole lifecycle.
    ``sessions`` (aligned with ``cids``) names each batched root's serving
    session when cross-session batching grouped tenants into one dispatch,
    so tracelens/SLO attribution can bill the right tenant per cid."""
    if not _MODE:
        return
    for st in _states():
        st.async_["dispatches"] += 1
        st.async_["roots"] += int(n_roots)
        if n_roots > 1:
            st.async_["multi_root_batches"] += 1
    if sessions is not None and any(s is not None for s in sessions):
        _note_event(
            "dispatch", roots=int(n_roots), cid=cid, cids=list(cids),
            program=program, sessions=list(sessions),
        )
    else:
        _note_event("dispatch", roots=int(n_roots), cid=cid, cids=list(cids), program=program)
    if _MEM_HOOK is not None:
        _MEM_HOOK("dispatch")


def record_blocking_sync(kind: str, cid: Optional[int] = None) -> Optional[dict]:
    """Count one host boundary (``item``/``numpy``/``print``/``shards``)
    that had to synchronously materialize a PENDING chain — reads of values
    already dispatched (in flight or done) are free and never counted. The
    assertable surface for "this chain cost one sync".

    ``cid`` is the pending chain's correlation id. Returns the timeline
    event token (any active mode) so the call site can close it with
    :func:`end_blocking_sync` once the host actually holds the value — the
    event then carries the true wall duration of the sync. In verbose mode
    the token lives on the per-state timelines; at plain mode it feeds the
    flight ring and the ``sync_wait`` aggregate (the non-verbose answer to
    "how long did we wait")."""
    if not _MODE:
        return None
    for st in _states():
        st.blocking[kind] = st.blocking.get(kind, 0) + 1
    ev = _note_event("blocking_sync", where=kind, cid=cid)
    if ev is not None:
        return ev
    # mode 1, flight disarmed: a plain token still feeds the wait aggregate
    return {"kind": "blocking_sync", "ts": time.perf_counter(), "where": kind, "cid": cid}


def end_blocking_sync(token: Optional[dict]) -> None:
    """Close a blocking-sync timeline event returned by
    :func:`record_blocking_sync`: stamps the wall ``dur`` the host boundary
    spent from noting the pending chain to holding the materialized value,
    folds it into every active state's ``sync_wait`` aggregate (count /
    total / max per trigger — reported in non-verbose mode too), and feeds
    the health layer's latency histograms via ``_SYNC_HOOK``."""
    if token is None:
        return
    dur = time.perf_counter() - token["ts"]
    token["dur"] = dur
    kind = str(token.get("where"))
    for st in _states():
        rec = st.sync_wait.get(kind)
        if rec is None:
            rec = st.sync_wait[kind] = {"count": 0, "total_s": 0.0, "max_s": 0.0}
        rec["count"] += 1
        rec["total_s"] += dur
        if dur > rec["max_s"]:
            rec["max_s"] = dur
    if _SYNC_HOOK is not None:
        _SYNC_HOOK(kind, token.get("cid"), dur)


def _render_async(st: _State) -> Dict[str, Any]:
    return {
        "dispatches": st.async_["dispatches"],
        "roots_dispatched": st.async_["roots"],
        "multi_root_batches": st.async_["multi_root_batches"],
        "blocking_syncs": dict(st.blocking),
        "blocking_total": sum(st.blocking.values()),
        "sync_wait": {
            kind: {
                "count": rec["count"],
                "total_s": round(rec["total_s"], 6),
                "max_s": round(rec["max_s"], 6),
            }
            for kind, rec in st.sync_wait.items()
        },
    }


def async_forcing() -> Dict[str, Any]:
    """The async-forcing picture: program ``dispatches`` (with total
    ``roots_dispatched`` and how many dispatches batched multiple roots)
    versus ``blocking_syncs`` — host boundaries that synchronously forced a
    pending chain, by kind, with their total."""
    return _render_async(_cur())


# ----------------------------------------------------------------------
# forcing-point attribution
# ----------------------------------------------------------------------
class _TriggerScope:
    """Reentrant scope naming the forcing point for any ``fusion.force``
    that fires inside it; the OUTERMOST scope wins (a print that forces via
    ``larray`` is attributed to print, not larray)."""

    __slots__ = ("name",)

    def __init__(self, name: str):
        self.name = name

    def __enter__(self) -> "_TriggerScope":
        _trigger_stack().append(self.name)
        return self

    def __exit__(self, *exc) -> None:
        _trigger_stack().pop()


_TRIGGER_SCOPES: Dict[str, _TriggerScope] = {}


def force_trigger(name: str) -> _TriggerScope:
    """The (cached, reusable) attribution scope for forcing trigger ``name``."""
    scope_ = _TRIGGER_SCOPES.get(name)
    if scope_ is None:
        scope_ = _TRIGGER_SCOPES[name] = _TriggerScope(name)
    return scope_


def current_trigger() -> str:
    """The attribution for a force firing right now (outermost scope, or the
    bare-``parray``-access default)."""
    stack = getattr(_TLS, "triggers", None)
    return stack[0] if stack else "parray"


def record_force(trigger: str, depth: int, compiled: bool = False, cid: Optional[int] = None) -> None:
    """Record one materialized chain: ``trigger`` names the forcing point,
    ``depth`` the recorded chain depth dispatched, ``compiled`` whether this
    force paid a fresh XLA compile (cache miss), ``cid`` the chain's
    correlation id."""
    if not _MODE:
        return
    for st in _states():
        rec = st.forces.get(trigger)
        if rec is None:
            rec = st.forces[trigger] = {"count": 0, "depth_total": 0, "max_depth": 0, "compiles": 0}
        rec["count"] += 1
        rec["depth_total"] += int(depth)
        if depth > rec["max_depth"]:
            rec["max_depth"] = int(depth)
        if compiled:
            rec["compiles"] += 1
    _note_event("force", trigger=trigger, depth=int(depth), compiled=compiled, cid=cid)
    for frame in _span_stack():
            frame.forces += 1
    if _MEM_HOOK is not None:
        _MEM_HOOK("force")


def _render_forces(st: _State) -> Dict[str, Dict[str, Any]]:
    out = {}
    for trigger, rec in st.forces.items():
        out[trigger] = {
            "count": rec["count"],
            "mean_depth": round(rec["depth_total"] / rec["count"], 2) if rec["count"] else 0.0,
            "max_depth": rec["max_depth"],
            "compiles": rec["compiles"],
        }
    return out


def forcing_points() -> Dict[str, Dict[str, Any]]:
    """Per-trigger forcing histogram: count, mean/max chain depth forced,
    and how many of those forces paid a compile."""
    return _render_forces(_cur())


# ----------------------------------------------------------------------
# compile / retrace tracking
# ----------------------------------------------------------------------
def record_retrace(family: tuple, shape_key) -> None:
    """Record a fusion-cache miss for op ``family`` (the DAG's op identities)
    under leaf-shape signature ``shape_key``. When one family accumulates
    ``_RETRACE_WARN_AFTER`` distinct shape signatures, a
    :class:`RetraceWarning` fires — exactly once per family (the warn
    decision reads the GLOBAL ledger, so scopes never re-warn)."""
    if not _MODE:
        return
    grec0 = _GLOBAL.retraces.get(family)
    already_warned = grec0 is not None and grec0["warned"]
    for st in _states():
        rec = st.retraces.get(family)
        if rec is None:
            # a family the GLOBAL ledger already warned on starts warned in
            # every fresh scope state too — otherwise per-request scopes
            # under shape churn would re-accumulate keys forever
            rec = st.retraces[family] = {"misses": 0, "keys": set(), "warned": already_warned}
        rec["misses"] += 1
        if not rec["warned"] and not already_warned:
            # the key set only exists to cross the warn threshold; once warned,
            # ``misses`` tracks volume and the set stops growing (shape churn is
            # exactly the case that would otherwise accumulate keys unboundedly)
            rec["keys"].add(shape_key)
    for frame in _span_stack():
            frame.retraces += 1
    grec = _GLOBAL.retraces.get(family)
    if grec is None:  # reset() raced the loop above; nothing to warn on
        return
    if not grec["warned"] and len(grec["keys"]) >= _RETRACE_WARN_AFTER:
        for st in _states():
            rec = st.retraces.get(family)
            if rec is not None:
                rec["warned"] = True
        warnings.warn(
            RetraceWarning(
                f"op family {'/'.join(family) or '<leaf>'} recompiled under "
                f"{len(grec['keys'])} distinct input shapes ({grec['misses']} cache "
                "misses): shape churn is defeating the fusion program cache — pad "
                "or bucket the varying dimension, or force the chain before the "
                "shape-dependent step"
            ),
            stacklevel=3,
        )


def _render_retraces(st: _State) -> Dict[str, Dict[str, Any]]:
    return {
        "/".join(family) or "<leaf>": {
            "misses": rec["misses"],
            "distinct_shapes": len(rec["keys"]),
            "warned": rec["warned"],
        }
        for family, rec in st.retraces.items()
    }


def retraces() -> Dict[str, Dict[str, Any]]:
    """Per-op-family fusion-cache miss accounting."""
    return _render_retraces(_cur())


def record_compile(label: str, cid: Optional[int] = None) -> None:
    """Count a jit program build outside the fusion cache (e.g. one
    ``MeshCommunication.apply`` kernel), keyed by kernel label."""
    if not _MODE:
        return
    for st in _states():
        st.compiles[label] = st.compiles.get(label, 0) + 1
    _note_event("compile", label=label, cid=cid)


# ----------------------------------------------------------------------
# engine dispatch accounting
# ----------------------------------------------------------------------
def record_dispatch(engine: str, fused: bool) -> None:
    """Count one L3-engine dispatch (``binary``/``local``/``reduce``/``cum``)
    as deferred-into-the-DAG (``fused``) or eager."""
    if not _MODE:
        return
    key = "fused" if fused else "eager"
    for st in _states():
        rec = st.dispatches.get(engine)
        if rec is None:
            rec = st.dispatches[engine] = {"fused": 0, "eager": 0}
        rec[key] += 1


def dispatches() -> Dict[str, Dict[str, int]]:
    """Per-engine fused-vs-eager dispatch counts."""
    return {k: dict(v) for k, v in _cur().dispatches.items()}


def record_unfused(engine: str, reason: str) -> None:
    """One breadcrumb per eager-fallback site: ``engine`` declined to defer
    an op for ``reason`` (``out=``, ``where=``, ``padded_broadcast``,
    ``tracer_payload``, ``record_failed:<Type>``, ...) — so ``report()``
    shows *why* a chain wasn't fused, not just that it wasn't."""
    if not _MODE:
        return
    for st in _states():
        rec = st.unfused.get(engine)
        if rec is None:
            rec = st.unfused[engine] = {}
        rec[reason] = rec.get(reason, 0) + 1


def unfused_reasons() -> Dict[str, Dict[str, int]]:
    """Per-engine reasons ops fell back to the eager engine instead of
    deferring into the fusion DAG."""
    return {k: dict(v) for k, v in _cur().unfused.items()}


def record_var_path(path: str) -> None:
    """Count which algorithm ``ht.var`` recorded: ``onepass`` (shifted-data
    moments, every real floating input) or ``twopass`` (``jnp.var``, complex
    input). Read with :func:`var_paths`; not part of ``report()``, whose
    key set streaming consumers pin."""
    if not _MODE:
        return
    for st in _states():
        st.var_paths[path] = st.var_paths.get(path, 0) + 1


def var_paths() -> Dict[str, int]:
    """Per-algorithm counts of ``ht.var`` calls (see :func:`record_var_path`)."""
    return dict(_cur().var_paths)


# ----------------------------------------------------------------------
# resilience accounting (core/resilience.py)
# ----------------------------------------------------------------------
def record_degraded(family: tuple, stage: str, error: str = "") -> None:
    """Record one guarded-forcing degradation: the fused program for op
    ``family`` failed at ``stage`` (``compile``/``execute``) and the chain
    was re-run as per-op eager dispatch (fusion quarantines the DAG key)."""
    if not _MODE:
        return
    key = "/".join(family) or "<leaf>"
    for st in _states():
        rec = st.degraded.get(key)
        if rec is None:
            rec = st.degraded[key] = {"count": 0, "stages": {}, "last_error": ""}
        rec["count"] += 1
        rec["stages"][stage] = rec["stages"].get(stage, 0) + 1
        if error:
            rec["last_error"] = error
    _note_event("degraded", family=key, stage=stage, error=error)


def degraded_counts() -> Dict[str, int]:
    """Per-op-family guarded-forcing degradation counts — the assertable
    surface (``collective_counts()``-style) the resilience suite pins."""
    return {key: rec["count"] for key, rec in _cur().degraded.items()}


def _render_degraded(st: _State) -> Dict[str, Dict[str, Any]]:
    return {
        key: {
            "count": rec["count"],
            "stages": dict(rec["stages"]),
            "last_error": rec["last_error"],
        }
        for key, rec in st.degraded.items()
    }


def degraded() -> Dict[str, Dict[str, Any]]:
    """Full degradation accounting: count, per-stage breakdown, last error."""
    return _render_degraded(_cur())


def record_fault(site: str, pattern: str = "") -> None:
    """Count one *injected* fault firing at ``site`` (``core/resilience.py``
    harness) — faults are first-class timeline events, so a trace shows the
    degradation/retry activity right next to the fault that caused it."""
    if not _MODE:
        return
    for st in _states():
        st.faults[site] = st.faults.get(site, 0) + 1
    _note_event("fault", site=site, pattern=pattern)


def fault_events() -> Dict[str, int]:
    """Per-site injected-fault counts as telemetry saw them (the resilience
    harness's own ``fault_counts()`` is the mode-independent ledger)."""
    return dict(_cur().faults)


def record_nonfinite(where: str) -> None:
    """Count one errstate non-finite detection at forcing point ``where``."""
    if not _MODE:
        return
    for st in _states():
        st.nonfinite[where] = st.nonfinite.get(where, 0) + 1
    _note_event("nonfinite", where=where)


def nonfinite_counts() -> Dict[str, int]:
    """Per-forcing-point errstate non-finite detections."""
    return dict(_cur().nonfinite)


def record_io_retry(site: str) -> None:
    """Count one transient-``OSError`` retry at I/O injection site ``site``."""
    if not _MODE:
        return
    for st in _states():
        st.io_retries[site] = st.io_retries.get(site, 0) + 1
    _note_event("io_retry", site=site)


def io_retries() -> Dict[str, int]:
    """Per-site transient I/O retry counts."""
    return dict(_cur().io_retries)


def record_checkpoint(event: str, step: Optional[int] = None, detail: str = "") -> None:
    """Count one checkpoint lifecycle event (``utils/checkpoint.py``):
    ``save`` (manifest committed), ``restore`` (verified restore completed),
    ``corrupt`` (a checkpoint failed verification), ``fallback`` (restore
    skipped unverifiable newer checkpoints), ``gc`` (retention/debris sweep
    removed something). The assertable surface the checkpoint suite pins;
    finer-grained phase boundaries ride :func:`record_event`
    (``checkpoint_phase``) so they land on the timeline without disturbing
    these counts."""
    if not _MODE:
        return
    for st in _states():
        st.checkpoint[event] = st.checkpoint.get(event, 0) + 1
    _note_event("checkpoint", event=event, step=step, detail=detail)
    if _MEM_HOOK is not None:
        _MEM_HOOK("checkpoint")


def checkpoint_events() -> Dict[str, int]:
    """Per-event checkpoint lifecycle counts (``save``/``restore``/
    ``corrupt``/``fallback``/``gc``)."""
    return dict(_cur().checkpoint)


# ----------------------------------------------------------------------
# spans
# ----------------------------------------------------------------------
class _SpanFrame:
    __slots__ = ("path", "t0", "collectives", "forces", "retraces", "timers")

    def __init__(self, path: str):
        self.path = path
        self.t0 = time.perf_counter()
        self.collectives: Dict[str, int] = {}
        self.forces = 0
        self.retraces = 0
        self.timers: Dict[str, float] = {}


@contextmanager
def span(name: str):
    """Scope all counters to a named region. Spans nest (``"fit"`` containing
    ``"fit/iter"``), attribute the collective / forcing / retrace deltas that
    occur inside them, absorb ``utils/profiling.Timer`` records closing
    within them, and mirror their own wall time into the Timer registry as
    ``span:<path>`` so the two report surfaces stay joined. In verbose mode
    each span emits ``span_begin``/``span_end`` timeline events — the B/E
    duration pair of the exported trace. While a profiler session records,
    the span is also a ``TraceAnnotation`` of the same path, so it shows on
    the device trace (with telemetry off: of the bare ``name``, since no
    path is kept). Yields the full span path (or None when telemetry is
    off)."""
    if not _MODE:
        with _Annotation(name):
            yield None
        return
    spans = _span_stack()
    path = (spans[-1].path + "/" + name) if spans else name
    frame = _SpanFrame(path)
    spans.append(frame)
    if _MODE >= 2:
        _emit("span_begin", name=path)
    try:
        with _Annotation(path):
            yield path
    finally:
        spans.pop()
        elapsed = time.perf_counter() - frame.t0
        if _MODE >= 2:
            _emit("span_end", name=path, dur=elapsed)
        for st in _states():
            rec = st.spans.get(path)
            if rec is None:
                rec = st.spans[path] = {
                    "calls": 0,
                    "total_s": 0.0,
                    "collectives": {},
                    "forces": 0,
                    "retraces": 0,
                    "timers": {},
                }
            rec["calls"] += 1
            rec["total_s"] += elapsed
            rec["forces"] += frame.forces
            rec["retraces"] += frame.retraces
            for op, cnt in frame.collectives.items():
                rec["collectives"][op] = rec["collectives"].get(op, 0) + cnt
            for tname, secs in frame.timers.items():
                rec["timers"][tname] = rec["timers"].get(tname, 0.0) + secs
        try:  # mirror into the Timer registry (utils/profiling nesting contract)
            from ..utils import profiling

            profiling.record_timing("span:" + path, elapsed)
        except Exception:  # pragma: no cover - report must not die on import order
            pass


def on_timer(name: str, elapsed: float) -> None:
    """Called by ``utils/profiling.Timer`` on every record: timers closing
    inside an active span are attributed to EVERY enclosing span — the same
    roll-up rule as collectives/forces (``span:`` mirrors excluded) — and in
    verbose mode each close lands on the timeline as a ``timer`` event (the
    exporter renders it as a B/E pair over its duration)."""
    if name.startswith("span:"):
        return
    if _MODE >= 2:
        _emit("timer", name=name, dur=elapsed)
    for frame in _span_stack():
        frame.timers[name] = frame.timers.get(name, 0.0) + elapsed


def _render_spans(st: _State) -> Dict[str, Dict[str, Any]]:
    return {
        path: {
            "calls": rec["calls"],
            "total_s": rec["total_s"],
            "collectives": dict(rec["collectives"]),
            "forces": rec["forces"],
            "retraces": rec["retraces"],
            "timers": dict(rec["timers"]),
        }
        for path, rec in st.spans.items()
    }


def spans() -> Dict[str, Dict[str, Any]]:
    """Per-span aggregates: calls, wall seconds, attributed collective
    counts, forces, retraces and nested timer seconds."""
    return _render_spans(_cur())


# ----------------------------------------------------------------------
# report
# ----------------------------------------------------------------------
def _memory_block() -> Dict[str, Any]:
    """Best-effort memory picture: per-device backend stats (TPU exposes
    them; forced-host CPU returns {}), live device-buffer bytes, the
    owner-attributed ledger (``core/memledger.py``) and its high watermark,
    plus the admission-gate configuration and any stored OOM forensic.
    Never forces a chain, never raises — and never INITIALIZES anything:
    until the mesh singleton exists only the jax-free state (watermark,
    gate, OOM report) is included, because report() (and the background
    metrics sink) must not pin the JAX backend before the user flips
    platforms (the lazy-singleton contract in heat_tpu/__init__.py)."""
    out: Dict[str, Any] = {"device": {}, "live_buffers": {}, "ledger": {}}
    try:
        from . import memledger

        # pure module state — safe before any backend exists
        out["watermark"] = memledger.watermark()
        out["budget"] = memledger.budget_info()
        oom = memledger.last_oom()
        if oom is not None:
            out["last_oom"] = oom
    except Exception:  # pragma: no cover - import-order safety only
        pass
    try:
        from . import communication

        if communication.MESH_WORLD is None:
            return out
        from ..utils import health, profiling

        out["device"] = profiling.device_memory_stats()
        out["host"] = profiling.host_memory_stats()
        out["live_buffers"] = health.memory_report()
        from . import memledger

        out["ledger"] = memledger.ledger()
    except Exception:  # pragma: no cover - backend-dependent
        pass
    return out


def _numerics_block() -> Dict[str, Any]:
    """The numerics-observability picture (``core/numlens.py``): sampling
    counters, per-program tensor statistics, the shadow-replay drift
    ledger, SDC canary summary, training-signal streams and numeric
    findings. Pure module state — never forces a chain, never initializes
    a backend (the lens only ever sees values that already landed)."""
    try:
        from . import numlens

        return numlens.numerics_block()
    except Exception:  # pragma: no cover - import-order safety only
        return {}


def _health_block(global_view: bool = False) -> Dict[str, Any]:
    """The runtime-health picture (``core/health_runtime.py``): flight-ring
    occupancy, watchdog state + last stall diagnosis, per-program and
    per-trigger latency histograms (p50/p90/p99) and the rolling SLO gauges.
    Pure module state — never forces a chain, never initializes a backend.
    ``global_view`` mirrors report()'s ``_state`` override: the background
    metrics sink streams the GLOBAL histograms whatever scope is active."""
    try:
        from . import health_runtime

        return health_runtime.health_block(global_view=global_view)
    except Exception:  # pragma: no cover - import-order safety only
        return {}


def _programs_block(top: Optional[int] = None) -> Dict[str, Any]:
    """Top-N cached sharded programs by dispatch count (cheap metadata only;
    memoized cost estimates — including each program's static memory peaks —
    are merged in when :func:`program_costs` has been asked to compute them;
    report() itself never compiles). ``cost_errors`` counts the programs
    whose cost estimate failed in the backend (``fusion.cost_error_count``)
    — failures are counted and warned once per session, never silent."""
    from . import fusion

    progs = fusion.programs()
    ranked = sorted(progs.items(), key=lambda kv: kv[1].get("dispatches", 0), reverse=True)
    n = _TOP_PROGRAMS if top is None else top
    return {
        "cached": len(progs),
        "cost_errors": fusion.cost_error_count(),
        "top": [dict(rec, key=key) for key, rec in ranked[:n]],
    }


def program_costs(top: Optional[int] = None, refresh: bool = False) -> Dict[str, Dict[str, Any]]:
    """Per-cached-program cost estimates keyed by program key: flops and
    bytes-accessed from XLA's cost analysis of the program's HLO, in-program
    collective counts (:func:`hlo_collective_counts`), and the logical
    operand/result bytes from the recorded signature. Estimates are computed
    by AOT-lowering the cached signature from its abstract leaf specs — an
    extra compile per program, so results are memoized (``refresh=True``
    recomputes) and ``report()`` only merges already-computed ones. Never
    touches live data or forces a chain."""
    from . import fusion

    return fusion.program_costs(top=top, refresh=refresh)


def report(*, _state: Optional[_State] = None) -> Dict[str, Any]:
    """The whole telemetry picture as one structured dict (JSON-ready via
    :func:`report_json`). Includes the fusion program-cache counters, the
    ``utils/profiling`` timer registry, the ``memory`` block and every
    completed :func:`scope` — one call answers "where did the time, the
    bytes, the compiles and the memory go". Inside a scope, the counter
    blocks are the scope's own isolated view (``_state`` is the internal
    override the background metrics sink uses to always stream the GLOBAL
    view, whatever scope the main thread happens to be inside)."""
    st = _state if _state is not None else _cur()
    doc: Dict[str, Any] = {
        "enabled": active(),
        "mode": {0: "off", 1: "on", 2: "verbose"}[_MODE],
        "collectives": _render_collectives(st),
        "collective_counts": {op: rec["count"] for op, rec in st.collectives.items()},
        "fused_collectives": dict(st.fused_collectives),
        "async_forcing": _render_async(st),
        "forcing_points": _render_forces(st),
        "dispatches": {k: dict(v) for k, v in st.dispatches.items()},
        "unfused_reasons": {k: dict(v) for k, v in st.unfused.items()},
        "retraces": _render_retraces(st),
        "degraded": _render_degraded(st),
        "nonfinite": dict(st.nonfinite),
        "io_retries": dict(st.io_retries),
        "checkpoint": dict(st.checkpoint),
        "faults": dict(st.faults),
        "jit_compiles": dict(st.compiles),
        "spans": _render_spans(st),
        "timeline": {
            "events": len(st.events),
            "events_dropped": st.events_dropped,
            "cap": _EVENT_CAP,
        },
        "scopes": scope_reports(),
        "memory": _memory_block(),
        "health": _health_block(global_view=_state is not None),
        "numerics": _numerics_block(),
    }
    try:
        from . import fusion

        doc["fusion_cache"] = fusion.cache_stats()
        doc["programs"] = _programs_block()
    except Exception:  # pragma: no cover
        pass
    try:
        from ..utils import profiling

        doc["timers"] = profiling.report()
    except Exception:  # pragma: no cover
        pass
    try:
        from . import serving

        if serving._SESSIONS:  # only when the serving layer has sessions
            doc["serving"] = serving.sessions_block()
    except Exception:  # pragma: no cover - the report never fails
        pass
    if _ELASTIC_HOOK is not None:
        try:
            doc["elastic"] = _ELASTIC_HOOK()
        except Exception:  # pragma: no cover - the report never fails
            pass
    if _AUTOSCALE_HOOK is not None:
        try:
            doc["autoscale"] = _AUTOSCALE_HOOK()
        except Exception:  # pragma: no cover - the report never fails
            pass
    if _MULTIHOST_HOOK is not None:
        try:
            doc["multihost"] = _MULTIHOST_HOOK()
        except Exception:  # pragma: no cover - the report never fails
            pass
    if _MODE >= 2:
        doc["events"] = list(st.events)
    return doc


def _jsonable(obj):
    """Deterministic JSON projection: tuple keys join with "/", sets sort,
    tuples become lists, numpy scalars unbox — the schema-stability contract
    (no ``default=str`` drift for structured content)."""
    if isinstance(obj, dict):
        out = {}
        for k, v in obj.items():
            if isinstance(k, tuple):
                k = "/".join(str(p) for p in k)
            elif not isinstance(k, str):
                k = str(k)
            out[k] = _jsonable(v)
        return out
    if isinstance(obj, (list, tuple, deque)):
        return [_jsonable(v) for v in obj]
    if isinstance(obj, (set, frozenset)):
        return sorted(str(v) for v in obj)
    if isinstance(obj, (str, int, float, bool)) or obj is None:
        return obj
    item = getattr(obj, "item", None)  # numpy scalars
    if callable(item):
        try:
            return item()
        except Exception:  # pragma: no cover
            pass
    return str(obj)


def report_json(path: Optional[str] = None, indent: int = 2) -> str:
    """:func:`report` serialized to JSON; written to ``path`` when given.
    Serialization is deterministic (:func:`_jsonable`): every key is a
    string, tuples/sets have a pinned projection, and ``default=str`` is
    only a last-resort safety net — round-tripping through ``json.loads``
    is schema-stable across calls."""
    text = json.dumps(_jsonable(report()), indent=indent, default=str)
    if path is not None:
        with open(path, "w") as fh:
            fh.write(text)
            fh.write("\n")
    return text


# ----------------------------------------------------------------------
# Chrome/Perfetto trace export
# ----------------------------------------------------------------------
def _host_index() -> int:
    try:
        from . import multihost

        return int(multihost.process_index())
    except Exception:  # pragma: no cover - import-order safety
        return 0


def _us(ts: float) -> float:
    return round(ts * 1e6, 3)


#: instant-event rendering: kind -> (category, name builder)
_INSTANT_KINDS = {
    "collective": ("collective", lambda ev: ev.get("op", "collective")),
    "fused_collective": ("collective", lambda ev: "fused:" + str(ev.get("op"))),
    "record": ("record", lambda ev: "record:" + str(ev.get("op"))),
    "compile": ("compile", lambda ev: "compile:" + str(ev.get("label") or ev.get("family") or ev.get("program"))),
    "force": ("force", lambda ev: "force:" + str(ev.get("trigger"))),
    "degraded": ("degrade", lambda ev: "degraded:" + str(ev.get("family"))),
    "fault": ("fault", lambda ev: "fault:" + str(ev.get("site"))),
    "io_retry": ("io", lambda ev: "io_retry:" + str(ev.get("site"))),
    "io": ("io", lambda ev: "io:" + str(ev.get("op", "op"))),
    "checkpoint": ("checkpoint", lambda ev: "checkpoint:" + str(ev.get("event"))),
    "checkpoint_phase": ("checkpoint", lambda ev: "ckpt:" + str(ev.get("phase"))),
    "nonfinite": ("errstate", lambda ev: "nonfinite:" + str(ev.get("where"))),
    "memory_gate": ("memory", lambda ev: "gate:" + str(ev.get("policy"))),
    "memory_oom": ("memory", lambda ev: "oom:" + str(ev.get("program"))),
    "stall": ("health", lambda ev: "stall:" + str(ev.get("site"))),
    "slo_breach": ("health", lambda ev: "slo:" + str(ev.get("metric"))),
    "flight_dump": ("health", lambda ev: "flight_dump:" + str(ev.get("reason"))),
    # numeric stats events additionally render as counter tracks (see
    # trace_events) — this entry covers the drift/sdc/train instants
    "numeric": ("numeric", lambda ev: "numeric:" + str(ev.get("event"))),
}


def async_pairs(evs: Optional[List[dict]] = None) -> List[tuple]:
    """Match the timeline's ``dispatch`` events to the ``blocking_sync``
    events that waited on them via correlation id: a sync waits on the
    dispatch whose root set (``cids``) contains its chain's ``cid``.
    Returns ``[(dispatch_event, sync_event), ...]`` — the exporter's async
    pair source and the assertable surface for "this sync waited on that
    program"."""
    if evs is None:
        evs = list(_cur().events)
    by_cid: Dict[int, dict] = {}
    for ev in evs:
        if ev.get("kind") != "dispatch":
            continue
        for cid in ev.get("cids") or ([ev["cid"]] if ev.get("cid") is not None else []):
            by_cid[cid] = ev
    pairs = []
    for ev in evs:
        if ev.get("kind") != "blocking_sync" or ev.get("cid") is None:
            continue
        disp = by_cid.get(ev["cid"])
        if disp is not None:
            pairs.append((disp, ev))
    return pairs


def trace_events(evs: Optional[List[dict]] = None, pid: Optional[int] = None) -> List[dict]:
    """Render the timeline as a list of Chrome trace-event dicts: spans and
    timers as B/E duration pairs, dispatch→blocking-sync as async ``b``/``e``
    pairs keyed by cid, everything else as thread-scoped instants. One
    process row per host (``pid`` defaults to ``multihost.process_index()``),
    everything on tid 0."""
    if evs is None:
        evs = list(_cur().events)
    if pid is None:
        pid = _host_index()
    tid = 0
    out: List[dict] = [
        {"ph": "M", "name": "process_name", "pid": pid, "tid": tid,
         "args": {"name": f"heat_tpu host {pid}"}},
        {"ph": "M", "name": "thread_name", "pid": pid, "tid": tid,
         "args": {"name": "python"}},
    ]

    def args_of(ev, *skip):
        return {
            k: _jsonable(v)
            for k, v in ev.items()
            if k not in ("kind", "ts") and k not in skip and v is not None
        }

    for ev in sorted(evs, key=lambda e: e.get("ts", 0.0)):
        kind = ev.get("kind")
        ts = _us(ev.get("ts", 0.0))
        if kind == "span_begin":
            out.append({"ph": "B", "cat": "span", "name": ev.get("name"),
                        "pid": pid, "tid": tid, "ts": ts, "args": args_of(ev, "name")})
        elif kind == "span_end":
            out.append({"ph": "E", "cat": "span", "name": ev.get("name"),
                        "pid": pid, "tid": tid, "ts": ts})
        elif kind == "timer":
            dur = float(ev.get("dur", 0.0))
            start = _us(ev["ts"] - dur)
            name = str(ev.get("name"))
            out.append({"ph": "B", "cat": "timer", "name": name,
                        "pid": pid, "tid": tid, "ts": start})
            out.append({"ph": "E", "cat": "timer", "name": name,
                        "pid": pid, "tid": tid, "ts": ts})
        elif kind == "blocking_sync":
            name = "sync:" + str(ev.get("where"))
            if "dur" in ev:
                out.append({"ph": "X", "cat": "sync", "name": name,
                            "pid": pid, "tid": tid, "ts": ts,
                            "dur": _us(float(ev["dur"])), "args": args_of(ev, "dur")})
            else:
                out.append({"ph": "i", "s": "t", "cat": "sync", "name": name,
                            "pid": pid, "tid": tid, "ts": ts, "args": args_of(ev)})
        elif kind == "dispatch":
            out.append({"ph": "i", "s": "t", "cat": "dispatch", "name": "dispatch",
                        "pid": pid, "tid": tid, "ts": ts, "args": args_of(ev)})
        elif kind == "memory":
            # counter ("C") tracks per host: Perfetto renders each args key
            # as a stacked series — one track for the owner-attributed live
            # bytes, one for the high watermark
            series = {"total": int(ev.get("total", 0))}
            for owner, nbytes in (ev.get("by_owner") or {}).items():
                series[str(owner)] = int(nbytes)
            out.append({"ph": "C", "cat": "memory", "name": "live_bytes",
                        "pid": pid, "tid": tid, "ts": ts, "args": series})
            out.append({"ph": "C", "cat": "memory", "name": "live_bytes_watermark",
                        "pid": pid, "tid": tid, "ts": ts,
                        "args": {"watermark": int(ev.get("watermark", 0))}})
        elif kind == "numeric" and ev.get("event") == "stats":
            # numerics counter tracks alongside memledger's: one track per
            # sampled program root (rms / absmax as stacked series, plus a
            # saturation track for the nonfinite + exponent-edge counts)
            label = f"numerics:{ev.get('program')}[{ev.get('root')}]"
            out.append({"ph": "C", "cat": "numeric", "name": label,
                        "pid": pid, "tid": tid, "ts": ts,
                        "args": {"rms": float(ev.get("rms", 0.0)),
                                 "absmax": float(ev.get("absmax", 0.0))}})
            out.append({"ph": "C", "cat": "numeric", "name": label + ":saturation",
                        "pid": pid, "tid": tid, "ts": ts,
                        "args": {"nonfinite": int(ev.get("nonfinite", 0)),
                                 "edge_low": int(ev.get("edge_low", 0)),
                                 "edge_high": int(ev.get("edge_high", 0))}})
        else:
            cat, name_of = _INSTANT_KINDS.get(kind, ("event", lambda e, k=kind: str(k)))
            out.append({"ph": "i", "s": "t", "cat": cat, "name": name_of(ev),
                        "pid": pid, "tid": tid, "ts": ts, "args": args_of(ev)})

    # dispatch -> blocking-sync async pairs, keyed by correlation id. The
    # sync event is stamped when the host boundary NOTES the pending chain
    # (just before it triggers the dispatch), so the pair opens at the
    # earlier of the two stamps and closes when the host holds the value.
    for disp, sync in async_pairs(evs):
        start = min(disp["ts"], sync["ts"])
        end = max(disp["ts"], sync["ts"] + float(sync.get("dur", 0.0)))
        ident = str(sync.get("cid"))
        name = "dispatch→sync"
        common = {"cat": "async_forcing", "name": name, "id": ident, "pid": pid, "tid": tid}
        out.append(dict(common, ph="b", ts=_us(start),
                        args={"program": disp.get("program"), "roots": disp.get("roots"),
                              "where": sync.get("where"), "cid": sync.get("cid")}))
        out.append(dict(common, ph="e", ts=_us(end)))
    return out


def export_trace(path: Optional[str] = None, events: Optional[List[dict]] = None) -> Dict[str, Any]:
    """Export the trace timeline as Chrome/Perfetto trace-event JSON
    (`chrome://tracing` / ui.perfetto.dev). Inside a :func:`scope` this
    exports the scope's own timeline. Returns the trace document; written to
    ``path`` when given. Requires ``HEAT_TPU_TELEMETRY=verbose`` to have
    been active while the events of interest were recorded (the timeline is
    empty otherwise — the export itself works in any mode and never forces a
    pending chain)."""
    doc = {
        "traceEvents": trace_events(events),
        "displayTimeUnit": "ms",
        "otherData": {
            "tool": "heat_tpu.telemetry",
            "host": _host_index(),
            "mode": {0: "off", 1: "on", 2: "verbose"}[_MODE],
            "events_dropped": _cur().events_dropped,
        },
    }
    if path is not None:
        with open(path, "w") as fh:
            json.dump(doc, fh)
            fh.write("\n")
    return doc


def merge_traces(
    paths: List[str],
    path: Optional[str] = None,
    align: bool = True,
    check_parity: bool = False,
) -> Dict[str, Any]:
    """Stitch per-host trace files (one :func:`export_trace` output per
    controller) into a single multi-process trace: each input keeps its own
    process row (re-pid'd by input order on collision), and ``align=True``
    shifts every input so its earliest timestamp sits at zero — perf_counter
    epochs differ across hosts, so only relative time is meaningful.

    ``check_parity=True`` runs :func:`trace_collective_parity` over the
    merged document — per-cid collective event counts must match across
    process rows (SPMD: every host records the same collectives). Problems
    warn and land under ``otherData["collective_parity"]``; they are the
    runtime signature of an H001 deadlock hazard (one host entered a
    collective its peers never recorded)."""
    merged: List[dict] = []
    seen_pids: set = set()
    dropped_total = 0
    for i, p in enumerate(paths):
        with open(p) as fh:
            doc = json.load(fh)
        other = doc.get("otherData")
        if isinstance(other, dict):
            try:
                dropped_total += int(other.get("events_dropped") or 0)
            except (TypeError, ValueError):
                pass
        evs = doc.get("traceEvents", [])
        pids = {ev.get("pid", 0) for ev in evs}
        remap = {}
        for old in sorted(pids):
            new = old
            while new in seen_pids:
                new = max(seen_pids) + 1
            seen_pids.add(new)
            remap[old] = new
        stamps = [ev["ts"] for ev in evs if "ts" in ev]
        base = min(stamps) if (align and stamps) else 0.0
        for ev in evs:
            ev = dict(ev)
            ev["pid"] = remap.get(ev.get("pid", 0), ev.get("pid", 0))
            if "ts" in ev:
                ev["ts"] = round(ev["ts"] - base, 3)
            merged.append(ev)
    doc = {
        "traceEvents": merged,
        "displayTimeUnit": "ms",
        "otherData": {
            "tool": "heat_tpu.telemetry",
            "merged_from": len(paths),
            "events_dropped": dropped_total,
        },
    }
    if check_parity:
        problems = trace_collective_parity(doc)
        if problems:
            doc["otherData"]["collective_parity"] = problems
            warnings.warn(
                "merged trace fails cross-host collective parity "
                f"({len(problems)} problem(s), first: {problems[0]}) — the runtime "
                "signature of a collective under host-divergent control flow "
                "(heat-lint H001)",
                stacklevel=2,
            )
    if path is not None:
        with open(path, "w") as fh:
            json.dump(doc, fh)
            fh.write("\n")
    return doc


def _load_trace_doc(doc_or_path):
    if not isinstance(doc_or_path, str):
        return doc_or_path, None
    try:
        with open(doc_or_path) as fh:
            return json.load(fh), None
    except Exception as exc:  # noqa: BLE001 - the problem IS the result
        return None, f"not valid JSON: {exc!r}"


def trace_collective_parity(doc_or_path) -> List[str]:
    """Cross-host collective parity of a (merged) trace: for every process
    row, count collective-category events keyed by (name, correlation id)
    and require identical multisets across rows. Under SPMD every host runs
    the same script, so per-host cid sequences align and each host must have
    recorded exactly the same collectives — a row missing (or holding extra)
    collective events is the already-exported-trace signature of the H001
    deadlock hazard: some hosts entered a collective the others never
    reached. Returns problem strings (empty = parity holds); single-row
    traces trivially pass."""
    doc, err = _load_trace_doc(doc_or_path)
    if err is not None:
        return [err]
    if not isinstance(doc, dict) or not isinstance(doc.get("traceEvents"), list):
        return ["missing traceEvents list"]
    per_pid: Dict[Any, Dict[tuple, int]] = {}
    for ev in doc["traceEvents"]:
        if not isinstance(ev, dict):
            continue
        ph = ev.get("ph")
        if ph == "M":
            # a process row exists even if it recorded nothing — an empty
            # row must still be compared (its silence IS the finding)
            per_pid.setdefault(ev.get("pid", 0), {})
            continue
        if ev.get("cat") != "collective":
            continue
        args = ev.get("args") or {}
        key = (str(ev.get("name")), args.get("cid"))
        counts = per_pid.setdefault(ev.get("pid", 0), {})
        counts[key] = counts.get(key, 0) + 1
    if len(per_pid) < 2:
        return []
    problems: List[str] = []
    pids = sorted(per_pid, key=str)
    ref_pid, ref = pids[0], per_pid[pids[0]]
    for pid in pids[1:]:
        counts = per_pid[pid]
        for key in sorted(set(ref) | set(counts), key=str):
            a, b = ref.get(key, 0), counts.get(key, 0)
            if a != b:
                name, cid = key
                where = f"collective {name!r}" + (f" cid {cid}" if cid is not None else "")
                problems.append(
                    f"{where}: host {ref_pid} recorded {a} event(s) but host {pid} "
                    f"recorded {b} — hosts diverged around this collective"
                )
    return problems


def validate_trace(doc_or_path, cross_host: bool = False) -> List[str]:
    """Structural problems of a Chrome trace-event document (or file path):
    empty list = loads and every event carries the required keys. The CLI's
    ``validate-trace`` and the CI matrix leg assert on this.
    ``cross_host=True`` additionally runs :func:`trace_collective_parity`
    (the ``validate-trace --cross-host`` CLI flag) so an exported multi-host
    trace surfaces the runtime signature of an H001 deadlock."""
    problems: List[str] = []
    doc, err = _load_trace_doc(doc_or_path)
    if err is not None:
        return [err]
    if not isinstance(doc, dict) or not isinstance(doc.get("traceEvents"), list):
        return ["missing traceEvents list"]
    open_async: Dict[str, int] = {}
    for i, ev in enumerate(doc["traceEvents"]):
        if not isinstance(ev, dict):
            problems.append(f"event {i} is not an object")
            continue
        ph = ev.get("ph")
        if ph is None or "pid" not in ev:
            problems.append(f"event {i} missing ph/pid: {ev}")
            continue
        if ph != "M" and "ts" not in ev:
            problems.append(f"event {i} ({ph}) missing ts")
        if ph in ("b", "e") and "id" not in ev:
            problems.append(f"async event {i} missing id")
        if ph == "C":
            # counter tracks (memory live-bytes, numerics rms/saturation)
            # must carry numeric series values or Perfetto drops the track
            args = ev.get("args")
            if not isinstance(args, dict) or not args:
                problems.append(f"counter event {i} missing args series")
            elif any(not isinstance(v, (int, float)) or isinstance(v, bool)
                     for v in args.values()):
                problems.append(f"counter event {i} has non-numeric series: {args}")
        if ph == "b":
            open_async[str(ev.get("id"))] = open_async.get(str(ev.get("id")), 0) + 1
        elif ph == "e":
            key = str(ev.get("id"))
            if open_async.get(key, 0) <= 0:
                problems.append(f"async end without begin (id {key})")
            else:
                open_async[key] -= 1
    for key, n in open_async.items():
        if n:
            problems.append(f"async begin without end (id {key})")
    if cross_host:
        problems.extend(trace_collective_parity(doc))
    return problems


# ----------------------------------------------------------------------
# streaming metrics sink: HEAT_TPU_METRICS=<path>
# ----------------------------------------------------------------------
class _MetricsSink:
    """Appends ``report()`` as one JSON line per flush to a file — the
    zero-code-change observability tap for long jobs (``tail -f`` / a
    sidecar scraper). A daemon thread flushes every ``interval`` seconds
    (0 = at-exit only); the atexit hook writes the final line. Flushes never
    raise and never force a pending chain."""

    def __init__(self, path: str, interval: float):
        self.path = path
        self.interval = float(interval)
        self.lines = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        if self.interval > 0 and self._thread is None:
            self._thread = threading.Thread(
                target=self._loop, name="heat-tpu-metrics", daemon=True
            )
            self._thread.start()

    def _loop(self) -> None:
        while not self._stop.wait(self.interval):
            self.flush("periodic")

    def flush(self, event: str = "flush") -> bool:
        try:
            # always the GLOBAL state: the daemon thread's flush must not
            # snapshot whatever request scope the main thread is inside
            doc = report(_state=_GLOBAL)
            doc.pop("events", None)  # the timeline has its own exporter
            # stable line schema: report() only joins the serving block
            # when sessions exist and the elastic block when the hook is
            # installed, but a streaming consumer needs every line to
            # carry the same keys — fill the conditional blocks in
            if "serving" not in doc:
                try:
                    from . import serving

                    doc["serving"] = serving.sessions_block()
                except Exception:  # noqa: BLE001 - sink lines never fail
                    doc["serving"] = {}
            if "elastic" not in doc:
                try:
                    doc["elastic"] = {} if _ELASTIC_HOOK is None else _ELASTIC_HOOK()
                except Exception:  # noqa: BLE001 - sink lines never fail
                    doc["elastic"] = {}
            if "autoscale" not in doc:
                try:
                    doc["autoscale"] = (
                        {} if _AUTOSCALE_HOOK is None else _AUTOSCALE_HOOK()
                    )
                except Exception:  # noqa: BLE001 - sink lines never fail
                    doc["autoscale"] = {}
            line = json.dumps(
                _jsonable({"ts": time.time(), "event": event, "report": doc}),
                default=str,
            )
            with open(self.path, "a") as fh:
                fh.write(line + "\n")
            self.lines += 1
            return True
        # swallowing is this sink's contract: the flush runs on a daemon
        # thread and at exit; ANY failure (full disk, revoked mount,
        # interpreter teardown) must drop the metrics line, never the job
        # heat-lint: disable=H003 — observability must never take the job down
        except Exception:  # noqa: BLE001
            return False

    def stop(self, final: bool = True) -> None:
        self._stop.set()
        if final:
            self.flush("exit")


_SINK: Optional[_MetricsSink] = None


def set_metrics_sink(path: Optional[str], interval: Optional[float] = None) -> Optional[_MetricsSink]:
    """(Re)configure the JSON-lines metrics sink in-process: ``path=None``
    stops it (no final line), otherwise every ``interval`` seconds (default
    ``HEAT_TPU_METRICS_INTERVAL``, 30s; 0 = at-exit only) and at interpreter
    exit one ``report()`` line is appended to ``path``. Returns the sink."""
    global _SINK
    if _SINK is not None:
        _SINK.stop(final=False)
        _SINK = None
    if path:
        if interval is None:
            interval = float(os.environ.get("HEAT_TPU_METRICS_INTERVAL", "30"))
        _SINK = _MetricsSink(path, interval)
        _SINK.start()
    return _SINK


def _sink_atexit() -> None:
    if _SINK is not None:
        _SINK.stop(final=True)


atexit.register(_sink_atexit)
if os.environ.get("HEAT_TPU_METRICS"):
    set_metrics_sink(os.environ["HEAT_TPU_METRICS"])


# ----------------------------------------------------------------------
# compiled-program (HLO) collective accounting
# ----------------------------------------------------------------------
#: collective opcodes as they appear in HLO text, in call position
#: (``all-reduce(...)`` / async ``all-reduce-start(...)``). Order matters:
#: longest-prefix alternatives first so ``all-to-all`` never half-matches.
_HLO_COLLECTIVE_RE = re.compile(
    r"\b(all-reduce-scatter|reduce-scatter|all-gather|all-reduce|all-to-all|"
    r"collective-permute|collective-broadcast)(?:-start)?\("
)


def hlo_collectives(hlo_text: str) -> List[Dict[str, str]]:
    """Collective *instructions* in an HLO dump: one entry per collective op
    in call position (async ``-start``/``-done`` pairs count once, via the
    start; instruction names and operand references never match). Each entry
    carries the op type and its source line for byte-budget checks."""
    out = []
    for line in hlo_text.splitlines():
        if "(" not in line or "=" not in line:
            continue
        # the regex requires "(" (or "-start(") right after the opcode, so
        # async "-done(" companions and name/operand references never match
        m = _HLO_COLLECTIVE_RE.search(line)
        if m:
            out.append({"op": m.group(1), "line": line.strip()})
    return out


def hlo_collective_counts(hlo_text: str) -> Dict[str, int]:
    """Per-type collective instruction counts of a compiled HLO dump —
    ``{"all-reduce": 3, "all-gather": 1}``. The readable replacement for
    counting regex hits against a single magic number."""
    counts: Dict[str, int] = {}
    for entry in hlo_collectives(hlo_text):
        counts[entry["op"]] = counts.get(entry["op"], 0) + 1
    return counts


def collective_budget_excess(
    counts: Dict[str, int], budget: Dict[str, int]
) -> Dict[str, str]:
    """Violations of a named per-type collective budget: any type over its
    allowance, or present but absent from the budget. Empty dict = within
    budget. Asserting ``collective_budget_excess(...) == {}`` fails with a
    diff that names the collective type instead of a magic total."""
    excess = {}
    for op, count in counts.items():
        allowed = budget.get(op)
        if allowed is None:
            excess[op] = f"{count} present but not budgeted"
        elif count > allowed:
            excess[op] = f"{count} > budget {allowed}"
    return excess
