"""Tracing / profiling subsystem.

The reference has no built-in profiling — its benchmarks hand-time with
``time.perf_counter`` (reference benchmarks/kmeans/heat-cpu.py:22-26) and
SURVEY.md §5 calls for ``jax.profiler`` traces as the first-class TPU
replacement. This module provides:

* :func:`trace` — context manager writing an XLA/TensorBoard trace directory
  (open with ``tensorboard --logdir`` or xprof) covering everything the
  enclosed code dispatches, including pallas kernels and ICI collectives.
* :func:`annotate` — named region that shows up inside device traces
  (``jax.profiler.TraceAnnotation``); usable as decorator or context manager.
* :class:`Timer` / :func:`timed` — a process-local registry of wall-clock
  timers that synchronize on device results (``block_until_ready``), so a
  timed region measures compute, not dispatch.
* :func:`report` — aggregate {name: {calls, total_s, mean_s, best_s}}.
* :func:`device_memory_stats` — per-device live-bytes snapshot where the
  backend exposes it (TPU does; forced-host CPU returns {}).
* :func:`host_memory_stats` — current/peak RSS + physical total of THIS
  process's host, the fallback memory surface on CPU meshes (and the
  denominator for fractional ``HEAT_TPU_MEMORY_BUDGET`` specs there).
"""

from __future__ import annotations

import contextlib
import functools
import os
import time
from typing import Any, Callable, Dict, Optional

import jax

# module-level, not per-call: record_timing sits on the Timer hot path and
# core.telemetry has no module-level dependency back on utils (no cycle)
from heat_tpu.core import telemetry as _telemetry

__all__ = [
    "Timer",
    "annotate",
    "device_memory_stats",
    "host_memory_stats",
    "record_timing",
    "report",
    "reset",
    "timed",
    "trace",
]


@contextlib.contextmanager
def trace(log_dir: str, create_perfetto_link: bool = False):
    """Write a device+host profiler trace of the enclosed block to ``log_dir``."""
    jax.profiler.start_trace(log_dir, create_perfetto_link=create_perfetto_link)
    try:
        yield log_dir
    finally:
        jax.profiler.stop_trace()


def annotate(name: str):
    """Named trace region: ``with annotate("lloyd_step"): ...`` or as a
    decorator. Regions nest and appear on the device timeline. A bare alias
    of ``jax.profiler.TraceAnnotation``, which counts nothing: the region
    that is also counted and timed is ``heat_tpu.telemetry.span``, itself an
    annotation of its path while a profiler session records."""
    return jax.profiler.TraceAnnotation(name)


class Timer:
    """Wall-clock timer that blocks on device work before stopping.

    >>> with Timer("assign"):           # records into the global registry
    ...     out = step(x)               # result synced automatically if returned
    """

    _registry: Dict[str, Dict[str, Any]] = {}

    def __init__(self, name: str, sync: bool = True):
        self.name = name
        self.sync = sync
        self._start = None
        self.elapsed = 0.0

    def __enter__(self) -> "Timer":
        self._start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        if self.sync and exc == (None, None, None):
            _sync_all_devices()
        self.elapsed = time.perf_counter() - self._start
        record_timing(self.name, self.elapsed)


def record_timing(name: str, elapsed: float) -> None:
    """Record one completed timing into the registry (the shared path for
    ``Timer`` and ``heat_tpu.telemetry.span``). Active telemetry spans absorb
    timers closing inside them (``ht.telemetry.span`` nesting contract), and
    in verbose mode every close lands on the trace timeline as a ``timer``
    event the exporter renders as a B/E duration pair."""
    rec = Timer._registry.setdefault(
        name, {"calls": 0, "total_s": 0.0, "best_s": float("inf")}
    )
    rec["calls"] += 1
    rec["total_s"] += elapsed
    rec["best_s"] = min(rec["best_s"], elapsed)
    if _telemetry._MODE:
        _telemetry.on_timer(name, elapsed)


@functools.lru_cache(maxsize=None)
def _sync_probe(device):
    # A compiled no-op pinned to one device. Executable launches are ordered
    # per device, so blocking on its output waits for all previously enqueued
    # COMPUTE on that device — a device_put would ride the transfer stream and
    # can complete while compute is still running. (jax.effects_barrier is NOT
    # a substitute either: it waits on effect tokens, not async dispatch.)
    return jax.jit(lambda: jax.numpy.zeros(()), device=device)


def _sync_all_devices() -> None:
    try:
        for d in jax.local_devices():
            _sync_probe(d)().block_until_ready()
    except Exception:  # pragma: no cover - backend-dependent
        pass


def timed(fn: Optional[Callable] = None, *, name: Optional[str] = None, sync: bool = True):
    """Decorator recording each call of ``fn`` under ``name`` (default: its
    qualname) and blocking on any returned jax arrays so device time counts."""

    def wrap(f):
        label = name or f.__qualname__

        @functools.wraps(f)
        def inner(*args, **kwargs):
            with annotate(label), Timer(label, sync=False) as t:
                out = f(*args, **kwargs)
                if sync:
                    jax.block_until_ready(out)
            return out

        return inner

    return wrap(fn) if fn is not None else wrap


def report() -> Dict[str, Dict[str, float]]:
    """Aggregated timings: {name: {calls, total_s, mean_s, best_s}}."""
    out = {}
    for name, rec in Timer._registry.items():
        out[name] = {
            "calls": rec["calls"],
            "total_s": rec["total_s"],
            "mean_s": rec["total_s"] / rec["calls"],
            "best_s": rec["best_s"],
        }
    return out


def reset() -> None:
    """Clear the timer registry."""
    Timer._registry.clear()


# class-level aliases so `Timer.report()` / `Timer.reset()` read naturally
Timer.report = staticmethod(report)
Timer.reset = staticmethod(reset)


def device_memory_stats() -> Dict[str, Dict[str, int]]:
    """Live/peak bytes per device, where the backend exposes memory_stats()."""
    out: Dict[str, Dict[str, int]] = {}
    for d in jax.devices():
        try:
            stats = d.memory_stats()
        except Exception:  # pragma: no cover - backend-dependent
            stats = None
        if stats:
            out[str(d)] = {
                k: int(v)
                for k, v in stats.items()
                if isinstance(v, (int, float)) and "bytes" in k
            }
    return out


def host_memory_stats() -> Dict[str, int]:
    """This process's host memory picture: current/peak RSS and the
    machine's physical total — the memory surface that matters on forced-
    host CPU meshes where ``device_memory_stats`` is empty (the XLA CPU
    backend reports no memory_stats), and the denominator a fractional
    ``HEAT_TPU_MEMORY_BUDGET`` resolves against there. Best-effort: keys
    are present only where the platform exposes them."""
    out: Dict[str, int] = {}
    try:
        page = int(os.sysconf("SC_PAGE_SIZE"))
        with open("/proc/self/statm") as fh:
            rss_pages = int(fh.read().split()[1])
        out["rss_bytes"] = rss_pages * page
    except (OSError, ValueError, IndexError):  # pragma: no cover - non-Linux
        pass
    try:
        import resource

        # ru_maxrss is KiB on Linux
        out["peak_rss_bytes"] = int(
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
        )
    except (ImportError, ValueError, OSError):  # pragma: no cover - non-POSIX
        pass
    try:
        out["total_bytes"] = int(os.sysconf("SC_PAGE_SIZE")) * int(
            os.sysconf("SC_PHYS_PAGES")
        )
    except (OSError, ValueError, AttributeError):  # pragma: no cover - non-POSIX
        pass
    return out
