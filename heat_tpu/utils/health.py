"""Mesh health / failure detection utilities.

The reference's failure story is MPI's: a dead rank aborts the job and
SLURM restarts it (SURVEY.md §5 — no in-framework detection). On TPU the
failure modes are different — a backend can hang rather than die —
so this module gives the runtime an explicit health surface:

* :func:`ping_mesh` — one tiny psum over every mesh device with a wall-clock
  budget, returning status + latency (run in a worker thread so a hung
  backend cannot hang the caller).
* :func:`assert_mesh_healthy` — raise if the mesh does not answer in time.
* :func:`memory_report` — live device-buffer bytes per device (leak triage).
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Optional

import jax
import jax.numpy as jnp

from ..core.communication import MeshCommunication, sanitize_comm

__all__ = ["ping_mesh", "assert_mesh_healthy", "memory_report"]


class MeshUnhealthyError(RuntimeError):
    """The device mesh failed to answer a collective within the budget."""


def _ping(comm: MeshCommunication) -> float:
    """One tiny all-device psum; returns the observed wall latency."""
    from jax.sharding import PartitionSpec as P

    start = time.perf_counter()
    x = jax.device_put(
        jnp.arange(comm.size, dtype=jnp.float32), comm.sharding(1, 0)
    )
    fn = jax.jit(
        jax.shard_map(
            lambda s: jax.lax.psum(s, comm.axis_name),
            mesh=comm.mesh,
            in_specs=P(comm.axis_name),
            out_specs=P(comm.axis_name),
            check_vma=False,
        )
    )
    out = fn(x)
    total = float(jnp.sum(out))  # host sync
    expect = float(comm.size) * sum(range(comm.size))
    if total != expect:
        raise MeshUnhealthyError(
            f"collective returned {total}, expected {expect} — mesh state corrupt"
        )
    return time.perf_counter() - start


def ping_mesh(comm: Optional[MeshCommunication] = None, timeout: float = 60.0) -> dict:
    """Probe the mesh with one collective under a wall-clock budget.

    Returns ``{"ok", "latency_s", "devices", "platform", "error"}``. A hung
    backend yields ``ok=False``
    with ``error="timeout"`` instead of hanging the caller — the probe runs
    in a worker thread.
    """
    comm = sanitize_comm(comm)
    info = {
        "ok": False,
        "latency_s": None,
        "devices": comm.size,
        "platform": comm.devices[0].platform if comm.devices else "?",
        "error": None,
    }
    # a DAEMON thread, not an executor: ThreadPoolExecutor.shutdown (and the
    # interpreter's atexit join of its non-daemon workers) would block on a
    # hung backend — the exact failure this probe exists to bound
    result: "queue.Queue" = queue.Queue(maxsize=1)

    def run():
        try:
            result.put(("ok", _ping(comm)))
        except Exception as exc:  # noqa: BLE001
            result.put(("err", f"{type(exc).__name__}: {exc}"))

    t = threading.Thread(target=run, daemon=True)
    t.start()
    try:
        kind, val = result.get(timeout=timeout)
    except queue.Empty:
        info["error"] = "timeout"
        return info
    if kind == "ok":
        info["latency_s"] = round(val, 6)
        info["ok"] = True
    else:
        info["error"] = val
    return info


def assert_mesh_healthy(comm: Optional[MeshCommunication] = None, timeout: float = 60.0) -> dict:
    """Raise :class:`MeshUnhealthyError` unless :func:`ping_mesh` succeeds."""
    info = ping_mesh(comm, timeout=timeout)
    if not info["ok"]:
        raise MeshUnhealthyError(f"mesh health probe failed: {info}")
    return info


def memory_report(comm: Optional[MeshCommunication] = None, top: int = 5) -> dict:
    """Live device-buffer bytes per device of ``comm``'s mesh, from
    ``jax.live_arrays()`` — the leak-triage companion of the reference's
    (non-existent) memory tooling; exceeds reference scope like
    utils/profiling does.

    Buffers are deduped with the ledger's own key (``memledger._buffer_key``
    — (device, buffer pointer), so the two surfaces can never disagree on
    what "one buffer" is), meaning a buffer addressable from multiple
    shards is never double-counted; deleted/donated arrays are
    skipped via ``is_deleted()`` plus the narrow ``RuntimeError`` the racing
    shards read raises — no blanket except. Returns ``total_bytes``,
    ``per_device_bytes``, the deduped ``buffer_count`` and the ``top``-K
    largest buffers (shape/dtype/bytes, owner-attributed via the
    ``core/memledger`` registry)."""
    from ..core import memledger

    comm = sanitize_comm(comm)
    mesh_devices = {str(d) for d in comm.devices}
    per_device: dict = {}
    total = 0
    buffer_count = 0
    seen: set = set()
    largest: list = []
    # attributed arrays claim their buffers first (same ordering rule as
    # memledger._scan): a global sharded array and its per-shard children
    # are BOTH live arrays over the same device buffers, and the dedupe
    # must not let enumeration order hand the bytes to an untagged child
    ranked = sorted(
        jax.live_arrays(),
        key=lambda arr: memledger._owner_of(arr) == memledger.UNATTRIBUTED,
    )
    for arr in ranked:
        try:
            if arr.is_deleted():
                continue
            shards = arr.addressable_shards
        except RuntimeError:  # deleted/donated between the check and the read
            continue
        arr_bytes = 0
        for i, s in enumerate(shards):
            key = str(s.device)
            if key not in mesh_devices:
                continue
            ident = memledger._buffer_key(s, arr, i)
            if ident in seen:
                continue
            seen.add(ident)
            try:
                nbytes = int(s.data.nbytes)
            except RuntimeError:  # deleted mid-walk
                continue
            per_device[key] = per_device.get(key, 0) + nbytes
            total += nbytes
            arr_bytes += nbytes
            buffer_count += 1
        if arr_bytes:
            largest.append(
                (
                    arr_bytes,
                    {
                        "nbytes": arr_bytes,
                        "shape": [int(d) for d in arr.shape],
                        "dtype": str(arr.dtype),
                        "owner": memledger._owner_of(arr),
                    },
                )
            )
    largest.sort(key=lambda t: -t[0])
    return {
        "total_bytes": total,
        "per_device_bytes": per_device,
        "buffer_count": buffer_count,
        "top_buffers": [rec for _, rec in largest[: max(0, int(top))]],
    }
