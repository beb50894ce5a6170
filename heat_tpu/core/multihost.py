"""The multi-host seam: every per-process decision in one place.

A 64-chip slice is multi-host: each controller process addresses only its
own host's devices, and the single-controller idioms ("rank 0's chunk",
"read every block") become per-host filters. The reference framework gets
this from MPI ranks (reference communication.py:1886-1891); here the facts
come from ``jax.process_index``/``device.process_index``, and the handful
of call sites that must care (sharded ingest, ``lshape``, per-shard saves)
route through these helpers so the contract is testable against a mocked
process topology (tests/test_multihost_seam.py) without owning two hosts.

Contract (documented in doc/internals_distribution.md):

* ``process_index()`` — this controller's process id (0 on a single host).
* ``is_addressable(device)`` — whether this process may transfer to/from
  the device. Ingest loops skip non-addressable ranks; the global array is
  assembled with ``make_array_from_single_device_arrays``, which accepts
  per-host partial shard lists.
* ``ranks_to_read(devices)`` — the (rank, device) pairs THIS process must
  populate when ingesting a split array, in rank order.
* ``representative_rank(devices)`` — the mesh rank whose chunk stands in
  for "the local shard" in single-array views (``lshape``): the first
  rank addressable by this process, so every host reports a shard it
  actually holds.
* ``io_owner()`` — whether this process performs the temp→target rename
  publishing an atomic file write (``resilience.atomic_write``): exactly
  one process may win the rename when every controller runs the same
  ``save_*`` call.
* ``process_count()`` / ``sync_processes(tag)`` — how many controllers the
  runtime has, and a named barrier across them. The checkpoint subsystem
  (``utils/checkpoint.py``) syncs after every host has published its shard
  files and before the owner hashes them into the manifest, so the commit
  point never references files still in flight. ``HEAT_TPU_BARRIER_TIMEOUT_MS``
  (default off; launcher-managed runs turn it on) bounds the wait: a peer
  dead mid-barrier surfaces as a ``resilience.StallError`` naming the tag
  instead of deadlocking.

Multi-process runtime (ROADMAP item 4)
--------------------------------------
Beyond the read-side seam, this module owns the process-world lifecycle:

* :func:`initialize_distributed` — the guarded ``jax.distributed`` bring-up:
  coordinator address / process id / world size from arguments or the
  ``HEAT_TPU_COORDINATOR`` / ``HEAT_TPU_NUM_PROCESSES`` /
  ``HEAT_TPU_PROCESS_ID`` environment (what :func:`spawn_local` exports),
  retry-with-backoff on *transient* coordinator connect faults
  (``resilience.retry_policy`` shapes the backoff), gloo CPU collectives so
  a CPU dev mesh runs real cross-process collectives, and the
  ``multihost.init`` fault site checked before every connect attempt.
* the **lease heartbeat daemon** (:func:`start_heartbeat`) — each process
  beats a lease file under ``$HEAT_TPU_MESH_DIR/lease/`` every
  ``HEAT_TPU_HEARTBEAT_MS``; a peer whose lease goes stale past
  ``HEAT_TPU_PEER_LOST_MS`` becomes a named :class:`PeerLostError` /
  ``peer_lost`` telemetry event instead of a hang. Detection must race
  ahead of XLA's coordination service, which hard-kills the *survivors* of
  a dead peer (``LOG(FATAL)`` in the client) — by the time XLA notices, a
  leased process has already drained and exited for reform.
* :func:`spawn_local` — the local launcher: N coordinated worker processes
  on one machine (CI's stand-in for N hosts), supervised across
  **generations**: when a worker dies, survivors detect the loss, drain,
  and exit with :data:`REFORM_EXIT`; the launcher re-ranks the survivors
  contiguously, bumps the mesh epoch, picks a fresh coordinator port and
  respawns them into a smaller world that restores from the newest
  verifying checkpoint. In-process reform across processes is impossible
  on this jax/jaxlib (the coordination service kills survivors), so the
  reform *ritual* (drain → checkpoint → re-init → restore) spans a process
  generation instead of a function call.

Observability: ``telemetry.report()["multihost"]`` (the set-attribute hook
pattern) carries heartbeat/barrier/abandoned-thread counters, and the ops
plane exports them as ``heat_tpu_peers_*`` / ``heat_tpu_barrier_*`` gauges
with a ``/readyz`` check that flips unready while a peer is lost.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import threading
import time
import warnings
from typing import Any, Callable, Dict, FrozenSet, List, Optional, Sequence, Tuple

import jax

__all__ = [
    "PeerLostError",
    "REFORM_EXIT",
    "check_peers",
    "heartbeat_stats",
    "initialize_distributed",
    "io_owner",
    "is_addressable",
    "lost_peers",
    "reform_exit",
    "mesh_dir",
    "mesh_epoch",
    "note_progress",
    "process_count",
    "process_index",
    "ranks_to_read",
    "report_stats",
    "representative_rank",
    "reset_peers",
    "spawn_local",
    "start_heartbeat",
    "stop_heartbeat",
    "sync_processes",
]

#: values that read as "knob off" (the shared env-knob convention)
_OFF_VALUES = ("", "0", "false", "off", "no")

#: worker exit code meaning "peer lost: I drained; respawn me into a smaller
#: world" — the launcher's reform signal (sysexits leaves 64-78 user-defined;
#: 77 avoids every shell/signal convention)
REFORM_EXIT = 77


class PeerLostError(RuntimeError):
    """A peer controller process stopped beating its lease: the process
    world is degraded and every cross-process interaction (barriers,
    collectives, cooperative checkpoints) would hang or die. ``peers`` names
    the lost process ids. Supervisor-managed workers drain and exit with
    :data:`REFORM_EXIT` on this; the launcher respawns the survivors into a
    smaller world."""

    def __init__(self, message: str, peers: Sequence[int] = ()):
        super().__init__(message)
        self.peers = tuple(int(p) for p in peers)


# ----------------------------------------------------------------------
# observability: report()["multihost"] (set-attribute hook at module bottom)
# ----------------------------------------------------------------------
_LOCK = threading.Lock()
_STATS: Dict[str, Any] = {
    "world": 1,              # processes in the current world (init-time fact)
    "epoch": 0,              # mesh generation (the launcher bumps per reform)
    "barriers": 0,           # sync_processes waits entered (multi-process)
    "barrier_timeouts": 0,   # barriers abandoned on StallError
    "abandoned_threads": 0,  # cumulative daemon barrier threads abandoned
    "heartbeats": 0,         # lease beats written
    "heartbeat_errors": 0,   # beats that failed to write (missed beats)
    "init_retries": 0,       # transient coordinator connect faults retried
}
#: peers declared lost by the lease daemon (process ids); module-level so
#: ``lost_peers()`` stays a lock-and-read even after the daemon stops
_LOST: set = set()
#: abandoned barrier daemon threads, pruned of finished ones on read — the
#: "still alive" gauge a flapping peer would otherwise grow without bound
_ABANDONED: List[threading.Thread] = []


def _abandoned_alive() -> int:
    with _LOCK:
        _ABANDONED[:] = [t for t in _ABANDONED if t.is_alive()]
        return len(_ABANDONED)


def _abandoned_cap() -> int:
    """``HEAT_TPU_ABANDONED_BARRIER_CAP``: warn once the number of abandoned
    barrier threads crosses this (default 8) — a flapping peer turning every
    barrier into a leaked thread deserves a loud signal before it turns into
    thread exhaustion."""
    raw = os.environ.get("HEAT_TPU_ABANDONED_BARRIER_CAP", "").strip()
    try:
        return max(1, int(raw)) if raw else 8
    except ValueError:
        return 8


def report_stats() -> Dict[str, Any]:
    """Snapshot of the multi-process runtime counters (joined into
    ``telemetry.report()`` as the ``multihost`` block; the ops plane exports
    the same numbers as ``heat_tpu_peers_*`` / ``heat_tpu_barrier_*``)."""
    with _LOCK:
        doc = dict(_STATS)
        doc["peers_lost"] = sorted(_LOST)
    hb = _HEARTBEAT
    doc["heartbeat_running"] = hb is not None and hb.is_alive()
    if hb is not None:
        doc["world"] = hb.world
        doc["epoch"] = hb.epoch
    doc["abandoned_alive"] = _abandoned_alive()
    return doc


def reset_peers() -> None:
    """Forget every lost-peer declaration (test isolation, and the first act
    of a fresh epoch: a respawned generation starts with a clean world)."""
    with _LOCK:
        _LOST.clear()


# ----------------------------------------------------------------------
# env knobs
# ----------------------------------------------------------------------
def _barrier_timeout_ms() -> Optional[float]:
    """The ``HEAT_TPU_BARRIER_TIMEOUT_MS`` knob: off by default (an infinite
    barrier is the correct production default — a slow peer is not a dead
    peer), a positive millisecond bound otherwise. Supervisor-managed runs
    (:func:`spawn_local`) export it, so barrier timeouts default ON there.
    Malformed values warn and read as off, never take the process down."""
    raw = os.environ.get("HEAT_TPU_BARRIER_TIMEOUT_MS", "").strip().lower()
    if raw in _OFF_VALUES:
        return None
    try:
        value = float(raw)
    except ValueError:
        warnings.warn(
            f"HEAT_TPU_BARRIER_TIMEOUT_MS={raw!r} is not a number; barrier "
            "timeout stays off",
            stacklevel=2,
        )
        return None
    return value if value > 0 else None


def _env_ms(name: str, default: float) -> float:
    raw = os.environ.get(name, "").strip()
    if not raw:
        return default
    try:
        value = float(raw)
    except ValueError:
        warnings.warn(f"{name}={raw!r} is not a number; using {default:g}", stacklevel=2)
        return default
    return value if value > 0 else default


def mesh_dir() -> Optional[str]:
    """``HEAT_TPU_MESH_DIR``: the shared directory the process world
    coordinates through (lease files, lost-peer markers, progress beacons).
    None when unset — single-process, or a deployment without shared
    storage, in which case the lease daemon simply never starts."""
    raw = os.environ.get("HEAT_TPU_MESH_DIR", "").strip()
    return raw or None


def mesh_epoch() -> int:
    """``HEAT_TPU_MESH_EPOCH``: which generation of the process world this
    is (0 for a first launch; the launcher bumps it on every reform so a
    stale lease from a previous generation can never read as a live peer)."""
    raw = os.environ.get("HEAT_TPU_MESH_EPOCH", "").strip()
    try:
        return int(raw) if raw else 0
    except ValueError:
        return 0


def _lease_path(mesh: str, epoch: int, proc: int) -> str:
    return os.path.join(mesh, "lease", f"epoch-{epoch:04d}", f"proc-{proc:05d}")


def _lost_dir(mesh: str, epoch: int) -> str:
    return os.path.join(mesh, "lost", f"epoch-{epoch:04d}")


def _progress_path(mesh: str, epoch: int, proc: int) -> str:
    return os.path.join(mesh, "progress", f"epoch-{epoch:04d}", f"proc-{proc:05d}")


def _write_atomic(path: str, payload: str) -> None:
    """Tiny single-file publication (lease beats, progress beacons): a
    reader never sees a torn write because the rename is atomic."""
    tmp = f"{path}.tmp-{os.getpid()}"
    with open(tmp, "w") as fh:
        fh.write(payload)
    os.replace(tmp, path)


# ----------------------------------------------------------------------
# the per-process facts
# ----------------------------------------------------------------------
def process_index() -> int:
    """This controller process's id; 0 when the backend has no notion of
    processes (single host, or an unstarted distributed runtime).

    Only the *backend-unavailable* error reads as "single host"
    (``RuntimeError`` from an uninitialized/unsupported backend). With a
    live distributed runtime the same error is a real fault and propagates:
    a misconfigured 8-process job silently running as 8 independent
    single-host jobs is the worst failure mode this seam can produce."""
    try:
        return int(jax.process_index())
    except RuntimeError:
        if jax.distributed.is_initialized():  # pragma: no cover - needs a live cluster
            raise
        return 0


def process_count() -> int:
    """How many controller processes the runtime has; 1 when the backend has
    no notion of processes. Error narrowing as :func:`process_index`: only a
    backend-unavailable ``RuntimeError`` with no live distributed client
    reads as a single-host world."""
    try:
        return int(jax.process_count())
    except RuntimeError:
        if jax.distributed.is_initialized():  # pragma: no cover - needs a live cluster
            raise
        return 1


def sync_processes(tag: str, timeout_ms: Optional[float] = None) -> None:
    """Named barrier across controller processes (no-op on a single host).

    Cooperative multi-file protocols (the sharded checkpoint writer) need
    one ordering guarantee the per-file atomic renames cannot give: every
    host's files are on the shared filesystem before the owner publishes the
    manifest that references them. ``tag`` names the barrier so mismatched
    call sites fail loudly instead of deadlocking silently.

    A peer that died mid-barrier would hang the survivors forever —
    ``jax``'s barrier has no timeout parameter. ``timeout_ms`` (or the
    ambient ``HEAT_TPU_BARRIER_TIMEOUT_MS`` knob; off by default, exported
    ON by :func:`spawn_local`) bounds the wait: the barrier runs on a daemon
    worker thread, and when the bound expires a ``resilience.StallError``
    naming the barrier tag surfaces at the call site instead of a deadlock.
    Abandoned barrier threads are counted (``report()["multihost"]``) and
    warn past ``HEAT_TPU_ABANDONED_BARRIER_CAP``, so a flapping peer cannot
    leak threads silently. The ``multihost.barrier`` fault site fires at
    entry, so chaos runs exercise exactly the blocked-barrier paths."""
    from . import resilience

    if resilience._ARMED:
        resilience.check("multihost.barrier")
    if process_count() <= 1:
        return
    from jax.experimental import multihost_utils

    with _LOCK:
        _STATS["barriers"] += 1
    if timeout_ms is None:
        timeout_ms = _barrier_timeout_ms()
    if timeout_ms is None:
        multihost_utils.sync_global_devices(tag)  # pragma: no cover - multi-host only
        return
    failure: List[BaseException] = []
    done = threading.Event()

    def _barrier() -> None:
        try:
            multihost_utils.sync_global_devices(tag)
        except BaseException as exc:  # noqa: BLE001 - relayed to the caller
            failure.append(exc)
        finally:
            done.set()

    worker = threading.Thread(
        target=_barrier, name=f"heat-tpu-barrier:{tag}", daemon=True
    )
    worker.start()
    if not done.wait(float(timeout_ms) / 1e3):
        with _LOCK:
            _STATS["barrier_timeouts"] += 1
            _STATS["abandoned_threads"] += 1
            abandoned_total = _STATS["abandoned_threads"]
            _ABANDONED.append(worker)
        cap = _abandoned_cap()
        if abandoned_total >= cap:
            warnings.warn(
                f"{abandoned_total} barrier daemon thread(s) abandoned on "
                f"timeout (cap {cap}, HEAT_TPU_ABANDONED_BARRIER_CAP): a "
                "flapping peer is leaking threads; reform the world or raise "
                "the barrier timeout",
                resilience.StallWarning,
                stacklevel=2,
            )
        raise resilience.StallError(
            f"barrier {tag!r} still waiting after {timeout_ms:g}ms "
            "(HEAT_TPU_BARRIER_TIMEOUT_MS): a peer process likely died "
            "mid-barrier; the hung sync is abandoned on its daemon thread"
        )
    if failure:
        raise failure[0]


def io_owner(proc: int | None = None) -> bool:
    """Whether this process owns the *publication* step of a cooperative
    file write (the temp→target rename of ``resilience.atomic_write``).

    Under multi-controller SPMD every process runs the same ``save_*`` call
    against the same target path; each writes a private temp, and exactly
    one rename may win — process 0's, the same convention as the reference's
    rank-0 responsibilities (reference io.py:198-226 token ring head). On a
    single host this is always True.

    When process 0 is the *dead* peer, no survivor owns publication in the
    degraded world — by design: cooperative saves fail fast with
    :class:`PeerLostError` there, and the launcher's re-rank makes the next
    generation's process ids contiguous again, so a process 0 (and therefore
    an owner) always exists in any world that commits."""
    return (process_index() if proc is None else proc) == 0


def is_addressable(device, proc: int | None = None) -> bool:
    """Whether ``device`` belongs to this process (may be transferred
    to/from). Devices without a ``process_index`` attribute are treated as
    addressable — the single-host CPU/TPU cases."""
    if proc is None:
        proc = process_index()
    return getattr(device, "process_index", proc) == proc


def ranks_to_read(devices: Sequence, proc: int | None = None) -> List[Tuple[int, object]]:
    """The (mesh_rank, device) pairs this process must populate when
    ingesting a split array — its addressable ranks, in rank order."""
    if proc is None:
        proc = process_index()
    return [(r, d) for r, d in enumerate(devices) if is_addressable(d, proc)]


def representative_rank(devices: Sequence, proc: int | None = None) -> int:
    """The mesh rank whose chunk this process reports as "the local shard"
    (``DNDarray.lshape``): the first addressable rank, falling back to 0
    when none is (defensive — a controller always owns at least one)."""
    if proc is None:
        proc = process_index()
    for r, d in enumerate(devices):
        if is_addressable(d, proc):
            return r
    return 0  # pragma: no cover - a controller always addresses a device


# ----------------------------------------------------------------------
# the lease heartbeat daemon: peer loss as a named event, not a hang
# ----------------------------------------------------------------------
class _HeartbeatDaemon(threading.Thread):
    """Beats this process's lease file and watches the peers'.

    One file per process per epoch under ``{mesh_dir}/lease/``; staleness is
    judged by file mtime against the watcher's clock — on one machine (the
    launcher case) that is the same clock, and on shared network storage the
    server stamps both sides. A peer is declared lost when its lease is
    older than ``lost_after_s`` (missing files get the same grace from
    daemon start, covering slow starters). Declarations are sticky until
    :func:`reset_peers`: a peer that comes *back* after being declared lost
    belongs to a previous world and must rejoin through a new epoch."""

    def __init__(
        self,
        mesh: str,
        process: int,
        world: int,
        epoch: int,
        interval_s: float,
        lost_after_s: float,
        on_peer_lost: Optional[Callable[[int], None]] = None,
    ):
        super().__init__(name="heat-tpu-heartbeat", daemon=True)
        self.mesh = mesh
        self.process = int(process)
        self.world = int(world)
        self.epoch = int(epoch)
        self.interval_s = float(interval_s)
        self.lost_after_s = float(lost_after_s)
        self.on_peer_lost = on_peer_lost
        self._watchdog_armed = False
        self._halt = threading.Event()
        self._started_at = time.time()
        self._lease = _lease_path(mesh, self.epoch, self.process)
        os.makedirs(os.path.dirname(self._lease), exist_ok=True)

    def stop(self) -> None:
        self._halt.set()

    def run(self) -> None:  # pragma: no branch - trivial loop shape
        beat = 0
        while not self._halt.is_set():
            beat += 1
            self._beat(beat)
            self._scan()
            self._halt.wait(self.interval_s)

    def _beat(self, beat: int) -> None:
        from . import resilience

        try:
            if resilience._ARMED:
                resilience.check("multihost.heartbeat")
            _write_atomic(
                self._lease,
                json.dumps(
                    {
                        "process": self.process,
                        "epoch": self.epoch,
                        "beat": beat,
                        "pid": os.getpid(),
                        "time": time.time(),
                    }
                ),
            )
            with _LOCK:
                _STATS["heartbeats"] += 1
        # a failed beat is a MISSED beat (counted, survivable), never a
        # daemon crash: the process is alive even when the mesh dir flakes
        except Exception:  # noqa: BLE001
            with _LOCK:
                _STATS["heartbeat_errors"] += 1

    def _scan(self) -> None:
        now = time.time()
        for peer in range(self.world):
            if peer == self.process:
                continue
            with _LOCK:
                if peer in _LOST:
                    continue
            try:
                age = now - os.stat(_lease_path(self.mesh, self.epoch, peer)).st_mtime
            except OSError:
                # never beaten: grace from daemon start covers slow starters
                age = now - self._started_at
            if age > self.lost_after_s:
                self._declare_lost(peer, age)

    def _declare_lost(self, peer: int, age_s: float) -> None:
        with _LOCK:
            if peer in _LOST:
                return
            _LOST.add(peer)
        # the marker is the launcher's evidence of WHO died, whatever exit
        # codes the generation ends with (collateral crashes included)
        try:
            lost_dir = _lost_dir(self.mesh, self.epoch)
            os.makedirs(lost_dir, exist_ok=True)
            _write_atomic(
                os.path.join(lost_dir, f"proc-{peer:05d}"),
                json.dumps(
                    {"peer": peer, "by": self.process, "age_s": round(age_s, 3),
                     "time": time.time()}
                ),
            )
        except OSError:  # pragma: no cover - marker is best-effort evidence
            pass
        from . import telemetry

        if telemetry._MODE:
            telemetry.record_event(
                "peer_lost", peer=peer, epoch=self.epoch,
                age_ms=round(age_s * 1e3, 1), world=self.world,
            )
        warnings.warn(
            f"peer process {peer} lost (lease silent {age_s * 1e3:.0f}ms > "
            f"{self.lost_after_s * 1e3:.0f}ms, epoch {self.epoch}): the "
            "process world is degraded",
            stacklevel=2,
        )
        if self.on_peer_lost is not None:
            try:
                self.on_peer_lost(peer)
            except Exception:  # noqa: BLE001 - callback must not kill the daemon
                pass
        self._maybe_arm_drain_watchdog()

    def _maybe_arm_drain_watchdog(self) -> None:
        """The zero-hang backstop for a peer that HANGS instead of dying.

        ``check_peers()`` only runs at step boundaries — a worker already
        blocked inside a cross-process collective when its peer went silent
        (a SIGSTOP'd or wedged process keeps its sockets open, so gloo never
        errors) would wait there forever. Once a loss is declared, this arms
        a one-shot timer: if the worker is still running after the grace, it
        is forced through :func:`reform_exit` so the launcher can reform.

        Strictly opt-in via ``HEAT_TPU_DRAIN_GRACE_MS`` — :func:`spawn_local`
        exports it for its workers; a bare process (tests driving the daemon
        in-process, notebooks) must never be ``os._exit``'d by a timer it
        did not ask for."""
        raw = os.environ.get("HEAT_TPU_DRAIN_GRACE_MS", "").strip().lower()
        if raw in _OFF_VALUES:
            return
        try:
            grace_ms = float(raw)
        except ValueError:
            return
        if grace_ms <= 0 or self._watchdog_armed:
            return
        self._watchdog_armed = True

        def _watch() -> None:  # pragma: no cover - exercised in the slow suite
            time.sleep(grace_ms / 1e3)
            with _LOCK:
                lost = sorted(_LOST)
            warnings.warn(
                f"peer(s) {lost} lost and this worker is still running after "
                f"the {grace_ms:g}ms drain grace (HEAT_TPU_DRAIN_GRACE_MS): "
                "likely blocked in a collective with a dead peer; forcing "
                "reform exit",
                stacklevel=2,
            )
            from . import telemetry

            if telemetry._MODE:
                telemetry.record_event(
                    "drain_watchdog_fired", peers=lost, epoch=self.epoch
                )
            reform_exit()

        threading.Thread(
            target=_watch, name="heat-tpu-drain-watchdog", daemon=True
        ).start()


_HEARTBEAT: Optional[_HeartbeatDaemon] = None


def start_heartbeat(
    *,
    mesh: Optional[str] = None,
    process: Optional[int] = None,
    world: Optional[int] = None,
    epoch: Optional[int] = None,
    interval_ms: Optional[float] = None,
    lost_ms: Optional[float] = None,
    on_peer_lost: Optional[Callable[[int], None]] = None,
) -> bool:
    """Start (or restart) the lease heartbeat daemon. Defaults come from the
    environment (``HEAT_TPU_MESH_DIR`` / ``HEAT_TPU_HEARTBEAT_MS`` /
    ``HEAT_TPU_PEER_LOST_MS`` / ``HEAT_TPU_MESH_EPOCH``) and the live
    backend (process index/count). Returns False — without starting — when
    no mesh dir is configured or the world is trivially single-process;
    True once the daemon is beating."""
    global _HEARTBEAT
    mesh = mesh if mesh is not None else mesh_dir()
    if not mesh:
        return False
    world = int(world) if world is not None else process_count()
    if world <= 1:
        return False
    process = int(process) if process is not None else process_index()
    epoch = int(epoch) if epoch is not None else mesh_epoch()
    interval_ms = (
        float(interval_ms) if interval_ms is not None
        else _env_ms("HEAT_TPU_HEARTBEAT_MS", 500.0)
    )
    lost_ms = (
        float(lost_ms) if lost_ms is not None
        else _env_ms("HEAT_TPU_PEER_LOST_MS", 5.0 * interval_ms)
    )
    stop_heartbeat()
    daemon = _HeartbeatDaemon(
        mesh, process, world, epoch,
        interval_s=interval_ms / 1e3, lost_after_s=lost_ms / 1e3,
        on_peer_lost=on_peer_lost,
    )
    with _LOCK:
        _STATS["world"] = world
        _STATS["epoch"] = epoch
    daemon.start()
    _HEARTBEAT = daemon
    return True


def stop_heartbeat() -> None:
    """Stop the lease daemon (idempotent). Lost-peer declarations persist —
    they describe the world, not the daemon; :func:`reset_peers` clears."""
    global _HEARTBEAT
    daemon, _HEARTBEAT = _HEARTBEAT, None
    if daemon is not None:
        daemon.stop()
        daemon.join(timeout=2.0)


def lost_peers() -> FrozenSet[int]:
    """The peer process ids currently declared lost (empty = healthy)."""
    with _LOCK:
        return frozenset(_LOST)


def check_peers() -> None:
    """Raise :class:`PeerLostError` if any peer is declared lost — the
    between-steps poll point of a supervisor-managed worker: turning the
    daemon's background declaration into control flow at a safe boundary,
    *before* the next cross-process collective can block on a dead peer."""
    with _LOCK:
        lost = sorted(_LOST)
    if lost:
        raise PeerLostError(
            f"peer process(es) {lost} lost (missed lease beats in epoch "
            f"{_STATS['epoch']}): drain and exit for reform", peers=lost,
        )


def heartbeat_stats() -> Dict[str, Any]:
    """Alias of :func:`report_stats` under the name tests/operators expect
    next to :func:`start_heartbeat`."""
    return report_stats()


def reform_exit() -> None:
    """Drain this worker with :data:`REFORM_EXIT`, bypassing atexit.

    A survivor of a lost peer must NOT run the interpreter's normal exit
    path: JAX's atexit handler calls ``jax.distributed.shutdown()``, whose
    coordination-service shutdown barrier blocks on the dead peer (~100 s
    at the default client heartbeat timeout) and then LOG(FATAL)s the
    survivor (SIGABRT) — turning a clean drain into a second casualty.
    ``os._exit`` skips all of that; callers must have flushed any results
    to disk first (the lease daemon is stopped here)."""
    stop_heartbeat()
    try:
        sys.stdout.flush()
        sys.stderr.flush()
    except Exception:  # pragma: no cover - broken pipes must not block exit
        pass
    os._exit(REFORM_EXIT)


def note_progress(step: int) -> None:
    """Publish this process's training progress (a step-number beacon under
    ``{mesh_dir}/progress/``). The launcher's chaos injector keys SIGKILLs
    off these ("kill rank 1 once it passes step 3"), and recovery timing
    reads the first beacon of a respawned generation. Best-effort: no mesh
    dir, no beacon."""
    mesh = mesh_dir()
    if not mesh:
        return
    path = _progress_path(mesh, mesh_epoch(), process_index())
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        _write_atomic(path, json.dumps({"step": int(step), "time": time.time()}))
    except OSError:  # pragma: no cover - beacon is best-effort
        pass


# ----------------------------------------------------------------------
# guarded bring-up: jax.distributed with retry, fault site, heartbeat
# ----------------------------------------------------------------------
def _transient_init_fault(exc: BaseException, policy) -> bool:
    """Whether a coordinator connect failure is worth a retry: connection
    errors and the transient-errno OSErrors always are; RuntimeErrors only
    when the message carries the coordination-service transient signatures
    (DEADLINE_EXCEEDED / UNAVAILABLE / refused / timed out)."""
    if isinstance(exc, ConnectionError):
        return True
    if isinstance(exc, OSError):
        return policy.is_transient(exc)
    if isinstance(exc, RuntimeError):
        msg = str(exc).lower()
        return any(
            key in msg
            for key in (
                "deadline", "unavailable", "timed out", "timeout",
                "connection refused", "failed to connect", "connection reset",
            )
        )
    return False


def initialize_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    *,
    heartbeat: bool = True,
    retries: Optional[int] = None,
    backoff_s: Optional[float] = None,
    **kwargs,
):
    """Bring up the multi-process runtime, guarded.

    Wraps ``communication.initialize`` (→ ``jax.distributed.initialize``)
    with the pieces a production bring-up needs:

    * **Configuration from the launcher env** — ``HEAT_TPU_COORDINATOR`` /
      ``HEAT_TPU_NUM_PROCESSES`` / ``HEAT_TPU_PROCESS_ID`` fill any argument
      left None, so a :func:`spawn_local` worker calls this with no
      arguments.
    * **CPU collectives** — a multi-process CPU world needs the gloo
      cross-process collective implementation; it is configured before the
      backend exists (the only time it can be).
    * **Retry with backoff** — transient coordinator connect faults (the
      coordinator's port not up yet, a connection reset mid-handshake) are
      retried with ``resilience.retry_policy``'s capped exponential backoff
      (``retries``/``backoff_s`` override). Non-transient faults propagate
      on the first attempt — error parity with the bare call.
    * **The ``multihost.init`` fault site** — checked before every connect
      attempt, so an injected ``ConnectionResetError`` exercises exactly the
      retry path a flaky coordinator would.
    * **Liveness** — with a mesh dir configured and a real multi-process
      world, the lease heartbeat daemon starts beating before this returns.

    Returns the refreshed default ``MeshCommunication`` spanning every
    process's devices. Idempotent like ``communication.initialize``."""
    from . import communication, resilience, telemetry

    env = os.environ
    if coordinator_address is None:
        coordinator_address = env.get("HEAT_TPU_COORDINATOR", "").strip() or None
    if num_processes is None:
        raw = env.get("HEAT_TPU_NUM_PROCESSES", "").strip()
        num_processes = int(raw) if raw else None
    if process_id is None:
        raw = env.get("HEAT_TPU_PROCESS_ID", "").strip()
        process_id = int(raw) if raw else None
    if (num_processes or 1) > 1:
        platforms = (env.get("JAX_PLATFORMS") or "").strip().lower()
        if platforms in ("", "cpu"):
            try:
                jax.config.update("jax_cpu_collectives_implementation", "gloo")
            except Exception:  # noqa: BLE001 - jaxlib without gloo: single-host only
                warnings.warn(
                    "gloo CPU collectives unavailable in this jaxlib; a "
                    "multi-process CPU mesh cannot run cross-process "
                    "collectives",
                    stacklevel=2,
                )
    policy = resilience.retry_policy
    attempts_left = policy.retries if retries is None else int(retries)
    delay = policy.base_delay if backoff_s is None else float(backoff_s)
    while True:
        try:
            if resilience._ARMED:
                resilience.check("multihost.init")
            comm = communication.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes,
                process_id=process_id,
                **kwargs,
            )
            break
        except Exception as exc:  # noqa: BLE001 - classified below
            if attempts_left <= 0 or not _transient_init_fault(exc, policy):
                raise
            attempts_left -= 1
            with _LOCK:
                _STATS["init_retries"] += 1
            if telemetry._MODE:
                telemetry.record_event(
                    "distributed_init_retry", error=repr(exc), delay_s=delay
                )
            time.sleep(min(delay, policy.max_delay))
            delay *= 2.0
    world = process_count()
    with _LOCK:
        _STATS["world"] = world
        _STATS["epoch"] = mesh_epoch()
    if telemetry._MODE:
        telemetry.record_event(
            "distributed_init",
            world=world, process=process_index(), epoch=mesh_epoch(),
        )
    if heartbeat and world > 1:
        start_heartbeat(world=world)
    return comm


# ----------------------------------------------------------------------
# the local launcher: N coordinated processes, supervised across generations
# ----------------------------------------------------------------------
def _free_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return int(s.getsockname()[1])


def _read_progress_step(mesh: str, epoch: int, proc: int) -> Optional[int]:
    try:
        with open(_progress_path(mesh, epoch, proc)) as fh:
            return int(json.load(fh).get("step"))
    except (OSError, ValueError, TypeError):
        return None


def _first_progress_time(mesh: str, epoch: int, world: int) -> Optional[float]:
    times = []
    for proc in range(world):
        try:
            times.append(os.stat(_progress_path(mesh, epoch, proc)).st_mtime)
        except OSError:
            pass
    return min(times) if times else None


def _read_lost_markers(mesh: str, epoch: int) -> FrozenSet[int]:
    lost = set()
    try:
        for name in os.listdir(_lost_dir(mesh, epoch)):
            if name.startswith("proc-"):
                try:
                    lost.add(int(name.split("-", 1)[1]))
                except ValueError:
                    pass
    except OSError:
        pass
    return frozenset(lost)


def _strip_device_count(flags: str) -> str:
    import re

    return re.sub(r"--xla_force_host_platform_device_count=\d+", "", flags).strip()


def spawn_local(
    n: int,
    command: Sequence[str],
    *,
    mesh: Optional[str] = None,
    max_reforms: int = 1,
    devices_per_process: int = 1,
    barrier_timeout_ms: float = 30_000.0,
    heartbeat_ms: float = 200.0,
    peer_lost_ms: Optional[float] = None,
    drain_grace_ms: Optional[float] = None,
    env: Optional[Dict[str, str]] = None,
    timeout_s: float = 600.0,
    kill: Optional[Dict[str, Any]] = None,
    stdout=None,
) -> Dict[str, Any]:
    """Launch ``command`` as ``n`` coordinated worker processes on this
    machine and supervise them across reform generations.

    Every worker gets the launcher contract in its environment:
    ``HEAT_TPU_COORDINATOR`` (a fresh localhost port per generation),
    ``HEAT_TPU_PROCESS_ID`` / ``HEAT_TPU_NUM_PROCESSES`` (contiguous,
    re-ranked each generation), ``HEAT_TPU_MESH_DIR`` / ``HEAT_TPU_MESH_EPOCH``
    (the shared lease/marker dir and the generation number), barrier
    timeouts ON (``HEAT_TPU_BARRIER_TIMEOUT_MS``), and fast lease cadence
    (``HEAT_TPU_HEARTBEAT_MS`` / ``HEAT_TPU_PEER_LOST_MS``) — a worker that
    calls :func:`initialize_distributed` needs nothing else.

    Generation protocol: a generation ends when every child has exited. All
    zero → done. Otherwise the lost set is read from the lease daemon's
    markers (``{mesh}/lost/epoch-*/``) — detection evidence, robust to
    collateral crashes — falling back to "non-zero, non-reform exits" when
    no survivor lived long enough to write one. If at least one worker asked
    for reform (exit :data:`REFORM_EXIT`) and reforms remain, the survivors
    respawn as a smaller world under the next epoch (fresh port, re-ranked
    ids); workers are expected to restore from the newest verifying
    checkpoint themselves. Children still alive after every non-lost member
    exited (a SIGSTOP'd or hung lost peer) are SIGKILLed — the launcher
    guarantees a generation cannot hang.

    Chaos injection (the process-level fault injector): ``kill={"rank": r,
    "at_step": s}`` SIGKILLs rank ``r`` once its progress beacon
    (:func:`note_progress`) reaches step ``s`` (or ``{"rank": r,
    "after_s": t}`` on a timer) in epoch 0.

    Returns ``{"ok", "reforms", "generations": [...], "t_kill", "mesh"}``;
    each generation records its epoch, world size, exit codes, lost set and
    timing (``t_spawn`` / ``t_first_progress``)."""
    import tempfile

    if mesh is None:
        mesh = tempfile.mkdtemp(prefix="heat-tpu-mesh-")
    os.makedirs(mesh, exist_ok=True)
    command = [str(c) for c in command]
    generations: List[Dict[str, Any]] = []
    result: Dict[str, Any] = {
        "ok": False, "reforms": 0, "generations": generations,
        "t_kill": None, "mesh": mesh,
    }
    world = int(n)
    epoch = 0
    kill_pending = dict(kill) if kill else None
    while True:
        port = _free_port()
        base_env = dict(os.environ)
        if env:
            base_env.update(env)
        flags = _strip_device_count(base_env.get("XLA_FLAGS", ""))
        base_env["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={devices_per_process}"
        ).strip()
        overrides = env or {}
        if "JAX_PLATFORMS" not in overrides:
            # spawn_local worlds are gloo CPU worlds by construction. An
            # inherited JAX_PLATFORMS (e.g. "tpu" on a chip host) must not
            # leak in: the parent may hold the chip, and a child that
            # reaches for it fails or hangs
            base_env["JAX_PLATFORMS"] = "cpu"
        if "HEAT_TPU_BARRIER_TIMEOUT_MS" not in overrides:
            base_env["HEAT_TPU_BARRIER_TIMEOUT_MS"] = f"{barrier_timeout_ms:g}"
        if "HEAT_TPU_HEARTBEAT_MS" not in overrides:
            base_env["HEAT_TPU_HEARTBEAT_MS"] = f"{heartbeat_ms:g}"
        if "HEAT_TPU_PEER_LOST_MS" not in overrides:
            base_env["HEAT_TPU_PEER_LOST_MS"] = (
                f"{peer_lost_ms if peer_lost_ms is not None else 5.0 * heartbeat_ms:g}"
            )
        if "HEAT_TPU_DRAIN_GRACE_MS" not in overrides:
            # the zero-hang backstop: a survivor stuck in a collective with
            # a hung (not dead) peer forces reform_exit after this grace
            base_env["HEAT_TPU_DRAIN_GRACE_MS"] = (
                f"{drain_grace_ms if drain_grace_ms is not None else max(2_000.0, 10.0 * heartbeat_ms):g}"
            )
        base_env["HEAT_TPU_MESH_DIR"] = mesh
        base_env["HEAT_TPU_MESH_EPOCH"] = str(epoch)
        base_env["HEAT_TPU_COORDINATOR"] = f"127.0.0.1:{port}"
        base_env["HEAT_TPU_NUM_PROCESSES"] = str(world)
        procs: List[subprocess.Popen] = []
        t_spawn = time.time()
        for rank in range(world):
            penv = dict(base_env)
            penv["HEAT_TPU_PROCESS_ID"] = str(rank)
            procs.append(
                subprocess.Popen(command, env=penv, stdout=stdout, stderr=stdout)
            )
        gen: Dict[str, Any] = {
            "epoch": epoch, "world": world, "exits": [None] * world,
            "lost": [], "t_spawn": t_spawn, "t_first_progress": None,
            "timed_out": False,
        }
        generations.append(gen)
        deadline = time.monotonic() + float(timeout_s)
        kill_fired_at = None
        while any(p.poll() is None for p in procs):
            if gen["t_first_progress"] is None:
                gen["t_first_progress"] = _first_progress_time(mesh, epoch, world)
            if kill_pending is not None and epoch == 0:
                rank = int(kill_pending.get("rank", world - 1))
                due = False
                if "at_step" in kill_pending:
                    step = _read_progress_step(mesh, epoch, rank)
                    due = step is not None and step >= int(kill_pending["at_step"])
                if "after_s" in kill_pending:
                    due = due or (time.time() - t_spawn) >= float(kill_pending["after_s"])
                if due and rank < world and procs[rank].poll() is None:
                    procs[rank].kill()
                    result["t_kill"] = kill_fired_at = time.time()
                    kill_pending = None
            # a lost-but-still-running child (SIGSTOP'd, hung in a dead
            # collective) must not hold the generation open once every live
            # member has exited: the launcher is the hang backstop
            marked = _read_lost_markers(mesh, epoch)
            if marked and all(
                procs[r].poll() is not None for r in range(world) if r not in marked
            ):
                for r in sorted(marked):
                    if r < world and procs[r].poll() is None:
                        procs[r].kill()
            if time.monotonic() > deadline:
                gen["timed_out"] = True
                for p in procs:
                    if p.poll() is None:
                        p.kill()
                break
            time.sleep(0.05)
        for p in procs:
            p.wait()
        gen["exits"] = [p.returncode for p in procs]
        if gen["t_first_progress"] is None:
            gen["t_first_progress"] = _first_progress_time(mesh, epoch, world)
        gen["duration_s"] = round(time.time() - t_spawn, 3)
        if gen["timed_out"]:
            return result
        if all(rc == 0 for rc in gen["exits"]):
            result["ok"] = True
            return result
        lost = set(_read_lost_markers(mesh, epoch))
        if kill_fired_at is not None and kill is not None:
            lost.add(int(kill.get("rank", world - 1)))
        # detection evidence first; exit-code forensics only as fallback
        lost = {r for r in lost if r < world and gen["exits"][r] != 0} or {
            r for r, rc in enumerate(gen["exits"]) if rc not in (0, REFORM_EXIT)
        }
        gen["lost"] = sorted(lost)
        asked_reform = any(rc == REFORM_EXIT for rc in gen["exits"])
        survivors = world - len(lost)
        if not asked_reform or survivors < 1 or result["reforms"] >= int(max_reforms):
            return result
        world = survivors
        epoch += 1
        result["reforms"] += 1


# report()["multihost"]: the set-attribute hook pattern (telemetry stays
# dependency-free; the ops plane reads the same hook for its gauges)
from . import telemetry as _telemetry  # noqa: E402

_telemetry._MULTIHOST_HOOK = report_stats
