"""The fault-tolerant multi-process runtime (ISSUE 19), single-process
side: the lease heartbeat daemon (peer loss as a NAMED event, never a
hang), the bounded barrier (StallError + abandoned-thread accounting),
guarded distributed bring-up (env config, retry on transient connect
faults, the ``multihost.init`` fault site), the degraded-world topology
contract with process 0 dead, checkpoint fast-fail under a lost peer, the
launcher's generation protocol (driven with jax-free stub workers), and
the observability joins (``report()["multihost"]``, ops-plane gauges,
``/readyz`` peers check).

The REAL 2-process runs — cross-process collectives over loopback gloo,
SIGKILL chaos, elastic reform with checkpoint-equality acceptance — live
in ``tests/test_multiproc.py`` (``-m slow``; the ``multiproc`` matrix leg
runs them under the CI fault mix).
"""

import json
import os
import sys
import tempfile
import threading
import time
import types
import unittest.mock
import warnings

import numpy as np

from heat_tpu.core import multihost, opsplane, resilience, telemetry
from heat_tpu.utils.checkpoint import save_checkpoint

from harness import TestCase

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _wait_for(predicate, timeout_s=5.0, interval_s=0.01):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval_s)
    return predicate()


class MultihostCase(TestCase):
    def setUp(self):
        super().setUp()
        multihost.stop_heartbeat()
        multihost.reset_peers()

    def tearDown(self):
        multihost.stop_heartbeat()
        multihost.reset_peers()
        super().tearDown()


class TestLeaseDaemon(MultihostCase):
    """The daemon's own steps, driven by hand on an unstarted daemon:
    ``_scan`` judges ``time.time()`` against each lease's mtime and the
    daemon's ``_started_at``, so a test ages a peer with ``os.utime`` (or
    the daemon by backdating ``_started_at``) instead of racing a 20 ms
    thread on a shared CPU. The real thread is started once, by
    ``test_heartbeat_fault_site_counts_missed_beats``, under a 10 s lease."""

    LEASE_S = 30.0
    AGED_S = 60.0

    def _daemon(self, mesh, world):
        return multihost._HeartbeatDaemon(
            mesh, 0, world, 0, interval_s=1.0, lost_after_s=self.LEASE_S
        )

    def _beat_as(self, mesh, peer, age_s=0.0):
        lease = multihost._lease_path(mesh, 0, peer)
        multihost._write_atomic(lease, "{}")
        stamp = time.time() - age_s
        os.utime(lease, (stamp, stamp))

    def test_stale_peer_declared_lost_with_marker_and_event(self):
        with tempfile.TemporaryDirectory() as mesh:
            daemon = self._daemon(mesh, world=2)
            # peer 1 beat once, long ago (backdated mtime = a dead process)
            self._beat_as(mesh, 1, age_s=self.AGED_S)
            with telemetry.enabled(2), warnings.catch_warnings():
                warnings.simplefilter("ignore")
                daemon._beat(1)
                daemon._scan()
                kinds = [e.get("kind") for e in telemetry.events()]
            self.assertEqual(multihost.lost_peers(), frozenset({1}))
            self.assertIn("peer_lost", kinds)
            # the declaration is control flow at the next safe boundary...
            with self.assertRaises(multihost.PeerLostError) as ctx:
                multihost.check_peers()
            self.assertEqual(ctx.exception.peers, (1,))
            # ...and durable evidence for the launcher, naming WHO died
            marker = os.path.join(multihost._lost_dir(mesh, 0), "proc-00001")
            self.assertTrue(os.path.exists(marker))
            with open(marker) as fh:
                self.assertEqual(json.load(fh)["peer"], 1)

    def test_beating_peer_stays_live_and_silent_peer_gets_grace(self):
        with tempfile.TemporaryDirectory() as mesh, warnings.catch_warnings():
            warnings.simplefilter("ignore")
            daemon = self._daemon(mesh, world=3)
            # peer 1 beats (we play it); peer 2 never starts
            self._beat_as(mesh, 1)
            daemon._scan()
            # a never-started peer is granted the lease window from daemon
            # start before being declared...
            self.assertEqual(multihost.lost_peers(), frozenset())
            daemon._started_at -= self.AGED_S
            daemon._scan()
            # ...and a live peer is never declared inside its own window
            self.assertEqual(multihost.lost_peers(), frozenset({2}))

    def test_declaration_sticky_until_reset(self):
        with tempfile.TemporaryDirectory() as mesh, warnings.catch_warnings():
            warnings.simplefilter("ignore")
            daemon = self._daemon(mesh, world=2)
            daemon._started_at -= self.AGED_S
            daemon._scan()
            self.assertEqual(multihost.lost_peers(), frozenset({1}))
            # a returning zombie belongs to a PREVIOUS world: fresh
            # beats must not resurrect it inside this epoch
            self._beat_as(mesh, 1)
            daemon._scan()
            self.assertEqual(multihost.lost_peers(), frozenset({1}))
            multihost.reset_peers()
            self.assertEqual(multihost.lost_peers(), frozenset())
            # after the reset the peer is judged by its lease again
            daemon._scan()
            self.assertEqual(multihost.lost_peers(), frozenset())
            self._beat_as(mesh, 1, age_s=self.AGED_S)
            daemon._scan()
            self.assertEqual(multihost.lost_peers(), frozenset({1}))

    def test_heartbeat_fault_site_counts_missed_beats(self):
        with tempfile.TemporaryDirectory() as mesh:
            before = multihost.report_stats()["heartbeat_errors"]
            with resilience.inject("multihost.heartbeat", times=3):
                self.assertTrue(
                    multihost.start_heartbeat(
                        mesh=mesh, process=0, world=2, epoch=0,
                        interval_ms=10.0, lost_ms=10_000.0,
                    )
                )
                self.assertTrue(
                    _wait_for(
                        lambda: multihost.report_stats()["heartbeat_errors"]
                        >= before + 3
                    )
                )
                # a missed beat is counted, never a daemon crash: once the
                # injected fault is spent, beating resumes on its own
                lease = multihost._lease_path(mesh, 0, 0)
                self.assertTrue(_wait_for(lambda: os.path.exists(lease)))
                multihost.stop_heartbeat()


class TestBarrier(MultihostCase):
    def test_fault_site_fires_before_single_host_early_out(self):
        # chaos runs must reach the barrier path even single-process
        with resilience.inject("multihost.barrier"):
            with self.assertRaises(resilience.FaultInjected):
                multihost.sync_processes("test.barrier.site")
        multihost.sync_processes("test.barrier.site")  # disarmed: no-op again

    def test_timeout_raises_stall_error_naming_tag_and_counts_abandoned(self):
        from jax.experimental import multihost_utils

        release = threading.Event()
        stats0 = multihost.report_stats()
        try:
            with unittest.mock.patch.object(
                multihost, "process_count", return_value=2
            ), unittest.mock.patch.object(
                multihost_utils,
                "sync_global_devices",
                side_effect=lambda tag: release.wait(10.0),
            ), unittest.mock.patch.dict(
                os.environ, {"HEAT_TPU_ABANDONED_BARRIER_CAP": "1"}
            ):
                with warnings.catch_warnings(record=True) as caught:
                    warnings.simplefilter("always")
                    with self.assertRaises(resilience.StallError) as ctx:
                        multihost.sync_processes("test.hung.barrier", timeout_ms=50.0)
            self.assertIn("test.hung.barrier", str(ctx.exception))
            stats = multihost.report_stats()
            self.assertEqual(stats["barrier_timeouts"], stats0["barrier_timeouts"] + 1)
            self.assertEqual(
                stats["abandoned_threads"], stats0["abandoned_threads"] + 1
            )
            self.assertGreaterEqual(stats["abandoned_alive"], 1)
            # past the cap the leak is loud, not silent
            self.assertTrue(
                any(issubclass(w.category, resilience.StallWarning) for w in caught)
            )
        finally:
            release.set()
        # released threads drop out of the pruned-alive gauge
        self.assertTrue(
            _wait_for(lambda: multihost.report_stats()["abandoned_alive"] == 0)
        )

    def test_worker_thread_failure_is_reraised_at_call_site(self):
        # the failure[0] arm: a barrier that ERRORS (vs hangs) must surface
        # the original exception, not a timeout
        from jax.experimental import multihost_utils

        def _boom(tag):
            raise ValueError(f"coordination rejected {tag}")

        with unittest.mock.patch.object(
            multihost, "process_count", return_value=2
        ), unittest.mock.patch.object(
            multihost_utils, "sync_global_devices", side_effect=_boom
        ):
            with self.assertRaises(ValueError) as ctx:
                multihost.sync_processes("test.error.barrier", timeout_ms=5_000.0)
        self.assertIn("test.error.barrier", str(ctx.exception))

    def test_malformed_timeout_env_warns_and_reads_off(self):
        with unittest.mock.patch.dict(
            os.environ, {"HEAT_TPU_BARRIER_TIMEOUT_MS": "soon"}
        ):
            with warnings.catch_warnings(record=True) as caught:
                warnings.simplefilter("always")
                self.assertIsNone(multihost._barrier_timeout_ms())
            self.assertTrue(caught)
        with unittest.mock.patch.dict(
            os.environ, {"HEAT_TPU_BARRIER_TIMEOUT_MS": "250"}
        ):
            self.assertEqual(multihost._barrier_timeout_ms(), 250.0)


class TestDegradedTopology(MultihostCase):
    """The world with process 0 dead: who owns what, and what fails fast."""

    DEVICES = [types.SimpleNamespace(process_index=p, id=i)
               for i, p in enumerate([0, 0, 1, 1])]

    def test_no_survivor_owns_publication(self):
        # process 0's rename-ownership does NOT fail over: the degraded
        # world cannot commit, by design — the launcher's re-rank gives the
        # NEXT generation a process 0 again
        self.assertTrue(multihost.io_owner(proc=0))
        self.assertFalse(multihost.io_owner(proc=1))

    def test_survivor_topology_reads_stay_correct(self):
        self.assertEqual(
            [r for r, _ in multihost.ranks_to_read(self.DEVICES, proc=1)], [2, 3]
        )
        self.assertEqual(multihost.representative_rank(self.DEVICES, proc=1), 2)

    def test_cooperative_save_fails_fast_named(self):
        with multihost._LOCK:
            multihost._LOST.add(0)
        try:
            with tempfile.TemporaryDirectory() as d:
                with self.assertRaises(multihost.PeerLostError) as ctx:
                    save_checkpoint(d, {"w": np.zeros(3)}, step=7)
                self.assertEqual(ctx.exception.peers, (0,))
                self.assertIn("step 7", str(ctx.exception))
                self.assertEqual(os.listdir(d), [])  # nothing staged
        finally:
            multihost.reset_peers()


class TestInitializeDistributed(MultihostCase):
    ENV = {
        "HEAT_TPU_COORDINATOR": "127.0.0.1:9999",
        "HEAT_TPU_NUM_PROCESSES": "4",
        "HEAT_TPU_PROCESS_ID": "2",
        "HEAT_TPU_MESH_DIR": "",
    }

    def test_env_fills_unset_arguments(self):
        from heat_tpu.core import communication

        sentinel = object()
        with unittest.mock.patch.dict(os.environ, self.ENV), unittest.mock.patch.object(
            communication, "initialize", return_value=sentinel
        ) as init:
            out = multihost.initialize_distributed(heartbeat=False)
        self.assertIs(out, sentinel)
        self.assertEqual(
            init.call_args.kwargs,
            {
                "coordinator_address": "127.0.0.1:9999",
                "num_processes": 4,
                "process_id": 2,
            },
        )

    def test_transient_connect_fault_is_retried(self):
        from heat_tpu.core import communication

        sentinel = object()
        retries0 = multihost.report_stats()["init_retries"]
        with unittest.mock.patch.dict(os.environ, self.ENV), unittest.mock.patch.object(
            communication,
            "initialize",
            side_effect=[ConnectionResetError("handshake"), sentinel],
        ) as init:
            out = multihost.initialize_distributed(heartbeat=False, backoff_s=0.001)
        self.assertIs(out, sentinel)
        self.assertEqual(init.call_count, 2)
        self.assertEqual(
            multihost.report_stats()["init_retries"], retries0 + 1
        )

    def test_injected_init_fault_exercises_the_retry_path(self):
        from heat_tpu.core import communication

        sentinel = object()
        with unittest.mock.patch.dict(os.environ, self.ENV), unittest.mock.patch.object(
            communication, "initialize", return_value=sentinel
        ), resilience.inject("multihost.init", exc=ConnectionResetError) as spec:
            out = multihost.initialize_distributed(heartbeat=False, backoff_s=0.001)
        self.assertIs(out, sentinel)
        self.assertEqual(spec.fired, 1)

    def test_non_transient_fault_propagates_first_attempt(self):
        from heat_tpu.core import communication

        with unittest.mock.patch.dict(os.environ, self.ENV), unittest.mock.patch.object(
            communication, "initialize", side_effect=ValueError("bad mesh shape")
        ) as init:
            with self.assertRaises(ValueError):
                multihost.initialize_distributed(heartbeat=False, backoff_s=0.001)
        self.assertEqual(init.call_count, 1)  # error parity with the bare call

    def test_transient_classifier(self):
        policy = resilience.retry_policy
        self.assertTrue(
            multihost._transient_init_fault(ConnectionRefusedError(), policy)
        )
        self.assertTrue(
            multihost._transient_init_fault(
                RuntimeError("DEADLINE_EXCEEDED: coordination service"), policy
            )
        )
        self.assertFalse(
            multihost._transient_init_fault(RuntimeError("duplicate task id"), policy)
        )
        self.assertFalse(multihost._transient_init_fault(ValueError("nope"), policy))


_STUB_WORKER = r"""
import fcntl, json, os, sys, time
rank = int(os.environ["HEAT_TPU_PROCESS_ID"])
epoch = int(os.environ["HEAT_TPU_MESH_EPOCH"])
world = int(os.environ["HEAT_TPU_NUM_PROCESSES"])
mesh = os.environ["HEAT_TPU_MESH_DIR"]
out = os.environ["STUB_OUT"]
casualty = epoch == 0 and world > 1 and rank == world - 1
if casualty:
    # held for life: the kernel drops it when this process is gone
    life = open(os.path.join(out, "casualty.lock"), "w")
    fcntl.flock(life, fcntl.LOCK_EX)
with open(os.path.join(out, f"ran-{epoch}-{rank}"), "w") as fh:
    json.dump({"world": world, "epoch": epoch}, fh)
if epoch == 0 and world > 1:
    if casualty:
        os._exit(9)
    # survivors: play the lease daemon's detection, then drain for reform.
    # First wait for the casualty to be dead (its ran file says the lock is
    # held; the lock falls with the process): the launcher SIGKILLs a marked
    # peer that outlives its survivors, and its exit 9 would read -9. The
    # launcher's generation timeout is the hang detector
    while not os.path.exists(os.path.join(out, f"ran-0-{world - 1}")):
        time.sleep(0.01)
    with open(os.path.join(out, "casualty.lock")) as fh:
        fcntl.flock(fh, fcntl.LOCK_SH)
    lost = os.path.join(mesh, "lost", f"epoch-{epoch:04d}")
    os.makedirs(lost, exist_ok=True)
    with open(os.path.join(lost, f"proc-{world - 1:05d}"), "w") as fh:
        json.dump({"peer": world - 1, "by": rank}, fh)
    os._exit(77)
os._exit(0)
"""


class TestSpawnLocalProtocol(MultihostCase):
    """The launcher's generation protocol, pinned with jax-free stub
    workers (the real collectives-and-checkpoints drive is the slow
    suite): marker-based lost attribution, survivor re-rank into a
    contiguous smaller world, the epoch bump, and the reform budget."""

    def _run(self, n, **kwargs):
        with tempfile.TemporaryDirectory() as out:
            result = multihost.spawn_local(
                n,
                [sys.executable, "-c", _STUB_WORKER],
                env={"STUB_OUT": out},
                timeout_s=60.0,
                **kwargs,
            )
            runs = {}
            for name in os.listdir(out):
                if name.startswith("ran-"):
                    with open(os.path.join(out, name)) as fh:
                        runs[name[4:]] = json.load(fh)
            return result, runs

    def test_clean_world_is_ok_without_reform(self):
        result, runs = self._run(1)
        self.assertTrue(result["ok"])
        self.assertEqual(result["reforms"], 0)
        self.assertEqual(runs["0-0"]["world"], 1)

    def test_reform_reranks_survivors_under_next_epoch(self):
        result, runs = self._run(3, max_reforms=1)
        self.assertTrue(result["ok"])
        self.assertEqual(result["reforms"], 1)
        gen0, gen1 = result["generations"]
        self.assertEqual(gen0["lost"], [2])  # from the markers, not exit codes
        self.assertEqual(gen0["exits"][2], 9)
        self.assertEqual([gen0["world"], gen1["world"]], [3, 2])
        self.assertEqual([gen0["epoch"], gen1["epoch"]], [0, 1])
        self.assertEqual(gen1["exits"], [0, 0])
        # generation 1 ranks are contiguous from 0: a process 0 exists again
        self.assertEqual(sorted(runs), ["0-0", "0-1", "0-2", "1-0", "1-1"])

    def test_exhausted_reform_budget_is_a_failure(self):
        result, _ = self._run(2, max_reforms=0)
        self.assertFalse(result["ok"])
        self.assertEqual(result["reforms"], 0)
        self.assertEqual(result["generations"][0]["lost"], [1])


class TestObservability(MultihostCase):
    def test_report_joins_multihost_block(self):
        doc = telemetry.report()
        self.assertIn("multihost", doc)
        block = doc["multihost"]
        for key in (
            "world", "epoch", "barriers", "barrier_timeouts",
            "abandoned_threads", "heartbeats", "heartbeat_errors",
            "init_retries", "peers_lost", "heartbeat_running", "abandoned_alive",
        ):
            self.assertIn(key, block)

    def test_opsplane_exports_peer_gauges(self):
        samples = {name: value for name, _, value in opsplane.collect()}
        self.assertIn("heat_tpu_peers_expected", samples)
        self.assertEqual(samples["heat_tpu_peers_lost"], 0.0)
        self.assertIn("heat_tpu_barrier_threads_abandoned", samples)

    def test_lost_peer_flips_readyz(self):
        self.assertTrue(opsplane.ready_status()["checks"]["peers"])
        with multihost._LOCK:
            multihost._LOST.add(1)
        try:
            status = opsplane.ready_status()
            self.assertFalse(status["checks"]["peers"])
            self.assertEqual(status["status"], "unready")
            samples = {name: value for name, _, value in opsplane.collect()}
            self.assertEqual(samples["heat_tpu_peers_lost"], 1.0)
        finally:
            multihost.reset_peers()
        self.assertTrue(opsplane.ready_status()["checks"]["peers"])


if __name__ == "__main__":
    import unittest

    unittest.main()
