"""Runtime health layer (ISSUE 11): always-on flight recorder, stall
watchdog, latency SLO histograms.

Pins the acceptance criteria: the flight ring captures compact timeline
events at ``HEAT_TPU_TELEMETRY=1`` (where the verbose timeline stays
empty) and auto-dumps a validated Perfetto trace + forensics bundle on an
injected OOM and on a fused-dispatch degrade; the watchdog detects an
injected stall (naming the in-flight program key and the pending DAG
roots) without false positives on a healthy mesh, and its ``raise``
policy surfaces a non-degradable ``StallError``; the log-bucketed
histograms track numpy percentiles within the bucket error bound and
surface per-program p50/p90/p99 in ``report()["health"]``; SLO gauges
count breaches; and none of it ever forces a pending chain or initializes
the backend. Runs green at mesh 1/3/8 (matrix legs), under
``HEAT_TPU_FAULTS=ci`` (explicit injections suspend the ambient mix) and
with the matrix's flight leg armed from the environment (setUp re-arms
per test and tearDown restores the ambient config).
"""

import importlib
import io
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time
import unittest
import warnings

import numpy as np

import heat_tpu as ht
from heat_tpu.core import fusion, health_runtime, memledger, resilience, telemetry

from harness import TestCase

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class HealthCase(TestCase):
    """Clean flight/watchdog/histogram state per test, exact under the
    ambient CI fault mix and under the matrix flight leg's env knobs
    (every test re-arms its own config and restores the ambient one)."""

    def setUp(self):
        self._suspend = resilience.suspended()
        self._suspend.__enter__()
        fusion.clear_cache()
        telemetry.reset()  # cascades into health_runtime.reset()
        memledger.reset()
        self._prev_budget = memledger.set_budget(None)
        self._prev_mode = telemetry.set_mode(1)
        self._prev_flight = health_runtime.set_flight(True, 256)
        self._prev_wd = health_runtime.set_watchdog(enabled=False)
        self._tmp = tempfile.mkdtemp(prefix="heat_tpu_flight_test_")
        self._prev_dir = health_runtime.set_dump_dir(self._tmp)

    def tearDown(self):
        health_runtime.set_dump_dir(self._prev_dir)
        health_runtime.set_watchdog(
            self._prev_wd[0], policy=self._prev_wd[1], enabled=self._prev_wd[2]
        )
        health_runtime.set_flight(self._prev_flight[0], self._prev_flight[1])
        telemetry.set_mode(self._prev_mode)
        telemetry.reset()
        memledger.set_budget(self._prev_budget[0], self._prev_budget[1])
        memledger.reset()
        self._suspend.__exit__(None, None, None)
        shutil.rmtree(self._tmp, ignore_errors=True)

    def _split_input(self, seed=0, n_mult=4):
        n = n_mult * self.get_size()
        return ht.array(
            np.random.default_rng(seed).standard_normal((n, 3)).astype(np.float32),
            split=0,
        )

    def _run_chain(self, seed=0):
        a = self._split_input(seed)
        return float((ht.exp(a * 0.25) + 1.0).sum())

    def _await_stall(self, timeout_s=3.0):
        end = time.monotonic() + timeout_s
        while time.monotonic() < end:
            stall = health_runtime.last_stall()
            if stall is not None:
                return stall
            time.sleep(0.02)
        return health_runtime.last_stall()


class TestFlightRing(HealthCase):
    @unittest.skipUnless(fusion.active(), "flight events ride the fused dispatch/sync seams")
    def test_ring_records_at_mode1_while_verbose_timeline_stays_empty(self):
        self._run_chain()
        kinds = {ev.get("kind") for ev in health_runtime.flight_events()}
        self.assertIn("blocking_sync", kinds)
        if fusion.active():
            self.assertIn("dispatch", kinds)
        # mode 1 is aggregate-only: the verbose per-state timeline must not
        # have been fed — the ring is the ONLY event capture at this mode
        self.assertEqual(len(telemetry._GLOBAL.events), 0)

    @unittest.skipUnless(fusion.active(), "flight events ride the fused dispatch/sync seams")
    def test_ring_cap_evicts_and_counts_drops(self):
        health_runtime.set_flight(True, 16)
        a = self._split_input()
        for i in range(24):  # every iteration emits >= 1 sync event
            float((a + float(i)).sum())
        stats = health_runtime.flight_stats()
        self.assertLessEqual(len(health_runtime.flight_events()), 16)
        self.assertEqual(stats["cap"], 16)
        self.assertGreater(stats["dropped"], 0)

    def test_disabled_recorder_is_a_noop(self):
        health_runtime.set_flight(False)
        self._run_chain()
        self.assertEqual(health_runtime.flight_events(), [])
        self.assertIsNone(health_runtime.auto_dump("oom"))

    def test_env_knobs_configure_a_fresh_interpreter(self):
        code = (
            "from heat_tpu.core import health_runtime as hr\n"
            "assert hr._ENABLED is False, hr._ENABLED\n"
            "assert hr._RING_CAP == 64, hr._RING_CAP\n"
            "print('OK')\n"
        )
        env = dict(os.environ)
        env.setdefault("JAX_PLATFORMS", "cpu")
        env["HEAT_TPU_FLIGHT"] = "0"
        env["HEAT_TPU_FLIGHT_EVENTS"] = "64"
        out = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True, text=True, env=env, cwd=_REPO,
        )
        self.assertEqual(out.returncode, 0, out.stderr)
        self.assertIn("OK", out.stdout)


class TestFlightDump(HealthCase):
    @unittest.skipUnless(fusion.active(), "flight events ride the fused dispatch/sync seams")
    def test_manual_dump_validates_and_carries_forensics(self):
        self._run_chain()
        dump = health_runtime.dump_flight(reason="manual")
        self.assertEqual(dump["problems"], [])
        self.assertTrue(os.path.exists(dump["trace_path"]))
        with open(dump["path"]) as fh:
            bundle = json.load(fh)
        for key in (
            "reason", "captured_utc", "telemetry_mode", "events", "ring_cap",
            "trace_path", "watchdog", "stalls", "health", "programs", "memory",
        ):
            self.assertIn(key, bundle)
        self.assertEqual(bundle["reason"], "manual")
        self.assertEqual(bundle["trace_problems"], [])
        self.assertGreater(bundle["events"], 0)

    @unittest.skipUnless(fusion.active(), "fusion disabled via HEAT_TPU_FUSION")
    def test_auto_dump_on_injected_oom_names_program(self):
        a = self._split_input(7)
        x = ht.exp(a * 0.25) + 1.0
        with resilience.inject("memory.exhausted", times=1):
            with warnings.catch_warnings(record=True):
                warnings.simplefilter("always")
                float(x.sum())
        self.assertIsNotNone(health_runtime.last_dump(), "OOM must auto-dump")
        # the exhaustion writes the "oom" bundle, then the guarded replay's
        # degrade seam writes a second one — find the OOM forensic itself
        oom_bundles = [
            os.path.join(self._tmp, name)
            for name in sorted(os.listdir(self._tmp))
            if "_oom_" in name and not name.endswith(".trace.json")
        ]
        self.assertTrue(oom_bundles, "no oom-reason bundle written")
        with open(oom_bundles[-1]) as fh:
            bundle = json.load(fh)
        self.assertEqual(bundle["reason"], "oom")
        self.assertEqual(bundle["trace_problems"], [])
        oom = bundle["memory"]["last_oom"]
        self.assertTrue(oom["program"], "bundle must name the failing program key")
        self.assertIn(oom["program"], bundle["programs"]["program_keys"])

    @unittest.skipUnless(fusion.active(), "fusion disabled via HEAT_TPU_FUSION")
    def test_auto_dump_on_fused_degrade(self):
        a = self._split_input(5)
        y = ht.log(ht.abs(a) + 2.0)
        with resilience.inject("fusion.compile", times=1):
            with warnings.catch_warnings(record=True) as caught:
                warnings.simplefilter("always")
                got = float(y.sum())
        self.assertIn(resilience.DegradedDispatchWarning, {w.category for w in caught})
        expect = float(np.sum(np.log(np.abs(np.asarray(a.larray)) + 2.0)))
        self.assertAlmostEqual(got / expect, 1.0, places=5)
        dump = health_runtime.last_dump()
        self.assertIsNotNone(dump, "degrade must trigger a flight auto-dump")
        with open(dump["path"]) as fh:
            self.assertEqual(json.load(fh)["reason"], "degrade")

    def test_auto_dump_throttles_per_reason(self):
        self._run_chain()
        first = health_runtime.auto_dump("degrade")
        self.assertIsNotNone(first)
        self.assertIsNone(health_runtime.auto_dump("degrade"), "throttled")
        self.assertIsNotNone(health_runtime.auto_dump("oom"), "per-reason throttle")


@unittest.skipUnless(fusion.active(), "fusion disabled via HEAT_TPU_FUSION")
class TestWatchdog(HealthCase):
    def test_detects_injected_stall_naming_program_and_pending_roots(self):
        health_runtime.set_watchdog(deadline_ms=80, policy="warn", enabled=True)
        a = self._split_input(3)
        with resilience.inject("watchdog.stall:dispatch", times=1):
            with warnings.catch_warnings(record=True) as caught:
                warnings.simplefilter("always")
                float((a * 2.0 + 1.0).sum())
                stall = self._await_stall()
        self.assertIsNotNone(stall, "watchdog must trip on the injected stall")
        self.assertEqual(stall["site"], "dispatch")
        self.assertIn(stall["program"], fusion.cache_stats()["program_keys"])
        self.assertTrue(stall["cids"], "diagnosis must carry the in-flight cids")
        self.assertIsInstance(stall["pending_roots"], list)
        self.assertGreaterEqual(health_runtime.watchdog_stats()["trips"], 1)
        stall_warns = [w for w in caught if w.category is resilience.StallWarning]
        self.assertTrue(stall_warns, "warn policy must emit a StallWarning")
        # the blocked sync's outer guard may trip too; at least one warning
        # must name the in-flight program key
        self.assertTrue(
            any(str(stall["program"]) in str(w.message) for w in stall_warns),
            [str(w.message) for w in stall_warns],
        )

    def test_no_false_positive_on_healthy_chain(self):
        health_runtime.set_watchdog(deadline_ms=30000, policy="warn", enabled=True)
        for i in range(3):
            self._run_chain(seed=i)
        self.assertIsNone(health_runtime.last_stall())
        stats = health_runtime.watchdog_stats()
        self.assertEqual(stats["trips"], 0)
        self.assertGreater(stats["arms"], 0, "guards must actually have armed")

    def test_raise_policy_raises_stall_error_and_chain_recovers(self):
        health_runtime.set_watchdog(deadline_ms=80, policy="raise", enabled=True)
        a = self._split_input(4)
        with resilience.inject("watchdog.stall:dispatch", times=1):
            with warnings.catch_warnings(record=True):
                warnings.simplefilter("always")
                with self.assertRaises(resilience.StallError):
                    float((a + 3.0).sum())
        # StallError must NOT degrade-to-eager (force_recoverable excludes
        # it) and the chain must stay re-forcible after the trip
        health_runtime.set_watchdog(policy="warn")
        got = float((a + 3.0).sum())
        expect = float(np.sum(np.asarray(a.larray) + 3.0))
        self.assertAlmostEqual(got / expect, 1.0, places=5)

    def test_dump_policy_writes_a_stall_bundle(self):
        health_runtime.set_watchdog(deadline_ms=80, policy="dump", enabled=True)
        a = self._split_input(6)
        with resilience.inject("watchdog.stall:dispatch", times=1):
            with warnings.catch_warnings(record=True):
                warnings.simplefilter("always")
                float((a - 1.0).sum())
                self.assertIsNotNone(self._await_stall())
        end = time.monotonic() + 3.0
        while time.monotonic() < end and health_runtime.last_dump() is None:
            time.sleep(0.02)
        dump = health_runtime.last_dump()
        self.assertIsNotNone(dump, "dump policy must write a bundle on trip")
        with open(dump["path"]) as fh:
            bundle = json.load(fh)
        self.assertEqual(bundle["reason"], "stall")
        self.assertTrue(bundle["stalls"], "bundle must carry the stall diagnosis")

    def test_set_watchdog_rejects_unknown_policy(self):
        with self.assertRaises(ValueError):
            health_runtime.set_watchdog(policy="panic")


class TestHistograms(HealthCase):
    def test_percentiles_track_numpy_within_bucket_error(self):
        rng = np.random.default_rng(42)
        samples = rng.lognormal(mean=-7.0, sigma=1.5, size=4000)
        h = health_runtime._Hist()
        for v in samples:
            h.observe(float(v))
        for q in (50, 90, 99):
            want = float(np.percentile(samples, q))
            got = h.percentile(q)
            self.assertLessEqual(
                abs(got - want) / want, 0.10,
                f"p{q}: hist {got} vs numpy {want}",
            )
        snap = h.snapshot()
        self.assertEqual(snap["count"], len(samples))
        self.assertLessEqual(snap["p50_s"], snap["p90_s"])
        self.assertLessEqual(snap["p90_s"], snap["p99_s"])

    @unittest.skipUnless(fusion.active(), "fusion disabled via HEAT_TPU_FUSION")
    def test_report_health_has_per_program_percentiles(self):
        for i in range(4):
            self._run_chain(seed=i)
        health = telemetry.report()["health"]
        disp = health["dispatch"]
        self.assertIn("*", disp)
        self.assertGreaterEqual(disp["*"]["count"], 1)
        programs = [k for k in disp if k != "*"]
        self.assertTrue(programs, "dispatch table must be keyed by program")
        for key in programs:
            self.assertIn(key, fusion.cache_stats()["program_keys"])
            for field in ("p50_s", "p90_s", "p99_s"):
                self.assertIn(field, disp[key])
        self.assertIn("*", health["compile"])  # the first run compiled

    @unittest.skipUnless(fusion.active(), "flight events ride the fused dispatch/sync seams")
    def test_sync_wait_aggregate_in_nonverbose_report(self):
        self._run_chain()
        sync_wait = telemetry.report()["async_forcing"]["sync_wait"]
        self.assertTrue(sync_wait, "mode 1 must aggregate blocking host waits")
        rec = next(iter(sync_wait.values()))
        self.assertGreaterEqual(rec["count"], 1)
        self.assertGreaterEqual(rec["total_s"], 0.0)
        self.assertGreaterEqual(rec["max_s"], 0.0)
        self.assertLessEqual(rec["max_s"], rec["total_s"] + 1e-9)

    @unittest.skipUnless(fusion.active(), "flight events ride the fused dispatch/sync seams")
    def test_scope_isolates_and_rolls_up(self):
        self._run_chain(seed=1)  # ambient-only traffic
        with telemetry.scope("inner"):
            before = health_runtime.health_block()["sync"]
            self.assertEqual(
                before.get("*", {}).get("count", 0), 0, "scope view must start empty"
            )
            self._run_chain(seed=2)
            inner = health_runtime.health_block()["sync"]["*"]["count"]
            self.assertGreaterEqual(inner, 1)
        overall = health_runtime.health_block(global_view=True)["sync"]["*"]["count"]
        self.assertGreater(overall, inner, "global view keeps ambient traffic")

    @unittest.skipUnless(fusion.active(), "flight events ride the fused dispatch/sync seams")
    def test_reset_clears_session_keeps_config(self):
        health_runtime.set_flight(True, 32)
        self._run_chain()
        self.assertTrue(health_runtime.flight_events())
        telemetry.reset()  # must cascade into the health layer
        self.assertEqual(health_runtime.flight_events(), [])
        health = health_runtime.health_block(global_view=True)
        self.assertEqual(health["sync"].get("*", {}).get("count", 0), 0)
        self.assertEqual(health["watchdog"]["trips"], 0)
        self.assertEqual(health_runtime.flight_stats()["cap"], 32, "config survives")


class TestSLO(HealthCase):
    @unittest.skipUnless(fusion.active(), "flight events ride the fused dispatch/sync seams")
    def test_breach_counts_and_ring_event(self):
        prev = health_runtime.set_slo(sync_ms=0.0001)
        try:
            self._run_chain()
            slo = health_runtime.health_block()["slo"]["sync"]
            self.assertGreaterEqual(slo["breaches_total"], 1)
            self.assertIsNotNone(slo["limit_ms"])
            kinds = {ev.get("kind") for ev in health_runtime.flight_events()}
            self.assertIn("slo_breach", kinds)
        finally:
            health_runtime.set_slo(
                sync_ms=None if prev["sync"] is None else prev["sync"] * 1e3
            )

    @unittest.skipUnless(fusion.active(), "flight events ride the fused dispatch/sync seams")
    def test_healthy_slo_reports_ok_ratio(self):
        prev = health_runtime.set_slo(sync_ms=60000.0)
        try:
            self._run_chain()
            slo = health_runtime.health_block()["slo"]["sync"]
            self.assertEqual(slo.get("window_breaches", 0), 0)
            self.assertEqual(slo.get("ok_ratio", 1.0), 1.0)
        finally:
            health_runtime.set_slo(
                sync_ms=None if prev["sync"] is None else prev["sync"] * 1e3
            )


class TestContracts(HealthCase):
    @unittest.skipUnless(fusion.active(), "fusion disabled via HEAT_TPU_FUSION")
    def test_health_surfaces_never_force_a_pending_chain(self):
        a = self._split_input(9)
        pending = a * 0.5 + 2.0
        self.assertTrue(fusion.is_deferred(pending))
        health_runtime.flight_stats()
        health_runtime.health_block(global_view=True)
        telemetry.report()
        self.assertTrue(
            fusion.is_deferred(pending), "health reads must not force the DAG"
        )
        self.assert_array_equal(
            pending, np.asarray(a.larray) * 0.5 + 2.0
        )

    def test_health_layer_never_initializes_the_backend(self):
        # a fresh interpreter arms flight + watchdog + SLO, reads every
        # health surface, and the lazy mesh singletons must stay untouched
        code = (
            "from heat_tpu.core import health_runtime as hr\n"
            "from heat_tpu.core import telemetry, communication\n"
            "hr.set_watchdog(deadline_ms=1000, policy='warn', enabled=True)\n"
            "hr.set_slo(sync_ms=5.0)\n"
            "hr.flight_stats(); hr.health_block(global_view=True)\n"
            "hr.watchdog_stats(); hr.stalls()\n"
            "telemetry.report()\n"
            "assert communication.MESH_WORLD is None, 'backend was initialized'\n"
            "print('OK')\n"
        )
        env = dict(os.environ)
        env.setdefault("JAX_PLATFORMS", "cpu")
        env["HEAT_TPU_FLIGHT"] = "1"
        env["HEAT_TPU_TELEMETRY"] = "1"
        out = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True, text=True, env=env, cwd=_REPO,
        )
        self.assertEqual(out.returncode, 0, out.stderr)
        self.assertIn("OK", out.stdout)


class TestHealthCLI(HealthCase):
    def _cli(self):
        return importlib.import_module("heat_tpu.telemetry")

    @unittest.skipUnless(fusion.active(), "flight events ride the fused dispatch/sync seams")
    def test_health_verb_renders_a_bundle(self):
        self._run_chain()
        dump = health_runtime.dump_flight(reason="manual")
        out = io.StringIO()
        rc = self._cli().main(["health", dump["path"]], out=out)
        self.assertEqual(rc, 0)
        text = out.getvalue()
        self.assertIn("watchdog", text)
        self.assertIn("flight", text)

    @unittest.skipUnless(fusion.active(), "flight events ride the fused dispatch/sync seams")
    def test_health_verb_live_json(self):
        self._run_chain()
        out = io.StringIO()
        rc = self._cli().main(["health", "--json"], out=out)
        self.assertEqual(rc, 0)
        doc = json.loads(out.getvalue())
        self.assertIn("sync", doc["health"])
        self.assertIn("watchdog", doc["health"])
        self.assertGreaterEqual(doc["health"]["sync"]["*"]["count"], 1)


if __name__ == "__main__":
    unittest.main()
