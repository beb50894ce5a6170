"""Multi-tenant serving layer (ISSUE 15): sessions with isolation, the
persistent program cache, admission control and cross-session batching.

Pins the acceptance criteria: concurrent client threads in
:class:`ht.serving.Session` scopes never bleed telemetry counters, errstate
policy, numlens sampling or quarantine state into each other; a populated
``HEAT_TPU_PROGRAM_CACHE_DIR`` warm-starts a fresh process with ZERO
recompiles for previously-seen signatures (``disk_hits``, asserted
in-process and across two real subprocesses); the admission token bucket
composes with memledger's headroom gate and the elastic ``admission_hold``
(a refused chain stays pending, forces after release, and is never degraded
or double-dispatched); and N=8 threaded synthetic clients on the warm mesh
complete every round with zero steady-state retraces (no latency is pinned
on the CPU: a p99 is a chip number). Runs green at mesh 1/3/8, with fusion
off (dispatch-seam tests skip), and under ``HEAT_TPU_FAULTS=ci`` (setUp suspends the
ambient mix so exact counts stay exact).
"""

import io
import json
import os
import subprocess
import sys
import tempfile
import threading
import time
import unittest
import warnings

import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu.core import fusion, memledger, numlens, resilience, serving, telemetry

from harness import TestCase


class ServingCase(TestCase):
    """Clean serving/fusion/telemetry state, exact under the CI fault mix."""

    def setUp(self):
        self._suspend = resilience.suspended()
        self._suspend.__enter__()
        fusion.clear_cache()
        telemetry.reset()
        memledger.reset()
        self._prev_budget = memledger.set_budget(None)
        self._prev_policy = serving._POLICY
        serving.set_admission(None)
        serving.disarm_cache()

    def tearDown(self):
        serving.set_admission(None, policy=self._prev_policy)
        serving.disarm_cache()
        memledger.set_budget(self._prev_budget[0], self._prev_budget[1])
        memledger.reset()
        telemetry.reset()
        self._suspend.__exit__(None, None, None)

    def _client_input(self, seed=0):
        n = 4 * self.get_size()
        return ht.array(
            np.random.default_rng(seed).standard_normal(n).astype(np.float32),
            split=0,
        )


# ----------------------------------------------------------------------
# satellite: thread-safe telemetry scopes
# ----------------------------------------------------------------------
class TestScopeThreadIsolation(ServingCase):
    def test_two_thread_scope_isolation(self):
        """Two threads in two scopes: each archive holds only its own
        counts, the global rollup holds both (the satellite pin)."""
        prev = telemetry.set_mode(1)
        try:
            telemetry.reset()
            barrier = threading.Barrier(2)
            errors = []

            def worker(name, n):
                try:
                    with telemetry.scope(name):
                        barrier.wait(timeout=10)
                        for _ in range(n):
                            telemetry.record_async_dispatch(1)
                except Exception as exc:  # surface thread failures
                    errors.append(exc)

            t1 = threading.Thread(target=worker, args=("tenant-a", 3))
            t2 = threading.Thread(target=worker, args=("tenant-b", 5))
            t1.start(); t2.start(); t1.join(); t2.join()
            self.assertEqual(errors, [])
            scopes = telemetry.scope_reports()
            self.assertEqual(scopes["tenant-a"]["async_forcing"]["dispatches"], 3)
            self.assertEqual(scopes["tenant-b"]["async_forcing"]["dispatches"], 5)
            self.assertEqual(telemetry.report()["async_forcing"]["dispatches"], 8)
        finally:
            telemetry.set_mode(prev)

    def test_scope_stack_is_thread_local(self):
        """A scope entered on one thread is invisible to another thread's
        innermost-scope resolution."""
        prev = telemetry.set_mode(1)
        try:
            telemetry.reset()
            inner_seen = []
            entered = threading.Event()
            release = threading.Event()

            def holder():
                with telemetry.scope("held"):
                    entered.set()
                    release.wait(timeout=10)

            t = threading.Thread(target=holder)
            t.start()
            self.assertTrue(entered.wait(timeout=10))
            # this thread has no scope: dispatches land on the global only
            telemetry.record_async_dispatch(1)
            inner_seen.append(telemetry._cur() is telemetry._GLOBAL)
            release.set()
            t.join()
            self.assertTrue(inner_seen[0])
            self.assertEqual(
                telemetry.scope_reports()["held"]["async_forcing"]["dispatches"], 0
            )
            self.assertEqual(telemetry.report()["async_forcing"]["dispatches"], 1)
        finally:
            telemetry.set_mode(prev)


# ----------------------------------------------------------------------
# session isolation
# ----------------------------------------------------------------------
class TestSessionIsolation(ServingCase):
    @pytest.mark.skipif(not fusion.active(), reason="fusion disabled")
    def test_per_session_billing(self):
        with serving.Session("alice") as alice:
            a = self._client_input(1)
            self.assertAlmostEqual(
                float(ht.sum(a * 2.0)), float(2.0 * np.sum(a.numpy())), places=3
            )
        with serving.Session("bob") as bob:
            b = self._client_input(2)
            float(ht.sum(b * 2.0))
            float(ht.mean(b + 1.0))
        self.assertEqual(alice.report()["stats"]["dispatches"], 1)
        self.assertGreaterEqual(bob.report()["stats"]["dispatches"], 2)
        names = [s["name"] for s in serving.sessions_block()["sessions"]]
        self.assertEqual(names, ["alice", "bob"])

    @pytest.mark.skipif(not fusion.active(), reason="fusion disabled")
    def test_errstate_isolated_between_threads(self):
        """Session A under errstate='raise' sees NonFiniteError for an inf
        chain; a CONCURRENT session B (inherit=ignore) computes the same
        chain untroubled — the thread-local override never leaks."""
        barrier = threading.Barrier(2)
        results = {}

        def strict():
            try:
                with serving.Session("strict", errstate="raise"):
                    barrier.wait(timeout=10)
                    z = ht.array(np.zeros(4 * self.get_size(), np.float32), split=0)
                    results["strict"] = float(ht.sum(ht.log(z)))
            except resilience.NonFiniteError:
                results["strict"] = "raised"
            except Exception as exc:
                results["strict"] = exc

        def lax():
            try:
                with serving.Session("lax"):
                    barrier.wait(timeout=10)
                    z = ht.array(np.zeros(4 * self.get_size(), np.float32), split=0)
                    results["lax"] = float(ht.sum(ht.log(z)))
            except Exception as exc:
                results["lax"] = exc

        t1 = threading.Thread(target=strict)
        t2 = threading.Thread(target=lax)
        t1.start(); t2.start(); t1.join(); t2.join()
        self.assertEqual(results["strict"], "raised")
        self.assertEqual(results["lax"], float("-inf"))

    @pytest.mark.skipif(not fusion.active(), reason="fusion disabled")
    def test_numlens_sampling_is_per_session(self):
        """A session in 'full' mode samples its own dispatches while the
        global lens stays off — and sampling stops at session exit."""
        self.assertEqual(numlens.mode(), "off")
        before = numlens.sampling_stats()["dispatches_sampled"]
        with serving.Session("sampled", numlens="full"):
            a = self._client_input(3)
            float(ht.sum(a * 3.0))
        inside = numlens.sampling_stats()["dispatches_sampled"]
        self.assertGreater(inside, before)
        b = self._client_input(4)
        float(ht.sum(b * 5.0))
        self.assertEqual(numlens.sampling_stats()["dispatches_sampled"], inside)

    @pytest.mark.skipif(not fusion.active(), reason="fusion disabled")
    def test_quarantine_view_contained_per_session(self):
        """A compile fault degrading session A's chain lands in A's
        quarantine view ONLY — B's view stays clean (containment)."""
        with serving.Session("victim") as victim:
            with resilience.inject("fusion.compile", times=1):
                with warnings.catch_warnings():
                    warnings.simplefilter("ignore")
                    a = self._client_input(5)
                    val = float(ht.sum(a * 7.0 - 2.0))
            self.assertAlmostEqual(
                val, float(np.sum(a.numpy() * 7.0 - 2.0)), places=2
            )
        with serving.Session("neighbor") as neighbor:
            # a structurally DIFFERENT chain: the quarantine ledger is
            # global by design (the bad program is bad for everyone) but
            # the incident VIEW is per-session
            b = self._client_input(6)
            float(ht.sum(b + 3.0))
        self.assertEqual(victim.report()["stats"]["degraded"], 1)
        self.assertTrue(victim.quarantined_programs())
        self.assertEqual(neighbor.report()["stats"]["degraded"], 0)
        self.assertEqual(neighbor.quarantined_programs(), [])


# ----------------------------------------------------------------------
# persistent program cache
# ----------------------------------------------------------------------
class TestPersistentCache(ServingCase):
    @pytest.mark.skipif(not fusion.active(), reason="fusion disabled")
    def test_disk_index_warm_start_in_process(self):
        """Re-forcing a previously-seen signature after clear_cache records
        a disk hit, not a compile — the warm-start accounting."""
        with tempfile.TemporaryDirectory() as d:
            serving.arm_cache(d)
            a = self._client_input(7)
            expect = float(np.sum(a.numpy() * 2.0 + 1.0))
            self.assertAlmostEqual(float(ht.sum(a * 2.0 + 1.0)), expect, places=3)
            st = serving.cache_stats()
            self.assertGreaterEqual(st["compiles"], 1)
            self.assertGreaterEqual(st["index_keys"], 1)
            fusion.clear_cache()  # simulate the fresh process
            a2 = self._client_input(7)
            self.assertAlmostEqual(float(ht.sum(a2 * 2.0 + 1.0)), expect, places=3)
            st = serving.cache_stats()
            self.assertEqual(st["compiles"], 0, "warm start must not recompile")
            self.assertGreaterEqual(st["disk_hits"], 1)
            self.assertEqual(st["misses"], st["compiles"] + st["disk_hits"])

    @pytest.mark.skipif(not fusion.active(), reason="fusion disabled")
    def test_disk_warm_start_not_billed_as_session_compile(self):
        """Session `compiles` agrees with the global retrace counter: a
        disk warm-start is a `disk_hit`, not a billed compile — a
        warm-started process must bill sessions zero compiles while
        `cache_stats()["compiles"]` stays 0."""
        with tempfile.TemporaryDirectory() as d:
            serving.arm_cache(d)
            a = self._client_input(22)
            expect = float(np.sum(a.numpy() * 5.0))
            with serving.Session("first") as s1:
                self.assertAlmostEqual(float(ht.sum(a * 5.0)), expect, places=3)
            self.assertGreaterEqual(s1.stats["compiles"], 1)
            fusion.clear_cache()  # fresh process: programs gone, index stays
            a2 = self._client_input(22)
            with serving.Session("second") as s2:
                self.assertAlmostEqual(float(ht.sum(a2 * 5.0)), expect, places=3)
            self.assertGreaterEqual(s2.stats["dispatches"], 1)
            self.assertEqual(
                s2.stats["compiles"], 0,
                "disk warm-start billed as a session compile",
            )
            self.assertEqual(serving.cache_stats()["compiles"], 0)

    @pytest.mark.skipif(not fusion.active(), reason="fusion disabled")
    def test_warmup_prebakes_and_seeds(self):
        with tempfile.TemporaryDirectory() as d:
            serving.arm_cache(d)
            a = self._client_input(8)
            r = serving.warmup([lambda: ht.sum(a * 4.0), "feedfacefeedface"])
            self.assertEqual(r["warmed"], 1)
            self.assertEqual(r["seeded"], 1)
            self.assertGreaterEqual(r["compiles"], 1)
            fusion.clear_cache()
            r2 = serving.warmup([lambda: ht.sum(a * 4.0)])
            self.assertEqual(r2["compiles"], 0)
            self.assertGreaterEqual(r2["disk_hits"], 1)

    def test_malformed_cache_dir_warns_and_disarms(self):
        """A file-where-a-dir-should-be warns and disarms instead of
        raising — the HEAT_TPU_MEMORY_BUDGET env-knob convention."""
        with tempfile.NamedTemporaryFile() as f:
            prev = os.environ.get("HEAT_TPU_PROGRAM_CACHE_DIR")
            os.environ["HEAT_TPU_PROGRAM_CACHE_DIR"] = f.name
            try:
                with self.assertWarns(UserWarning):
                    self.assertIsNone(serving._parse_env_cache_dir())
            finally:
                if prev is None:
                    del os.environ["HEAT_TPU_PROGRAM_CACHE_DIR"]
                else:
                    os.environ["HEAT_TPU_PROGRAM_CACHE_DIR"] = prev

    def test_corrupt_index_entries_skipped_with_one_warning(self):
        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "programs.jsonl")
            with open(path, "w") as fh:
                fh.write('{"key": "aaaabbbbccccdddd", "family": "sum"}\n')
                fh.write("{not json at all\n")
                fh.write('{"nokey": true}\n')
                fh.write('{"key": "1111222233334444", "family": "mean"}\n')
            with warnings.catch_warnings(record=True) as caught:
                warnings.simplefilter("always")
                info = serving.arm_cache(d)
            self.assertEqual(info["index_keys"], 2)
            self.assertEqual(info["skipped"], 2)
            index_warnings = [
                w for w in caught if "persistent program index" in str(w.message)
            ]
            self.assertEqual(len(index_warnings), 1, "the warning is one-shot")

    def test_cold_then_warm_across_processes(self):
        """The two-process pin: a second process against the populated
        cache dir records ZERO compiles for the warmed signatures."""
        script = (
            "import numpy as np, heat_tpu as ht\n"
            "from heat_tpu.core import serving\n"
            "a = ht.array(np.arange(32, dtype=np.float32), split=0)\n"
            "b = ht.array(np.ones(32, dtype=np.float32), split=0)\n"
            "assert abs(float(ht.sum(a * 2.0 + b)) - float((np.arange(32) * 2.0 + 1).sum())) < 1e-3\n"
            "float(ht.mean(a - b))\n"
            "st = serving.cache_stats()\n"
            "import json; print('STATS ' + json.dumps("
            "{'compiles': st['compiles'], 'disk_hits': st['disk_hits'],"
            " 'index_keys': st['index_keys']}))\n"
        )
        with tempfile.TemporaryDirectory() as d:
            env = dict(os.environ)
            env["HEAT_TPU_PROGRAM_CACHE_DIR"] = d
            env["JAX_PLATFORMS"] = "cpu"
            # the ambient matrix legs must not leak into the fixture
            # processes: fused dispatch on, faults/lens/budget off
            for knob in ("HEAT_TPU_FUSION", "HEAT_TPU_FAULTS", "HEAT_TPU_NUMLENS",
                         "HEAT_TPU_MEMORY_BUDGET", "HEAT_TPU_TELEMETRY"):
                env.pop(knob, None)
            runs = []
            for label in ("cold", "warm"):
                proc = subprocess.run(
                    [sys.executable, "-c", script],
                    env=env, capture_output=True, text=True, timeout=240,
                )
                self.assertEqual(
                    proc.returncode, 0,
                    f"{label} run failed:\n{proc.stdout}\n{proc.stderr}",
                )
                line = [l for l in proc.stdout.splitlines() if l.startswith("STATS ")]
                self.assertTrue(line, f"{label} run printed no stats: {proc.stdout}")
                runs.append(json.loads(line[-1][len("STATS "):]))
            cold, warm = runs
            self.assertGreaterEqual(cold["compiles"], 1)
            self.assertEqual(cold["disk_hits"], 0)
            self.assertEqual(warm["compiles"], 0,
                             f"warm start recompiled: {warm}")
            self.assertGreaterEqual(warm["disk_hits"], 1)
            self.assertGreaterEqual(warm["index_keys"], cold["compiles"])


class TestCompileCachePlacement(unittest.TestCase):
    """Where XLA's persistent compile cache lives: wherever
    ``JAX_COMPILATION_CACHE_DIR`` says, untouched by every code path; else,
    for entry points only, one fixed git-ignored path inside the checkout."""

    _REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def _run(self, script, cwd, **env_overrides):
        env = {
            k: v for k, v in os.environ.items()
            if k not in ("JAX_COMPILATION_CACHE_DIR", "HEAT_TPU_PROGRAM_CACHE_DIR")
        }
        env.update(env_overrides, JAX_PLATFORMS="cpu")
        env["PYTHONPATH"] = self._REPO + os.pathsep + env.get("PYTHONPATH", "")
        proc = subprocess.run(
            [sys.executable, "-c", script], env=env, cwd=cwd,
            capture_output=True, text=True, timeout=240,
        )
        self.assertEqual(proc.returncode, 0, f"{proc.stdout}\n{proc.stderr}")
        return json.loads(proc.stdout.strip().splitlines()[-1])

    def test_env_dir_survives_every_arming_path(self):
        script = (
            "import json, sys, jax, heat_tpu\n"
            "from heat_tpu.core import serving\n"
            "seen = {'import': jax.config.jax_compilation_cache_dir}\n"
            "serving.disarm_cache()\n"
            "seen['disarm_import'] = jax.config.jax_compilation_cache_dir\n"
            "serving.arm_cache(sys.argv[1])\n"
            "seen['arm'] = jax.config.jax_compilation_cache_dir\n"
            "serving.disarm_cache()\n"
            "seen['disarm'] = jax.config.jax_compilation_cache_dir\n"
            "seen['entry'] = serving.use_entry_point_compile_cache()\n"
            "seen['entry_config'] = jax.config.jax_compilation_cache_dir\n"
            "print(json.dumps(seen))\n"
        )
        with tempfile.TemporaryDirectory() as d:
            xla, armed, other = (os.path.join(d, n) for n in ("xla-env", "armed", "other"))
            seen = self._run(
                script.replace("sys.argv[1]", repr(other)), d,
                JAX_COMPILATION_CACHE_DIR=xla, HEAT_TPU_PROGRAM_CACHE_DIR=armed,
            )
            self.assertEqual(set(seen.values()), {xla}, seen)
            # the program-key index may live where it likes
            self.assertTrue(os.path.isdir(armed) and os.path.isdir(other))

    def test_entry_point_default_is_one_fixed_path_in_the_checkout(self):
        want = os.path.join(self._REPO, ".jax_cache")
        self.assertEqual(serving.DEFAULT_COMPILE_CACHE_DIR, want)
        script = (
            "import json, jax, heat_tpu\n"
            "from heat_tpu.core import serving\n"
            "before = jax.config.jax_compilation_cache_dir\n"
            "print(json.dumps([before, serving.use_entry_point_compile_cache(),"
            " jax.config.jax_compilation_cache_dir]))\n"
        )
        with tempfile.TemporaryDirectory() as elsewhere:
            for cwd in (elsewhere, self._REPO):
                before, returned, configured = self._run(script, cwd)
                self.assertIsNone(before, "importing heat_tpu must arm no cache")
                self.assertEqual((returned, configured), (want, want))


# ----------------------------------------------------------------------
# admission control + gate composition
# ----------------------------------------------------------------------
class TestAdmission(ServingCase):
    @pytest.mark.skipif(not fusion.active(), reason="fusion disabled")
    def test_raise_policy_names_session_and_bucket(self):
        with serving.Session("limited", admission_rate=0.5, admission_burst=1,
                             policy="raise") as sess:
            a = self._client_input(9)
            float(ht.sum(a * 2.0))  # spends the single burst token
            pending = ht.sum(a * 3.0)
            with self.assertRaises(serving.AdmissionError) as ctx:
                float(pending)
            self.assertIn("limited", str(ctx.exception))
            self.assertIn("session:limited", str(ctx.exception))
            # the refused chain is intact: pending, never degraded
            self.assertTrue(fusion.is_deferred(pending))
            self.assertEqual(fusion.cache_stats()["degraded"], 0)
            self.assertEqual(sess.stats["admission_refused"], 1)
            # after refill it dispatches normally — same chain, no rewalk
            time.sleep(2.1)
            self.assertAlmostEqual(
                float(pending), float(np.sum(a.numpy() * 3.0)), places=3
            )

    @pytest.mark.skipif(not fusion.active(), reason="fusion disabled")
    def test_wait_policy_blocks_until_refill(self):
        # 0.5s per token: even a slow first dispatch (compile) cannot
        # refill the bucket before the second one arrives
        with serving.Session("patient", admission_rate=2, admission_burst=1) as sess:
            a = self._client_input(10)
            float(ht.sum(a * 2.0))
            t0 = time.perf_counter()
            self.assertAlmostEqual(
                float(ht.sum(a * 3.0)), float(np.sum(a.numpy() * 3.0)), places=3
            )
            waited = time.perf_counter() - t0
        self.assertGreaterEqual(sess.stats["admission_waits"], 1)
        self.assertGreater(waited, 0.05)  # the refill was actually slept

    @pytest.mark.skipif(not fusion.active(), reason="fusion disabled")
    def test_wait_does_not_convoy_neighbor_sessions(self):
        """The containment contract under `wait`: the refill sleep happens
        BEFORE the force lock, so a rate-limited tenant blocked on tokens
        stalls only its own thread — a neighbour session's dispatches run
        to completion well inside the limited tenant's ~2s refill wait."""
        fast_done = threading.Event()
        limited_still_waiting = []
        errors = []

        def limited():
            try:
                with serving.Session("slowpoke", admission_rate=0.5,
                                     admission_burst=1):
                    a = self._client_input(20)
                    float(ht.sum(a * 2.0))  # spends the only token
                    float(ht.sum(a * 3.0))  # sleeps ~2s for the refill
            except Exception as exc:  # surface thread failures
                errors.append(exc)

        def unlimited():
            try:
                with serving.Session("neighbor"):
                    b = self._client_input(21)
                    for k in range(4, 9):
                        float(ht.sum(b * float(k)))
                    limited_still_waiting.append(t1.is_alive())
            except Exception as exc:
                errors.append(exc)
            finally:
                fast_done.set()

        t1 = threading.Thread(target=limited)
        t2 = threading.Thread(target=unlimited)
        t1.start()
        time.sleep(0.3)  # let the limited tenant reach its refill sleep
        t2.start()
        self.assertTrue(fast_done.wait(timeout=60))
        t1.join(timeout=60)
        t2.join(timeout=60)
        self.assertEqual(errors, [])
        # convoyed, the neighbour's first dispatch would sit behind the force
        # lock until the limited tenant's refill ended and it had dispatched
        self.assertEqual(
            limited_still_waiting, [True],
            "neighbour's dispatches convoyed behind the limited tenant's "
            "admission wait",
        )

    @pytest.mark.skipif(not fusion.active(), reason="fusion disabled")
    def test_global_bucket_gates_outside_sessions(self):
        serving.set_admission(0.5, 1, policy="raise")
        a = self._client_input(11)
        float(ht.sum(a * 2.0))
        with self.assertRaises(serving.AdmissionError) as ctx:
            float(ht.sum(a * 3.0))
        self.assertIn("global", str(ctx.exception))

    @pytest.mark.skipif(not fusion.active(), reason="fusion disabled")
    def test_set_admission_hot_update_preserves_counters(self):
        """The ISSUE 18 satellite pin: re-tuning a live bucket's rate/burst
        mid-traffic reconfigures it IN PLACE — the refused/waited_s billing
        counters survive and accumulated tokens clamp to the new burst,
        instead of the old rebuild-and-forget."""
        serving.set_admission(0.5, 1, policy="raise")
        bucket = serving._GLOBAL_BUCKET
        a = self._client_input(16)
        float(ht.sum(a * 2.0))  # spends the only token
        with self.assertRaises(serving.AdmissionError):
            float(ht.sum(a * 3.0))
        self.assertEqual(bucket.refused, 1)
        serving.set_admission(100, 8, policy="raise")
        # same object, counters intact, config live
        self.assertIs(serving._GLOBAL_BUCKET, bucket)
        self.assertEqual(bucket.refused, 1)
        self.assertGreaterEqual(bucket.admitted, 1)
        self.assertEqual(bucket.rate, 100.0)
        self.assertEqual(bucket.burst, 8.0)
        # the empty bucket stayed empty through the upgrade (no fresh-bucket
        # grace burst) — it refuses until the NEW rate actually refills it
        with self.assertRaises(serving.AdmissionError):
            float(ht.sum(a * 4.0))
        time.sleep(0.05)  # 100/s refill: ~5 tokens
        float(ht.sum(a * 4.0))
        # clamping down: accumulated tokens never exceed the new burst
        time.sleep(0.05)  # refill toward burst=8 at 100/s
        serving.set_admission(100, 2, policy="raise")
        self.assertIs(serving._GLOBAL_BUCKET, bucket)
        with bucket._lock:
            self.assertLessEqual(bucket.tokens, 2.0)


class TestGateComposition(ServingCase):
    """Admission token bucket x memledger headroom x elastic hold: a chain
    refused by ANY gate stays pending, forces after release, and is never
    degraded or double-dispatched."""

    @pytest.mark.skipif(not fusion.active(), reason="fusion disabled")
    def test_memledger_refusal_contained_then_released(self):
        prev_mode = telemetry.set_mode(1)
        try:
            with serving.Session("tight") as sess:
                a = self._client_input(12)
                memledger.set_budget(1, "raise")  # one byte: everything refused
                pending = ht.sum(a * 6.0)
                with self.assertRaises(memledger.MemoryBudgetExceeded):
                    float(pending)
                self.assertTrue(fusion.is_deferred(pending))
                self.assertEqual(fusion.cache_stats()["degraded"], 0)
                self.assertEqual(sess.stats["mem_refused"], 1)
                memledger.set_budget(None)  # release: the SAME chain forces
                self.assertAlmostEqual(
                    float(pending), float(np.sum(a.numpy() * 6.0)), places=3
                )
                # exactly one dispatch of that program: refused attempt + retry
                # did not double-dispatch (the compile happened once, pre-gate)
                self.assertEqual(
                    telemetry.report()["async_forcing"]["dispatches"], 1
                )
        finally:
            telemetry.set_mode(prev_mode)

    @pytest.mark.skipif(not fusion.active(), reason="fusion disabled")
    def test_elastic_hold_composes_with_session_gates(self):
        with serving.Session("held", admission_rate=1000, admission_burst=8):
            a = self._client_input(13)
            pending = ht.sum(a * 8.0)
            with memledger.admission_hold("reform"):
                with self.assertRaises(memledger.MemoryBudgetExceeded) as ctx:
                    float(pending)
                self.assertIn("reform", str(ctx.exception))
            self.assertTrue(fusion.is_deferred(pending))
            self.assertEqual(fusion.cache_stats()["degraded"], 0)
            self.assertAlmostEqual(
                float(pending), float(np.sum(a.numpy() * 8.0)), places=3
            )

    @pytest.mark.skipif(not fusion.active(), reason="fusion disabled")
    def test_refused_chain_absorbed_by_neighbor_batch_not_redispatched(self):
        """The PR 8 drain-exclusion pin, extended to the serving gate: a
        chain refused at the admission gate stays in the live-root registry;
        a LATER force may batch it (it was never dispatched), and the
        original read then finds the value installed — never two
        dispatches of the same root."""
        prev_mode = telemetry.set_mode(1)
        try:
            serving.set_admission(0.2, 1, policy="raise")
            with serving.Session("bursty"):
                a = self._client_input(14)
                big_n = 8192 * self.get_size()  # > _BATCH_BYTES: no batching
                big = ht.array(np.ones(big_n, np.float32), split=0)
                float(ht.sum(big * 2.0))  # spends the only token
                pending = ht.sum(a * 9.0)  # small root
                with self.assertRaises(serving.AdmissionError):
                    float(pending)
                self.assertTrue(fusion.is_deferred(pending))
                serving.set_admission(None)  # gate released
                # a neighbor's force batches the still-pending refused root
                other = self._client_input(15)
                float(ht.sum(other * 9.0))
                dispatches = telemetry.report()["async_forcing"]
                self.assertGreaterEqual(dispatches["multi_root_batches"], 1)
                # the refused root's value is already installed: reading it
                # adds NO dispatch
                before = telemetry.report()["async_forcing"]["dispatches"]
                self.assertAlmostEqual(
                    float(pending), float(np.sum(a.numpy() * 9.0)), places=3
                )
                self.assertEqual(
                    telemetry.report()["async_forcing"]["dispatches"], before
                )
        finally:
            telemetry.set_mode(prev_mode)

    @pytest.mark.skipif(not fusion.active(), reason="fusion disabled")
    def test_shed_tier_chain_dispatches_cleanly_after_recovery(self):
        """ISSUE 18 tier-flip composition: a batch-tier chain refused
        mid-overload (ShedError) stays pending and never degraded; once
        the controller lifts shedding the SAME chain force-dispatches
        exactly once, while interactive traffic was never gated at all."""
        prev_mode = telemetry.set_mode(1)
        try:
            serving.shed(("batch",))
            with serving.Session("bg", tier="preemptible") as bg:  # alias
                a = self._client_input(17)
                pending = ht.sum(a * 4.0)
                with self.assertRaises(serving.ShedError) as ctx:
                    float(pending)
                self.assertIn("bg", str(ctx.exception))
                self.assertTrue(fusion.is_deferred(pending))
                self.assertEqual(fusion.cache_stats()["degraded"], 0)
                self.assertEqual(bg.stats["shed"], 1)
                # interactive neighbour keeps dispatching mid-overload
                with serving.Session("fg", tier="interactive"):
                    b = self._client_input(18)
                    float(ht.sum(b * 5.0))
                before = telemetry.report()["async_forcing"]["dispatches"]
                serving.shed(())  # recovery: shedding lifts
                self.assertAlmostEqual(
                    float(pending), float(np.sum(a.numpy() * 4.0)), places=3
                )
                self.assertEqual(
                    telemetry.report()["async_forcing"]["dispatches"],
                    before + 1,
                )
        finally:
            serving.shed(())
            telemetry.set_mode(prev_mode)

    @pytest.mark.skipif(not fusion.active(), reason="fusion disabled")
    def test_shed_tier_chain_absorbed_by_neighbor_batch(self):
        """Shed-refusal composes with the drain-exclusion contract exactly
        like an admission refusal: after shedding lifts, a neighbour's
        force may absorb the still-pending batch-tier root into its batch
        — reading it then adds NO dispatch (never double-dispatched)."""
        prev_mode = telemetry.set_mode(1)
        try:
            serving.shed(("batch",))
            with serving.Session("bursty-batch", tier="batch"):
                a = self._client_input(19)
                pending = ht.sum(a * 9.0)
                with self.assertRaises(serving.ShedError):
                    float(pending)
                self.assertTrue(fusion.is_deferred(pending))
                serving.shed(())  # overload over
                other = self._client_input(15)
                float(ht.sum(other * 9.0))  # same program family: batches
                self.assertGreaterEqual(
                    telemetry.report()["async_forcing"]["multi_root_batches"],
                    1,
                )
                before = telemetry.report()["async_forcing"]["dispatches"]
                self.assertAlmostEqual(
                    float(pending), float(np.sum(a.numpy() * 9.0)), places=3
                )
                self.assertEqual(
                    telemetry.report()["async_forcing"]["dispatches"], before
                )
        finally:
            serving.shed(())
            telemetry.set_mode(prev_mode)


class TestConcurrentRootRegistration(ServingCase):
    @pytest.mark.skipif(not fusion.active(), reason="fusion disabled")
    def test_register_root_during_force_never_crashes(self):
        """The batch window invites other threads to register roots WHILE a
        force iterates the live-root registry — the registry key snapshot
        is taken under ``fusion._ROOTS_LOCK`` so concurrent inserts can
        never raise "dictionary changed size during iteration" mid-force."""
        errors = []
        stop = threading.Event()

        def forcer():
            try:
                with serving.Session("forcer"):
                    a = self._client_input(30)
                    for _ in range(25):
                        float(ht.sum(a * 2.0))
            except Exception as exc:
                errors.append(exc)
            finally:
                stop.set()

        def registrar():
            try:
                with serving.Session("registrar"):
                    b = self._client_input(31)
                    pending = []
                    while not stop.is_set():
                        # each product is a deferred root: register_root
                        # fires on this thread with no force lock held
                        pending.append(b * 1.5)
                        if len(pending) > 256:
                            pending.clear()
            except Exception as exc:
                errors.append(exc)

        t1 = threading.Thread(target=forcer)
        t2 = threading.Thread(target=registrar)
        t1.start(); t2.start()
        t1.join(timeout=60); t2.join(timeout=60)
        self.assertEqual(errors, [])


# ----------------------------------------------------------------------
# N=8 synthetic clients: zero steady-state retraces
# ----------------------------------------------------------------------
class TestServingThroughput(ServingCase):
    ROUNDS = 40

    def _client_chain(self, arr, k):
        # Single code object shared by prebake and the measured clients: the
        # DAG walk dedups leaves by object identity, so two *literal* 1.0
        # scalars collapse into one shared leaf while a computed k does not —
        # building the chain anywhere else yields a different signature.
        return ht.sum(arr * k + 1.0)

    @pytest.mark.skipif(not fusion.active(), reason="fusion disabled")
    def test_n8_zero_steady_state_retraces(self):
        # pre-bake every batch-size signature 1..8: cross-session batching
        # groups k small identical-structure roots into one program whose
        # signature depends on k, so steady state must have them all cached
        for k in range(1, 9):
            outs = [
                self._client_chain(self._client_input(30 + j), 1.0 + j * 0.25)
                for j in range(k)
            ]
            for o in outs:
                float(o)
        # N=8 concurrent sessions, one thread each
        barrier = threading.Barrier(8)
        completed = [0] * 8
        errors = []
        compiles_before = fusion.cache_stats()["compiles"]

        def client(idx):
            try:
                with serving.Session(f"client{idx}"):
                    arr = self._client_input(40 + idx)
                    barrier.wait(timeout=30)
                    for i in range(self.ROUNDS):
                        float(self._client_chain(arr, 1.0 + i * 0.25))
                        completed[idx] += 1
            except Exception as exc:
                errors.append(exc)

        threads = [threading.Thread(target=client, args=(i,)) for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        self.assertEqual(errors, [])
        retraces = fusion.cache_stats()["compiles"] - compiles_before
        self.assertEqual(retraces, 0, "steady-state traffic must not retrace")
        self.assertEqual(sum(completed), 8 * self.ROUNDS)
        # no latency is asserted here: the p99 of a serving chain under load
        # is `serve_bursty_1c`'s, on the chip, under a bound (ROADMAP B-II.4)

    @pytest.mark.skipif(not fusion.active(), reason="fusion disabled")
    def test_cross_session_batch_bills_each_tenant(self):
        """Two sessions' pending roots ride ONE dispatch; the timeline event
        carries both session names and each tenant is billed its root."""
        prev_mode = telemetry.set_mode("verbose")
        try:
            telemetry.reset()
            with serving.Session("tenant-x") as sx:
                x = self._client_input(50)
                out_x = ht.sum(x * 11.0)  # pending small root, billed to x
            with serving.Session("tenant-y") as sy:
                y = self._client_input(51)
                # forcing y's root batches tenant-x's still-pending root
                self.assertAlmostEqual(
                    float(ht.sum(y * 11.0)),
                    float(np.sum(y.numpy() * 11.0)), places=3,
                )
            self.assertAlmostEqual(
                float(out_x), float(np.sum(x.numpy() * 11.0)), places=3
            )
            events = [
                ev for ev in telemetry.events()
                if ev.get("kind") == "dispatch" and ev.get("sessions")
            ]
            self.assertTrue(events, "no session-stamped dispatch event")
            stamped = set()
            for ev in events:
                stamped.update(s for s in ev["sessions"] if s)
            self.assertIn("tenant-x", stamped)
            self.assertIn("tenant-y", stamped)
            self.assertEqual(sx.report()["stats"]["roots"], 1)
            self.assertEqual(sy.report()["stats"]["roots"], 1)
        finally:
            telemetry.set_mode(prev_mode)


# ----------------------------------------------------------------------
# report + CLI surfaces
# ----------------------------------------------------------------------
class TestServingReport(ServingCase):
    @pytest.mark.skipif(not fusion.active(), reason="fusion disabled")
    def test_report_carries_serving_block(self):
        with serving.Session("reported"):
            a = self._client_input(60)
            float(ht.sum(a * 12.0))
        doc = telemetry.report()
        self.assertIn("serving", doc)
        names = [s["name"] for s in doc["serving"]["sessions"]]
        self.assertIn("reported", names)

    @pytest.mark.skipif(not fusion.active(), reason="fusion disabled")
    def test_cli_sessions_verb_live_and_from_file(self):
        import importlib

        # the package attribute `heat_tpu.telemetry` resolves to the CORE
        # module; the CLI shim is the SUBMODULE heat_tpu/telemetry.py
        cli = importlib.import_module("heat_tpu.telemetry")

        with serving.Session("cli-tenant"):
            a = self._client_input(61)
            float(ht.sum(a * 13.0))
        out = io.StringIO()
        self.assertEqual(cli.main(["sessions"], out=out), 0)
        self.assertIn("cli-tenant", out.getvalue())
        out = io.StringIO()
        self.assertEqual(cli.main(["sessions", "--json"], out=out), 0)
        doc = json.loads(out.getvalue())
        self.assertEqual(doc["source"], "<live>")
        self.assertIn(
            "cli-tenant", [s["name"] for s in doc["serving"]["sessions"]]
        )
        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "report.json")
            telemetry.report_json(path)
            out = io.StringIO()
            self.assertEqual(cli.main(["sessions", path, "--json"], out=out), 0)
            doc = json.loads(out.getvalue())
            self.assertEqual(doc["source"], path)
            self.assertIn(
                "cli-tenant", [s["name"] for s in doc["serving"]["sessions"]]
            )

    def test_sessions_block_without_traffic(self):
        blk = serving.sessions_block()
        self.assertEqual(blk["sessions"], [])
        self.assertEqual(blk["active"], 0)
        self.assertIsNone(blk["admission"]["global"])

    def test_duplicate_session_name_rejected(self):
        with serving.Session("dup"):
            with self.assertRaises(ValueError):
                serving.Session("dup").__enter__()


if __name__ == "__main__":
    unittest.main()
