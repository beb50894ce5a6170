"""Phase spans of the forcing path (ISSUE 27): ``heat.force`` with its five
children, ``heat.place`` and ``heat.read`` as ``TraceAnnotation``s on the
profiler's own clock, the same intervals as ``phase_*`` counters of
``fusion.cache_stats()``, one switch (``telemetry.tracing()``), and the
``python -m heat_tpu.telemetry gaps`` reading of a profiler trace. ISSUE 37:
the read's two children (``heat.read.ready``, ``heat.read.copy``), the ``copy``
phase of a fit and of a QR call, and the verb's one clock: the window of the
shift from the runtime's own events and the cut of every idle gap.

Nothing here asserts an absolute time: counters are held against the
durations of the spans they were taken beside.
"""

import gc
import glob
import importlib
import io
import json
import os
import sys
import tempfile
import threading
import unittest
import warnings
from unittest import mock

import jax
import numpy as np

import heat_tpu as ht
from heat_tpu.core import fusion, memledger, opsplane, resilience, serving, telemetry

from harness import TestCase

# ``heat_tpu.telemetry`` the attribute is core/telemetry.py; the CLI is the module
telemetry_cli = importlib.import_module("heat_tpu.telemetry")

FORCE_PHASES = ("admit", "walk", "lookup", "dispatch", "install")
PHASE_KEYS = (
    ["phase_forces", "phase_places", "phase_place_ns", "phase_reads", "phase_read_ns"]
    + ["phase_read_ready_ns", "phase_read_copy_ns"]
    + [f"phase_{name}_ns" for name in FORCE_PHASES]
)
COPY_KEYS = ["phase_read_ready_ns", "phase_read_copy_ns", "phase_kmeans_copy_ns", "phase_qr_copy_ns"]
TESTS = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(TESTS, "data")
SCAN_TRACE = os.path.join(os.path.dirname(TESTS), "chipbench", "tests", "data", "scan_3ops.xplane.pb")


def _delta(before, after):
    return {k: after[k] - before[k] for k in PHASE_KEYS + ["forces", "records"]}


def _profiled(directory):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(directory, profiler_options=options)


def _spans(directory):
    """Every ``heat.*`` / ``user.*`` host span of the recorded trace as
    ``(thread, name, start_ns, end_ns, stats)``, by start."""
    (path,) = glob.glob(os.path.join(directory, "plugins", "profile", "*", "*.xplane.pb"))
    out = []
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)  # nanobind's stats type
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(("heat.", "user.")):
                        out.append(
                            (line.name, e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
                        )
    return sorted(out, key=lambda sp: sp[2])


class PhaseCase(TestCase):
    """Clean fusion/telemetry/memory state, exact under the CI fault mix."""

    def setUp(self):
        self._suspend = resilience.suspended()
        self._suspend.__enter__()
        fusion.clear_cache()
        telemetry.reset()
        memledger.reset()

    def tearDown(self):
        memledger.reset()
        telemetry.reset()
        self._suspend.__exit__(None, None, None)

    def _input(self, seed=0):
        n = 4 * self.get_size()
        return ht.array(
            np.random.default_rng(seed).standard_normal((n, 3)).astype(np.float32), split=0
        )


class TestSwitch(PhaseCase):
    def test_is_enabled_exists_and_is_off_without_a_session(self):
        # the guard a JAX upgrade trips first: tracing() leans on TraceMe's
        self.assertTrue(callable(getattr(jax.profiler.TraceAnnotation, "is_enabled", None)))
        self.assertFalse(jax.profiler.TraceAnnotation.is_enabled())
        self.assertFalse(telemetry.tracing())
        with telemetry.enabled(1):
            self.assertTrue(telemetry.tracing())

    @unittest.skipUnless(fusion.active(), "fusion disabled via HEAT_TPU_FUSION")
    def test_off_leaves_every_phase_key_alone_and_counts_records(self):
        x = self._input()
        before = fusion.cache_stats()
        r = ht.sum(x * 2 + 1)
        pending = fusion.cache_stats()["records"] - before["records"]
        self.assertGreaterEqual(pending, 3)  # mul, add, sum (casts besides)
        value = float(r)
        (x * 3).larray.block_until_ready()
        got = _delta(before, fusion.cache_stats())
        self.assertTrue(np.isfinite(value))
        self.assertEqual({k: got[k] for k in PHASE_KEYS}, dict.fromkeys(PHASE_KEYS, 0))
        self.assertEqual(got["forces"], 2)
        self.assertGreater(got["records"], pending)

    @unittest.skipUnless(fusion.active(), "fusion disabled via HEAT_TPU_FUSION")
    def test_telemetry_on_without_a_profiler_counts_and_opens_no_span(self):
        x = self._input()
        before = fusion.cache_stats()
        with telemetry.enabled(1):
            float(ht.sum(x * 2 + 1))
            (x * 3).numpy()
        got = _delta(before, fusion.cache_stats())
        self.assertEqual((got["phase_forces"], got["phase_places"], got["phase_reads"]), (2, 2, 2))
        for name in FORCE_PHASES:
            self.assertGreater(got[f"phase_{name}_ns"], 0, name)
        self.assertGreater(got["phase_place_ns"], 0)
        self.assertGreater(got["phase_read_ns"], 0)
        samples = {
            (name, labels.get("phase")): value for name, labels, value in opsplane.collect()
        }
        self.assertEqual(samples[("heat_tpu_fusion_phase_forces_total", None)], 2.0)
        self.assertAlmostEqual(
            samples[("heat_tpu_fusion_phase_seconds_total", "dispatch")],
            fusion.cache_stats()["phase_dispatch_ns"] * 1e-9,
        )

    @unittest.skipUnless(fusion.active(), "fusion disabled via HEAT_TPU_FUSION")
    def test_a_raise_at_the_forcing_seam_still_closes_heat_place(self):
        y = ht.log(self._input() * 0.0 - 1.0)  # NaN throughout
        before = fusion.cache_stats()
        with telemetry.enabled(1), ht.errstate(nonfinite="raise"):
            with self.assertRaises(resilience.NonFiniteError):
                y.larray
        got = _delta(before, fusion.cache_stats())
        self.assertEqual((got["phase_forces"], got["phase_places"], got["phase_reads"]), (1, 1, 0))
        self.assertIsInstance(y._payload, fusion.LazyArray, "the raise must leave the wrapper unforced")

    def test_clear_cache_zeroes_the_new_counters(self):
        with telemetry.enabled(1):
            float(ht.sum(self._input() * 2))
        fusion.clear_cache()
        stats = fusion.cache_stats()
        self.assertEqual([stats[k] for k in PHASE_KEYS + ["records"]], [0] * (len(PHASE_KEYS) + 1))

    def test_phases_object_without_a_profiler(self):
        ph = telemetry.Phases("heat.test", cid=1)
        self.assertEqual(ph.phase("a"), 0)
        self.assertEqual(ph.phase("a"), 0)  # already running: nothing closes
        took = ph.phase("b")
        self.assertEqual(ph.ns, {"a": took})
        ph.note(program="k")  # no span to carry it: a no-op
        total = ph.close()
        self.assertEqual(set(ph.ns), {"a", "b"})
        self.assertGreaterEqual(total, ph.ns["a"] + ph.ns["b"])

    # -- ISSUE 37: the three waits part into ready and copy ----------------
    @staticmethod
    def _three_waits(x):
        """The three places where the program itself blocks on the device."""
        float(ht.sum(x * 2 + 1))
        (x * 3).numpy()
        ht.cluster.KMeans(n_clusters=2, max_iter=2, random_state=0).fit(x)
        rows = np.random.default_rng(1).standard_normal((16 * ht.get_comm().size, 4)).astype(np.float32)
        ht.linalg.qr(ht.array(rows, split=0))

    def test_ready_then_of_an_untraced_region_is_the_fetch_alone(self):
        with mock.patch.object(jax, "block_until_ready") as wait:
            got = telemetry.ready_then(telemetry.no_phase, 3, lambda v: v + 1, "sync")
        self.assertEqual((got, wait.call_count), (4, 0))

    def test_ready_then_asks_for_the_copy_waits_then_fetches(self):
        ph, order = telemetry.Phases("heat.test"), []

        class Value:  # a leaf: the copy is asked for before the wait, as the fetch alone asks for it
            def copy_to_host_async(self):
                order.append("ask")

        ph.phase("sync")  # the running phase may be the wait's own (heat.qr.sync)
        with mock.patch.object(jax, "block_until_ready", side_effect=lambda v: order.append("wait")):
            got = telemetry.ready_then(ph.phase, (Value(), Value()), lambda v: order.append("fetch") or 4, "sync")
        ph.close()
        self.assertEqual((got, order, list(ph.ns)), (4, ["ask", "ask", "wait", "fetch"], ["sync", "copy"]))

    def test_off_makes_the_fetches_it_made_and_waits_for_nothing(self):
        x = self._input()
        self._three_waits(x)  # compiled
        before = fusion.cache_stats()
        with mock.patch.object(jax, "block_until_ready", wraps=jax.block_until_ready) as wait, \
                mock.patch.object(jax, "device_get", wraps=jax.device_get) as get:
            self._three_waits(x)
        after = fusion.cache_stats()
        self.assertEqual(wait.call_count, 0, "an untraced read waited before its fetch")
        self.assertEqual(get.call_count, 2, "numpy() and the fit: one fetch each")
        self.assertEqual({k: after[k] - before[k] for k in COPY_KEYS}, dict.fromkeys(COPY_KEYS, 0))

    @unittest.skipUnless(fusion.active(), "fusion disabled via HEAT_TPU_FUSION")
    def test_the_reads_two_parts_add_up_to_the_read(self):
        x = self._input()
        before = fusion.cache_stats()
        with telemetry.enabled(1):
            float(ht.sum(x * 2 + 1))
            (x * 3).numpy()
        got = _delta(before, fusion.cache_stats())
        parts = got["phase_read_ready_ns"] + got["phase_read_copy_ns"]
        self.assertGreater(got["phase_read_ready_ns"], 0)
        self.assertGreater(got["phase_read_copy_ns"], 0)
        self.assertLessEqual(parts, got["phase_read_ns"])  # the parts lie inside the read
        self.assertLessEqual(got["phase_read_ns"] - parts, max(0.2 * got["phase_read_ns"], 50_000.0))

    def test_a_traced_fit_and_a_traced_qr_grow_their_copy_key_once(self):
        x = self._input()
        self._three_waits(x)
        for region, run in (("kmeans", lambda: ht.cluster.KMeans(n_clusters=2, max_iter=2, random_state=0).fit(x)),
                            ("qr", lambda: ht.linalg.qr(ht.array(np.eye(8 * self.get_size(), 4, dtype=np.float32), split=0)))):
            before = fusion.cache_stats()
            with telemetry.enabled(1), mock.patch.object(jax, "block_until_ready", wraps=jax.block_until_ready) as wait:
                run()
            after = fusion.cache_stats()
            self.assertEqual(wait.call_count, 1, region)
            self.assertEqual(after[f"phase_{region}_syncs"] - before[f"phase_{region}_syncs"], 1, region)
            self.assertGreater(after[f"phase_{region}_copy_ns"] - before[f"phase_{region}_copy_ns"], 0, region)
            self.assertGreater(after[f"phase_{region}_sync_ns"] - before[f"phase_{region}_sync_ns"], 0, region)
            self.assertEqual(after["phase_reads"], before["phase_reads"], region)  # neither goes through heat.read

    def test_opsplane_exports_the_new_phases_as_label_values(self):
        with telemetry.enabled(1):
            self._three_waits(self._input())
        text = opsplane.render()
        self.assertEqual(opsplane.validate_exposition(text), [])
        for family, phase in (("fusion", "read_ready"), ("fusion", "read_copy"), ("kmeans", "copy"), ("qr", "copy")):
            self.assertIn(f'heat_tpu_{family}_phase_seconds_total{{phase="{phase}"}}', text)


@unittest.skipUnless(fusion.active(), "fusion disabled via HEAT_TPU_FUSION")
class TestProfiledSpans(PhaseCase):
    """One profiler session on the CPU backend: a scalar read, an ``larray``
    force, a ``numpy()``, user spans, and a force that drains another root."""

    @staticmethod
    def _scenario(x):
        scalar = float(ht.sum(x * 2 + 1))
        (x * 3).larray.block_until_ready()
        gathered = (x - 1).numpy()
        with telemetry.span("user.off"):  # telemetry off: the bare name
            pass
        with telemetry.enabled(1), telemetry.span("user.fit"), telemetry.span("iter"):
            float(ht.sum(x))
        # the drain policy forces the big pending root from inside the small
        # chain's memory gate: a recursive force
        big = ht.ones((4096 * ht.get_comm().size, 8), split=0) * 2.0
        chain = ht.sqrt(ht.abs(x * 1.5 + 2.0))
        prev = memledger.set_budget(1, "drain")
        try:
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", memledger.MemoryBudgetWarning)
                float(chain.sum())
        finally:
            memledger.set_budget(prev[0], prev[1])
        return scalar, gathered, not fusion.is_deferred(big)

    @classmethod
    def setUpClass(cls):
        cls._tmp = tempfile.TemporaryDirectory()
        with resilience.suspended():
            fusion.clear_cache()
            memledger.reset()
            n = 4 * ht.get_comm().size
            x = ht.array(np.arange(3 * n, dtype=np.float32).reshape(n, 3), split=0)
            cls._scenario(x)  # every program compiled before the session
            cls.expected_scalar = float(np.sum(np.arange(3 * n, dtype=np.float32) * 2 + 1))
            # Each counted interval encloses its span by a clock read and a
            # few bytecodes. Where the host stalls in one of those (a
            # collection, a descheduled thread: about one session in twelve
            # on the CPU mesh) the sums part by more than the tolerance,
            # and that is not the program's: the session is recorded again
            for attempt in range(4):
                cls.directory = os.path.join(cls._tmp.name, str(attempt))
                cls._record(x)
                if not cls._disagreements():
                    break
            memledger.reset()

    @classmethod
    def _record(cls, x):
        gc.collect()
        gc.disable()
        _profiled(cls.directory)
        try:
            with jax.profiler.TraceAnnotation("user.first"):
                pass  # the session's first event pays for its buffer
            before = fusion.cache_stats()
            cls.scalar, cls.gathered, cls.big_forced = cls._scenario(x)
            cls.delta = _delta(before, fusion.cache_stats())
            cls.program_keys = fusion.cache_stats()["program_keys"]
        finally:
            jax.profiler.stop_trace()
            gc.enable()
        cls.spans = _spans(cls.directory)

    @classmethod
    def _disagreements(cls):
        """Counters whose delta is not the summed duration of their spans
        within 20 % or 50 us, whichever is larger."""
        out = []
        for name in [f"force.{phase}" for phase in FORCE_PHASES] + ["place", "read"]:
            counted = cls.delta[f"phase_{name.split('.')[-1]}_ns"]
            total = sum(sp[3] - sp[2] for sp in cls.spans if sp[1] == "heat." + name)
            if abs(counted - total) > max(0.2 * total, 50_000.0):
                out.append((name, counted, total))
        return out

    @classmethod
    def tearDownClass(cls):
        cls._tmp.cleanup()

    def _named(self, name):
        return [sp for sp in self.spans if sp[1] == name]

    def _forces(self, recursive):
        return [sp for sp in self._named("heat.force") if bool(sp[4].get("recursive")) == recursive]

    def test_answers_are_unchanged(self):
        self.assertAlmostEqual(self.scalar / self.expected_scalar, 1.0, places=5)
        self.assertEqual(self.gathered.shape, (4 * self.get_size(), 3))

    def test_each_force_has_its_five_children_in_order(self):
        forces = self._forces(recursive=False)
        self.assertEqual(len(forces), self.delta["phase_forces"])
        self.assertGreaterEqual(len(forces), 5)
        children = [sp for sp in self.spans if sp[1].startswith("heat.force.")]
        claimed = 0
        for thread, _, start, end, stats in forces:
            inside = [c for c in children if c[0] == thread and start <= c[2] and c[3] <= end]
            self.assertEqual([c[1] for c in inside], [f"heat.force.{p}" for p in FORCE_PHASES])
            for left, right in zip(inside, inside[1:]):
                self.assertLessEqual(left[3], right[2], "children overlap")
            self.assertIn(stats["trigger"], ("larray", "parray"))
            self.assertIn(stats["program"], self.program_keys)
            claimed += len(inside)
        self.assertEqual(claimed, len(children), "a phase span outside every heat.force")

    def test_place_follows_its_force_and_read_only_scalars(self):
        forces = self._forces(recursive=False)
        places, reads = self._named("heat.place"), self._named("heat.read")
        self.assertEqual(len(places), self.delta["phase_places"])
        self.assertEqual(len(reads), self.delta["phase_reads"])
        by_cid = {sp[4]["cid"]: sp for sp in forces}
        for place in places:
            force = by_cid[place[4]["cid"]]
            self.assertGreaterEqual(place[2], force[3], "heat.place opened inside heat.force")
        kinds = [sp[4]["kind"] for sp in reads]
        self.assertEqual(kinds.count("numpy"), 1)
        self.assertEqual(kinds.count("item"), 3)
        first, second = forces[0], forces[1]  # float(sum(..)), then (x * 3).larray
        read_cids = [sp[4]["cid"] for sp in reads]
        self.assertIn(first[4]["cid"], read_cids)
        self.assertNotIn(second[4]["cid"], read_cids)
        for read in reads:  # the payload is forced first: never around a force
            for force in self._named("heat.force"):
                self.assertFalse(read[2] <= force[2] < read[3], "heat.read holds a heat.force")

    def _read_children(self, kind):
        reads = [sp for sp in self._named("heat.read") if sp[4]["kind"] == kind]
        self.assertTrue(reads, kind)
        for thread, _, start, end, _ in reads:
            inside = [
                sp for sp in self.spans
                if sp[1].startswith("heat.read.") and sp[0] == thread and start <= sp[2] and sp[3] <= end
            ]
            self.assertEqual([sp[1] for sp in inside], ["heat.read.ready", "heat.read.copy"])
            self.assertLessEqual(inside[0][3], inside[1][2], "ready and copy overlap")
        return len(reads)

    def test_an_items_read_holds_ready_then_copy(self):
        self.assertEqual(self._read_children("item"), 3)

    def test_a_numpys_read_holds_ready_then_copy(self):
        self.assertEqual(self._read_children("numpy"), 1)

    def test_ready_and_copy_counters_are_their_spans_durations(self):
        self.assertEqual(len(self._named("heat.read.ready")), self.delta["phase_reads"])
        self.assertEqual(len(self._named("heat.read.copy")), self.delta["phase_reads"])
        for part in ("ready", "copy"):
            counted = self.delta[f"phase_read_{part}_ns"]
            total = sum(sp[3] - sp[2] for sp in self._named(f"heat.read.{part}"))
            self.assertLessEqual(abs(counted - total), max(0.2 * total, 50_000.0), part)
        parts = self.delta["phase_read_ready_ns"] + self.delta["phase_read_copy_ns"]
        self.assertLessEqual(parts, self.delta["phase_read_ns"])

    def test_counters_are_the_spans_durations(self):
        forces = self._forces(recursive=False)
        for phase in FORCE_PHASES:  # a recursive force opens no child
            self.assertEqual(len(self._named(f"heat.force.{phase}")), len(forces))
        self.assertEqual(self._disagreements(), [])

    def test_a_recursive_force_is_one_childless_uncounted_span(self):
        self.assertTrue(self.big_forced)
        recursive = self._forces(recursive=True)
        self.assertEqual(len(recursive), 1)
        _, _, start, end, _ = recursive[0]
        lookups = [sp for sp in self._named("heat.force.lookup") if sp[2] <= start and end <= sp[3]]
        self.assertEqual(len(lookups), 1, "the drain runs under the outer force's memory gate")
        self.assertEqual(self.delta["forces"], self.delta["phase_forces"] + 1)

    def test_telemetry_span_is_a_trace_annotation_of_its_path(self):
        names = [sp[1] for sp in self.spans]
        self.assertEqual(names.count("user.off"), 1)
        self.assertEqual(names.count("user.fit"), 1)
        self.assertEqual(names.count("user.fit/iter"), 1)
        fit, inner = self._named("user.fit")[0], self._named("user.fit/iter")[0]
        self.assertTrue(fit[2] <= inner[2] and inner[3] <= fit[3])

    def test_gaps_verb_reads_the_trace(self):
        out = io.StringIO()
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            self.assertEqual(telemetry_cli.main(["gaps", self.directory, "--json"], out=out), 0)
            doc = json.loads(out.getvalue())
            text = io.StringIO()
            self.assertEqual(telemetry_cli.main(["gaps", doc["source"]], out=text), 0)
        self.assertTrue(doc["device"].startswith("cpu:"))
        self.assertAlmostEqual(doc["busy_s"] + doc["idle_s"], doc["window_s"], places=9)
        self.assertGreater(doc["busy_s"], 0.0)
        heat = [sp for sp in self.spans if sp[1].startswith("heat.")]
        first, last = heat[0][2], max(sp[3] for sp in heat)
        self.assertAlmostEqual(doc["window_s"], (last - first) * 1e-9, places=6)
        by_span = doc["idle_by_span_s"]
        self.assertIn("heat.force.dispatch", by_span)
        self.assertIn("heat.read", by_span)
        self.assertGreaterEqual(by_span["outside"], 0.0)
        # one forcing thread: every idle instant has one innermost span or none
        self.assertAlmostEqual(sum(by_span.values()), doc["idle_s"], places=6)
        self.assertIn("idle by innermost heat.* span", text.getvalue())
        self.assertIn("outside", text.getvalue())
        # a CPU backend: no runtime event, no shift, every gap is the host's
        self.assertEqual((doc["aligned"], doc["programs"], doc["consistent"]), ("none needed", 0, True))
        self.assertEqual((doc["shift_lo_us"], doc["shift_hi_us"]), (0.0, 0.0))
        self.assertAlmostEqual(doc["launch_plus_completion_s"] + doc["queued_s"] + doc["inside_program_s"], 0.0, places=12)
        self.assertIn("heat.read.copy", by_span)


class TestGapsArithmetic(unittest.TestCase):
    def test_innermost_pieces(self):
        pieces = telemetry_cli._innermost(
            [(0.0, 10.0, "force"), (1.0, 4.0, "walk"), (4.0, 9.0, "dispatch"), (12.0, 13.0, "read")]
        )
        self.assertEqual(
            pieces,
            [(0.0, 1.0, "force"), (1.0, 4.0, "walk"), (4.0, 9.0, "dispatch"),
             (9.0, 10.0, "force"), (12.0, 13.0, "read")],
        )

    def test_overlap_with_gaps(self):
        pieces = [(0.0, 1.0, "force"), (1.0, 4.0, "walk"), (4.0, 9.0, "dispatch"), (12.0, 13.0, "read")]
        got = telemetry_cli._overlap(pieces, [(0.5, 2.0), (3.0, 5.0), (8.0, 12.5)])
        self.assertEqual(got, {"force": 0.5, "walk": 2.0, "dispatch": 2.0, "read": 0.5})
        # overlapping without nesting (device ops, two threads): still disjoint
        union = telemetry_cli._innermost([(3, 4, "b"), (0, 2, "b"), (1, 2.5, "b"), (0.5, 1.5, "b")])
        self.assertEqual(sum(e - s for s, e, _ in union), 2.5 + 1.0)
        self.assertTrue(all(a[1] <= b[0] for a, b in zip(union, union[1:])))

    # -- ISSUE 37: one clock. Made-up events, seconds; a device's clock runs
    # SHIFT behind the host's (its lines lie early, as a v5e's do) ----------
    SHIFT = 400e-6

    @classmethod
    def _programs(cls, shift=None, done_after=30e-6):
        """Three programs of 1 ms, 3 ms apart on the host's clock: enqueued
        for 40 us, started 100 us after the enqueue ended, ``Done`` (10 us)
        beginning ``done_after`` after the end. Returns the device's modules
        (on ITS clock) and the host's enqueues and dones."""
        shift = cls.SHIFT if shift is None else shift
        modules, enqueues, dones = [], [], []
        for k in range(3):
            t = 10e-3 + k * 3e-3  # the enqueue's end, host clock
            enqueues.append((t - 40e-6, t))
            modules.append((t + 100e-6 - shift, t + 1100e-6 - shift))
            dones.append((t + 1100e-6 + done_after, t + 1100e-6 + done_after + 10e-6))
        return modules, enqueues, dones

    def test_a_known_shift_is_recovered_as_a_window_that_holds_it(self):
        got = telemetry_cli._shift_window(*self._programs())
        self.assertEqual((got["programs"], got["unmatched"], got["consistent"]), (3, 0, True))
        self.assertAlmostEqual(got["shift_lo_us"], 400.0 - 100.0, places=6)  # as if nothing were launch
        self.assertAlmostEqual(got["shift_hi_us"], 400.0 + 30.0, places=6)  # as if nothing were completion
        self.assertTrue(got["shift_lo_us"] <= 1e6 * self.SHIFT <= got["shift_hi_us"])

    def test_a_shift_that_drifted_is_reported_inconsistent(self):
        modules, enqueues, dones = self._programs()
        modules[2] = (modules[2][0] - 200e-6, modules[2][1] - 200e-6)  # the third lies 200 us earlier still
        got = telemetry_cli._shift_window(modules, enqueues, dones)
        self.assertFalse(got["consistent"])
        self.assertGreater(got["shift_lo_us"], got["shift_hi_us"])
        short = telemetry_cli._shift_window(modules[:2], enqueues, dones)
        self.assertEqual((short["programs"], short["unmatched"], short["consistent"]), (2, 1, True))
        self.assertEqual(telemetry_cli._shift_window([], [], [])["by_part_us"], [[0.0, 0.0]])

    def test_a_shift_that_steps_shows_in_the_parts_and_the_pairs_follow_it(self):
        """What a v5e session does about 1.2 s in (PERF.md, PR 37): the
        device's lines move by a quarter of a millisecond."""
        modules, enqueues, dones = [], [], []
        for k in range(128):  # 1 ms programs, 2 ms apart; launch 100 us, completion 30 us
            t, shift = 10e-3 + k * 2e-3, (600e-6 if k < 64 else 350e-6)
            enqueues.append((t - 40e-6, t))
            modules.append((t + 100e-6 - shift, t + 1100e-6 - shift))
            dones.append((t + 1130e-6, t + 1140e-6))
        window = telemetry_cli._shift_window(modules, enqueues, dones)
        self.assertFalse(window["consistent"])  # no one shift fits: 500 .. 380
        self.assertEqual(len(window["by_part_us"]), 4)
        for part, true in zip(window["by_part_us"], (600.0, 600.0, 350.0, 350.0)):
            self.assertAlmostEqual(part[0], true - 100.0, places=6)
            self.assertAlmostEqual(part[1], true + 30.0, places=6)
        gaps = [(a[1], b[0]) for a, b in zip(modules, modules[1:])]
        got = telemetry_cli._cut(gaps, modules, enqueues, dones, [], window["by_part_us"])
        # 127 gaps of 130 us beside the host's part, one of them 250 us longer on the device's clock: the step's own
        self.assertAlmostEqual(got["launch_plus_completion_s"], 127 * 130e-6 + 250e-6, places=12)
        self.assertAlmostEqual(got["launch_s"][0], 0.0, places=12)
        self.assertAlmostEqual(got["launch_s"][1], 127 * 130e-6, places=12)
        self.assertAlmostEqual(got["completion_s"][0], 127 * 130e-6 + 250e-6, places=12)

    def test_two_devices_have_a_window_each(self):
        near, far = self._programs(shift=400e-6), self._programs(shift=1500e-6)
        windows = [telemetry_cli._shift_window(*dev) for dev in (near, far)]
        self.assertTrue(windows[0]["shift_lo_us"] <= 400.0 <= windows[0]["shift_hi_us"])
        self.assertTrue(windows[1]["shift_lo_us"] <= 1500.0 <= windows[1]["shift_hi_us"])
        self.assertLess(windows[0]["shift_hi_us"], windows[1]["shift_lo_us"])

    def test_a_gap_is_cut_into_host_launch_and_completion(self):
        modules, enqueues, dones = self._programs()
        gaps = [(a[1], b[0]) for a, b in zip(modules, modules[1:])]  # 2 ms each, the device's clock
        spans = [(11.2e-3, 12.5e-3, "heat.force"), (12.0e-3, 12.5e-3, "heat.force.dispatch")]
        window = telemetry_cli._shift_window(modules, enqueues, dones)
        self.assertEqual(window["by_part_us"], [[window["shift_lo_us"], window["shift_hi_us"]]])  # under 64 programs: one part
        got = telemetry_cli._cut(gaps, modules, enqueues, dones, spans, window["by_part_us"])
        # per gap: Done start (t + 1130 us) -> next enqueue end (t + 3000): 1870 us of host, 130 of the rest
        self.assertAlmostEqual(got["launch_plus_completion_s"], 2 * 130e-6, places=12)
        self.assertAlmostEqual(sum(got["idle_by_span_s"].values()), 2 * 1870e-6, places=12)
        self.assertAlmostEqual(got["idle_by_span_s"]["heat.force"], 800e-6, places=12)
        self.assertAlmostEqual(got["idle_by_span_s"]["heat.force.dispatch"], 500e-6, places=12)
        self.assertAlmostEqual(got["idle_by_span_s"]["outside"], 2 * 1870e-6 - 1300e-6, places=12)
        for at in (0, 1):  # each pair adds up to the exact sum, at either end of the window
            self.assertAlmostEqual(got["launch_s"][at] + got["completion_s"][at], 2 * 130e-6, places=12)
        self.assertAlmostEqual(got["launch_s"][0], 0.0, places=12)  # the low shift: all of it completion
        self.assertAlmostEqual(got["completion_s"][1], 0.0, places=12)  # the high shift: all of it launch
        self.assertEqual((got["queued_s"], got["inside_program_s"]), (0.0, 0.0))
        pieces = got["launch_plus_completion_s"] + sum(got["idle_by_span_s"].values())
        self.assertAlmostEqual(pieces, sum(e - s for s, e in gaps), places=12)

    def test_a_queued_gap_a_gap_inside_a_program_and_the_edges(self):
        modules, enqueues, dones = self._programs()
        enqueues[1] = (10.5e-3, 10.6e-3)  # the second was enqueued while the first ran
        inside = (modules[2][0] + 100e-6, modules[2][0] + 150e-6)
        gaps = [(modules[0][0] - 300e-6, modules[0][0]), (modules[0][1], modules[1][0]),
                (modules[1][1], modules[2][0]), inside, (modules[2][1], modules[2][1] + 50e-6)]
        got = telemetry_cli._cut(gaps, modules, enqueues, dones, [])
        self.assertAlmostEqual(got["queued_s"], 2e-3, places=12)
        self.assertAlmostEqual(got["inside_program_s"], 50e-6, places=12)
        # the edges: the gap's own end stands for the missing event, the host's part stays inside the gap
        self.assertAlmostEqual(got["idle_by_span_s"]["outside"], 300e-6 + 1870e-6, places=12)
        self.assertAlmostEqual(got["launch_plus_completion_s"], 130e-6 + 50e-6, places=12)
        total = got["queued_s"] + got["inside_program_s"] + got["launch_plus_completion_s"] + got["idle_by_span_s"]["outside"]
        self.assertAlmostEqual(total, sum(e - s for s, e in gaps), places=12)
        # a CPU backend: no program at all, every gap is the host's by its span
        cpu = telemetry_cli._cut([(1.0, 2.0)], [], [], [], [(0.5, 1.5, "heat.read")])
        self.assertEqual(cpu["idle_by_span_s"], {"heat.read": 0.5, "outside": 0.5})
        self.assertEqual(cpu["launch_plus_completion_s"], 0.0)

    def test_round_trips_by_program(self):
        modules, enqueues, dones = self._programs()
        forces = [(9.8e-3, 10.05e-3, "aa"), (12.7e-3, 13.05e-3, "bb")]  # none over the third's enqueue
        got = telemetry_cli._round_trips(modules, enqueues, dones, forces)
        self.assertEqual({k: v["n"] for k, v in got.items()}, {"all": 3, "aa": 1, "bb": 1, "-": 1})
        for key in got:  # enqueue end -> Done start less the device's 1 ms: 100 + 30 us, whatever the shift
            self.assertAlmostEqual(got[key]["launch_plus_completion_us"][0], 130.0, places=6)
            self.assertAlmostEqual(got[key]["device_us"][1], 1000.0, places=6)
        self.assertAlmostEqual(got["aa"]["host_before_us"][0], 200.0, places=6)  # its span's start -> enqueue end
        self.assertAlmostEqual(got["aa"]["host_after_us"][0], 12.7e3 - 11.13e3, places=6)  # Done -> the next span
        self.assertAlmostEqual(got["bb"]["host_before_us"][0], 300.0, places=6)
        self.assertAlmostEqual(got["bb"]["host_after_us"][0], 0.0, places=6)  # no span over the next
        self.assertAlmostEqual(got["-"]["host_before_us"][0], 16e3 - 14.13e3, places=6)  # from the last Done
        enqueues[1] = (10.5e-3, 10.6e-3)  # queued behind the first: not a launch + completion
        queued = telemetry_cli._round_trips(modules, enqueues, dones, [])
        self.assertEqual(queued["all"]["n"], 3)
        self.assertAlmostEqual(queued["all"]["launch_plus_completion_us"][0], 130.0, places=6)
        self.assertEqual(telemetry_cli._round_trips([], [], [], []), {})

    def _recorded(self, path):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            doc = telemetry_cli._gaps_doc(path)
        pieces = (
            doc["launch_plus_completion_s"] + doc["queued_s"] + doc["inside_program_s"]
            + sum(doc["idle_by_span_s"].values())
        )
        self.assertAlmostEqual(pieces, doc["idle_s"], places=9)  # the pieces add up to the idle time
        for at in (0, 1):  # the pairs add up to the exact sum, less the window's two edge gaps
            self.assertLessEqual(doc["launch_s"][at] + doc["completion_s"][at], doc["launch_plus_completion_s"] + 1e-12)
        return doc

    def test_the_recorded_scan_trace(self):
        """Three scan trials on a v5e, 18 programs, recorded before the
        program had spans (``chipbench/tests/data``; read, not edited)."""
        doc = self._recorded(SCAN_TRACE)
        self.assertEqual((doc["device"], doc["programs"], doc["unmatched"]), ("/device:TPU:0", 18, 0))
        self.assertTrue(doc["consistent"])
        self.assertEqual(doc["aligned"], "by the runtime's events")
        self.assertAlmostEqual(doc["shift_lo_us"], -27.1, delta=0.1)
        self.assertAlmostEqual(doc["shift_hi_us"], 417.7, delta=0.1)
        trips = doc["round_trips"]
        self.assertEqual(set(trips), {"all", "-"})  # no heat.force span in it
        self.assertAlmostEqual(trips["all"]["launch_plus_completion_us"][0], 536.6, delta=0.1)
        self.assertAlmostEqual(trips["all"]["device_us"][0], 12996.3, delta=0.1)
        self.assertEqual(list(doc["idle_by_span_s"]), ["outside"])
        self.assertAlmostEqual(1e6 * doc["launch_plus_completion_s"], 9092.6, delta=0.1)

    def test_the_recorded_small_cell_trace(self):
        """Three ``moments_small_1c`` trials on a v5e with the program's spans
        (``tests/data/small_3ops.xplane.pb``, PR 37): 18 programs of 6-11 us,
        the device's lines 1.6-1.9 ms early."""
        doc = self._recorded(os.path.join(DATA, "small_3ops.xplane.pb"))
        self.assertEqual((doc["programs"], doc["unmatched"], doc["consistent"]), (18, 0, True))
        self.assertAlmostEqual(doc["shift_lo_us"], 1616.6, delta=0.1)
        self.assertAlmostEqual(doc["shift_hi_us"], 1907.4, delta=0.1)
        self.assertAlmostEqual(1e6 * doc["idle_s"], 16932.2, delta=0.1)
        self.assertAlmostEqual(1e6 * doc["launch_plus_completion_s"], 7753.0, delta=0.1)
        self.assertAlmostEqual(1e6 * sum(doc["idle_by_span_s"].values()), 9176.0, delta=0.1)  # the host's part
        self.assertAlmostEqual(1e6 * doc["inside_program_s"], 3.2, delta=0.1)
        self.assertEqual(doc["queued_s"], 0.0)
        by_span = doc["idle_by_span_s"]
        self.assertAlmostEqual(1e6 * by_span["heat.force.dispatch"], 3311.1, delta=0.1)
        self.assertAlmostEqual(1e6 * by_span["heat.read.copy"], 2469.1, delta=0.1)
        self.assertAlmostEqual(1e6 * by_span["heat.read.ready"], 210.3, delta=0.1)  # the wait is completion, not host
        self.assertAlmostEqual(1e6 * by_span["outside"], 2217.3, delta=0.1)
        trips = doc["round_trips"]
        self.assertEqual(len(trips), 7)  # all, and the trial's six programs by their key
        self.assertEqual(sorted(v["n"] for v in trips.values()), [3] * 6 + [18])
        self.assertAlmostEqual(trips["all"]["launch_plus_completion_us"][0], 436.1, delta=0.1)
        self.assertAlmostEqual(trips["all"]["host_before_us"][0], 251.1, delta=0.1)
        self.assertAlmostEqual(trips["all"]["host_after_us"][0], 275.7, delta=0.1)

    def test_a_directory_without_a_trace_is_an_error(self):
        out = io.StringIO()
        with tempfile.TemporaryDirectory() as empty:
            self.assertEqual(telemetry_cli.main(["gaps", empty], out=out), 2)
        self.assertIn("ERROR", out.getvalue())


@unittest.skipUnless(fusion.active(), "fusion disabled via HEAT_TPU_FUSION")
class TestThreads(PhaseCase):
    def test_eight_serving_threads_lose_no_update(self):
        clients, rounds = 8, 12
        errors = []
        barrier = threading.Barrier(clients)

        def client(i):
            try:
                with serving.Session(f"tenant-{i}"):
                    x = self._input(i)
                    barrier.wait(timeout=60)
                    for r in range(rounds):
                        float(ht.sum(x * float(r + 1) + float(i)))
            except Exception as exc:  # noqa: BLE001 - reported below
                errors.append(repr(exc))

        before = fusion.cache_stats()
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            with telemetry.enabled(1):
                threads = [threading.Thread(target=client, args=(i,)) for i in range(clients)]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(timeout=300)
        finally:
            sys.setswitchinterval(interval)
        self.assertEqual([t.is_alive() for t in threads], [False] * clients)
        self.assertEqual(errors, [])
        got = _delta(before, fusion.cache_stats())
        # a neighbour's batch may land a client's node: fewer forces than
        # results, but every force timed, and every wrapper placed and read
        self.assertEqual(got["phase_forces"], got["forces"])
        self.assertGreaterEqual(got["forces"], 1)
        self.assertEqual(got["phase_places"], clients * rounds)
        self.assertEqual(got["phase_reads"], clients * rounds)
