"""Phase spans of the forcing path (ISSUE 27): ``heat.force`` with its five
children, ``heat.place`` and ``heat.read`` as ``TraceAnnotation``s on the
profiler's own clock, the same intervals as ``phase_*`` counters of
``fusion.cache_stats()``, one switch (``telemetry.tracing()``), and the
``python -m heat_tpu.telemetry gaps`` reading of a profiler trace.

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
    + [f"phase_{name}_ns" for name in FORCE_PHASES]
)


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
