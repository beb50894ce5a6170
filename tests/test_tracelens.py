"""Trace analytics: attribution, critical path, straggler naming, detectors
(ISSUE 13).

Pins the acceptance criteria: on the reduction-chain workload the analyzer
attributes >= 95% of window wall time (explicit ``unattributed`` remainder
<= 5%) and its per-chain summary confirms 1 dispatch + <= 1 blocking sync per
fused chain; an injected one-host delay fault (``trace.hostdelay``) on a
merged multi-host trace yields a ``tracelens.straggler`` finding naming the
correct host; a truncated window is refused (``TraceIncompleteError``) unless
``allow_partial``, with a one-shot ``TimelineDroppedWarning`` at the first cap
eviction; the joins the analyzer sits on survive adversarial event streams;
flight-recorder bundles embed the one-page diagnosis; and the analyzer is
post-hoc only (never forces a chain, never initializes a backend). Runs green
at mesh 1/3/5/8 (matrix legs), with fusion off, and under
``HEAT_TPU_FAULTS=ci`` (exact-count tests shield with
``resilience.suspended()``).
"""

import importlib
import io
import json
import os
import subprocess
import sys
import tempfile
import unittest
import warnings

import numpy as np

import heat_tpu as ht
from heat_tpu.core import fusion, health_runtime, resilience, telemetry, tracelens

from harness import TestCase

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_BLOCKING = tracelens._BLOCKING_BUCKETS


class TracelensCase(TestCase):
    """verbose mode + clean caches, exact under the ambient CI fault mix."""

    def setUp(self):
        self._suspend = resilience.suspended()
        self._suspend.__enter__()
        self._prev_mode = telemetry.set_mode("verbose")
        fusion.clear_cache()
        telemetry.reset()

    def tearDown(self):
        telemetry.set_mode(self._prev_mode)
        telemetry.reset()
        self._suspend.__exit__(None, None, None)

    def _split_input(self, seed=0, n_mult=4):
        n = n_mult * self.get_size()
        return ht.array(
            np.random.default_rng(seed).standard_normal((n, 3)).astype(np.float32),
            split=0,
        )

    def _reduction_chain(self, seed=0):
        """The kmeans-shaped bench chain: mean -> var -> std, all read.
        Live-analysis tests need a populated timeline; the eager engine
        (HEAT_TPU_FUSION=0) records no dispatch/compile events, so they
        skip rather than assert on an empty window."""
        a = self._split_input(seed)
        m, v, s = ht.mean(a), ht.var(a), ht.std(a)
        out = float(m) + float(v) + float(s)
        if not telemetry.events():
            self.skipTest("engine records no timeline events (fusion off)")
        return out


def _bucket_sum(analysis):
    return sum(rec["s"] for rec in analysis["attribution"]["overall"].values())


# ----------------------------------------------------------------------
# time attribution (tentpole part 1)
# ----------------------------------------------------------------------
class TestAttribution(TracelensCase):
    def test_reduction_chain_attribution_covers_95_pct(self):
        # THE acceptance pin: every wall-clock microsecond of the window is
        # bucketed, with the explicit unattributed remainder <= 5%
        self._reduction_chain()
        self._reduction_chain(seed=1)
        ana = tracelens.analyze()
        self.assertGreater(ana["window_s"], 0.0)
        self.assertLessEqual(
            ana["attribution"]["unattributed_pct"], 5.0, ana["attribution"]
        )
        # the accounting is falsifiable: buckets + remainder == the window
        total = _bucket_sum(ana) + ana["attribution"]["unattributed_s"]
        self.assertAlmostEqual(total, ana["window_s"], places=5)
        for bucket, rec in ana["attribution"]["overall"].items():
            self.assertIn(bucket, tracelens._BUCKET_PRIORITY)
            self.assertGreaterEqual(rec["s"], 0.0)

    def test_clean_workload_yields_no_findings(self):
        # the matrix leg's contract: the clean bench-shaped workload must
        # analyze without a single warning/error finding
        self._reduction_chain()
        ana = tracelens.analyze()
        self.assertEqual(
            [f for f in ana["findings"] if f["severity"] != "info"], [],
            ana["findings"],
        )

    @unittest.skipUnless(fusion.active(), "fusion disabled via HEAT_TPU_FUSION")
    def test_chains_confirm_one_dispatch_one_sync(self):
        # ROADMAP 1's metric, asserted by machine: each fused chain is one
        # dispatch and at most one blocking sync
        self._reduction_chain()
        ana = tracelens.analyze()
        self.assertGreaterEqual(len(ana["chains"]), 1, telemetry.events())
        for chain in ana["chains"]:
            self.assertEqual(chain["dispatches"], 1, chain)
            if fusion.collectives_active():
                self.assertLessEqual(chain["syncs"], 1, chain)

    @unittest.skipUnless(fusion.active(), "fusion disabled via HEAT_TPU_FUSION")
    def test_per_program_attribution_keys_are_cache_keys(self):
        self._reduction_chain()
        ana = tracelens.analyze()
        per_prog = ana["attribution"]["per_program"]
        self.assertGreaterEqual(len(per_prog), 1)
        cache_keys = set(fusion.cache_stats()["program_keys"])
        for key, rec in per_prog.items():
            self.assertIn(key, cache_keys)
            self.assertGreaterEqual(rec["dispatches"], 1)
            self.assertGreaterEqual(sum(rec[b] for b in _BLOCKING), 0.0)

    def test_exported_file_analyzes_like_live(self):
        # source polymorphism: a written trace file round-trips through the
        # Perfetto inversion with the same coverage contract
        self._reduction_chain()
        live = tracelens.analyze()
        with tempfile.TemporaryDirectory() as td:
            path = os.path.join(td, "trace.json")
            telemetry.export_trace(path)
            from_file = tracelens.analyze(path)
        self.assertLessEqual(from_file["attribution"]["unattributed_pct"], 5.0)
        self.assertEqual(from_file["source"], path)
        # the dominant bucket survives the round trip
        def top(ana):
            overall = ana["attribution"]["overall"]
            return max(overall, key=lambda b: overall[b]["s"])
        self.assertEqual(top(live), top(from_file))

    def test_analyze_requires_events(self):
        with self.assertRaises(ValueError):
            tracelens.analyze()


# ----------------------------------------------------------------------
# critical path (tentpole part 2)
# ----------------------------------------------------------------------
class TestCriticalPath(TracelensCase):
    def test_path_is_ordered_blocking_and_bounded_by_window(self):
        self._reduction_chain()
        ana = tracelens.analyze()
        crit = ana["critical_path"]
        self.assertGreater(crit["total_s"], 0.0)
        self.assertLessEqual(crit["total_s"], ana["window_s"] + 1e-6)
        self.assertGreaterEqual(crit["sync_pct"], 0.0)
        self.assertLessEqual(crit["sync_pct"], 100.0)
        for step in crit["steps"]:
            self.assertIn(step["bucket"], _BLOCKING)
            self.assertGreaterEqual(step["dur_s"], 0.0)
        if not crit["truncated"]:
            self.assertAlmostEqual(
                sum(s["dur_s"] for s in crit["steps"]), crit["total_s"], places=4
            )

    def test_dp_picks_longest_chain_over_overlapping_segments(self):
        # merged/adversarial traces produce OVERLAPPING reconstructed
        # segments; the DP must not greedily chain through a short recent
        # segment when a longer earlier one also fits
        segments = [
            {"start": 0.0, "end": 10.0, "bucket": "compile", "program": None, "cid": 1},
            {"start": 9.0, "end": 10.5, "bucket": "sync_wait", "program": None, "cid": 2},
            {"start": 10.6, "end": 11.0, "bucket": "device_execute", "program": None, "cid": 3},
        ]
        crit = tracelens._critical_path(segments)
        self.assertAlmostEqual(crit["total_s"], 10.4, places=6)
        self.assertEqual([s["cid"] for s in crit["steps"]], [1, 3])

    def test_serial_segments_all_land_on_the_path(self):
        segments = [
            {"start": float(i), "end": i + 0.5, "bucket": "device_execute",
             "program": "p", "cid": i}
            for i in range(5)
        ]
        crit = tracelens._critical_path(segments)
        self.assertAlmostEqual(crit["total_s"], 2.5, places=6)
        self.assertEqual(len(crit["steps"]), 5)
        self.assertEqual(crit["sync_pct"], 100.0)


# ----------------------------------------------------------------------
# cross-host straggler attribution (tentpole part 3)
# ----------------------------------------------------------------------
_STRAGGLER_WORKER = r"""
import contextlib, sys, time
import heat_tpu.core.telemetry as telemetry
import heat_tpu.core.resilience as resilience


class Clock:
    # the host's clock as telemetry reads it, driven by this script alone:
    # perf_counter reads it, sleep (the trace.hostdelay site's) advances it
    now = 1000.0

    def __getattr__(self, name):
        return getattr(time, name)

    def perf_counter(self):
        return self.now

    def sleep(self, seconds):
        self.now += seconds


clock = telemetry.time = Clock()
out_path, slow = sys.argv[1], sys.argv[2] == "slow"
telemetry.set_mode("verbose")
telemetry.reset()
ctx = resilience.inject("trace.hostdelay", times=None) if slow else contextlib.nullcontext()
with ctx:
    for _ in range(12):
        telemetry.record_collective("allreduce", axis="x", nbytes=1024, dtype="float32")
        clock.now += 0.002
telemetry.export_trace(out_path)
from heat_tpu.core import communication
assert communication.MESH_WORLD is None, "worker initialized a backend"
"""


class TestStragglerAttribution(TracelensCase):
    def _run_hosts(self, td, n_hosts, slow_host):
        """One simulated host per subprocess, all recording the same
        collective sequence on a clock the worker drives (2 ms a record);
        ``slow_host`` (if any) runs with the ``trace.hostdelay`` fault armed,
        so every record first advances that clock by
        HEAT_TPU_TRACE_DELAY_MS — cumulative lag only tracelens can name,
        and the same however busy the machine is."""
        env = dict(os.environ)
        env.setdefault("JAX_PLATFORMS", "cpu")
        env.pop("HEAT_TPU_FAULTS", None)  # deterministic workers under the ci leg
        env["HEAT_TPU_TRACE_DELAY_MS"] = "15"
        paths, procs = [], []
        for h in range(n_hosts):
            path = os.path.join(td, f"host{h}.json")
            paths.append(path)
            mode = "slow" if h == slow_host else "fast"
            procs.append(subprocess.Popen(
                [sys.executable, "-c", _STRAGGLER_WORKER, path, mode],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True, env=env, cwd=_REPO,
            ))
            # bound concurrent imports by core count (load only: no worker
            # reads the wall clock)
            if len(procs) >= max(1, min(4, os.cpu_count() or 1)):
                procs.pop(0).wait()
        for p in procs:
            p.wait()
            self.assertEqual(p.returncode, 0, p.stderr.read())
        return paths

    def test_injected_delay_names_the_straggling_host(self):
        # THE acceptance pin: one-host delay fault -> tracelens.straggler
        # finding naming that host, on the merged trace (mesh 3 runs 3
        # simulated hosts, mesh 8 runs 8 — per the matrix legs)
        n_hosts = max(3, min(self.get_size(), 8))
        slow = n_hosts // 2
        with tempfile.TemporaryDirectory() as td:
            paths = self._run_hosts(td, n_hosts, slow)
            merged = os.path.join(td, "merged.json")
            telemetry.merge_traces(paths, merged)
            ana = tracelens.analyze(merged)

            self.assertEqual(ana["hosts"], n_hosts)
            strag = ana["stragglers"]
            self.assertEqual(strag["straggler"], slow, strag)
            self.assertGreaterEqual(strag["matched_collectives"], 12)
            # the named host's residual lag dominates every peer's
            worst = strag["lag_ms"][str(slow)]
            for pid, lag in strag["lag_ms"].items():
                if pid != str(slow):
                    self.assertGreater(worst, lag)
            findings = [f for f in ana["findings"] if f["rule"] == "tracelens.straggler"]
            self.assertEqual(len(findings), 1, ana["findings"])
            self.assertEqual(findings[0]["host"], slow)
            self.assertEqual(findings[0]["severity"], "warning")

            # control: merging only the healthy hosts names no straggler at
            # the same default threshold (their driven clocks agree exactly)
            healthy = [p for h, p in enumerate(paths) if h != slow]
            merged2 = os.path.join(td, "healthy.json")
            telemetry.merge_traces(healthy, merged2)
            ana2 = tracelens.analyze(merged2)
            self.assertIsNone(ana2["stragglers"]["straggler"], ana2["stragglers"])
            self.assertEqual(
                [f for f in ana2["findings"] if f["rule"] == "tracelens.straggler"], []
            )

    def test_single_host_has_no_straggler_block(self):
        self._reduction_chain()
        ana = tracelens.analyze()
        self.assertEqual(ana["stragglers"]["hosts"], 1)
        self.assertIsNone(ana["stragglers"]["straggler"])

    def test_clock_offset_is_removed_before_lag(self):
        # two synthetic hosts with identical cadence but wildly different
        # perf_counter epochs: after offset estimation neither host lags
        def host(base):
            return [
                {"kind": "collective", "ts": base + 0.01 * k, "op": "allreduce"}
                for k in range(8)
            ]
        doc = {0: host(0.0), 1: host(123.456)}
        strag = tracelens._stragglers(doc, straggler_s=0.005)
        self.assertIsNone(strag["straggler"], strag)
        self.assertAlmostEqual(strag["offsets_ms"]["1"], 123456.0, delta=1.0)
        self.assertLess(max(strag["lag_ms"].values()), 1.0)


# ----------------------------------------------------------------------
# anti-pattern detectors (tentpole part 4) — hand-built streams, no mesh
# ----------------------------------------------------------------------
class TestDetectors(TracelensCase):
    def test_sync_storm_inside_a_span(self):
        evs = [{"kind": "span_begin", "ts": 0.0, "name": "loop"}]
        for i in range(30):
            evs.append({"kind": "blocking_sync", "ts": 0.01 * (i + 1),
                        "where": "item", "dur": 0.001})
        evs.append({"kind": "span_end", "ts": 0.5, "name": "loop", "dur": 0.5})
        ana = tracelens.analyze(evs, sync_storm_k=8)
        hits = [f for f in ana["findings"] if f["rule"] == "tracelens.sync_storm"]
        self.assertEqual(len(hits), 1, ana["findings"])
        self.assertEqual(hits[0]["data"]["span"], "loop")
        self.assertEqual(hits[0]["data"]["syncs"], 30)

    def test_sync_storm_rolling_window_without_spans(self):
        evs = [
            {"kind": "blocking_sync", "ts": 0.005 * i, "where": "item", "dur": 0.001}
            for i in range(30)
        ]
        ana = tracelens.analyze(evs, sync_storm_k=8)
        hits = [f for f in ana["findings"] if f["rule"] == "tracelens.sync_storm"]
        self.assertEqual(len(hits), 1, ana["findings"])

    def test_retrace_storm_per_family(self):
        evs = [
            {"kind": "compile", "ts": 0.01 * i, "family": "exp|add", "cid": i}
            for i in range(6)
        ]
        evs.append({"kind": "compile", "ts": 0.9, "family": "stable", "cid": 99})
        ana = tracelens.analyze(evs, retrace_k=4)
        hits = [f for f in ana["findings"] if f["rule"] == "tracelens.retrace_storm"]
        self.assertEqual(len(hits), 1, ana["findings"])
        self.assertEqual(hits[0]["data"]["family"], "exp|add")
        self.assertEqual(hits[0]["data"]["compiles"], 6)

    def test_reshard_pingpong_on_alternating_targets(self):
        evs = [
            {"kind": "fused_collective", "ts": 0.1, "op": "reshard",
             "cid": 1, "detail": "split=0"},
            {"kind": "fused_collective", "ts": 0.2, "op": "reshard",
             "cid": 2, "detail": "split=1"},
            {"kind": "fused_collective", "ts": 0.3, "op": "reshard",
             "cid": 3, "detail": "split=0"},
        ]
        ana = tracelens.analyze(evs)
        hits = [f for f in ana["findings"] if f["rule"] == "tracelens.reshard_pingpong"]
        self.assertEqual(len(hits), 1, ana["findings"])
        self.assertEqual(hits[0]["data"]["targets"], ["split=0", "split=1", "split=0"])

    def test_monotone_reshards_are_clean(self):
        evs = [
            {"kind": "fused_collective", "ts": 0.1 * i, "op": "reshard",
             "cid": i, "detail": f"split={i}"}
            for i in range(4)
        ]
        ana = tracelens.analyze(evs)
        self.assertEqual(
            [f for f in ana["findings"] if f["rule"] == "tracelens.reshard_pingpong"],
            [],
        )

    def test_device_idle_gap(self):
        # nothing in flight between two distant stamps: the whole window is
        # provably idle device time
        evs = [
            {"kind": "collective", "ts": 0.0, "op": "allreduce"},
            {"kind": "collective", "ts": 1.0, "op": "allreduce"},
        ]
        ana = tracelens.analyze(evs)
        hits = [f for f in ana["findings"] if f["rule"] == "tracelens.device_idle"]
        self.assertEqual(len(hits), 1, ana["findings"])
        self.assertEqual(hits[0]["severity"], "warning")  # 100% of the window
        self.assertGreaterEqual(hits[0]["data"]["host_gap_pct"], 99.0)

    def test_real_reshards_carry_detail(self):
        if not (fusion.active() and fusion.collectives_active()):
            self.skipTest("reshard nodes need collective-aware fusion")
        if self.get_size() < 2:
            self.skipTest("resplit is shard-trivial on a single device")
        # the fusion seam stamps the reshard target the ping-pong detector
        # keys on; the reshard only becomes a fused node when the input
        # carries a pending chain
        a = self._split_input()
        b = ht.resplit(a * 2.0, None)
        float(ht.sum(b))
        details = [
            e.get("detail")
            for e in telemetry.events()
            if e["kind"] == "fused_collective" and e["op"] == "reshard"
        ]
        self.assertGreaterEqual(len(details), 1, telemetry.events())
        self.assertIn("replicated", details)


# ----------------------------------------------------------------------
# dropped-events soundness (satellite 1)
# ----------------------------------------------------------------------
class TestDroppedEvents(TracelensCase):
    def _overflow(self, cap=8, extra=12):
        prev = telemetry._EVENT_CAP
        telemetry._EVENT_CAP = cap
        telemetry.reset()  # rebuilds the deques at the patched cap
        self.addCleanup(lambda: (setattr(telemetry, "_EVENT_CAP", prev),
                                 telemetry.reset()))
        for i in range(cap + extra):
            telemetry.record_event("probe", index=i)

    def test_analyze_refuses_truncated_window(self):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", telemetry.TimelineDroppedWarning)
            self._overflow()
        with self.assertRaises(tracelens.TraceIncompleteError):
            tracelens.analyze()

    def test_allow_partial_analyzes_with_loud_caveat(self):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", telemetry.TimelineDroppedWarning)
            self._overflow()
        ana = tracelens.analyze(allow_partial=True)
        self.assertTrue(ana["partial"])
        self.assertEqual(ana["events_dropped"], 12)
        partial = [f for f in ana["findings"] if f["rule"] == "tracelens.partial"]
        self.assertEqual(len(partial), 1)
        self.assertEqual(partial[0]["severity"], "info")
        self.assertIn("PARTIAL", tracelens.render(ana))

    def test_first_eviction_warns_once(self):
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            self._overflow()
        dropped = [w for w in caught
                   if issubclass(w.category, telemetry.TimelineDroppedWarning)]
        self.assertEqual(len(dropped), 1, [str(w.message) for w in caught])
        self.assertIn("HEAT_TPU_TELEMETRY_EVENTS", str(dropped[0].message))
        # the latch re-arms only at reset()
        with warnings.catch_warnings(record=True) as caught2:
            warnings.simplefilter("always")
            telemetry.record_event("probe", index=-1)
        self.assertEqual(
            [w for w in caught2
             if issubclass(w.category, telemetry.TimelineDroppedWarning)], []
        )

    def test_export_carries_dropped_count_and_file_is_refused(self):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", telemetry.TimelineDroppedWarning)
            self._overflow()
        with tempfile.TemporaryDirectory() as td:
            path = os.path.join(td, "trace.json")
            doc = telemetry.export_trace(path)
            self.assertEqual(doc["otherData"]["events_dropped"], 12)
            with self.assertRaises(tracelens.TraceIncompleteError):
                tracelens.analyze(path)
            self.assertTrue(tracelens.analyze(path, allow_partial=True)["partial"])

    def test_merge_sums_dropped_counts(self):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", telemetry.TimelineDroppedWarning)
            self._overflow()
        with tempfile.TemporaryDirectory() as td:
            p1, p2 = os.path.join(td, "a.json"), os.path.join(td, "b.json")
            telemetry.export_trace(p1)
            telemetry.export_trace(p2)
            merged = telemetry.merge_traces([p1, p2])
            self.assertEqual(merged["otherData"]["events_dropped"], 24)

    def test_clean_window_never_warns(self):
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            self._reduction_chain()
        self.assertEqual(
            [w for w in caught
             if issubclass(w.category, telemetry.TimelineDroppedWarning)], []
        )


# ----------------------------------------------------------------------
# pairing robustness on adversarial streams (satellite 2)
# ----------------------------------------------------------------------
class TestPairingRobustness(TracelensCase):
    def test_async_pairs_duplicate_cids_last_dispatch_wins(self):
        d1 = {"kind": "dispatch", "ts": 0.1, "cid": 7, "cids": [7], "roots": 1}
        d2 = {"kind": "dispatch", "ts": 0.2, "cid": 7, "cids": [7], "roots": 1}
        s = {"kind": "blocking_sync", "ts": 0.3, "cid": 7, "dur": 0.01}
        pairs = telemetry.async_pairs([d1, d2, s])
        self.assertEqual(len(pairs), 1)
        self.assertIs(pairs[0][0], d2)

    def test_async_pairs_orphans_drop_out(self):
        evs = [
            {"kind": "dispatch", "ts": 0.1, "cid": 1, "cids": [1], "roots": 1},
            {"kind": "blocking_sync", "ts": 0.2, "cid": 99, "dur": 0.01},
            {"kind": "blocking_sync", "ts": 0.3},  # no cid at all
        ]
        self.assertEqual(telemetry.async_pairs(evs), [])

    def _random_soup(self, rng, n=40):
        """An adversarial stream: shuffled order, orphan syncs, duplicate
        cids, unstamped durs, unmatched span begins, garbage timestamps."""
        evs = []
        for _ in range(n):
            roll = rng.integers(0, 8)
            ts = float(rng.uniform(0, 1.0))
            cid = int(rng.integers(1, 6))
            if roll == 0:
                evs.append({"kind": "dispatch", "ts": ts, "cid": cid,
                            "cids": [cid, cid + 1], "roots": 2, "program": f"p{cid}"})
            elif roll == 1:
                ev = {"kind": "blocking_sync", "ts": ts, "cid": cid, "where": "item"}
                if rng.integers(0, 2):
                    ev["dur"] = float(rng.uniform(0, 0.05))
                evs.append(ev)
            elif roll == 2:
                evs.append({"kind": "compile", "ts": ts, "cid": cid,
                            "family": f"f{cid % 2}", "program": f"p{cid}"})
            elif roll == 3:
                evs.append({"kind": "span_begin", "ts": ts, "name": "loop"})
            elif roll == 4:
                evs.append({"kind": "span_end", "ts": ts, "name": "loop", "dur": 0.1})
            elif roll == 5:
                evs.append({"kind": "collective", "ts": ts, "op": "allreduce"})
            elif roll == 6:
                evs.append({"kind": "blocking_sync", "ts": float("nan"), "cid": cid})
            else:
                evs.append({"kind": "fused_collective", "ts": ts, "op": "reshard",
                            "cid": cid, "detail": f"split={int(rng.integers(0, 2))}"})
        rng.shuffle(evs)
        return evs

    def test_analyze_invariants_hold_on_adversarial_streams(self):
        # property-style: whatever the soup, the accounting stays closed —
        # non-negative buckets, buckets + unattributed == window, critical
        # path inside the window, and no crash
        for seed in range(20):
            rng = np.random.default_rng(seed)
            evs = self._random_soup(rng)
            ana = tracelens.analyze(evs)
            window = ana["window_s"]
            self.assertGreaterEqual(window, 0.0, f"seed {seed}")
            for bucket, rec in ana["attribution"]["overall"].items():
                self.assertGreaterEqual(rec["s"], -1e-9, f"seed {seed}: {bucket}")
            self.assertGreaterEqual(
                ana["attribution"]["unattributed_s"], -1e-9, f"seed {seed}"
            )
            self.assertAlmostEqual(
                _bucket_sum(ana) + ana["attribution"]["unattributed_s"],
                window, places=5, msg=f"seed {seed}",
            )
            self.assertLessEqual(
                ana["critical_path"]["total_s"], window + 1e-6, f"seed {seed}"
            )
            json.dumps(ana)  # the whole analysis stays JSON-serializable
            tracelens.render(ana)

    def test_sync_without_dispatch_is_sync_wait_not_device(self):
        evs = [{"kind": "blocking_sync", "ts": 0.1, "cid": 5, "dur": 0.2,
                "where": "drain"}]
        ana = tracelens.analyze(evs)
        overall = ana["attribution"]["overall"]
        self.assertIn("sync_wait", overall)
        self.assertNotIn("device_execute", overall)

    def test_dispatch_without_sync_is_not_provably_idle(self):
        evs = [
            {"kind": "dispatch", "ts": 0.0, "cid": 1, "cids": [1], "roots": 1},
            {"kind": "collective", "ts": 1.0, "op": "allreduce"},
        ]
        ana = tracelens.analyze(evs)
        overall = ana["attribution"]["overall"]
        self.assertIn("host_async", overall)
        self.assertNotIn("host_gap", overall)
        self.assertEqual(
            [f for f in ana["findings"] if f["rule"] == "tracelens.device_idle"], []
        )


# ----------------------------------------------------------------------
# CLI: analyze / --against / --json / --allow-partial (tentpole + CI)
# ----------------------------------------------------------------------
class TestCLI(TracelensCase):
    @property
    def _cli(self):
        # the package attribute `heat_tpu.telemetry` resolves to the CORE
        # module; the CLI shim must be imported by its module path
        return importlib.import_module("heat_tpu.telemetry")

    def _export(self, td, name="trace.json"):
        self._reduction_chain()
        path = os.path.join(td, name)
        telemetry.export_trace(path)
        return path

    def test_analyze_clean_trace_exits_zero(self):
        with tempfile.TemporaryDirectory() as td:
            path = self._export(td)
            out = io.StringIO()
            rc = self._cli.main(["analyze", path], out=out)
            text = out.getvalue()
        self.assertEqual(rc, 0, text)
        self.assertIn("time attribution:", text)
        self.assertIn("critical path", text)

    def test_analyze_json_is_machine_checkable(self):
        with tempfile.TemporaryDirectory() as td:
            path = self._export(td)
            out = io.StringIO()
            rc = self._cli.main(["analyze", path, "--json"], out=out)
            doc = json.loads(out.getvalue())
        self.assertEqual(rc, 0)
        self.assertLessEqual(doc["attribution"]["unattributed_pct"], 5.0)
        self.assertEqual(doc["findings"], [])
        self.assertIn("critical_path", doc)

    def test_against_self_is_clean_and_regression_gates(self):
        with tempfile.TemporaryDirectory() as td:
            path = self._export(td)
            out = io.StringIO()
            rc = self._cli.main(["analyze", path, "--against", path], out=out)
            self.assertEqual(rc, 0, out.getvalue())

            # a degraded "new" trace: the same window plus a sync storm —
            # the diff must flag the new finding and exit nonzero
            evs = [{"kind": "span_begin", "ts": 0.0, "name": "loop"}]
            for i in range(40):
                evs.append({"kind": "blocking_sync", "ts": 0.01 * (i + 1),
                            "where": "item", "dur": 0.001})
            evs.append({"kind": "span_end", "ts": 0.9, "name": "loop", "dur": 0.9})
            bad = os.path.join(td, "bad.json")
            with open(bad, "w") as fh:
                json.dump({"traceEvents": telemetry.trace_events(evs, pid=0),
                           "otherData": {"events_dropped": 0}}, fh)
            out = io.StringIO()
            rc = self._cli.main(["analyze", bad, "--against", path], out=out)
            text = out.getvalue()
        self.assertEqual(rc, 1, text)
        self.assertIn("sync_storm", text)

    def test_against_accepts_saved_analysis(self):
        with tempfile.TemporaryDirectory() as td:
            path = self._export(td)
            out = io.StringIO()
            self._cli.main(["analyze", path, "--json"], out=out)
            saved = os.path.join(td, "analysis.json")
            with open(saved, "w") as fh:
                fh.write(out.getvalue())
            out = io.StringIO()
            rc = self._cli.main(["analyze", path, "--against", saved], out=out)
        self.assertEqual(rc, 0, out.getvalue())

    def test_malformed_input_exits_two(self):
        with tempfile.TemporaryDirectory() as td:
            bad = os.path.join(td, "bad.json")
            with open(bad, "w") as fh:
                fh.write("{not json")
            out = io.StringIO()
            self.assertEqual(self._cli.main(["analyze", bad], out=out), 2)
            self.assertIn("ERROR", out.getvalue())
            notrace = os.path.join(td, "notatrace.json")
            with open(notrace, "w") as fh:
                json.dump({"hello": 1}, fh)
            out = io.StringIO()
            self.assertEqual(self._cli.main(["analyze", notrace], out=out), 2)

    def test_truncated_trace_refused_unless_allow_partial(self):
        with tempfile.TemporaryDirectory() as td:
            doc = {
                "traceEvents": [
                    {"ph": "i", "s": "t", "cat": "collective", "name": "allreduce",
                     "pid": 0, "tid": 0, "ts": 0.0, "args": {}},
                    {"ph": "i", "s": "t", "cat": "collective", "name": "allreduce",
                     "pid": 0, "tid": 0, "ts": 1000.0, "args": {}},
                ],
                "otherData": {"events_dropped": 3},
            }
            path = os.path.join(td, "truncated.json")
            with open(path, "w") as fh:
                json.dump(doc, fh)
            out = io.StringIO()
            self.assertEqual(self._cli.main(["analyze", path], out=out), 2)
            self.assertIn("REFUSED", out.getvalue())
            out = io.StringIO()
            rc = self._cli.main(["analyze", path, "--allow-partial"], out=out)
            self.assertEqual(rc, 0, out.getvalue())  # info caveat doesn't gate
            self.assertIn("PARTIAL", out.getvalue())


# ----------------------------------------------------------------------
# flight-recorder integration (satellite 3)
# ----------------------------------------------------------------------
class TestFlightDiagnosis(TracelensCase):
    def test_dump_bundle_embeds_one_page_diagnosis(self):
        prev = health_runtime.set_flight(True, 256)
        self.addCleanup(lambda: health_runtime.set_flight(*prev))
        telemetry.reset()
        self._reduction_chain()
        with tempfile.TemporaryDirectory() as td:
            dump = health_runtime.dump_flight(
                os.path.join(td, "bundle.json"), reason="test"
            )
            with open(dump["path"]) as fh:
                bundle = json.load(fh)
        diag = bundle.get("diagnosis")
        self.assertIsInstance(diag, dict, bundle.keys())
        self.assertNotIn("error", diag, diag)
        self.assertIn("trace window", diag["text"])
        self.assertIn("attribution", diag)
        self.assertIsInstance(diag["findings"], list)
        # a ring is a window by construction: the diagnosis never refuses
        self.assertIn("unattributed_pct", diag)

    def test_diagnose_never_raises_on_garbage(self):
        self.assertIn("error", tracelens.diagnose([]))
        out = tracelens.diagnose([{"kind": "collective"}])  # no ts at all
        self.assertIsInstance(out, dict)


# ----------------------------------------------------------------------
# post-hoc purity: never forces, never initializes (acceptance)
# ----------------------------------------------------------------------
class TestAnalyzerPurity(TracelensCase):
    @unittest.skipUnless(fusion.active(), "fusion disabled via HEAT_TPU_FUSION")
    def test_analyze_never_forces_a_pending_chain(self):
        a = self._split_input()
        x = ht.exp(a * 0.5) + 1.0
        self.assertTrue(fusion.is_deferred(x))
        tracelens.analyze()
        tracelens.render(tracelens.analyze())
        self.assertTrue(fusion.is_deferred(x), "analyze forced the chain")

    def test_analyzer_never_initializes_the_backend(self):
        # the health-layer subprocess pattern: a full analyze + render over
        # synthetic events must not bring up a mesh
        code = (
            "from heat_tpu.core import tracelens\n"
            "evs = [\n"
            "    {'kind': 'dispatch', 'ts': 0.0, 'cid': 1, 'cids': [1],\n"
            "     'roots': 1, 'program': 'p1'},\n"
            "    {'kind': 'compile', 'ts': 0.01, 'cid': 1, 'program': 'p1'},\n"
            "    {'kind': 'blocking_sync', 'ts': 0.0, 'cid': 1, 'dur': 0.1,\n"
            "     'where': 'item'},\n"
            "]\n"
            "ana = tracelens.analyze(evs)\n"
            "tracelens.render(ana)\n"
            "tracelens.diff(ana, ana)\n"
            "from heat_tpu.core import communication\n"
            "assert communication.MESH_WORLD is None, 'backend was initialized'\n"
            "print('OK')\n"
        )
        env = dict(os.environ)
        env.setdefault("JAX_PLATFORMS", "cpu")
        out = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True, text=True, env=env, cwd=_REPO,
        )
        self.assertEqual(out.returncode, 0, out.stderr)
        self.assertIn("OK", out.stdout)


# ----------------------------------------------------------------------
# diff semantics
# ----------------------------------------------------------------------
class TestDiff(TracelensCase):
    def test_self_diff_is_clean(self):
        self._reduction_chain()
        ana = tracelens.analyze()
        delta = tracelens.diff(ana, ana)
        self.assertTrue(delta["ok"], delta)
        self.assertEqual(delta["new_findings"], [])
        self.assertEqual(delta["bucket_shifts_pts"], {})

    def test_unattributed_growth_is_a_regression(self):
        self._reduction_chain()
        ana = tracelens.analyze()
        worse = json.loads(json.dumps(ana))
        worse["attribution"]["unattributed_pct"] = (
            ana["attribution"]["unattributed_pct"] + 10.0
        )
        delta = tracelens.diff(ana, worse)
        self.assertFalse(delta["ok"])
        self.assertTrue(
            any("unattributed" in r for r in delta["regressions"]), delta
        )

    def test_critical_path_growth_is_a_regression(self):
        self._reduction_chain()
        ana = tracelens.analyze()
        worse = json.loads(json.dumps(ana))
        worse["critical_path"]["total_s"] = ana["critical_path"]["total_s"] * 3.0
        delta = tracelens.diff(ana, worse)
        self.assertFalse(delta["ok"])
        self.assertGreater(delta["critical_path_growth_pct"], 100.0)


if __name__ == "__main__":
    unittest.main()
