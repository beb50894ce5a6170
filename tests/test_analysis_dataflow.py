"""Distribution-flow verifier (heat_tpu/analysis/dataflow): the lattice,
rules S101-S105 (one true positive + one true negative each, plus the
interprocedural fixtures where the hazard is only visible through a helper
call), loop widening, static cost budgets + exit codes, the CLI (text/JSON,
baseline namespace isolation), the never-initializes/never-forces pins, and
the static-vs-observed byte drift check at the live mesh."""

from __future__ import annotations

import importlib
import io
import json
import os
import subprocess
import sys
import tempfile
import unittest

import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu.analysis import callgraph, dataflow, engine, lattice
from heat_tpu.analysis.lattice import TOP, UNKNOWN, AbstractArray, Const, Scalar
from heat_tpu.core import fusion

from harness import TestCase


@pytest.mark.parametrize("n", [16, 128, 200, 256, 512, 640, 1152, 1664, 2048])
def test_cholqr2_gram_entries_mirror_the_runtime(n):
    """What the verifier prices for a CholeskyQR2 Gram psum is what
    ``core/linalg/qr.py`` records: all of it where the products run whole,
    the upper block triangle where they run by column blocks."""
    qr_mod = importlib.import_module("heat_tpu.core.linalg.qr")
    assert dataflow._cholqr2_gram_entries(n) == qr_mod._gram_entries(n) <= n * n
    assert (qr_mod._gram_entries(n) < n * n) == (qr_mod._block_count(n) > 1)


def rules_of(findings, *, active_only: bool = True):
    return [
        f.rule
        for f in findings
        if not (active_only and (f.suppressed or f.baselined))
    ]


def verify(src, **kw):
    findings, _ = dataflow.verify_source(src, "fixture.py", mesh_size=8, **kw)
    return findings


class TestLattice(TestCase):
    def test_split_join_tops_out_on_disagreement(self):
        a = AbstractArray(rank=2, split=0, shape=(8, 4), dtype="float32")
        b = AbstractArray(rank=2, split=1, shape=(8, 4), dtype="float32")
        j = lattice.join(a, b)
        self.assertIs(j.split, TOP)
        self.assertEqual(j.shape, (8, 4))
        j2 = lattice.join(a, a.with_(shape=(8, 6)))
        self.assertEqual(j2.split, 0)
        self.assertEqual(j2.shape, (8, None))

    def test_join_of_incompatible_kinds_is_unknown(self):
        self.assertIs(lattice.join(AbstractArray(rank=1), Scalar()), UNKNOWN)

    def test_divergence_joins_sticky(self):
        j = lattice.join(Scalar(divergent=True, via_call=True), Scalar())
        self.assertTrue(j.divergent)
        self.assertTrue(j.via_call)

    def test_logical_bytes(self):
        a = AbstractArray(rank=2, split=0, shape=(8, 4), dtype="float64")
        self.assertEqual(lattice.logical_bytes(a), 8 * 4 * 8)
        self.assertIsNone(lattice.logical_bytes(a.with_(shape=(8, None))))

    def test_bcast_shape(self):
        self.assertEqual(lattice.bcast_shape((8, 1), (4,)), (8, 4))
        self.assertEqual(lattice.bcast_shape((8, None), (8, 4)), (8, None))
        self.assertIsNone(lattice.bcast_shape(None, (3,)))


class TestS101ImplicitReshard(TestCase):
    def test_mixed_split_binary_op_flags_with_bytes(self):
        findings = verify(
            """
import heat_tpu as ht
a = ht.ones((512, 64), split=0)
b = ht.ones((512, 64), split=1)
c = a + b
"""
        )
        self.assertEqual(rules_of(findings), ["S101"])
        # 512*64*4 bytes: the resharded (non-dominant) operand's payload
        self.assertIn("131072", findings[0].message)
        self.assertIn("resharded implicitly", findings[0].message)

    def test_hazard_only_visible_through_helper_call(self):
        # the helper itself is clean in isolation; only the mixed-split
        # calling context makes its binary op an implicit reshard
        findings = verify(
            """
import heat_tpu as ht

def combine(u, v):
    return u * v

a = ht.ones((128, 8), split=0)
b = ht.ones((128, 8), split=1)
c = combine(a, b)
"""
        )
        self.assertEqual(rules_of(findings), ["S101"])
        self.assertEqual(findings[0].line, 5)  # flagged at the op, in the helper

    def test_where_with_mixed_splits_flags(self):
        findings = verify(
            """
import heat_tpu as ht
cond = ht.ones((64, 64), split=0)
x = ht.ones((64, 64), split=0)
y = ht.ones((64, 64), split=1)
z = ht.where(cond, x, y)
"""
        )
        self.assertEqual(rules_of(findings), ["S101"])

    def test_same_split_and_replicated_operands_are_clean(self):
        findings = verify(
            """
import heat_tpu as ht
a = ht.ones((64, 64), split=0)
b = ht.ones((64, 64), split=0)
r = ht.ones((64, 64))
c = a + b
d = a + r
e = a * 2.0
"""
        )
        self.assertEqual(rules_of(findings), [])

    def test_broadcast_offset_alignment_is_clean(self):
        # (64, 32) split=1 + (32,) split=0 broadcast-align to the SAME axis
        findings = verify(
            """
import heat_tpu as ht
a = ht.ones((64, 32), split=1)
b = ht.ones((32,), split=0)
c = a + b
"""
        )
        self.assertEqual(rules_of(findings), [])

    def test_explicit_resplit_fix_is_clean(self):
        findings = verify(
            """
import heat_tpu as ht
a = ht.ones((512, 64), split=0)
b = ht.ones((512, 64), split=1)
b = ht.resplit(b, 0)
c = a + b
"""
        )
        self.assertEqual(rules_of(findings), [])

    def test_suppression_same_line_and_line_above(self):
        findings = verify(
            """
import heat_tpu as ht
a = ht.ones((64, 64), split=0)
b = ht.ones((64, 64), split=1)
c = a + b  # heat-lint: disable=S101 -- intended implicit reshard
# heat-lint: disable=S101 -- second site, also intended
d = b + a
"""
        )
        self.assertEqual(rules_of(findings), [])
        self.assertEqual(sum(1 for f in findings if f.suppressed), 2)


class TestS102LoopSyncThroughCall(TestCase):
    def test_blocking_helper_called_in_loop_flags(self):
        findings = verify(
            """
import heat_tpu as ht

def loss(x):
    return float(x.sum())

a = ht.ones((256, 8), split=0)
for i in range(10):
    l = loss(a)
"""
        )
        self.assertEqual(rules_of(findings), ["S102"])
        self.assertEqual(findings[0].line, 9)  # the call site in the loop

    def test_annotated_param_seeds_the_array(self):
        # no concrete caller needed: `x: DNDarray` is enough for the effect
        findings = verify(
            """
import heat_tpu as ht
from heat_tpu.core.dndarray import DNDarray

def loss(x: DNDarray):
    return float(x.sum())

def train(x: DNDarray):
    out = 0.0
    while out < 100.0:
        out = out + loss(x)
    return out
"""
        )
        self.assertEqual(rules_of(findings), ["S102"])

    def test_call_outside_loop_and_nonblocking_helper_are_clean(self):
        findings = verify(
            """
import heat_tpu as ht

def loss(x):
    return float(x.sum())

def step(x):
    return x * 2.0

a = ht.ones((256, 8), split=0)
l = loss(a)
for i in range(10):
    a = step(a)
"""
        )
        self.assertEqual(rules_of(findings), [])

    def test_two_levels_deep(self):
        findings = verify(
            """
import heat_tpu as ht

def inner(x):
    return float(x.mean())

def outer(x):
    return inner(x) + 1.0

a = ht.ones((64,), split=0)
for i in range(3):
    v = outer(a)
"""
        )
        # the loop's call to `outer` carries inner's blocking summary
        self.assertEqual(rules_of(findings), ["S102"])
        self.assertEqual(findings[0].line, 12)


class TestS103SplitDowngrade(TestCase):
    def test_resplit_to_none_of_sharded_value_flags(self):
        findings = verify(
            """
import heat_tpu as ht
a = ht.ones((1024, 16), split=0)
b = ht.resplit(a, None)
"""
        )
        self.assertEqual(rules_of(findings), ["S103"])
        self.assertIn("65536", findings[0].message)  # 1024*16*4 allgathered

    def test_inplace_resplit_default_axis_flags(self):
        findings = verify(
            """
import heat_tpu as ht
a = ht.ones((1024, 16), split=1)
a.resplit_()
"""
        )
        self.assertEqual(rules_of(findings), ["S103"])

    def test_axis_change_and_replicated_source_are_clean(self):
        findings = verify(
            """
import heat_tpu as ht
a = ht.ones((1024, 16), split=0)
b = ht.resplit(a, 1)
r = ht.ones((8, 8))
c = ht.resplit(r, None)
"""
        )
        self.assertEqual(rules_of(findings), [])

    def test_axis_change_still_prices_the_reshard(self):
        _, stats = dataflow.verify_source(
            """
import heat_tpu as ht
a = ht.ones((1024, 16), split=0)
b = ht.resplit(a, 1)
""",
            "fixture.py",
            mesh_size=8,
        )
        region = stats["regions"]["fixture.py::<module>"]
        self.assertEqual(region["cost"].get("reshard"), 1024 * 16 * 4)


class TestS104InterproceduralDivergence(TestCase):
    def test_collective_in_helper_under_divergent_branch(self):
        findings = verify(
            """
from heat_tpu.core import multihost

def helper(x, comm):
    comm.allreduce(x)

def bad(x, comm):
    if multihost.process_index() == 0:
        helper(x, comm)
"""
        )
        self.assertEqual(rules_of(findings), ["S104"])
        self.assertEqual(findings[0].line, 9)  # the call site on the branch

    def test_divergence_via_callee_return(self):
        findings = verify(
            """
from heat_tpu.core import multihost

def is_owner():
    return multihost.process_index() == 0

def bad(x):
    if is_owner():
        y = x.numpy()
"""
        )
        self.assertEqual(rules_of(findings), ["S104"])
        self.assertEqual(findings[0].line, 9)

    def test_early_exit_divergence_through_helper(self):
        findings = verify(
            """
from heat_tpu.core import multihost

def sync_all(x, comm):
    comm.allreduce(x)

def publish(x, comm):
    owner = multihost.io_owner()
    if not owner:
        return
    sync_all(x, comm)
"""
        )
        self.assertEqual(rules_of(findings), ["S104"])

    def test_local_divergence_with_local_collective_is_h001s_job(self):
        # both the divergence and the collective are in one function: H001
        # reports it; S104 must NOT double-report
        findings = verify(
            """
from heat_tpu.core import multihost

def bad(x, comm):
    if multihost.process_index() == 0:
        comm.allreduce(x)
"""
        )
        self.assertEqual(rules_of(findings), [])
        lint = engine.lint_source(
            """
from heat_tpu.core import multihost

def bad(x, comm):
    if multihost.process_index() == 0:
        comm.allreduce(x)
""",
            "fixture.py",
            rules="H001",
        )
        self.assertEqual(rules_of(lint), ["H001"])

    def test_helper_call_on_uniform_path_is_clean(self):
        findings = verify(
            """
def helper(x, comm):
    comm.allreduce(x)

def good(x, comm):
    helper(x, comm)
"""
        )
        self.assertEqual(rules_of(findings), [])


class TestLoopWidening(TestCase):
    def test_split_churn_widens_to_top_no_false_positive(self):
        # x's split alternates per iteration; after widening it is ⊤, and a
        # binary op against a concrete split must NOT claim S101
        findings = verify(
            """
import heat_tpu as ht
a = ht.ones((64, 64), split=0)
x = ht.ones((64, 64), split=0)
for i in range(4):
    x = ht.resplit(x, 1)
    x = x + 1.0
y = a + x
"""
        )
        self.assertEqual(rules_of(findings), [])

    def test_stable_loop_keeps_concrete_state(self):
        # the loop does not change x's layout: the hazard AFTER the loop is
        # still concrete and fires
        findings = verify(
            """
import heat_tpu as ht
a = ht.ones((64, 64), split=0)
x = ht.ones((64, 64), split=1)
for i in range(4):
    x = x * 2.0
y = a + x
"""
        )
        self.assertEqual(rules_of(findings), ["S101"])

    def test_nested_loops_terminate(self):
        findings = verify(
            """
import heat_tpu as ht
x = ht.ones((32, 32), split=0)
for i in range(3):
    for j in range(3):
        x = x + 1.0
    while x is not None:
        x = x * 0.5
"""
        )
        self.assertEqual(rules_of(findings), [])


class TestInterproceduralMachinery(TestCase):
    def test_qr_tuple_unpack_carries_layouts(self):
        findings = verify(
            """
import heat_tpu as ht
a = ht.ones((512, 16), split=0)
b = ht.ones((512, 16), split=1)
q, r = ht.linalg.qr(a)
bad = q + b
"""
        )
        # q inherits a's split=0; q + b(split=1) is the implicit reshard
        self.assertEqual(rules_of(findings), ["S101"])

    def test_estimator_instance_attrs_flow_through_methods(self):
        findings = verify(
            """
import heat_tpu as ht

class Model:
    def __init__(self):
        self.w = ht.ones((64, 8), split=1)

    def apply(self, x):
        return x * self.w

m = Model()
x = ht.ones((64, 8), split=0)
y = m.apply(x)
"""
        )
        self.assertEqual(rules_of(findings), ["S101"])

    def test_callgraph_sccs_order_callees_first(self):
        graph = callgraph.build_from_sources(
            {
                "m.py": """
def a():
    return b()

def b():
    return c()

def c():
    return 1
"""
            }
        )
        order = [fn.name for scc in graph.sccs() for fn in scc]
        self.assertLess(order.index("c"), order.index("b"))
        self.assertLess(order.index("b"), order.index("a"))

    def test_recursion_is_detected_not_looped(self):
        findings = verify(
            """
import heat_tpu as ht

def ping(x, n):
    if n <= 0:
        return x
    return pong(x, n - 1)

def pong(x, n):
    return ping(x * 2.0, n)

a = ht.ones((16,), split=0)
b = ping(a, 3)
"""
        )
        self.assertEqual(rules_of(findings), [])  # terminates, no crash


class TestCostModelAndBudgets(TestCase):
    def test_static_workload_formulas_at_mesh_8(self):
        self.assertEqual(
            dataflow.static_workload_bytes("qr_cholqr2", 8), {"allreduce": 2048}
        )
        self.assertEqual(
            dataflow.static_workload_bytes("qr_cholqr2_blocked", 8),
            {"allreduce": 2 * 3 * 128 * 128 * 4},  # the upper block triangle, twice
        )
        self.assertEqual(
            dataflow.static_workload_bytes("qr_tsqr", 8), {"allgather": 4608}
        )
        self.assertEqual(
            dataflow.static_workload_bytes("solve_triangular", 8),
            {"allreduce": 1280},
        )

    def test_single_device_mesh_prices_zero(self):
        for name in dataflow.DRIFT_WORKLOADS:
            self.assertEqual(dataflow.static_workload_bytes(name, 1), {})

    def test_budget_violation_reports_s105(self):
        findings, _ = dataflow.verify_source(
            """
import heat_tpu as ht

def gather_all(x):
    return ht.resplit(x, None)  # heat-lint: disable=S103 -- fixture

a = ht.ones((4096, 64), split=0)
b = gather_all(a)
""",
            "fixture.py",
            mesh_size=8,
            budgets={"*gather_all": 1024},
        )
        s105 = [f for f in findings if f.rule == "S105"]
        self.assertEqual(len(s105), 1)
        self.assertIn("gather_all", s105[0].message)
        self.assertIn("1024", s105[0].message)

    def test_budget_respected_is_clean(self):
        findings, _ = dataflow.verify_source(
            "import heat_tpu as ht\na = ht.ones((8, 8), split=0)\nb = a + a\n",
            "fixture.py",
            mesh_size=8,
            budgets={"*": 10 * 1024 * 1024},
        )
        self.assertEqual([f for f in findings if f.rule == "S105"], [])

    def test_negative_split_spellings_are_one_axis(self):
        # split=-1 on rank 2 IS axis 1 (the runtime's sanitize_axis): two
        # spellings of one axis must not read as S101 disagreement...
        findings = verify(
            """
import heat_tpu as ht
a = ht.ones((4, 8), split=-1)
b = ht.ones((4, 8), split=1)
c = a + b
"""
        )
        self.assertEqual(rules_of(findings), [])
        # ...while a genuinely different axis still fires
        findings = verify(
            """
import heat_tpu as ht
a = ht.ones((4, 8), split=-1)
b = ht.ones((4, 8), split=0)
c = a + b
"""
        )
        self.assertEqual(rules_of(findings), ["S101"])
        # and resplit(-2 -> same axis as 0) is not a downgrade or a move
        _, stats = dataflow.verify_source(
            "import heat_tpu as ht\n"
            "a = ht.ones((4, 8), split=-2)\n"
            "b = ht.resplit(a, 0)\n",
            "fixture.py",
            mesh_size=8,
        )
        self.assertEqual(stats["regions"], {})

    def test_branch_arms_take_costlier_path_not_sum(self):
        # one 2 MiB reshard in EACH arm of an if/else: the region bound is
        # one arm's bytes, never both
        _, stats = dataflow.verify_source(
            """
import heat_tpu as ht

def f(flag):
    x = ht.ones((1024, 512), split=0)
    if flag:
        y = ht.resplit(x, 1)
    else:
        y = ht.resplit(x, 1)
    return y

f(True)
""",
            "fixture.py",
            mesh_size=8,
        )
        self.assertEqual(
            stats["regions"]["fixture.py::f"]["bytes"], 1024 * 512 * 4
        )

    def test_loop_fixpoint_prices_one_interpretation(self):
        # a stable loop body re-interprets for the fixpoint check but the
        # cost model must price ONE execution of the body
        _, stats = dataflow.verify_source(
            """
import heat_tpu as ht

def f():
    x = ht.ones((1024, 512), split=0)
    for i in range(4):
        y = x.sum()
    return x

f()
""",
            "fixture.py",
            mesh_size=8,
        )
        self.assertEqual(
            stats["regions"]["fixture.py::f"]["cost"].get("reduce.psum"), 4
        )

    def test_blocking_helper_in_while_test_flags_s102(self):
        # the convergence-check shape: the helper call lives in the TEST,
        # which re-evaluates every iteration (H002 counts While tests too)
        findings = verify(
            """
import heat_tpu as ht
from heat_tpu.core.dndarray import DNDarray

def loss(x: DNDarray):
    return float(x.sum())

def train(x: DNDarray):
    while loss(x) > 0.1:
        x = x * 0.5
    return x
"""
        )
        self.assertEqual(rules_of(findings), ["S102"])
        self.assertEqual(findings[0].line, 9)

    def test_total_bytes_counts_callees_exactly_once(self):
        # caller regions merge callee costs; the TOTAL sums only module
        # regions so a helper-bearing workload never double-counts
        _, stats = dataflow.verify_source(
            """
import heat_tpu as ht

def gram(x):
    return ht.resplit(x, None)  # heat-lint: disable=S103 -- fixture

a = ht.ones((128, 64), split=0)
g = gram(a)
""",
            "fixture.py",
            mesh_size=8,
        )
        self.assertEqual(stats["total_bytes"], 128 * 64 * 4)

    def test_drift_entry_incomparable_is_strict_json(self):
        entry = dataflow._drift_entry({"allreduce": 2048}, {})
        self.assertIsNone(entry["ratio"])
        self.assertFalse(entry["within_bound"])
        self.assertNotIn("Infinity", json.dumps(entry))

    def test_parse_budget_arg(self):
        self.assertEqual(dataflow.parse_budget_arg("*fit=2MiB"), ("*fit", 2 << 20))
        self.assertEqual(dataflow.parse_budget_arg("x=4096"), ("x", 4096))
        with self.assertRaises(ValueError):
            dataflow.parse_budget_arg("no-equals")
        with self.assertRaises(ValueError):
            dataflow.parse_budget_arg("x=2furlongs")


class TestVerifyCLI(TestCase):
    def _fixture(self, body: str) -> str:
        fd, path = tempfile.mkstemp(suffix=".py", prefix="heat_verify_fix_")
        with os.fdopen(fd, "w") as fh:
            fh.write(body)
        self.addCleanup(os.unlink, path)
        return path

    def test_dirty_fixture_exits_1_clean_exits_0(self):
        from heat_tpu.analysis.__main__ import main

        dirty = self._fixture(
            "import heat_tpu as ht\n"
            "a = ht.ones((64, 4), split=0)\n"
            "b = ht.ones((64, 4), split=1)\n"
            "c = a + b\n"
        )
        clean = self._fixture(
            "import heat_tpu as ht\na = ht.ones((64, 4), split=0)\nb = a + a\n"
        )
        buf = io.StringIO()
        self.assertEqual(main(["verify", dirty], out=buf), 1)
        self.assertIn("S101", buf.getvalue())
        buf = io.StringIO()
        self.assertEqual(main(["verify", clean], out=buf), 0)

    def test_json_format_parses_with_stats(self):
        from heat_tpu.analysis.__main__ import main

        dirty = self._fixture(
            "import heat_tpu as ht\n"
            "a = ht.ones((64, 4), split=0)\n"
            "b = ht.resplit(a, None)\n"
        )
        buf = io.StringIO()
        self.assertEqual(main(["verify", dirty, "--json"], out=buf), 1)
        doc = json.loads(buf.getvalue())
        self.assertEqual(doc["findings"][0]["rule"], "S103")
        self.assertEqual(doc["summary"]["active"], 1)
        self.assertIn("regions", doc["stats"])
        self.assertEqual(doc["stats"]["mesh_size"], 8)

    def test_budget_flag_and_bad_budget_usage_error(self):
        from heat_tpu.analysis.__main__ import main

        dirty = self._fixture(
            "import heat_tpu as ht\n"
            "a = ht.ones((4096, 64), split=0)\n"
            "b = ht.resplit(a, None)  # heat-lint: disable=S103 -- fixture\n"
        )
        buf = io.StringIO()
        self.assertEqual(main(["verify", dirty, "--budget", "*=1KiB"], out=buf), 1)
        self.assertIn("S105", buf.getvalue())
        buf = io.StringIO()
        self.assertEqual(main(["verify", dirty, "--budget", "broken"], out=buf), 2)

    def test_unknown_rule_is_usage_error(self):
        from heat_tpu.analysis.__main__ import main

        buf = io.StringIO()
        self.assertEqual(main(["verify", "--rules", "S999", "tests"], out=buf), 2)

    def test_rules_verb_lists_both_passes(self):
        from heat_tpu.analysis.__main__ import main

        buf = io.StringIO()
        self.assertEqual(main(["rules"], out=buf), 0)
        text = buf.getvalue()
        for rid in ("H001", "H005", "S101", "S102", "S103", "S104", "S105"):
            self.assertIn(rid, text)

    def test_repo_library_and_examples_verify_clean(self):
        from heat_tpu.analysis.__main__ import main

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        buf = io.StringIO()
        rc = main(
            [
                "verify",
                os.path.join(repo, "heat_tpu", "cluster"),
                os.path.join(repo, "heat_tpu", "regression"),
                os.path.join(repo, "examples"),
            ],
            out=buf,
        )
        self.assertEqual(rc, 0, buf.getvalue())


class TestBaselineNamespaces(TestCase):
    def test_verify_write_preserves_h_entries(self):
        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "base.json")
            h_doc = {
                "version": 1,
                "fingerprints": {"feedc0ffee000000": 1},
                "entries": [
                    {
                        "rule": "H002",
                        "path": "x.py",
                        "line": 3,
                        "source": "float(x)",
                        "fingerprint": "feedc0ffee000000",
                    }
                ],
            }
            with open(path, "w") as fh:
                json.dump(h_doc, fh)
            findings = verify(
                "import heat_tpu as ht\n"
                "a = ht.ones((8, 8), split=0)\n"
                "b = ht.ones((8, 8), split=1)\n"
                "c = a + b\n"
            )
            doc = engine.write_baseline(path, findings, namespaces=("S",))
            rules = sorted(e["rule"] for e in doc["entries"])
            self.assertEqual(rules, ["H002", "S101"])
            self.assertIn("feedc0ffee000000", doc["fingerprints"])
            # rewriting the S namespace again replaces S entries, keeps H
            doc2 = engine.write_baseline(path, [], namespaces=("S",))
            self.assertEqual([e["rule"] for e in doc2["entries"]], ["H002"])

    def test_lint_write_preserves_s_entries(self):
        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "base.json")
            findings = verify(
                "import heat_tpu as ht\n"
                "a = ht.ones((8, 8), split=0)\n"
                "b = ht.ones((8, 8), split=1)\n"
                "c = a + b\n"
            )
            engine.write_baseline(path, findings, namespaces=("S",))
            # now the lint writes ITS namespace over the same file
            lint = engine.lint_source("import time\n", "y.py")
            doc = engine.write_baseline(path, lint, namespaces=("H",))
            self.assertEqual([e["rule"] for e in doc["entries"]], ["S101"])

    def test_verify_baseline_absorbs_known_findings(self):
        src = (
            "import heat_tpu as ht\n"
            "a = ht.ones((8, 8), split=0)\n"
            "b = ht.ones((8, 8), split=1)\n"
            "c = a + b\n"
        )
        findings = verify(src)
        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "base.json")
            engine.write_baseline(path, findings, namespaces=("S",))
            baseline = engine.load_baseline(path)
            fresh = verify(src)
            engine.apply_baseline(fresh, baseline)
            self.assertEqual(rules_of(fresh), [])
            self.assertTrue(all(f.baselined for f in fresh))

    def test_fingerprints_survive_line_shifts(self):
        src = (
            "import heat_tpu as ht\n"
            "a = ht.ones((8, 8), split=0)\n"
            "b = ht.ones((8, 8), split=1)\n"
            "c = a + b\n"
        )
        shifted = "import heat_tpu as ht\n# a comment pushes lines down\n" + src[
            len("import heat_tpu as ht\n"):
        ]
        f1 = verify(src)
        f2 = verify(shifted)
        self.assertEqual(
            [x.fingerprint() for x in f1], [x.fingerprint() for x in f2]
        )
        self.assertNotEqual([x.line for x in f1], [x.line for x in f2])


class TestNeverInitializesOrForces(TestCase):
    def test_verify_never_forces_a_pending_chain(self):
        a = ht.array(np.ones((8 * max(1, self.get_size()), 4), np.float32), split=0)
        pending = a * 2.0 + 1.0
        dataflow.verify_source(
            "import heat_tpu as ht\nx = ht.ones((8, 8), split=0)\ny = x + x\n",
            "fixture.py",
        )
        if fusion.active():
            self.assertTrue(fusion.is_deferred(pending))
        self.assert_array_equal(pending, np.full((8 * max(1, self.get_size()), 4), 3.0, np.float32))

    def test_verify_never_initializes_the_backend(self):
        # a fresh interpreter runs a whole verify (incl. budgets) and the
        # lazy mesh singletons must still be untouched afterwards
        code = (
            "import json, sys\n"
            "from heat_tpu.analysis import dataflow\n"
            "src = 'import heat_tpu as ht\\n'\n"
            "src += 'a = ht.ones((64, 8), split=0)\\n'\n"
            "src += 'b = ht.ones((64, 8), split=1)\\n'\n"
            "src += 'c = a + b\\n'\n"
            "f, stats = dataflow.verify_source(src, 'fix.py', budgets={'*': 1})\n"
            "assert any(x.rule == 'S101' for x in f), f\n"
            "from heat_tpu.core import communication\n"
            "assert communication.MESH_WORLD is None, 'backend was initialized'\n"
            "assert communication._MeshCommunication__default_comm is None if hasattr(communication, '_MeshCommunication__default_comm') else True\n"
            "print('OK')\n"
        )
        env = dict(os.environ)
        env.setdefault("JAX_PLATFORMS", "cpu")
        out = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True,
            text=True,
            env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        self.assertEqual(out.returncode, 0, out.stderr)
        self.assertIn("OK", out.stdout)


class TestRuntimeExplicitReshard(TestCase):
    """The runtime half of S101: `__binary_op` routes identical-shape
    mixed-split operands through the explicit resplit seam — the reshard is
    a recorded collective with telemetry bytes and its fault site, not an
    XLA-internal surprise."""

    def _operands(self):
        rng = np.random.default_rng(3)
        a_np = rng.standard_normal((8 * max(1, self.get_size()), 8)).astype(np.float32)
        b_np = rng.standard_normal(a_np.shape).astype(np.float32)
        return a_np, b_np, ht.array(a_np, split=0), ht.array(b_np, split=1)

    def test_mixed_split_binary_matches_oracle_and_keeps_dominance(self):
        a_np, b_np, a, b = self._operands()
        c = a + b
        self.assertEqual(c.split, 0)  # split dominance unchanged
        self.assert_array_equal(c, a_np + b_np)
        d = b * a
        self.assertEqual(d.split, 1)
        self.assert_array_equal(d, b_np * a_np)

    @staticmethod
    def _reshard_delta(telemetry, before):
        rec = telemetry.collectives().get("reshard", {"count": 0, "bytes": 0})
        return (
            rec["count"] - before.get("count", 0),
            rec["bytes"] - before.get("bytes", 0),
        )

    def test_reshard_records_telemetry_bytes(self):
        from heat_tpu.core import telemetry

        a_np, b_np, a, b = self._operands()
        with telemetry.enabled():
            before = dict(telemetry.collectives().get("reshard", {}))
            (a - b).larray
            count, nbytes = self._reshard_delta(telemetry, before)
        self.assertEqual(count, 1)
        self.assertEqual(nbytes, b_np.size * 4)

    def test_reshard_fault_site_fires(self):
        from heat_tpu.core import resilience

        _, _, a, b = self._operands()
        with resilience.inject("collective.reshard", exc=RuntimeError, times=1):
            with self.assertRaises(RuntimeError):
                _ = a + b

    def test_same_split_and_broadcast_pay_no_reshard(self):
        from heat_tpu.core import telemetry

        a_np, b_np, a, _ = self._operands()
        a2 = ht.array(b_np, split=0)
        row = ht.array(b_np[:1], split=1)  # broadcasted: different shapes
        with telemetry.enabled():
            before = dict(telemetry.collectives().get("reshard", {}))
            (a + a2).larray
            (a + row).larray
            count, _ = self._reshard_delta(telemetry, before)
        self.assertEqual(count, 0)


class TestDriftCheck(TestCase):
    def test_static_within_bound_of_observed_at_live_mesh(self):
        # the acceptance pin: static estimates within DRIFT_FACTOR of
        # telemetry-observed bytes on >= 2 workloads (at mesh 1 both sides
        # are zero and the entries degenerate to ratio 1.0)
        report = dataflow.drift_report()
        self.assertEqual(report["mesh_size"], self.get_size())
        self.assertGreaterEqual(len(report["workloads"]), 2)
        for name, rec in report["workloads"].items():
            self.assertTrue(
                rec["within_bound"],
                f"{name}: static {rec['static_total']} vs observed "
                f"{rec['observed_total']} (ratio {rec['ratio']})",
            )

    def test_compare_observed_round_trip(self):
        report = dataflow.drift_report()
        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "obs.json")
            with open(path, "w") as fh:
                json.dump(report, fh)
            with open(path) as fh:
                loaded = json.load(fh)
        diff = dataflow.compare_observed(loaded)
        self.assertEqual(diff["mesh_size"], self.get_size())
        for rec in diff["workloads"].values():
            self.assertTrue(rec["within_bound"])

    def test_cli_observed_diff(self):
        from heat_tpu.analysis.__main__ import main

        report = dataflow.drift_report()
        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "obs.json")
            with open(path, "w") as fh:
                json.dump(report, fh)
            fixture = os.path.join(d, "clean.py")
            with open(fixture, "w") as fh:
                fh.write("import heat_tpu as ht\na = ht.ones((8, 8), split=0)\n")
            buf = io.StringIO()
            rc = main(["verify", fixture, "--observed", path], out=buf)
            self.assertEqual(rc, 0, buf.getvalue())
            self.assertIn("drift", buf.getvalue())
            # a cooked report that drifts 10x must fail the run
            for rec in report["workloads"].values():
                for op in list(rec["observed"]):
                    rec["observed"][op] *= 10
                rec.pop("static", None)
            bad = os.path.join(d, "bad.json")
            with open(bad, "w") as fh:
                json.dump(report, fh)
            buf = io.StringIO()
            rc = main(["verify", fixture, "--observed", bad], out=buf)
            if self.get_size() > 1:  # at mesh 1 observed stays zero
                self.assertEqual(rc, 1, buf.getvalue())


if __name__ == "__main__":
    unittest.main()
