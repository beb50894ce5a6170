"""QR depth: oracle sweeps across all splits/shapes (incl. ragged and
short-wide) plus HLO schedule assertions — the tall-skinny split-0 TSQR must
not all-gather the full operand (reference heat/core/linalg/qr.py:319-1042 is
the spec; its tile-CAQR never gathers the operand either)."""

import re
import warnings

import numpy as np
import pytest

import heat_tpu as ht

from harness import TestCase


class TestQRAllSplits(TestCase):
    def _check(self, m, n, split, seed=0):
        rng = np.random.default_rng(seed)
        a_np = rng.standard_normal((m, n))
        a = ht.array(a_np, split=split)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            Q, R = ht.linalg.qr(a)
        q, r = Q.numpy(), R.numpy()
        k = min(m, n)
        self.assertEqual(q.shape, (m, k))
        self.assertEqual(r.shape, (k, n))
        np.testing.assert_allclose(q @ r, a_np, atol=1e-10)
        np.testing.assert_allclose(q.T @ q, np.eye(k), atol=1e-10)
        self.assertLess(np.abs(np.tril(r, -1)).max(), 1e-12)
        return Q, R

    def test_tall_skinny_divisible(self):
        p = self.get_size()
        Q, R = self._check(8 * p, 6, 0)
        self.assertEqual(Q.split, 0)
        self.assertEqual(R.split, None)

    def test_tall_skinny_ragged(self):
        p = self.get_size()
        for m in (8 * p + 1, 8 * p + p - 1, 2 * p + 1):
            self._check(m, min(4, m), 0, seed=m)

    def test_split1_all_shapes(self):
        p = self.get_size()
        for (m, n) in [(6 * p, 4 * p), (6 * p + 1, 2 * p + 1), (3 * p + 2, p + 1)]:
            if m < n:
                continue
            Q, R = self._check(m, n, 1, seed=m * n)
            if p > 1:
                self.assertEqual(Q.split, 1)
                self.assertEqual(R.split, 1)

    def test_short_wide(self):
        p = self.get_size()
        for split in (None, 0, 1):
            self._check(p + 1, 4 * p + 3, split, seed=11)

    def test_square_and_none(self):
        p = self.get_size()
        self._check(4 * p, 4 * p, None)
        self._check(4 * p, 4 * p, 0)

    def test_calc_q_false(self):
        a = ht.array(np.random.default_rng(1).standard_normal((4 * self.get_size(), 3)), split=0)
        out = ht.linalg.qr(a, calc_q=False)
        self.assertIsNone(out.Q)
        self.assertEqual(out.R.shape, (3, 3))

    def test_short_wide_large_warns(self):
        p = self.get_size()
        if p == 1:
            self.skipTest("warning only fires for distributed operands")
        import importlib

        qr_mod = importlib.import_module("heat_tpu.core.linalg.qr")
        old = qr_mod._REPLICATED_MAX_ELEMENTS
        qr_mod._REPLICATED_MAX_ELEMENTS = 10
        try:
            a = ht.array(np.random.default_rng(2).standard_normal((p, 3 * p)), split=1)
            with warnings.catch_warnings(record=True) as w:
                warnings.simplefilter("always")
                ht.linalg.qr(a)
            self.assertTrue(any("replicated" in str(x.message).lower() for x in w))
        finally:
            qr_mod._REPLICATED_MAX_ELEMENTS = old

    def test_int_input_promotes(self):
        p = self.get_size()
        a_np = np.arange(8 * p * 3).reshape(8 * p, 3)
        a = ht.array(a_np, split=0)
        Q, R = ht.linalg.qr(a)
        self.assertTrue(ht.core.types.heat_type_is_inexact(Q.dtype))
        np.testing.assert_allclose(Q.numpy() @ R.numpy(), a_np, atol=1e-8)


class TestQRSchedule(TestCase):
    """HLO assertions: the distributed schedules never gather the operand."""

    def test_tsqr_never_gathers_operand(self):
        p = self.get_size()
        if p == 1:
            self.skipTest("schedule only exists on a distributed mesh")
        from heat_tpu.core.linalg.qr import _tsqr_program

        m, n = 64 * p, 8
        comm = self.comm
        fn = _tsqr_program(comm.mesh, comm.axis_name, m // p, n, p, "float64")
        import jax.numpy as jnp

        hlo = fn.lower(jnp.zeros((m, n), jnp.float64)).compile().as_text()
        # every all-gather/all-reduce in the program must move only R-tile
        # volume (p * n * n), never the (m, n) operand
        coll = re.findall(r"(?:all-gather|all-reduce)[^\n]*", hlo)
        self.assertTrue(coll, "TSQR lost its R-factor all-gather")
        for line in coll:
            for shape in re.findall(r"f\d+\[([\d,]+)\]", line):
                elems = int(np.prod([int(d) for d in shape.split(",")]))
                self.assertLessEqual(
                    elems,
                    p * n * n,
                    f"collective moves more than the R tiles: {line[:120]}",
                )

    def test_panel_qr_collective_budget(self):
        p = self.get_size()
        if p == 1:
            self.skipTest("schedule only exists on a distributed mesh")
        from heat_tpu.core.linalg.qr import _panel_program

        m, n = 16 * p, 2 * p
        c = n // p
        comm = self.comm
        fn = _panel_program(comm.mesh, comm.axis_name, m, c, n, p, "float64")
        import jax.numpy as jnp

        hlo = fn.lower(jnp.zeros((m, n), jnp.float64)).compile().as_text()
        # each panel broadcast moves one (m, c) panel (+ its (c, c) R block),
        # possibly fused into a single psum tuple — never the full operand
        coll = re.findall(r"(?:all-gather|all-reduce)[^\n]*", hlo)
        self.assertTrue(coll, "panel loop lost its broadcasts")
        budget = m * c + c * c
        for line in coll:
            for shape in re.findall(r"f\d+\[([\d,]+)\]", line):
                elems = int(np.prod([int(d) for d in shape.split(",")]))
                self.assertLessEqual(
                    elems,
                    budget,
                    f"collective moves more than one panel: {line[:120]}",
                )


class TestQRGuards(TestCase):
    def test_wide_block_never_silently_gathers(self):
        # block = ceil(m/p) < n would make the TSQR R-gather move the FULL
        # operand; such shapes must take the (warned above threshold)
        # replicated fallback instead
        p = self.get_size()
        if p == 1:
            self.skipTest("needs a distributed mesh")
        import importlib

        qr_mod = importlib.import_module("heat_tpu.core.linalg.qr")
        m, n = 2 * p, p + 2  # m >= n but block=2 < n
        a = ht.array(np.random.default_rng(0).standard_normal((m, n)), split=0)
        old = qr_mod._REPLICATED_MAX_ELEMENTS
        qr_mod._REPLICATED_MAX_ELEMENTS = 1
        try:
            with warnings.catch_warnings(record=True) as w:
                warnings.simplefilter("always")
                Q, R = ht.linalg.qr(a)
            self.assertTrue(any("replicated" in str(x.message).lower() for x in w))
        finally:
            qr_mod._REPLICATED_MAX_ELEMENTS = old
        np.testing.assert_allclose(Q.numpy() @ R.numpy(), a.numpy(), atol=1e-10)


class TestCholQR2(TestCase):
    """CholeskyQR2: the MXU-native tall-skinny method (opt-in)."""

    def test_orthonormal_and_reconstructs(self):
        rng = np.random.default_rng(20)
        for shape in ((64, 6), (37, 5)):  # divisible and ragged rows
            a_np = rng.standard_normal(shape).astype(np.float32)
            for split in (None, 0):
                a = ht.resplit(ht.array(a_np), split)
                q, r = ht.linalg.qr(a, method="cholqr2")
                q_np = np.asarray(q.larray)
                r_np = np.asarray(r.larray)
                np.testing.assert_allclose(q_np.T @ q_np, np.eye(shape[1]), atol=2e-4)
                np.testing.assert_allclose(q_np @ r_np, a_np, atol=2e-4)
                assert np.allclose(r_np, np.triu(r_np))  # upper triangular
                if split == 0:
                    assert q.split == 0 and r.split is None

    def test_r_matches_tsqr_up_to_sign(self):
        rng = np.random.default_rng(21)
        a_np = rng.standard_normal((48, 4)).astype(np.float32)
        a = ht.array(a_np, split=0)
        _, r_chol = ht.linalg.qr(a, method="cholqr2")
        _, r_tsqr = ht.linalg.qr(a, method="tsqr")
        # QR is unique up to column signs of Q / row signs of R
        np.testing.assert_allclose(
            np.abs(np.asarray(r_chol.larray)), np.abs(np.asarray(r_tsqr.larray)), rtol=1e-3, atol=1e-4
        )

    def test_calc_q_false(self):
        a = ht.random.randn(32, 3)
        q, r = ht.linalg.qr(a, method="cholqr2", calc_q=False)
        assert q is None and r.shape == (3, 3)

    def test_breakdown_raises(self):
        col = np.arange(24, dtype=np.float32)[:, None]
        a_np = np.concatenate([col, col, col], axis=1)  # rank 1
        with pytest.raises(ValueError, match="cholqr2 broke down"):
            ht.linalg.qr(ht.array(a_np, split=0), method="cholqr2")

    def test_auto_is_the_default_method(self):
        # "auto" is the default (CholeskyQR2's tall work is all GEMMs): a
        # bare qr() on a well-conditioned tall-skinny operand must take the
        # cholqr2 path
        rng = np.random.default_rng(23)
        a_np = rng.standard_normal((64, 4)).astype(np.float32)
        q, r = ht.linalg.qr(ht.array(a_np, split=0))
        assert (np.diag(np.asarray(r.larray)) > 0).all()  # cholqr2 signature
        q_np = np.asarray(q.larray)
        np.testing.assert_allclose(q_np.T @ q_np, np.eye(4), atol=2e-4)

    def test_auto_uses_cholqr2_when_well_conditioned(self):
        rng = np.random.default_rng(22)
        a_np = rng.standard_normal((48, 4)).astype(np.float32)
        a = ht.array(a_np, split=0)
        q, r = ht.linalg.qr(a, method="auto")
        q_np, r_np = np.asarray(q.larray), np.asarray(r.larray)
        np.testing.assert_allclose(q_np.T @ q_np, np.eye(4), atol=2e-4)
        np.testing.assert_allclose(q_np @ r_np, a_np, atol=2e-4)
        # auto must pick cholqr2 here: its R diagonal is positive by
        # construction (Cholesky factors), while TSQR signs are arbitrary
        assert (np.diag(r_np) > 0).all()

    def test_auto_falls_back_on_breakdown(self):
        # rank-1: cholqr2 breaks down; auto must return valid TSQR factors
        # instead of raising
        col = np.arange(24, dtype=np.float32)[:, None]
        a_np = np.concatenate([col, col + 0.001, col - 0.001], axis=1)
        a_np[0] += np.array([1e-4, -1e-4, 2e-4], np.float32)
        q, r = ht.linalg.qr(ht.array(a_np, split=0), method="auto")
        np.testing.assert_allclose(
            np.asarray(q.larray) @ np.asarray(r.larray), a_np, atol=1e-3
        )

    def test_bf16_stream_kernel(self):
        # the raw kernel's half-width stream (stage_qr_marginal's bf16
        # variant): operand/Q stay bfloat16, Gram accumulates f32, the
        # small Cholesky/inverse run f32 — and the probe accepts a
        # well-conditioned operand at bf16's own noise floor
        import importlib
        import jax
        import jax.numpy as jnp

        qr_mod = importlib.import_module("heat_tpu.core.linalg.qr")
        x = jax.random.normal(jax.random.PRNGKey(0), (512, 16), jnp.float32).astype(
            jnp.bfloat16
        )
        q, r, ok = qr_mod._cholqr2_kernel(x)
        assert bool(ok)
        assert q.dtype == jnp.bfloat16 and r.dtype == jnp.float32
        qn = np.asarray(q, np.float32)
        assert np.abs(qn.T @ qn - np.eye(16)).max() < 0.03  # bf16 ulp class
        np.testing.assert_allclose(
            qn @ np.asarray(r), np.asarray(x, np.float32), atol=0.15
        )

    def test_probe_rejects_finite_but_degraded_orthogonality(self):
        # advisor r04#3: a finite Gram Cholesky is NOT sufficient — near the
        # 1/sqrt(eps) conditioning bound Q1 drifts from orthonormal while
        # everything stays finite. The probe must gate on ||Q1^H Q1 - I|| too.
        # The exact operand regime where that window opens is platform- and
        # build-sensitive, so the threshold logic is unit-tested directly.
        import importlib
        import jax.numpy as jnp

        qr_mod = importlib.import_module("heat_tpu.core.linalg.qr")
        probe = qr_mod._cholqr2_probe_ok
        n = 4
        eye = jnp.eye(n, dtype=jnp.float32)
        r_ok = jnp.triu(jnp.ones((n, n), jnp.float32))
        # finite factors, tiny orthogonality error: accept
        assert bool(probe(r_ok, r_ok, eye + 1e-6, eye))
        # finite factors, error past the 0.5 recovery band: reject
        g_bad = eye.at[0, 1].set(0.6)
        assert not bool(probe(r_ok, r_ok, g_bad, eye))
        # non-finite first-pass factor: reject even with a clean-looking g2
        r_nan = r_ok.at[0, 0].set(jnp.nan)
        assert not bool(probe(r_nan, r_ok, eye, eye))
        assert not bool(probe(r_ok, r_nan, eye, eye))

    def test_auto_square_skips_cholqr2_probe(self):
        # a square (or insufficiently tall) operand must NOT run the probe:
        # its (n, n) Gram would be a silent full-size replication
        import importlib
        import unittest.mock

        qr_mod = importlib.import_module("heat_tpu.core.linalg.qr")

        a_np = np.random.default_rng(24).standard_normal((12, 12)).astype(np.float32)
        with unittest.mock.patch.object(
            qr_mod, "_cholqr2_kernel",
            side_effect=AssertionError("auto probed a non-tall operand"),
        ):
            q, r = ht.linalg.qr(ht.array(a_np, split=0), method="auto")
        np.testing.assert_allclose(
            np.asarray(q.larray) @ np.asarray(r.larray), a_np, atol=1e-4
        )

    def test_auto_split1_keeps_panel_layout(self):
        # split=1 R layout must not depend on conditioning: auto always
        # routes the panel path there (R split=1 by contract)
        p = self.get_size()
        if p == 1:
            self.skipTest("panel layout only exists on a distributed mesh")
        a_np = np.random.default_rng(25).standard_normal((8 * p, 2 * p)).astype(np.float32)
        q, r = ht.linalg.qr(ht.array(a_np, split=1), method="auto")
        assert r.split == 1
        np.testing.assert_allclose(q.numpy() @ r.numpy(), a_np, atol=1e-3)

    def test_auto_short_wide_goes_householder(self):
        a_np = np.random.default_rng(23).standard_normal((3, 9)).astype(np.float32)
        q, r = ht.linalg.qr(ht.array(a_np), method="auto")
        np.testing.assert_allclose(
            np.asarray(q.larray) @ np.asarray(r.larray), a_np, atol=1e-4
        )

    def test_validation(self):
        with pytest.raises(ValueError, match="tall operand"):
            ht.linalg.qr(ht.ones((3, 8)), method="cholqr2")
        with pytest.raises(ValueError, match="unknown qr method"):
            ht.linalg.qr(ht.ones((8, 3)), method="nope")

    def test_complex_operand_unitary(self):
        rng = np.random.default_rng(22)
        a_np = (rng.standard_normal((40, 4)) + 1j * rng.standard_normal((40, 4))).astype(
            np.complex64
        )
        q, r = ht.linalg.qr(ht.array(a_np, split=0), method="cholqr2")
        q_np = np.asarray(q.larray)
        np.testing.assert_allclose(q_np.conj().T @ q_np, np.eye(4), atol=3e-4)
        np.testing.assert_allclose(q_np @ np.asarray(r.larray), a_np, atol=3e-4)
