"""The bincount and histogram paths against NumPy on the CPU mesh (the
one-hot branch is steered as TPUs take it)."""

import numpy as np

import heat_tpu  # noqa: F401 - establishes the mesh


class TestFastBincount:
    def test_bincount_paths_agree(self):
        import heat_tpu as ht

        rng = np.random.default_rng(2)
        vals = rng.integers(0, 40, 5000).astype(np.int32)
        res = ht.bincount(ht.array(vals), minlength=50).numpy()
        np.testing.assert_array_equal(res, np.bincount(vals, minlength=50))
        w = rng.random(5000).astype(np.float32)
        res = ht.bincount(ht.array(vals), weights=ht.array(w)).numpy()
        np.testing.assert_allclose(res, np.bincount(vals, weights=w), rtol=1e-4)

    def test_onehot_branch_agrees_with_scatter(self, monkeypatch):
        # the CPU test backend normally takes the scatter branch; force the
        # one-hot branch so its numerics are covered too
        import jax

        from heat_tpu.core import statistics as st

        rng = np.random.default_rng(7)
        idx = np.asarray(rng.integers(0, 30, 4000), dtype=np.int32)
        import jax.numpy as jnp

        expect = np.bincount(idx, minlength=30)
        with monkeypatch.context() as m:
            m.setattr(jax, "default_backend", lambda: "tpu")
            got = st._fast_bincount(jnp.asarray(idx), 30)
            np.testing.assert_array_equal(np.asarray(got), expect)
            w = rng.random(4000).astype(np.float32)
            got_w = st._fast_bincount(jnp.asarray(idx), 30, jnp.asarray(w))
            np.testing.assert_allclose(np.asarray(got_w), np.bincount(idx, weights=w), rtol=1e-4)

    def test_histogram_matches_numpy(self):
        import heat_tpu as ht

        rng = np.random.default_rng(3)
        x = rng.standard_normal(20000).astype(np.float32)
        for kwargs in [
            {"bins": 17},
            {"bins": 10, "range": (-1.0, 1.0)},
            {"bins": 12, "density": True},
        ]:
            h, e = ht.histogram(ht.array(x), **kwargs)
            hn, en = np.histogram(x, **kwargs)
            np.testing.assert_allclose(h.numpy(), hn, rtol=1e-4, atol=1e-6)
            np.testing.assert_allclose(e.numpy(), en, rtol=1e-5, atol=1e-6)

    def test_histogram_weighted(self):
        import heat_tpu as ht

        rng = np.random.default_rng(4)
        x = rng.standard_normal(5000).astype(np.float32)
        w = rng.random(5000).astype(np.float32)
        h, e = ht.histogram(ht.array(x), bins=9, weights=ht.array(w))
        hn, en = np.histogram(x, bins=9, weights=w)
        np.testing.assert_allclose(h.numpy(), hn, rtol=1e-4)

