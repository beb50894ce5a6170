"""Tests for the ML layer: spatial, cluster, graph, classification,
naive_bayes, regression (reference models: heat/{spatial,cluster,...}/tests)."""

import numpy as np
import pytest
from scipy.spatial.distance import cdist as scipy_cdist

import heat_tpu as ht

from harness import TestCase


def make_blobs(n_per=32, centers=((0, 0), (6, 6), (0, 6)), std=0.6, seed=0):
    rng = np.random.default_rng(seed)
    pts, labels = [], []
    for i, c in enumerate(centers):
        pts.append(rng.normal(c, std, size=(n_per, len(c))))
        labels += [i] * n_per
    X = np.concatenate(pts).astype(np.float32)
    y = np.array(labels)
    perm = rng.permutation(len(y))
    return X[perm], y[perm]


class TestSpatial(TestCase):
    def test_cdist_oracle(self):
        rng = np.random.default_rng(0)
        a = rng.random((16, 4)).astype(np.float32)
        b = rng.random((24, 4)).astype(np.float32)
        expected = scipy_cdist(a, b)
        for sa in (None, 0):
            for sb in (None, 0):
                d = ht.spatial.cdist(ht.array(a, split=sa), ht.array(b, split=sb))
                np.testing.assert_allclose(d.numpy(), expected, rtol=1e-4, atol=1e-4)
                d = ht.spatial.cdist(
                    ht.array(a, split=sa), ht.array(b, split=sb), quadratic_expansion=True
                )
                np.testing.assert_allclose(d.numpy(), expected, rtol=1e-3, atol=1e-3)
        # symmetric (Y=None) — ring path when split
        ds = ht.spatial.cdist(ht.array(a, split=0))
        np.testing.assert_allclose(ds.numpy(), scipy_cdist(a, a), rtol=1e-4, atol=1e-4)
        self.assertEqual(ds.split, 0)

    def test_ring_vs_local_consistency(self):
        # both operands split and divisible -> exercises the ppermute ring
        rng = np.random.default_rng(1)
        a = rng.random((16, 3)).astype(np.float32)
        b = rng.random((8, 3)).astype(np.float32)
        d = ht.spatial.cdist(ht.array(a, split=0), ht.array(b, split=0))
        np.testing.assert_allclose(d.numpy(), scipy_cdist(a, b), rtol=1e-4, atol=1e-4)

    def test_rbf_manhattan(self):
        rng = np.random.default_rng(2)
        a = rng.random((8, 3)).astype(np.float32)
        sigma = 2.0
        expected = np.exp(-scipy_cdist(a, a) ** 2 / (2 * sigma**2))
        for quad in (False, True):
            r = ht.spatial.rbf(ht.array(a, split=0), sigma=sigma, quadratic_expansion=quad)
            np.testing.assert_allclose(r.numpy(), expected, rtol=1e-3, atol=1e-4)
        m = ht.spatial.manhattan(ht.array(a, split=0))
        np.testing.assert_allclose(
            m.numpy(), scipy_cdist(a, a, metric="cityblock"), rtol=1e-4, atol=1e-4
        )
        with pytest.raises(NotImplementedError):
            ht.spatial.cdist(ht.arange(4))
        with pytest.raises(ValueError):
            ht.spatial.cdist(ht.ones((4, 2)), ht.ones((4, 3)))


def _cluster_accuracy(pred, true, k):
    """Best-permutation match fraction (cluster ids are arbitrary)."""
    from itertools import permutations

    best = 0.0
    for perm in permutations(range(k)):
        mapped = np.array([perm[p] for p in pred])
        best = max(best, float(np.mean(mapped == true)))
    return best


class TestCluster(TestCase):
    def test_kmeans(self):
        X, y = make_blobs()
        for split in (None, 0):
            x = ht.array(X, split=split)
            km = ht.cluster.KMeans(n_clusters=3, init="kmeans++", max_iter=50, random_state=5)
            km.fit(x)
            self.assertEqual(km.cluster_centers_.shape, (3, 2))
            labels = km.labels_.numpy()
            self.assertGreater(_cluster_accuracy(labels, y, 3), 0.95)
            pred = km.predict(x).numpy()
            np.testing.assert_array_equal(pred, labels)
            self.assertIsNotNone(km.inertia_)
            self.assertGreater(km.n_iter_, 0)
        # get/set params (estimator API)
        params = km.get_params()
        self.assertEqual(params["n_clusters"], 3)
        km.set_params(n_clusters=4)
        self.assertEqual(km.n_clusters, 4)
        with pytest.raises(ValueError):
            ht.cluster.KMeans(init="bogus").fit(ht.array(X))
        with pytest.raises(ValueError):
            km.fit(X)

    def test_kmeans_fused_path_matches_jnp(self):
        # the sharded fused-pallas dispatch (``lloyd.takes`` pretended, the
        # kernel interpreted on the CPU mesh): same fixed point and labels as
        # the jnp path the CPU takes. Only split=0 — a replicated operand on a
        # multi-device mesh has no fused dispatch.
        import unittest.mock

        from heat_tpu.ops import lloyd

        X, y = make_blobs()
        x = ht.array(X, split=0)
        ref = ht.cluster.KMeans(n_clusters=3, init="kmeans++", max_iter=50, random_state=5)
        self.assertIsNone(ref._fused_mode(x)[0])
        ref.fit(x)
        with unittest.mock.patch.object(lloyd, "takes", lambda shape, k, split, sharding: "sharded"):
            got = ht.cluster.KMeans(n_clusters=3, init="kmeans++", max_iter=50, random_state=5)
            self.assertEqual(got._fused_mode(x), ("sharded", True))
            got.fit(x)
        self.assertGreater(_cluster_accuracy(got.labels_.numpy(), y, 3), 0.95)
        np.testing.assert_array_equal(got.labels_.numpy(), ref.labels_.numpy())
        np.testing.assert_allclose(
            got.cluster_centers_.numpy(), ref.cluster_centers_.numpy(), rtol=1e-4, atol=1e-4
        )
        np.testing.assert_allclose(got.inertia_, ref.inertia_, rtol=1e-3)

    def test_kmeans_fused_ragged_rows(self):
        # prime row count: the sharded kernel must mask the physical pad
        import unittest.mock

        from heat_tpu.ops import lloyd

        rng = np.random.default_rng(12)
        X = np.concatenate(
            [rng.normal(0, 0.3, (101, 3)), rng.normal(4, 0.3, (102, 3))]
        ).astype(np.float32)
        y = np.array([0] * 101 + [1] * 102)
        x = ht.array(X, split=0)
        with unittest.mock.patch.object(lloyd, "takes", lambda shape, k, split, sharding: "sharded"):
            km = ht.cluster.KMeans(n_clusters=2, random_state=3)
            self.assertEqual(km._fused_mode(x)[0], "sharded")
            km.fit(x)
        self.assertGreater(_cluster_accuracy(km.labels_.numpy(), y, 2), 0.99)
        self.assertEqual(km.labels_.shape[0], 203)

    def test_kmeans_fused_backend_failure_propagates(self):
        # a pallas kernel that fails to lower or run raises out of fit():
        # there is no path from a kernel exception to the jnp path
        import unittest.mock

        from heat_tpu.ops import lloyd

        X, _ = make_blobs()
        with unittest.mock.patch.object(
            lloyd, "takes", lambda shape, k, split, sharding: "sharded"
        ), unittest.mock.patch.object(
            lloyd, "fused_lloyd_run_sharded", side_effect=RuntimeError("mosaic")
        ):
            km = ht.cluster.KMeans(n_clusters=3, random_state=5, max_iter=50)
            with pytest.raises(RuntimeError, match="mosaic"):
                km.fit(ht.array(X, split=0))

    def test_kmeans_precomputed_init(self):
        X, y = make_blobs()
        init = ht.array(np.array([[0.0, 0.0], [6.0, 6.0], [0.0, 6.0]], dtype=np.float32))
        km = ht.cluster.KMeans(n_clusters=3, init=init, max_iter=20)
        km.fit(ht.array(X, split=0))
        self.assertGreater(_cluster_accuracy(km.labels_.numpy(), y, 3), 0.95)
        with pytest.raises(ValueError):
            ht.cluster.KMeans(n_clusters=5, init=init)

    def test_kmedians_kmedoids(self):
        X, y = make_blobs()
        x = ht.array(X, split=0)
        for cls in (ht.cluster.KMedians, ht.cluster.KMedoids):
            est = cls(n_clusters=3, init="kmeans++", random_state=3)
            est.fit(x)
            self.assertGreater(_cluster_accuracy(est.labels_.numpy(), y, 3), 0.9)
        # medoids are actual data points
        med = ht.cluster.KMedoids(n_clusters=3, init="kmeans++", random_state=3)
        med.fit(x)
        centers = med.cluster_centers_.numpy()
        for c in centers:
            self.assertTrue(np.any(np.all(np.isclose(X, c, atol=1e-5), axis=1)))

    def test_spectral(self):
        X, y = make_blobs(n_per=20, std=0.4, seed=4)
        x = ht.array(X, split=0)
        sp = ht.cluster.Spectral(
            n_clusters=3, gamma=0.5, n_lanczos=30, random_state=7, init="kmeans++"
        )
        sp.fit(x)
        self.assertGreater(_cluster_accuracy(sp.labels_.numpy(), y, 3), 0.85)
        with pytest.raises(NotImplementedError):
            ht.cluster.Spectral(metric="cosine")
        with pytest.raises(ValueError):
            sp.fit(X)


class TestGraph(TestCase):
    def test_laplacian(self):
        X, _ = make_blobs(n_per=8)
        x = ht.array(X, split=0)
        lap = ht.graph.Laplacian(
            lambda z: ht.spatial.rbf(z, sigma=1.0, quadratic_expansion=True),
            definition="norm_sym",
        )
        L = lap.construct(x).numpy()
        # symmetric, unit diagonal, eigenvalues in [0, 2]
        np.testing.assert_allclose(L, L.T, atol=1e-5)
        np.testing.assert_allclose(np.diag(L), 1.0, atol=1e-5)
        ev = np.linalg.eigvalsh(L)
        self.assertGreater(ev.min(), -1e-4)
        self.assertLess(ev.max(), 2.0 + 1e-4)
        simple = ht.graph.Laplacian(
            lambda z: ht.spatial.rbf(z, sigma=1.0), definition="simple"
        ).construct(x).numpy()
        np.testing.assert_allclose(simple.sum(axis=1), 0.0, atol=1e-4)
        with pytest.raises(NotImplementedError):
            ht.graph.Laplacian(lambda z: z, definition="rw")


class TestClassification(TestCase):
    def test_knn(self):
        X, y = make_blobs(seed=8)
        split_at = 64
        for split in (None, 0):
            xtr = ht.array(X[:split_at], split=split)
            ytr = ht.array(y[:split_at].astype(np.int32), split=split)
            xte = ht.array(X[split_at:], split=split)
            knn = ht.classification.KNeighborsClassifier(n_neighbors=5)
            knn.fit(xtr, ytr)
            pred = knn.predict(xte).numpy()
            self.assertGreater(np.mean(pred == y[split_at:]), 0.9)
        # one-hot labels path
        onehot = np.eye(3, dtype=np.float32)[y[:split_at]]
        knn = ht.classification.KNeighborsClassifier(n_neighbors=5)
        knn.fit(ht.array(X[:split_at]), ht.array(onehot))
        pred = knn.predict(ht.array(X[split_at:])).numpy()
        self.assertGreater(np.mean(pred == y[split_at:]), 0.9)
        with pytest.raises(ValueError):
            knn.fit(ht.array(X[:10]), ht.array(y[:5].astype(np.int32)))
        with pytest.raises(RuntimeError):
            ht.classification.KNeighborsClassifier().predict(xte)


class TestNaiveBayes(TestCase):
    def test_gaussian_nb(self):
        X, y = make_blobs(seed=9)
        split_at = 64
        for split in (None, 0):
            xtr = ht.array(X[:split_at], split=split)
            ytr = ht.array(y[:split_at].astype(np.int32), split=split)
            xte = ht.array(X[split_at:], split=split)
            nb = ht.naive_bayes.GaussianNB()
            nb.fit(xtr, ytr)
            pred = nb.predict(xte).numpy()
            self.assertGreater(np.mean(pred == y[split_at:]), 0.9)
        proba = nb.predict_proba(xte).numpy()
        np.testing.assert_allclose(proba.sum(axis=1), 1.0, rtol=1e-4)
        lp = nb.predict_log_proba(xte).numpy()
        np.testing.assert_allclose(np.exp(lp), proba, rtol=1e-4, atol=1e-30)
        # partial_fit in two batches converges to similar params
        nb2 = ht.naive_bayes.GaussianNB()
        nb2.partial_fit(
            ht.array(X[:32]), ht.array(y[:32].astype(np.int32)), classes=ht.array([0, 1, 2])
        )
        nb2.partial_fit(ht.array(X[32:split_at]), ht.array(y[32:split_at].astype(np.int32)))
        pred2 = nb2.predict(ht.array(X[split_at:])).numpy()
        self.assertGreater(np.mean(pred2 == y[split_at:]), 0.85)
        # sample weights change the estimates
        w = np.ones(split_at, np.float32)
        w[:10] = 100.0
        nbw = ht.naive_bayes.GaussianNB()
        nbw.fit(xtr, ytr, sample_weight=w)
        nbu = ht.naive_bayes.GaussianNB()
        nbu.fit(xtr, ytr)
        self.assertFalse(np.allclose(np.asarray(nbw.theta_), np.asarray(nbu.theta_)))
        with pytest.raises(ValueError):
            ht.naive_bayes.GaussianNB(priors=ht.array([0.5, 0.6, 0.2])).fit(xtr, ytr)
        with pytest.raises(RuntimeError):
            ht.naive_bayes.GaussianNB().predict(xte)


def _numpy_lasso_cd(X, y, lam, max_iter, tol):
    """Oracle: the reference's exact coordinate-descent (lasso.py:150-171)."""
    n, m = X.shape
    theta = np.zeros(m, dtype=np.float64)
    for _ in range(max_iter):
        old = theta.copy()
        for j in range(m):
            X_j = X[:, j]
            y_est = X @ theta
            rho = np.mean(X_j * (y - y_est + theta[j] * X_j))
            if j == 0:
                theta[j] = rho
            else:
                theta[j] = np.sign(rho) * max(abs(rho) - lam, 0.0)
        if np.sqrt(np.mean((theta - old) ** 2)) < tol:
            break
    return theta


class TestRegression(TestCase):
    def test_lasso(self):
        rng = np.random.default_rng(10)
        n, m = 80, 6
        X = rng.standard_normal((n, m)).astype(np.float32)
        X[:, 0] = 1.0  # intercept feature, reference convention
        true_coef = np.array([0.5, 2.0, -1.5, 0.0, 0.0, 1.0], dtype=np.float32)
        yv = (X @ true_coef + 0.01 * rng.standard_normal(n)).astype(np.float32)
        expected = _numpy_lasso_cd(X.astype(np.float64), yv.astype(np.float64), 0.01, 200, 1e-6)
        for split in (None, 0):
            x = ht.array(X, split=split)
            y = ht.array(yv, split=split)
            lasso = ht.regression.Lasso(lam=0.01, max_iter=200)
            lasso.fit(x, y)
            theta = lasso.theta.numpy().reshape(-1)
            # parity with the reference algorithm
            np.testing.assert_allclose(theta, expected, atol=1e-3)
            self.assertAlmostEqual(float(lasso.intercept_.item()), expected[0], places=3)
            pred = lasso.predict(x).numpy().reshape(-1)
            np.testing.assert_allclose(pred, X @ expected, atol=1e-2)
        # strong penalty sparsifies the non-intercept coefficients
        hard = ht.regression.Lasso(lam=5.0, max_iter=100)
        hard.fit(ht.array(X), ht.array(yv))
        self.assertTrue(np.count_nonzero(np.abs(hard.coef_.numpy()) > 1e-3) < m - 1)
        with pytest.raises(TypeError):
            lasso.fit(X, yv)
        with pytest.raises(RuntimeError):
            ht.regression.Lasso().predict(ht.array(X))


class TestBatchParallelInit(TestCase):
    def test_batchparallel_recovers_blobs(self):
        # scalable init: per-device kmeans++ + one (p*k, f) candidate gather
        p = self.get_size()
        rng = np.random.default_rng(0)
        blobs = np.concatenate(
            [rng.standard_normal((40 * max(p, 2), 4)) + c * 8 for c in range(4)]
        )
        rng.shuffle(blobs)
        x = ht.array(blobs, split=0)
        km = ht.cluster.KMeans(n_clusters=4, init="batchparallel", max_iter=50).fit(x)
        centers = np.sort(km.cluster_centers_.numpy()[:, 0])
        np.testing.assert_allclose(centers, [0, 8, 16, 24], atol=1.5)

    def test_batchparallel_falls_back_single_device(self):
        # ragged or single-device inputs quietly use the kmeans++ path
        rng = np.random.default_rng(1)
        x = ht.array(rng.standard_normal((4 * self.get_size() + 1, 3)), split=0)
        km = ht.cluster.KMeans(n_clusters=2, init="batchparallel", max_iter=10).fit(x)
        self.assertEqual(km.cluster_centers_.shape, (2, 3))
