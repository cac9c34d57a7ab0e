"""ML estimator tests: clustering, classification, regression,
preprocessing, spatial distances, graph Laplacian (reference pattern:
per-subpackage tests/ with synthetic data)."""

import numpy as np

import jax.numpy as jnp

import heat_tpu as ht

from test_suites.basic_test import TestCase


class TestSpatial(TestCase):
    def setUp(self):
        np.random.seed(11)
        self.x = np.random.randn(20, 4).astype(np.float32)
        self.y = np.random.randn(12, 4).astype(np.float32)

    def test_cdist(self):
        from scipy.spatial.distance import cdist as scipy_cdist

        expected = scipy_cdist(self.x, self.y)
        for split in (None, 0):
            X = ht.array(self.x, split=split)
            Y = ht.array(self.y)
            for quad in (False, True):
                got = ht.spatial.cdist(X, Y, quadratic_expansion=quad)
                np.testing.assert_allclose(got.numpy(), expected, rtol=1e-3, atol=1e-4)
        # X ≡ Y symmetry path
        X = ht.array(self.x, split=0)
        d = ht.spatial.cdist(X)
        np.testing.assert_allclose(d.numpy(), scipy_cdist(self.x, self.x), rtol=1e-3, atol=1e-4)

    def test_manhattan_rbf(self):
        from scipy.spatial.distance import cdist as scipy_cdist

        X = ht.array(self.x, split=0)
        Y = ht.array(self.y)
        np.testing.assert_allclose(
            ht.spatial.manhattan(X, Y).numpy(),
            scipy_cdist(self.x, self.y, metric="cityblock"),
            rtol=1e-4,
        )
        sigma = 2.0
        d2 = scipy_cdist(self.x, self.y) ** 2
        np.testing.assert_allclose(
            ht.spatial.rbf(X, Y, sigma=sigma).numpy(),
            np.exp(-d2 / (2 * sigma * sigma)),
            rtol=1e-3,
            atol=1e-5,
        )


class TestClustering(TestCase):
    def _blobs(self):
        return ht.utils.data.create_spherical_dataset(
            num_samples_cluster=64, radius=0.5, offset=6.0, random_state=5
        )

    def test_kmeans(self):
        data = self._blobs()
        km = ht.cluster.KMeans(n_clusters=4, init="kmeans++", max_iter=100, random_state=3)
        km.fit(data)
        self.assertEqual(km.cluster_centers_.shape, (4, 3))
        labels = km.labels_.numpy()
        self.assertEqual(labels.shape, (256,))
        # every ground-truth block maps to a single cluster
        for b in range(4):
            blk = labels[b * 64 : (b + 1) * 64]
            self.assertEqual(len(np.unique(blk)), 1)
        # blocks map to distinct clusters
        self.assertEqual(len(np.unique([labels[b * 64] for b in range(4)])), 4)
        self.assertIsNotNone(km.inertia_)
        # predict on the same data reproduces labels
        np.testing.assert_array_equal(km.predict(data).numpy(), labels)

    def test_kmeans_random_init_and_dndarray_init(self):
        data = self._blobs()
        km = ht.cluster.KMeans(n_clusters=4, init="random", max_iter=50, random_state=1)
        km.fit(data)
        self.assertEqual(km.cluster_centers_.shape, (4, 3))
        init = km.cluster_centers_
        km2 = ht.cluster.KMeans(n_clusters=4, init=init, max_iter=10)
        km2.fit(data)
        self.assertEqual(km2.cluster_centers_.shape, (4, 3))
        with self.assertRaises(ValueError):
            ht.cluster.KMeans(n_clusters=4, init="bogus").fit(data)

    def test_kmedians_kmedoids(self):
        data = self._blobs()
        for cls in (ht.cluster.KMedians, ht.cluster.KMedoids):
            est = cls(n_clusters=4, init="kmeans++", random_state=7)
            est.fit(data)
            labels = est.labels_.numpy()
            for b in range(4):
                blk = labels[b * 64 : (b + 1) * 64]
                self.assertEqual(len(np.unique(blk)), 1, f"{cls.__name__} split cluster")
        # medoids are actual data points
        est = ht.cluster.KMedoids(n_clusters=4, random_state=7).fit(data)
        dat = data.numpy()
        for c in est.cluster_centers_.numpy():
            self.assertTrue(np.any(np.all(np.isclose(dat, c, atol=1e-5), axis=1)))

    def test_spectral(self):
        data = self._blobs()
        sp = ht.cluster.Spectral(
            n_clusters=4, gamma=0.1, metric="rbf", n_lanczos=40, assign_labels="kmeans"
        )
        sp.fit(data)
        labels = sp.labels_.numpy()
        self.assertEqual(labels.shape, (256,))
        # spectral on well-separated blobs: blocks are pure
        purity = np.mean(
            [np.max(np.bincount(labels[b * 64 : (b + 1) * 64])) / 64 for b in range(4)]
        )
        self.assertGreater(purity, 0.9)


class TestClassification(TestCase):
    def test_knn(self):
        np.random.seed(13)
        train = np.concatenate(
            [np.random.randn(30, 2) + 4, np.random.randn(30, 2) - 4]
        ).astype(np.float32)
        labels = np.concatenate([np.zeros(30), np.ones(30)]).astype(np.int32)
        test = np.array([[4.0, 4.0], [-4.0, -4.0], [5.0, 3.0]], dtype=np.float32)
        knn = ht.classification.KNeighborsClassifier(n_neighbors=5)
        knn.fit(ht.array(train, split=0), ht.array(labels, split=0))
        pred = knn.predict(ht.array(test))
        np.testing.assert_array_equal(pred.numpy(), [0, 1, 0])


class TestGaussianNB(TestCase):
    def test_fit_predict_vs_sklearn_math(self):
        np.random.seed(17)
        x0 = np.random.randn(50, 3) + np.array([3, 0, 0])
        x1 = np.random.randn(50, 3) + np.array([-3, 0, 0])
        X = np.concatenate([x0, x1]).astype(np.float32)
        y = np.concatenate([np.zeros(50), np.ones(50)]).astype(np.int32)
        nb = ht.naive_bayes.GaussianNB()
        nb.fit(ht.array(X, split=0), ht.array(y, split=0))
        pred = nb.predict(ht.array(X, split=0))
        acc = (pred.numpy() == y).mean()
        self.assertGreater(acc, 0.95)
        probs = nb.predict_proba(ht.array(X[:5]))
        np.testing.assert_allclose(probs.numpy().sum(axis=1), 1.0, rtol=1e-5)
        # partial_fit merge equals one-shot fit
        nb2 = ht.naive_bayes.GaussianNB()
        nb2.partial_fit(ht.array(X[:40], split=0), ht.array(y[:40]), classes=ht.array([0, 1]))
        nb2.partial_fit(ht.array(X[40:], split=0), ht.array(y[40:]))
        np.testing.assert_allclose(nb2.theta_.numpy(), nb.theta_.numpy(), rtol=1e-4)
        np.testing.assert_allclose(nb2.var_.numpy(), nb.var_.numpy(), rtol=1e-3)


class TestLasso(TestCase):
    def test_fit_recovers_sparse_coefficients(self):
        np.random.seed(19)
        n, f = 200, 8
        X = np.random.randn(n, f).astype(np.float32)
        beta = np.array([2.0, 0, 0, -3.0, 0, 0, 1.5, 0], dtype=np.float32)
        y = X @ beta + 0.01 * np.random.randn(n).astype(np.float32)
        lasso = ht.regression.Lasso(lam=0.2, max_iter=200, tol=1e-8)
        lasso.fit(ht.array(X, split=0), ht.array(y, split=0))
        coef = lasso.coef_.numpy().ravel()
        # support recovery
        self.assertTrue(np.all(np.abs(coef[[1, 2, 4, 5, 7]]) < 0.1))
        self.assertTrue(np.all(np.abs(coef[[0, 3, 6]]) > 0.5))
        # coefficient values match sklearn's coordinate descent (same
        # mean-scale objective): spot-check against known shrinkage
        from sklearn.linear_model import Lasso as SkLasso

        sk = SkLasso(alpha=0.2).fit(X, y)
        np.testing.assert_allclose(coef, sk.coef_, atol=1e-2)
        pred = lasso.predict(ht.array(X, split=0))
        self.assertLess(lasso.rmse(ht.array(y), pred), 1.0)


class TestPreprocessing(TestCase):
    def setUp(self):
        np.random.seed(23)
        self.x = (np.random.randn(40, 5) * np.array([1, 10, 0.1, 5, 2]) + 7).astype(np.float32)

    def test_standard_scaler(self):
        for split in (None, 0):
            X = ht.array(self.x, split=split)
            sc = ht.preprocessing.StandardScaler()
            out = sc.fit_transform(X)
            np.testing.assert_allclose(out.numpy().mean(axis=0), 0.0, atol=1e-5)
            np.testing.assert_allclose(out.numpy().std(axis=0), 1.0, atol=1e-4)
            back = sc.inverse_transform(out)
            np.testing.assert_allclose(back.numpy(), self.x, rtol=1e-4)

    def test_minmax_scaler(self):
        X = ht.array(self.x, split=0)
        sc = ht.preprocessing.MinMaxScaler(feature_range=(0.0, 1.0))
        out = sc.fit_transform(X)
        np.testing.assert_allclose(out.numpy().min(axis=0), 0.0, atol=1e-6)
        np.testing.assert_allclose(out.numpy().max(axis=0), 1.0, atol=1e-6)
        back = sc.inverse_transform(out)
        np.testing.assert_allclose(back.numpy(), self.x, rtol=1e-4)

    def test_normalizer(self):
        X = ht.array(self.x, split=0)
        out = ht.preprocessing.Normalizer(norm="l2").fit_transform(X)
        np.testing.assert_allclose(np.linalg.norm(out.numpy(), axis=1), 1.0, rtol=1e-5)

    def test_maxabs_robust(self):
        X = ht.array(self.x, split=0)
        out = ht.preprocessing.MaxAbsScaler().fit_transform(X)
        self.assertLessEqual(np.abs(out.numpy()).max(), 1.0 + 1e-6)
        rs = ht.preprocessing.RobustScaler()
        out = rs.fit_transform(X)
        np.testing.assert_allclose(np.median(out.numpy(), axis=0), 0.0, atol=1e-5)


class TestGraph(TestCase):
    def test_laplacian(self):
        np.random.seed(29)
        x = np.random.randn(16, 3).astype(np.float32)
        X = ht.array(x, split=0)
        lap = ht.graph.Laplacian(
            lambda a: ht.spatial.rbf(a, sigma=1.0, quadratic_expansion=True), definition="norm_sym"
        )
        L = lap.construct(X)
        l_np = L.numpy()
        # symmetric, unit diagonal, eigenvalues in [0, 2]
        np.testing.assert_allclose(l_np, l_np.T, atol=1e-5)
        np.testing.assert_allclose(np.diag(l_np), 1.0, atol=1e-5)
        w = np.linalg.eigvalsh(l_np)
        self.assertGreater(w.min(), -1e-5)
        self.assertLess(w.max(), 2 + 1e-5)

    def test_simple_laplacian_rowsum_zero(self):
        x = np.random.randn(10, 3).astype(np.float32)
        X = ht.array(x, split=0)
        lap = ht.graph.Laplacian(
            lambda a: ht.spatial.rbf(a, sigma=1.0), definition="simple"
        )
        L = lap.construct(X)
        np.testing.assert_allclose(L.numpy().sum(axis=1), 0.0, atol=1e-4)


class TestBaseEstimator(TestCase):
    def test_params_roundtrip(self):
        km = ht.cluster.KMeans(n_clusters=3, max_iter=10)
        params = km.get_params()
        self.assertEqual(params["n_clusters"], 3)
        km.set_params(n_clusters=5)
        self.assertEqual(km.n_clusters, 5)
        with self.assertRaises(ValueError):
            km.set_params(bogus=1)
        self.assertTrue(ht.is_estimator(km))
        self.assertTrue(ht.is_clusterer(km))
        self.assertFalse(ht.is_classifier(km))
        knn = ht.classification.KNeighborsClassifier()
        self.assertTrue(ht.is_classifier(knn))
        self.assertTrue(ht.is_transformer(ht.preprocessing.StandardScaler()))
        self.assertTrue(ht.is_regressor(ht.regression.Lasso()))
        self.assertIn("KMeans", repr(km))


if __name__ == "__main__":
    import unittest

    unittest.main()


class TestSparseEncoders(TestCase):
    """ISSUE 18: transforms that EMIT sparse outputs — one-hot and
    TF-IDF return DCSR matrices, register as serving ``transform``
    endpoints, and stream host-resident inputs with stage_out
    writeback."""

    def _codes(self, n=30, seed=40):
        rng = np.random.default_rng(seed)
        return np.stack(
            [rng.integers(0, 4, n), rng.integers(10, 13, n), rng.integers(-2, 1, n)],
            axis=1,
        ).astype(np.int32)

    def test_onehot_sparse_output_matches_dense_oracle(self):
        codes = self._codes()
        enc = ht.preprocessing.OneHotEncoder().fit(codes)
        out = enc.transform(codes)
        self.assertIsInstance(out, ht.sparse.DCSR_matrix)
        self.assertEqual(out.shape, (30, enc.n_features_out_))
        self.assertEqual(out.nnz, 30 * 3)  # exactly one 1.0 per (row, feature)
        dense = out.todense().numpy()
        # invert: each feature block's argmax recovers the code
        for f, cats in enumerate(enc.categories_):
            lo = int(enc._offsets[f])
            block = dense[:, lo : lo + len(cats)]
            np.testing.assert_array_equal(cats[block.argmax(1)], codes[:, f])
            np.testing.assert_allclose(block.sum(1), 1.0)

    def test_onehot_unknown_category_encodes_zero_block(self):
        codes = self._codes()
        enc = ht.preprocessing.OneHotEncoder().fit(codes)
        probe = codes[:2].copy()
        probe[0, 1] = 99  # unseen at fit time
        dense = enc.transform(probe).todense().numpy()
        lo = int(enc._offsets[1])
        hi = int(enc._offsets[2])
        np.testing.assert_array_equal(dense[0, lo:hi], 0.0)
        self.assertAlmostEqual(float(dense[1].sum()), 3.0)

    def test_onehot_serving_program_and_endpoint(self):
        codes = self._codes()
        enc = ht.preprocessing.OneHotEncoder().fit(codes)
        spec = enc.serving_program()
        run = spec["build"]()
        batch = jnp.asarray(codes[:8])
        got = np.asarray(run(batch, *spec["args"]))
        ref = enc.transform(codes[:8]).todense().numpy()
        np.testing.assert_array_equal(got, ref)
        # and the public endpoint constructor accepts the transformer
        ep = ht.serving.transform_endpoint(enc, buckets=(8,))
        self.assertEqual(ep.name, "onehot-transform")

    def test_onehot_stream_transform_writeback(self):
        codes = self._codes(n=200, seed=41)
        enc = ht.preprocessing.OneHotEncoder().fit(codes)
        streamed = enc.stream_transform(codes, slab=1 << 10)  # many windows
        ref = enc.transform(codes).todense().numpy()
        np.testing.assert_array_equal(streamed, ref)

    def _counts(self, n=25, v=12, seed=42):
        rng = np.random.default_rng(seed)
        counts = rng.poisson(0.6, (n, v)).astype(np.float32)
        counts[0] = 0  # an empty document: norm must not divide by zero
        return counts

    def test_tfidf_matches_sklearn_formula(self):
        counts = self._counts()
        tf = ht.preprocessing.TfidfTransformer().fit(counts)
        out = tf.transform(counts)
        self.assertIsInstance(out, ht.sparse.DCSR_matrix)
        n, v = counts.shape
        df = (counts > 0).sum(0)
        idf = np.log((1.0 + n) / (1.0 + df)) + 1.0
        ref = counts * idf[None, :]
        norms = np.linalg.norm(ref, axis=1, keepdims=True)
        ref = np.divide(ref, norms, out=np.zeros_like(ref), where=norms > 0)
        np.testing.assert_allclose(out.todense().numpy(), ref, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(tf.idf_, idf.astype(np.float32), rtol=1e-6)

    def test_tfidf_preserves_sparsity_pattern(self):
        counts = self._counts(seed=43)
        out = ht.preprocessing.TfidfTransformer().fit(counts).transform(counts)
        self.assertEqual(out.nnz, int((counts != 0).sum()))

    def test_tfidf_serving_and_stream_agree(self):
        counts = self._counts(n=150, seed=44)
        tf = ht.preprocessing.TfidfTransformer().fit(counts)
        ref = tf.transform(counts).todense().numpy()
        spec = tf.serving_program()
        run = spec["build"]()
        got = np.asarray(run(jnp.asarray(counts), *spec["args"]))
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)
        streamed = tf.stream_transform(counts, slab=1 << 11)
        np.testing.assert_allclose(streamed, ref, rtol=1e-5, atol=1e-6)
        ep = ht.serving.transform_endpoint(tf, buckets=(8,))
        self.assertEqual(ep.name, "tfidf-transform")

    def test_fit_validation(self):
        enc = ht.preprocessing.OneHotEncoder()
        with self.assertRaises(TypeError):
            enc.fit(np.zeros((4, 2), np.float32))  # float codes rejected
        with self.assertRaises(RuntimeError):
            enc.transform(self._codes())
        enc.fit(self._codes())
        with self.assertRaises(ValueError):
            enc.transform(self._codes()[:, :2])
        tf = ht.preprocessing.TfidfTransformer()
        with self.assertRaises(RuntimeError):
            tf.transform(self._counts())
        with self.assertRaises(ValueError):
            ht.preprocessing.TfidfTransformer(norm="l1")
