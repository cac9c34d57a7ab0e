"""Tests for ``ht.jit`` — the fused-program surface (no reference analog;
the reference is torch-eager throughout; one fused program closes the
per-op dispatch gap)."""

import numpy as np
import pytest

import heat_tpu as ht

from test_suites.basic_test import TestCase


class TestHtJit(TestCase):
    def test_elementwise_chain_matches_eager(self):
        x = ht.random.randn(257, 3, split=0)  # odd length exercises padding

        def chain(y):
            return ht.exp(ht.sin(y) * 2.0 + y)

        fused = ht.jit(chain)
        out = fused(x)
        ref = chain(x)
        self.assertEqual(out.split, ref.split)
        self.assertEqual(out.shape, ref.shape)
        np.testing.assert_allclose(out.numpy(), ref.numpy(), rtol=1e-5, atol=1e-5)

    def test_matmul_reduction_sharded(self):
        x = ht.random.randn(64, 8, split=0)

        @ht.jit
        def gram_rows(y):
            g = ht.matmul(y, ht.transpose(y))
            return ht.sum(g, axis=1)

        out = gram_rows(x)
        ref = ht.sum(ht.matmul(x, ht.transpose(x)), axis=1)
        self.assertEqual(out.split, ref.split)
        np.testing.assert_allclose(out.numpy(), ref.numpy(), rtol=1e-4, atol=1e-4)

    def test_resplit_inside(self):
        x = ht.random.randn(32, 16, split=0)
        fused = ht.jit(lambda y: ht.mean(y.resplit(1), axis=0))
        ref = ht.mean(x.resplit(1), axis=0)
        np.testing.assert_allclose(fused(x).numpy(), ref.numpy(), rtol=1e-5, atol=1e-5)

    def test_pytree_in_out(self):
        a = ht.arange(12, split=0).astype(ht.float32)
        b = ht.ones((12,), split=0)

        @ht.jit
        def f(pair, scale):
            s = pair["a"] + pair["b"] * scale
            return {"sum": s, "total": ht.sum(s)}

        out = f({"a": a, "b": b}, 3.0)
        np.testing.assert_allclose(
            out["sum"].numpy(), np.arange(12, dtype=np.float32) + 3.0
        )
        self.assertAlmostEqual(float(out["total"]), float(np.sum(np.arange(12) + 3.0)), places=3)

    def test_single_program_and_cache(self):
        calls = [0]

        def chain(y):
            calls[0] += 1
            return ht.sqrt(ht.abs(y)) + 1.0

        fused = ht.jit(chain)
        x = ht.random.randn(64, split=0)
        fused(x)
        fused(x + 1.0)  # same signature: no retrace
        self.assertEqual(calls[0], 1)
        self.assertEqual(len(fused._ht_jit_cache), 1)
        fused(ht.random.randn(32, split=0))  # new shape: retrace
        self.assertEqual(calls[0], 2)
        self.assertEqual(len(fused._ht_jit_cache), 2)

    def test_static_scalar_keys_cache(self):
        fused = ht.jit(lambda y, p: y**p)
        x = ht.full((8,), 2.0, split=0)
        np.testing.assert_allclose(fused(x, 2).numpy(), np.full(8, 4.0))
        np.testing.assert_allclose(fused(x, 3).numpy(), np.full(8, 8.0))
        self.assertEqual(len(fused._ht_jit_cache), 2)

    def test_resplit_physical_sharding_under_jit(self):
        # jax.device_put on a Tracer is not a binding constraint (the
        # sharding is silently dropped); communication.place must lower to
        # with_sharding_constraint under trace so split metadata and the
        # physical layout stay in sync
        x = ht.random.randn(64, 8, split=0)
        out = ht.jit(lambda y: y.resplit(1))(x)
        self.assertEqual(out.split, 1)
        eager = x.resplit(1)
        self.assertEqual(
            {s.data.shape for s in out._phys.addressable_shards},
            {s.data.shape for s in eager._phys.addressable_shards},
        )

    def test_data_dependent_op_raises_helpfully(self):
        x = ht.array([1.0, 0.0, 2.0, 0.0], split=0)
        fused = ht.jit(lambda y: ht.nonzero(y))
        with pytest.raises(TypeError, match="ht.jit"):
            fused(x)

    def test_estimator_predict_under_jit(self):
        # estimators compose with ht.jit: a fitted model's predict traces
        # into one program (labels keep their split and values)
        rng = np.random.default_rng(4)
        x = ht.array(rng.standard_normal((96, 3)).astype(np.float32), split=0)
        km = ht.cluster.KMeans(n_clusters=3, init="kmeans++", random_state=0).fit(x)
        fused_predict = ht.jit(km.predict)
        out = fused_predict(x)
        ref = km.predict(x)
        self.assertEqual(out.split, ref.split)
        np.testing.assert_array_equal(out.numpy(), ref.numpy())

    def test_preprocessing_pipeline_under_jit(self):
        rng = np.random.default_rng(5)
        x = ht.array(rng.standard_normal((64, 6)).astype(np.float32), split=0)

        @ht.jit
        def pipeline(a):
            sc = ht.preprocessing.StandardScaler(copy=False)
            z = sc.fit_transform(a)
            rb = ht.preprocessing.RobustScaler(copy=False)
            return rb.fit_transform(z)

        ref = ht.preprocessing.RobustScaler(copy=False).fit_transform(
            ht.preprocessing.StandardScaler(copy=False).fit_transform(x)
        )
        np.testing.assert_allclose(
            pipeline(x).numpy(), ref.numpy(), rtol=1e-4, atol=1e-5
        )

    def test_mixed_dtypes_and_int_output(self):
        x = ht.random.randn(40, split=0)

        @ht.jit
        def f(y):
            return ht.argmax(y), y * 2.0

        idx, doubled = f(x)
        self.assertEqual(int(idx), int(np.argmax(x.numpy())))
        np.testing.assert_allclose(doubled.numpy(), x.numpy() * 2.0, rtol=1e-6)

    # ---- donation + closure guard (VERDICT r4 #7 / ADVICE r4) ---- #
    def test_donation_frees_input_buffer(self):
        f = ht.jit(lambda y: y * 2.0 + 1.0, donate_argnums=(0,))
        x = ht.arange(1000, dtype=ht.float32, split=0)
        phys = x._phys
        out = f(x)
        # the donated input buffer must actually be reused/deleted —
        # the live-buffer criterion from the r4 limitation note
        self.assertTrue(phys.is_deleted())
        np.testing.assert_allclose(out.numpy(), np.arange(1000) * 2.0 + 1.0)
        # cache-hit path donates too
        x2 = ht.arange(1000, dtype=ht.float32, split=0)
        p2 = x2._phys
        f(x2)
        self.assertTrue(p2.is_deleted())

    def test_donation_is_positionally_selective(self):
        g = ht.jit(lambda a, b: a + b, donate_argnums=(1,))
        a = ht.arange(100, dtype=ht.float32)
        b = ht.arange(100, dtype=ht.float32)
        pa, pb = a._phys, b._phys
        out = g(a, b)
        self.assertFalse(pa.is_deleted())
        self.assertTrue(pb.is_deleted())
        np.testing.assert_allclose(out.numpy(), np.arange(100) * 2.0)

    def test_donation_rejects_bad_positions_and_argnames(self):
        with self.assertRaises(TypeError):
            ht.jit(lambda y: y, donate_argnames=("y",))
        f = ht.jit(lambda y: y * 1.0, donate_argnums=(3,))
        with self.assertRaises(ValueError):
            f(ht.arange(4, dtype=ht.float32))

    def test_closure_capture_warns(self):
        import warnings

        cap = ht.arange(8, dtype=ht.float32)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            ht.jit(lambda z: z + cap)(ht.arange(8, dtype=ht.float32))
        self.assertTrue(
            any("closes over DNDarray" in str(x.message) for x in w)
        )

    def test_no_capture_no_warning(self):
        import warnings

        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            ht.jit(lambda z: ht.exp(z))(ht.arange(8, dtype=ht.float32))
        self.assertFalse(any("closes over" in str(x.message) for x in w))

    def test_container_closure_capture_warns(self):
        import warnings

        def outer():
            bag = {"w": ht.arange(6, dtype=ht.float32)}
            return ht.jit(lambda z: z + bag["w"])

        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            outer()(ht.arange(6, dtype=ht.float32))
        self.assertTrue(any("closes over DNDarray" in str(x.message) for x in w))

    def test_attribute_name_no_false_positive(self):
        import warnings

        # module global named like an attribute the fn uses: co_names
        # would flag it; the LOAD_GLOBAL scan must not
        globals()["T"] = ht.arange(4, dtype=ht.float32)
        try:
            with warnings.catch_warnings(record=True) as w:
                warnings.simplefilter("always")
                f = ht.jit(lambda x: ht.transpose(ht.reshape(x, (2, 2))).T)
                f(ht.arange(4, dtype=ht.float32))
            self.assertFalse(any("closes over" in str(x.message) for x in w))
        finally:
            del globals()["T"]

    def test_dndarray_default_argument_warns(self):
        import warnings

        w_default = ht.arange(4, dtype=ht.float32)

        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")

            @ht.jit
            def step(x, wgt=w_default):
                return x * wgt

            step(ht.arange(4, dtype=ht.float32))
        self.assertTrue(any("closes over DNDarray" in str(x.message) for x in w))
