"""Matmul-native dense factorization suite (ISSUE 19).

The contract, pinned four ways:

1. **Correctness** — polar/eigh/cholesky/lu/solve/svd against their
   defining identities and the numpy/jnp oracles, across splits,
   ragged orders (pad blocks engaged), and complex dtypes.
2. **Movement** — the collective census of each solver's compiled
   program equals its registered plan exactly: ppermute-ring chains
   only, no all-gather of any operand. (The census must trace the FULL
   factor tuple — tracing one factor lets XLA dead-code-eliminate the
   rings that only feed the others.)
3. **Bit-identity** — ``HEAT_TPU_REDIST_OVERLAP=0`` (sequential
   oracle) and ``=1`` (pipelined rings) produce byte-identical factors
   for every solver: the rings only place, select, or accumulate in
   one fixed order, so the knob can only change issue order.
4. **Plans** — ``golden_factorization_plans()`` is deterministic and
   its plan_ids stable, riding the same determinism leg as the
   redistribution plans (scripts/redist_plans.py).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import heat_tpu as ht

from heat_tpu.core.linalg import basics
from heat_tpu.core.linalg import factorizations as F
from heat_tpu.core.linalg.svd import FullMatricesNotSupported
from heat_tpu.redistribution import planner
from heat_tpu.redistribution.staging import HostArray

from test_suites.basic_test import TestCase, env_pin

P = len(jax.devices())

needs_mesh = pytest.mark.skipif(P < 2, reason="needs a real mesh")


def _overlap(mode):
    return env_pin(planner.OVERLAP_ENV, mode)


def _clear_programs():
    """The ring programs cache on (mesh, ..., pipelined); clearing on a
    mode flip forces a rebuild so the env gate is re-read."""
    F._polar_program.cache_clear()
    F._blocked_factor_program.cache_clear()
    F._blocked_solve_program.cache_clear()
    basics._cmatmul_program.cache_clear()


def _spd(n, dtype=np.float32, seed=0, complex_=False):
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((n, n))
    if complex_:
        a = a + 1j * rng.standard_normal((n, n))
    h = a @ a.conj().T / n + np.eye(n) * 2
    return h.astype(dtype)


def _randn(m, n, dtype=np.float32, seed=0):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((m, n)).astype(dtype)


def _wellcond(n, seed=0, diag=3.0):
    """General square matrix with condition number O(1): scaled noise
    (sigma_max ~ 2) around a shifted diagonal. An unscaled randn + c*eye
    draw can land an eigenvalue near zero (cond 1e5 at some seeds) and
    turn a residual check into a conditioning lottery."""
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((n, n)) / np.sqrt(n)
    return (a + np.eye(n) * diag).astype(np.float32)


class TestPolar(TestCase):
    def test_polar_identities_split_sweep(self):
        an = _randn(192, 40, seed=1)
        for split in (None, 0, 1):
            u, h = ht.linalg.polar(ht.array(an, split=split))
            un, hn = np.asarray(u.larray), np.asarray(h.larray)
            np.testing.assert_allclose(un @ hn, an, atol=1e-4)
            np.testing.assert_allclose(un.T @ un, np.eye(40), atol=1e-4)
            # exactly symmetric by construction (symmetrized return)
            np.testing.assert_array_equal(hn, hn.T)
            self.assertEqual(u.split, 0 if split is not None else None)
            self.assertIsNone(h.split)

    def test_polar_ragged_and_tiny(self):
        # m not divisible by p (pad rows), and n < p (devices with
        # all-pad shards): the diag(A, I) pad seeding must keep both
        # exact
        for (m, n) in ((67, 13), (37, 5)):
            an = _randn(m, n, seed=2)
            u, h = ht.linalg.polar(ht.array(an, split=0))
            np.testing.assert_allclose(
                np.asarray(u.larray) @ np.asarray(h.larray), an, atol=1e-4
            )

    def test_polar_left(self):
        an = _randn(24, 96, seed=3)
        u, h = ht.linalg.polar(ht.array(an, split=1), side="left")
        un, hn = np.asarray(u.larray), np.asarray(h.larray)
        np.testing.assert_allclose(hn @ un, an, atol=1e-4)
        np.testing.assert_allclose(un @ un.T, np.eye(24), atol=1e-4)

    def test_polar_validation(self):
        a = ht.array(_randn(8, 16), split=None)
        with self.assertRaises(ValueError):
            ht.linalg.polar(a)  # m < n needs side="left"
        with self.assertRaises(ValueError):
            ht.linalg.polar(a, side="middle")


class TestCholeskyLuDet(TestCase):
    def test_cholesky_matches_oracle(self):
        hn = _spd(96, seed=4)
        for split in (None, 0, 1):
            l = ht.linalg.cholesky(ht.array(hn, split=split))
            ln = np.asarray(l.larray)
            np.testing.assert_allclose(ln @ ln.T, hn, atol=1e-4)
            np.testing.assert_allclose(ln, np.tril(ln), atol=0)

    def test_cholesky_ragged(self):
        hn = _spd(37, seed=5)  # pad blocks engaged on the 8-mesh
        l = ht.linalg.cholesky(ht.array(hn, split=0))
        ln = np.asarray(l.larray)
        np.testing.assert_allclose(ln @ ln.T, hn, atol=1e-4)

    def test_lu_reconstruction(self):
        an = _wellcond(96, seed=6)
        perm, l, u = ht.linalg.lu(ht.array(an, split=0))
        pn = np.asarray(perm.larray)
        ln, un = np.asarray(l.larray), np.asarray(u.larray)
        np.testing.assert_allclose(ln @ un, an[pn], atol=1e-4)
        np.testing.assert_allclose(ln, np.tril(ln), atol=0)
        np.testing.assert_allclose(np.diag(ln), np.ones(96), atol=0)
        np.testing.assert_allclose(un, np.triu(un), atol=0)
        self.assertEqual(sorted(pn.tolist()), list(range(96)))

    @needs_mesh
    def test_det_blocked_path_sign_and_value(self):
        n = max(520, F._EIGH_RESPLIT_MIN_N + 8)
        an = (
            _randn(n, n, seed=7) * 0.002 + np.eye(n, dtype=np.float32) * 1.001
        )
        an[0] *= -1  # odd permutation-free sign flip
        ref = np.linalg.det(an.astype(np.float64))
        for split in (0, 1):
            got = float(np.asarray(ht.linalg.det(ht.array(an, split=split)).larray))
            self.assertLess(abs(got - ref) / abs(ref), 1e-4)

    @needs_mesh
    def test_inv_blocked_path(self):
        n = 520
        an = (_randn(n, n, seed=8) * 0.1 + np.eye(n, dtype=np.float32) * 3)
        ref = np.linalg.inv(an)
        for split in (0, 1):
            iv = ht.linalg.inv(ht.array(an, split=split))
            self.assertEqual(iv.split, split)
            np.testing.assert_allclose(np.asarray(iv.larray), ref, atol=1e-4)


class TestSolve(TestCase):
    def test_solve_gen_and_pos(self):
        n = 96
        an = _wellcond(n, seed=9)
        hn = _spd(n, seed=10)
        bn = _randn(n, 7, seed=11)
        for split in (None, 0, 1):
            a = ht.array(an, split=split)
            b = ht.array(bn, split=0 if split is not None else None)
            x = ht.linalg.solve(a, b)
            np.testing.assert_allclose(an @ np.asarray(x.larray), bn, atol=1e-3)
            xp = ht.linalg.solve(ht.array(hn, split=split), b, assume_a="pos")
            np.testing.assert_allclose(hn @ np.asarray(xp.larray), bn, atol=1e-3)

    def test_solve_vector_rhs(self):
        n = 64
        hn = _spd(n, seed=12)
        bn = _randn(n, 1, seed=13)[:, 0]
        x = ht.linalg.solve(
            ht.array(hn, split=0), ht.array(bn, split=0), assume_a="pos"
        )
        self.assertEqual(x.ndim, 1)
        np.testing.assert_allclose(hn @ np.asarray(x.larray), bn, atol=1e-3)

    def test_solve_validation(self):
        a = ht.array(_spd(16), split=None)
        b = ht.array(_randn(12, 2), split=None)
        with self.assertRaises(ValueError):
            ht.linalg.solve(a, b)  # shape mismatch
        with self.assertRaises(ValueError):
            ht.linalg.solve(a, ht.array(_randn(16, 2), split=None), assume_a="sym")

    def test_solve_host_rhs_streams(self):
        """HostArray RHS: factor once, stream column windows through
        the staged double-buffer, HostArray result (PR 11 composition)."""
        n = 64
        hn = _spd(n, seed=14)
        bn = _randn(n, 96, seed=15)
        x = ht.linalg.solve(
            ht.array(hn, split=0), HostArray(bn), assume_a="pos"
        )
        self.assertIsInstance(x, HostArray)
        out = x.window(0, 0, n)
        np.testing.assert_allclose(hn @ out, bn, atol=1e-3)


class TestEigh(TestCase):
    def test_eigh_matches_oracle(self):
        hn = _spd(96, seed=16) * 3
        ref = np.linalg.eigvalsh(hn)
        for split in (None, 0):
            w, v = ht.linalg.eigh(ht.array(hn, split=split))
            wn, vn = np.asarray(w.larray), np.asarray(v.larray)
            np.testing.assert_allclose(np.sort(wn), ref, rtol=1e-3, atol=1e-4)
            np.testing.assert_allclose(
                vn @ np.diag(wn) @ vn.T, hn, atol=1e-3
            )
            np.testing.assert_allclose(vn.T @ vn, np.eye(96), atol=1e-4)

    def test_eigh_uplo_triangle_only(self):
        hn = _spd(48, seed=17)
        lower = np.tril(hn) + np.triu(_randn(48, 48, seed=18), 1)  # junk upper
        w, _ = ht.linalg.eigh(ht.array(lower, split=0), UPLO="L")
        np.testing.assert_allclose(
            np.sort(np.asarray(w.larray)), np.linalg.eigvalsh(hn),
            rtol=1e-3, atol=1e-4,
        )
        with self.assertRaises(ValueError):
            ht.linalg.eigh(ht.array(hn, split=0), UPLO="X")

    @needs_mesh
    def test_eigh_distributed_recursion(self):
        """Force the divide-and-conquer to RECURSE distributed (not
        fall back to the local eigh of the sub-blocks) by lowering the
        resplit threshold below the first level's branch sizes (~32):
        one distributed recursion level, whose sub-branches solve
        locally. (At 8 every level down to 8x8 compiled its own polar,
        TSQR and ring programs: 91 s for the same code paths.)"""
        hn = _spd(64, seed=19) * 2
        old = F._EIGH_RESPLIT_MIN_N
        F._EIGH_RESPLIT_MIN_N = 24
        try:
            w, v = ht.linalg.eigh(ht.array(hn, split=0))
        finally:
            F._EIGH_RESPLIT_MIN_N = old
        wn, vn = np.asarray(w.larray), np.asarray(v.larray)
        np.testing.assert_allclose(
            np.sort(wn), np.linalg.eigvalsh(hn), rtol=1e-3, atol=1e-4
        )
        np.testing.assert_allclose(vn @ np.diag(wn) @ vn.T, hn, atol=1e-3)


class TestFullSVD(TestCase):
    def test_matches_jnp_svd_one_device(self):
        """The documented-tolerance acceptance pin: a split-0 operand's
        reduced factors match jnp.linalg.svd on the 1-device (local)
        path and both distributed methods to rtol 1e-4."""
        an = _randn(128, 24, seed=20)
        ref_u, ref_s, ref_vh = np.linalg.svd(an, full_matrices=False)
        for kwargs in (
            {"split": None},
            {"split": 0, "method": "qr"},
            {"split": 0, "method": "polar"},
        ):
            split = kwargs.pop("split")
            u, s, vh = ht.linalg.svd(ht.array(an, split=split), **kwargs)
            un, sn, vhn = (
                np.asarray(u.larray), np.asarray(s.larray), np.asarray(vh.larray)
            )
            np.testing.assert_allclose(sn, ref_s, rtol=1e-4, atol=1e-4)
            np.testing.assert_allclose(
                un @ np.diag(sn) @ vhn, an, atol=1e-4
            )
            # factors match the oracle up to per-column phase
            np.testing.assert_allclose(
                np.abs(np.diag(ref_vh @ vhn.conj().T)), np.ones(24), atol=1e-3
            )

    def test_values_only_never_forms_uv(self):
        an = _randn(128, 24, seed=21)
        ref = np.linalg.svd(an, compute_uv=False)
        for method in ("qr", "polar"):
            s = ht.linalg.svd(
                ht.array(an, split=0), compute_uv=False, method=method
            )
            np.testing.assert_allclose(np.asarray(s.larray), ref, rtol=1e-3)
        # full_matrices is irrelevant (and must not raise) without U/V
        s = ht.linalg.svd(
            ht.array(an, split=0), full_matrices=True, compute_uv=False
        )
        np.testing.assert_allclose(np.asarray(s.larray), ref, rtol=1e-3)

    def test_full_matrices_typed_error(self):
        a = ht.array(_randn(32, 8, seed=22), split=0)
        with self.assertRaises(FullMatricesNotSupported) as ctx:
            ht.linalg.svd(a, full_matrices=True)
        msg = str(ctx.exception)
        self.assertIn("hsvd_rank", msg)
        self.assertIn("eigh", msg)
        self.assertTrue(issubclass(FullMatricesNotSupported, NotImplementedError))

    def test_wide_operand(self):
        an = _randn(24, 96, seed=23)
        u, s, vh = ht.linalg.svd(ht.array(an, split=1))
        np.testing.assert_allclose(
            np.asarray(u.larray) @ np.diag(np.asarray(s.larray))
            @ np.asarray(vh.larray),
            an, atol=1e-4,
        )

    def test_host_values_only_gram(self):
        an = _randn(512, 24, seed=24)
        s = ht.linalg.svd(HostArray(an), compute_uv=False)
        ref = np.linalg.svd(an, compute_uv=False)
        np.testing.assert_allclose(
            np.asarray(s.larray), ref, rtol=1e-3, atol=1e-4
        )

    @needs_mesh
    def test_polar_path_census_no_all_gather(self):
        """The acceptance pin: the polar-composition SVD's distributed
        census has ZERO all-gathers — the operand (and everything else)
        moves only on collective-permute rings."""
        a = ht.array(_randn(128, 24, seed=25), split=0)
        rep = ht.observability.collective_counts(
            lambda x: tuple(ht.linalg.svd(x, method="polar")), a
        )
        self.assertEqual(rep.counts["all-gather"], 0)
        self.assertEqual(rep.counts["all-reduce"], 0)
        self.assertEqual(rep.counts["all-to-all"], 0)
        self.assertGreater(rep.counts["collective-permute"], 0)


@needs_mesh
class TestCensusMatchesPlan(TestCase):
    """Collective census of each solver's compiled program == the
    registered plan, exactly. The census traces the FULL factor tuple:
    tracing a single factor lets XLA DCE the rings feeding the others
    (polar's H ring vanishes from a U-only trace)."""

    def _plan_counts(self, kind, gshape):
        return F._factorization_plan(
            kind, gshape, "float32", P, planner.budget_bytes()
        ).collective_counts()

    def test_polar_census(self):
        a = ht.array(_randn(256, 64, seed=26), split=0)
        rep = ht.observability.collective_counts(
            lambda x: tuple(ht.linalg.polar(x)), a
        )
        self.assertEqual(
            {k: v for k, v in rep.counts.items() if v},
            self._plan_counts("polar", (256, 64)),
        )

    def test_cholesky_census(self):
        a = ht.array(_spd(96, seed=27), split=0)
        rep = ht.observability.collective_counts(ht.linalg.cholesky, a)
        self.assertEqual(
            {k: v for k, v in rep.counts.items() if v},
            self._plan_counts("cholesky", (96, 96)),
        )

    def test_lu_census(self):
        a = ht.array(_wellcond(96, seed=28), split=0)
        rep = ht.observability.collective_counts(
            lambda x: tuple(ht.linalg.lu(x)), a
        )
        self.assertEqual(
            {k: v for k, v in rep.counts.items() if v},
            self._plan_counts("lu", (96, 96)),
        )

    def test_solve_census_is_factor_plus_substitution(self):
        n, nrhs = 96, 8
        hn = _spd(n, seed=29)
        b = ht.array(_randn(n, nrhs, seed=30), split=0)
        rep = ht.observability.collective_counts(
            lambda u, v: ht.linalg.solve(u, v, assume_a="pos"),
            ht.array(hn, split=0), b,
        )
        chol = self._plan_counts("cholesky", (n, n))
        sub = self._plan_counts("solve-chol", (n, nrhs))
        want = {k: chol.get(k, 0) + sub.get(k, 0) for k in set(chol) | set(sub)}
        self.assertEqual({k: v for k, v in rep.counts.items() if v}, want)


@needs_mesh
class TestBitIdentity(TestCase):
    """Sequential (OVERLAP=0) vs pipelined (OVERLAP=1) ring forms are
    byte-identical for every solver — the rings only place, select, or
    accumulate in ONE fixed order, so the knob can only change issue
    order, never an addition order."""

    def _both_modes(self, fn):
        out = []
        for mode in ("0", "1"):
            with _overlap(mode):
                _clear_programs()
                out.append([np.asarray(x) for x in fn()])
        _clear_programs()
        for a, b in zip(*out):
            np.testing.assert_array_equal(a, b)

    def test_polar_bit_identical(self):
        an = _randn(192, 40, seed=31)

        def run():
            u, h = ht.linalg.polar(ht.array(an, split=0))
            return [u.larray, h.larray]

        self._both_modes(run)

    def test_cholesky_lu_bit_identical(self):
        hn = _spd(96, seed=32)
        an = _wellcond(96, seed=33)

        def run():
            l = ht.linalg.cholesky(ht.array(hn, split=0))
            perm, ll, uu = ht.linalg.lu(ht.array(an, split=0))
            return [l.larray, perm.larray, ll.larray, uu.larray]

        self._both_modes(run)

    # slow: ~23 s of compiles; polar and cholesky/lu keep the seq-vs-pipelined pin in tier-1
    @pytest.mark.slow
    def test_solve_eigh_bit_identical(self):
        hn = _spd(64, seed=34) * 2
        bn = _randn(64, 5, seed=35)

        def run():
            x = ht.linalg.solve(
                ht.array(hn, split=0), ht.array(bn, split=0), assume_a="pos"
            )
            w, v = ht.linalg.eigh(ht.array(hn, split=0))
            return [x.larray, w.larray, v.larray]

        self._both_modes(run)


class TestGoldenPlans(TestCase):
    def test_plans_deterministic(self):
        first = F.golden_factorization_plans()
        second = F.golden_factorization_plans()
        self.assertEqual(len(first), 5)
        names = [n for n, _ in first]
        self.assertEqual(len(set(names)), 5)
        for (n1, s1), (n2, s2) in zip(first, second):
            self.assertEqual(n1, n2)
            self.assertEqual(s1.plan_id, s2.plan_id)
            self.assertEqual(s1.collective_counts(), s2.collective_counts())
            # every plan is ppermute-only movement
            self.assertEqual(
                set(s1.collective_counts()), {"collective-permute"}
            )


class TestSolveEndpoint(TestCase):
    def test_chol_endpoint_serves_batches(self):
        from heat_tpu.serving.dispatcher import Dispatcher

        n = 24
        hn = _spd(n, seed=36)
        l = ht.linalg.cholesky(ht.array(hn, split=None))
        ep = F.solve_endpoint(l, buckets=(4, 16), name="chol-solve")
        rng = np.random.default_rng(37)
        batch = rng.standard_normal((3, n)).astype(np.float32)
        with Dispatcher(ep, poll_s=0.001) as d:
            out = np.asarray(d.submit(batch).result(timeout=60))
        for i in range(3):
            np.testing.assert_allclose(hn @ out[i], batch[i], atol=1e-3)

    def test_lu_endpoint_serves_batches(self):
        from heat_tpu.serving.dispatcher import Dispatcher

        n = 24
        an = _wellcond(n, seed=38, diag=5.0)
        fac = ht.linalg.lu(ht.array(an, split=None))
        ep = F.solve_endpoint(fac, buckets=(4,), name="lu-solve")
        rng = np.random.default_rng(39)
        batch = rng.standard_normal((2, n)).astype(np.float32)
        with Dispatcher(ep, poll_s=0.001) as d:
            out = np.asarray(d.submit(batch).result(timeout=60))
        for i in range(2):
            np.testing.assert_allclose(an @ out[i], batch[i], atol=1e-3)


if __name__ == "__main__":
    import unittest

    unittest.main()
