"""Lane-packing relayout engine (``heat_tpu.kernels.relayout`` + the
``packed-pivot`` redistribution strategy).

Three contracts pinned here:

1. the pack/unpack primitives are pure permutation + zero-pad — the XLA
   formulation and the Pallas tiled-copy kernel (interpret mode on CPU)
   are BIT-IDENTICAL, and unpack inverts pack exactly;
2. the planner's lane-fill cost term picks ``packed-pivot`` exactly for
   narrow-minor-dim reshape pivots and keeps the direct pivot for
   lane-friendly ones, with the SAME collective census either way;
3. the executed packed programs reproduce the oracle bit-for-bit under
   every ``HEAT_TPU_RELAYOUT_KERNEL`` setting (kernel-on == kernel-off
   == direct), with the compiled HLO census equal to the plan's.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import heat_tpu as ht

from heat_tpu.kernels import relayout
from heat_tpu.redistribution import RedistSpec, executor, planner

from test_suites.basic_test import TestCase, env_pin

P = len(jax.devices())
BUDGET = planner.DEFAULT_BUDGET_MB << 20


# the shared env save/set/restore helper (test_suites.basic_test)
_env = env_pin


def _pack_oracle(x, rows, c_in, c_out, p):
    """Independent numpy formulation of the pack layout."""
    xb = np.zeros((rows, c_out), dtype=np.asarray(x).dtype)
    xb[:, :c_in] = np.asarray(x).reshape(rows, c_in)
    cpp = c_out // p
    return xb.reshape(rows, p, cpp).transpose(1, 0, 2).reshape(p, rows * cpp)


class TestPrimitives(TestCase):
    CASES = [
        # (rows, c_in, c_out, p)
        (12, 25, 32, 8),
        (8, 5, 8, 8),
        (40, 40, 40, 8),     # no widen: group only
        (16, 3, 4, 4),
        (7, 13, 15, 5),      # odd everything
        (1, 25, 32, 8),      # single row
    ]

    def test_xla_matches_numpy_oracle(self):
        for rows, c_in, c_out, p in self.CASES:
            x = jnp.arange(rows * c_in, dtype=jnp.float32) + 1.0
            got = relayout.pack_rows(x, rows, c_in, c_out, p, impl="xla")
            np.testing.assert_array_equal(
                np.asarray(got), _pack_oracle(x, rows, c_in, c_out, p)
            )

    def test_pallas_bit_identical_to_xla(self):
        for rows, c_in, c_out, p in self.CASES:
            for dt in (jnp.float32, jnp.int32):
                x = jnp.arange(rows * c_in, dtype=dt)
                a = relayout.pack_rows(x, rows, c_in, c_out, p, impl="xla")
                b = relayout.pack_rows(x, rows, c_in, c_out, p, impl="pallas")
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
                ua = relayout.unpack_rows(a, rows, c_out, c_in, p, impl="xla")
                ub = relayout.unpack_rows(a, rows, c_out, c_in, p, impl="pallas")
                np.testing.assert_array_equal(np.asarray(ua), np.asarray(ub))

    def test_unpack_inverts_pack(self):
        for rows, c_in, c_out, p in self.CASES:
            x = jnp.arange(rows * c_in, dtype=jnp.float32) * 0.5
            for impl in ("xla", "pallas"):
                packed = relayout.pack_rows(x, rows, c_in, c_out, p, impl=impl)
                back = relayout.unpack_rows(packed, rows, c_out, c_in, p, impl=impl)
                np.testing.assert_array_equal(np.asarray(back), np.asarray(x))

    def test_special_float_bits_round_trip(self):
        # a relayout must move BITS, never canonicalize values
        vals = np.array(
            [0.0, -0.0, np.inf, -np.inf, np.nan, -np.nan, 1e-45, -1e-45],
            dtype=np.float32,
        )
        x = jnp.asarray(np.resize(vals, 4 * 6))
        for impl in ("xla", "pallas"):
            packed = relayout.pack_rows(x, 4, 6, 8, 8, impl=impl)
            back = relayout.unpack_rows(packed, 4, 8, 6, 8, impl=impl)
            np.testing.assert_array_equal(
                np.asarray(back).view(np.uint32), np.asarray(x).view(np.uint32)
            )

    def test_invalid_shapes_rejected(self):
        x = jnp.zeros((12 * 25,), jnp.float32)
        with self.assertRaises(ValueError):
            relayout.pack_rows(x, 12, 25, 30, 8)  # p does not divide c_out
        with self.assertRaises(ValueError):
            relayout.pack_rows(x, 12, 25, 16, 8)  # c_out < c_in
        with self.assertRaises(ValueError):
            relayout.unpack_rows(jnp.zeros((8, 48), jnp.float32), 12, 32, 33, 8)  # widen on unpack

    def test_lane_fill(self):
        self.assertEqual(relayout.lane_fill(128), 1.0)
        self.assertEqual(relayout.lane_fill(512), 1.0)
        self.assertAlmostEqual(relayout.lane_fill(25), 25 / 128)
        self.assertAlmostEqual(relayout.lane_fill(4), 4 / 128)
        self.assertAlmostEqual(relayout.lane_fill(130), 130 / 256)
        self.assertEqual(relayout.lane_fill(0), 1.0)


class TestDispatch(TestCase):
    def test_escape_hatch_forces_xla(self):
        with _env("HEAT_TPU_RELAYOUT_KERNEL", "0"):
            self.assertEqual(relayout.kernel_mode(), "0")
            self.assertEqual(relayout.decide("pack", 8, 25, 32, 8, "float32"), "xla")

    def test_forced_mode_serves_pallas(self):
        with _env("HEAT_TPU_RELAYOUT_KERNEL", "1"):
            self.assertEqual(relayout.decide("pack", 8, 25, 32, 8, "float32"), "pallas")

    def test_auto_is_xla_and_says_why(self):
        """The Pallas kernels are off ``auto`` on every backend (Mosaic
        refuses them, tests/test_chip_compile.py pins it): the decision
        is XLA and carries the compiler's reason."""
        sig = ("pack", 8, 25, 32, 8, "float32")
        with _env("HEAT_TPU_RELAYOUT_KERNEL", None):
            self.assertEqual(relayout.decide(*sig), "xla")
            self.assertIn("unsupported shape cast", relayout.last_decisions()[sig]["why"])

    def test_forced_mode_unserviceable_falls_back(self):
        from heat_tpu.observability import telemetry

        telemetry.reset()
        telemetry.enable()
        try:
            with _env("HEAT_TPU_RELAYOUT_KERNEL", "1"):
                # c_out beyond the VMEM block budget: kernel refuses
                big = relayout._BLOCK_ELEMS * 2
                impl = relayout.decide("pack", 4, big // 2, big, 2, "float32")
                self.assertEqual(impl, "xla")
                snap = telemetry.snapshot()["counters"]
                self.assertGreaterEqual(snap.get("relayout.kernel.fallback", 0), 1)
        finally:
            telemetry.disable()
            telemetry.reset()

    def test_kernel_hit_telemetry(self):
        from heat_tpu.observability import telemetry

        telemetry.reset()
        telemetry.enable()
        try:
            x = jnp.arange(8 * 25, dtype=jnp.float32)
            relayout.pack_rows(x, 8, 25, 32, 8, impl="pallas")
            snap = telemetry.snapshot()["counters"]
            self.assertGreaterEqual(snap.get("relayout.kernel.hit", 0), 1)
        finally:
            telemetry.disable()
            telemetry.reset()


class TestPackedPlans(TestCase):
    NARROW = RedistSpec.normalize(
        (1000, 250000), "float32", 1, 1, 8, reshape_to=(10_000_000, 25)
    )
    WIDE = RedistSpec.normalize(
        (65536, 4096), "float32", 1, 1, 8, reshape_to=(131072, 2048)
    )

    def test_packed_sides(self):
        self.assertEqual(planner._packed_sides(self.NARROW), (False, True))
        self.assertEqual(planner._packed_sides(self.WIDE), (False, False))
        rev = RedistSpec.normalize(
            (10_000_000, 25), "float32", 1, 1, 8, reshape_to=(1000, 250000)
        )
        self.assertEqual(planner._packed_sides(rev), (True, False))

    def test_lane_fill_term_picks_packed_for_narrow_only(self):
        self.assertEqual(planner.plan(self.NARROW, BUDGET).strategy, "packed-pivot")
        self.assertEqual(planner.plan(self.WIDE, BUDGET).strategy, "split0-pivot")

    def test_packed_census_equals_direct_census(self):
        """Packing changes layouts, never movement: the packed plan's
        collective census equals the direct pivot's for the same spec."""
        packed = planner.plan(self.NARROW, BUDGET)
        direct = planner._pivot_schedule(self.NARROW, BUDGET)
        self.assertEqual(packed.collective_counts(), direct.collective_counts())

    def test_packed_cost_beats_direct_exactly_on_narrow(self):
        packed = planner._packed_pivot_schedule(self.NARROW, BUDGET)
        direct = planner._pivot_schedule(self.NARROW, BUDGET)
        self.assertLess(planner._cost(packed), planner._cost(direct))

    def test_pack_unpack_steps_carry_bytes(self):
        sched = planner.plan(self.NARROW, BUDGET)
        kinds = [s.kind for s in sched.steps]
        self.assertIn("pack", kinds)
        self.assertIn("unpack", kinds)
        for st in sched.steps:
            if st.kind in ("pack", "unpack"):
                self.assertGreater(st.bytes_copied, 0)
                self.assertGreater(st.peak_bytes, 0)
        # the one HEAVILY lane-amplified write is the LAST step (the dst
        # materialization); every other step streams (near-)full lanes
        self.assertEqual(sched.steps[-1].kind, "unpack")
        self.assertLess(sched.steps[-1].lane_fill, 0.5)
        amplified = [s for s in sched.steps if s.lane_fill < 0.5]
        self.assertEqual(len(amplified), 1)

    def test_tighter_budget_rechunks_packed(self):
        # the default plan already runs overlap-grain laps (ISSUE 6), so
        # the budget must tighten past the grain before it adds chunks
        base = planner.plan(self.NARROW, BUDGET)
        tight = planner.plan(self.NARROW, BUDGET // 8)
        self.assertLessEqual(
            max(s.peak_bytes for s in tight.steps if s.is_collective), BUDGET // 8
        )
        self.assertGreater(
            tight.collective_counts()["all-to-all"],
            base.collective_counts()["all-to-all"],
        )

    def test_packed_within_budget(self):
        sched = planner.plan(self.NARROW, BUDGET)
        self.assertTrue(sched.within_budget)


@pytest.mark.skipif(P < 2, reason="needs a real mesh")
class TestPackedExecutor(TestCase):
    """Numerics + census of the executed packed programs. Shapes sized
    so the packed sides engage on the test mesh (narrow cols over P)."""

    def _cases(self):
        """(in_shape, out_shape) pairs big enough that the lane-fill
        cost term beats the per-collective ALPHA — the planner routes
        them packed on the 8-device mesh (some degrade to the direct
        pivot on 2/4-device meshes; correctness must hold either way)."""
        return [
            ((4096, 24), (2048, 48)),      # packed both sides
            ((4096, 25), (10240, 10)),     # uneven cols: widen + narrow pads
            ((2048, 48), (4096, 24)),      # reverse
            ((8192, 6), (6144, 8)),        # very narrow both sides
            ((4096, 200), (102400, 8)),    # wide in, narrow out
        ]

    def test_packed_reshape_matches_oracle(self):
        for in_shape, out_shape in self._cases():
            if in_shape[0] % P or out_shape[0] % P:
                continue
            oracle = np.arange(int(np.prod(in_shape)), dtype=np.float32).reshape(in_shape)
            x = ht.array(oracle, split=1)
            got = ht.reshape(x, out_shape, new_split=1)
            self.assertEqual(got.split, 1)
            self.assert_array_equal(got, oracle.reshape(out_shape))

    def test_kernel_on_off_bit_identical(self):
        """HEAT_TPU_RELAYOUT_KERNEL=1 (Pallas tiled copy, interpret on
        CPU) and =0 (XLA formulation) must produce bit-identical
        physical arrays on every program-backed spec."""
        for in_shape, out_shape in self._cases():
            if in_shape[0] % P or out_shape[0] % P:
                continue
            oracle = np.arange(int(np.prod(in_shape)), dtype=np.float32).reshape(in_shape)
            x = ht.array(oracle, split=1)
            spec = RedistSpec.normalize(
                in_shape, "float32", 1, 1, P, reshape_to=out_shape
            )
            results = {}
            for mode in ("0", "1"):
                with _env("HEAT_TPU_RELAYOUT_KERNEL", mode):
                    results[mode] = np.asarray(
                        executor.execute(self.comm, x._phys, spec)
                    )
            np.testing.assert_array_equal(results["0"], results["1"])

    def test_packed_census_matches_compiled_hlo(self):
        """Executed census == plan census for a packed spec, end to end
        through the public reshape."""
        in_shape, out_shape = (4096, 24), (2048, 48)
        if in_shape[0] % P or out_shape[0] % P:
            pytest.skip("mesh does not divide the packed test shape")
        x = ht.zeros(in_shape, split=1)
        sched = ht.redistribution.explain(x, reshape=out_shape, new_split=1)
        self.assertEqual(sched.strategy, "packed-pivot")
        rep = ht.observability.collective_counts(
            lambda v: ht.reshape(v, out_shape, new_split=1), x
        )
        for op, n in sched.collective_counts().items():
            self.assertEqual(rep.counts[op], n, op)
        self.assertEqual(rep.total, sched.n_collectives)
        self.assertEqual(rep.counts["all-gather"], 0)

    def test_relayout_strategy_telemetry(self):
        from heat_tpu.observability import telemetry

        if (4096 % P) or (2048 % P):
            pytest.skip("mesh does not divide the packed test shape")
        telemetry.reset()
        telemetry.enable()
        try:
            x = ht.zeros((4096, 24), split=1)
            ht.reshape(x, (2048, 48), new_split=1)
            snap = telemetry.snapshot()["counters"]
            self.assertGreaterEqual(snap.get("redist.relayout.packed", 0), 1)
            w = ht.zeros((4096, 256 * P), split=1)
            ht.reshape(w, (2048, 512 * P), new_split=1)
            snap = telemetry.snapshot()["counters"]
            self.assertGreaterEqual(snap.get("redist.relayout.direct", 0), 1)
        finally:
            telemetry.disable()
            telemetry.reset()

    def test_packed_program_shardlint_info_downgrade(self):
        """PR-3 contract carried over unchanged: the packed program runs
        under jax.named_scope("redist_plan_<id>"), so shardlint reports
        its collectives at info severity with the plan id attached."""
        if (4096 % P) or (2048 % P):
            pytest.skip("mesh does not divide the packed test shape")
        x = ht.zeros((4096, 24), split=1)
        sched = ht.redistribution.explain(x, reshape=(2048, 48), new_split=1)
        self.assertEqual(sched.strategy, "packed-pivot")
        rep = ht.analysis.check(
            lambda v: ht.reshape(v, (2048, 48), new_split=1), x
        )
        sl101 = [f for f in rep.findings if f.rule == "SL101"]
        for f in sl101:
            self.assertEqual(f.severity, "info")
            self.assertIn(sched.plan_id, f.message)
        self.assertTrue(rep.ok)

    def test_planner_escape_hatch_still_exact(self):
        """HEAT_TPU_REDIST_PLANNER=0 (legacy monolithic path) agrees
        with the packed plan's result — the hatch's contract."""
        oracle = np.arange(4096 * 24, dtype=np.float32).reshape(4096, 24)
        x = ht.array(oracle, split=1)
        planned = ht.reshape(x, (2048, 48), new_split=1)
        with _env("HEAT_TPU_REDIST_PLANNER", "0"):
            legacy = ht.reshape(x, (2048, 48), new_split=1)
        self.assert_array_equal(planned, oracle.reshape(2048, 48))
        np.testing.assert_array_equal(
            np.asarray(planned._phys), np.asarray(legacy._phys)
        )


if __name__ == "__main__":
    import unittest

    unittest.main()
