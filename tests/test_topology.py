"""Two-tier DCN-aware topology (ISSUE 8): the ``Topology`` abstraction,
hierarchical redistribution planning, tier-priced collectives, the
slice-major TSQR grouping, the hierarchical DP wire, and rule SL107.

The contract pinned here, four ways:

1. **Topology** — ``HEAT_TPU_TOPOLOGY`` resolution (auto-on-CPU = flat,
   forced ``SxC``, product-mismatch = flat), the slice/chip subgroup
   helpers, and the edge classification.
2. **Plans** — at a tiered topology the big cross-slice moves plan
   ``hierarchical-a2a`` (intra-slice pivot + inter-slice exchange), the
   tiers are priced (DCN ≈ 8× ICI), plans that keep their flat strategy
   differ from the flat plan ONLY via the tier/topology annotations,
   and with the topology unset/flat every plan is byte-identical to the
   PR 7 era (the ci.sh auto-on-CPU parity leg diffs the full dump).
3. **Acceptance** — at the simulated 2×8 mesh the 1 GB split-1 reshape
   (its 16-divisible view) and the 1 GB resplit plan
   ``hierarchical-a2a`` with int8-encoded cross-slice bytes ≤ 1/4 of
   the flat plan's payload; the compiled HLO census equals the tiered
   plan at 2×4 (executable on the 8-device test mesh) and the executed
   result is bit-identical to the flat-topology program.
4. **Tiers elsewhere** — ring hops classify ``tier="dcn"`` (the
   ``axis_index ± 1`` wraparound crosses the slice boundary), the TSQR
   tree groups slice-major, the DP quant step decomposes hierarchically,
   and SL107 flags an undecomposed flat cross-tier collective while the
   planner-stamped programs downgrade to info.
"""

import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import heat_tpu as ht

from heat_tpu.core import _padding
from heat_tpu.core.communication import (
    DCN_BPS,
    DCN_PENALTY,
    ICI_BPS,
    Topology,
    topology_for,
)
from heat_tpu.kernels import quant
from heat_tpu.observability.hlo import _count_ops
from heat_tpu.redistribution import RedistSpec, executor, planner

from test_suites.basic_test import TestCase, env_pin

P = len(jax.devices())
BUDGET = planner.DEFAULT_BUDGET_MB << 20


def _spec(name):
    return dict(planner.golden_specs())[name]


class TestTopologyAbstraction(TestCase):
    def test_parse_and_str(self):
        t = Topology.parse("2x8")
        self.assertEqual((t.n_slices, t.chips_per_slice), (2, 8))
        self.assertEqual(str(t), "2x8")
        self.assertIsNone(Topology.parse("garbage"))
        self.assertIsNone(Topology.parse("0x8"))

    def test_subgroup_helpers(self):
        t = Topology(2, 4)
        self.assertEqual(t.chip_axis_groups(), [[0, 1, 2, 3], [4, 5, 6, 7]])
        self.assertEqual(t.slice_axis_groups(), [[0, 4], [1, 5], [2, 6], [3, 7]])
        self.assertEqual([t.slice_of(i) for i in range(8)], [0, 0, 0, 0, 1, 1, 1, 1])
        self.assertTrue(t.crosses(3, 4))
        self.assertFalse(t.crosses(0, 3))
        self.assertTrue(t.spans([0, 7]))
        self.assertFalse(t.spans([4, 5, 6, 7]))

    def test_env_resolution(self):
        with env_pin("HEAT_TPU_TOPOLOGY", "2x4"):
            t = topology_for(8)
            self.assertEqual((t.n_slices, t.chips_per_slice), (2, 4))
            self.assertTrue(t.tiered)
            # product mismatch resolves FLAT, never an unrealizable mesh
            self.assertFalse(topology_for(16).tiered)
        with env_pin("HEAT_TPU_TOPOLOGY", "flat"):
            self.assertFalse(topology_for(8).tiered)
        with env_pin("HEAT_TPU_TOPOLOGY", None):
            # auto on the CPU test mesh: no slice_index -> flat
            self.assertFalse(topology_for(P).tiered)

    def test_bandwidth_constants(self):
        self.assertEqual(DCN_PENALTY, int(ICI_BPS / DCN_BPS))
        self.assertGreaterEqual(DCN_PENALTY, 4)
        t = Topology(2, 8)
        self.assertEqual(t.bandwidth("ici"), ICI_BPS)
        self.assertEqual(t.bandwidth("dcn"), DCN_BPS)

    def test_resolve_topology_forms(self):
        self.assertIsNone(planner.resolve_topology(8, "flat"))
        self.assertEqual(planner.resolve_topology(8, "2x4"), (2, 4))
        self.assertEqual(planner.resolve_topology(8, (2, 4)), (2, 4))
        self.assertIsNone(planner.resolve_topology(8, "2x8"))  # mismatch
        with self.assertRaises(ValueError):
            planner.resolve_topology(8, "nonsense")

    def test_comm_topology_property(self):
        with env_pin("HEAT_TPU_TOPOLOGY", "2x4"):
            t = self.comm.topology
            if self.comm.size == 8:
                self.assertTrue(t.tiered)
            self.assertEqual(t.size, self.comm.size)


class TestTieredPlans(TestCase):
    """Pure-Python planner pins — no mesh needed."""

    # the golden strategies under a forced 2x4 factorization of the
    # p=8 matrix: big cross-slice moves decompose, small ones stay on
    # their (now DCN-priced) flat forms because ALPHA dominates
    TIERED_2X4_PINS = {
        "resplit_0_to_1_p8": "all-to-all",
        "resplit_chunked_2gb_p8": "hierarchical-a2a",
        "resplit_ring_8gb_p8": "hierarchical-a2a",
        "reshape_pivot_p8": "hierarchical-a2a",
        "reshape_split1_1gb_p8": "hierarchical-a2a",
        "reshape_packed_rev_p8": "hierarchical-a2a",
        "reshape_lane_1gb_p8": "hierarchical-a2a",
        "replicate_p8": "replicate",
        "reshape_gather_fallback_p8": "gather-reshape",
    }

    def test_tiered_golden_strategies(self):
        for name, want in self.TIERED_2X4_PINS.items():
            sched = planner.plan(_spec(name), BUDGET, quant="0", topology="2x4")
            self.assertEqual(sched.strategy, want, name)
            if sched.n_collectives:
                self.assertIsNotNone(sched.topology, name)
                self.assertTrue(
                    all(st.tier in ("ici", "dcn") for st in sched.steps if st.is_collective),
                    name,
                )

    def test_flat_strategy_differs_only_by_tier_annotation(self):
        """A spec that keeps its flat strategy at a tiered topology must
        serialize identically to the flat plan once the tier/topology
        keys are stripped — the tier annotation is the WHOLE diff."""
        spec = _spec("resplit_0_to_1_p8")
        flat = planner.plan(spec, BUDGET, quant="0", topology="flat")
        tiered = planner.plan(spec, BUDGET, quant="0", topology="2x4")
        self.assertNotEqual(flat.plan_id, tiered.plan_id)
        d_flat = flat.as_dict(with_plan_id=False)
        d_tiered = tiered.as_dict(with_plan_id=False)
        d_tiered.pop("topology")
        for st in d_tiered["steps"]:
            st.pop("tier", None)
        self.assertEqual(d_flat, d_tiered)

    def test_flat_topology_byte_identical_to_ambient_flat(self):
        """topology="flat" == ambient resolution on this (flat) world ==
        the pre-ISSUE-8 serialization: no tier keys, no topology key."""
        for name, spec in planner.golden_specs():
            forced = planner.plan(spec, BUDGET, quant="0", topology="flat")
            self.assertNotIn('"tier"', forced.canonical_json(), name)
            self.assertNotIn('"topology"', forced.canonical_json(), name)

    def test_hierarchical_decomposition_structure(self):
        """Each hierarchical lap is an (ici, dcn) all-to-all pair; the
        intra hop carries L(C-1)/C, the inter hop L(S-1)/S — the
        portable-redistribution factorization across tiers."""
        spec = RedistSpec.normalize((4096, 2048), "float32", 0, 1, 8)
        sched = planner.plan(spec, BUDGET, quant="0", topology="2x4")
        self.assertEqual(sched.strategy, "hierarchical-a2a")
        colls = [st for st in sched.steps if st.is_collective]
        self.assertEqual([st.tier for st in colls], ["ici", "dcn"])
        L = 4096 * 2048 * 4 // 8
        self.assertEqual(colls[0].bytes_moved, L * 3 // 4)  # (C-1)/C
        self.assertEqual(colls[1].bytes_moved, L * 1 // 2)  # (S-1)/S
        tb = sched.tier_bytes()
        self.assertEqual(tb, {"ici": L * 3 // 4, "dcn": L * 1 // 2})

    def test_tier_pricing_beats_flat_on_big_moves(self):
        """The cost model's point: at 2x4 the hierarchical plan's
        modeled byte-equivalents undercut the slice-spanning flat form
        (whose every byte pays the DCN penalty)."""
        spec = _spec("resplit_chunked_2gb_p8")
        hier = planner.plan(spec, BUDGET, quant="0", topology="2x4")
        self.assertEqual(hier.strategy, "hierarchical-a2a")
        flat_cost = planner._cost(
            planner._tier_flat(
                planner.plan(spec, BUDGET, quant="0", topology="flat"), (2, 4)
            )
        )
        self.assertLess(planner._cost(hier), flat_cost)

    def test_tiered_overlap_group_arithmetic(self):
        """A tiered chunk group prices a pipelined lap at
        max(ici, dcn*penalty, copy) with the first wires / last copy
        exposed (the ISSUE 8 extension of the max(wire, copy) model)."""
        spec = _spec("resplit_chunked_2gb_p8")
        sched = planner.plan(spec, BUDGET, quant="0", topology="2x4")
        self.assertIsNotNone(sched.overlap)
        for g in sched.overlap["groups"]:
            self.assertIn("ici_bytes", g)
            pen = g["dcn_penalty"]
            wi = g["ici_bytes"] // g["laps"]
            wd = g["dcn_bytes"] * pen // g["laps"]
            c = g["copy_bytes"] // g["laps"]
            self.assertEqual(
                g["critical_path_bytes"],
                wi + wd + c + (g["laps"] - 1) * max(wi, wd, c),
            )
            self.assertEqual(g["wire_bytes"], g["ici_bytes"] + g["dcn_bytes"] * pen)
        self.assertEqual(DCN_PENALTY, sched.topology["dcn_penalty"])

    def test_ring_hops_tier_classified(self):
        """Satellite: the ring's ``axis_index ± 1`` wraparound crosses
        the slice boundary at any tiered factorization — every hop is
        classified (and priced) ``tier="dcn"``."""
        spec = RedistSpec.normalize((4096, 2048), "float32", 0, 1, 8)
        sched = planner.plan(spec, 1 << 20, quant="0", topology="2x4")
        if sched.strategy != "ring":  # the race is budget-dependent
            ring = [
                c for c in planner._resplit_candidates(spec, 1 << 20, (2, 4))
                if c.strategy == "ring"
            ][0]
            sched = ring
        hops = [st for st in sched.steps if st.kind == "ppermute"]
        self.assertTrue(hops)
        for st in hops:
            self.assertEqual(st.tier, "dcn")

    def test_describe_renders_tiers(self):
        spec = _spec("resplit_chunked_2gb_p8")
        text = planner.plan(spec, BUDGET, quant="0", topology="2x4").describe()
        self.assertIn("tier=ici", text)
        self.assertIn("tier=dcn", text)
        self.assertIn("topology: 2x4 two-tier", text)
        self.assertIn("model=max(ici", text)

    def test_quant_targets_the_dcn_hop_only(self):
        """ISSUE 8 codec policy: in a hierarchical plan the inter-slice
        exchange is the FIRST (and only) group the wire codec targets —
        the ICI pivot ships exact, and the DCN hop's encoded bytes come
        in at the int8 ratio."""
        spec = RedistSpec.normalize((4096, 2048), "float32", 0, 1, 8)
        plain = planner.plan(spec, BUDGET, quant="0", topology="2x4")
        q = planner.plan(spec, BUDGET, quant="int8", topology="2x4")
        self.assertIsNotNone(q.quant)
        self.assertEqual(q.collective_counts(), plain.collective_counts())
        self.assertEqual(q.tier_bytes()["ici"], plain.tier_bytes()["ici"])
        self.assertLessEqual(
            q.tier_bytes()["dcn"], 0.26 * plain.tier_bytes()["dcn"]
        )
        kinds = [st.kind for st in q.steps]
        self.assertIn("quantize", kinds)
        # the quantize step sits right before the dcn hop, not the ici one
        qi = kinds.index("quantize")
        self.assertEqual(q.steps[qi + 1].tier, "dcn")

    def test_plan_cache_keyed_on_topology(self):
        spec = RedistSpec.normalize((4096, 2048), "float32", 0, 1, 8)
        a = planner.plan(spec, BUDGET, quant="0", topology="flat")
        b = planner.plan(spec, BUDGET, quant="0", topology="2x4")
        self.assertNotEqual(a.plan_id, b.plan_id)
        # and a repeat serve is the cached object
        self.assertIs(planner.plan(spec, BUDGET, quant="0", topology="2x4"), b)

    def test_tier_time_model(self):
        spec = RedistSpec.normalize((4096, 2048), "float32", 0, 1, 8)
        sched = planner.plan(spec, BUDGET, quant="0", topology="2x4")
        m = planner.tier_time_model(sched)
        tb = sched.tier_bytes()
        self.assertEqual(m["ici_bytes"], tb["ici"])
        self.assertEqual(m["dcn_bytes"], tb["dcn"])
        self.assertAlmostEqual(m["total_s"], tb["ici"] / ICI_BPS + tb["dcn"] / DCN_BPS)


class TestAcceptance2x8(TestCase):
    """The ISSUE 8 acceptance pins at the simulated 2×8 (16-chip,
    two-slice) mesh — pure planner arithmetic, no devices."""

    def test_1gb_reshape_plans_hierarchical_with_quarter_dcn_bytes(self):
        spec = _spec("reshape_split1_1gb_p16")
        self.assertEqual(spec.logical_bytes, 10**9)
        flat = planner.plan(spec, BUDGET, quant="0", topology="flat")
        hier = planner.plan(spec, BUDGET, quant="int8", topology="2x8")
        self.assertEqual(hier.strategy, "hierarchical-a2a")
        # a topology-blind plan's collectives all span slices: its WHOLE
        # payload rides DCN. The hierarchical plan's int8-encoded
        # inter-slice exchange ships <= 1/4 of that.
        self.assertLessEqual(
            hier.tier_bytes()["dcn"], 0.25 * flat.bytes_moved,
            (hier.tier_bytes(), flat.bytes_moved),
        )
        # raw (codec off) the DCN hop still ships only the (S-1)/S
        # crossing fraction — ~0.53 of the flat payload
        raw = planner.plan(spec, BUDGET, quant="0", topology="2x8")
        self.assertLessEqual(raw.tier_bytes()["dcn"], 0.6 * flat.bytes_moved)
        self.assertTrue(hier.within_budget)

    def test_1gb_resplit_plans_hierarchical_with_quarter_dcn_bytes(self):
        spec = _spec("resplit_1gb_p16")
        flat = planner.plan(spec, BUDGET, quant="0", topology="flat")
        hier = planner.plan(spec, BUDGET, quant="int8", topology="2x8")
        self.assertEqual(hier.strategy, "hierarchical-a2a")
        self.assertLessEqual(hier.tier_bytes()["dcn"], 0.25 * flat.bytes_moved)

    def test_bench_row_models_at_least_2x(self):
        """Satellite floor: the analytic 2x8 rows model >= 2x
        hierarchical+int8 vs flat+f32."""
        spec = _spec("resplit_1gb_p16")
        flat = planner.plan(spec, BUDGET, quant="0", topology="flat")
        hier = planner.plan(spec, BUDGET, quant="int8", topology="2x8")
        t_flat = flat.bytes_moved / DCN_BPS
        m = planner.tier_time_model(hier)
        self.assertGreaterEqual(t_flat / m["total_s"], 2.0)
        dp = quant.dp_step_model_2tier(400_000_000, compute_s=1e-3)
        self.assertTrue(dp["dcn_bound"])
        self.assertGreaterEqual(dp["model_speedup"], 2.0)
        # compute-bound layers gain exactly nothing — max(), not magic
        dp2 = quant.dp_step_model_2tier(1_000_000, compute_s=1e-2)
        self.assertEqual(dp2["model_speedup"], 1.0)

    def test_tsqr_grouping_slice_major(self):
        from heat_tpu.core.linalg.qr import _tsqr_grouping

        self.assertEqual(_tsqr_grouping(16, (2, 8)), 8)
        self.assertEqual(_tsqr_grouping(8, (2, 4)), 4)
        # flat keeps the pre-ISSUE-8 rule verbatim
        self.assertEqual(_tsqr_grouping(8, None), 1)
        self.assertEqual(_tsqr_grouping(16, None), 4)
        # degenerate factorizations fall back flat
        self.assertEqual(_tsqr_grouping(8, (8, 1)), 1)


@pytest.mark.skipif(P != 8, reason="executable tier pins are 8-mesh-shaped")
class TestTieredExecutor(TestCase):
    """The 2x4 factorization of the REAL 8-device test mesh: compiled
    census == tiered plan, executed result bit-identical to the
    flat-topology program (the acceptance criteria, executable here)."""

    def _census_of(self, prog, spec):
        phys = _padding.phys_shape(spec.gshape, spec.src_split, spec.mesh_size)
        arg = jax.ShapeDtypeStruct(
            phys,
            np.dtype(spec.dtype),
            sharding=self.comm.sharding(len(phys), spec.src_split),
        )
        text = prog.lower(arg).compile().as_text()
        return {k: v for k, v in _count_ops(text).items() if v}

    def test_census_matches_tiered_plan(self):
        spec = RedistSpec.normalize((4096, 2048), "float32", 0, 1, 8)
        sched = planner.plan(spec, BUDGET, quant="0", topology="2x4")
        self.assertEqual(sched.strategy, "hierarchical-a2a")
        for pipelined in (False, True):
            prog = executor._move_program(
                self.comm, spec, BUDGET, pipelined, None, (2, 4)
            )
            self.assertEqual(self._census_of(prog, spec), sched.collective_counts())

    def test_executed_bit_identical_to_flat_program(self):
        rng = np.random.default_rng(0)
        cases = [
            RedistSpec.normalize((4096, 2048), "float32", 0, 1, 8),
            RedistSpec.normalize(
                (40960, 40), "float32", 1, 1, 8, reshape_to=(20480, 80)
            ),
        ]
        for spec in cases:
            hier = planner.plan(spec, BUDGET, quant="0", topology="2x4")
            flat = planner.plan(spec, BUDGET, quant="0", topology="flat")
            self.assertEqual(hier.strategy, "hierarchical-a2a", spec)
            oracle = rng.standard_normal(spec.gshape).astype(np.float32)
            x = ht.array(oracle, split=spec.src_split)
            y_hier = executor.execute(self.comm, x._phys, spec, hier)
            y_flat = executor.execute(self.comm, x._phys, spec, flat)
            np.testing.assert_array_equal(np.asarray(y_hier), np.asarray(y_flat))
            logical = np.asarray(
                _padding.unpad(y_hier, spec.out_shape, spec.dst_split)
            )
            np.testing.assert_array_equal(
                logical, oracle.reshape(spec.out_shape), str(spec)
            )

    def test_quantized_dcn_hop_within_tolerance(self):
        spec = RedistSpec.normalize((4096, 2048), "float32", 0, 1, 8)
        q = planner.plan(spec, BUDGET, quant="int8", topology="2x4")
        rng = np.random.default_rng(1)
        oracle = rng.standard_normal((4096, 2048)).astype(np.float32)
        x = ht.array(oracle, split=0)
        y = executor.execute(self.comm, x._phys, spec, q)
        got = np.asarray(_padding.unpad(y, (4096, 2048), 1))
        err = np.abs(got - oracle).max()
        self.assertGreater(err, 0.0)  # the DCN hop really encoded
        self.assertLessEqual(err, quant.tolerance("int8") * np.abs(oracle).max())

    def test_seq_vs_pipelined_bit_identical(self):
        spec = RedistSpec.normalize((4096, 2048), "float32", 0, 1, 8)
        sched = planner.plan(spec, 4 << 20, quant="0", topology="2x4")
        self.assertTrue(any(st.overlap for st in sched.steps))
        oracle = np.arange(4096 * 2048, dtype=np.float32).reshape(4096, 2048)
        x = ht.array(oracle, split=0)
        outs = {}
        for mode in ("0", "1"):
            with env_pin(planner.OVERLAP_ENV, mode):
                outs[mode] = np.asarray(
                    executor.execute(self.comm, x._phys, spec, sched)
                )
        np.testing.assert_array_equal(outs["0"], outs["1"])

    def test_hierarchical_allreduce_sum_matches_psum(self):
        from jax import shard_map
        from jax.sharding import PartitionSpec as PS

        rng = np.random.default_rng(2)
        h = rng.standard_normal((8, 5000)).astype(np.float32)
        comm = self.comm

        def body(hl):
            out, resid = quant.hierarchical_allreduce_sum(
                hl[0], comm.axis_name, 2, 4, "int8"
            )
            return out[None], resid[None]

        f = jax.jit(shard_map(  # jitted: eager shard_map runs op by op
            body,
            mesh=comm.mesh,
            in_specs=(PS(comm.axis_name, None),),
            out_specs=(PS(comm.axis_name, None), PS(comm.axis_name, None)),
            check_vma=False,
        ))
        out, resid = f(comm.shard(jnp.asarray(h), 0))
        want = h.sum(axis=0)
        got = np.asarray(out)
        for d in range(8):
            err = np.abs(got[d] - want).max()
            self.assertLessEqual(err, quant.tolerance("int8") * np.abs(want).max() * 2)
        # the residuals reconstruct the compression error: sum of all
        # carries == exact - decoded (each chip position owns a block)
        approx = got[0] + np.asarray(resid).sum(axis=0)
        np.testing.assert_allclose(approx, want, rtol=1e-5, atol=1e-4)


class TestShardlintSL107(TestCase):
    @pytest.mark.skipif(P % 2, reason="an odd mesh has no 2-slice factorization")
    def test_fixture_trips_at_tiered_topology_only(self):
        sys.path.insert(0, "tests")
        import analysis_fixtures as fx

        x = ht.zeros((4096, 2048), split=0)
        rep_flat = ht.analysis.check(fx.flat_dcn_a2a_program, x, topology="flat")
        self.assertFalse([f for f in rep_flat.findings if f.rule == "SL107"])
        rep = ht.analysis.check(fx.flat_dcn_a2a_program, x, topology=f"2x{P // 2}")
        sl107 = [f for f in rep.findings if f.rule == "SL107"]
        self.assertTrue(sl107)
        for f in sl107:
            self.assertIn(f.severity, ("warning", "error"))
            self.assertIn("cross-tier", f.message)

    @pytest.mark.skipif(P < 8, reason="hierarchical plans need the 8-mesh")
    def test_planner_stamped_program_downgrades_to_info(self):
        x = ht.zeros((4096, 2048), split=0)
        with env_pin("HEAT_TPU_TOPOLOGY", "2x4"):
            planner.clear_plan_cache()
            try:
                sched = ht.redistribution.explain(x, 1)
                self.assertEqual(sched.strategy, "hierarchical-a2a")
                rep = ht.analysis.check(lambda v: v.resplit(1), x)
                sl107 = [f for f in rep.findings if f.rule == "SL107"]
                self.assertTrue(sl107)
                for f in sl107:
                    self.assertEqual(f.severity, "info")
                    self.assertIn(sched.plan_id, f.message)
                self.assertTrue(rep.ok)
            finally:
                planner.clear_plan_cache()

    def test_encoded_dp_wire_downgrades_to_info(self):
        """The hierarchical DP gradient wire's inter-slice gather runs
        under the wire-codec stamp: SL107 reports it as the sanctioned
        encoded cross-tier exchange."""
        from heat_tpu.analysis.boundaries import wire_codec_stamped

        self.assertTrue(wire_codec_stamped("transpose/wire_codec_int8/all_gather"))


if __name__ == "__main__":
    import unittest

    unittest.main()
