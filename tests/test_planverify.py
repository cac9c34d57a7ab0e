"""Schedule-IR plan verifier + memcheck cross-check tests (ISSUE 10).

Contracts pinned here:

- ``ht.analysis.verify_plan`` passes on EVERY golden-matrix plan — all
  topologies (flat / 2x4 / 2x8), quant on and off, both as Schedule
  objects and as their canonical-JSON dumps (the exact lines the ci.sh
  ``scripts/verify_plans.py`` sweep consumes).
- Every mutation class a malformed plan can carry is caught with the
  violated invariant NAMED: accounting, composition, conservation,
  quant-pairing, tier-labels, overlap-structure, plan-id, step-kinds
  (the ISSUE 14 ``progress`` invariant's mutation classes live in
  tests/test_commcheck.py).
- ``scripts/verify_plans.py`` exits 0 over a fresh dump and 1 over a
  corrupted one, naming the invariant — the CI leg's contract.
- memcheck's static peak on the three GATED redistribution programs is
  within 2x of the compiler's own ``Compiled.memory_analysis()`` on the
  tier-1 CPU mesh — the model stays honest against XLA.
- The ``Schedule.liveness`` hook agrees with the step accounting and
  never perturbs the canonical serialization (flat plans stay
  byte-identical — the ISSUE 10 escape-hatch clause).
"""

import copy
import json
import os
import subprocess
import sys

import pytest

import jax

import heat_tpu as ht

from heat_tpu.analysis.planverify import PlanVerificationError, verify_plan
from heat_tpu.redistribution import planner
from heat_tpu.redistribution.spec import RedistSpec

from test_suites.basic_test import TestCase

P = len(jax.devices())
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BUDGET = planner.DEFAULT_BUDGET_MB << 20


class TestGoldenMatrixVerifies(TestCase):
    """The tentpole acceptance: every golden plan, every topology,
    quant on and off, proves well-formed."""

    def test_all_golden_plans_all_topologies_all_codecs(self):
        n = 0
        for topo in ("flat", "2x4", "2x8"):
            for q in ("0", "int8"):
                for name, spec in planner.golden_specs():
                    sched = planner.plan(spec, BUDGET, quant=q, topology=topo)
                    res = verify_plan(sched, topology=topo)
                    self.assertTrue(res["ok"], f"{name}@{topo} quant={q}")
                    # the serialized form (what ci.sh sweeps) verifies too
                    res_json = verify_plan(sched.canonical_json(), topology=topo)
                    self.assertTrue(res_json["ok"], f"{name}@{topo} quant={q} (json)")
                    self.assertEqual(res_json["plan_id"], sched.plan_id)
                    n += 1
        self.assertEqual(n, 3 * 2 * len(planner.golden_specs()))

    def test_bf16_codec_plans_verify(self):
        spec = RedistSpec.normalize((32768, 16384), "float32", 0, 1, 8)
        sched = planner.plan(spec, BUDGET, quant="bf16", topology="flat")
        self.assertEqual(sched.quant["mode"], "bf16")
        self.assertTrue(verify_plan(sched, topology="flat")["ok"])

    def test_report_shape_and_checks(self):
        sched = planner.plan(
            planner.golden_specs()[1][1], BUDGET, quant="0", topology="flat"
        )
        res = verify_plan(sched)
        for key in ("ok", "plan_id", "strategy", "checks", "violations"):
            self.assertIn(key, res)
        for inv in ("composition", "conservation", "accounting",
                    "quant-pairing", "tier-labels", "overlap-structure",
                    "progress", "plan-id"):
            self.assertIn(inv, res["checks"])


class TestMalformedPlansFail(TestCase):
    """Every corruption class fails with the violated invariant named —
    what byte-level dump diffing can never see."""

    def _base(self, name="resplit_chunked_2gb_p8", quant="0"):
        spec = dict(planner.golden_specs())[name]
        sched = planner.plan(spec, BUDGET, quant=quant, topology="flat")
        return json.loads(sched.canonical_json())

    def _expect(self, plan_dict, invariant):
        with self.assertRaises(PlanVerificationError) as cm:
            verify_plan(plan_dict)
        self.assertEqual(cm.exception.invariant, invariant, str(cm.exception))
        self.assertIn(invariant, str(cm.exception))
        # non-raising mode collects the same violation
        res = verify_plan(plan_dict, raise_on_violation=False)
        self.assertFalse(res["ok"])
        self.assertIn(invariant, [v["invariant"] for v in res["violations"]])

    def test_unknown_step_kind(self):
        m = self._base()
        m["steps"][0]["kind"] = "teleport"
        self._expect(m, "step-kinds")

    def test_corrupted_peak_accounting(self):
        m = self._base()
        m["peak_bytes"] += 1
        self._expect(m, "accounting")

    def test_corrupted_census(self):
        m = self._base()
        m["collective_counts"] = {"all-gather": 99}
        self._expect(m, "accounting")

    def test_wrong_strategy_composition(self):
        m = self._base()
        m["strategy"] = "ring"  # steps are a2a laps, not p-1 ppermutes
        self._expect(m, "composition")

    def test_byte_conservation(self):
        m = self._base()
        for st in m["steps"]:
            if st["kind"] == "all_to_all":
                st["bytes_moved"] += 4096
        m["bytes_moved"] = sum(s["bytes_moved"] for s in m["steps"])
        # accounting now self-consistent — only the GEOMETRY recompute
        # (and the stale overlap/plan-id) can catch it; conservation
        # must be among the named violations
        res = verify_plan(m, raise_on_violation=False)
        self.assertFalse(res["ok"])
        self.assertIn("conservation", [v["invariant"] for v in res["violations"]])

    def test_dropped_dequantize(self):
        m = self._base(quant="int8")
        m["steps"] = [s for s in m["steps"] if s["kind"] != "dequantize"]
        self._expect(m, "quant-pairing")

    def test_inconsistent_wire_ratio(self):
        m = self._base(quant="int8")
        m["quant"]["ratio"] = 0.9999
        self._expect(m, "quant-pairing")

    def test_tier_label_on_flat_plan(self):
        m = self._base()
        for st in m["steps"]:
            if st["kind"] == "all_to_all":
                st["tier"] = "dcn"
                break
        self._expect(m, "tier-labels")

    def test_tiered_plan_against_wrong_expected_topology(self):
        spec = dict(planner.golden_specs())["resplit_1gb_p16"]
        sched = planner.plan(spec, BUDGET, quant="0", topology="2x8")
        self.assertIsNotNone(sched.topology)
        with self.assertRaises(PlanVerificationError) as cm:
            verify_plan(sched, topology="flat")
        self.assertEqual(cm.exception.invariant, "tier-labels")

    def test_hierarchical_tier_order(self):
        spec = dict(planner.golden_specs())["resplit_1gb_p16"]
        sched = planner.plan(spec, BUDGET, quant="0", topology="2x8")
        self.assertEqual(sched.strategy, "hierarchical-a2a")
        m = json.loads(sched.canonical_json())
        colls = [s for s in m["steps"] if s["kind"] == "all_to_all"]
        colls[0]["tier"], colls[1]["tier"] = colls[1]["tier"], colls[0]["tier"]
        self._expect(m, "tier-labels")

    def test_corrupted_overlap_arithmetic(self):
        m = self._base()
        self.assertTrue(m.get("overlap"), "fixture spec must pipeline")
        m["overlap"]["groups"][0]["critical_path_bytes"] += 1
        self._expect(m, "overlap-structure")

    def test_forged_plan_id(self):
        m = self._base()
        m["plan_id"] = "deadbeef0000"
        self._expect(m, "plan-id")


class TestLivenessHooks(TestCase):
    """The ISSUE 10 liveness hooks on the Schedule IR: per-step live
    accounting consistent with the step peaks, and INVISIBLE to the
    canonical serialization (flat plans stay byte-identical)."""

    def test_liveness_account(self):
        spec = dict(planner.golden_specs())["resplit_chunked_2gb_p8"]
        sched = planner.plan(spec, BUDGET, quant="0", topology="flat")
        live = sched.liveness()
        self.assertEqual(len(live), sched.n_steps)
        self.assertEqual(
            max(e["transient_bytes"] for e in live), sched.peak_bytes
        )
        resident = sched.resident_bytes
        self.assertEqual(resident, spec.src_shard_bytes + spec.dst_shard_bytes)
        for e in live:
            self.assertEqual(e["live_bytes"], resident + e["transient_bytes"])
        self.assertEqual(
            sched.liveness_peak_bytes, resident + sched.peak_bytes
        )

    def test_src_shard_bytes_geometry(self):
        spec = RedistSpec.normalize((63, 48), "float32", 0, 1, 8)
        # padded source shard: 63 -> 64 rows over 8 devices
        self.assertEqual(spec.src_shard_bytes, 64 * 48 * 4 // 8)
        rep = RedistSpec.normalize((64, 48), "float32", None, 1, 8)
        self.assertEqual(rep.src_shard_bytes, 64 * 48 * 4)

    def test_liveness_never_touches_serialization(self):
        spec = dict(planner.golden_specs())["resplit_0_to_1_p8"]
        sched = planner.plan(spec, BUDGET, quant="0", topology="flat")
        before = sched.canonical_json()
        sched.liveness()
        _ = sched.liveness_peak_bytes
        self.assertEqual(sched.canonical_json(), before)
        self.assertNotIn("liveness", before)
        self.assertNotIn("resident", before)


class TestVerifyPlansCLI(TestCase):
    """scripts/verify_plans.py: exit 0 over a fresh dump, exit 1 with
    the invariant named over a corrupted one — the ci.sh leg contract."""

    # slow: ~22 s of CLI subprocesses; runs in scripts/ci.sh's full leg
    @pytest.mark.slow
    def test_cli_ok_and_malformed(self):
        import tempfile

        env = dict(os.environ, JAX_PLATFORMS="cpu")
        dump = subprocess.run(
            [sys.executable, os.path.join(ROOT, "scripts", "redist_plans.py")],
            capture_output=True, text=True, env=env,
        )
        self.assertEqual(dump.returncode, 0, dump.stderr)
        with tempfile.TemporaryDirectory() as td:
            good = os.path.join(td, "plans.txt")
            with open(good, "w") as f:
                f.write(dump.stdout)
            ok = subprocess.run(
                [sys.executable, os.path.join(ROOT, "scripts", "verify_plans.py"), good],
                capture_output=True, text=True, env=env,
            )
            self.assertEqual(ok.returncode, 0, ok.stdout + ok.stderr)
            self.assertIn("well-formed", ok.stdout)

            # corrupt one plan's accounting; the sweep must fail and
            # name the invariant
            lines = dump.stdout.strip().splitlines()
            name, _, payload = lines[1].partition("\t")
            plan = json.loads(payload)
            plan["peak_bytes"] += 1
            lines[1] = f"{name}\t{json.dumps(plan, sort_keys=True, separators=(',', ':'))}"
            bad = os.path.join(td, "bad.txt")
            with open(bad, "w") as f:
                f.write("\n".join(lines) + "\n")
            r = subprocess.run(
                [sys.executable, os.path.join(ROOT, "scripts", "verify_plans.py"), bad],
                capture_output=True, text=True, env=env,
            )
            self.assertEqual(r.returncode, 1, r.stdout + r.stderr)
            self.assertIn("accounting", r.stdout)
            self.assertIn("FAIL", r.stdout)


class TestMemcheckXLACrossCheck(TestCase):
    """The acceptance pin: memcheck's static peak on the three GATED
    redistribution bench programs is within 2x of the compiler's own
    memory_analysis() on the tier-1 CPU mesh. Compile-only (ht.zeros
    operands; nothing executes beyond the zeros placement)."""

    @pytest.mark.skipif(P != 8, reason="pinned on the tier-1 8-device mesh")
    def test_gated_rows_within_2x_of_xla(self):
        cases = {
            "resplit_1gb": (
                ht.zeros((1000, 250000), split=0),
                lambda y: y.resplit(1),
            ),
            "reshape_split1_1gb": (
                ht.zeros((1000, 250000), split=1),
                lambda y: ht.reshape(y, (10_000_000, -1), new_split=1),
            ),
            "reshape_lane_1gb": (
                ht.zeros((65536, 4096), split=1),
                lambda y: ht.reshape(y, (131072, 2048), new_split=1),
            ),
        }
        for row, (x, fn) in cases.items():
            rep = ht.analysis.memcheck(fn, x)
            ctx = rep.context
            self.assertGreater(ctx["static_peak_bytes"], 0, row)
            self.assertIn("xla_peak_bytes", ctx, f"{row}: no memory_analysis on this backend")
            ratio = ctx["static_peak_bytes"] / max(ctx["xla_peak_bytes"], 1)
            self.assertGreaterEqual(ratio, 0.5, f"{row}: model under XLA/2 ({ratio:.2f})")
            self.assertLessEqual(ratio, 2.0, f"{row}: model over 2x XLA ({ratio:.2f})")
            # the gated rows themselves stay finding-free
            self.assertEqual([str(f) for f in rep.errors], [], row)


if __name__ == "__main__":
    import unittest

    unittest.main()
