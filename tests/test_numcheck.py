"""Pass 6 (numcheck) — precision-flow & tolerance-budget verifier
(ISSUE 17).

Contracts pinned here:

- Every SL6xx golden bad fixture trips at its DECLARED severity (SL601
  warning below the 65536 extent / error at or past it, SL602 error at
  default MXU precision / info when HIGHEST-stamped or
  pragma-acknowledged, SL603 error on both carry arms, SL604 warning
  under the x64-off policy), and every clean twin comes back clean —
  the fix each finding names really is the fix.
- The IR rules (SL601-SL603) are folded into ``ht.analysis.check``;
  SL604 stays standalone-only (a source rule the jaxpr cannot witness),
  and the shared ``analysis/_dtypes.py`` vocabulary keeps SL104's
  widening verdict and SL601's low-precision verdict deciding casts in
  exactly one place.
- The ``HEAT_TPU_NUMCHECK_ACC_DIM`` gate moves the SL601 threshold
  (env and ``acc_dim=`` forms agree) without entering any program cache
  key, and the ``# numcheck: ignore[...]`` pragma downgrades without
  silencing.
- The shipped numeric contracts — TSQR, hSVD level-0, the collective
  matmul ring, ``quantized_allreduce_sum``, the kcluster serving
  endpoint, the driver training step — are numcheck-clean at zero
  errors, and the whole ``heat_tpu/`` tree passes the planar
  precision-policy source arm.
- Seeded mutations (the ci.sh proof): delete the PR 5 planar
  ``precision="highest"`` default -> SL602 error; strip the gram
  builders' ``preferred_element_type=jnp.float32`` -> SL601; narrow an
  EF carry to bf16 -> SL603.
- The ``tolerance`` invariant: every golden-matrix plan (all
  topologies, quant on and off) and every staged golden plan composes
  to exactly its ``quant.tol`` annotation, while >= 6 hand-mutated
  plans fail ``verify_plan`` with ``invariant="tolerance"`` and the
  defective step named (the tier-flip form lands as an SL605 finding
  from the standalone ``check_tolerance``).

Everything here runs on the tier-1 CPU mesh at 8 AND 5 devices — the
collective pins that need a real mesh carry their own skips.
"""

import copy
import importlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import heat_tpu as ht

import analysis_fixtures as fx

from heat_tpu.analysis import _dtypes, ircheck
from heat_tpu.analysis.planverify import (
    PlanVerificationError,
    check_tolerance,
    verify_plan,
)
from heat_tpu.kernels import quant
from heat_tpu.redistribution import planner

from test_suites.basic_test import TestCase, env_pin

# the module is shadowed by the function in the package namespace
numcheck_mod = importlib.import_module("heat_tpu.analysis.numcheck")
numcheck = numcheck_mod.numcheck

P = len(jax.devices())
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BUDGET = planner.DEFAULT_BUDGET_MB << 20

PLANAR_REL = "heat_tpu/core/complex_planar.py"


def _read(rel):
    with open(os.path.join(ROOT, rel), encoding="utf-8") as f:
        return f.read()


def _gauss_args(n=64):
    k = jnp.linspace(0.0, 1.0, n * n, dtype=jnp.float32).reshape(n, n)
    return k, k + 1.0, k + 2.0, k + 3.0


# ------------------------------------------------------------------ #
# golden bad fixtures: each rule trips at its declared severity      #
# ------------------------------------------------------------------ #
class TestGoldenBadFixtures(TestCase):
    def test_low_precision_gram_trips_sl601_warning(self):
        x = jnp.zeros((2048, 64), jnp.bfloat16)
        rep = numcheck(fx.low_precision_gram_program, x)
        hits = [f for f in rep.findings if f.rule == "SL601"]
        self.assertTrue(hits, [repr(f) for f in rep.findings])
        # extent 2048 is past the 1024 gate but below the error floor
        self.assertTrue(all(f.severity == "warning" for f in hits))
        self.assertTrue(rep.ok)  # warnings report, never gate
        clean = numcheck(fx.f32_accum_gram_program, x)
        self.assertEqual([f for f in clean.findings if f.rule == "SL601"], [])

    def test_raw_bf16_reduce_trips_sl601_error(self):
        x = jnp.zeros((70000,), jnp.bfloat16)
        rep = numcheck(fx.low_precision_reduce_program, x)
        hits = [f for f in rep.findings if f.rule == "SL601"]
        self.assertTrue(hits, [repr(f) for f in rep.findings])
        # extent 70000 >= 65536: every bf16 partial saturates 8 mantissa
        # bits long before the sum closes — error, gates
        self.assertTrue(all(f.severity == "error" for f in hits))
        self.assertFalse(rep.ok)
        # jnp.sum auto-upcasts internally: the clean twin IS the idiom
        clean = numcheck(fx.upcast_reduce_program, x)
        self.assertEqual([f for f in clean.findings if f.rule == "SL601"], [])

    def test_gauss_default_precision_trips_sl602_error(self):
        rep = numcheck(fx.gauss_default_precision_program, *_gauss_args())
        hits = [f for f in rep.findings if f.rule == "SL602"]
        # both cancelling outputs (p1-p2 and p3-p1-p2) are findings
        self.assertGreaterEqual(len(hits), 2, [repr(f) for f in rep.findings])
        self.assertTrue(all(f.severity == "error" for f in hits))
        self.assertFalse(rep.ok)

    def test_gauss_highest_precision_downgrades_to_info(self):
        rep = numcheck(fx.gauss_highest_precision_program, *_gauss_args())
        hits = [f for f in rep.findings if f.rule == "SL602"]
        self.assertTrue(hits)
        self.assertTrue(all(f.severity == "info" for f in hits))
        self.assertTrue(rep.ok)

    def test_bf16_scan_carry_trips_sl603_error(self):
        x = jnp.linspace(0.0, 1.0, 16 * 8, dtype=jnp.float32).reshape(16, 8)
        rep = numcheck(fx.bf16_carry_scan_program, x)
        hits = [f for f in rep.findings if f.rule == "SL603"]
        self.assertTrue(hits, [repr(f) for f in rep.findings])
        self.assertTrue(all(f.severity == "error" for f in hits))
        clean = numcheck(fx.f32_carry_scan_program, x)
        self.assertEqual([f for f in clean.findings if f.rule == "SL603"], [])

    def test_bf16_ef_carry_trips_sl603_error(self):
        carry = jnp.zeros((128,), jnp.float32)
        grad = jnp.ones((128,), jnp.float32)
        rep = numcheck(fx.bf16_ef_carry_program, carry, grad)
        hits = [f for f in rep.findings if f.rule == "SL603"]
        self.assertTrue(hits, [repr(f) for f in rep.findings])
        self.assertTrue(all(f.severity == "error" for f in hits))
        clean = numcheck(fx.f32_ef_carry_program, carry, grad)
        self.assertEqual([f for f in clean.findings if f.rule == "SL603"], [])

    def test_f64_request_trips_sl604_under_x64_off_policy(self):
        x = jnp.ones((32,), jnp.float32)
        rep = numcheck(fx.f64_request_program, x, x64=False)
        hits = [f for f in rep.findings if f.rule == "SL604"]
        self.assertTrue(hits, [repr(f) for f in rep.findings])
        self.assertTrue(all(f.severity == "warning" for f in hits))
        self.assertTrue(hits[0].path.endswith("analysis_fixtures.py"))
        self.assertTrue(hits[0].line)
        # with x64 honored there is nothing to degrade
        on = numcheck(fx.f64_request_program, x, x64=True)
        self.assertEqual([f for f in on.findings if f.rule == "SL604"], [])
        clean = numcheck(fx.f32_request_program, x, x64=False)
        self.assertEqual([f for f in clean.findings if f.rule == "SL604"], [])


# ------------------------------------------------------------------ #
# the acc-dim gate and the acknowledgement pragma                    #
# ------------------------------------------------------------------ #
class TestThresholdAndPragma(TestCase):
    def test_acc_dim_param_moves_the_sl601_threshold(self):
        x = jnp.zeros((512, 64), jnp.bfloat16)  # extent 512 < 1024
        self.assertEqual(
            [f.rule for f in numcheck(fx.low_precision_gram_program, x).findings
             if f.rule == "SL601"],
            [],
        )
        rep = numcheck(fx.low_precision_gram_program, x, acc_dim=256)
        self.assertIn("SL601", [f.rule for f in rep.findings])
        self.assertEqual(rep.context["acc_dim"], 256)

    def test_acc_dim_gate_moves_the_sl601_threshold(self):
        x = jnp.zeros((512, 64), jnp.bfloat16)
        with env_pin("HEAT_TPU_NUMCHECK_ACC_DIM", "256"):
            rep = numcheck(fx.low_precision_gram_program, x)
        self.assertIn("SL601", [f.rule for f in rep.findings])
        self.assertEqual(rep.context["acc_dim"], 256)

    def test_acc_dim_gate_never_enters_program_keys(self):
        """affects_programs=False: the threshold tunes a REPORT, not a
        program — flipping it must leave every cache roster alone."""
        from heat_tpu.core import gates

        spec = gates.GATES["HEAT_TPU_NUMCHECK_ACC_DIM"]
        self.assertFalse(spec.affects_programs)
        self.assertEqual(len(spec.scopes), 0)

    def test_pragma_downgrades_sl602_to_info(self):
        self.assertEqual(
            numcheck_mod.fn_pragmas(fx.gauss_pragma_acknowledged_program),
            frozenset({"SL602"}),
        )
        rep = numcheck(fx.gauss_pragma_acknowledged_program, *_gauss_args())
        hits = [f for f in rep.findings if f.rule == "SL602"]
        self.assertTrue(hits)  # acknowledged, not silenced
        self.assertTrue(all(f.severity == "info" for f in hits))
        self.assertTrue(rep.ok)


# ------------------------------------------------------------------ #
# the check() fold and the shared dtype vocabulary                   #
# ------------------------------------------------------------------ #
class TestCheckFold(TestCase):
    def test_check_folds_sl602(self):
        rep = ht.analysis.check(fx.gauss_default_precision_program, *_gauss_args())
        self.assertIn("SL602", [f.rule for f in rep.findings])

    def test_check_folds_sl601(self):
        x = jnp.zeros((2048, 64), jnp.bfloat16)
        rep = ht.analysis.check(fx.low_precision_gram_program, x)
        self.assertIn("SL601", [f.rule for f in rep.findings])

    def test_check_does_not_fold_sl604(self):
        """SL604 is standalone-only: a SOURCE rule the jaxpr cannot
        witness — folding it would re-flag every sanctioned widening
        SL104 already prices."""
        x = jnp.ones((32,), jnp.float32)
        rep = ht.analysis.check(fx.f64_request_program, x)
        self.assertNotIn("SL604", [f.rule for f in rep.findings])

    def test_jit_wrapper_carries_numcheck_hook(self):
        @ht.jit
        def program(a, b):
            return jnp.matmul(a, b)

        rep = program.numcheck(
            jnp.zeros((2048, 64), jnp.bfloat16).T,
            jnp.zeros((2048, 64), jnp.bfloat16),
        )
        self.assertIn("SL601", [f.rule for f in rep.findings])
        self.assertEqual(rep.context["pass"], "numcheck")

    def test_dtype_vocabulary_is_shared(self):
        """SL104 (ircheck) and SL601-SL603 (numcheck) read the SAME
        ``_dtypes.py`` classifiers — the two passes can never disagree
        on what a cast costs."""
        self.assertIs(ircheck._effective_itemsize, _dtypes.effective_itemsize)
        self.assertIs(ircheck._lossy_narrowing, _dtypes.lossy_narrowing)
        self.assertIs(ircheck._promotion_ceiling, _dtypes.promotion_ceiling)
        self.assertIs(ircheck._widens_past, _dtypes.widens_past)
        self.assertIs(numcheck_mod._dtypes, _dtypes)
        self.assertTrue(_dtypes.is_low_precision(jnp.bfloat16))
        self.assertTrue(_dtypes.is_low_precision(jnp.float16))
        self.assertFalse(_dtypes.is_low_precision(jnp.float32))
        # lossy_narrowing is SL104's float->int8 arm; the bf16 cast
        # shape belongs to SL603's low-precision walk instead
        self.assertTrue(_dtypes.lossy_narrowing(jnp.float32, jnp.int8))
        self.assertFalse(_dtypes.lossy_narrowing(jnp.float32, jnp.bfloat16))


# ------------------------------------------------------------------ #
# shipped numeric contracts stay clean                               #
# ------------------------------------------------------------------ #
class TestCleanPins(TestCase):
    @pytest.mark.skipif(P < 2, reason="needs a real mesh")
    def test_tsqr_numcheck_clean(self):
        a = ht.random.randn(16 * P, 2 * P, split=0)
        rep = numcheck(lambda v: ht.linalg.qr(v), a)
        self.assertEqual(rep.errors, [])

    @pytest.mark.skipif(P < 2, reason="needs a real mesh")
    def test_hsvd_level0_numcheck_clean(self):
        from heat_tpu.core.linalg.svdtools import _local_svd_fn

        comm = ht.get_comm()
        phys = comm.shard(jnp.ones((16, 4 * P), jnp.float32), 1)
        fn = _local_svd_fn(
            comm.mesh, comm.axis_name, 16, phys.shape[1] // P, 3, "float32", 5
        )
        rep = numcheck(fn, phys)
        self.assertEqual(rep.errors, [])

    @pytest.mark.skipif(P < 2, reason="needs a real mesh")
    def test_ring_cmatmul_numcheck_clean(self):
        a = ht.ones((512, 64 * P), split=1)
        b = ht.ones((64 * P, 512), split=0)
        with env_pin(planner.OVERLAP_ENV, "1"):
            rep = numcheck(lambda u, v: ht.matmul(u, v), a, b)
        self.assertEqual(rep.errors, [])

    @pytest.mark.skipif(P < 2, reason="needs a real mesh")
    def test_quantized_allreduce_numcheck_clean(self):
        """The int8 wire codec accumulates FULL-WIDTH (decode-then-sum,
        f32 EF residual) — the shape SL601/SL603 exist to protect."""
        from jax.sharding import PartitionSpec as PS

        from jax import shard_map

        comm = self.comm

        def body(hl):
            out, resid = quant.quantized_allreduce_sum(
                hl[0], comm.axis_name, P, "int8"
            )
            return out[None], resid[None]

        f = shard_map(
            body,
            mesh=comm.mesh,
            in_specs=(PS(comm.axis_name, None),),
            out_specs=(PS(comm.axis_name, None), PS(comm.axis_name, None)),
            check_vma=False,
        )
        phys = comm.shard(jnp.ones((P, 5000), jnp.float32), 0)
        rep = numcheck(f, phys)
        self.assertEqual(rep.errors, [])

    def test_kcluster_endpoint_numcheck_clean(self):
        from heat_tpu.cluster import _kcluster

        centers = jnp.linspace(0.0, 1.0, 5 * 12, dtype=jnp.float32).reshape(5, 12)
        spec = _kcluster.serving_spec("euclidean", centers)
        prog = spec["build"]()
        batch = jnp.zeros((8, 12), jnp.float32)
        rep = numcheck(prog, batch, *spec["args"])
        self.assertEqual(rep.errors, [])

    @pytest.mark.skipif(P < 2, reason="needs a real mesh")
    def test_training_step_numcheck_clean(self):
        import __graft_entry__ as graft

        fn, args = graft.training_step_program(P)
        rep = numcheck(fn, *args)
        self.assertEqual(rep.errors, [])
        self.assertEqual(rep.context["pass"], "numcheck")

    def test_tree_passes_the_planar_policy_arm(self):
        rep = numcheck_mod.lint_paths([os.path.join(ROOT, "heat_tpu")], root=ROOT)
        self.assertEqual([str(f) for f in rep.findings], [])
        self.assertEqual(rep.context["pass"], "numcheck")


# ------------------------------------------------------------------ #
# seeded mutations (the ci.sh proof)                                 #
# ------------------------------------------------------------------ #
class TestSeededMutations(TestCase):
    """Remove ONE precision invariant, the verifier trips. Each
    mutation asserts its anchor still exists, so source drift fails
    loudly instead of silently weakening the proof."""

    def test_mutation_dropped_planar_highest_default_trips_sl602(self):
        """Invariant: the PR 5 planar fix — every Gauss-form op in
        core/complex_planar.py defaults its MXU precision to HIGHEST.
        Mutation: delete the default — the 13% on-chip defect comes
        back, and the policy arm catches it at PR time."""
        src = _read(PLANAR_REL)
        needle = '    if precision is None:\n        precision = "highest"\n'
        self.assertIn(needle, src)
        clean = numcheck_mod.lint_source(src, PLANAR_REL)
        self.assertEqual([f for f in clean if f.severity == "error"], [])
        mutated = src.replace(needle, "")
        found = numcheck_mod.lint_source(mutated, PLANAR_REL)
        hits = [f for f in found if f.rule == "SL602"]
        self.assertTrue(hits, [repr(f) for f in found])
        self.assertTrue(all(f.severity == "error" for f in hits))
        self.assertTrue(all(f.path == PLANAR_REL for f in hits))

    def test_mutation_policy_table_tracks_the_module(self):
        """Every op the policy table prices exists in the planar module
        — a renamed op would silently drop out of enforcement, so the
        drift is itself an error."""
        import ast

        policy = numcheck_mod.PLANAR_PRECISION_POLICY
        self.assertEqual(policy["matmul"], "highest")
        self.assertEqual(policy["dot"], "highest")
        tree = ast.parse(_read(PLANAR_REL))
        defs = {
            n.name for n in ast.walk(tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        }
        for op in policy:
            self.assertIn(op, defs, f"policy op {op!r} not in {PLANAR_REL}")

    def test_mutation_stripped_gram_accumulator_trips_sl601(self):
        """Invariant: the kcluster gram builders accumulate wide
        (``preferred_element_type=jnp.float32``, cluster/_pallas.py).
        Mutation: strip the argument on a bf16 gram — the accumulator
        collapses to bf16 and SL601 fires."""
        src = _read("heat_tpu/cluster/_pallas.py")
        self.assertGreaterEqual(
            src.count("preferred_element_type=jnp.float32"), 2
        )
        x = jnp.zeros((2048, 64), jnp.bfloat16)
        kept = numcheck_mod.scan_jaxpr_precision(
            jax.make_jaxpr(fx.f32_accum_gram_program)(x)
        )
        self.assertEqual([f.rule for f in kept if f.rule == "SL601"], [])
        stripped = numcheck_mod.scan_jaxpr_precision(
            jax.make_jaxpr(fx.low_precision_gram_program)(x)
        )
        self.assertIn("SL601", [f.rule for f in stripped])

    def test_mutation_narrowed_ef_carry_trips_sl603(self):
        """Invariant: optim/dp_optimizer.py holds its error-feedback
        carry in f32 (the residual IS the low-order bits). Mutation:
        return the carry narrowed to bf16 — pass 6 sees the
        cross-program cast."""
        carry = jnp.zeros((128,), jnp.float32)
        grad = jnp.ones((128,), jnp.float32)
        kept = numcheck_mod.scan_jaxpr_precision(
            jax.make_jaxpr(fx.f32_ef_carry_program)(carry, grad)
        )
        self.assertEqual([f.rule for f in kept if f.rule == "SL603"], [])
        narrowed = numcheck_mod.scan_jaxpr_precision(
            jax.make_jaxpr(fx.bf16_ef_carry_program)(carry, grad)
        )
        hits = [f for f in narrowed if f.rule == "SL603"]
        self.assertTrue(hits)
        self.assertTrue(all(f.severity == "error" for f in hits))


# ------------------------------------------------------------------ #
# the tolerance invariant (pass 6's dynamic half)                    #
# ------------------------------------------------------------------ #
class TestToleranceInvariant(TestCase):
    def test_all_golden_plans_tolerance_clean(self):
        n = 0
        for topo in ("flat", "2x4", "2x8"):
            for q in ("0", "int8"):
                for name, spec in planner.golden_specs():
                    sched = planner.plan(spec, BUDGET, quant=q, topology=topo)
                    res = verify_plan(sched, topology=topo)
                    self.assertTrue(res["ok"], f"{name}@{topo} quant={q}")
                    self.assertIn("tolerance", res["checks"])
                    self.assertEqual(check_tolerance(sched), [], f"{name}@{topo}")
                    n += 1
        self.assertEqual(n, 3 * 2 * len(planner.golden_specs()))

    def test_staged_golden_plans_tolerance_clean(self):
        from heat_tpu.redistribution import staging

        for name, sched in staging.golden_staged_plans():
            res = verify_plan(sched)
            self.assertTrue(res["ok"], name)
            self.assertIn("tolerance", res["checks"])
            self.assertEqual(check_tolerance(sched), [], name)

    def test_composed_bound_equals_the_codec_tolerance(self):
        """The arithmetic contract behind the invariant: a quantized
        plan's declared tol IS the codec's pinned per-crossing bound,
        and the step-level recomputation reproduces it."""
        spec = dict(planner.golden_specs())["resplit_chunked_2gb_p8"]
        sched = planner.plan(spec, BUDGET, quant="int8", topology="flat")
        self.assertEqual(sched.quant_tolerance, quant.tolerance("int8"))
        tols = sched.step_tolerances()
        self.assertEqual(len(tols), len(sched.steps))
        q_idx = [k for k, st in enumerate(sched.steps) if st.kind == "quantize"]
        self.assertTrue(q_idx)
        for k, t in enumerate(tols):
            expect = quant.tolerance("int8") if k in q_idx else 0.0
            self.assertEqual(t, expect, f"step {k}")
        # disjoint chunks: the end-to-end bound is the max leg, and
        # every leg composes to exactly one crossing
        self.assertEqual(
            quant.compose_tolerance([tols[q_idx[0]]]), sched.quant_tolerance
        )
        self.assertEqual(quant.compose_tolerance([]), 0.0)
        self.assertEqual(quant.compose_tolerance([0.25, 0.25]), 0.5)
        self.assertEqual(planner.quant_tolerance(None), 0.0)
        self.assertEqual(planner.quant_tolerance("int8"), quant.tolerance("int8"))

    def test_exact_bit_plans_declare_zero(self):
        spec = dict(planner.golden_specs())["resplit_chunked_2gb_p8"]
        sched = planner.plan(spec, BUDGET, quant="0", topology="flat")
        self.assertEqual(sched.quant_tolerance, 0.0)
        self.assertEqual(sched.step_tolerances(), [0.0] * len(sched.steps))

    def test_tolerance_hooks_never_touch_serialization(self):
        """The Schedule-side hooks are read-only: calling them leaves
        the canonical bytes (and so the plan_id) unchanged."""
        spec = dict(planner.golden_specs())["resplit_chunked_2gb_p8"]
        sched = planner.plan(spec, BUDGET, quant="int8", topology="flat")
        before = sched.canonical_json()
        self.assertGreater(sched.quant_tolerance, 0.0)
        self.assertTrue(any(t > 0.0 for t in sched.step_tolerances()))
        self.assertEqual(sched.canonical_json(), before)

    # -- the seeded tolerance mutations (>= 6 name the step) -------- #
    def _qplan(self, topo="flat", quant_mode="int8"):
        spec = dict(planner.golden_specs())["resplit_chunked_2gb_p8"]
        sched = planner.plan(spec, BUDGET, quant=quant_mode, topology=topo)
        return json.loads(sched.canonical_json())

    def _expect_tolerance(self, m, step_named=True, topo=None):
        with self.assertRaises(PlanVerificationError) as cm:
            verify_plan(m, topology=topo)
        self.assertEqual(cm.exception.invariant, "tolerance", str(cm.exception))
        if step_named:
            self.assertIn("step [", str(cm.exception))
        # the non-raising mode and the standalone entry agree
        res = verify_plan(m, topology=topo, raise_on_violation=False)
        self.assertIn("tolerance", [v["invariant"] for v in res["violations"]])
        found = check_tolerance(m)
        self.assertTrue(found)
        self.assertTrue(all(f.rule == "SL605" for f in found))
        return cm.exception

    def test_mutation_doubled_tol_annotation_fails_tolerance(self):
        """Loosen the declared budget 2x: the recomposition says the
        steps only spend the codec's pinned bound."""
        m = self._qplan()
        m["quant"]["tol"] = m["quant"]["tol"] * 2
        self._expect_tolerance(m, step_named=False)

    def test_mutation_zeroed_tol_annotation_fails_tolerance(self):
        """Claim exact-bit on a quantized plan: the quantize steps
        provably spend tolerance the annotation denies."""
        m = self._qplan()
        m["quant"]["tol"] = 0.0
        self._expect_tolerance(m, step_named=False)

    def test_mutation_encode_mode_swap_names_the_step(self):
        """Retag one encode step bf16 in an int8 plan: the per-step
        contract (mode pins the detail prefix) breaks at that step."""
        m = self._qplan()
        qs = [k for k, st in enumerate(m["steps"]) if st["kind"] == "quantize"]
        st = m["steps"][qs[0]]
        st["detail"] = st["detail"].replace("int8-encode", "bf16-encode", 1)
        e = self._expect_tolerance(m)
        self.assertIn(f"step [{qs[0]}] (quantize)", str(e))

    def test_mutation_requantized_chunk_names_the_step(self):
        """Point the second encode at the FIRST chunk's leg: that leg
        would cross the wire encoded twice — the composition doubles
        past the declared budget."""
        m = self._qplan()
        qs = [k for k, st in enumerate(m["steps"]) if st["kind"] == "quantize"]
        self.assertGreaterEqual(len(qs), 2)
        m["steps"][qs[1]]["chunk"] = m["steps"][qs[0]]["chunk"]
        e = self._expect_tolerance(m)
        self.assertIn(f"step [{qs[1]}] (quantize)", str(e))

    def test_mutation_stripped_wire_marker_names_the_step(self):
        """Strip the ``[int8 wire]`` suffix from a sandwiched
        collective: the encode/decode pair brackets a step that no
        longer claims the encoded payload."""
        m = self._qplan()
        k = next(
            k for k, st in enumerate(m["steps"])
            if st["kind"] == "all_to_all"
            and st.get("detail", "").endswith(" [int8 wire]")
        )
        st = m["steps"][k]
        st["detail"] = st["detail"][: -len(" [int8 wire]")]
        e = self._expect_tolerance(m)
        self.assertIn(f"step [{k}] (all_to_all)", str(e))

    def test_mutation_forged_wire_marker_names_the_step(self):
        """Forge an ``[int8 wire]`` claim on an EXACT-BIT plan: a
        collective spends tolerance no quant annotation budgets."""
        m = self._qplan(quant_mode="0")
        self.assertIsNone(m.get("quant"))
        k = next(
            k for k, st in enumerate(m["steps"]) if st["kind"] == "all_to_all"
        )
        m["steps"][k]["detail"] = m["steps"][k]["detail"] + " [int8 wire]"
        e = self._expect_tolerance(m)
        self.assertIn(f"step [{k}] (all_to_all)", str(e))

    def test_mutation_corrupted_decode_names_the_step(self):
        """Corrupt the decode detail after an encode: the sandwich
        closes on a step that no longer proves the full-width
        reconstruction."""
        m = self._qplan()
        k = next(
            k for k, st in enumerate(m["steps"]) if st["kind"] == "dequantize"
        )
        m["steps"][k]["detail"] = "corrupt " + m["steps"][k]["detail"]
        e = self._expect_tolerance(m)
        self.assertIn(f"step [{k}] (dequantize)", str(e))

    def test_mutation_tier_flip_lands_as_sl605(self):
        """Relabel a codec-carrying dcn hop as ici in a hierarchical
        plan: ``verify_plan`` trips the earlier ``tier-labels``
        invariant by design (alternation breaks first), so the
        standalone ``check_tolerance`` proves the tolerance-side
        verdict — SL605, the step named."""
        m = self._qplan(topo="2x4")
        self.assertEqual(m["strategy"], "hierarchical-a2a")
        k = next(
            k for k, st in enumerate(m["steps"])
            if st.get("tier") == "dcn"
            and k > 0
            and m["steps"][k - 1]["kind"] == "quantize"
        )
        m["steps"][k]["tier"] = "ici"
        found = check_tolerance(m)
        self.assertTrue(found)
        self.assertTrue(all(f.rule == "SL605" for f in found))
        self.assertTrue(all(f.severity == "error" for f in found))
        self.assertIn(f"step [{k}]", str(found[0]))

    def test_check_tolerance_names_the_plan(self):
        m = self._qplan()
        m["quant"]["tol"] = 0.0
        found = check_tolerance(m)
        self.assertTrue(found)
        self.assertIn(m["plan_id"], str(found[0]))


# ------------------------------------------------------------------ #
# lint.py CLI: pass 6 rides the single CI lint entry                 #
# ------------------------------------------------------------------ #
class TestLintCLI(TestCase):
    def test_pass_numcheck_clean_tree_exits_zero(self):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        r = subprocess.run(
            [
                sys.executable,
                os.path.join(ROOT, "scripts", "lint.py"),
                os.path.join(ROOT, "heat_tpu"),
                "--pass",
                "numcheck",
            ],
            capture_output=True,
            text=True,
            env=env,
        )
        self.assertEqual(r.returncode, 0, r.stdout + r.stderr)
        self.assertIn("[numcheck]", r.stdout)

    def test_pass_all_runs_four_passes_in_one_process(self):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        r = subprocess.run(
            [
                sys.executable,
                os.path.join(ROOT, "scripts", "lint.py"),
                os.path.join(ROOT, "heat_tpu"),
                "--pass",
                "all",
            ],
            capture_output=True,
            text=True,
            env=env,
        )
        self.assertEqual(r.returncode, 0, r.stdout + r.stderr)
        for tag in ("[srclint]", "[effectcheck]", "[commcheck]", "[numcheck]"):
            self.assertIn(tag, r.stdout)


# ------------------------------------------------------------------ #
# scripts/verify_plans.py sweeps the tolerance invariant             #
# ------------------------------------------------------------------ #
class TestVerifyPlansSweep(TestCase):
    @pytest.mark.slow
    def test_sweep_passes_and_mutated_dump_names_tolerance(self):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        dump = subprocess.run(
            [sys.executable, os.path.join(ROOT, "scripts", "redist_plans.py")],
            capture_output=True,
            text=True,
            env=env,
        )
        self.assertEqual(dump.returncode, 0, dump.stderr)
        ok = subprocess.run(
            [sys.executable, os.path.join(ROOT, "scripts", "verify_plans.py")],
            input=dump.stdout,
            capture_output=True,
            text=True,
            env=env,
        )
        self.assertEqual(ok.returncode, 0, ok.stdout + ok.stderr)
        # hand-mutate one quantized plan's tol annotation: the sweep
        # fails naming the tolerance invariant
        lines = dump.stdout.splitlines()
        mutated = []
        hit = False
        for line in lines:
            name, _, payload = line.partition("\t")
            if payload and not hit:
                d = json.loads(payload)
                if d.get("quant"):
                    d["quant"]["tol"] = float(d["quant"]["tol"]) * 2
                    payload = json.dumps(
                        d, sort_keys=True, separators=(",", ":")
                    )
                    hit = True
            mutated.append(f"{name}\t{payload}" if payload else line)
        self.assertTrue(hit, "no quantized plan in the dump")
        bad = subprocess.run(
            [sys.executable, os.path.join(ROOT, "scripts", "verify_plans.py")],
            input="\n".join(mutated) + "\n",
            capture_output=True,
            text=True,
            env=env,
        )
        self.assertEqual(bad.returncode, 1, bad.stdout + bad.stderr)
        self.assertIn("tolerance", bad.stdout)


if __name__ == "__main__":
    import unittest

    unittest.main()
