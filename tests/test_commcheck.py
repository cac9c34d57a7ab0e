"""Pass 5 (commcheck) — SPMD collective-congruence & progress verifier
(ISSUE 14).

Contracts pinned here:

- Every SL5xx golden bad fixture trips at its DECLARED severity (SL501
  error, SL502 error, SL503 error-on-cycle / warning-on-independent,
  SL504 warning), and every clean twin comes back clean — the fix each
  finding names really is the fix.
- The IR rules are folded into ``ht.analysis.check`` (one report proves
  congruence AND the SL1xx movement rules), and the shared
  ``analysis/_groups.py`` parser keeps SL107's cross-tier verdict and
  SL502's congruence verdict reading the same HLO line identically.
- The shipped collective contracts — TSQR (barrier AND forced-ring
  forms), hSVD level-0, the collective-matmul ring, the kcluster
  serving endpoint, the driver training step — are commcheck-clean at
  zero errors, and the whole ``heat_tpu/`` tree is SL504-clean.
- The ``progress`` invariant: every golden-matrix plan (all topologies,
  quant on and off) and every staged golden plan replays to completion,
  while a hand-mutated plan fails ``verify_plan`` with
  ``invariant="progress"`` and the violating step named.
- Seeded mutations (the ci.sh proof): drop one pair from a
  ring_all_gather schedule -> SL502; make a cond predicate
  device-dependent -> SL501; remove the executor's / the endpoint's
  epoch-fence call -> SL504.
- The ``capture_epoch``/``check_epoch`` object-level fence: no-op until
  the elastic runtime stamps a world, typed ``WorldChangedError`` on a
  stale token, inert under ``HEAT_TPU_RESILIENCE=0``.

Everything here runs on the tier-1 CPU mesh at 8 AND 5 devices — the
group fixtures that need an even mesh carry their own skips.
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

from heat_tpu.analysis import findings
from heat_tpu.analysis.planverify import (
    PlanVerificationError,
    check_progress,
    verify_plan,
)
from heat_tpu.kernels import cmatmul
from heat_tpu.redistribution import planner
from heat_tpu.resilience import checkpoint as ck
from heat_tpu.resilience import elastic

from test_suites.basic_test import TestCase, env_pin

# the module is shadowed by the function in the package namespace
commcheck_mod = importlib.import_module("heat_tpu.analysis.commcheck")
commcheck = commcheck_mod.commcheck

P = len(jax.devices())
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BUDGET = planner.DEFAULT_BUDGET_MB << 20


def _read(rel):
    with open(os.path.join(ROOT, rel), encoding="utf-8") as f:
        return f.read()


def _x(rows=64, cols=8):
    return ht.array(
        np.arange(rows * cols, dtype=np.float32).reshape(rows, cols) + 1.0,
        split=0,
    )


# ------------------------------------------------------------------ #
# golden bad fixtures: each rule trips at its declared severity      #
# ------------------------------------------------------------------ #
class TestGoldenBadFixtures(TestCase):
    @pytest.mark.skipif(P < 2, reason="needs a real mesh")
    def test_divergent_cond_trips_sl501_error(self):
        rep = commcheck(fx.divergent_cond_collective_program, _x())
        hits = [f for f in rep.findings if f.rule == "SL501"]
        self.assertTrue(hits, [repr(f) for f in rep.findings])
        self.assertTrue(all(f.severity == "error" for f in hits))
        self.assertFalse(rep.ok)

    @pytest.mark.skipif(P < 2, reason="needs a real mesh")
    def test_uniform_cond_twin_is_clean(self):
        """The fix SL501 names — psum the local condition — is clean."""
        rep = commcheck(fx.uniform_cond_collective_program, _x())
        self.assertEqual(rep.rule_ids, [])

    @pytest.mark.skipif(P < 2, reason="needs a real mesh")
    def test_divergent_while_trips_sl501_error(self):
        rep = commcheck(fx.divergent_while_collective_program, _x())
        hits = [f for f in rep.findings if f.rule == "SL501"]
        self.assertTrue(hits)
        self.assertIn("while", hits[0].message)

    @pytest.mark.skipif(P < 2, reason="needs a real mesh")
    def test_open_ring_trips_sl502_error(self):
        rep = commcheck(fx.open_ring_program, _x())
        hits = [f for f in rep.findings if f.rule == "SL502"]
        self.assertTrue(hits, [repr(f) for f in rep.findings])
        self.assertTrue(all(f.severity == "error" for f in hits))
        self.assertIn("hang", hits[0].message)

    @pytest.mark.skipif(P < 2, reason="needs a real mesh")
    def test_closed_ring_twin_has_no_sl502(self):
        """The complete +1 ring (the SL101 fixture) is CONGRUENT — pass
        5 has no complaint even where pass 1 flags the movement."""
        rep = commcheck(fx.ppermute_ring_program, _x())
        self.assertNotIn("SL502", rep.rule_ids)

    @pytest.mark.skipif(P < 2, reason="needs a real mesh")
    def test_opposite_order_branches_trip_sl503_error(self):
        rep = commcheck(fx.opposite_order_collectives_program, _x())
        hits = [f for f in rep.findings if f.rule == "SL503"]
        self.assertTrue(hits, [repr(f) for f in rep.findings])
        self.assertTrue(all(f.severity == "error" for f in hits))
        self.assertIn("OPPOSITE", hits[0].message)
        # the divergence that arms the cycle is itself reported
        self.assertIn("SL501", rep.rule_ids)

    @pytest.mark.skipif(
        P < 4 or P % 2, reason="group fixtures need an even mesh >= 4"
    )
    def test_overlapping_groups_trip_sl503_warning(self):
        rep = commcheck(fx.overlapping_groups_program, _x())
        hits = [f for f in rep.findings if f.rule == "SL503"]
        self.assertTrue(hits, [repr(f) for f in rep.findings])
        self.assertTrue(all(f.severity == "warning" for f in hits))

    @pytest.mark.skipif(
        P < 4 or P % 2, reason="group fixtures need an even mesh >= 4"
    )
    def test_aligned_groups_twin_is_clean(self):
        rep = commcheck(fx.aligned_groups_program, _x())
        self.assertEqual(rep.rule_ids, [])

    @pytest.mark.skipif(P < 2, reason="needs a real mesh")
    def test_ir_rules_fold_into_check(self):
        """One ``ht.analysis.check`` report proves congruence AND the
        SL1xx movement rules — the pass-5 folding contract."""
        rep = ht.analysis.check(fx.divergent_cond_collective_program, _x())
        self.assertIn("SL501", rep.rule_ids)

    def test_unfenced_dispatch_src_trips_sl504_warning(self):
        found = commcheck_mod.lint_source(
            fx.UNFENCED_DISPATCH_SRC, "heat_tpu/serving/dispatcher.py"
        )
        hits = [f for f in found if f.rule == "SL504"]
        self.assertTrue(hits)
        self.assertTrue(all(f.severity == "warning" for f in hits))
        # both the public entry and the worker root are flagged
        self.assertGreaterEqual(len(hits), 2)

    def test_fenced_dispatch_twin_is_clean(self):
        found = commcheck_mod.lint_source(
            fx.FENCED_DISPATCH_SRC, "heat_tpu/serving/dispatcher.py"
        )
        self.assertEqual(found, [])

    def test_sl504_is_scoped_to_dispatch_modules(self):
        """The same unfenced source OUTSIDE the dispatch layer is not in
        scope — a public library op is not a dispatch entry."""
        found = commcheck_mod.lint_source(
            fx.UNFENCED_DISPATCH_SRC, "heat_tpu/core/_operations.py"
        )
        self.assertEqual(found, [])

    def test_fenced_dispatch_module_population_pinned(self):
        self.assertEqual(
            commcheck_mod.FENCED_DISPATCH_MODULES,
            ("redistribution/executor.py", "serving/dispatcher.py"),
        )

    def test_sl5xx_rules_are_cataloged(self):
        for rule in ("SL501", "SL502", "SL503", "SL504"):
            self.assertIn(rule, findings.RULES)


# ------------------------------------------------------------------ #
# the shared group parser: one verdict for SL107 and SL502           #
# ------------------------------------------------------------------ #
class TestSharedGroupParser(TestCase):
    def test_ircheck_uses_the_shared_parser(self):
        from heat_tpu.analysis import _groups, ircheck

        self.assertIs(ircheck._parse_groups, _groups.parse_groups)

    def test_iota_form_one_verdict(self):
        from heat_tpu.analysis import _groups

        line = "all-to-all(p0), replica_groups=[2,4]<=[8], dimensions={0}"
        want = [[0, 1, 2, 3], [4, 5, 6, 7]]
        self.assertEqual(_groups.parse_groups(line), want)
        self.assertEqual(_groups.parse_replica_groups(line), want)
        self.assertIsNone(_groups.partition_defect(want, 8))

    def test_explicit_form_and_defects(self):
        from heat_tpu.analysis import _groups

        line = "all-gather(p0), replica_groups={{0,1},{2,3}}"
        groups = _groups.parse_replica_groups(line)
        self.assertEqual(groups, [[0, 1], [2, 3]])
        # congruent over 4 devices, non-covering over 8
        self.assertIsNone(_groups.partition_defect(groups, 4))
        self.assertIn("no group", _groups.partition_defect(groups, 8))
        self.assertIn(
            "more than one", _groups.partition_defect([[0, 1], [1, 2]], 4)
        )

    def test_pair_defects(self):
        from heat_tpu.analysis import _groups

        # a complete ring is congruent; partner swaps are congruent
        ring = [(s, (s + 1) % 4) for s in range(4)]
        self.assertIsNone(_groups.permutation_defect(ring, 4))
        self.assertIsNone(_groups.permutation_defect([(0, 1), (1, 0)], 4))
        # the hang shapes
        self.assertIn(
            "duplicate source", _groups.permutation_defect([(0, 1), (0, 2)], 4)
        )
        self.assertIn(
            "duplicate target", _groups.permutation_defect([(0, 2), (1, 2)], 4)
        )
        self.assertIn(
            "outside", _groups.permutation_defect([(0, 9)], 4)
        )
        self.assertIn(
            "never", _groups.permutation_defect([(0, 1), (1, 2)], 4)
        )


# ------------------------------------------------------------------ #
# clean pins: the shipped collective contracts                       #
# ------------------------------------------------------------------ #
class TestCleanPins(TestCase):
    @pytest.mark.skipif(P < 2, reason="needs a real mesh")
    def test_tsqr_commcheck_clean(self):
        a = ht.random.randn(16 * P, 2 * P, split=0)
        rep = commcheck(lambda v: ht.linalg.qr(v), a)
        self.assertEqual(rep.errors, [])

    @pytest.mark.skipif(P < 2, reason="needs a real mesh")
    def test_tsqr_forced_ring_commcheck_clean(self):
        """The ring TSQR builds its permutation through
        ``grouped_ring_perm`` — complete by construction, and pass 5
        proves it on the compiled module."""
        a = ht.random.randn(16 * P, 2 * P, split=0)
        with env_pin(planner.OVERLAP_ENV, "1"):
            rep = commcheck(lambda v: ht.linalg.qr(v), a)
        self.assertEqual(rep.errors, [])

    @pytest.mark.skipif(P < 2, reason="needs a real mesh")
    def test_hsvd_level0_commcheck_clean(self):
        from heat_tpu.core.linalg.svdtools import _local_svd_fn

        comm = ht.get_comm()
        phys = comm.shard(jnp.ones((16, 4 * P), jnp.float32), 1)
        fn = _local_svd_fn(
            comm.mesh, comm.axis_name, 16, phys.shape[1] // P, 3, "float32", 5
        )
        rep = commcheck(fn, phys)
        self.assertEqual(rep.errors, [])
        self.assertEqual(rep.context["collective_counts"], {})

    @pytest.mark.skipif(P < 2, reason="needs a real mesh")
    def test_ring_cmatmul_commcheck_clean(self):
        a = ht.ones((512, 64 * P), split=1)
        b = ht.ones((64 * P, 512), split=0)
        with env_pin(planner.OVERLAP_ENV, "1"):
            rep = commcheck(lambda u, v: ht.matmul(u, v), a, b)
        self.assertEqual(rep.errors, [])

    def test_kcluster_endpoint_commcheck_clean(self):
        from heat_tpu.cluster import _kcluster

        centers = jnp.linspace(0.0, 1.0, 5 * 12, dtype=jnp.float32).reshape(5, 12)
        spec = _kcluster.serving_spec("euclidean", centers)
        prog = spec["build"]()
        batch = jnp.zeros((8, 12), jnp.float32)
        rep = commcheck(prog, batch, *spec["args"])
        self.assertEqual(rep.errors, [])

    @pytest.mark.skipif(P < 2, reason="needs a real mesh")
    def test_training_step_commcheck_clean(self):
        import __graft_entry__ as graft

        fn, args = graft.training_step_program(P)
        rep = commcheck(fn, *args)
        self.assertEqual(rep.errors, [])
        self.assertEqual(rep.context["pass"], "commcheck")

    def test_tree_is_sl504_clean(self):
        rep = commcheck_mod.lint_paths([os.path.join(ROOT, "heat_tpu")], root=ROOT)
        self.assertEqual([str(f) for f in rep.findings], [])


# ------------------------------------------------------------------ #
# the progress invariant (pass 5's dynamic half)                     #
# ------------------------------------------------------------------ #
class TestProgressInvariant(TestCase):
    def test_all_golden_plans_progress_clean(self):
        n = 0
        for topo in ("flat", "2x4", "2x8"):
            for q in ("0", "int8"):
                for name, spec in planner.golden_specs():
                    sched = planner.plan(spec, BUDGET, quant=q, topology=topo)
                    res = verify_plan(sched, topology=topo)
                    self.assertTrue(res["ok"], f"{name}@{topo} quant={q}")
                    self.assertIn("progress", res["checks"])
                    self.assertEqual(check_progress(sched), [], f"{name}@{topo}")
                    n += 1
        self.assertEqual(n, 3 * 2 * len(planner.golden_specs()))

    def test_staged_golden_plans_progress_clean(self):
        from heat_tpu.redistribution import staging

        for name, sched in staging.golden_staged_plans():
            res = verify_plan(sched)
            self.assertTrue(res["ok"], name)
            self.assertIn("progress", res["checks"])
            self.assertEqual(check_progress(sched), [], name)

    def _chunked(self, topo="flat"):
        spec = dict(planner.golden_specs())["resplit_chunked_2gb_p8"]
        sched = planner.plan(spec, BUDGET, quant="0", topology=topo)
        return json.loads(sched.canonical_json())

    def test_mutation_reordered_laps_fail_progress(self):
        """Swap the chunk tags of the first two overlap laps: bytes,
        kinds, counts all conserve — only the replay sees that the
        depth-2 double buffer would consume an unissued lap."""
        m = self._chunked()
        a2a = [k for k, st in enumerate(m["steps"]) if st["kind"] == "all_to_all"]
        self.assertGreaterEqual(len(a2a), 2)
        i, j = a2a[0], a2a[1]
        m["steps"][i]["chunk"], m["steps"][j]["chunk"] = (
            m["steps"][j]["chunk"],
            m["steps"][i]["chunk"],
        )
        with self.assertRaises(PlanVerificationError) as cm:
            verify_plan(m)
        self.assertEqual(cm.exception.invariant, "progress", str(cm.exception))
        self.assertIn("unissued lap", str(cm.exception))
        self.assertIn("pipe0", str(cm.exception))
        # the non-raising mode and the standalone entry agree
        res = verify_plan(m, raise_on_violation=False)
        self.assertIn("progress", [v["invariant"] for v in res["violations"]])
        found = check_progress(m)
        self.assertTrue(found)
        self.assertTrue(all(f.rule == "SL503" for f in found))

    def test_mutation_split_hierarchical_pair_fails_progress(self):
        """Retag one dcn half to a different chunk than its ici pivot:
        the inter-slice exchange would consume a lap the intra-slice
        half never issued."""
        m = self._chunked(topo="2x4")
        self.assertEqual(m["strategy"], "hierarchical-a2a")
        dcn = [k for k, st in enumerate(m["steps"]) if st.get("tier") == "dcn"]
        self.assertTrue(dcn)
        m["steps"][dcn[0]]["chunk"] = 7
        with self.assertRaises(PlanVerificationError) as cm:
            verify_plan(m, topology="2x4")
        self.assertEqual(cm.exception.invariant, "progress", str(cm.exception))

    def test_mutation_open_ring_named_by_standalone_replay(self):
        """Drop one hop from the ring plan: ``verify_plan`` fails at
        composition (exactly p-1 hops), and the standalone replay names
        the progress defect — defense in depth for plans that never
        came from this planner (the MPMD stage-graph case)."""
        spec = dict(planner.golden_specs())["resplit_ring_8gb_p8"]
        sched = planner.plan(spec, BUDGET, quant="0", topology="flat")
        m = json.loads(sched.canonical_json())
        hops = [k for k, st in enumerate(m["steps"]) if st["kind"] == "ppermute"]
        del m["steps"][hops[-1]]
        found = check_progress(m)
        self.assertTrue(found)
        self.assertTrue(any("ring does not close" in f.message for f in found))
        self.assertTrue(any("p-1" in f.message for f in found))
        with self.assertRaises(PlanVerificationError):
            verify_plan(m)

    def test_mutation_broken_topology_product_fails(self):
        """A topology annotation that does not factor the mesh can never
        partition it — both tier-labels and the replay refuse it."""
        m = self._chunked(topo="2x4")
        m["topology"]["n_slices"] = 3
        found = check_progress(m)
        self.assertTrue(any("partition" in f.message for f in found))
        res = verify_plan(m, raise_on_violation=False, topology=None)
        self.assertFalse(res["ok"])

    def test_check_progress_findings_name_the_plan(self):
        m = self._chunked()
        a2a = [k for k, st in enumerate(m["steps"]) if st["kind"] == "all_to_all"]
        m["steps"][a2a[0]]["chunk"], m["steps"][a2a[1]]["chunk"] = (
            m["steps"][a2a[1]]["chunk"],
            m["steps"][a2a[0]]["chunk"],
        )
        for f in check_progress(m):
            self.assertEqual(f.severity, "error")
            self.assertIn(str(m["plan_id"]), f.message)

    def test_congruence_hooks_never_touch_serialization(self):
        """The Schedule-side hooks are read-only: calling them leaves
        the canonical bytes (and so the plan_id) unchanged."""
        spec = dict(planner.golden_specs())["resplit_chunked_2gb_p8"]
        sched = planner.plan(spec, BUDGET, quant="0", topology="flat")
        before = sched.canonical_json()
        structure = sched.collective_group_structure()
        laps = sched.overlap_lap_chunks("pipe0")
        self.assertTrue(structure)
        self.assertEqual(laps, sorted(laps))
        self.assertEqual(sched.canonical_json(), before)

    def test_group_structure_partitions_the_mesh(self):
        """Every reported subgroup shape multiplies back to mesh_size —
        the partition property the replay re-proves on dumps."""
        for topo in ("flat", "2x4"):
            for name, spec in planner.golden_specs():
                sched = planner.plan(spec, BUDGET, quant="0", topology=topo)
                for g in sched.collective_group_structure():
                    self.assertEqual(
                        g["n_groups"] * g["group_size"],
                        sched.spec.mesh_size,
                        f"{name}@{topo}: {g}",
                    )


# ------------------------------------------------------------------ #
# seeded mutations (the ci.sh proof)                                 #
# ------------------------------------------------------------------ #
class TestSeededMutations(TestCase):
    """Remove ONE congruence invariant, the verifier trips. Each
    mutation asserts its anchor still exists, so source drift fails
    loudly instead of silently weakening the proof."""

    @pytest.mark.skipif(P < 2, reason="needs a real mesh")
    def test_mutation_dropped_ring_pair_trips_sl502(self):
        """Invariant: ring_all_gather rides the COMPLETE +1 ring from
        grouped_ring_perm. Mutation: drop the wraparound pair — the
        congruence scan sees a device that receives without sending."""
        from jax.sharding import PartitionSpec as PS

        from jax import shard_map

        comm = self.comm
        full = cmatmul.grouped_ring_perm(1, P)
        self.assertEqual(len(full), P)

        def program(perm):
            def body(xl):
                i = jax.lax.axis_index(comm.axis_name)
                return cmatmul.ring_all_gather(xl, comm.axis_name, P, i, perm)

            return shard_map(
                body,
                mesh=comm.mesh,
                in_specs=(PS(comm.axis_name, None),),
                out_specs=PS(None, None, None),
                check_vma=False,
            )

        phys = comm.shard(jnp.ones((4 * P, 4), jnp.float32), 0)
        clean = commcheck(program(full), phys)
        self.assertNotIn("SL502", [f.rule for f in clean.errors])
        mutated = commcheck(program(full[:-1]), phys)
        hits = [f for f in mutated.findings if f.rule == "SL502"]
        self.assertTrue(hits, [repr(f) for f in mutated.findings])
        self.assertTrue(all(f.severity == "error" for f in hits))

    @pytest.mark.skipif(P < 2, reason="needs a real mesh")
    def test_mutation_device_dependent_predicate_trips_sl501(self):
        """Invariant: a collective-launching cond rides a full-axis
        reduced predicate. Mutation: predicate becomes the LOCAL
        condition — one token, and the lattice sees the divergence."""
        from jax import lax
        from jax.sharding import PartitionSpec as PS

        from jax import shard_map

        comm = self.comm

        def program(mutated):
            def body(xl):
                local = (xl.sum() > 0.0).astype(jnp.float32)
                pred = local if mutated else lax.psum(local, comm.axis_name)
                return lax.cond(
                    pred > 0.0,
                    lambda v: lax.psum(v, comm.axis_name),
                    lambda v: v,
                    xl,
                )

            return shard_map(
                body,
                mesh=comm.mesh,
                in_specs=(PS(comm.axis_name, None),),
                out_specs=PS(comm.axis_name, None),
                check_vma=False,
            )

        phys = comm.shard(jnp.ones((4 * P, 4), jnp.float32), 0)
        self.assertEqual(commcheck(program(False), phys).rule_ids, [])
        rep = commcheck(program(True), phys)
        self.assertIn("SL501", [f.rule for f in rep.errors])

    def test_mutation_unfenced_executor_trips_sl504(self):
        """Invariant: the executor's entry carries the PR 13 epoch
        fence. Mutation: delete the check_world call."""
        src = _read("heat_tpu/redistribution/executor.py")
        anchor = "    _elastic.check_world(comm)\n"
        self.assertIn(anchor, src)
        clean = commcheck_mod.lint_source(src, "heat_tpu/redistribution/executor.py")
        self.assertEqual([f for f in clean if f.rule == "SL504"], [])
        mutated = src.replace(anchor, "")
        found = commcheck_mod.lint_source(
            mutated, "heat_tpu/redistribution/executor.py"
        )
        hits = [f for f in found if f.rule == "SL504"]
        self.assertTrue(hits, [repr(f) for f in found])
        self.assertIn("execute", hits[0].message)

    def test_mutation_unfenced_endpoint_trips_sl504(self):
        """Invariant: Endpoint.run fences on its world token. Mutation:
        delete the check_epoch call."""
        src = _read("heat_tpu/serving/dispatcher.py")
        anchor = "        _elastic.check_epoch(self._world_token"
        self.assertIn(anchor, src)
        clean = commcheck_mod.lint_source(src, "heat_tpu/serving/dispatcher.py")
        self.assertEqual([f for f in clean if f.rule == "SL504"], [])
        lines = [
            ln for ln in src.splitlines(keepends=True)
            if not ln.startswith(anchor)
        ]
        mutated = "".join(lines)
        self.assertNotEqual(mutated, src)
        found = commcheck_mod.lint_source(mutated, "heat_tpu/serving/dispatcher.py")
        hits = [f for f in found if f.rule == "SL504"]
        self.assertTrue(hits, [repr(f) for f in found])
        self.assertTrue(any("run" in f.message for f in hits))


# ------------------------------------------------------------------ #
# the object-level epoch fence (capture_epoch / check_epoch)         #
# ------------------------------------------------------------------ #
class TestEpochFence(TestCase):
    def test_noop_until_a_world_is_stamped(self):
        elastic._clear_stamps()
        token = elastic.capture_epoch()
        elastic.check_epoch(token)  # fresh: no-op
        elastic.check_epoch(None)  # unfenced holder: no-op
        elastic.check_epoch(token - 1)  # stale but fence disarmed: no-op

    def test_stale_token_raises_typed_and_hatch_inerts(self):
        class _Dummy:
            pass

        stale = _Dummy()
        try:
            elastic.stamp(stale)  # arm the fence
            token = elastic.capture_epoch() - 1  # a holder built pre-resize
            with env_pin(ck.RESILIENCE_ENV, "0"):
                elastic.check_epoch(token)  # escape hatch: never raises
            with env_pin(ck.RESILIENCE_ENV, "auto"):
                with self.assertRaises(elastic.WorldChangedError) as cm:
                    elastic.check_epoch(token, what="test endpoint")
                self.assertIn("test endpoint", str(cm.exception))
                elastic.check_epoch(elastic.capture_epoch())  # fresh: no-op
        finally:
            elastic._clear_stamps()


# ------------------------------------------------------------------ #
# the CLI face (scripts/lint.py --pass commcheck | all)              #
# ------------------------------------------------------------------ #
class TestLintCLI(TestCase):
    def test_pass_commcheck_clean_tree_exits_zero(self):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        r = subprocess.run(
            [
                sys.executable,
                os.path.join(ROOT, "scripts", "lint.py"),
                os.path.join(ROOT, "heat_tpu"),
                "--pass",
                "commcheck",
            ],
            capture_output=True,
            text=True,
            env=env,
        )
        self.assertEqual(r.returncode, 0, r.stdout + r.stderr)
        self.assertIn("[commcheck]", r.stdout)

    def test_pass_all_runs_three_passes_in_one_process(self):
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
        for tag in ("[srclint]", "[effectcheck]", "[commcheck]"):
            self.assertIn(tag, r.stdout)


# ------------------------------------------------------------------ #
# scripts/verify_plans.py sweeps the progress invariant              #
# ------------------------------------------------------------------ #
class TestVerifyPlansSweep(TestCase):
    @pytest.mark.slow
    def test_sweep_passes_and_mutated_dump_names_progress(self):
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
        # hand-mutate one chunked plan's lap order: the sweep fails
        # naming the progress invariant and the violating group
        mutated_lines = []
        hit = False
        for line in dump.stdout.splitlines():
            name, _, payload = line.partition("\t")
            if not hit and payload:
                d = json.loads(payload)
                a2a = [
                    k
                    for k, st in enumerate(d.get("steps") or [])
                    if st.get("kind") == "all_to_all"
                    and st.get("chunk") is not None
                    and st.get("overlap") is not None
                ]
                if len(a2a) >= 2 and d.get("overlap"):
                    i, j = a2a[0], a2a[1]
                    d["steps"][i]["chunk"], d["steps"][j]["chunk"] = (
                        d["steps"][j]["chunk"],
                        d["steps"][i]["chunk"],
                    )
                    line = name + "\t" + json.dumps(d, sort_keys=True)
                    hit = True
            mutated_lines.append(line)
        self.assertTrue(hit, "no chunked overlap plan in the dump to mutate")
        bad = subprocess.run(
            [sys.executable, os.path.join(ROOT, "scripts", "verify_plans.py")],
            input="\n".join(mutated_lines) + "\n",
            capture_output=True,
            text=True,
            env=env,
        )
        self.assertEqual(bad.returncode, 1, bad.stdout + bad.stderr)
        self.assertIn("progress", bad.stdout)
