"""shardlint (``heat_tpu.analysis``): golden-finding tests.

The deliberately-bad fixture programs must trigger the IR rules
(implicit reshard, replicated materialization, gather-fed reduction,
dtype widening, missed donation, host sync); the shipped contracts —
TSQR, hSVD level-0, ring attention, sharded reductions — must come back
with zero error-severity findings; and the source lint must pass the
shipped tree while catching seeded violations. This is the machine
-enforced form of the collective pins in ``tests/test_observability.py``
and the MULTICHIP dryrun.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

import jax
import jax.numpy as jnp

import heat_tpu as ht

import analysis_fixtures as fx

from heat_tpu.analysis import boundaries, findings, srclint

from test_suites.basic_test import TestCase

P = len(jax.devices())
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _big_split0():
    # large enough that the per-device all-to-all shard clears the 1 MiB
    # default threshold on the 5- and 8-device CI meshes, and divisible
    # by both mesh sizes (2^16 * 5 rows) so no pad rows blur the
    # aval-alias match or sit between the gather and its reduce consumer
    return ht.random.randn(327680, 16, split=0)


class TestIRCheckBadFixture(TestCase):
    """The acceptance contract: one deliberately-bad program, >= 3
    distinct rule ids (implicit reshard, missed donation, host sync)."""

    @pytest.mark.skipif(P < 2, reason="needs a real mesh")
    def test_bad_program_reports_the_golden_rules(self):
        rep = ht.analysis.check(fx.bad_program, _big_split0())
        self.assertFalse(rep.ok)
        ids = set(rep.rule_ids)
        self.assertIn("SL101", ids)  # implicit reshard (all-to-all)
        self.assertIn("SL102", ids)  # replicated materialization
        self.assertIn("SL105", ids)  # missed donation
        self.assertIn("SL106", ids)  # host sync (untaken debug arm)
        self.assertGreaterEqual(len(ids), 3)
        # findings carry byte estimates and severities
        gather = rep.by_rule("SL102")[0]
        self.assertEqual(gather.severity, "error")
        self.assertGreaterEqual(gather.nbytes, (1 << 18) * 16 * 4)
        self.assertTrue(all(f.rule in findings.RULES for f in rep))

    @pytest.mark.skipif(P < 2, reason="needs a real mesh")
    def test_gather_fed_reduction(self):
        rep = ht.analysis.check(fx.gather_reduce_program, _big_split0())
        ids = set(rep.rule_ids)
        self.assertIn("SL102", ids)
        self.assertIn("SL103", ids)
        # the sharded twin is the fix — and it is clean
        clean = ht.analysis.check(lambda v: ht.sum(v), _big_split0())
        self.assertEqual(clean.rule_ids, [])

    def test_dtype_widening(self):
        rep = ht.analysis.check(fx.widening_program, ht.random.randn(4096, split=0))
        self.assertEqual(rep.rule_ids, ["SL104"])
        self.assertTrue(rep.ok)  # warning severity: reports, does not gate

    @pytest.mark.skipif(P < 2, reason="needs a real mesh")
    def test_unscaled_int8_narrowing_trips_sl104_at_error(self):
        """ISSUE 7 golden bad-fixture: a hand-rolled UNSCALED
        astype(int8) feeding a psum is the gradient-compression
        accident the narrowing arm exists for — error severity, gates.
        Only wire_codec-stamped converts (heat_tpu.kernels.quant)
        downgrade to info; that pin lives in tests/test_quant.py."""
        rep = ht.analysis.check(fx.int8_wire_program, ht.random.randn(64, 48, split=0))
        sl104 = [f for f in rep.findings if f.rule == "SL104"]
        self.assertTrue(sl104)
        self.assertTrue(any(f.severity == "error" for f in sl104))
        self.assertIn("kernels.quant", sl104[0].message)
        self.assertFalse(rep.ok)

    @pytest.mark.skipif(P < 2, reason="needs a real mesh")
    def test_int8_narrowing_inside_nested_jit_still_trips(self):
        """The backward walk crosses call boundaries: an unscaled
        astype(int8) hiding inside a nested jit wrapper whose OUTPUT
        feeds the collective must trip the same error — the producer
        map steps from the pjit eqn onto its sub-jaxpr's outvars."""
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import PartitionSpec as PS

        from jax import shard_map

        enc = jax.jit(lambda g: g.astype(jnp.int8))  # shardlint: ignore[SL202] -- fixture

        x = ht.random.randn(64, 48, split=0)
        comm = x.comm

        def nested(v):
            phys = v._phys

            def body(xl):
                return lax.psum(enc(xl), comm.axis_name).astype(jnp.float32)

            spec = PS(*(comm.axis_name if k == 0 else None for k in range(phys.ndim)))
            return shard_map(
                body, mesh=comm.mesh, in_specs=(spec,),
                out_specs=PS(*(None,) * phys.ndim), check_vma=False,
            )(phys)

        rep = ht.analysis.check(nested, x, scan_source=False)
        sl104 = [f for f in rep.findings if f.rule == "SL104"]
        self.assertTrue(any(f.severity == "error" for f in sl104))

        # the inverse guard: a SIBLING int8 output of the same jit
        # wrapper, NOT on the collective's dataflow path, must not trip
        # (call outvars map 1:1 onto sub-jaxpr outvars — only the
        # index-matched one continues the walk)
        two = jax.jit(  # shardlint: ignore[SL202] -- fixture
            lambda g: (g.astype(jnp.int8), g * 2.0)
        )

        def sibling(v):
            phys = v._phys

            def body(xl):
                q, f = two(xl)
                return lax.psum(f, comm.axis_name) + q.astype(jnp.float32).sum()

            spec = PS(*(comm.axis_name if k == 0 else None for k in range(phys.ndim)))
            return shard_map(
                body, mesh=comm.mesh, in_specs=(spec,),
                out_specs=PS(*(None,) * phys.ndim), check_vma=False,
            )(phys)

        clean = ht.analysis.check(sibling, x, scan_source=False)
        self.assertFalse(
            any(f.rule == "SL104" and f.severity == "error" for f in clean.findings)
        )

    @pytest.mark.skipif(P < 2, reason="needs a real mesh")
    def test_donation_bookkeeping_suppresses_sl105(self):
        x = _big_split0()
        undonated = ht.analysis.check(ht.jit(fx.donated_program), x)
        self.assertIn("SL105", undonated.rule_ids)
        donated = ht.analysis.check(ht.jit(fx.donated_program, donate_argnums=0), x)
        self.assertNotIn("SL105", donated.rule_ids)

    @pytest.mark.skipif(P < 2, reason="needs a real mesh")
    def test_unstamped_ppermute_loop_trips_sl101(self):
        """ISSUE 6 golden bad-fixture: a hand-rolled ppermute relayout
        loop with no plan stamp still trips SL101 at full severity —
        the planner's own pipelined ring programs downgrade to info
        (tests/test_overlap.py), the UNstamped chain must not."""
        rep = ht.analysis.check(fx.ppermute_ring_program, _big_split0())
        hops = [f for f in rep.by_rule("SL101") if f.op == "collective-permute"]
        self.assertTrue(hops)
        for f in hops:
            self.assertIn(f.severity, ("warning", "error"))
            self.assertGreaterEqual(f.nbytes, 1 << 20)

    @pytest.mark.skipif(P < 2, reason="needs a real mesh")
    def test_library_ring_schedules_report_as_info(self):
        """The library's OWN documented ring schedules (the distributed
        sort networks' block rotations here) are not hand-rolled
        accidents: their collective-permute hops report at info, keyed
        on the instruction's source_file (boundaries.RING_SCHEDULE_MODULES)."""
        x = ht.random.randn(P * (1 << 20), split=0)  # MB-class hops
        rep = ht.analysis.check(lambda v: ht.sort(v)[0], x)
        hops = [f for f in rep.findings if f.op == "collective-permute"]
        self.assertTrue(hops)
        for f in hops:
            self.assertEqual(f.severity, "info")
            self.assertIn("ring schedule", f.message)

    def test_trace_abort_reports_host_sync_not_raise(self):
        def syncing(v):
            s = ht.sum(v)
            return v * float(s)  # concretizes under trace

        rep = ht.analysis.check(syncing, ht.arange(64, split=0).astype(ht.float32))
        self.assertIn("SL106", rep.rule_ids)
        self.assertFalse(rep.ok)

    def test_serving_sync_handler_trips_sl106(self):
        """ISSUE 9 golden bad fixture: a BLOCKING host sync inside a
        serving request handler — the dispatch→result hot path budget
        is zero undeclared device_get, and the check aborts at the
        concretizing read with SL106 at error severity."""
        rep = ht.analysis.check(fx.serving_sync_handler, ht.random.randn(32, 8, split=0))
        self.assertFalse(rep.ok)
        sl106 = rep.by_rule("SL106")
        self.assertTrue(sl106)
        self.assertTrue(any(f.severity == "error" for f in sl106))

    def test_report_dict_shape(self):
        rep = ht.analysis.check(fx.widening_program, ht.random.randn(256, split=0))
        d = rep.as_dict()
        for key in ("ok", "rule_ids", "findings", "context"):
            self.assertIn(key, d)
        self.assertEqual(d["findings"][0]["rule"], "SL104")
        json.dumps(d)  # JSON-ready
        self.assertTrue(repr(rep).startswith("AnalysisReport("))


class TestIRCheckCleanContracts(TestCase):
    """TSQR / hSVD level-0 / ring attention — the pinned collective
    contracts — must report ZERO error-severity findings: the analyzer
    turns the hand-written pins into a machine-enforced contract."""

    @pytest.mark.skipif(P < 2, reason="needs a real mesh")
    def test_tsqr_clean(self):
        a = ht.random.randn(16 * P, 2 * P, split=0)
        rep = ht.analysis.check(lambda x: ht.linalg.qr(x), a)
        self.assertEqual(rep.errors, [])

    @pytest.mark.skipif(P < 2, reason="needs a real mesh")
    def test_hsvd_level0_clean(self):
        from heat_tpu.core.linalg.svdtools import _local_svd_fn

        comm = ht.get_comm()
        phys = comm.shard(jnp.ones((16, 4 * P), jnp.float32), 1)
        fn = _local_svd_fn(comm.mesh, comm.axis_name, 16, phys.shape[1] // P, 3, "float32", 5)
        rep = ht.analysis.check(fn, phys)  # .lower fast path
        self.assertEqual(rep.errors, [])
        self.assertEqual(rep.context["collective_counts"], {})

    @pytest.mark.skipif(P < 2, reason="needs a real mesh")
    def test_ring_attention_clean(self):
        S, D = 8 * P, 8
        q = ht.random.randn(2, S, D, split=1)
        rep = ht.analysis.check(
            lambda a, b, c: ht.nn.ring_attention(a, b, c, causal=True), q, q, q
        )
        self.assertEqual(rep.errors, [])

    @pytest.mark.skipif(P < 2, reason="needs a real mesh")
    def test_training_step_clean(self):
        import __graft_entry__ as graft

        fn, args = graft.training_step_program(P)
        rep = ht.analysis.check(fn, *args)
        self.assertEqual(rep.errors, [])


class TestFactorizationLint(TestCase):
    """ISSUE 19: the gather-then-``jnp.linalg.inv`` anti-pattern (the
    path ``ht.linalg.inv`` ran before the blocked ring-LU) trips
    SL102/SL106 as a golden bad fixture, and the blocked ``solve`` that
    replaced it is pinned memcheck-clean and SL-clean."""

    @pytest.mark.skipif(P < 2, reason="needs a real mesh")
    def test_gather_inv_fixture_trips_sl102_sl106(self):
        x = ht.random.randn(2560, 2560, split=0)
        rep = ht.analysis.check(fx.gather_inv_program, x)
        ids = set(rep.rule_ids)
        self.assertIn("SL102", ids)  # whole-operand replicated gather
        self.assertIn("SL106", ids)  # host read in the debug arm
        gather = rep.by_rule("SL102")[0]
        self.assertEqual(gather.severity, "error")
        self.assertGreaterEqual(gather.nbytes, 2560 * 2560 * 4)

    @pytest.mark.skipif(P < 2, reason="needs a real mesh")
    def test_blocked_solve_sl_clean(self):
        n = 128 * P
        a = ht.random.randn(n, n, split=0) * 0.01 + ht.eye((n, n), split=0) * 4
        b = ht.random.randn(n, 16, split=0)
        rep = ht.analysis.check(
            lambda u, v: ht.linalg.solve(u, v, assume_a="pos"), a, b
        )
        self.assertEqual(rep.errors, [])
        # the plan-stamped panel rings report at info only
        hops = [f for f in rep.findings if f.op == "collective-permute"]
        for f in hops:
            self.assertEqual(f.severity, "info")

    @pytest.mark.skipif(P < 2, reason="needs a real mesh")
    def test_blocked_solve_memcheck_clean(self):
        n = 128 * P
        a = ht.random.randn(n, n, split=0) * 0.01 + ht.eye((n, n), split=0) * 4
        b = ht.random.randn(n, 16, split=0)
        rep = ht.analysis.memcheck(
            lambda u, v: ht.linalg.solve(u, v, assume_a="pos"), a, b
        )
        self.assertEqual(rep.errors, [])
        self.assertGreater(rep.context["static_peak_bytes"], 0)


class TestMemCheckGoldenFixtures(TestCase):
    """ISSUE 10 (pass 3, memcheck): each SL3xx golden bad fixture trips
    at its pinned severity, and the shipped contracts — TSQR, hSVD
    level-0, the serving endpoint program, the training step — come
    back clean under the default budget."""

    @pytest.mark.skipif(P < 2, reason="needs a real mesh")
    def test_over_budget_program_trips_sl301_under_forced_budget(self):
        x = ht.random.randn(1 << 16, 16, split=0)  # 4 MiB operand
        rep = ht.analysis.memcheck(fx.over_budget_program, x, hbm_bytes=1 << 20)
        self.assertFalse(rep.ok)
        sl301 = rep.by_rule("SL301")
        self.assertTrue(sl301)
        self.assertEqual(sl301[0].severity, "error")
        self.assertGreater(sl301[0].nbytes, 1 << 20)
        # ... and the same program under the default 16 GiB budget is clean
        clean = ht.analysis.memcheck(fx.over_budget_program, x)
        self.assertNotIn("SL301", clean.rule_ids)
        self.assertEqual(
            clean.context["hbm_budget_bytes"],
            ht.analysis.hbm_budget_bytes(),
        )

    @pytest.mark.skipif(P < 2, reason="needs a real mesh")
    def test_dropped_donation_trips_sl302(self):
        """Donation declared via ht.jit bookkeeping but unusable (no
        output aliases the donated aval) — the executable drops it, and
        only the input_output_aliases check can see that. The honored
        twin (full-size output) stays clean: the alias map carries the
        donated parameter."""
        x = ht.random.randn(64, 4096, split=0)
        dropped = ht.analysis.memcheck(
            ht.jit(fx.dropped_donation_program, donate_argnums=0), x
        )
        self.assertFalse(dropped.ok)
        sl302 = dropped.by_rule("SL302")
        self.assertTrue(sl302)
        self.assertEqual(sl302[0].severity, "error")
        self.assertIn("input_output_aliases", sl302[0].message)
        honored = ht.analysis.memcheck(ht.jit(fx.donated_program, donate_argnums=0), x)
        self.assertNotIn("SL302", honored.rule_ids)
        self.assertIn(0, honored.context.get("aliased_params", []))

    @pytest.mark.skipif(P < 2, reason="needs a real mesh")
    def test_explicit_donation_on_jitted_fn_is_checked(self):
        """The already-jitted (.lower fast path) form honors an EXPLICIT
        donate_argnums: the donated compile is what gets alias-checked,
        so a dropped donation reports SL302 there too — not just on the
        ht.jit wrap path."""
        import jax as _jax

        dropped = _jax.jit(lambda a: a[:16] * 1.0)  # shardlint: ignore[SL202] -- fixture
        x = jnp.ones((64, 4096), jnp.float32)
        rep = ht.analysis.memcheck(dropped, x, donate_argnums=(0,))
        self.assertIn("SL302", rep.rule_ids)
        honored = _jax.jit(lambda a: a * 1.0)  # shardlint: ignore[SL202] -- fixture
        clean = ht.analysis.memcheck(honored, x, donate_argnums=(0,))
        self.assertNotIn("SL302", clean.rule_ids)
        self.assertIn(0, clean.context.get("aliased_params", []))

    @pytest.mark.skipif(P < 2, reason="needs a real mesh")
    def test_shard_map_passthrough_keeps_caller_replication_fact(self):
        """A shard_map whose output PASSES an input through must not
        rewrite the caller value's replication fact in place (the body
        invar aliases the caller's buffer record): a replicated value
        flowing through a sharded-out passthrough stays SL303-eligible
        for ITS OWN live range."""
        import importlib

        import jax as _jax
        from jax.sharding import PartitionSpec as PS

        from jax import shard_map

        mc = importlib.import_module("heat_tpu.analysis.memcheck")
        comm = ht.get_comm()
        f = lambda a: shard_map(
            lambda b: b, mesh=comm.mesh, in_specs=(PS(None, None),),
            out_specs=PS(comm.axis_name, None), check_vma=False,
        )(a)
        closed = _jax.make_jaxpr(f)(jnp.ones((8, 16), jnp.float32))
        interp = mc._Interp(comm.size)
        in_fact = mc._Fact(8 * 16 * 4, True)
        interp.run(closed.jaxpr, [in_fact], local_avals=False)
        self.assertTrue(in_fact.replicated)

    @pytest.mark.skipif(P < 2, reason="needs a real mesh")
    def test_replicated_liverange_trips_sl303(self):
        x = ht.random.randn(1 << 18, 8, split=0)  # 8 MiB replicated copy
        rep = ht.analysis.memcheck(fx.replicated_liverange_program, x)
        sl303 = rep.by_rule("SL303")
        self.assertTrue(sl303)
        self.assertEqual(sl303[0].severity, "warning")
        self.assertTrue(rep.ok)  # warning severity: reports, does not gate
        self.assertGreaterEqual(sl303[0].nbytes, 1 << 20)
        # the sharded twin (no replicated materialization) is clean
        clean = ht.analysis.memcheck(lambda v: v.resplit(1).resplit(0), x)
        self.assertNotIn("SL303", clean.rule_ids)

    @pytest.mark.skipif(P < 2, reason="needs a real mesh")
    def test_shipped_contracts_memcheck_clean(self):
        a = ht.random.randn(16 * P, 2 * P, split=0)
        self.assertEqual(ht.analysis.memcheck(lambda v: ht.linalg.qr(v), a).rule_ids, [])
        from heat_tpu.core.linalg.svdtools import _local_svd_fn

        comm = ht.get_comm()
        phys = comm.shard(jnp.ones((16, 4 * P), jnp.float32), 1)
        fn = _local_svd_fn(comm.mesh, comm.axis_name, 16, phys.shape[1] // P, 3, "float32", 5)
        self.assertEqual(ht.analysis.memcheck(fn, phys).rule_ids, [])

    @pytest.mark.skipif(P < 2, reason="needs a real mesh")
    def test_training_step_memcheck_clean(self):
        import __graft_entry__ as graft

        fn, args = graft.training_step_program(P)
        rep = ht.analysis.memcheck(fn, *args)
        self.assertEqual(rep.rule_ids, [])
        self.assertGreater(rep.context["static_peak_bytes"], 0)

    def test_serving_endpoint_program_memcheck_clean(self):
        from heat_tpu.cluster import _kcluster

        centers = jnp.linspace(0.0, 1.0, 5 * 12, dtype=jnp.float32).reshape(5, 12)
        spec = _kcluster.serving_spec("euclidean", centers)
        prog = spec["build"]()
        batch = jnp.zeros((8, 12), jnp.float32)
        rep = ht.analysis.memcheck(prog, batch, *spec["args"])
        self.assertEqual(rep.rule_ids, [])

    def test_sl3xx_rules_are_cataloged(self):
        for rule in ("SL301", "SL302", "SL303"):
            self.assertIn(rule, findings.RULES)


class TestSrcLint(TestCase):
    def test_shipped_tree_is_clean(self):
        rep = srclint.lint_paths([os.path.join(ROOT, "heat_tpu")], root=ROOT)
        self.assertEqual([str(f) for f in rep.errors], [])

    def test_seeded_bare_jit_fails(self):
        src = textwrap.dedent(
            """
            import jax

            def public_op(x):
                return jax.jit(lambda v: v * 2)(x)
            """
        )
        found = srclint.lint_source(src, "core/somemodule.py")
        self.assertEqual([f.rule for f in found], ["SL202"])
        self.assertEqual(found[0].severity, "error")

    def test_seeded_undeclared_device_get_fails(self):
        src = textwrap.dedent(
            """
            import jax

            def mean_to_host(x):
                return float(jax.device_get(x).mean())
            """
        )
        found = srclint.lint_source(src, "core/somemodule.py")
        self.assertEqual([f.rule for f in found], ["SL201"])

    def test_new_sync_in_core_statistics_must_be_declared(self):
        # the percentile-q declaration covers percentile ONLY: the same
        # call in any other function of the same file still gates
        src = "import jax\ndef median_fast(x):\n    return jax.device_get(x)\n"
        found = srclint.lint_source(src, "heat_tpu/core/statistics.py")
        self.assertIn("SL201", [f.rule for f in found])
        declared = "import jax\ndef percentile(x):\n    return jax.device_get(x)\n"
        found = srclint.lint_source(declared, "heat_tpu/core/statistics.py")
        self.assertNotIn("SL201", [f.rule for f in found])

    def test_pragma_suppresses_with_reason(self):
        src = (
            "import jax\n"
            "def f(x):\n"
            "    return jax.device_get(x)  # shardlint: ignore[SL201] -- test\n"
        )
        self.assertEqual(srclint.lint_source(src, "core/m.py"), [])

    def test_from_jax_import_jit_flagged(self):
        found = srclint.lint_source("from jax import jit\n", "core/m.py")
        self.assertEqual([f.rule for f in found], ["SL202"])

    def test_private_builder_jit_allowed(self):
        src = "import jax\ndef _my_program(shape):\n    return jax.jit(lambda v: v)\n"
        self.assertEqual(srclint.lint_source(src, "core/m.py"), [])

    def test_unsanitized_public_op_warns(self):
        src = "def frobnicate(x):\n    return x + 1\n"
        found = srclint.lint_source(src, "heat_tpu/core/arithmetics.py")
        self.assertEqual([f.rule for f in found], ["SL203"])
        self.assertEqual(found[0].severity, "warning")
        routed = "from .sanitation import sanitize_in\ndef frobnicate(x):\n    sanitize_in(x)\n    return x + 1\n"
        self.assertEqual(srclint.lint_source(routed, "heat_tpu/core/arithmetics.py"), [])


class TestLintCLI(TestCase):
    """scripts/lint.py: exit 0 on the shipped tree, nonzero on a seeded
    violation — the exact contract ci.sh leans on."""

    def test_cli_exit_codes(self):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        ok = subprocess.run(
            [sys.executable, os.path.join(ROOT, "scripts", "lint.py"),
             os.path.join(ROOT, "heat_tpu")],
            capture_output=True, text=True, env=env,
        )
        self.assertEqual(ok.returncode, 0, ok.stdout + ok.stderr)
        import tempfile

        with tempfile.TemporaryDirectory() as td:
            bad = os.path.join(td, "seeded.py")
            with open(bad, "w") as f:
                f.write("import jax\ndef op(x):\n    return jax.jit(lambda v: v)(jax.device_get(x))\n")
            r = subprocess.run(
                [sys.executable, os.path.join(ROOT, "scripts", "lint.py"), bad],
                capture_output=True, text=True, env=env,
            )
            self.assertEqual(r.returncode, 1, r.stdout + r.stderr)
            self.assertIn("SL201", r.stdout)
            self.assertIn("SL202", r.stdout)

    # slow: ~17 s of CLI subprocesses; test_cli_exit_codes keeps the exit-code contract in tier-1
    @pytest.mark.slow
    def test_sarif_format_exit_codes(self):
        """ISSUE 10 satellite: `--format sarif` emits one SARIF 2.1.0
        document with one run per pass and rule ids = SLxxx, while the
        exit-code contract is unchanged — 0 on the clean tree, 1 on a
        seeded violation (the gate is the findings, not the format)."""
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        ok = subprocess.run(
            [sys.executable, os.path.join(ROOT, "scripts", "lint.py"),
             "--format", "sarif", os.path.join(ROOT, "heat_tpu")],
            capture_output=True, text=True, env=env,
        )
        self.assertEqual(ok.returncode, 0, ok.stdout + ok.stderr)
        doc = json.loads(ok.stdout)
        self.assertEqual(doc["version"], "2.1.0")
        # one run per pass — the default `--pass all` is the single CI
        # lint entry (ISSUE 14; ISSUE 17 adds pass 6): passes 2, 4, 5
        # AND 6 in one process, one SARIF document with one run per pass
        self.assertEqual(
            [run["tool"]["driver"]["name"] for run in doc["runs"]],
            [
                "shardlint/srclint",
                "shardlint/effectcheck",
                "shardlint/commcheck",
                "shardlint/numcheck",
            ],
        )
        import tempfile

        with tempfile.TemporaryDirectory() as td:
            bad = os.path.join(td, "seeded.py")
            with open(bad, "w") as f:
                f.write("import jax\ndef op(x):\n    return jax.jit(lambda v: v)(jax.device_get(x))\n")
            r = subprocess.run(
                [sys.executable, os.path.join(ROOT, "scripts", "lint.py"),
                 "--format", "sarif", bad],
                capture_output=True, text=True, env=env,
            )
            self.assertEqual(r.returncode, 1, r.stdout + r.stderr)
            doc = json.loads(r.stdout)
            results = doc["runs"][0]["results"]
            rules = {res["ruleId"] for res in results}
            self.assertIn("SL201", rules)
            self.assertIn("SL202", rules)
            self.assertTrue(all(res["level"] in ("error", "warning", "note") for res in results))
            # findings anchor on file:line for CI annotation
            loc = results[0]["locations"][0]["physicalLocation"]
            self.assertTrue(loc["artifactLocation"]["uri"].endswith("seeded.py"))
            self.assertGreaterEqual(loc["region"]["startLine"], 1)
            # declared rules carry the catalog text
            driver = doc["runs"][0]["tool"]["driver"]
            self.assertTrue(
                all(rule["id"] in findings.RULES for rule in driver["rules"])
            )


class TestBoundaries(TestCase):
    def test_percentile_is_the_only_core_whitelisted_sync(self):
        """The named host-boundary whitelist holds exactly ONE core/
        entry: the percentile q round-trip. Any new sync in a core
        compute path must add a named declaration here — this test is
        the tripwire that makes the diff visible."""
        core_entries = [
            name
            for name, (path, _qn, _reason) in boundaries.HOST_BOUNDARIES.items()
            if path.startswith("core/")
        ]
        self.assertEqual(core_entries, ["percentile-q"])
        # and the declaration matches the real site
        path, qualname, reason = boundaries.HOST_BOUNDARIES["percentile-q"]
        self.assertEqual((path, qualname), ("core/statistics.py", "percentile"))
        self.assertTrue(reason)

    def test_is_declared_sync_categories(self):
        ok, cat = boundaries.is_declared_sync("heat_tpu/core/statistics.py", "percentile")
        self.assertEqual((ok, cat), (True, "percentile-q"))
        ok, cat = boundaries.is_declared_sync("heat_tpu/core/io.py", "anything")
        self.assertTrue(ok)
        self.assertTrue(cat.startswith("host-module:"))
        ok, cat = boundaries.is_declared_sync(
            "heat_tpu/core/linalg/svdtools.py", "_hsvd_impl.inner_helper"
        )
        self.assertTrue(ok)  # a boundary owns its nested helpers
        self.assertTrue(cat.startswith("data-dependent:"))
        ok, _ = boundaries.is_declared_sync("heat_tpu/core/statistics.py", "median")
        self.assertFalse(ok)

    def test_every_declaration_points_at_real_code(self):
        """Declarations must not go stale: each declared (file, function)
        still exists in the tree."""
        import ast

        decls = (
            [(p, q) for (p, q) in boundaries.HOST_FUNCS]
            + [(p, q) for (p, q) in boundaries.DATA_DEPENDENT_BOUNDARIES]
            + [(p, q) for (p, q, _r) in boundaries.HOST_BOUNDARIES.values()]
        )
        for path, qualname in decls:
            full = os.path.join(ROOT, "heat_tpu", path)
            self.assertTrue(os.path.exists(full), f"stale declaration path: {path}")
            tree = ast.parse(open(full).read())
            names = set()

            def collect(node, stack):
                for child in ast.iter_child_nodes(node):
                    if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
                        names.add(".".join(stack + [child.name]))
                        collect(child, stack + [child.name])
                    else:
                        collect(child, stack)

            collect(tree, [])
            self.assertIn(qualname, names, f"stale declaration: {path}:{qualname}")
        for mod in boundaries.HOST_MODULES:
            self.assertTrue(os.path.exists(os.path.join(ROOT, "heat_tpu", mod)))


class TestSparseEngineFixtures(TestCase):
    """ISSUE 18: the sparse-engine golden fixtures — the gather-per-row
    SpMV anti-pattern trips SL101/SL103, and the engine's kernel SpMM
    and PageRank step programs pin LINT-CLEAN across ircheck, memcheck
    and numcheck."""

    def _sparse_split0(self, n=327680):
        import numpy as np
        import scipy.sparse as sp

        rng = np.random.default_rng(0x18)
        m, nnz = 4096, 400000
        rows = rng.integers(0, m, nnz)
        cols = rng.integers(0, n, nnz)
        csr = sp.csr_matrix(
            (rng.random(nnz).astype(np.float32), (rows, cols)), shape=(m, n)
        )
        csr.sum_duplicates()
        return csr

    @pytest.mark.skipif(P < 2, reason="needs a real mesh")
    def test_gather_per_row_spmv_trips_sl101_sl103(self):
        # narrow dense operand: the nnz gathers must dominate the
        # largest input for SL102 to reach error severity (gating)
        csr = self._sparse_split0(n=32768)
        A = ht.sparse.sparse_csr_matrix(csr, split=0)
        x = ht.random.randn(csr.shape[1], 16, split=0)
        comm, m = A.comm, A.shape[0]
        # components passed as TRACED args — closure capture would
        # constant-fold them replicated and hide the gathers
        rep = ht.analysis.check(
            lambda r, i, d, v: fx.gather_per_row_spmv_program(comm, m, r, i, d, v),
            A._rows, *A._phys_components[1:], x._phys,
            min_bytes=1 << 17,
        )
        ids = set(rep.rule_ids)
        self.assertIn("SL101", ids)  # bare constraint -> implicit all-to-all
        self.assertIn("SL103", ids)  # gathered values feed a reduction
        self.assertIn("SL102", ids)  # the gather itself materializes
        self.assertFalse(rep.ok)

    @pytest.mark.skipif(P < 2, reason="needs a real mesh")
    def test_kernel_spmm_path_is_lint_clean(self):
        """The engine's distributed SpMM local program: no implicit
        reshards (the dense operand arrives replicated BY PLAN), no
        collectives at all, honest memory facts, f32-accumulating."""
        import numpy as np

        from heat_tpu.kernels import spmm as kspmm

        csr = self._sparse_split0()
        A = ht.sparse.sparse_dbcsr_matrix(csr, split=0)
        bdata, bcol, brow, bmask = A._phys_components
        x = np.ones((csr.shape[1], 4), np.float32)
        prog = kspmm.spmm_bcsr_program(
            A.comm, A.shape[0], A.nb, A.slab_bricks, 0, 2, "float32", "xla"
        )
        rep = ht.analysis.check(prog, bdata, bcol, brow, bmask, x)
        self.assertEqual([f for f in rep.findings if f.severity == "error"], [])
        self.assertEqual(
            [f for f in rep.findings if f.rule in ("SL101", "SL102", "SL103")],
            [],
        )
        mem = ht.analysis.memcheck(prog, bdata, bcol, brow, bmask, x)
        self.assertTrue(mem.ok)
        num = ht.analysis.numcheck(prog, bdata, bcol, brow, bmask, x)
        self.assertTrue(num.ok)

    @pytest.mark.skipif(P < 2, reason="needs a real mesh")
    def test_pagerank_step_program_is_lint_clean(self):
        import numpy as np

        csr = self._sparse_split0().T.tocsr()  # (n, m): square not needed
        csr = csr[: csr.shape[1], :].tocsr()
        A = ht.sparse.sparse_dbcsr_matrix(csr, split=0)
        bdata, bcol, brow, bmask = A._phys_components
        step = fx.make_pagerank_step(
            A.comm, A.shape[0], A.nb, A.slab_bricks, alpha=0.85
        )
        r = np.full(csr.shape[1], 1.0 / csr.shape[1], np.float32)
        tel = np.float32(0.15 / csr.shape[1])
        rep = ht.analysis.check(step, bdata, bcol, brow, bmask, r, tel)
        self.assertEqual([f for f in rep.findings if f.severity == "error"], [])
        mem = ht.analysis.memcheck(step, bdata, bcol, brow, bmask, r, tel)
        self.assertTrue(mem.ok)
        num = ht.analysis.numcheck(step, bdata, bcol, brow, bmask, r, tel)
        self.assertTrue(num.ok)


if __name__ == "__main__":
    import unittest

    unittest.main()
