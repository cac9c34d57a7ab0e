"""Pass 4 (ISSUE 12): the gate registry + gatecheck/racecheck analyzer.

Contracts pinned here:

- **Registry round-trip** — every ``HEAT_TPU_*`` gate read anywhere in
  ``heat_tpu/`` is declared in ``core/gates.py`` (a raw ``os.environ``
  grep over the tree finds ZERO gate reads outside the registry — the
  same verdict rule SL403 reaches), declarations are well-formed, and
  no declaration is dead.
- **Cache-key byte identity** — with all gates at defaults, the
  registry-derived keys reproduce the PR 11 artifacts bit-for-bit: the
  golden plan_ids (pinned hex), the empty AOT gate fingerprint, and
  key-for-key equality between ``gates.aot_fingerprint()`` and the PR 9
  hand-rolled prefix scan it replaced, at every gate combination tried.
- **AOT roster invalidation** — registering a NEW program-affecting
  gate invalidates stored envelopes as ``version_mismatch`` (never a
  stale hit).
- **Golden bad fixtures** fire each SL401–SL405 rule; the shipped
  dispatcher/aot_cache/telemetry/executor/staging modules and the
  golden plan forms (flat/2x4/2x8, quant on+off, staged) come back
  SL4xx-clean.
- **Seeded-bug mutations** (the ci.sh leg): removing one gate from a
  program-cache key trips SL402; removing one lock acquisition from a
  guarded dispatcher path trips SL404 — each at error severity, with
  the invariant named.
- **Threading stress** — the SL404-clean dispatcher/telemetry paths
  stay exact-total under concurrent clients.
"""

import os
import re
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import heat_tpu as ht

import analysis_fixtures as fx

from heat_tpu.analysis import effectcheck, findings
from heat_tpu.core import gates
from heat_tpu.redistribution import planner, staging
from heat_tpu.serving import aot_cache
from heat_tpu.serving.dispatcher import Dispatcher, Endpoint

from test_plan_ids import PLAN_IDS
from test_suites.basic_test import TestCase, env_pin

P = len(jax.devices())
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HT = os.path.join(ROOT, "heat_tpu")


def _read(rel):
    with open(os.path.join(ROOT, rel), encoding="utf-8") as f:
        return f.read()


def _tree_sources():
    for root, dirs, files in os.walk(HT):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in sorted(files):
            if f.endswith(".py"):
                fp = os.path.join(root, f)
                rel = os.path.relpath(fp, ROOT).replace(os.sep, "/")
                with open(fp, encoding="utf-8") as fh:
                    yield rel, fh.read()


# ------------------------------------------------------------------ #
# registry round-trip                                                #
# ------------------------------------------------------------------ #
class TestGateRegistry(TestCase):
    def test_every_gate_token_in_tree_is_declared(self):
        """Every concrete HEAT_TPU_* token in the library source is a
        declared gate (or a proper prefix of one, e.g. the old
        exclusion-list spellings in comments)."""
        token = re.compile(r"HEAT_TPU_[A-Z0-9_]*[A-Z0-9]")
        declared = set(gates.GATES)
        undeclared = {}
        for rel, src in _tree_sources():
            for name in set(token.findall(src)):
                ok = name in declared or any(
                    g.startswith(name) for g in declared
                )
                if not ok:
                    undeclared.setdefault(name, rel)
        self.assertEqual(
            undeclared, {},
            f"HEAT_TPU_* names read/mentioned but not declared in "
            f"core/gates.py: {undeclared}",
        )

    def test_no_dead_declarations(self):
        """Every declared gate is actually read somewhere in the tree."""
        blob = "\n".join(src for _, src in _tree_sources())
        for name in gates.GATES:
            self.assertIn(name, blob, f"{name} declared but never referenced")

    def test_raw_read_grep_matches_sl403_verdict(self):
        """The satellite's cross-check: a raw grep for ``os.environ``
        over ``heat_tpu/`` finds gate reads ONLY in core/gates.py, and
        the SL403 sweep reaches the same verdict (zero findings)."""
        import ast

        def uses_environ(src):
            for node in ast.walk(ast.parse(src)):
                if (
                    isinstance(node, ast.Attribute)
                    and node.attr in ("environ", "getenv")
                    and isinstance(node.value, ast.Name)
                    and node.value.id == "os"
                ):
                    return True
            return False

        raw = [
            rel
            for rel, src in _tree_sources()
            if not rel.endswith("core/gates.py") and uses_environ(src)
        ]
        self.assertEqual(raw, [], f"raw os.environ access outside the registry: {raw}")
        report = effectcheck.lint_paths([HT], root=ROOT)
        self.assertEqual([f for f in report if f.rule == "SL403"], [])

    def test_get_rejects_undeclared_names(self):
        with self.assertRaises(KeyError):
            gates.get("HEAT_TPU_NOT_A_GATE")
        with self.assertRaises(KeyError):
            gates.is_set("HEAT_TPU_NOT_A_GATE")

    def test_get_mirrors_environ_semantics(self):
        with env_pin("HEAT_TPU_REDIST_OVERLAP", None):
            self.assertIsNone(gates.get("HEAT_TPU_REDIST_OVERLAP"))
            self.assertEqual(gates.get("HEAT_TPU_REDIST_OVERLAP", "auto"), "auto")
            self.assertFalse(gates.is_set("HEAT_TPU_REDIST_OVERLAP"))
        with env_pin("HEAT_TPU_REDIST_OVERLAP", "0"):
            self.assertEqual(gates.get("HEAT_TPU_REDIST_OVERLAP", "auto"), "0")
            self.assertTrue(gates.is_set("HEAT_TPU_REDIST_OVERLAP"))

    def test_scope_and_roster_derivations(self):
        affecting = {s.name for s in gates.affecting_programs()}
        # the serving/telemetry/tracing switches change no program
        # bytes, and neither does the checkpoint store path (ISSUE 13)
        # or the numcheck analyzer threshold (ISSUE 17 — read-only
        # report tuning); the resilience runtime switch IS roster
        # material (its registration version-bumps pre-resilience AOT
        # envelopes)
        self.assertNotIn("HEAT_TPU_SERVING_AOT", affecting)
        self.assertNotIn("HEAT_TPU_SERVING_CACHE", affecting)
        self.assertNotIn("HEAT_TPU_TELEMETRY", affecting)
        self.assertNotIn("HEAT_TPU_CKPT_DIR", affecting)
        self.assertNotIn("HEAT_TPU_TRACE", affecting)
        self.assertNotIn("HEAT_TPU_NUMCHECK_ACC_DIM", affecting)
        self.assertIn("HEAT_TPU_RESILIENCE", affecting)
        self.assertEqual(len(affecting), len(gates.GATES) - 6)
        self.assertEqual(
            gates.program_gate_roster(), ",".join(sorted(affecting))
        )
        # plan-scope gates are exactly the components of the planner key
        plan_scope = {s.name for s in gates.scope_gates("plan")}
        self.assertEqual(
            plan_scope,
            {
                "HEAT_TPU_REDIST_BUDGET_MB", "HEAT_TPU_WIRE_QUANT",
                "HEAT_TPU_TOPOLOGY", "HEAT_TPU_OOC", "HEAT_TPU_OOC_SLAB_MB",
                "HEAT_TPU_HBM_BYTES",
            },
        )
        with self.assertRaises(ValueError):
            gates.scope_gates("nonsense")

    def test_executor_program_keys_derive_from_registry(self):
        """The executor's cached-builder signatures carry one declared
        ``key_params`` name for every program-scope gate — the
        'cache keys derive from the registry' pin, enforced in depth by
        rule SL402."""
        import inspect

        from heat_tpu.redistribution import executor

        for builder in (
            executor._move_program, executor._pivot_program,
            executor._packed_pivot_program,
        ):
            params = set(inspect.signature(builder.__wrapped__).parameters)
            for spec in gates.scope_gates("program"):
                if spec.name in ("HEAT_TPU_SORT_KERNEL", "HEAT_TPU_RELAYOUT_KERNEL",
                                 "HEAT_TPU_SPMM_KERNEL",
                                 "HEAT_TPU_REDIST_PLANNER"):
                    continue  # keyed one level down (impl strings / route)
                self.assertTrue(
                    params & set(spec.key_params),
                    f"{builder.__wrapped__.__name__} carries no key param "
                    f"for {spec.name} (declared: {spec.key_params})",
                )
        packed = set(
            inspect.signature(executor._packed_pivot_program.__wrapped__).parameters
        )
        self.assertTrue(
            packed & set(gates.GATES["HEAT_TPU_RELAYOUT_KERNEL"].key_params)
        )


# ------------------------------------------------------------------ #
# cache-key byte identity (the PR 11 artifacts)                      #
# ------------------------------------------------------------------ #
#: the golden specs' plan_ids (all gates at defaults), as PR 11 HEAD
#: made them: rows of the one table, ``tests/test_plan_ids.py``
_PR11_PLAN_IDS = {name: PLAN_IDS[name] for name, _ in planner.golden_specs()}


def _pr9_hand_fingerprint():
    """The PR 9 hand-rolled prefix scan the registry derivation
    replaced — kept here as the oracle the derivation must match."""
    return tuple(
        sorted(
            (k, v)
            for k, v in os.environ.items()
            if k.startswith("HEAT_TPU_")
            and not k.startswith(("HEAT_TPU_SERVING", "HEAT_TPU_TELEMETRY"))
        )
    )


class TestCacheKeyByteIdentity(TestCase):
    def test_golden_plan_ids_unchanged_from_pr11(self):
        got = {
            name: planner.plan(spec).plan_id
            for name, spec in planner.golden_specs()
        }
        self.assertEqual(got, _PR11_PLAN_IDS)

    # slow: ~25 s subprocess dump; the plan-id pin above stays in tier-1
    @pytest.mark.slow
    def test_golden_dump_bytes_unchanged_from_pr11(self):
        """The `scripts/redist_plans.py` dump — every canonical plan
        serialization, quant twins included — byte-identical to PR 11
        HEAD (sha256 captured there), flat and at the forced 2x8
        two-tier topology. ISSUE 19 APPENDED the five factorization
        rows (``golden_factorization_plans``): the PR 11 pin now holds
        over the dump minus that suffix — the pre-existing rows must
        never drift — and a second pin holds the full dump including
        the appended rows."""
        import hashlib
        import subprocess
        import sys

        pinned = {
            (): (
                "7f180a82cfcb327cc839728fb972cac0d6cfc37374119da1082d46c40318854e",
                "5148ccf9de9537c1e56050b913655deb51e7ea9e5d77415acb5840bace3cdb9d",
            ),
            ("--topology", "2x8"): (
                "415455b3a8d83a21b050763f26ababb4d1b3ff3876b5fe992434544565d330a4",
                "fb6fe31cd1b67a9c76ea4e815c9752d8fa75b5035cf40ea88475e5976e433674",
            ),
        }
        n_fac = 5  # the ISSUE 19 factorization rows, appended last
        for extra, (want_pr11, want_full) in pinned.items():
            out = subprocess.run(
                [sys.executable, os.path.join(ROOT, "scripts", "redist_plans.py"), *extra],
                capture_output=True, check=True, cwd=ROOT,
            ).stdout
            prefix = b"".join(out.splitlines(keepends=True)[:-n_fac])
            self.assertEqual(hashlib.sha256(prefix).hexdigest(), want_pr11, extra)
            self.assertEqual(hashlib.sha256(out).hexdigest(), want_full, extra)

    def test_aot_fingerprint_empty_at_defaults(self):
        with env_pin("HEAT_TPU_OOC", None), env_pin("HEAT_TPU_WIRE_QUANT", None):
            fp = {
                k: v for k, v in gates.aot_fingerprint()
                if k in ("HEAT_TPU_OOC", "HEAT_TPU_WIRE_QUANT")
            }
            self.assertEqual(fp, {})

    def test_aot_fingerprint_matches_pr9_hand_filter(self):
        """Key-for-key equality with the retired prefix scan, across
        gate combinations (including an UNREGISTERED name, which stays
        conservatively key material exactly as before)."""
        combos = [
            {},
            {"HEAT_TPU_OOC": "1"},
            {"HEAT_TPU_WIRE_QUANT": "bf16", "HEAT_TPU_TOPOLOGY": "2x4"},
            {"HEAT_TPU_TELEMETRY": "1", "HEAT_TPU_SERVING_AOT": "1"},
            {"HEAT_TPU_FUTURE_UNREGISTERED": "x", "HEAT_TPU_HBM_BYTES": "123"},
        ]
        for combo in combos:
            pins = [env_pin(k, v) for k, v in combo.items()]
            try:
                for p in pins:
                    p.__enter__()
                self.assertEqual(
                    gates.aot_fingerprint(), _pr9_hand_fingerprint(), combo
                )
            finally:
                for p in reversed(pins):
                    p.__exit__(None, None, None)

    def test_new_program_gate_invalidates_aot_envelopes(self):
        """The roster pin: an envelope stored today is refused as
        version_mismatch — never served stale — once a new
        program-affecting gate is registered."""
        import tempfile

        with tempfile.TemporaryDirectory() as root:
            store = aot_cache.AOTStore(root)
            self.assertTrue(store.store("deadbeef", b"blob", None))
            self.assertIsNotNone(store.load("deadbeef"))
            self.assertEqual(store.stats["version_mismatch"], 0)
            fake = gates.GateSpec(
                "HEAT_TPU_TEST_ONLY_FAKE", default="0", values=("0", "1"),
                affects_programs=True, scopes=("program", "aot"),
                key_params=("fake",), help="test-only",
            )
            gates.declare(fake)
            try:
                self.assertIsNone(store.load("deadbeef"))
                self.assertEqual(store.stats["version_mismatch"], 1)
            finally:
                gates.GATES.pop("HEAT_TPU_TEST_ONLY_FAKE")
            # roster restored: the envelope (overwritten semantics aside)
            # verifies again
            self.assertIsNotNone(store.load("deadbeef"))


# ------------------------------------------------------------------ #
# golden bad fixtures: each rule fires                               #
# ------------------------------------------------------------------ #
class TestGoldenBadFixtures(TestCase):
    def test_sl401_use_after_donate(self):
        x = ht.ones((64, 8), split=0 if P > 1 else None)
        rep = effectcheck.check_donation(fx.use_after_donate_program, x)
        self.assertEqual({f.rule for f in rep}, {"SL401"})
        self.assertEqual(rep.findings[0].severity, "error")
        clean = effectcheck.check_donation(fx.donate_then_done_program, x)
        self.assertEqual(list(clean), [])

    def test_sl401_folds_into_ircheck(self):
        x = ht.ones((64, 8), split=0 if P > 1 else None)
        rep = ht.analysis.check(fx.use_after_donate_program, x)
        self.assertIn("SL401", rep.rule_ids)
        self.assertFalse(rep.ok)

    def test_sl402_stale_lru_builder(self):
        found = effectcheck.lint_source(fx.STALE_KEY_BUILDER_SRC, "heat_tpu/x.py")
        self.assertEqual({f.rule for f in found}, {"SL402"})
        self.assertIn("HEAT_TPU_REDIST_OVERLAP", found[0].message)
        self.assertIn("pipelined", found[0].message)  # the named fix

    def test_sl402_stale_dict_key(self):
        found = effectcheck.lint_source(fx.STALE_DICT_KEY_SRC, "heat_tpu/x.py")
        self.assertEqual({f.rule for f in found}, {"SL402"})
        self.assertIn("HEAT_TPU_TOPOLOGY", found[0].message)

    def test_sl403_raw_reads(self):
        found = effectcheck.lint_source(fx.RAW_GATE_READ_SRC, "heat_tpu/x.py")
        self.assertEqual({f.rule for f in found}, {"SL403"})
        self.assertEqual(len(found), 3)  # get, enumeration, containment
        # the registry module itself is the sanctioned read site
        self.assertEqual(
            effectcheck.lint_source(fx.RAW_GATE_READ_SRC, "heat_tpu/core/gates.py"),
            [],
        )

    def test_sl403_resolves_module_constant_names(self):
        """The codebase's historical read idiom — the gate name in a
        module-level ``*_ENV`` constant — is a raw read too."""
        src = (
            'import os\n'
            'OVERLAP_ENV = "HEAT_TPU_REDIST_OVERLAP"\n'
            'def overlap_mode():\n'
            '    return os.environ.get(OVERLAP_ENV, "auto")\n'
        )
        found = effectcheck.lint_source(src, "heat_tpu/x.py")
        self.assertEqual({f.rule for f in found}, {"SL403"})
        self.assertIn("HEAT_TPU_REDIST_OVERLAP", found[0].message)

    def test_sl403_ignores_prefix_free_env_enumeration(self):
        src = (
            'import os\n'
            'def diag():\n'
            '    return {k: v for k, v in os.environ.items() if k.startswith("SLURM_")}\n'
        )
        self.assertEqual(effectcheck.lint_source(src, "heat_tpu/x.py"), [])

    def test_snapshot_recognizes_spellings(self):
        with env_pin("HEAT_TPU_REDIST_OVERLAP", "force"):
            self.assertTrue(gates.snapshot()["HEAT_TPU_REDIST_OVERLAP"]["recognized"])
        with env_pin("HEAT_TPU_WIRE_QUANT", "int8"):
            self.assertTrue(gates.snapshot()["HEAT_TPU_WIRE_QUANT"]["recognized"])
        with env_pin("HEAT_TPU_OOC", "banana"):
            self.assertFalse(gates.snapshot()["HEAT_TPU_OOC"]["recognized"])
        with env_pin("HEAT_TPU_SERVING_CACHE", "/any/path"):
            self.assertTrue(gates.snapshot()["HEAT_TPU_SERVING_CACHE"]["recognized"])

    def test_sl404_unguarded_attr(self):
        found = effectcheck.lint_source(fx.UNGUARDED_ATTR_SRC, "heat_tpu/x.py")
        self.assertEqual({f.rule for f in found}, {"SL404"})
        self.assertIn("_counts", found[0].message)

    def test_sl404_annotation_declares_lock_free(self):
        annotated = fx.UNGUARDED_ATTR_SRC.replace(
            'self._counts = {"batches": 0}',
            'self._counts = {"batches": 0}  # racecheck: guarded-by(GIL; test-only tallies)',
        )
        self.assertEqual(effectcheck.lint_source(annotated, "heat_tpu/x.py"), [])

    def test_sl405_pipeline_protocol(self):
        found = effectcheck.lint_source(fx.PIPELINE_PROTOCOL_SRC, "heat_tpu/x.py")
        self.assertEqual({f.rule for f in found}, {"SL405"})
        by_line = {f.line: f.message for f in found}
        self.assertEqual(len(found), 3)  # inverted, unfenced, dropped
        self.assertTrue(any("consumes lap k before" in m for m in by_line.values()))
        self.assertTrue(any("JUST issued" in m for m in by_line.values()))
        self.assertTrue(any("never consumed" in m for m in by_line.values()))

    def test_rules_catalogued(self):
        for rule in ("SL401", "SL402", "SL403", "SL404", "SL405", "SL406"):
            self.assertIn(rule, findings.RULES)


# ------------------------------------------------------------------ #
# clean pins                                                         #
# ------------------------------------------------------------------ #
class TestCleanPins(TestCase):
    def test_tree_is_sl4xx_clean(self):
        report = effectcheck.lint_paths([HT], root=ROOT)
        self.assertEqual(list(report), [], [repr(f) for f in report])

    def test_threaded_and_cached_modules_individually_clean(self):
        for rel in (
            "heat_tpu/serving/dispatcher.py",
            "heat_tpu/serving/aot_cache.py",
            "heat_tpu/observability/telemetry.py",
            "heat_tpu/redistribution/executor.py",
            "heat_tpu/redistribution/staging.py",
            "heat_tpu/redistribution/planner.py",
            "heat_tpu/utils/data/partial_dataset.py",
        ):
            found = effectcheck.lint_source(_read(rel), rel)
            self.assertEqual(found, [], (rel, [repr(f) for f in found]))

    def test_golden_plan_forms_protocol_clean(self):
        """The plan-side SL405 sweep over every golden form the ci.sh
        determinism leg dumps: flat + 2x4 + 2x8, quant off and forced,
        plus the staged window schedules."""
        n = 0
        for topo in (None, (2, 4), (2, 8)):
            for quant in ("0", "int8"):
                for name, spec in planner.golden_specs():
                    if topo and spec.mesh_size != topo[0] * topo[1]:
                        continue
                    sched = planner.plan(
                        spec, quant=quant, topology=topo if topo else "flat"
                    )
                    self.assertEqual(
                        effectcheck.check_plan_protocol(sched), [], (name, topo, quant)
                    )
                    n += 1
        for name, sched in staging.golden_staged_plans():
            self.assertEqual(effectcheck.check_plan_protocol(sched), [], name)
            n += 1
        self.assertGreaterEqual(n, 60)

    def test_shipped_double_buffer_loops_clean(self):
        """_run_laps and stream_windows ARE depth-2 claimants — the
        detector must recognize and pass them (not skip them)."""
        src = _read("heat_tpu/redistribution/executor.py")
        self.assertIn("def _run_laps", src)
        self.assertEqual(
            [f for f in effectcheck.lint_source(src, "heat_tpu/redistribution/executor.py")],
            [],
        )


# ------------------------------------------------------------------ #
# seeded-bug mutations (the ci.sh proof)                             #
# ------------------------------------------------------------------ #
class TestSeededBugMutations(TestCase):
    """Acceptance: remove ONE invariant from the real source, the lint
    trips at error. Each mutation asserts its anchor still exists, so
    source drift fails loudly instead of silently weakening the proof."""

    def test_mutation_gate_dropped_from_program_cache_key_trips_sl402(self):
        """Invariant: HEAT_TPU_REDIST_OVERLAP is a component of every
        executor program-cache key (the ``pipelined`` parameter).
        Mutation: drop the parameter and resolve the gate inside the
        cached builder — the post-PR-5 review line made mechanical."""
        src = _read("heat_tpu/redistribution/executor.py")
        anchor = "def _move_program(\n    comm, spec: RedistSpec, budget: int, pipelined: bool = False,"
        self.assertIn(anchor, src)
        mutated = src.replace(
            anchor,
            "def _move_program(\n    comm, spec: RedistSpec, budget: int,",
        ).replace(
            "    sched = _planner.plan(\n        spec, budget, quant=wire or \"0\", topology=topo if topo else \"flat\"\n    )\n    mesh, axis_name = comm.mesh, comm.axis_name\n    p = spec.mesh_size\n    i, j = spec.src_split, spec.dst_split",
            "    sched = _planner.plan(\n        spec, budget, quant=wire or \"0\", topology=topo if topo else \"flat\"\n    )\n    pipelined = _overlap_active(sched)\n    mesh, axis_name = comm.mesh, comm.axis_name\n    p = spec.mesh_size\n    i, j = spec.src_split, spec.dst_split",
            1,
        )
        self.assertNotEqual(mutated, src)
        found = effectcheck.lint_source(mutated, "heat_tpu/redistribution/executor.py")
        hits = [f for f in found if f.rule == "SL402" and "HEAT_TPU_REDIST_OVERLAP" in f.message]
        self.assertTrue(hits, [repr(f) for f in found])
        self.assertTrue(all(f.severity == "error" for f in hits))

    def test_mutation_gate_dropped_from_plan_cache_key_trips_sl402(self):
        """Invariant: the resolved topology is a component of the
        planner's dict-cache key. Mutation: delete it from the tuple."""
        src = _read("heat_tpu/redistribution/planner.py")
        anchor = 'key = (spec, b, qmode or "0", topo)'
        self.assertIn(anchor, src)
        mutated = src.replace(anchor, 'key = (spec, b, qmode or "0")')
        found = effectcheck.lint_source(mutated, "heat_tpu/redistribution/planner.py")
        hits = [f for f in found if f.rule == "SL402" and "HEAT_TPU_TOPOLOGY" in f.message]
        self.assertTrue(hits, [repr(f) for f in found])

    def test_mutation_lock_dropped_from_dispatcher_path_trips_sl404(self):
        """Invariant: every access of Dispatcher._counts/_lat holds
        _counts_lock. Mutation: remove one acquisition (any of them)."""
        src = _read("heat_tpu/serving/dispatcher.py")
        acquisitions = src.count("with self._counts_lock:")
        self.assertGreaterEqual(acquisitions, 4)
        for i in range(acquisitions):
            # rebuild the source with occurrence i (and only it) replaced
            pieces = src.split("with self._counts_lock:")
            mutated = ""
            for j, piece in enumerate(pieces):
                mutated += piece
                if j < len(pieces) - 1:
                    mutated += (
                        "if True:  # mutated" if j == i else "with self._counts_lock:"
                    )
            found = effectcheck.lint_source(mutated, "heat_tpu/serving/dispatcher.py")
            hits = [f for f in found if f.rule == "SL404"]
            self.assertTrue(hits, f"occurrence {i}: no SL404 on lock removal")
            self.assertTrue(all(f.severity == "error" for f in hits))

    def test_mutation_inverted_loop_trips_sl405(self):
        """Invariant: _run_laps issues lap k+1 before consuming lap k.
        Mutation: swap the two statements (the sequential regression)."""
        src = _read("heat_tpu/redistribution/executor.py")
        anchor = (
            "        nxt = issue(idx[i])  # lap i on the wire ...\n"
            "        state = consume(state, prev, idx[i - 1])  # ... while i-1 relayouts\n"
        )
        self.assertIn(anchor, src)
        mutated = src.replace(
            anchor,
            "        state = consume(state, prev, idx[i - 1])\n"
            "        nxt = issue(idx[i])\n",
        )
        found = effectcheck.lint_source(mutated, "heat_tpu/redistribution/executor.py")
        hits = [f for f in found if f.rule == "SL405"]
        self.assertTrue(hits, [repr(f) for f in found])


# ------------------------------------------------------------------ #
# threading stress: exact totals on the SL404-clean paths            #
# ------------------------------------------------------------------ #
class TestConcurrencyExactTotals(TestCase):
    def test_dispatcher_counts_exact_under_concurrent_clients(self):
        ep = Endpoint({8: jax.jit(lambda b: b * 2.0)}, (4,), np.float32)
        n_threads, per_thread = 8, 25
        ok, rejected = [], []
        with Dispatcher(ep, max_queue=256) as d:
            def client(seed):
                rng = np.random.default_rng(seed)
                for _ in range(per_thread):
                    x = rng.standard_normal((2, 4)).astype(np.float32)
                    try:
                        fut = d.submit(x)
                    except Exception:
                        rejected.append(1)
                        continue
                    np.testing.assert_allclose(
                        np.asarray(fut.result(timeout=30)), x * 2.0, rtol=1e-6
                    )
                    ok.append(1)

            threads = [
                threading.Thread(target=client, args=(s,)) for s in range(n_threads)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            stats = d.stats()
        total = n_threads * per_thread
        self.assertEqual(len(ok) + len(rejected), total)
        self.assertEqual(stats["requests"], len(ok))
        self.assertEqual(stats["rejected"], len(rejected))
        self.assertEqual(stats["rows"] + stats["shed"] * 0, 2 * len(ok))
        self.assertEqual(len(ok), total)  # queue is deep enough: no rejects

    def test_telemetry_counters_exact_under_concurrent_recorders(self):
        from heat_tpu.observability import telemetry

        telemetry.reset()
        telemetry.enable()
        try:
            n_threads, per_thread = 16, 500

            def recorder():
                for _ in range(per_thread):
                    telemetry.inc("effectcheck.stress")
                    telemetry.observe("effectcheck.stress.t", 0.001)

            threads = [threading.Thread(target=recorder) for _ in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            snap = telemetry.snapshot()
            self.assertEqual(
                snap["counters"]["effectcheck.stress"], n_threads * per_thread
            )
            self.assertEqual(
                snap["timers"]["effectcheck.stress.t"]["calls"],
                n_threads * per_thread,
            )
        finally:
            telemetry.disable()
            telemetry.reset()


if __name__ == "__main__":
    import unittest

    unittest.main()
