#!/usr/bin/env python
"""Dump the canonical serialization of every golden redistribution plan.

The ci.sh determinism leg runs this twice and diffs the output: plans
key the executor's program cache (``plan_id`` = sha1 of the canonical
serialization), so they must be byte-identical run-to-run — any
nondeterminism in the planner (dict ordering, float formatting,
environment leakage) shows up here as a diff before it can show up as a
phantom cache miss or a flapping golden test. The ISSUE-6 ``overlap``
annotation (pipe tags per step, per-group critical-path model,
``model_speedup``) is part of the canonical serialization, so the
determinism leg covers the annotated plans and their plan_ids — and the
annotation is gate-independent (``HEAT_TPU_REDIST_OVERLAP`` switches
the executor's issue order, never the plan), so an ambient gate cannot
make two runs diverge either.

ISSUE 7: every golden spec is dumped TWICE — the full-width plan
(``quant="0"``) and the forced-int8 plan (``quant="int8"``, suffixed
``.quant``) — both pinned explicitly, so the quant-annotated plan_ids
are covered by the determinism diff and an ambient ``HEAT_TPU_WIRE_QUANT``
cannot make two CI runs diverge.

ISSUE 8: ``--topology SxC`` dumps the golden matrix planned at a forced
two-tier topology (suffix ``@SxC``). The ci.sh determinism leg runs the
dump twice at the DEFAULT (flat — pinned explicitly, so an ambient
``HEAT_TPU_TOPOLOGY`` cannot make runs diverge) and twice at ``2x8``,
diffing both pairs: tiered plan_ids differ from flat ones only via the
tier/topology annotations, and both must be byte-identical run-to-run.

Pure Python: no mesh, no jax device work — safe on any container.
"""

import argparse
import sys

from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def rows(topology=None):
    """``(name, Schedule)`` for every row of the dump, in dump order —
    ``tests/test_plan_ids.py`` holds each row's ``plan_id`` to its table."""
    from heat_tpu.redistribution import planner

    # the default budget / codec / topology, pinned explicitly so an
    # ambient HEAT_TPU_REDIST_BUDGET_MB / HEAT_TPU_WIRE_QUANT /
    # HEAT_TPU_TOPOLOGY cannot make two CI runs diverge
    budget = planner.DEFAULT_BUDGET_MB << 20
    pinned = topology if topology else "flat"
    suffix = f"@{topology}" if topology else ""
    for name, spec in planner.golden_specs():
        yield f"{name}{suffix}", planner.plan(spec, budget, quant="0", topology=pinned)
    for name, spec in planner.golden_specs():
        yield f"{name}.quant{suffix}", planner.plan(spec, budget, quant="int8", topology=pinned)

    # ISSUE 11: the out-of-core staged golden plans ride the same
    # determinism + verify_plan sweep. Slab/working-set bytes are pinned
    # inside golden_staged_plans (NOT the ambient HEAT_TPU_OOC* env),
    # and host-staging plans are topology-free (mesh_size 1, no
    # collectives), so the tiered dump rows are identical to the flat
    # ones by construction — dumped in every topology run so each diff
    # pair covers them.
    from heat_tpu.redistribution import staging

    for name, sched in staging.golden_staged_plans():
        yield f"{name}{suffix}", sched

    # ISSUE 19: the dense-factorization ring schedules ride the same
    # determinism + verify_plan sweep. Shapes/budget are pinned inside
    # golden_factorization_plans (NOT the ambient env), and the plans
    # are pure ppermute rings over a flat split-0 mesh — topology-free
    # like the staged plans — so the tiered dump rows are identical to
    # the flat ones by construction; dumped in every topology run so
    # each diff pair covers them.
    from heat_tpu.core.linalg.factorizations import golden_factorization_plans

    for name, sched in golden_factorization_plans():
        yield f"{name}{suffix}", sched


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument(
        "--topology",
        default=None,
        help="force a two-tier topology (e.g. 2x8) for every golden plan; "
        "default: flat (pinned — NOT the ambient HEAT_TPU_TOPOLOGY)",
    )
    args = ap.parse_args()
    for name, sched in rows(args.topology):
        print(f"{name}\t{sched.canonical_json()}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
