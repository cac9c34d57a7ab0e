"""Schedule-IR plan verifier — ``ht.analysis.verify_plan``.

The redistribution planner's golden matrix is pinned today by byte-level
dump diffing (ci.sh runs ``scripts/redist_plans.py`` twice and diffs):
that catches nondeterminism, but a plan that is *deterministically
wrong* — corrupted accounting, a dropped dequantize step, a tier label
that contradicts the topology — would diff clean forever. This module
closes that gap: it symbolically executes a
:class:`~heat_tpu.redistribution.schedule.Schedule` (or its parsed
canonical-JSON dict) over abstract shard shapes and PROVES the plan
well-formed, invariant by invariant:

``composition``
    the step sequence is one that takes ``spec.src`` to ``spec.dst``:
    per-strategy symbolic templates over the step kinds (an a2a plan is
    laps of slice→all-to-all→scatter; a pivot is stage-in → local
    reshape → stage-out; a ring is exactly ``p-1`` ppermute hops; a
    hierarchical plan alternates intra-slice/inter-slice exchanges),
    with the spec-side preconditions (splits, reshape validity) checked
    so the matched template provably ends at ``(out_shape, dst_split)``.
``conservation``
    per-step byte conservation: the collective payloads re-derived from
    the spec's geometry (padded shard bytes, crossing fractions, lap
    counts) equal the plan's recorded movement — exactly, including the
    chunking floor-division the planner applies.
``accounting``
    the recorded ``peak_bytes``/``bytes_moved``/``bytes_copied``/
    ``collective_counts``/``within_budget`` fields equal what the steps
    recompute to (the liveness-based peak of the step list — see
    :meth:`Schedule.liveness`).
``quant-pairing``
    every wire-codec collective sits inside a quantize → collective →
    dequantize triple, codec steps appear iff the schedule carries a
    ``quant`` annotation, and the annotation's ``bytes_raw``/
    ``bytes_sent``/``ratio`` arithmetic is consistent (``wire_ratio``
    is recomputed, not trusted).
``tier-labels``
    tier labels are consistent with the ``topology`` annotation (and
    with an explicitly expected ``topology=`` argument): flat plans
    carry no tiers, tiered flat-structure plans ride DCN end to end,
    hierarchical plans carry both tiers in intra/inter order, and
    ``n_slices * chips_per_slice == mesh_size``.
``overlap-structure``
    pipeline groups are well-formed laps: each group's tag anchors the
    right number of collective laps, and the depth-2 critical-path
    arithmetic (``w + (laps-1)·max(w, c) + c``; the tiered
    ``max(ici, dcn·penalty, copy)`` form) reproduces the annotation.
``staging``
    out-of-core window schedules (ISSUE 11, ``host-staging`` plans):
    every ``stage_in`` pairs with its ``stage_out`` on writeback
    passes, each pass's windows conserve the operand exactly, the
    recorded depth-2 slab occupancies match the window+prefetch
    recompute, the resident working set plus the slab peak fits
    ``tiers.capacity("hbm")``, and the annotation's lattice time model
    (``tiers.transfer_time`` over the pcie/hbm edges) is reproduced.
``progress``
    the collective-congruence replay (ISSUE 14, pass 5's dynamic half):
    a symbolic per-device execution of the schedule proving every
    participant can RUN it to completion — every collective step's
    group structure is congruent across participants (hierarchical
    ici/dcn pairs ride partitions of the mesh, ``S·C == p``), every
    ring closes in exactly ``p-1`` hops (the replay delivers all ``p``
    blocks), each hierarchical lap's intra/inter halves carry the SAME
    chunk index (a split pair leaves one tier waiting on an unissued
    lap), and every depth-2 overlap group issues its laps in exactly
    the order the double-buffer consumes them (``0..laps-1`` — a
    reordered lap makes the consume slot read an unissued buffer).
    Available standalone as :func:`check_progress` — what the MPMD
    stage-graph verifier will consume per stage.
``tolerance``
    the error-bound recomputation (ISSUE 17, pass 6's dynamic half):
    the end-to-end error bound recomputed from the recorded per-step
    tolerances — each quantize step contributes the codec's pinned
    ``tolerance(mode)`` to the disjoint payload leg it encodes (one
    ``(overlap, chunk)`` lap, one ring hop block, one standalone
    phase), staging/relayout/overlap steps are exact-bit, and in a
    hierarchical plan only dcn-tier crossings may carry the codec (the
    PR 8 policy) — must equal the schedule-level ``quant.tol``
    annotation, which itself must equal
    ``kernels.quant.tolerance(mode)``. Every encoded crossing must be
    codec-sandwiched and attributed (``[<mode> wire]``), and no
    exact-bit plan may claim one. Available standalone as
    :func:`check_tolerance` (SL605 findings) — the budget contract the
    Newton–Schulz and MPMD tolerance consumers read.
``plan-id``
    the ``plan_id`` is the sha1 of the canonical serialization — a
    hand-edited or bit-rotted dump cannot keep its id.

Runs in pure Python (no mesh, no jax device work), so the ci.sh
determinism leg sweeps it over every dumped golden plan — flat, 2x4,
2x8, quant on and off — and tier-1 pins the same sweep in-process.
"""

from __future__ import annotations

import hashlib
import json

from typing import Any, Dict, List, Optional, Tuple, Union

__all__ = [
    "PlanVerificationError", "check_progress", "check_tolerance",
    "verify_plan",
]

_COLLECTIVE_KINDS = ("all_to_all", "all_gather", "ppermute")
_LOCAL_KINDS = (
    "slice", "pad", "reshape", "concat", "pack", "unpack",
    "quantize", "dequantize",
)
_CODEC_KINDS = ("quantize", "dequantize")
# ISSUE 11: the out-of-core staging transfers (redistribution.staging)
# — they move bytes across the pcie edge of the memory-tier lattice but
# launch no collective, so they sit in neither class above
_STAGING_KINDS = ("stage_in", "stage_out")


class PlanVerificationError(ValueError):
    """One violated plan invariant, named.

    Attributes
    ----------
    invariant : the violated invariant's name (``composition``,
        ``conservation``, ``accounting``, ``quant-pairing``,
        ``tier-labels``, ``overlap-structure``, ``staging``,
        ``progress``, ``tolerance``, ``plan-id``, ``step-kinds``).
    detail : what exactly failed, with the offending numbers.
    plan_id : the plan's id when known.
    """

    def __init__(self, invariant: str, detail: str, plan_id: Optional[str] = None):
        self.invariant = invariant
        self.detail = detail
        self.plan_id = plan_id
        where = f"plan {plan_id} " if plan_id else "plan "
        super().__init__(f"{where}violates invariant '{invariant}': {detail}")


def _pad_extent(n: int, p: int) -> int:
    from ..core import _padding

    return _padding.pad_extent(int(n), int(p))


def _prod(shape) -> int:
    n = 1
    for s in shape:
        n *= int(s)
    return n


def _itemsize(dtype: str) -> int:
    import numpy as np

    return np.dtype(dtype).itemsize


def _as_plan_dict(plan) -> Dict[str, Any]:
    from ..redistribution.schedule import Schedule

    if isinstance(plan, Schedule):
        return plan.as_dict()
    if isinstance(plan, str):
        plan = json.loads(plan)
    if not isinstance(plan, dict):
        raise TypeError(
            f"verify_plan expects a Schedule, a plan dict, or its JSON "
            f"serialization — got {type(plan).__name__}"
        )
    return plan


def _expected_topology(topology) -> Union[None, str, Tuple[int, int]]:
    """Normalize the expected-topology argument: ``None`` = no
    expectation (self-consistency only), ``"flat"`` = must be untiered,
    ``"SxC"``/``(S, C)``/``Topology`` = must match."""
    if topology is None:
        return None
    if isinstance(topology, str):
        t = topology.strip().lower()
        if t in ("flat", "1", ""):
            return "flat"
        parts = t.split("x")
        if len(parts) == 2 and parts[0].isdigit() and parts[1].isdigit():
            return (int(parts[0]), int(parts[1]))
        raise ValueError(f"verify_plan: unknown topology expectation {topology!r}")
    if isinstance(topology, tuple):
        return (int(topology[0]), int(topology[1]))
    n_slices = getattr(topology, "n_slices", None)
    chips = getattr(topology, "chips_per_slice", None)
    if n_slices is not None and chips is not None:
        return (int(n_slices), int(chips)) if int(n_slices) > 1 else "flat"
    raise TypeError(f"verify_plan: cannot interpret topology {topology!r}")


def _stage_local_bytes(shape, axis: int, p: int, item: int) -> int:
    """Per-device bytes of the doubly-padded buffer one pivot stage
    exchanges (the planner's stage geometry: the stage's split axis
    padded to divide the mesh)."""
    padded = [
        _pad_extent(d, p) if ax == axis else int(d) for ax, d in enumerate(shape)
    ]
    return _prod(padded) // p * item


def verify_plan(
    plan,
    topology=None,
    raise_on_violation: bool = True,
) -> Dict[str, Any]:
    """Verify one Schedule-IR plan against its invariants.

    Parameters
    ----------
    plan : a :class:`~heat_tpu.redistribution.schedule.Schedule`, the
        dict of its ``as_dict()``/canonical serialization, or that
        serialization as a JSON string (what ``scripts/redist_plans.py``
        dumps — the ci.sh sweep feeds those lines straight in).
    topology : optional EXPECTED topology — ``"flat"`` (the plan must be
        untiered), an ``"SxC"`` string / ``(S, C)`` tuple /
        ``core.communication.Topology`` (the plan's annotation must
        match). Default ``None`` checks self-consistency only.
    raise_on_violation : raise :class:`PlanVerificationError` on the
        first violated invariant (the CI mode — the violated invariant
        is named in the exception); with ``False`` all violations are
        collected into the returned report.

    Returns ``{"ok", "plan_id", "strategy", "checks", "violations"}``;
    ``checks`` lists every invariant that was evaluated.
    """
    d = _as_plan_dict(plan)
    plan_id = d.get("plan_id")
    violations: List[PlanVerificationError] = []

    def fail(invariant: str, detail: str) -> None:
        err = PlanVerificationError(invariant, detail, plan_id=plan_id)
        if raise_on_violation:
            raise err
        violations.append(err)

    spec = d.get("spec") or {}
    strategy = d.get("strategy", "")
    steps: List[Dict[str, Any]] = list(d.get("steps") or [])
    gshape = tuple(int(v) for v in (spec.get("gshape") or ()))
    out_shape = (
        tuple(int(v) for v in spec["reshape_to"])
        if spec.get("reshape_to") is not None
        else gshape
    )
    is_reshape = spec.get("reshape_to") is not None
    src = spec.get("src_split")
    dst = spec.get("dst_split")
    p = int(spec.get("mesh_size", 1))
    item = _itemsize(spec.get("dtype", "float32"))
    size = _prod(gshape)

    # ---- step-kinds: the vocabulary itself ----------------------------
    for k, st in enumerate(steps):
        kind = st.get("kind")
        if (
            kind not in _COLLECTIVE_KINDS
            and kind not in _LOCAL_KINDS
            and kind not in _STAGING_KINDS
        ):
            fail("step-kinds", f"step [{k}] has unknown kind {kind!r}")
        if st.get("tier") not in (None, "ici", "dcn", "pcie"):
            fail("step-kinds", f"step [{k}] has unknown tier {st.get('tier')!r}")
        if kind in _STAGING_KINDS and st.get("tier") != "pcie":
            fail(
                "step-kinds",
                f"staging step [{k}] ({kind}) must ride tier 'pcie' — got "
                f"{st.get('tier')!r}",
            )
        if kind not in _STAGING_KINDS and st.get("tier") == "pcie":
            fail(
                "step-kinds",
                f"step [{k}] ({kind}) claims tier 'pcie' — reserved for "
                "stage_in/stage_out",
            )
        for field in ("bytes_moved", "bytes_copied", "peak_bytes"):
            if int(st.get(field, 0)) < 0:
                fail("step-kinds", f"step [{k}] has negative {field}")
        if kind in _LOCAL_KINDS and int(st.get("bytes_moved", 0)) != 0:
            fail(
                "step-kinds",
                f"local step [{k}] ({kind}) claims bytes_moved="
                f"{st['bytes_moved']} — only collectives and staging "
                "transfers move bytes",
            )

    coll = [st for st in steps if st.get("kind") in _COLLECTIVE_KINDS]

    # ---- accounting: the recorded fields vs the steps -----------------
    recomputed_peak = max((int(st.get("peak_bytes", 0)) for st in steps), default=0)
    if int(d.get("peak_bytes", 0)) != recomputed_peak:
        fail(
            "accounting",
            f"recorded peak_bytes={d.get('peak_bytes')} but the liveness "
            f"recompute over the steps gives {recomputed_peak}",
        )
    from ..redistribution.schedule import Schedule as _Schedule

    if isinstance(plan, _Schedule):
        # the liveness hook must agree with the step accounting: resident
        # shards + the recomputed transient peak
        live = plan.liveness()
        live_peak = max((e["transient_bytes"] for e in live), default=0)
        if live_peak != recomputed_peak or plan.liveness_peak_bytes != (
            plan.resident_bytes + recomputed_peak
        ):
            fail(
                "accounting",
                f"Schedule.liveness() peak {live_peak} (+resident "
                f"{plan.resident_bytes}) disagrees with the step "
                f"accounting peak {recomputed_peak}",
            )
    moved = sum(int(st.get("bytes_moved", 0)) for st in steps)
    if int(d.get("bytes_moved", 0)) != moved:
        fail(
            "accounting",
            f"recorded bytes_moved={d.get('bytes_moved')} != step sum {moved}",
        )
    copied = sum(int(st.get("bytes_copied", 0)) for st in steps)
    if int(d.get("bytes_copied", 0)) != copied:
        fail(
            "accounting",
            f"recorded bytes_copied={d.get('bytes_copied')} != step sum {copied}",
        )
    budget = int(d.get("budget_bytes", 0))
    if budget < 1:
        fail("accounting", f"budget_bytes={budget} is not positive")
    if bool(d.get("within_budget")) != (recomputed_peak <= budget):
        fail(
            "accounting",
            f"within_budget={d.get('within_budget')} contradicts peak "
            f"{recomputed_peak} vs budget {budget}",
        )
    counts: Dict[str, int] = {}
    op_of = {"all_to_all": "all-to-all", "all_gather": "all-gather",
             "ppermute": "collective-permute"}
    for st in coll:
        op = op_of[st["kind"]]
        counts[op] = counts.get(op, 0) + 1
    if dict(d.get("collective_counts") or {}) != counts:
        fail(
            "accounting",
            f"recorded collective_counts={d.get('collective_counts')} != "
            f"step census {counts}",
        )

    # ---- quant-pairing ------------------------------------------------
    quant = d.get("quant")
    n_q = sum(1 for st in steps if st.get("kind") == "quantize")
    n_dq = sum(1 for st in steps if st.get("kind") == "dequantize")
    if (n_q or n_dq) and not quant:
        fail(
            "quant-pairing",
            f"{n_q} quantize / {n_dq} dequantize steps but no schedule-"
            "level quant annotation",
        )
    if n_q != n_dq:
        fail("quant-pairing", f"{n_q} quantize steps vs {n_dq} dequantize steps")
    for k, st in enumerate(steps):
        if st.get("kind") == "quantize":
            nxt = steps[k + 1] if k + 1 < len(steps) else None
            nxt2 = steps[k + 2] if k + 2 < len(steps) else None
            if nxt is None or nxt.get("kind") not in _COLLECTIVE_KINDS:
                fail(
                    "quant-pairing",
                    f"quantize step [{k}] is not followed by a collective "
                    "(the encoded wire has no consumer)",
                )
            elif nxt2 is None or nxt2.get("kind") != "dequantize":
                fail(
                    "quant-pairing",
                    f"wire-codec collective [{k + 1}] is not followed by a "
                    "dequantize (the received blocks stay encoded)",
                )
    if quant:
        mode = quant.get("mode")
        if mode not in ("int8", "bf16"):
            fail("quant-pairing", f"unknown wire-codec mode {mode!r}")
        if n_q == 0:
            fail("quant-pairing", "quant annotation present but no quantize step")
        raw_q, sent_q = int(quant.get("bytes_raw", -1)), int(quant.get("bytes_sent", -1))
        if raw_q < sent_q or sent_q < 0:
            fail(
                "quant-pairing",
                f"quant annotation bytes_raw={raw_q} < bytes_sent={sent_q} "
                "(the codec cannot inflate the wire)",
            )
        if sent_q != moved:
            fail(
                "quant-pairing",
                f"quant annotation bytes_sent={sent_q} != the steps' wire "
                f"total {moved}",
            )
        want_ratio = round(sent_q / raw_q, 4) if raw_q else 1.0
        if abs(float(quant.get("ratio", -1)) - want_ratio) > 1e-9:
            fail(
                "quant-pairing",
                f"quant ratio={quant.get('ratio')} != recomputed "
                f"{want_ratio} (wire_ratio arithmetic is not consistent)",
            )

    # ---- tier-labels --------------------------------------------------
    topo = d.get("topology")
    expected = _expected_topology(topology)
    if expected == "flat" and topo is not None:
        fail(
            "tier-labels",
            f"expected a flat plan but the schedule carries topology {topo}",
        )
    if isinstance(expected, tuple):
        got = (
            (int(topo["n_slices"]), int(topo["chips_per_slice"])) if topo else None
        )
        # the planner's own resolution semantics: a forced SxC that does
        # not factor THIS spec's mesh falls back to flat, and plans that
        # launch no collectives never carry the annotation at all.
        # Factorization ring schedules (ISSUE 19) are planned topology-
        # blind — every collective is a nearest-neighbour ppermute hop of
        # a pre-declared ring, so a forced topology never annotates them.
        want = (
            expected
            if (
                expected[0] * expected[1] == p
                and coll
                and not strategy.startswith("factorization-")
            )
            else None
        )
        if got != want:
            fail(
                "tier-labels",
                f"expected topology "
                f"{want and f'{want[0]}x{want[1]}' or 'flat'} (from "
                f"{expected[0]}x{expected[1]} over a {p}-device mesh) but "
                f"the schedule carries {got and f'{got[0]}x{got[1]}'}",
            )
    if topo is not None:
        S, C = int(topo.get("n_slices", 0)), int(topo.get("chips_per_slice", 0))
        if S < 2 or C < 1 or S * C != p:
            fail(
                "tier-labels",
                f"topology annotation {S}x{C} does not factor the mesh "
                f"(mesh_size {p})",
            )
        if int(topo.get("dcn_penalty", 0)) < 1:
            fail("tier-labels", f"dcn_penalty={topo.get('dcn_penalty')} is not >= 1")
    tiers = [st.get("tier") for st in coll]
    if topo is None:
        if any(t is not None for t in tiers):
            fail(
                "tier-labels",
                "tier labels present on a flat plan (no topology annotation)",
            )
    else:
        if any(t is None for t in tiers):
            fail(
                "tier-labels",
                "a tiered plan's collectives must all carry a tier label",
            )
        if strategy == "hierarchical-a2a":
            # intra-slice pivot first, inter-slice exchange second — per lap
            if tiers[0::2] != ["ici"] * len(tiers[0::2]) or tiers[1::2] != [
                "dcn"
            ] * len(tiers[1::2]):
                fail(
                    "tier-labels",
                    f"hierarchical-a2a tiers must alternate ici,dcn per lap "
                    f"— got {tiers}",
                )
        elif any(t != "dcn" for t in tiers):
            fail(
                "tier-labels",
                f"a slice-spanning flat-structure plan rides DCN end to end "
                f"— got tiers {tiers}",
            )
    for k, st in enumerate(steps):
        if (
            st.get("kind") not in _COLLECTIVE_KINDS
            and st.get("kind") not in _STAGING_KINDS
            and st.get("tier") is not None
        ):
            fail("tier-labels", f"local step [{k}] ({st['kind']}) carries a tier")

    # ---- composition: src must compose to dst -------------------------
    kinds = [st["kind"] for st in steps if st.get("kind") not in _CODEC_KINDS]
    coll_kinds = [k for k in kinds if k in _COLLECTIVE_KINDS]

    def _compose() -> Optional[str]:
        if strategy == "noop":
            if steps:
                return "a noop plan must have no steps"
            if src != dst or (is_reshape and gshape != out_shape):
                return "a noop plan must not change split or shape"
        elif strategy == "local":
            if p > 1 and size > 0:
                return f"a local plan needs a 1-device mesh or empty array (p={p})"
        elif strategy == "slice":
            if src is not None or dst is None:
                return f"slice serves replicated->split only (src={src}, dst={dst})"
            if coll_kinds:
                return f"slice must launch no collectives — got {coll_kinds}"
        elif strategy == "replicate":
            if dst is not None:
                return f"replicate must end replicated (dst={dst})"
            if coll_kinds != ["all_gather"]:
                return f"replicate is ONE all-gather — got {coll_kinds}"
        elif strategy == "gather-reshape":
            if coll_kinds != ["all_gather"]:
                return f"gather-reshape is ONE all-gather — got {coll_kinds}"
            if is_reshape and "reshape" not in kinds:
                return "gather-reshape never reshapes the gathered array"
        elif strategy == "local-reshape":
            if coll_kinds:
                return f"local-reshape must launch no collectives — got {coll_kinds}"
        elif strategy in ("all-to-all", "chunked-all-to-all"):
            if is_reshape:
                return "a pure-resplit strategy cannot serve a reshape spec"
            if src is None or dst is None or src == dst:
                return f"resplit needs two distinct splits (src={src}, dst={dst})"
            if not coll_kinds or set(coll_kinds) != {"all_to_all"}:
                return f"the exchange must be all-to-all laps — got {coll_kinds}"
            if strategy == "chunked-all-to-all" and len(coll_kinds) < 2:
                return "a chunked plan needs >= 2 laps"
        elif strategy == "ring":
            if is_reshape:
                return "ring serves pure resplits only"
            if coll_kinds != ["ppermute"] * (p - 1):
                return (
                    f"ring is exactly p-1={p - 1} ppermute hops — got "
                    f"{len(coll_kinds)} of {sorted(set(coll_kinds))}"
                )
        elif strategy in ("split0-pivot", "packed-pivot"):
            if not is_reshape:
                return "the pivot serves reshape-with-repartition specs only"
            if kinds.count("reshape") != 1:
                return (
                    f"the pivot has exactly one local reshape at full width "
                    f"— got {kinds.count('reshape')}"
                )
            if not gshape or not out_shape:
                return "the pivot needs non-scalar source and target shapes"
            if gshape[0] % p or out_shape[0] % p:
                return (
                    f"pivot divisibility violated: leading extents "
                    f"{gshape[0]}/{out_shape[0]} must divide p={p}"
                )
            if set(coll_kinds) - {"all_to_all"}:
                return f"pivot stages exchange via all-to-all — got {coll_kinds}"
            piv = kinds.index("reshape")
            n_in = sum(1 for k in kinds[:piv] if k in _COLLECTIVE_KINDS)
            n_out = sum(1 for k in kinds[piv:] if k in _COLLECTIVE_KINDS)
            if (src not in (None, 0)) != (n_in > 0):
                return (
                    f"stage-in mismatch: src_split={src} but {n_in} "
                    "collectives before the pivot reshape"
                )
            if (dst not in (None, 0)) != (n_out > 0):
                return (
                    f"stage-out mismatch: dst_split={dst} but {n_out} "
                    "collectives after the pivot reshape"
                )
        elif strategy == "hierarchical-a2a":
            if topo is None:
                return "hierarchical-a2a requires a topology annotation"
            if set(coll_kinds) != {"all_to_all"}:
                return f"hierarchical laps exchange via all-to-all — got {coll_kinds}"
            if len(coll_kinds) % 2:
                return (
                    f"hierarchical laps come in intra/inter pairs — got "
                    f"{len(coll_kinds)} collectives"
                )
        elif strategy == "host-staging":
            # ISSUE 11: the out-of-core window stream — no mesh
            # movement at all, only pcie staging transfers
            if coll_kinds:
                return f"host-staging launches no collectives — got {coll_kinds}"
            if not kinds or any(k not in _STAGING_KINDS for k in kinds):
                return (
                    "host-staging steps are stage_in/stage_out windows only "
                    f"— got {sorted(set(kinds) - set(_STAGING_KINDS))}"
                )
            if d.get("staging") is None:
                return "host-staging requires a staging annotation"
            if src is not None or dst is not None:
                return (
                    "host-staging streams a host-resident operand — splits "
                    f"must be None (src={src}, dst={dst})"
                )
        elif strategy.startswith("factorization-"):
            # ISSUE 19: the dense-factorization ring schedules
            # (core/linalg/factorizations._factorization_plan) — every
            # collective is a ppermute hop of a pre-declared ring, and
            # the hop census per solver is a pinned contract
            # (tests/test_factorizations.py proves census == plan)
            if is_reshape:
                return "a factorization plan never reshapes its operand"
            if src != 0 or dst != 0:
                return (
                    f"factorization plans serve split-0 operands in place "
                    f"(src={src}, dst={dst})"
                )
            kind_f = strategy[len("factorization-"):]
            want = {
                "polar": 5 * (p - 1),
                "cholesky": p * (p - 1),
                "lu": p * (p - 1) + (p - 1) ** 2,
                "solve-chol": 2 * (p - 1) ** 2,
                "solve-lu": 2 * (p - 1) ** 2,
            }.get(kind_f)
            if want is None:
                return f"unknown factorization kind {kind_f!r}"
            if set(coll_kinds) - {"ppermute"}:
                return (
                    f"factorization rings are ppermute-only — got "
                    f"{sorted(set(coll_kinds))}"
                )
            if len(coll_kinds) != want:
                return (
                    f"factorization-{kind_f} at p={p} is exactly {want} "
                    f"ppermute hop(s) — got {len(coll_kinds)}"
                )
        else:
            return f"unknown strategy {strategy!r}"
        return None

    detail = _compose()
    if detail is not None:
        fail("composition", detail)

    # ---- conservation: movement re-derived from the spec geometry -----
    raw_total = int(quant["bytes_raw"]) if quant else moved

    def _expected_raw() -> Optional[int]:
        if strategy in ("noop", "local", "slice", "local-reshape"):
            return 0
        if strategy == "host-staging":
            # every pass streams the whole operand across pcie once
            # (twice with writeback) — the window partition must
            # conserve it exactly
            sg = d.get("staging") or {}
            return sum(
                size * item * (2 if pm.get("writeback") else 1)
                for pm in (sg.get("passes") or [])
            )
        if strategy in ("replicate", "gather-reshape"):
            return size * item * (p - 1) // p
        if strategy in ("all-to-all", "chunked-all-to-all") or (
            strategy == "hierarchical-a2a" and not is_reshape
        ):
            shape = list(gshape)
            shape[src] = _pad_extent(shape[src], p)
            shape[dst] = _pad_extent(shape[dst], p)
            L = _prod(shape) // p * item
            if strategy == "hierarchical-a2a":
                S, C = int(topo["n_slices"]), int(topo["chips_per_slice"])
                K = max(len(coll_kinds) // 2, 1)
                return (L * (C - 1) // C // K) * K + (L * (S - 1) // S // K) * K
            Cn = max(len(coll_kinds), 1)
            return (L * (p - 1) // p // Cn) * Cn
        if strategy == "ring":
            shape = list(gshape)
            shape[src] = _pad_extent(shape[src], p)
            shape[dst] = _pad_extent(shape[dst], p)
            L = _prod(shape) // p * item
            return (L // p) * (p - 1)
        if strategy in ("split0-pivot", "packed-pivot") or (
            strategy == "hierarchical-a2a" and is_reshape
        ):
            piv = kinds.index("reshape") if "reshape" in kinds else len(kinds)
            pos = [i for i, k in enumerate(kinds) if k in _COLLECTIVE_KINDS]
            n_in = sum(1 for i in pos if i < piv)
            n_out = len(pos) - n_in
            hier = strategy == "hierarchical-a2a"
            total = 0
            for n_stage, shape, axis in (
                (n_in, gshape, src),
                (n_out, out_shape, dst),
            ):
                if not n_stage:
                    continue
                L = _stage_local_bytes(shape, axis, p, item)
                if hier:
                    S, C = int(topo["n_slices"]), int(topo["chips_per_slice"])
                    K = max(n_stage // 2, 1)
                    total += (L * (C - 1) // C // K) * K + (L * (S - 1) // S // K) * K
                else:
                    total += (L * (p - 1) // p // n_stage) * n_stage
            return total
        if strategy.startswith("factorization-"):
            # recompute the ring payloads from the spec geometry exactly
            # as _factorization_plan prices them (norm-ring scalars ride
            # the real component's width on complex dtypes)
            kind_f = strategy[len("factorization-"):]
            rt = (
                item // 2
                if str(spec.get("dtype", "")).startswith("complex")
                else item
            )
            if kind_f == "polar":
                n_cols = gshape[1]
                mc = -(-n_cols // p)
                return (p - 1) * rt + 4 * (p - 1) * mc * n_cols * item
            nb = -(-gshape[0] // p)
            if kind_f == "cholesky":
                return p * (p - 1) * nb * nb * item
            if kind_f == "lu":
                n_pad = nb * p
                return p * (p - 1) * nb * nb * item + sum(
                    (p - 1) * nb * (n_pad - (k + 1) * nb) * item
                    for k in range(p - 1)
                )
            if kind_f in ("solve-chol", "solve-lu"):
                return 2 * (p - 1) ** 2 * nb * gshape[1] * item
        return None

    try:
        expected_raw = _expected_raw()
    except (TypeError, IndexError, KeyError, ZeroDivisionError) as e:
        expected_raw = None
        fail(
            "conservation",
            f"the spec geometry of strategy {strategy} is underivable "
            f"({type(e).__name__}: {e}) — spec and strategy disagree",
        )
    if expected_raw is not None and expected_raw != raw_total:
        fail(
            "conservation",
            f"strategy {strategy} over {spec} must move {expected_raw} raw "
            f"wire bytes per device — the plan records {raw_total}",
        )

    # ---- overlap-structure --------------------------------------------
    overlap = d.get("overlap")
    if overlap:
        if int(overlap.get("depth", 0)) != 2:
            fail("overlap-structure", f"unsupported pipeline depth {overlap.get('depth')}")
        groups = list(overlap.get("groups") or [])
        if not groups:
            fail("overlap-structure", "overlap annotation with no groups")
        seq_sum = sum(int(g.get("sequential_bytes", 0)) for g in groups)
        cp_sum = sum(int(g.get("critical_path_bytes", 0)) for g in groups)
        if int(overlap.get("sequential_bytes", -1)) != seq_sum:
            fail(
                "overlap-structure",
                f"annotation sequential_bytes={overlap.get('sequential_bytes')} "
                f"!= group sum {seq_sum}",
            )
        if int(overlap.get("critical_path_bytes", -1)) != cp_sum:
            fail(
                "overlap-structure",
                f"annotation critical_path_bytes="
                f"{overlap.get('critical_path_bytes')} != group sum {cp_sum}",
            )
        if cp_sum and abs(
            float(overlap.get("model_speedup", -1)) - round(seq_sum / cp_sum, 4)
        ) > 1e-9:
            fail(
                "overlap-structure",
                f"model_speedup={overlap.get('model_speedup')} != recomputed "
                f"{round(seq_sum / cp_sum, 4)}",
            )
        lap_mult = 2 if strategy == "hierarchical-a2a" else 1
        for g in groups:
            tag, laps = g.get("tag"), int(g.get("laps", 0))
            anchored = sum(
                1
                for st in steps
                if st.get("kind") in _COLLECTIVE_KINDS and st.get("overlap") == tag
            )
            if anchored != laps * lap_mult:
                fail(
                    "overlap-structure",
                    f"group {tag!r} models {laps} lap(s) but {anchored} "
                    f"collective step(s) carry the tag (expected "
                    f"{laps * lap_mult})",
                )
            wire, copy = int(g.get("wire_bytes", 0)), int(g.get("copy_bytes", 0))
            seq_g, cp_g = int(g.get("sequential_bytes", -1)), int(
                g.get("critical_path_bytes", -1)
            )
            if seq_g != wire + copy:
                fail(
                    "overlap-structure",
                    f"group {tag!r} sequential_bytes={seq_g} != wire+copy "
                    f"{wire + copy}",
                )
            if laps >= 2:
                if "ici_bytes" in g:
                    pen = int(g.get("dcn_penalty", 1))
                    ici, dcn = int(g.get("ici_bytes", 0)), int(g.get("dcn_bytes", 0))
                    if wire != ici + dcn * pen:
                        fail(
                            "overlap-structure",
                            f"tiered group {tag!r} wire_bytes={wire} != "
                            f"ici + dcn·penalty = {ici + dcn * pen}",
                        )
                    wi, wd, c = ici // laps, dcn * pen // laps, copy // laps
                    want_cp = wi + wd + c + (laps - 1) * max(wi, wd, c)
                else:
                    w, c = wire // laps, copy // laps
                    want_cp = w + (laps - 1) * max(w, c) + c
                if cp_g != want_cp:
                    fail(
                        "overlap-structure",
                        f"group {tag!r} critical_path_bytes={cp_g} != the "
                        f"depth-2 model {want_cp}",
                    )
                if cp_g >= seq_g:
                    fail(
                        "overlap-structure",
                        f"group {tag!r} models no gain (critical path "
                        f"{cp_g} >= sequential {seq_g}) — the planner drops "
                        "such groups",
                    )

    # ---- staging: the out-of-core window schedule (ISSUE 11) ----------
    staging = d.get("staging")
    stage_steps = [st for st in steps if st.get("kind") in _STAGING_KINDS]
    if stage_steps and not staging:
        fail(
            "staging",
            f"{len(stage_steps)} stage_in/stage_out step(s) but no "
            "schedule-level staging annotation",
        )
    if staging:
        if not stage_steps:
            fail("staging", "staging annotation present but no staging step")
        if int(staging.get("depth", 0)) != 2:
            fail("staging", f"unsupported staging depth {staging.get('depth')}")
        if int(staging.get("host_bytes", -1)) != size * item:
            fail(
                "staging",
                f"annotation host_bytes={staging.get('host_bytes')} != the "
                f"operand's {size * item} B",
            )
        if int(staging.get("slab_bytes", -1)) != budget:
            fail(
                "staging",
                f"annotation slab_bytes={staging.get('slab_bytes')} != the "
                f"schedule budget {budget} (the slab IS the staged budget)",
            )
        passes = list(staging.get("passes") or [])
        if not passes:
            fail("staging", "staging annotation with no passes")
        idx = 0
        max_window = 0
        pcie_total = 0
        for pm in passes:
            tag, n = pm.get("tag"), int(pm.get("n_windows", 0))
            wb = bool(pm.get("writeback"))
            per = 2 if wb else 1
            seg = stage_steps[idx : idx + n * per]
            idx += n * per
            if len(seg) != n * per:
                fail(
                    "staging",
                    f"pass {tag!r} declares {n} window(s) "
                    f"({'with' if wb else 'no'} writeback) but the step list "
                    "ran out — stage-in/stage-out pairing is broken",
                )
                break
            win_bytes: List[int] = []
            for k in range(n):
                si = seg[per * k]
                if si.get("kind") != "stage_in":
                    fail(
                        "staging",
                        f"pass {tag!r} window {k}: expected stage_in, got "
                        f"{si.get('kind')}",
                    )
                if wb:
                    so = seg[per * k + 1]
                    if so.get("kind") != "stage_out":
                        fail(
                            "staging",
                            f"pass {tag!r} window {k}: writeback pass must "
                            f"pair stage_in with stage_out, got {so.get('kind')}",
                        )
                    elif int(so.get("bytes_moved", -1)) != int(si.get("bytes_moved", 0)):
                        fail(
                            "staging",
                            f"pass {tag!r} window {k}: stage_out ships "
                            f"{so.get('bytes_moved')} B != the window's "
                            f"{si.get('bytes_moved')} B stage_in",
                        )
                win_bytes.append(int(si.get("bytes_moved", 0)))
            if sum(win_bytes) != size * item:
                fail(
                    "staging",
                    f"pass {tag!r} windows sum to {sum(win_bytes)} B != the "
                    f"operand's {size * item} B — window conservation broken",
                )
            if win_bytes and max(win_bytes) != int(pm.get("window_bytes", -1)):
                fail(
                    "staging",
                    f"pass {tag!r} annotation window_bytes="
                    f"{pm.get('window_bytes')} != max window {max(win_bytes)}",
                )
            if int(pm.get("pcie_bytes", -1)) != sum(win_bytes) * per:
                fail(
                    "staging",
                    f"pass {tag!r} annotation pcie_bytes={pm.get('pcie_bytes')} "
                    f"!= streamed total {sum(win_bytes) * per}",
                )
            # depth-2 slab occupancy: window k's transient is its own
            # bytes plus the prefetched window k+1
            for k in range(n):
                occ = win_bytes[k] + (win_bytes[k + 1] if k + 1 < n else 0)
                for st in seg[per * k : per * k + per]:
                    if int(st.get("peak_bytes", -1)) != occ:
                        fail(
                            "staging",
                            f"pass {tag!r} window {k}: recorded slab occupancy "
                            f"{st.get('peak_bytes')} B != depth-2 recompute "
                            f"{occ} B (this window + the prefetched next)",
                        )
            max_window = max(max_window, max(win_bytes or [0]))
            pcie_total += sum(win_bytes) * per
        if idx != len(stage_steps):
            fail(
                "staging",
                f"{len(stage_steps) - idx} staging step(s) not covered by "
                "any declared pass",
            )
        if int(staging.get("n_windows", -1)) != sum(
            int(pm.get("n_windows", 0)) for pm in passes
        ):
            fail(
                "staging",
                f"annotation n_windows={staging.get('n_windows')} != pass sum "
                f"{sum(int(pm.get('n_windows', 0)) for pm in passes)}",
            )
        if int(staging.get("window_bytes", -1)) != max_window:
            fail(
                "staging",
                f"annotation window_bytes={staging.get('window_bytes')} != "
                f"max window {max_window}",
            )
        # the slab peak must fit the hbm tier next to the resident
        # working set. The budget checked is the one RECORDED in the
        # annotation (the capacity the plan was sized against), so a
        # dumped plan's well-formedness is environment-independent —
        # `staging.prove_fits` re-checks the AMBIENT capacity at
        # execution time, where the current chip is what matters.
        from ..core import tiers as _tiers_mod

        resident = int(staging.get("resident_bytes", 0))
        if resident < 0:
            fail("staging", f"negative resident_bytes {resident}")
        hbm_cap = int(
            staging.get("hbm_capacity_bytes", _tiers_mod.capacity("hbm"))
        )
        if hbm_cap < 1:
            fail("staging", f"annotation hbm_capacity_bytes={hbm_cap} is not positive")
        if resident + recomputed_peak > hbm_cap:
            fail(
                "staging",
                f"staged working set {resident} B + slab peak "
                f"{recomputed_peak} B exceeds the recorded hbm capacity "
                f"{hbm_cap} B — the window schedule does not fit the chip "
                "it was sized for",
            )
        model = staging.get("model") or {}
        want_pcie_s = round(pcie_total / _tiers_mod.PCIE_BPS, 9)
        want_hbm_s = round(pcie_total / _tiers_mod.HBM_BPS, 9)
        n_total = sum(int(pm.get("n_windows", 0)) for pm in passes)
        seq_s = want_pcie_s + want_hbm_s
        cp_s = max(want_pcie_s, want_hbm_s) + min(want_pcie_s, want_hbm_s) / max(
            n_total, 1
        )
        for field, want in (
            ("pcie_s", want_pcie_s),
            ("hbm_s", want_hbm_s),
            ("sequential_s", round(seq_s, 9)),
            ("critical_path_s", round(cp_s, 9)),
            ("model_speedup", round(seq_s / cp_s, 4) if cp_s else 1.0),
            ("bound_gbps", round(pcie_total / cp_s / 1e9, 3) if cp_s else 0.0),
        ):
            if abs(float(model.get(field, -1)) - want) > 1e-6:
                fail(
                    "staging",
                    f"model {field}={model.get(field)} != the lattice "
                    f"recompute {want} (tiers.transfer_time arithmetic)",
                )

    # ---- progress: the collective-congruence replay (ISSUE 14) --------
    for _rule, defect in _progress_defects(d, steps, coll, p, strategy, topo):
        fail("progress", defect)

    # ---- tolerance: the error-bound recomputation (ISSUE 17) ----------
    for defect in _tolerance_defects(d, steps, quant, strategy, topo):
        fail("tolerance", defect)

    # ---- plan-id: the sha1 of the canonical serialization -------------
    if plan_id is not None:
        stripped = {k: v for k, v in d.items() if k != "plan_id"}
        canonical = json.dumps(stripped, sort_keys=True, separators=(",", ":"))
        want = hashlib.sha1(canonical.encode()).hexdigest()[:12]
        if want != plan_id:
            fail(
                "plan-id",
                f"plan_id {plan_id} != sha1 of the canonical serialization "
                f"({want}) — the plan was edited after stamping",
            )

    checks = [
        "step-kinds", "accounting", "quant-pairing", "tier-labels",
        "composition", "conservation", "overlap-structure", "staging",
        "progress", "tolerance", "plan-id",
    ]
    return {
        "ok": not violations,
        "plan_id": plan_id,
        "strategy": strategy,
        "checks": checks,
        "violations": [
            {"invariant": v.invariant, "detail": v.detail} for v in violations
        ],
    }


# --------------------------------------------------------------------- #
# the progress replay (ISSUE 14 — pass 5's dynamic half)                #
# --------------------------------------------------------------------- #
def _progress_defects(
    d: Dict[str, Any],
    steps: List[Dict[str, Any]],
    coll: List[Dict[str, Any]],
    p: int,
    strategy: str,
    topo: Optional[Dict[str, Any]],
) -> List[Tuple[str, str]]:
    """Symbolically replay one schedule per device and return every way
    it fails to make progress, as ``(rule, detail)`` pairs (SL502 for
    incongruent group structure, SL503 for issue-order defects; empty =
    every participant runs the plan to completion). Pure arithmetic over
    the plan dict — no mesh, no jax."""
    defects: List[Tuple[str, str]] = []

    # group congruence: every tiered collective's implied subgroup
    # structure must partition the mesh — the hierarchical ici half
    # rides S groups of C chips, the dcn half C groups of S same-index
    # chips; both partition iff S·C == p
    if topo is not None:
        S, C = int(topo.get("n_slices", 0)), int(topo.get("chips_per_slice", 0))
        if S * C != p or S < 2 or C < 1:
            defects.append((
                "SL502",
                f"group congruence broken: topology {S}x{C} does not "
                f"partition the {p}-device mesh — the subgroup collectives "
                "can never match across participants",
            ))

    # ring closure: after hop d every device holds the block of the
    # member d positions behind it; the ring closes iff the p-1 hops
    # deliver all p distinct offsets
    if strategy == "ring":
        hops = [st for st in steps if st.get("kind") == "ppermute"]
        delivered = {0} | {(k + 1) % p for k in range(len(hops))}
        if len(hops) != p - 1 or len(delivered) != p:
            defects.append((
                "SL502",
                f"ring does not close: {len(hops)} hop(s) deliver "
                f"{len(delivered)} of the {p} blocks — exactly p-1={p - 1} "
                "hops close the ring; any other count leaves a device "
                "waiting on a block that never arrives",
            ))

    # hierarchical lap pairing: each lap's intra-slice (ici) and
    # inter-slice (dcn) halves must carry the SAME chunk index — a
    # split pair means one tier's exchange consumes a lap the other
    # tier has not issued. Paired BY TIER LABEL, not raw step index, so
    # an untiered collective (a warmup gather, a tail flush) can never
    # shift the pairing frame and false-fail every following lap
    if strategy == "hierarchical-a2a":
        ici = [st for st in coll if st.get("tier") == "ici"]
        dcn = [st for st in coll if st.get("tier") == "dcn"]
        if len(ici) != len(dcn):
            defects.append((
                "SL502",
                f"hierarchical lap pairing broken: {len(ici)} intra-slice "
                f"(ici) half(s) vs {len(dcn)} inter-slice (dcn) half(s) — "
                "every lap's ici pivot needs exactly one dcn exchange",
            ))
        else:
            for k, (si, sd) in enumerate(zip(ici, dcn)):
                ci, cd = si.get("chunk"), sd.get("chunk")
                if ci != cd:
                    defects.append((
                        "SL502",
                        f"hierarchical lap pairing broken: intra-slice half "
                        f"of lap {k} carries chunk {ci!r} but its "
                        f"inter-slice half carries chunk {cd!r} — the dcn "
                        "exchange would consume a lap the ici pivot has not "
                        "issued",
                    ))
                    break

    # depth-2 lap replay: each overlap group's tagged laps must be
    # issued in exactly the order the double buffer consumes them
    # (consume of lap k-1 happens at issue of lap k: any gap, dup, or
    # reorder makes the consume slot read an unissued buffer)
    overlap = d.get("overlap")
    if overlap:
        lap_mult = 2 if strategy == "hierarchical-a2a" else 1
        for g in overlap.get("groups") or []:
            tag = g.get("tag")
            tagged = [
                st
                for st in steps
                if st.get("kind") in _COLLECTIVE_KINDS and st.get("overlap") == tag
            ]
            units = [
                tagged[i * lap_mult : (i + 1) * lap_mult]
                for i in range(len(tagged) // lap_mult)
            ]
            for i, unit in enumerate(units):
                chunks = {u.get("chunk") for u in unit}
                if len(chunks) > 1:
                    defects.append((
                        "SL503",
                        f"overlap group {tag!r} lap {i} spans chunks "
                        f"{sorted(chunks, key=repr)} — one lap unit must be "
                        "one chunk",
                    ))
            lap_chunks = [u[0].get("chunk") for u in units if u]
            if any(c is not None for c in lap_chunks):
                want = list(range(len(units)))
                if lap_chunks != want:
                    defects.append((
                        "SL503",
                        f"overlap group {tag!r} issues laps in chunk order "
                        f"{lap_chunks} — the depth-2 double buffer consumes "
                        f"lap k-1 at issue of lap k, so the order must be "
                        f"{want}; as recorded, a consume slot would read an "
                        "unissued lap",
                    ))
    return defects


# --------------------------------------------------------------------- #
# the tolerance recomputation (ISSUE 17 — pass 6's dynamic half)        #
# --------------------------------------------------------------------- #
def _wire_claim(detail: str) -> Optional[str]:
    """The codec mode a collective step's detail claims (the planner's
    ``" [<mode> wire]"`` suffix), or None for an exact-bit wire."""
    for m in ("int8", "bf16"):
        if detail.endswith(f" [{m} wire]"):
            return m
    return None


def _tolerance_defects(d, steps, quant, strategy, topo) -> List[str]:
    """Every tolerance-budget defect of one plan dict, step-named.

    The recomputation: each ``quantize`` step contributes the codec's
    pinned ``tolerance(mode)`` to the payload leg it encodes (the lossy
    rounding happens at encode — the collective ships the encoded bits
    verbatim and the dequantize is exact given them); every other step
    kind — slice/concat/pack/unpack/reshape relayouts, staging
    transfers, overlap bookkeeping — is an exact-bit copy contributing
    0.0. Payload legs are disjoint: a pipelined exchange encodes each
    ``(overlap, chunk)`` lap once, a ring encodes each positional hop
    block once, and in a hierarchical plan only the ``tier="dcn"``
    crossings carry a codec at all (the PR 8 policy — the ICI pivot
    ships exact). ``compose_tolerance`` over a leg therefore yields
    exactly ``tolerance(mode)``, and the end-to-end bound — the max
    over disjoint legs — must equal the schedule-level ``quant.tol``
    annotation (0.0 with no annotation). Cross-iteration accumulation
    is the DP optimizer's error-feedback contract (the f32 EF carry in
    optim/dp_optimizer.py — rule SL603 guards its dtype), not a plan
    property.
    """
    defects: List[str] = []
    q_idx = [k for k, st in enumerate(steps) if st.get("kind") == "quantize"]
    claiming = [
        k
        for k, st in enumerate(steps)
        if st.get("kind") in _COLLECTIVE_KINDS
        and _wire_claim(st.get("detail") or "")
    ]
    mode = (quant or {}).get("mode")
    if not quant:
        # exact-bit plan: no collective may claim an encoded wire (the
        # codec-step census itself is quant-pairing's invariant)
        for k in claiming:
            defects.append(
                f"step [{k}] ({steps[k].get('kind')}) claims an encoded "
                f"wire ('{_wire_claim(steps[k].get('detail') or '')} wire') "
                "but the plan declares no quant annotation — an undeclared "
                "lossy crossing has no tolerance budget"
            )
        return defects
    if mode not in ("int8", "bf16"):
        return defects  # quant-pairing owns the mode vocabulary

    from ..kernels import quant as _quant

    step_tol = float(_quant.tolerance(mode))
    try:
        declared = float(quant.get("tol"))
    except (TypeError, ValueError):
        defects.append(
            f"quant annotation tol={quant.get('tol')!r} is not a number"
        )
        return defects
    if declared != step_tol:
        defects.append(
            f"quant annotation tol={declared!r} != the {mode} codec's "
            f"pinned tolerance {step_tol!r} (kernels.quant.tolerance) — "
            "the declared budget does not match what the codec guarantees"
        )

    sandwiched: List[int] = []
    for k in q_idx:
        st = steps[k]
        det = st.get("detail") or ""
        if not det.startswith(f"{mode}-encode wire blocks"):
            defects.append(
                f"step [{k}] (quantize) detail {det[:40]!r}... does not "
                f"record a {mode} encode — the step's tolerance "
                "contribution cannot be attributed to the declared codec"
            )
        nxt = steps[k + 1] if k + 1 < len(steps) else None
        if nxt is None or nxt.get("kind") not in _COLLECTIVE_KINDS:
            continue  # the sandwich structure itself is quant-pairing's
        if (
            nxt.get("chunk") != st.get("chunk")
            or nxt.get("overlap") != st.get("overlap")
        ):
            defects.append(
                f"step [{k}] (quantize) encodes leg "
                f"(overlap={st.get('overlap')!r}, chunk={st.get('chunk')!r}) "
                f"but the collective it feeds, step [{k + 1}] "
                f"({nxt.get('kind')}), ships "
                f"(overlap={nxt.get('overlap')!r}, chunk={nxt.get('chunk')!r}) "
                "— the encoded payload and the wire crossing disagree, so "
                "the per-leg composition is unprovable"
            )
        sandwiched.append(k + 1)
        ndet = nxt.get("detail") or ""
        if _wire_claim(ndet) != mode:
            defects.append(
                f"step [{k + 1}] ({nxt.get('kind')}) rides between a "
                f"quantize/dequantize pair but does not claim the "
                f"'[{mode} wire]' — the encoded crossing is unattributed"
            )
        if topo is not None and strategy == "hierarchical-a2a" and nxt.get("tier") != "dcn":
            defects.append(
                f"step [{k + 1}] ({nxt.get('kind')}, tier="
                f"{nxt.get('tier')!r}) carries the codec in a hierarchical "
                "plan — the codec policy charges only dcn-tier legs (the "
                "ICI pivot ships exact-bit), so an encoded "
                f"{nxt.get('tier')!r} crossing spends tolerance the "
                "annotation never budgeted"
            )
        nxt2 = steps[k + 2] if k + 2 < len(steps) else None
        if nxt2 is not None and nxt2.get("kind") == "dequantize":
            ddet = nxt2.get("detail") or ""
            if not ddet.startswith(f"{mode}-decode"):
                defects.append(
                    f"step [{k + 2}] (dequantize) detail {ddet[:40]!r}... "
                    f"does not record a {mode} decode — the round-trip "
                    "this leg's tolerance bound prices is not the one "
                    "recorded"
                )

    for k in claiming:
        if k not in sandwiched:
            defects.append(
                f"step [{k}] ({steps[k].get('kind')}) claims an encoded "
                "wire but is not quantize/dequantize-sandwiched — a "
                "crossing outside the codec pairing carries no budgeted "
                "tolerance"
            )

    # ---- per-leg composition: each disjoint payload leg crosses the
    # codec once, so compose_tolerance over its encodes must equal the
    # per-crossing pin; the end-to-end bound is the max over legs
    legs: Dict[Any, List[float]] = {}
    for k in q_idx:
        st = steps[k]
        tag, chunk = st.get("overlap"), st.get("chunk")
        if chunk is not None:
            key = (tag, chunk)
        elif tag is not None:
            nxt = steps[k + 1] if k + 1 < len(steps) else {}
            if nxt.get("kind") == "ppermute":
                key = (tag, "hop", k)  # ring hops ship disjoint blocks
            else:
                key = (tag, None)
        else:
            key = ("solo", k)  # standalone sandwich = its own phase
        legs.setdefault(key, []).append(step_tol)
    for key in sorted(legs, key=repr):
        if len(legs[key]) > 1:
            tag, chunk = key[0], key[1]
            defects.append(
                f"payload leg (overlap={tag!r}, chunk={chunk!r}) is "
                f"encoded {len(legs[key])} times — its composed bound "
                f"{_quant.compose_tolerance(legs[key])!r} exceeds the "
                f"declared per-crossing budget {declared!r} (double-encode)"
            )
    composed = max(
        (_quant.compose_tolerance(tols) for tols in legs.values()),
        default=0.0,
    )
    if q_idx and not defects and composed != declared:
        defects.append(
            f"end-to-end composed bound {composed!r} != the declared "
            f"quant.tol {declared!r}"
        )
    return defects


def check_tolerance(plan) -> list:
    """The plan-side tolerance-budget check (pass 6's dynamic half),
    standalone: recompute one plan's end-to-end error bound from its
    recorded per-step tolerances and return an error-severity SL605
    finding per defect — empty means the composed bound provably equals
    the schedule-level ``quant.tol`` annotation (0.0 for exact-bit
    plans). The same recomputation gates ``verify_plan`` under the
    ``tolerance`` invariant; this entry point mirrors
    :func:`check_progress` so the golden-dump sweeps (and the
    Newton–Schulz / MPMD tolerance-budget consumers the ROADMAP names)
    can collect findings instead of catching exceptions."""
    from .findings import Finding

    d = _as_plan_dict(plan)
    steps = list(d.get("steps") or [])
    defects = _tolerance_defects(
        d, steps, d.get("quant"), d.get("strategy", ""), d.get("topology")
    )
    plan_id = d.get("plan_id")
    return [
        Finding("SL605", "error", f"plan {plan_id}: {defect}")
        for defect in defects
    ]


def check_progress(plan) -> list:
    """The plan-side collective-congruence check (pass 5's dynamic
    half), standalone: replay one Schedule (or plan dict / canonical
    JSON line) per device and return error-severity findings (SL502
    for incongruent group structure, SL503 for issue-order defects) for
    every progress defect — empty means every participant provably runs
    the plan to completion. The same replay gates ``verify_plan`` under the
    ``progress`` invariant; this entry point mirrors
    :func:`~heat_tpu.analysis.effectcheck.check_plan_protocol` so the
    golden-plan sweeps (and the future MPMD stage-graph verifier) can
    collect findings instead of catching exceptions."""
    from .findings import Finding

    d = _as_plan_dict(plan)
    steps = list(d.get("steps") or [])
    coll = [st for st in steps if st.get("kind") in _COLLECTIVE_KINDS]
    p = int((d.get("spec") or {}).get("mesh_size", 1))
    defects = _progress_defects(
        d, steps, coll, p, d.get("strategy", ""), d.get("topology")
    )
    plan_id = d.get("plan_id")
    return [
        Finding(rule, "error", f"plan {plan_id}: {defect}")
        for rule, defect in defects
    ]
