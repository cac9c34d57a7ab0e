"""Pass 5: commcheck — SPMD collective congruence & progress.

Heat's MPI heritage makes the *mismatched collective* the canonical
failure mode, and on TPU it is not an error but a silent hang: a
``psum`` issued under a predicate that differs across devices, a
``ppermute`` whose pairs leave one device waiting for a block that
never leaves, two subgroup collectives whose issue order differs
between participants — each deadlocks the mesh with nothing on stderr.
PR 13's resilience layer can only *detect* that hang at runtime (the
epoch fence turns it into a typed ``WorldChangedError``); this pass
proves the congruence statically, before any TPU minute is spent, over
the same traced/compiled programs the other passes inspect:

========  ========  ====================================================
rule      severity  fires when
========  ========  ====================================================
SL501     error     divergent-collective: a ``lax.cond``/``while``
                    whose body (transitively) launches a collective is
                    predicated on a value NOT provably replicated across
                    the shard_map body's devices — devices branch apart
                    and the collective never matches (a replication
                    lattice over the jaxpr decides: sharded inputs and
                    ``axis_index`` vary, full-axis ``psum``/
                    ``all_gather`` results are uniform, elementwise ops
                    preserve uniformity)
SL502     error     incomplete-permute: a compiled collective whose
                    group structure is incongruent — ``ppermute``
                    ``source_target_pairs`` that are not a permutation
                    of the axis group (duplicate source/target, ids off
                    the mesh, receivers that never send), or
                    ``replica_groups`` that do not partition the mesh —
                    some device waits forever. The library's documented
                    ring schedules (``boundaries.RING_SCHEDULE_MODULES``)
                    and plan-stamped programs downgrade to info via the
                    existing SL101 machinery
SL503     warn/err  collective-order divergence: two collectives whose
                    inter-device issue order can differ. Error on a
                    cross-group dependency CYCLE in the per-axis-group
                    channel graph (the branches of a divergent ``cond``
                    issue matched collectives in opposite orders);
                    warning on unordered INDEPENDENT collectives whose
                    group partitions partially overlap (the compiler may
                    schedule them differently per participant) — info
                    when plan-stamped (the executor's pipelined laps are
                    ordered by the lap chain)
SL504     warning   unfenced-entry: an executor/dispatcher entry point
                    (``FENCED_DISPATCH_MODULES``) that issues
                    collectives without the PR 13 ``WorldChangedError``
                    epoch-fence check reachable on entry — the lint that
                    keeps future entry points failing *typed* instead of
                    hanging on a re-resolved world
========  ========  ====================================================

The IR rules (SL501–SL503) are folded into :func:`ht.analysis.check`
and available standalone as :func:`ht.analysis.commcheck(fn, *args)
<commcheck>`; the source rule (SL504) rides ``scripts/lint.py --pass
commcheck|all``. The dynamic half — the ``progress`` invariant proving
every *Schedule-IR plan*'s collective steps congruent (rings close in
exactly p-1 hops, hierarchical ici/dcn pairs partition the mesh,
depth-2 lap tags never consume an unissued lap) — lives in
:func:`ht.analysis.check_progress` / ``verify_plan`` and is swept over
every golden plan dump in ci.sh. Together they are the verifier the
ROADMAP's MPMD pipeline item requires ("``verify_plan`` proving the
stage graph") — built now, over every program the repo already ships.
"""

from __future__ import annotations

import ast
import os
import re

from typing import Any, Dict, FrozenSet, List, Optional, Set, Tuple

from .findings import AnalysisReport, Finding
from .srclint import (
    _call_name,
    _iter_py_files,
    _pragmas_of,
    _suppressed,
    _Scope,
)

__all__ = [
    "FENCED_DISPATCH_MODULES",
    "commcheck",
    "lint_paths",
    "lint_source",
    "scan_hlo_congruence",
    "scan_jaxpr_divergence",
]


# --------------------------------------------------------------------- #
# the replication lattice (SL501 / SL503, jaxpr half)                   #
# --------------------------------------------------------------------- #
#: collectives whose FULL-AXIS result is identical on every participant
_UNIFORM_COLLECTIVES = frozenset(
    {"psum", "psum2", "pmax", "pmin", "all_gather", "all_gather_invariant"}
)
#: collectives whose result is per-device by construction
_VARYING_COLLECTIVES = frozenset(
    {"all_to_all", "ppermute", "psum_scatter", "reduce_scatter"}
)
_ALL_COLLECTIVES = _UNIFORM_COLLECTIVES | _VARYING_COLLECTIVES



def _is_literal(v) -> bool:
    return type(v).__name__ == "Literal"


def _sub_jaxprs(val):
    out = []
    vals = val if isinstance(val, (list, tuple)) else (val,)
    for v in vals:
        inner = getattr(v, "jaxpr", None)
        if inner is not None and hasattr(v, "consts"):  # ClosedJaxpr
            out.append(inner)
        elif hasattr(v, "eqns"):  # raw Jaxpr
            out.append(v)
    return out


def _count_collectives(jaxpr) -> int:
    n = 0
    todo, seen = [jaxpr], set()
    while todo:
        jx = todo.pop()
        if id(jx) in seen:
            continue
        seen.add(id(jx))
        for eqn in jx.eqns:
            if eqn.primitive.name in _ALL_COLLECTIVES:
                n += 1
            for val in eqn.params.values():
                todo.extend(_sub_jaxprs(val))
    return n


def _groups_key(eqn) -> Optional[Tuple[Tuple[int, ...], ...]]:
    """Canonical group partition of a collective eqn: tuples of device
    indices from ``axis_index_groups`` (``perm`` pairs for ppermute read
    as their participant set per +d class is NOT reconstructed — the
    pair list itself is the key), ``None`` for the full axis."""
    name = eqn.primitive.name
    if name == "ppermute":
        perm = eqn.params.get("perm")
        return tuple((int(s), int(t)) for s, t in perm) if perm else None
    groups = eqn.params.get("axis_index_groups")
    if not groups:
        return None
    return tuple(tuple(int(i) for i in g) for g in groups)


def _partial_overlap(ka, kb) -> bool:
    """Do two group partitions overlap without being identical on the
    overlap — the shape where per-participant issue order can differ?"""
    if ka == kb or (ka is None and kb is None):
        return False
    sa = [frozenset(g) for g in ka] if ka is not None else []
    sb = [frozenset(g) for g in kb] if kb is not None else []
    if ka is None:
        sa = [frozenset().union(*sb)]  # the full axis covers b's devices
    if kb is None:
        sb = [frozenset().union(*sa)]
    for ga in sa:
        for gb in sb:
            if ga & gb and ga != gb:
                return True
    return False


class _Coll:
    __slots__ = ("eqn", "key", "stamped")

    def __init__(self, eqn, key, stamped):
        self.eqn = eqn
        self.key = key
        self.stamped = stamped


def _eqn_stamped(eqn) -> bool:
    # the stamp spellings are DEFINED once, in boundaries.py, next to
    # the named_scope emitters — reusing them here keeps the jaxpr-side
    # downgrade in lockstep with the HLO-side SL101/SL102 downgrade
    from .boundaries import _CMATMUL_MARKER, _PLAN_MARKER

    try:
        stack = str(eqn.source_info.name_stack)
        return bool(_PLAN_MARKER.search(stack) or _CMATMUL_MARKER.search(stack))
    except Exception:
        return False


class _RepInterp:
    """Replication-lattice interpreter over one shard_map body (and its
    nested calls): per-value fact = "provably identical on every device
    of the body's mesh axis". Emits SL501/SL503 findings."""

    def __init__(self, findings: List[Finding], label: str, quiet: bool = False):
        self.findings = findings if not quiet else []
        self.label = label
        self.quiet = quiet

    def _flag(self, finding: Finding) -> None:
        if not self.quiet:
            self.findings.append(finding)

    def run(self, jaxpr, in_facts: List[bool]) -> List[bool]:
        facts: Dict[int, bool] = {}
        for var, f in zip(jaxpr.invars, in_facts):
            facts[id(var)] = bool(f)

        def get(v) -> bool:
            if _is_literal(v):
                return True
            return facts.get(id(v), True)  # constvars: baked-in, uniform

        colls: List[_Coll] = []
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            out_fact: Optional[bool] = None
            if name == "axis_index":
                out_fact = False  # the device-identity source
            elif name in _UNIFORM_COLLECTIVES:
                # full-axis reductions/gathers are uniform; grouped ones
                # are uniform only WITHIN their group — conservatively
                # varying across the mesh
                out_fact = not eqn.params.get("axis_index_groups")
                colls.append(_Coll(eqn, _groups_key(eqn), _eqn_stamped(eqn)))
            elif name in _VARYING_COLLECTIVES:
                out_fact = False
                colls.append(_Coll(eqn, _groups_key(eqn), _eqn_stamped(eqn)))
            elif name == "cond":
                self._cond(eqn, get, facts)
                continue
            elif name == "while":
                self._while(eqn, get, facts)
                continue
            elif name == "scan":
                self._scan(eqn, get, facts)
                continue
            elif name in ("jit", "closed_call", "core_call", "remat",
                          "checkpoint", "custom_jvp_call", "custom_vjp_call",
                          "custom_vjp_call_jaxpr"):
                sub = self._first_matching_sub(eqn)
                if sub is not None:
                    outs = self.run(sub, [get(v) for v in eqn.invars])
                    for var, f in zip(eqn.outvars, outs):
                        facts[id(var)] = f
                    continue
                out_fact = all(get(v) for v in eqn.invars)
            else:
                out_fact = all(get(v) for v in eqn.invars)
            for var in eqn.outvars:
                facts[id(var)] = bool(out_fact)

        self._order_divergence(jaxpr, colls)
        return [get(v) for v in jaxpr.outvars]

    # ------------------------------------------------------------------ #
    def _first_matching_sub(self, eqn):
        for val in eqn.params.values():
            for sub in _sub_jaxprs(val):
                if len(sub.invars) == len(eqn.invars):
                    return sub
        return None

    def _cond(self, eqn, get, facts) -> None:
        branches = [
            sub for val in (eqn.params.get("branches") or ()) for sub in _sub_jaxprs(val)
        ]
        pred_uniform = get(eqn.invars[0])
        op_facts = [get(v) for v in eqn.invars[1:]]
        n_coll = sum(_count_collectives(b) for b in branches)
        if n_coll and not pred_uniform:
            self._flag(
                Finding(
                    "SL501",
                    "error",
                    f"divergent collective{self._where()}: a cond/switch whose "
                    f"branches launch {n_coll} collective(s) is predicated on a "
                    "value not provably replicated across the shard_map devices "
                    "— devices branch apart and the collective never matches "
                    "(a silent hang on TPU). Make the predicate a full-axis "
                    "reduction (psum/pmax) of the local condition, or hoist "
                    "the collective out of the branch",
                    op="cond",
                )
            )
            # cross-group dependency cycle: matched collectives issued in
            # OPPOSITE orders by two branches — the per-axis-group channel
            # graph of the diverged mesh contains a cycle (A waits on B's
            # group, B waits on A's)
            sigs = []
            for b in branches:
                order = []
                todo = [b]
                while todo:
                    jx = todo.pop(0)
                    for beqn in jx.eqns:
                        if beqn.primitive.name in _ALL_COLLECTIVES:
                            order.append((beqn.primitive.name, _groups_key(beqn)))
                        for val in beqn.params.values():
                            todo.extend(_sub_jaxprs(val))
                sigs.append(order)
            reported = False
            for i in range(len(sigs)):
                for j in range(i + 1, len(sigs)):
                    if reported:
                        break
                    for x in sigs[i]:
                        for y in sigs[i]:
                            if x == y:
                                continue
                            if (
                                x in sigs[j]
                                and y in sigs[j]
                                and sigs[i].index(x) < sigs[i].index(y)
                                and sigs[j].index(x) > sigs[j].index(y)
                            ):
                                self._flag(
                                    Finding(
                                        "SL503",
                                        "error",
                                        f"collective-order divergence{self._where()}: "
                                        f"branches of a divergent cond issue {x[0]} "
                                        f"and {y[0]} in OPPOSITE orders — a "
                                        "cross-group dependency cycle in the "
                                        "per-axis-group channel graph: devices "
                                        "taking different branches each wait for "
                                        "the collective the other has not issued "
                                        "yet (deadlock)",
                                        op="cond",
                                    )
                                )
                                reported = True
                                break
                        if reported:
                            break
        # branch outputs: uniform only if the predicate is uniform AND
        # every branch produces a uniform value at that position
        branch_outs = [self.run(b, list(op_facts)) for b in branches] or [[]]
        for k, var in enumerate(eqn.outvars):
            per_branch = [outs[k] for outs in branch_outs if k < len(outs)]
            facts[id(var)] = bool(pred_uniform and per_branch and all(per_branch))

    def _while(self, eqn, get, facts) -> None:
        cn = int(eqn.params.get("cond_nconsts", 0))
        bn = int(eqn.params.get("body_nconsts", 0))
        cond_jx = (_sub_jaxprs(eqn.params.get("cond_jaxpr")) or [None])[0]
        body_jx = (_sub_jaxprs(eqn.params.get("body_jaxpr")) or [None])[0]
        cc = [get(v) for v in eqn.invars[:cn]]
        bc = [get(v) for v in eqn.invars[cn : cn + bn]]
        carry = [get(v) for v in eqn.invars[cn + bn :]]
        if body_jx is not None:
            probe = _RepInterp(self.findings, self.label, quiet=True)
            for _ in range(len(carry) + 2):  # monotone: falls only downward
                nxt = probe.run(body_jx, bc + carry)
                nxt = [a and b for a, b in zip(carry, nxt + carry[len(nxt) :])]
                if nxt == carry:
                    break
                carry = nxt
        pred_uniform = True
        if cond_jx is not None:
            probe = _RepInterp(self.findings, self.label, quiet=True)
            outs = probe.run(cond_jx, cc + carry)
            pred_uniform = bool(outs[0]) if outs else True
        n_coll = sum(_count_collectives(jx) for jx in (cond_jx, body_jx) if jx is not None)
        if n_coll and not pred_uniform:
            self._flag(
                Finding(
                    "SL501",
                    "error",
                    f"divergent collective{self._where()}: a while-loop whose "
                    f"body launches {n_coll} collective(s) has a continuation "
                    "predicate not provably replicated across the shard_map "
                    "devices — devices exit the loop on different iterations "
                    "and the next collective never matches (a silent hang on "
                    "TPU). Reduce the local condition with a full-axis "
                    "psum/pmax so every device agrees on the trip count",
                    op="while",
                )
            )
        if body_jx is not None:
            # final, finding-emitting pass over the stabilized facts
            self.run(body_jx, bc + carry)
        # a divergent predicate means per-device trip counts: even a
        # uniformity-preserving carry (a loop counter) diverges
        for var, f in zip(eqn.outvars, carry + [True] * len(eqn.outvars)):
            facts[id(var)] = bool(f and pred_uniform)

    def _scan(self, eqn, get, facts) -> None:
        sub = (_sub_jaxprs(eqn.params.get("jaxpr")) or [None])[0]
        if sub is None:
            for var in eqn.outvars:
                facts[id(var)] = all(get(v) for v in eqn.invars)
            return
        nc = int(eqn.params.get("num_consts", 0))
        ncar = int(eqn.params.get("num_carry", 0))
        consts = [get(v) for v in eqn.invars[:nc]]
        carry = [get(v) for v in eqn.invars[nc : nc + ncar]]
        xs = [get(v) for v in eqn.invars[nc + ncar :]]
        probe = _RepInterp(self.findings, self.label, quiet=True)
        for _ in range(ncar + 2):
            outs = probe.run(sub, consts + carry + xs)
            nxt = [a and b for a, b in zip(carry, outs[:ncar])]
            if nxt == carry:
                break
            carry = nxt
        outs = self.run(sub, consts + carry + xs)  # findings pass
        ys = outs[ncar:]
        for k, var in enumerate(eqn.outvars):
            facts[id(var)] = bool(outs[k]) if k < ncar else bool(
                ys[k - ncar] if k - ncar < len(ys) else True
            )

    # ------------------------------------------------------------------ #
    def _order_divergence(self, jaxpr, colls: List[_Coll]) -> None:
        """SL503, straight-line arm: two INDEPENDENT collectives of this
        body whose group partitions partially overlap — the compiler is
        free to schedule them in different orders on different
        participants. Dependence is the dataflow closure within this
        jaxpr (conservative: an unreachable producer means independent)."""
        if len(colls) < 2:
            return
        producers = {}
        for idx, eqn in enumerate(jaxpr.eqns):
            for ov in eqn.outvars:
                producers[id(ov)] = (idx, eqn)
        pos = {id(c.eqn): k for k, c in enumerate(colls)}

        def depends(b_eqn, a_eqn) -> bool:
            stack = [v for v in b_eqn.invars if not _is_literal(v)]
            seen: Set[int] = set()
            while stack:
                v = stack.pop()
                if id(v) in seen:
                    continue
                seen.add(id(v))
                hit = producers.get(id(v))
                if hit is None:
                    continue
                _, src = hit
                if src is a_eqn:
                    return True
                stack.extend(u for u in src.invars if not _is_literal(u))
            return False

        reported: Set[Tuple] = set()
        for i in range(len(colls)):
            for j in range(i + 1, len(colls)):
                a, b = colls[i], colls[j]
                if not _partial_overlap(a.key, b.key):
                    continue
                if depends(b.eqn, a.eqn):
                    continue
                sig = (a.eqn.primitive.name, a.key, b.eqn.primitive.name, b.key)
                if sig in reported:
                    continue
                reported.add(sig)
                severity = "info" if (a.stamped or b.stamped) else "warning"
                blessing = (
                    " (plan-stamped: the executor's lap chain orders them)"
                    if severity == "info"
                    else ""
                )
                self._flag(
                    Finding(
                        "SL503",
                        severity,
                        f"collective-order divergence{self._where()}: independent "
                        f"{a.eqn.primitive.name} and {b.eqn.primitive.name} ride "
                        "PARTIALLY overlapping group partitions with no dataflow "
                        "ordering between them — participants shared by unequal "
                        "groups may observe the two collectives in different "
                        "issue orders; sequence them explicitly (dataflow or "
                        f"optimization_barrier) or align their groups{blessing}",
                        op=b.eqn.primitive.name,
                    )
                )

    def _where(self) -> str:
        return f" in {self.label}" if self.label else ""


def scan_jaxpr_divergence(closed, label: str = "") -> List[Finding]:
    """Rules SL501/SL503 over one (closed) jaxpr: find every
    ``shard_map`` body — the level where per-device values and explicit
    collectives live — and run the replication-lattice interpreter over
    it. Outside shard_map the partitioner keeps control flow globally
    consistent, so only manual SPMD bodies are candidates. Returns
    findings (empty = congruent)."""
    jaxpr = getattr(closed, "jaxpr", closed)
    findings: List[Finding] = []
    todo, seen = [jaxpr], set()
    while todo:
        jx = todo.pop()
        if id(jx) in seen:
            continue
        seen.add(id(jx))
        for eqn in jx.eqns:
            if eqn.primitive.name == "shard_map":
                body = None
                for val in eqn.params.values():
                    subs = _sub_jaxprs(val)
                    if subs:
                        body = subs[0]
                        break
                if body is None:
                    continue
                # replicated <=> the operand's PartitionSpec names no mesh axis
                in_specs = eqn.params.get("in_specs") or ()
                in_facts = [
                    all(e is None for e in (in_specs[k] if k < len(in_specs) else ()))
                    for k in range(len(body.invars))
                ]
                _RepInterp(findings, label).run(body, in_facts)
                todo.append(body)  # nested shard_maps still walked
            else:
                for val in eqn.params.values():
                    todo.extend(_sub_jaxprs(val))
    return findings


# --------------------------------------------------------------------- #
# SL502 — group congruence of the compiled collectives (HLO half)       #
# --------------------------------------------------------------------- #
def scan_hlo_congruence(text: str) -> List[Finding]:
    """Rule SL502 over one compiled module's text: every collective
    line's group structure must be congruent — ``source_target_pairs`` a
    permutation of the axis group, ``replica_groups`` a partition of the
    mesh (``num_partitions``). Ring-module and plan-stamped lines
    downgrade to info through the same ``boundaries`` machinery SL101
    uses; everything else is an error — the incongruent collective is a
    hang, not a wrong answer."""
    from ..observability.hlo import _COLLECTIVE_LINE, _shaped_bytes
    from ._groups import (
        parse_replica_groups,
        parse_source_target_pairs,
        partition_defect,
        permutation_defect,
    )
    from .boundaries import planned_reshard_plan_id, ring_schedule_module

    findings: List[Finding] = []
    m_parts = re.search(r"num_partitions=(\d+)", text)
    n_dev = int(m_parts.group(1)) if m_parts else None
    seen: Set[Tuple[str, str, bool]] = set()
    for m in _COLLECTIVE_LINE.finditer(text):
        ssa, result_type, op = m.group(1), m.group(2), m.group(3)
        line_end = text.find("\n", m.end())
        full_line = text[m.start() : len(text) if line_end == -1 else line_end]
        if op == "collective-permute":
            pairs = parse_source_target_pairs(full_line)
            defect = permutation_defect(pairs, n_dev) if pairs else None
        else:
            grps = parse_replica_groups(full_line)
            defect = partition_defect(grps, n_dev) if grps else None
        if defect is None:
            continue
        stamp = planned_reshard_plan_id(full_line)
        blessed = ring_schedule_module(full_line, text)
        # dedup WITHIN a severity class only — a blessed/stamped line
        # must never mask a later hand-rolled hang with the same defect
        key = (op, defect, bool(stamp or blessed))
        if key in seen:
            continue
        seen.add(key)
        nbytes = _shaped_bytes(result_type)
        if stamp or blessed:
            kind = "plan-stamped schedule" if stamp else "documented ring schedule"
            findings.append(
                Finding(
                    "SL502",
                    "info",
                    f"incongruent-looking {op} in a {kind} "
                    f"({stamp or blessed}): {defect} — the module's own "
                    "block rotation/exchange; verified by its plan "
                    "contract, reported for the audit trail",
                    op=op,
                    nbytes=nbytes,
                )
            )
            continue
        findings.append(
            Finding(
                "SL502",
                "error",
                f"incomplete permute/partition: {op} ({ssa}, ~{nbytes} B) — "
                f"{defect}. On TPU this is a silent hang: the unmatched "
                "device waits forever. Close the ring "
                "(kernels.cmatmul.grouped_ring_perm builds complete grouped "
                "permutations) or make the groups partition the mesh",
                op=op,
                nbytes=nbytes,
            )
        )
    return findings


# --------------------------------------------------------------------- #
# the standalone pass runner (SL501-SL503, IR half)                     #
# --------------------------------------------------------------------- #
def commcheck(fn, *args, mesh=None, **kwargs) -> AnalysisReport:
    """Statically prove the collective congruence of the program
    ``fn(*args, **kwargs)`` compiles to (same argument contract as
    :func:`ht.analysis.check`; compile-only, nothing executes). Runs the
    SL501/SL503 replication-lattice walk over the jaxpr and the SL502
    group-congruence scan over the compiled HLO. The same scans are
    folded into :func:`ht.analysis.check`; this entry point runs pass 5
    alone (cheaper, and the report context carries the pass name the
    MPMD stage-graph annotation will consume)."""
    import numpy as np

    from ..observability.hlo import _count_ops
    from .ircheck import _lower_checked

    findings: List[Finding] = []
    context: Dict[str, Any] = {"pass": "commcheck"}
    if mesh is not None:
        context["mesh_devices"] = int(np.asarray(mesh.devices).size)

    lowered = _lower_checked(fn, args, kwargs, findings)
    if lowered is None:
        return AnalysisReport(findings, context)
    closed, compiled = lowered

    label = getattr(fn, "__name__", "") or ""
    findings += scan_jaxpr_divergence(closed, label=label)
    text = compiled.as_text()
    context["collective_counts"] = {k: v for k, v in _count_ops(text).items() if v}
    findings += scan_hlo_congruence(text)
    findings.sort(key=lambda f: ({"error": 0, "warning": 1, "info": 2}[f.severity], f.rule))
    return AnalysisReport(findings, context)


# --------------------------------------------------------------------- #
# SL504 — unfenced dispatch entry (source half)                         #
# --------------------------------------------------------------------- #
#: the executor/dispatcher layer — modules whose entry points issue
#: collectives on behalf of callers and must therefore carry the PR 13
#: epoch fence (``elastic.check_world``/``check_epoch``) on every entry
#: path: a dispatch racing a world re-resolution fails TYPED instead of
#: hanging on devices that are gone. Scoped, like PLANNER_MODULES — a
#: public library op (``ht.sum``) is not a dispatch entry; the executor
#: fences for it. tests pin the population.
FENCED_DISPATCH_MODULES: Tuple[str, ...] = (
    "redistribution/executor.py",
    "serving/dispatcher.py",
)

#: the fence spellings the rule recognizes (resilience/elastic.py)
_FENCE_NAMES: FrozenSet[str] = frozenset({"check_world", "check_epoch"})

#: lax collective launchers — reaching one of these means the closure
#: issues mesh collectives directly
_LAUNCH_ATTRS: FrozenSet[str] = frozenset(
    {"all_to_all", "ppermute", "psum", "all_gather", "psum_scatter",
     "pmax", "pmin", "reduce_scatter"}
)


def _issues_collectives(fn_node: ast.AST) -> bool:
    """Does a function body contain a collective ISSUE SITE: a lax
    collective launch, a compiled-program invocation (the executor's
    ``_*_program(...)(phys)`` shape), or a program-table dispatch (the
    serving ``self.programs[bucket](...)`` shape)?"""
    for node in ast.walk(fn_node):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if isinstance(f, ast.Attribute) and f.attr in _LAUNCH_ATTRS:
            return True
        if isinstance(f, ast.Call) and _call_name(f.func).endswith("_program"):
            return True
        if isinstance(f, ast.Subscript):
            base = f.value
            name = base.attr if isinstance(base, ast.Attribute) else getattr(base, "id", "")
            if name == "programs":
                return True
    return False


def _fences(fn_node: ast.AST) -> bool:
    return any(
        isinstance(node, ast.Call) and _call_name(node.func) in _FENCE_NAMES
        for node in ast.walk(fn_node)
    )


def _closure_nodes(
    root_name: str,
    mod_fns: Dict[str, ast.FunctionDef],
    methods: Optional[Dict[str, ast.FunctionDef]] = None,
) -> List[ast.FunctionDef]:
    """The intra-module call closure of one entry: bare-name calls onto
    module functions plus ``self.m(...)`` edges within the class — the
    same reachability SL402 uses."""
    start = (methods or {}).get(root_name) or mod_fns.get(root_name)
    if start is None:
        return []
    out: List[ast.FunctionDef] = []
    seen: Set[str] = {root_name}
    todo = [start]
    while todo:
        cur = todo.pop()
        out.append(cur)
        for node in ast.walk(cur):
            if not isinstance(node, ast.Call):
                continue
            callee = None
            if isinstance(node.func, ast.Name) and node.func.id in mod_fns:
                callee = mod_fns[node.func.id]
                key = node.func.id
            elif (
                methods
                and isinstance(node.func, ast.Attribute)
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id == "self"
                and node.func.attr in methods
            ):
                callee = methods[node.func.attr]
                key = node.func.attr
            if callee is not None and key not in seen:
                seen.add(key)
                todo.append(callee)
    return out


def lint_source(src: str, rel: str) -> List[Finding]:
    """Rule SL504 over one module (only :data:`FENCED_DISPATCH_MODULES`
    are in scope): every ENTRY — a public module-level function, a
    public method, or a worker-thread root — whose intra-module closure
    issues collectives must reach an epoch-fence call in that closure."""
    rel = rel.replace("\\", "/")
    if not any(rel.endswith(sfx) for sfx in FENCED_DISPATCH_MODULES):
        return []
    try:
        tree = ast.parse(src)
    except SyntaxError as e:
        return [Finding("SL201", "error", f"unparseable module: {e}", path=rel, line=e.lineno)]
    pragmas = _pragmas_of(src)
    findings: List[Finding] = []
    mod_fns = {n.name: n for n in tree.body if isinstance(n, ast.FunctionDef)}

    def check_entry(name: str, node: ast.FunctionDef, methods=None, cls=None) -> None:
        closure = _closure_nodes(name, mod_fns, methods)
        if not closure:
            return
        if not any(_issues_collectives(fn) for fn in closure):
            return
        if any(_fences(fn) for fn in closure):
            return
        stack = (cls.name, name) if cls is not None else (name,)
        lines = (cls.lineno, node.lineno) if cls is not None else (node.lineno,)
        scope = _Scope(stack, lines)
        if _suppressed("SL504", node.lineno, scope, pragmas):
            return
        where = ".".join(stack)
        findings.append(
            Finding(
                "SL504",
                "warning",
                f"unfenced dispatch entry {where!r}: this executor/dispatcher "
                "path issues collectives with no WorldChangedError epoch-fence "
                "(elastic.check_world / check_epoch) reachable on entry — work "
                "dispatched across a world re-resolution hangs on devices that "
                "are gone instead of failing typed. Fence the entry (see "
                "redistribution/executor.execute), or declare the design with "
                "`# shardlint: ignore[SL504] -- reason`",
                path=rel,
                line=node.lineno,
            )
        )

    for name, node in mod_fns.items():
        if not name.startswith("_"):
            check_entry(name, node)
    for cls in [n for n in tree.body if isinstance(n, ast.ClassDef)]:
        methods = {n.name: n for n in cls.body if isinstance(n, ast.FunctionDef)}
        worker_roots: Set[str] = set()
        for m in methods.values():
            for node in ast.walk(m):
                if isinstance(node, ast.Call) and _call_name(node.func) == "Thread":
                    for kw in node.keywords:
                        if kw.arg == "target" and isinstance(kw.value, ast.Attribute):
                            if (
                                isinstance(kw.value.value, ast.Name)
                                and kw.value.value.id == "self"
                                and kw.value.attr in methods
                            ):
                                worker_roots.add(kw.value.attr)
        for name, node in methods.items():
            public = not name.startswith("_") and name != "__init__"
            if public or name in worker_roots:
                check_entry(name, node, methods=methods, cls=cls)
    findings.sort(key=lambda f: (f.path or "", f.line or 0, f.rule))
    return findings


def lint_paths(paths, root: Optional[str] = None) -> AnalysisReport:
    """Pass 5's source half over every ``.py`` file under ``paths`` (the
    commcheck face of ``scripts/lint.py``)."""
    root = os.path.abspath(root or os.getcwd())
    findings: List[Finding] = []
    n_files = 0
    for path in paths:
        for fp in _iter_py_files(path):
            n_files += 1
            rel = os.path.relpath(os.path.abspath(fp), root).replace(os.sep, "/")
            # only the fenced-dispatch modules are in scope — skipping
            # the rest BEFORE open() keeps `--pass all` from paying a
            # third full-tree read for a two-module rule
            if not any(rel.endswith(sfx) for sfx in FENCED_DISPATCH_MODULES):
                continue
            with open(fp, encoding="utf-8") as f:
                src = f.read()
            findings += lint_source(src, rel)
    return AnalysisReport(findings, context={"files": n_files, "pass": "commcheck"})
