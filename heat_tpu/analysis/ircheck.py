"""Pass 1: IR lint — ``ht.analysis.check(fn, *args)``.

Traces and compiles ``fn`` for the example arguments exactly the way a
real dispatch would (the :func:`~heat_tpu.observability.hlo` machinery:
DNDarray leaves feed physical arrays, metadata rebuilds at trace time),
then walks the jaxpr and the compiled StableHLO and emits structured
:class:`~heat_tpu.analysis.findings.Finding`\\ s. Nothing executes on
device — the whole pass is compile-only, cheap enough for tests and CI.

The point (arxiv 2112.01075, arxiv 2112.09017): reshard cost is a
static property of source/target shardings, and TPU-scale linear
algebra lives or dies on every intermediate staying distributed — both
are checkable *here*, before any TPU minute is spent. The rules:

========  ========  ====================================================
rule      severity  fires when
========  ========  ====================================================
SL101     warn/err  an all-to-all (or a hand-rolled collective-permute
                    chain hop) moves ≥ ``min_bytes`` (err when it moves
                    ≥ ``replicate_frac`` of the largest input)
SL102     warn/err  an all-gather materializes ≥ ``min_bytes`` (same
                    escalation — a full-operand gather is an error)
SL103     warning   an all-gather result feeds a ``reduce``
SL104     warning   an inexact value widens past core/types.py
                    promotion of the program inputs; its NARROWING arm
                    (error) fires when an unscaled float→int8 cast
                    feeds a collective — the sanctioned dtype narrowing
                    is the stamped block-quantized wire codec
                    (``heat_tpu.kernels.quant``), which downgrades to
                    info
SL105     warning   an output aliases an argument's aval but the buffer
                    is not donated (cross-checked against ht.jit's
                    donation bookkeeping)
SL106     error     the program syncs the host (seen in source, or the
                    trace aborts on a concretization error); ambiguous
                    ``int()``/``float()`` casts report as warnings
SL107     warn/err  cross-tier collective not decomposed (ISSUE 8): at
                    a two-tier topology, a FLAT collective whose
                    replica groups (or ppermute source-target pairs)
                    span slices moves ≥ ``min_bytes`` across DCN — the
                    whole payload completes at the slow tier. The
                    sanctioned forms are the planner's
                    ``hierarchical-a2a`` programs and the hierarchical
                    DP wire, whose stamped collectives (and the
                    library's documented ring schedules) downgrade to
                    info. Evaluated only when a tiered topology is in
                    effect (``topology=`` arg or ``HEAT_TPU_TOPOLOGY``).
========  ========  ====================================================

The contracts the repo already pins stay clean by construction: TSQR's
one p·K² R-stack all-gather and ring attention's two ppermutes sit far
under ``min_bytes`` at any sane K, and the hSVD level-0 sketch compiles
to zero collectives.
"""

from __future__ import annotations

import re

from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from .findings import AnalysisReport, Finding

__all__ = ["check"]



def _nbytes(shape, dtype) -> int:
    n = 1
    for s in shape:
        n *= int(s)
    return n * np.dtype(dtype).itemsize


# ONE dtype vocabulary (analysis/_dtypes.py, ISSUE 17) shared with
# numcheck's SL601-SL603 precision rules — the widening/narrowing
# classification of a cast is decided in exactly one place
from ._dtypes import effective_itemsize as _effective_itemsize
from ._dtypes import (
    INT8_DTYPES as _INT8_DTYPES,
    lossy_narrowing as _lossy_narrowing,
    promotion_ceiling as _promotion_ceiling,
    widens_past as _widens_past,
)


def _walk_jaxprs(jaxpr):
    """Yield every eqn of ``jaxpr`` and its nested sub-jaxprs (pjit /
    scan / cond / shard_map bodies)."""
    from jax.extend import core as jex_core

    todo = [jaxpr]
    seen = set()
    while todo:
        jx = todo.pop()
        if id(jx) in seen:
            continue
        seen.add(id(jx))
        for eqn in jx.eqns:
            yield eqn
            for val in eqn.params.values():
                for sub in _as_jaxprs(val, jex_core):
                    todo.append(sub)


def _as_jaxprs(val, jex_core):
    out = []
    vals = val if isinstance(val, (list, tuple)) else (val,)
    for v in vals:
        closed = getattr(v, "jaxpr", None)
        if closed is not None and hasattr(v, "consts"):  # ClosedJaxpr
            out.append(closed)
        elif hasattr(v, "eqns"):  # raw Jaxpr
            out.append(v)
    return out


def _trace_errors():
    import jax

    return (
        jax.errors.ConcretizationTypeError,
        jax.errors.TracerArrayConversionError,
        jax.errors.TracerBoolConversionError,
        jax.errors.TracerIntegerConversionError,
    )


def _lower_checked(fn, args, kwargs, findings: List[Finding]):
    """Trace and compile-only lower the checked program — the ONE
    definition of the trace-abort contract shared by every pass entry
    (``check``, ``commcheck``): a host-read abort appends an SL106
    finding and returns ``None``, so the entry points can never drift
    on which malformed programs produce a report instead of a raise.
    Returns ``(closed_jaxpr, compiled)`` on success."""
    import jax

    from ..observability.hlo import _build_traceable

    kind, target, traced_in = _build_traceable(fn, args, kwargs)
    try:
        if kind == "lower":
            try:
                closed = jax.make_jaxpr(target)(*args, **kwargs)
            except TypeError:
                # make_jaxpr traces EVERY argument; a jitted fn with
                # static (non-array) args needs the AOT trace, which
                # respects the jit's own static_argnums
                closed = target.trace(*args, **kwargs).jaxpr
            compiled = target.lower(*args, **kwargs).compile()
        else:
            closed = jax.make_jaxpr(target)(*traced_in)
            # compile-only lowering of the CHECKED program — never
            # dispatched, so ht.jit's hooks have nothing to observe here
            compiled = jax.jit(target).lower(*traced_in).compile()  # shardlint: ignore[SL202]
    except _trace_errors() as e:
        findings.append(
            Finding(
                "SL106",
                "error",
                "trace aborted: the program reads device VALUES on the host "
                f"(concretization) — {type(e).__name__}: {str(e).splitlines()[0]}",
            )
        )
        return None
    except TypeError as e:
        if "ht.jit" in str(e) and "host" in str(e):
            findings.append(
                Finding("SL106", "error", f"trace aborted by a host read: {e}")
            )
            return None
        raise
    return closed, compiled


# ONE parser (analysis/_groups.py, ISSUE 14) shared with commcheck's
# SL502/SL503 congruence rules — the cross-tier and the incongruent
# verdicts can never disagree about what the same HLO line says
from ._groups import parse_groups as _parse_groups


def check(
    fn: Callable,
    *args,
    mesh=None,
    min_bytes: int = 1 << 20,
    replicate_frac: float = 0.5,
    donate_argnums: Optional[Tuple[int, ...]] = None,
    scan_source: bool = True,
    topology=None,
    **kwargs,
) -> AnalysisReport:
    """Statically analyze the program ``fn(*args, **kwargs)`` compiles to.

    ``fn`` may be a public heat_tpu function over DNDarrays, an
    ``ht.jit``-wrapped function, or an already-jitted jax callable; the
    arguments are example inputs fixing shapes/shardings (same contract
    as :func:`ht.observability.collective_counts`). Compile-only.

    Parameters
    ----------
    mesh : optional ``jax.sharding.Mesh`` the program is meant for —
        recorded in the report context (DNDarray arguments already carry
        their mesh via their communicator).
    min_bytes : collectives moving less than this are structural, not
        findings (default 1 MiB — TSQR's R-stack gather passes clean).
    replicate_frac : an all-gather/all-to-all moving at least this
        fraction of the largest input escalates to ``error``.
    donate_argnums : positional args whose buffers the caller donates at
        dispatch time; defaults to the checked ``ht.jit`` wrapper's own
        donation bookkeeping when present.
    scan_source : also scan ``fn``'s source for host syncs hiding in
        untaken branches (rule SL106).
    topology : two-tier topology override for rule SL107 (``"SxC"``
        string, ``core.communication.Topology``, or ``(S, C)`` tuple);
        the default ``None`` resolves the ambient ``HEAT_TPU_TOPOLOGY``
        per collective (flat topologies never fire the rule).

    Returns an :class:`AnalysisReport`; ``report.ok`` is False iff an
    error-severity finding gates.
    """
    import jax

    from ..observability.hlo import (
        _COLLECTIVE_LINE,
        _count_ops,
        _shaped_bytes,
    )

    findings: List[Finding] = []
    context: Dict[str, Any] = {"pass": "ircheck", "min_bytes": int(min_bytes)}
    if mesh is not None:
        context["mesh_devices"] = int(np.asarray(mesh.devices).size)

    if scan_source:
        from .srclint import scan_program_source

        findings += scan_program_source(fn)

    lowered = _lower_checked(fn, args, kwargs, findings)
    if lowered is None:
        return AnalysisReport(findings, context)
    closed, compiled = lowered

    # ---- SL401: use-after-donate (pass 4 folded into the IR check) ----
    from .effectcheck import scan_jaxpr_donation

    findings += scan_jaxpr_donation(
        closed, label=getattr(fn, "__name__", "") or ""
    )

    in_avals = [(tuple(a.shape), str(a.dtype)) for a in closed.in_avals]
    out_avals = [(tuple(a.shape), str(a.dtype)) for a in closed.out_avals]
    in_bytes = [_nbytes(s, d) for s, d in in_avals]
    max_in = max(in_bytes, default=0)
    context["max_input_bytes"] = int(max_in)
    err_bytes = max(int(min_bytes), int(replicate_frac * max_in))

    text = compiled.as_text()
    context["collective_counts"] = {k: v for k, v in _count_ops(text).items() if v}

    # ---- SL501-SL503: collective congruence (pass 5 folded in) --------
    from .commcheck import scan_hlo_congruence, scan_jaxpr_divergence

    _label = getattr(fn, "__name__", "") or ""
    findings += scan_jaxpr_divergence(closed, label=_label)
    findings += scan_hlo_congruence(text)

    # ---- SL601-SL603: precision flow (pass 6 folded in) ---------------
    # SL604 (f64 under x64-off) stays standalone-only: it is a SOURCE
    # rule a jaxpr cannot witness, and folding it would re-flag every
    # sanctioned widening fixture SL104 already prices
    from .numcheck import fn_pragmas, scan_jaxpr_precision

    findings += scan_jaxpr_precision(
        closed, label=_label, pragmas=fn_pragmas(fn)
    )

    # ---- SL101 / SL102: large resharding collectives -------------------
    from .boundaries import (
        planned_reshard_plan_id,
        ring_schedule_module,
        wire_codec_stamped,
    )

    gather_names: List[Tuple[str, int]] = []
    for m in _COLLECTIVE_LINE.finditer(text):
        ssa, result_type, op = m.group(1), m.group(2), m.group(3)
        nbytes = _shaped_bytes(result_type)
        if op == "all-gather":
            gather_names.append((ssa, nbytes))
        if op not in ("all-to-all", "all-gather", "collective-permute") or nbytes < min_bytes:
            continue
        rule = "SL102" if op == "all-gather" else "SL101"
        # planner-issued reshards (redistribution/executor.py programs run
        # under jax.named_scope("redist_plan_<id>") — including ISSUE 6's
        # software-pipelined ppermute chains — and the collective-matmul
        # rings under jax.named_scope("cmatmul_ring_<tag>"), stamping the
        # marker into the instruction's op_name metadata) are the
        # budgeted, cost-modeled movement itself — report them at info
        # severity with the stamp attached instead of flagging the
        # subsystems' own schedules (see boundaries.PLANNER_MODULES)
        line_end = text.find("\n", m.end())
        full_line = text[m.start() : len(text) if line_end == -1 else line_end]
        plan_id = planned_reshard_plan_id(full_line)
        if plan_id is not None:
            if plan_id.startswith("cmatmul:"):
                msg = (
                    f"planned collective-matmul movement ({plan_id}): {op} "
                    f"moves ~{nbytes} B ({ssa}) inside a stamped "
                    "heat_tpu.kernels.cmatmul ring — the decomposed "
                    "gather/reduction of the linalg overlap forms "
                    "(HEAT_TPU_REDIST_OVERLAP)"
                )
            else:
                msg = (
                    f"planned reshard (redist plan {plan_id}): {op} moves "
                    f"~{nbytes} B ({ssa}) under the redistribution "
                    "planner's peak-memory budget — inspect with "
                    "ht.redistribution.explain"
                )
            findings.append(Finding(rule, "info", msg, op=op, nbytes=nbytes))
            continue
        if op == "collective-permute":
            # the library's own DOCUMENTED ring schedules (sort
            # networks, halo exchange, ring attention) rotate blocks by
            # design — info, keyed on source_file since shard_map bodies
            # carry no stampable named scope. Hand-rolled loops in user
            # code still fall through to full severity.
            blessed = ring_schedule_module(full_line, text)
            if blessed is not None:
                findings.append(
                    Finding(
                        rule,
                        "info",
                        f"ring schedule ({blessed}): a collective-permute "
                        f"hop ships ~{nbytes} B ({ssa}) — the documented "
                        "block rotation of the library's own distributed "
                        "algorithm, not a relayout accident",
                        op=op,
                        nbytes=nbytes,
                    )
                )
                continue
        severity = "error" if nbytes >= err_bytes else "warning"
        what = {
            "all-to-all": "implicit reshard: an all-to-all relayouts",
            "collective-permute": (
                "implicit reshard: a hand-rolled collective-permute hop ships"
            ),
            "all-gather": "replicated materialization: an all-gather assembles",
        }[op]
        findings.append(
            Finding(
                rule,
                severity,
                f"{what} ~{nbytes} B ({ssa}); largest input is {max_in} B — "
                "align the operand's split with the op (resplit once, "
                "upstream, or keep the intermediate distributed)",
                op=op,
                nbytes=nbytes,
            )
        )

    # ---- SL107: cross-tier collective not decomposed (ISSUE 8) ---------
    # at a tiered topology, a flat collective whose replica groups span
    # slices pushes its WHOLE payload across DCN — the planner's
    # hierarchical-a2a (intra-slice pivot + inter-slice exchange) is the
    # decomposed form; its stamped programs (and the hierarchical DP
    # wire) report at info, as do the library's documented ring
    # schedules. The mesh size comes from the compiled module's own
    # num_partitions (a subgroup collective's ids can omit the top
    # devices, so max-id+1 would mis-resolve the topology and silently
    # skip genuinely DCN-crossing subgroup exchanges); max-id+1 is only
    # the fallback when the header is absent.
    from ..core import communication as _communication

    _num_parts = re.search(r"num_partitions=(\d+)", text)
    _module_n_dev = int(_num_parts.group(1)) if _num_parts else 0

    def _sl107_topology(n_dev: int):
        if topology is None:
            return _communication.topology_for(n_dev)
        return _communication.topology_for(n_dev, topology)

    for m in _COLLECTIVE_LINE.finditer(text):
        ssa, result_type, op = m.group(1), m.group(2), m.group(3)
        nbytes = _shaped_bytes(result_type)
        if nbytes < min_bytes:
            continue
        line_end = text.find("\n", m.end())
        full_line = text[m.start() : len(text) if line_end == -1 else line_end]
        grps = _parse_groups(full_line)
        if not grps:
            continue
        n_dev = _module_n_dev or (max((i for g in grps for i in g), default=-1) + 1)
        topo = _sl107_topology(n_dev)
        if not topo.tiered:
            continue
        if op == "collective-permute":
            spanning = any(len(g) >= 2 and topo.crosses(g[0], g[1]) for g in grps)
        else:
            spanning = any(topo.spans(g) for g in grps)
        if not spanning:
            continue
        plan_id = planned_reshard_plan_id(full_line)
        if plan_id is None and wire_codec_stamped(full_line):
            plan_id = "wire-codec"
        if plan_id is not None:
            findings.append(
                Finding(
                    "SL107",
                    "info",
                    f"planned cross-tier movement ({plan_id}): {op} crosses "
                    f"slices at {topo} with ~{nbytes} B ({ssa}) — the "
                    "decomposed/budgeted DCN hop itself (hierarchical-a2a "
                    "ships pre-packed per-slice rows; inspect with "
                    "ht.redistribution.explain)",
                    op=op,
                    nbytes=nbytes,
                )
            )
            continue
        blessed = ring_schedule_module(full_line, text)
        if blessed is not None:
            findings.append(
                Finding(
                    "SL107",
                    "info",
                    f"documented ring schedule ({blessed}) crosses slices at "
                    f"{topo}: a {op} ships ~{nbytes} B over DCN on the "
                    "wraparound edges — the algorithm's block rotation, "
                    "priced (not flagged) at the tier penalty",
                    op=op,
                    nbytes=nbytes,
                )
            )
            continue
        severity = "error" if nbytes >= err_bytes else "warning"
        findings.append(
            Finding(
                "SL107",
                severity,
                f"cross-tier collective not decomposed: a flat {op} whose "
                f"replica groups span slices at {topo} moves ~{nbytes} B — "
                "every byte completes at DCN speed (~8x ICI). Decompose it "
                "hierarchically: intra-slice pivot + inter-slice exchange "
                "(the redistribution planner's hierarchical-a2a, or "
                "kernels.quant.hierarchical_allreduce_sum for gradient "
                "all-reduces)",
                op=op,
                nbytes=nbytes,
            )
        )

    # ---- SL103: all-gather feeding a reduction -------------------------
    # consumer shapes differ by backend: a direct `reduce(`, the CPU
    # `reduce-window` ladder, or a `call` into a %parallel_reduce-*
    # computation — all carry a "reduce" token on the consuming line.
    # metadata={op_name=...} trailers are stripped first: a consumer whose
    # source location merely MENTIONS reduce is not a reduction, and a
    # gather already feeding reduce-scatter needs no reduce-scatter advice
    lines = [ln.split(" metadata=")[0] for ln in text.splitlines()]
    for ssa, nbytes in gather_names:
        operand = re.compile(re.escape(ssa) + r"(?![\w.\-])")
        for line in lines:
            if "reduce" not in line or "all-reduce" in line or "reduce-scatter" in line:
                continue
            lhs = line.strip().removeprefix("ROOT ").startswith(ssa)
            if not lhs and operand.search(line):
                findings.append(
                    Finding(
                        "SL103",
                        "warning",
                        f"all-gather result {ssa} (~{nbytes} B) feeds a "
                        "reduction — a reduce-scatter (or local reduce + "
                        "small all-reduce) moves O(1/p) of the bytes",
                        op="all-gather",
                        nbytes=nbytes,
                    )
                )
                break

    # ---- SL104: dtype widening beyond input promotion ------------------
    ceiling = _promotion_ceiling(d for _, d in in_avals)
    seen_widen = set()
    for eqn in _walk_jaxprs(closed.jaxpr):
        if eqn.primitive.name != "convert_element_type":
            continue
        src_dt = np.dtype(eqn.invars[0].aval.dtype)
        dst_dt = np.dtype(eqn.params.get("new_dtype"))
        if _widens_past(src_dt, dst_dt, ceiling) and (src_dt.name, dst_dt.name) not in seen_widen:
            seen_widen.add((src_dt.name, dst_dt.name))
            findings.append(
                Finding(
                    "SL104",
                    "warning",
                    f"dtype widening {src_dt.name} -> {dst_dt.name}: wider "
                    "than core/types.py promotion of any input "
                    f"(ceiling {ceiling * 8}-bit) — likely an accidental "
                    "64-bit constant or astype",
                    op="convert_element_type",
                )
            )

    # ---- SL104 (narrowing arm): float->int8 feeding a collective -------
    # an UNSCALED astype(int8) before a psum/all-to-all truncates the
    # payload and wraps the reduction — the accident gradient
    # compression invites. The sanctioned narrowing is the
    # block-quantized wire codec (kernels/quant.py), whose encode/decode
    # bodies run under jax.named_scope("wire_codec_<mode>"): the stamp
    # rides the eqn's name_stack, and stamped converts report at info
    # (wire_codec_stamped imported with the SL101 boundary helpers).
    from jax.extend import core as jex_core

    collective_prims = {
        "psum", "all_to_all", "all_gather", "ppermute", "pmax", "pmin",
        "psum_scatter", "reduce_scatter",
    }
    passthrough_prims = {
        "concatenate", "reshape", "transpose", "squeeze", "broadcast_in_dim",
        "slice", "dynamic_slice", "pad", "rev", "select_n", "copy",
        # jnp.where/clip/round wrap their select/round bodies in nested
        # pjit eqns: the outer walk continues through the pjit's OWN
        # invars (the operands), which is exactly the dataflow step
        "jit", "custom_jvp_call", "custom_vjp_call",
    }
    seen_narrow = set()
    # ONE producer map over every (sub-)jaxpr: vars are unique objects,
    # so the map lets the backward walk cross call boundaries — a
    # convert hiding inside a nested pjit is reached by stepping from
    # the pjit eqn onto its sub-jaxpr's OUTVARS (the value the outer
    # program actually consumes), not just its outer operands.
    producers = {}
    collective_eqns = []
    todo_jx, seen_jx = [closed.jaxpr], set()
    while todo_jx:
        jx = todo_jx.pop()
        if id(jx) in seen_jx:
            continue
        seen_jx.add(id(jx))
        for eqn in jx.eqns:
            for ov in eqn.outvars:
                producers[id(ov)] = eqn
            if eqn.primitive.name in collective_prims:
                collective_eqns.append(eqn)
            for val in eqn.params.values():
                todo_jx.extend(_as_jaxprs(val, jex_core))

    def _sub_outvar_for(eqn, v):
        """The sub-jaxpr outvar that PRODUCES the outer var ``v`` of a
        call eqn (pjit/custom_*): call outvars map 1:1 onto the
        sub-jaxpr's outvars by position, so only the index-matched one
        continues the walk — a sibling output of the same jit wrapper
        is not on the collective's dataflow path."""
        try:
            idx = next(i for i, ov in enumerate(eqn.outvars) if ov is v)
        except StopIteration:
            return []
        out = []
        for val in eqn.params.values():
            for sub in _as_jaxprs(val, jex_core):
                outvars = getattr(sub, "jaxpr", sub).outvars
                if idx < len(outvars):
                    out.append(outvars[idx])
        return out

    for eqn in collective_eqns:
        stack = [(v, 0) for v in eqn.invars]
        visited = set()
        while stack:
            v, depth = stack.pop()
            if depth > 12 or isinstance(v, jex_core.Literal) or id(v) in visited:
                continue
            visited.add(id(v))
            src = producers.get(id(v))
            if src is None:
                continue
            name = src.primitive.name
            if name == "convert_element_type":
                src_dt = np.dtype(src.invars[0].aval.dtype)
                dst_dt = np.dtype(src.params.get("new_dtype"))
                if _lossy_narrowing(src_dt, dst_dt):
                    stamped = wire_codec_stamped(str(src.source_info.name_stack))
                    dkey = (src_dt.name, dst_dt.name, eqn.primitive.name, stamped)
                    if dkey in seen_narrow:
                        continue
                    seen_narrow.add(dkey)
                    if stamped:
                        findings.append(
                            Finding(
                                "SL104",
                                "info",
                                f"sanctioned wire-codec narrowing: {src_dt.name} "
                                f"-> {dst_dt.name} feeds a {eqn.primitive.name} "
                                "inside a wire_codec-stamped encode "
                                "(heat_tpu.kernels.quant) — the block-quantized "
                                "collective payload, scale per tile",
                                op="convert_element_type",
                            )
                        )
                    else:
                        findings.append(
                            Finding(
                                "SL104",
                                "error",
                                f"lossy dtype narrowing {src_dt.name} -> "
                                f"{dst_dt.name} feeds a {eqn.primitive.name}: an "
                                "unscaled astype before a collective truncates "
                                "the payload (int8 sums wrap) — use the "
                                "block-quantized wire codec "
                                "(heat_tpu.kernels.quant) or ship full width",
                                op="convert_element_type",
                            )
                        )
                continue  # a convert ends the walk either way
            if name in passthrough_prims:
                stack.extend((u, depth + 1) for u in src.invars)
                # a call primitive's RESULT is produced by its
                # sub-jaxpr's outvars: step inside (index-matched) so a
                # convert hiding in a nested jit wrapper is reached,
                # while the wrapper's unrelated sibling outputs are not
                if name in ("jit", "custom_jvp_call", "custom_vjp_call"):
                    stack.extend((u, depth + 1) for u in _sub_outvar_for(src, v))

    # ---- SL105: aliasable output not donated ---------------------------
    # with explicit donation bookkeeping the per-aval check below is the
    # authority (a PARTIALLY donated program still has missed donations to
    # report); only without it does module-level aliasing mean "the caller
    # already donated through raw jax.jit" and silence the rule. The
    # donation resolver is SHARED with memcheck's SL302 (analysis._donation)
    # so "should donate" and "donation dropped" can never disagree about
    # what was declared.
    from ._donation import donated_avals as _donated_avals_shared

    donated = _donated_avals_shared(fn, args, donate_argnums)
    have_bookkeeping = bool(donated) or donate_argnums is not None
    if have_bookkeeping or "input_output_alias" not in text:
        in_set = set(in_avals)
        flagged = set()
        for shape, dtype in out_avals:
            aval = (shape, dtype)
            nbytes = _nbytes(shape, dtype)
            if (
                nbytes >= min_bytes
                and aval in in_set
                and aval not in donated
                and aval not in flagged
            ):
                flagged.add(aval)
                findings.append(
                    Finding(
                        "SL105",
                        "warning",
                        f"an output of shape {shape} {dtype} (~{nbytes} B) "
                        "aliases an argument's aval but the buffer is not "
                        "donated — pass donate_argnums to ht.jit so the "
                        "pipeline reuses the input HBM",
                        nbytes=nbytes,
                    )
                )

    findings.sort(key=lambda f: ({"error": 0, "warning": 1, "info": 2}[f.severity], f.rule))
    return AnalysisReport(findings, context)
