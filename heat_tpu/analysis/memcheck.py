"""Pass 3: memory lint — ``ht.analysis.memcheck(fn, *args)``.

shardlint's first two passes check WHAT a program launches (collectives,
host syncs) and what the tree looks like; this pass checks whether the
program FITS. It is a whole-program abstract interpreter over the jaxpr
(the same trace-to-one-program machinery as ``check`` and
``collective_counts``): every value gets a dataflow fact — per-device
local shard bytes, replication, dtype — propagated GSPMD-style
(arXiv:2105.04663: sharding is a per-value dataflow fact), a linear-scan
liveness analysis assigns each value a live range over a flattened
event timeline, and the maximum of live local bytes over program points
is the **static peak-HBM estimate per device**. Compile-only: nothing
executes, so the pass is cheap enough for tests, CI and serving
admission control.

The estimate is deliberately a *model*, cross-checked against the
compiler's own buffer assignment (``Compiled.memory_analysis()``, read
via ``core.jit.executable_memory_stats``) where the backend reports it
— tier-1 pins the model within 2x of XLA on the gated redistribution
programs. The rules:

========  ========  ====================================================
rule      severity  fires when
========  ========  ====================================================
SL301     error     the static peak estimate exceeds the per-device HBM
                    budget (``HEAT_TPU_HBM_BYTES``; default 16 GiB, the
                    v5e HBM) — the program cannot fit at dispatch, so
                    reject it at compile time (serving admission raises
                    the typed ``ServingOverloaded(reason="hbm-estimate")``
                    from the same number)
SL302     error     donation was DECLARED (``donate_argnums`` /
                    ``ht.jit`` bookkeeping) but the compiled
                    executable's ``input_output_aliases`` never reuse
                    the donated buffer — the donation was silently
                    dropped and both copies stay live in HBM. The
                    executable-level upgrade of SL105 ("should donate"),
                    sharing one donation resolver
                    (``analysis._donation``) with it
SL303     warning   a replicated value at least ``min_bytes`` large
                    stays live across >= 2 collective steps — a
                    per-device materialization whose residency the
                    redistribution planner's transient peak accounting
                    never sees
========  ========  ====================================================

The interpreter walks nested jaxprs (pjit / custom_* / shard_map
bodies). Inside ``shard_map`` the body avals ARE the per-device local
shapes, so bytes are taken at face value and ``in_specs``/``out_specs``
decide replication; outside, a value's local bytes are its global aval
bytes divided by its propagated sharding factor. ``scan``/``while``/
``cond`` bodies are scanned for collective events but treated as opaque
for liveness (their internals execute under their own transient
footprint; the carried values are accounted at the call site).
"""

from __future__ import annotations

import bisect
import os
import warnings

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from .findings import AnalysisReport, Finding

__all__ = ["DEFAULT_HBM_BYTES", "HBM_ENV", "hbm_budget_bytes", "memcheck"]

from ..core import tiers as _tiers

#: per-device HBM of the deployment target (v5e: 16 GiB) — the SL301
#: budget when ``HEAT_TPU_HBM_BYTES`` is unset. Since ISSUE 11 the
#: number is the ``hbm`` tier's capacity in the one memory-tier cost
#: lattice (``core.tiers``); aliased here for the established imports.
DEFAULT_HBM_BYTES = _tiers.DEFAULT_HBM_BYTES
HBM_ENV = _tiers.HBM_ENV

#: jaxpr primitives that launch a collective — the "steps" rule SL303
#: counts a replicated live range across.
_COLLECTIVE_PRIMS = frozenset(
    {
        "all_gather", "all_gather_invariant", "all_to_all", "pmax", "pmin",
        "ppermute", "psum", "psum2", "psum_scatter", "reduce_scatter",
    }
)

#: collectives whose RESULT is identical on every device of the group.
_REPLICATING_PRIMS = frozenset(
    {"all_gather", "all_gather_invariant", "pmax", "pmin", "psum", "psum2"}
)

_CALL_PARAM_KEYS = ("jaxpr", "call_jaxpr", "fun_jaxpr", "fwd_jaxpr_thunk")


def hbm_budget_bytes() -> int:
    """Per-device HBM budget for rule SL301 (``HEAT_TPU_HBM_BYTES``,
    default 16 GiB — the v5e chip): ``tiers.capacity("hbm")``, the hbm
    tier's capacity in the memory-tier lattice. One number shared with
    serving admission and the out-of-core staging slab ceiling."""
    return _tiers.capacity("hbm")


def _aval_bytes(aval) -> int:
    shape = getattr(aval, "shape", ())
    dtype = getattr(aval, "dtype", None)
    if dtype is None:
        return 0
    n = 1
    for s in shape:
        n *= int(s)
    try:
        item = np.dtype(dtype).itemsize
    except TypeError:
        # extended dtypes (PRNG keys): 4 bytes per 32-bit key word
        item = 4
    return n * item


def _closed_of(val):
    """The (raw) jaxprs a param value holds, if any."""
    out = []
    vals = val if isinstance(val, (list, tuple)) else (val,)
    for v in vals:
        inner = getattr(v, "jaxpr", None)
        if inner is not None and hasattr(v, "consts"):  # ClosedJaxpr
            out.append(inner)
        elif hasattr(v, "eqns"):  # raw Jaxpr
            out.append(v)
    return out


def _spec_is_replicated(spec) -> bool:
    """A shard_map in_specs/out_specs entry (a PartitionSpec) that names
    no mesh axis means the body sees (or produces) the full value on
    every device."""
    return all(e is None for e in spec)


class _Fact:
    """Per-value dataflow fact: local (per-device) bytes + replication."""

    __slots__ = ("local_bytes", "replicated")

    def __init__(self, local_bytes: int, replicated: bool):
        self.local_bytes = int(local_bytes)
        self.replicated = bool(replicated)


class _Interp:
    """One whole-program abstract interpretation: flat event timeline,
    per-value facts, born/last-use liveness."""

    def __init__(self, mesh_size: int):
        self.mesh_size = max(1, int(mesh_size))
        self.n_events = 0
        self.collective_events: List[int] = []
        self.facts: Dict[int, _Fact] = {}
        self.born: Dict[int, int] = {}
        self.last_use: Dict[int, int] = {}
        self.pinned: List[int] = []  # var ids live to program end
        # sub-jaxpr invars ALIAS the caller's buffers (a call passes a
        # reference, not a copy): canon maps a body var onto the outer
        # var's liveness record so nesting never double-counts a value
        self.canon: Dict[int, int] = {}

    # ------------------------------------------------------------------ #
    def _event(self, collective: bool = False) -> int:
        ev = self.n_events
        self.n_events += 1
        if collective:
            self.collective_events.append(ev)
        return ev

    def _vid(self, var) -> int:
        vid = id(var)
        while vid in self.canon:
            vid = self.canon[vid]
        return vid

    def _define(self, var, fact: _Fact, ev: int) -> None:
        vid = id(var)
        self.facts[vid] = fact
        self.born[vid] = ev
        self.last_use[vid] = ev

    def _bind(self, sub_var, outer_var, fallback: _Fact, ev: int) -> None:
        """Bind a body invar to the caller's buffer: alias when the
        outer var carries a fact, define fresh otherwise (literals)."""
        outer_vid = self._vid(outer_var) if outer_var is not None else None
        if outer_vid is not None and outer_vid in self.facts:
            self.canon[id(sub_var)] = outer_vid
            if ev > self.last_use[outer_vid]:
                self.last_use[outer_vid] = ev
        else:
            self._define(sub_var, fallback, ev)

    def _use(self, var, ev: int) -> None:
        vid = self._vid(var)
        if vid in self.facts and ev > self.last_use[vid]:
            self.last_use[vid] = ev

    def _fact_of(self, var) -> Optional[_Fact]:
        return self.facts.get(self._vid(var))

    # ------------------------------------------------------------------ #
    def run(
        self,
        jaxpr,
        in_facts: List[_Fact],
        local_avals: bool,
        bind_to: Optional[list] = None,
    ) -> List[_Fact]:
        """Interpret one (sub-)jaxpr; returns the outvar facts.
        ``local_avals``: inside a shard_map body, avals are already
        per-device local shapes (factor 1). ``bind_to``: the caller's
        invars this body's invars alias (same buffers, one liveness)."""
        ev0 = self._event()
        for k, (var, fact) in enumerate(zip(jaxpr.invars, in_facts)):
            outer = bind_to[k] if bind_to is not None and k < len(bind_to) else None
            self._bind(var, outer, fact, ev0)
        for eqn in jaxpr.eqns:
            self._eqn(eqn, local_avals)
        out = []
        ev_end = self._event()
        for var in jaxpr.outvars:
            fact = self._fact_of(var)
            if fact is None:  # Literal / constvar output
                fact = _Fact(_aval_bytes(getattr(var, "aval", None)), False)
            else:
                self._use(var, ev_end)
            out.append(fact)
        return out

    # ------------------------------------------------------------------ #
    def _eqn(self, eqn, local_avals: bool) -> None:
        name = eqn.primitive.name
        in_facts = [self._fact_of(v) for v in eqn.invars]
        array_facts = [f for f in in_facts if f is not None]

        if name == "shard_map":
            self._shard_map(eqn)
        elif name in ("jit", "closed_call", "core_call", "remat",
                      "checkpoint", "custom_jvp_call", "custom_vjp_call",
                      "custom_vjp_call_jaxpr"):
            self._call(eqn, local_avals)
        elif name in ("scan", "while", "cond"):
            # opaque for liveness; their bodies' collectives still count
            # as timeline steps so SL303 stays sound
            n_coll = 0
            for val in eqn.params.values():
                for sub in _closed_of(val):
                    n_coll += self._count_collectives(sub)
            for _ in range(n_coll):
                self._event(collective=True)
            self._default(eqn, local_avals, array_facts)
        else:
            self._default(eqn, local_avals, array_facts)

    def _default(self, eqn, local_avals: bool, array_facts) -> None:
        name = eqn.primitive.name
        ev = self._event(collective=name in _COLLECTIVE_PRIMS)
        for v in eqn.invars:
            self._use(v, ev)
        if name in _REPLICATING_PRIMS:
            replicated = self.mesh_size > 1
        elif name == "sharding_constraint":
            s = eqn.params.get("sharding")
            replicated = bool(getattr(s, "is_fully_replicated", False)) and self.mesh_size > 1
        elif name in ("all_to_all", "ppermute", "psum_scatter", "reduce_scatter"):
            replicated = False
        elif array_facts:
            replicated = all(f.replicated for f in array_facts)
        else:
            # literal-only producers (iota, scalar broadcasts): identical
            # by construction, not a materialized exchange product — never
            # SL303 candidates
            replicated = False
        for var in eqn.outvars:
            gb = _aval_bytes(getattr(var, "aval", None))
            if local_avals or replicated:
                local = gb
            else:
                local = gb // self.mesh_size
            self._define(var, _Fact(local, replicated), ev)

    def _call(self, eqn, local_avals: bool) -> None:
        sub = None
        for key in _CALL_PARAM_KEYS:
            if key in eqn.params:
                subs = _closed_of(eqn.params[key])
                if subs:
                    sub = subs[0]
                    break
        if sub is None:
            for val in eqn.params.values():
                subs = _closed_of(val)
                if subs:
                    sub = subs[0]
                    break
        in_facts = []
        for v, sv in zip(eqn.invars, getattr(sub, "invars", ())):
            f = self._fact_of(v)
            if f is None:
                gb = _aval_bytes(getattr(sv, "aval", None))
                f = _Fact(gb if local_avals else gb // self.mesh_size, False)
            in_facts.append(f)
        if sub is None or len(sub.invars) != len(eqn.invars):
            self._default(eqn, local_avals, [f for f in in_facts if f])
            return
        out_facts = self.run(sub, in_facts, local_avals, bind_to=list(eqn.invars))
        ev = self._event()
        for var, fact in zip(eqn.outvars, out_facts):
            self._define(var, fact, ev)

    def _shard_map(self, eqn) -> None:
        body = None
        for val in eqn.params.values():
            subs = _closed_of(val)
            if subs:
                body = subs[0]
                break
        in_names = eqn.params.get("in_specs") or ()
        out_names = eqn.params.get("out_specs") or ()
        if body is None or len(body.invars) != len(eqn.invars):
            self._default(eqn, False, [f for f in (self._fact_of(v) for v in eqn.invars) if f])
            return
        in_facts = []
        for k, sv in enumerate(body.invars):
            names = in_names[k] if k < len(in_names) else ()
            in_facts.append(
                _Fact(
                    _aval_bytes(getattr(sv, "aval", None)),  # body avals are LOCAL
                    _spec_is_replicated(names) and self.mesh_size > 1,
                )
            )
        out_facts = self.run(body, in_facts, local_avals=True, bind_to=list(eqn.invars))
        ev = self._event()
        for k, var in enumerate(eqn.outvars):
            names = out_names[k] if k < len(out_names) else ()
            local = (
                out_facts[k].local_bytes
                if k < len(out_facts)
                else _aval_bytes(getattr(var, "aval", None))
            )
            # a FRESH fact: for a passthrough output the body fact is the
            # canon-aliased CALLER fact — out_names describes this eqn's
            # result, and mutating the shared object would retroactively
            # rewrite the input value's replication flag
            self._define(
                var,
                _Fact(local, _spec_is_replicated(names) and self.mesh_size > 1),
                ev,
            )

    def _count_collectives(self, jaxpr) -> int:
        n = 0
        todo, seen = [jaxpr], set()
        while todo:
            jx = todo.pop()
            if id(jx) in seen:
                continue
            seen.add(id(jx))
            for eqn in jx.eqns:
                if eqn.primitive.name in _COLLECTIVE_PRIMS:
                    n += 1
                for val in eqn.params.values():
                    todo.extend(_closed_of(val))
        return n

    # ------------------------------------------------------------------ #
    def peak_bytes(self, baseline: int = 0) -> int:
        """Liveness peak: max over events of the summed live local bytes
        (plus ``baseline`` resident constant bytes)."""
        if not self.n_events:
            return baseline
        delta = [0] * (self.n_events + 1)
        pinned = set(self.pinned)
        for vid, fact in self.facts.items():
            if not fact.local_bytes:
                continue
            end = self.n_events - 1 if vid in pinned else self.last_use[vid]
            delta[self.born[vid]] += fact.local_bytes
            delta[end + 1] -= fact.local_bytes
        peak, live = 0, 0
        for d in delta:
            live += d
            peak = max(peak, live)
        return peak + baseline

    def replicated_live_ranges(self, min_bytes: int) -> List[Tuple[int, int, int]]:
        """(local_bytes, n_collectives_spanned, born_event) of every
        replicated value >= ``min_bytes`` whose live range spans >= 2
        collective steps — the SL303 candidates."""
        pinned = set(self.pinned)
        out = []
        for vid, fact in self.facts.items():
            if not fact.replicated or fact.local_bytes < min_bytes:
                continue
            b = self.born[vid]
            e = self.n_events - 1 if vid in pinned else self.last_use[vid]
            # collectives strictly after the value exists, up to its last use
            lo = bisect.bisect_right(self.collective_events, b)
            hi = bisect.bisect_right(self.collective_events, e)
            n = hi - lo
            if n >= 2:
                out.append((fact.local_bytes, n, b))
        out.sort(key=lambda t: (-t[0], t[2]))
        return out


def _input_facts(fn, args, kwargs, traced_in, mesh_size: int) -> List[_Fact]:
    """Facts for the flat traced inputs: DNDarray leaves carry their
    split (split ``None`` on a real mesh = replicated), jax arrays their
    placement sharding."""
    import jax

    from ..core.dndarray import DNDarray
    from ..core.jit import _is_leaf

    from ..sparse.dbcsr_matrix import DBCSR_matrix
    from ..sparse.dcsr_matrix import DCSR_matrix

    leaves, _ = jax.tree.flatten((args, kwargs), is_leaf=_is_leaf)
    facts = []
    for leaf in leaves:
        if isinstance(leaf, (DCSR_matrix, DBCSR_matrix)):
            # sparse operands price by their ACTUAL nnz-padded component
            # bytes (data + indices + metadata), never the dense shape —
            # a 1%-occupancy matrix would otherwise fail admission 100x
            # too early
            gb = int(leaf.component_nbytes)
            if leaf.split is None or leaf.comm.size <= 1:
                facts.append(_Fact(gb, leaf.comm.size > 1))
            else:
                facts.append(_Fact(gb // max(leaf.comm.size, 1), False))
        elif isinstance(leaf, DNDarray):
            phys = leaf._phys
            gb = int(np.prod(phys.shape, dtype=np.int64)) * np.dtype(phys.dtype).itemsize
            if leaf.split is None or leaf.comm.size <= 1:
                facts.append(_Fact(gb, leaf.comm.size > 1))
            else:
                facts.append(_Fact(gb // max(leaf.comm.size, 1), False))
        elif isinstance(leaf, jax.Array):
            gb = int(np.prod(leaf.shape, dtype=np.int64)) * np.dtype(leaf.dtype).itemsize
            try:
                sharding = leaf.sharding
                n_dev = len(sharding.device_set)
                replicated = bool(sharding.is_fully_replicated) and n_dev > 1
            except Exception:
                n_dev, replicated = 1, False
            if replicated or n_dev <= 1:
                facts.append(_Fact(gb, replicated or mesh_size > 1 and n_dev > 1))
            else:
                facts.append(_Fact(gb // n_dev, False))
    return facts[: len(traced_in)] if len(facts) > len(traced_in) else facts


def memcheck(
    fn,
    *args,
    hbm_bytes: Optional[int] = None,
    min_bytes: int = 1 << 20,
    donate_argnums: Optional[Tuple[int, ...]] = None,
    mesh=None,
    **kwargs,
) -> AnalysisReport:
    """Statically bound the per-device memory of ``fn(*args, **kwargs)``.

    ``fn`` may be a public heat_tpu function over DNDarrays, an
    ``ht.jit``-wrapped function, or an already-jitted jax callable (same
    contract as :func:`ht.analysis.check`). Compile-only — the program
    is traced and compiled exactly like a real dispatch (donation
    included), never executed.

    Parameters
    ----------
    hbm_bytes : per-device HBM budget for rule SL301; default the
        ``HEAT_TPU_HBM_BYTES`` env (v5e 16 GiB when unset).
    min_bytes : replicated values below this size never fire SL303.
    donate_argnums : positional args donated at dispatch time; defaults
        to the checked ``ht.jit`` wrapper's own bookkeeping (the shared
        resolver in ``analysis._donation`` — the same one SL105 uses).
    mesh : optional mesh, recorded in the report context.

    Returns an :class:`AnalysisReport` whose ``context`` carries
    ``static_peak_bytes`` (the liveness peak estimate per device),
    ``hbm_budget_bytes``, and — where the backend reports them — the
    compiler's own ``xla_*`` buffer-assignment numbers for cross-check.
    """
    import jax

    from ..core.jit import (
        executable_input_output_aliases,
        executable_memory_stats,
    )
    from ..observability.hlo import _build_traceable
    from ._donation import declared_donate_argnums, donated_leaf_positions
    # the ONE definition of "the program concretizes on the host" — shared
    # with pass 1 so both passes classify the same program identically
    from .ircheck import _trace_errors

    budget = hbm_budget_bytes() if hbm_bytes is None else max(1, int(hbm_bytes))
    findings: List[Finding] = []
    context: Dict[str, Any] = {
        "pass": "memcheck",
        "hbm_budget_bytes": int(budget),
        "min_bytes": int(min_bytes),
    }
    if mesh is not None:
        context["mesh_devices"] = int(np.asarray(mesh.devices).size)

    kind, target, traced_in = _build_traceable(fn, args, kwargs)
    donate_user = declared_donate_argnums(fn, donate_argnums)
    donate_positions: Tuple[int, ...] = ()
    try:
        with warnings.catch_warnings():
            # a dropped donation raises OUR finding (SL302), not jax's
            # "donated buffers were not usable" warning noise
            warnings.simplefilter("ignore")
            if kind == "lower":
                try:
                    closed = jax.make_jaxpr(target)(*args, **kwargs)
                except TypeError:
                    closed = target.trace(*args, **kwargs).jaxpr
                if donate_user:
                    # an EXPLICIT donate_argnums on an already-jitted fn:
                    # apply it through an outer jit (jax maps user argnums
                    # onto the flat parameters) so the compiled form — and
                    # therefore the SL302 alias check, the pinning, and
                    # the xla cross-check — is the donated program, not a
                    # silently undonated twin
                    donate_positions = donated_leaf_positions(
                        fn, args, kwargs, donate_argnums
                    )
                    try:
                        compiled = jax.jit(  # shardlint: ignore[SL202] -- compile-only analyzer lowering
                            target, donate_argnums=donate_user
                        ).lower(*args, **kwargs).compile()
                    except TypeError:
                        # static-arg jitted fns cannot be re-wrapped: fall
                        # back to the fn's own lowering, donation unchecked
                        donate_positions = ()
                        compiled = target.lower(*args, **kwargs).compile()
                else:
                    compiled = target.lower(*args, **kwargs).compile()
            else:
                if donate_user:
                    donate_positions = donated_leaf_positions(
                        fn, args, kwargs, donate_argnums
                    )
                closed = jax.make_jaxpr(target)(*traced_in)
                # compile-only lowering of the CHECKED program, donation
                # applied the way ht.jit would apply it at dispatch
                compiled = jax.jit(  # shardlint: ignore[SL202] -- compile-only analyzer lowering
                    target, donate_argnums=donate_positions
                ).lower(*traced_in).compile()
    except _trace_errors() as e:
        findings.append(
            Finding(
                "SL106",
                "error",
                "trace aborted: the program reads device VALUES on the host "
                f"(concretization) — {type(e).__name__}: {str(e).splitlines()[0]}",
            )
        )
        return AnalysisReport(findings, context)

    # mesh size: the DNDarray arguments' communicator, else the compiled
    # module's own partition count
    mesh_size = 1
    from ..core.dndarray import DNDarray

    leaves, _ = jax.tree.flatten((args, kwargs), is_leaf=lambda x: isinstance(x, DNDarray))
    for leaf in leaves:
        if isinstance(leaf, DNDarray):
            mesh_size = max(mesh_size, leaf.comm.size)
    if mesh_size == 1:
        import re as _re

        m = _re.search(r"num_partitions=(\d+)", compiled.as_text())
        if m:
            mesh_size = int(m.group(1))
    context["mesh_size"] = int(mesh_size)

    # ---- abstract interpretation + liveness ---------------------------
    interp = _Interp(mesh_size)
    if kind == "lower":
        in_facts = [
            _Fact(_aval_bytes(a) // mesh_size if mesh_size > 1 else _aval_bytes(a), False)
            for a in closed.in_avals
        ]
    else:
        in_facts = _input_facts(fn, args, kwargs, traced_in, mesh_size)
        if len(in_facts) != len(closed.jaxpr.invars):
            in_facts = [
                _Fact(_aval_bytes(getattr(v, "aval", None)) // max(mesh_size, 1), False)
                for v in closed.jaxpr.invars
            ]
    const_baseline = 0
    for c in getattr(closed, "consts", ()):
        shape = getattr(c, "shape", ())
        dtype = getattr(c, "dtype", None)
        if dtype is not None:
            const_baseline += int(np.prod(shape, dtype=np.int64)) * np.dtype(dtype).itemsize
    interp.run(closed.jaxpr, in_facts, local_avals=False)
    # arguments the caller did NOT donate stay resident for the whole
    # program (XLA's buffer assignment charges them end to end), and so
    # do the program outputs
    donated_set = set(donate_positions)
    for pos, var in enumerate(closed.jaxpr.invars):
        if pos not in donated_set:
            interp.pinned.append(id(var))
    for var in closed.jaxpr.outvars:
        if id(var) in interp.facts:
            interp.pinned.append(id(var))

    static_peak = interp.peak_bytes(baseline=const_baseline)
    context["static_peak_bytes"] = int(static_peak)
    context["n_events"] = interp.n_events
    context["n_collective_events"] = len(interp.collective_events)

    xla = executable_memory_stats(compiled)
    if xla is not None:
        context["xla_argument_bytes"] = xla["argument_bytes"]
        context["xla_output_bytes"] = xla["output_bytes"]
        context["xla_temp_bytes"] = xla["temp_bytes"]
        context["xla_alias_bytes"] = xla["alias_bytes"]
        context["xla_peak_bytes"] = xla["peak_bytes"]

    # ---- SL301: over the HBM budget ------------------------------------
    if static_peak > budget:
        xla_note = (
            f"; the compiler's own assignment says {xla['peak_bytes']} B"
            if xla is not None
            else ""
        )
        findings.append(
            Finding(
                "SL301",
                "error",
                f"static peak-HBM estimate {static_peak} B exceeds the "
                f"per-device budget {budget} B ({HBM_ENV}; v5e default "
                f"{DEFAULT_HBM_BYTES} B){xla_note} — the program cannot "
                "fit at dispatch; shrink the live set (donate inputs, "
                "stage through the redistribution planner) or raise the "
                "budget",
                nbytes=int(static_peak),
            )
        )

    # ---- SL302: donation declared but dropped by the executable --------
    if donate_user and donate_positions:
        aliased = {a["param_number"] for a in executable_input_output_aliases(compiled)}
        context["donated_params"] = list(donate_positions)
        context["aliased_params"] = sorted(aliased)
        for pos in donate_positions:
            if pos in aliased:
                continue
            aval = closed.in_avals[pos] if pos < len(closed.in_avals) else None
            nb = _aval_bytes(aval)
            shape = tuple(getattr(aval, "shape", ()))
            findings.append(
                Finding(
                    "SL302",
                    "error",
                    f"donation silently dropped: argument buffer {shape} "
                    f"(~{nb} B, parameter {pos}) was declared donated but "
                    "the compiled executable's input_output_aliases never "
                    "reuse it — both copies stay live in HBM while the "
                    "caller believes one was reclaimed (no output matches "
                    "its shape/dtype, or XLA could not alias it)",
                    nbytes=nb,
                )
            )

    # ---- SL303: replicated value live across >= 2 collective steps ----
    for local_bytes, n_coll, _born in interp.replicated_live_ranges(min_bytes)[:8]:
        findings.append(
            Finding(
                "SL303",
                "warning",
                f"replicated value (~{local_bytes} B per device) stays "
                f"live across {n_coll} collective steps — a per-device "
                "materialization the redistribution planner's transient "
                "peak accounting never sees; consume it before the "
                "collective chain, or keep it sharded and gather late",
                nbytes=int(local_bytes),
            )
        )

    findings.sort(key=lambda f: ({"error": 0, "warning": 1, "info": 2}[f.severity], f.rule))
    return AnalysisReport(findings, context)
