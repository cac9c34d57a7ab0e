"""The env-gate registry — every ``HEAT_TPU_*`` switch declared ONCE.

Since PR 4 every subsystem has shipped behind an environment gate
(kernel dispatch, planner routing, overlap issue order, wire codec,
topology, out-of-core staging, serving AOT, telemetry, capacity
overrides), and every PR since 5 has carried the same review line: "the
gate is a component of every plan/program/AOT cache key". That
convention was enforced BY HAND at 60+ read sites — and the PR 9/10
hardening lists were dominated by exactly the omission class it guards
against: a cache key missing one gate component silently serves a stale
compiled program, the worst failure mode a serving stack can have.

This module makes the convention *provable*:

- every gate is declared once, as a :class:`GateSpec` — name, legal
  values, default, whether its value changes the PROGRAMS the library
  builds (``affects_programs``), which cache layers must key on it
  (``scopes``: ``plan`` / ``program`` / ``aot``), and the conventional
  parameter names its resolved value travels under (``key_params`` —
  what the SL402 staleness rule checks cache keys against);
- :func:`get` is the ONE ``os.environ`` read site for gates in the
  whole tree — rule SL403 (``heat_tpu.analysis.effectcheck``) makes a
  raw ``os.environ`` read of a ``HEAT_TPU_*`` name an error-severity
  finding anywhere outside this module;
- the AOT cache's gate stamp set DERIVES from the registry
  (:func:`aot_fingerprint` — byte-compatible with the PR 9 hand-filter
  at every gate combination), and :func:`program_gate_roster` stamps
  the registered program-affecting gate NAMES into every stored AOT
  envelope, so registering a new program-affecting gate in a later
  version invalidates old envelopes (``version_mismatch``) instead of
  ever serving a stale hit.

Reading a gate::

    from heat_tpu.core import gates
    raw = gates.get("HEAT_TPU_REDIST_OVERLAP")      # Optional[str], os.environ semantics
    raw = gates.get("HEAT_TPU_TOPOLOGY", "auto")    # with a default

``get`` intentionally returns the RAW environment string (or the
default): the per-gate mode/byte/path parsing stays at the accessor the
subsystem has always exported (``planner.overlap_mode``,
``staging.ooc_mode``, ``tiers.capacity``, ...), declared here in each
spec's ``accessors`` so the analyzer knows which function reads which
gate. Behavior is therefore byte-identical to the pre-registry readers
at every gate value — the golden plans, plan_ids, program cache keys
and AOT envelope keys are pinned unchanged in tier-1.

Stdlib-only on purpose: this module is imported by
``observability.telemetry`` at process start, before jax or any heavy
core module loads.
"""

from __future__ import annotations

import os

from typing import Dict, Iterable, Optional, Tuple

__all__ = [
    "GATES",
    "GateSpec",
    "PREFIX",
    "accessor_gates",
    "affecting_programs",
    "aot_fingerprint",
    "declare",
    "get",
    "is_set",
    "program_gate_roster",
    "scope_gates",
    "snapshot",
]

PREFIX = "HEAT_TPU_"

#: the PR 9 stamp-filter exclusions, kept for UNREGISTERED names only:
#: a set env var the registry does not know is conservatively key
#: material (exactly the old prefix scan), unless it rides one of these
#: prefixes — which the old scan excluded too. Registered gates are
#: classified by their own ``affects_programs`` flag instead.
_UNREGISTERED_EXCLUDE = ("HEAT_TPU_SERVING", "HEAT_TPU_TELEMETRY")

#: the repo-wide accepted spellings of the boolean gate values (what the
#: subsystem accessors and telemetry's ``_env_truthy`` have always
#: parsed) — :meth:`GateSpec.recognizes` accepts them for any gate whose
#: legal values include the corresponding canonical form.
_FALSY_SPELLINGS = ("0", "off", "false", "no")
_TRUTHY_SPELLINGS = ("1", "on", "true", "force", "yes")

#: cache layers a gate can be key material for. ``plan``: the planner's
#: schedule cache (resolved value in the plan key / plan_id); ``program``:
#: the executor/builder lru program caches (resolved value a builder
#: parameter); ``aot``: the persistent serving envelope keys (raw value
#: in the gate fingerprint).
SCOPES = ("plan", "program", "aot")


class GateSpec:
    """One declared environment gate.

    Attributes
    ----------
    name : the full ``HEAT_TPU_*`` environment variable name.
    default : the raw default applied when the variable is unset —
        documentation of the escape-hatch/auto resolution, never
        substituted by :func:`get` unless the caller passes it.
    values : legal RESOLVED values for mode gates (documentation +
        ``check_value``), or ``None`` for free-form gates (ints, paths).
    kind : ``"mode"`` | ``"int"`` | ``"bytes"`` | ``"path"``.
    affects_programs : True when the gate's value changes the plans or
        compiled programs the library builds — such gates are AOT key
        material and SL402 subjects. (Serving/telemetry switches change
        no program bytes and are False.)
    scopes : which cache layers key on the gate (subset of
        :data:`SCOPES`).
    key_params : conventional parameter names the gate's RESOLVED value
        travels under between the resolution site and the cached
        builders (``pipelined``, ``wire``, ``topo``...) — what rule
        SL402 accepts as "this builder keys on the gate".
    accessors : function names (terminal, as called) that read/resolve
        this gate — the analyzer's map from a call site to a gate.
    help : one-line contract.
    """

    __slots__ = (
        "name", "default", "values", "kind", "affects_programs",
        "scopes", "key_params", "accessors", "help",
    )

    def __init__(self, name, default, values=None, kind="mode",
                 affects_programs=True, scopes=(), key_params=(),
                 accessors=(), help=""):
        if not name.startswith(PREFIX):
            raise ValueError(f"gate name must start with {PREFIX!r}, got {name!r}")
        bad = set(scopes) - set(SCOPES)
        if bad:
            raise ValueError(f"unknown cache scopes {sorted(bad)} for {name}")
        self.name = name
        self.default = default
        self.values = tuple(values) if values is not None else None
        self.kind = kind
        self.affects_programs = bool(affects_programs)
        self.scopes = frozenset(scopes)
        self.key_params = tuple(key_params)
        self.accessors = tuple(accessors)
        self.help = help

    def check_value(self, resolved: str) -> bool:
        """Is ``resolved`` a legal resolved value? Free-form gates accept
        anything."""
        return self.values is None or resolved in self.values

    def recognizes(self, raw: Optional[str]) -> bool:
        """Does the raw environment spelling resolve to a declared legal
        value? Accepts the repo-wide truthy/falsy spelling families
        (``on``/``force``/``yes`` → ``1``, ``off``/``no`` → ``0``) and
        the empty string (which every accessor resolves to its default).
        A False here means the accessor will silently fall through to
        its default arm — worth surfacing in diagnostics."""
        if self.values is None or raw is None:
            return True
        v = raw.strip().lower()
        if v == "" or v in self.values:
            return True
        if "0" in self.values and v in _FALSY_SPELLINGS:
            return True
        if "1" in self.values and v in _TRUTHY_SPELLINGS:
            return True
        return False

    def __repr__(self) -> str:
        return (
            f"GateSpec({self.name}, default={self.default!r}, "
            f"affects_programs={self.affects_programs}, "
            f"scopes={sorted(self.scopes)})"
        )


GATES: Dict[str, GateSpec] = {}


def declare(spec: GateSpec) -> GateSpec:
    """Register a gate. Re-declaring a name replaces the entry (the
    testing hook: tests register throwaway gates and pop them back out
    of :data:`GATES`)."""
    GATES[spec.name] = spec
    return spec


# --------------------------------------------------------------------- #
# the declarations — one per gate, the whole surface                    #
# --------------------------------------------------------------------- #
declare(GateSpec(
    "HEAT_TPU_SORT_KERNEL", default="auto", values=("0", "1", "auto"),
    affects_programs=True, scopes=("program", "aot"),
    key_params=("impl", "path", "engine"),
    accessors=("sort_kernel_mode",),
    help="sort-kernel dispatch: 0 = lax.sort oracle everywhere, 1 = force "
         "the radix/columnsort engines, auto = TPU autotune",
))
declare(GateSpec(
    "HEAT_TPU_RELAYOUT_KERNEL", default="auto", values=("0", "1", "auto"),
    affects_programs=True, scopes=("program", "aot"),
    key_params=("impl", "impl_in", "impl_out"),
    accessors=("kernel_mode", "relayout_kernel_mode"),
    help="lane-packing relayout kernel dispatch: 0 = XLA formulation, "
         "1 = force the Pallas tiled copy, auto = TPU autotune",
))
declare(GateSpec(
    "HEAT_TPU_SPMM_KERNEL", default="auto", values=("0", "1", "auto"),
    affects_programs=True, scopes=("program", "aot"),
    key_params=("impl", "path"),
    accessors=("spmm_kernel_mode",),
    help="block-sparse SpMM/SDDMM dispatch: 0 = gather-free XLA "
         "segment-sum oracle, 1 = force the Pallas brick kernel "
         "(interpret mode off-TPU), auto = TPU autotune",
))
declare(GateSpec(
    "HEAT_TPU_REDIST_PLANNER", default="1", values=("0", "1"),
    affects_programs=True, scopes=("program", "aot"),
    key_params=(),
    accessors=("planner_enabled",),
    help="planner routing: 0 restores the legacy one-collective relayout "
         "paths (a binary route switch — programs differ wholesale, so the "
         "route, not a value, is the key material)",
))
declare(GateSpec(
    "HEAT_TPU_REDIST_BUDGET_MB", default=str(256), kind="int",
    affects_programs=True, scopes=("plan", "program", "aot"),
    key_params=("budget", "budget_bytes", "b"),
    accessors=("budget_bytes",),
    help="per-device transient budget (MiB) the planner chunks under; "
         "resolved bytes are the `budget` component of every plan and "
         "executor program key",
))
declare(GateSpec(
    "HEAT_TPU_REDIST_OVERLAP", default="auto", values=("0", "1", "auto"),
    affects_programs=True, scopes=("program", "aot"),
    key_params=("pipelined", "overlap"),
    accessors=("overlap_mode", "_overlap_active", "ring_enabled"),
    help="depth-2 software-pipelined issue order: 0 = sequential oracle, "
         "1 = force, auto = follow the plan's overlap annotation; resolved "
         "bool is the `pipelined` component of every executor program key",
))
declare(GateSpec(
    "HEAT_TPU_WIRE_QUANT", default="auto", values=("0", "1", "int8", "bf16", "auto"),
    affects_programs=True, scopes=("plan", "program", "aot"),
    key_params=("wire", "quant", "qmode", "codec", "mode"),
    accessors=("wire_quant_mode", "wire_quant_gate"),
    help="wire codec on transient exchanges: 0 = full-width exact-bit, "
         "1 = force int8, bf16 = force the cast codec, auto = int8 on TPU; "
         "resolved codec is the `quant` plan-key and `wire` program-key "
         "component",
))
declare(GateSpec(
    "HEAT_TPU_TOPOLOGY", default="auto", values=None, kind="mode",
    affects_programs=True, scopes=("plan", "program", "aot"),
    key_params=("topo", "topology"),
    accessors=("topology_for", "resolve_topology"),
    help="two-tier topology: auto = slice_index off the resolved world, "
         "SxC = forced factorization, flat = one ICI domain; resolved "
         "(S, C) is the `topology` plan-key and `topo` program-key "
         "component",
))
declare(GateSpec(
    "HEAT_TPU_OOC", default="auto", values=("0", "1", "auto"),
    affects_programs=True, scopes=("plan", "aot"),
    key_params=("staged", "engaged"),
    accessors=("ooc_mode", "ooc_engaged"),
    help="out-of-core staging: 0 = materialize (escape hatch), 1 = force "
         "the staged window pipeline, auto = stage host-resident operands. "
         "A route switch like REDIST_PLANNER — staged plans are a distinct "
         "plan family, no lru program builder keys on the raw mode",
))
declare(GateSpec(
    "HEAT_TPU_OOC_SLAB_MB", default=str(256), kind="int",
    affects_programs=True, scopes=("plan", "aot"),
    key_params=("slab", "slab_bytes"),
    accessors=("slab_bytes",),
    help="HBM slab budget (MiB) for the depth-2 staging windows; resolved "
         "bytes are the staged plan's budget component",
))
declare(GateSpec(
    "HEAT_TPU_VMEM_BYTES", default=str(128 << 20), kind="bytes",
    affects_programs=True, scopes=("aot",),
    key_params=("vmem_bytes",),
    accessors=("capacity",),
    help="vmem tier capacity override (core.tiers)",
))
declare(GateSpec(
    "HEAT_TPU_HBM_BYTES", default=str(16 << 30), kind="bytes",
    affects_programs=True, scopes=("plan", "aot"),
    key_params=("hbm_bytes", "hbm_cap", "budget"),
    accessors=("capacity", "hbm_budget_bytes"),
    help="hbm tier capacity override — the SL301 budget, serving admission "
         "limit, and staging slab ceiling (one number, read one way)",
))
declare(GateSpec(
    "HEAT_TPU_HOST_BYTES", default=str(48 << 30), kind="bytes",
    affects_programs=True, scopes=("aot",),
    key_params=("host_bytes",),
    accessors=("capacity",),
    help="host tier capacity override (core.tiers)",
))
declare(GateSpec(
    "HEAT_TPU_SERVING_AOT", default="auto", values=("0", "1", "auto"),
    affects_programs=False, scopes=(),
    key_params=(),
    accessors=("enabled", "active_store"),
    help="persistent AOT program cache switch: 0 = hooks never install "
         "(escape hatch), 1 = on, auto = on iff HEAT_TPU_SERVING_CACHE "
         "names a directory. Changes WHERE programs come from, never "
         "their bytes — not key material",
))
declare(GateSpec(
    "HEAT_TPU_SERVING_CACHE", default="~/.cache/heat_tpu/aot", kind="path",
    affects_programs=False, scopes=(),
    key_params=(),
    accessors=("cache_dir",),
    help="AOT store root (trust boundary: same write permissions as the "
         "deployment's code). A path, never program-bytes key material",
))
declare(GateSpec(
    "HEAT_TPU_TELEMETRY", default="0", values=("0", "1"),
    affects_programs=False, scopes=(),
    key_params=(),
    accessors=("enabled",),
    help="telemetry registry switch — records host-side values only, "
         "changes no program bytes",
))
declare(GateSpec(
    "HEAT_TPU_TRACE", default="auto", values=("0", "1", "auto"),
    affects_programs=False, scopes=(),
    key_params=(),
    accessors=("trace_mode", "enabled"),
    help="span tracer + flight-recorder export switch "
         "(observability.tracing): 0 = hard off (the zero-overhead "
         "escape hatch — every probe is one module-bool read), 1 = "
         "collect, auto = follow the telemetry switch. Records "
         "host-side spans only — plans, plan_ids, programs, and AOT "
         "envelope keys are byte-identical at every value "
         "(affects_programs=False by construction, diffed in CI)",
))
declare(GateSpec(
    "HEAT_TPU_RESILIENCE", default="auto", values=("0", "1", "auto"),
    affects_programs=True, scopes=("aot",),
    key_params=(),
    accessors=("resilience_mode", "resilience_enabled"),
    help="elastic fault-tolerant runtime switch (heat_tpu.resilience): "
         "0 = exact pre-resilience paths everywhere (escape hatch — no "
         "checkpoint hooks, no world-epoch guards, no drain fences), "
         "1 = force (the chaos CI leg), auto = engage where the caller "
         "hands the runtime a checkpoint config or watcher. "
         "Conservatively program-affecting: the elastic runtime re-enters "
         "cached programs across world re-resolutions under the epoch "
         "discipline this gate installs, and AOT envelopes exported "
         "before the resilience runtime predate the restore contract's "
         "world re-binding — the roster bump (version_mismatch for "
         "pre-resilience envelopes) is the designed invalidation",
))
declare(GateSpec(
    "HEAT_TPU_CKPT_DIR", default="~/.cache/heat_tpu/ckpt", kind="path",
    affects_programs=False, scopes=(),
    key_params=(),
    accessors=("ckpt_dir",),
    help="checkpoint store root (heat_tpu.resilience.checkpoint). TRUST "
         "BOUNDARY like the AOT store: envelopes are integrity-checked "
         "(per-entry sha256) but restore unpickles nothing — still, the "
         "directory must carry the same write permissions as the "
         "deployment's code. A path, never program-bytes key material",
))
declare(GateSpec(
    "HEAT_TPU_NUMCHECK_ACC_DIM", default=str(1024), kind="int",
    affects_programs=False, scopes=(),
    key_params=(),
    accessors=("acc_dim_threshold",),
    help="analyzer pass 6 (numcheck) SL601 reduction-extent threshold: "
         "a dot_general/reduce_sum/scan carry accumulating in bf16/f16 "
         "over a contraction/reduction extent >= this value fires "
         "low-precision-accumulation (warning; >= 65536 escalates to "
         "error regardless). Read-only analyzer tuning — changes which "
         "findings a report carries, never any plan, plan_id, program, "
         "or AOT key (affects_programs=False by construction)",
))


# --------------------------------------------------------------------- #
# the accessor                                                          #
# --------------------------------------------------------------------- #
def get(name: str, default: Optional[str] = None) -> Optional[str]:
    """The RAW environment value of a registered gate — the one
    sanctioned ``os.environ`` read for ``HEAT_TPU_*`` names (rule SL403
    flags any other). Semantics are exactly ``os.environ.get(name,
    default)``; per-gate parsing stays with the subsystem accessors
    declared in the spec. Unknown names raise — a read of an undeclared
    gate is the bug the registry exists to prevent."""
    if name not in GATES:
        raise KeyError(
            f"gates.get: {name!r} is not a declared gate — declare it in "
            "heat_tpu/core/gates.py (name, default, affects_programs, "
            "cache scopes) before reading it"
        )
    return os.environ.get(name, default)


def is_set(name: str) -> bool:
    """Is the registered gate explicitly set in the environment?"""
    if name not in GATES:
        raise KeyError(f"gates.is_set: {name!r} is not a declared gate")
    return name in os.environ


# --------------------------------------------------------------------- #
# derivations — what the cache layers key on                            #
# --------------------------------------------------------------------- #
def affecting_programs() -> Tuple[GateSpec, ...]:
    """The registered gates whose value changes the programs the library
    builds, sorted by name — the AOT stamp population."""
    return tuple(
        GATES[name] for name in sorted(GATES) if GATES[name].affects_programs
    )


def scope_gates(scope: str) -> Tuple[GateSpec, ...]:
    """Registered gates that are key material for one cache layer
    (``plan`` / ``program`` / ``aot``), sorted by name."""
    if scope not in SCOPES:
        raise ValueError(f"unknown cache scope {scope!r} (one of {SCOPES})")
    return tuple(
        GATES[name] for name in sorted(GATES) if scope in GATES[name].scopes
    )


def aot_fingerprint() -> Tuple[Tuple[str, str], ...]:
    """``(name, raw value)`` of every gate that must distinguish
    persistent AOT cache keys: registered program-affecting gates that
    are SET in the environment, plus any set ``HEAT_TPU_*`` variable the
    registry does not know (an unknown gate is conservatively key
    material, exactly like the PR 9 prefix scan it replaces — minus the
    scan's serving/telemetry exclusions, which are now the registered
    ``affects_programs=False`` entries). Byte-compatible with the old
    hand-filter at every gate combination; empty at defaults."""
    out = []
    for k, v in os.environ.items():
        if not k.startswith(PREFIX):
            continue
        spec = GATES.get(k)
        if spec is not None:
            if spec.affects_programs:
                out.append((k, v))
        elif not k.startswith(_UNREGISTERED_EXCLUDE):
            out.append((k, v))
    return tuple(sorted(out))


def program_gate_roster() -> str:
    """Comma-joined sorted NAMES of the registered program-affecting
    gates — stamped into every AOT envelope's meta (not its key), so a
    version that registers a new program-affecting gate refuses every
    envelope written under the old roster (``version_mismatch``: the old
    artifacts may predate the gate's subsystem entirely) instead of ever
    serving one stale."""
    return ",".join(s.name for s in affecting_programs())


def accessor_gates() -> Dict[str, Tuple[str, ...]]:
    """``{accessor function name: (gate names...)}`` over every declared
    spec — the analyzer's (SL402) map from a call site to the gates it
    may read. A name shared by several accessors maps to all of them
    (the checker is conservative)."""
    out: Dict[str, Tuple[str, ...]] = {}
    for name in sorted(GATES):
        for acc in GATES[name].accessors:
            out[acc] = out.get(acc, ()) + (name,)
    return out


def snapshot() -> Dict[str, Dict[str, object]]:
    """Declaration + current raw value of every gate — introspection for
    tests and the warmup/diagnostics CLIs."""
    out = {}
    for name, spec in sorted(GATES.items()):
        raw = os.environ.get(name)
        out[name] = {
            "default": spec.default,
            "values": spec.values,
            "kind": spec.kind,
            "affects_programs": spec.affects_programs,
            "scopes": sorted(spec.scopes),
            "key_params": spec.key_params,
            "raw": raw,
            "set": name in os.environ,
            # a set-but-unrecognized raw value resolves to the accessor's
            # default arm — surfaced here so diagnostics can say so
            "recognized": spec.recognizes(raw),
            "help": spec.help,
        }
    return out
