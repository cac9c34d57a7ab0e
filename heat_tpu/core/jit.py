"""Fused-program surface: ``ht.jit``.

The reference framework is eager: every ``heat.*`` call runs its own
kernels (torch eager + MPI). This repo's eager path is already compiled
per op, but a CHAIN of public ops still pays one XLA program dispatch per
op (root ``PERF.md``, section 7, row 4 is the cell that would measure
it). The reference has no answer to this; on TPU the answer is the same
one JAX gives: trace the whole user function into ONE XLA program.

``ht.jit(fn)`` wraps a function of DNDarrays (any pytree of DNDarrays,
jax arrays and static Python values) so that every ``heat_tpu`` op inside
it is traced — metadata propagation (gshape/split/dtype) runs once at
trace time, the array math fuses into a single program, and XLA inserts
the collectives implied by the shardings. Works because every public op
routes device math through ``jnp``/``lax`` on the physical array and
keeps host control flow metadata-only.

Limitations (clear errors, not wrong answers):

- Ops whose OUTPUT SHAPE depends on data (``unique``, ``nonzero``,
  boolean-mask indexing) cannot be traced — they need a host read of
  counts. Calling them under ``ht.jit`` raises jax's concretization
  error, re-raised with a pointer here. Use them eagerly, outside.
- DNDarrays closed over (not passed as arguments) are baked into the
  program as constants; pass arrays as arguments. The wrapper WARNS at
  first trace when the function's closure cells hold a DNDarray — the
  constant pins its buffer in HBM for the cache entry's lifetime and
  ignores later updates to the Python variable.
- Non-array hashable arguments (Python ints/floats/bools/strings) are
  STATIC: part of the program cache key, baked into the trace — unlike
  ``jax.jit``, which traces scalars as weak-typed arrays. A
  per-call-varying scalar (a learning rate, a threshold) therefore
  retraces and recompiles on every new value and grows the wrapper's
  cache without bound; pass such scalars as 0-d jax/numpy arrays
  (``jnp.float32(lr)``) to trace them instead.
- The traced function must be functional on its DNDarray arguments:
  in-place ``x[i] = v`` on an ARGUMENT mutates the Python wrapper at
  trace time only, it does not feed back to the caller's array.
"""

from __future__ import annotations

import functools
import re
import time
import warnings

import numpy as np

import jax

from typing import Any, Callable, Dict, List, Optional

from .dndarray import DNDarray
from ..observability import events as _obs_events
from ..observability import telemetry as _telemetry
from ..observability.tracing import span as _span

# __all__ stays ["jit"]: the executable_* introspection helpers below
# are the analyzer's module-level readers (heat_tpu.analysis.memcheck),
# not part of the star-exported array API surface.
__all__ = ["jit"]


# ---------------------------------------------------------------------- #
# serving AOT hooks (ISSUE 9)                                            #
# ---------------------------------------------------------------------- #
# ``heat_tpu.serving.aot_cache`` installs an object here when the
# persistent AOT program cache is enabled (HEAT_TPU_SERVING_AOT /
# HEAT_TPU_SERVING_CACHE). The wrapper consults it on an ht-level cache
# MISS: ``load(...)`` may return a ready ``(callable, out_box)`` entry
# rebuilt from a serialized ``jax.export`` artifact (cold start becomes
# load-not-compile), and after a fresh first dispatch ``store(...)``
# persists the newly compiled program. With the hooks uninstalled (the
# default, and the HEAT_TPU_SERVING_AOT=0 escape hatch) every code path
# below is byte-identical to the pre-serving wrapper.
_AOT_HOOKS = None


def install_aot_hooks(hooks) -> None:
    """Install (or with ``None`` uninstall) the serving AOT cache hooks.
    ``hooks`` must provide ``load(fn, treedef, specs, donate_user,
    donate_positions, jit_kwargs)`` returning an entry or ``None``, and
    ``store(fn, treedef, specs, donate_user, donate_positions,
    jit_kwargs, jitted, traced_in, out_box)`` (both must never raise)."""
    global _AOT_HOOKS
    _AOT_HOOKS = hooks


def aot_hooks():
    """The installed serving AOT hooks object, or ``None``."""
    return _AOT_HOOKS


# every live ht.jit wrapper, so the elastic runtime's eviction sweep
# (heat_tpu.resilience.elastic.invalidate_caches) can drop program
# entries compiled against a world that no longer exists. Entries are
# keyed on comm IDENTITY (_DndSpec), so a re-resolved world can never
# HIT a stale entry — the sweep reclaims the memory.
import weakref

_LIVE_WRAPPERS: "weakref.WeakSet" = weakref.WeakSet()


def clear_wrapper_caches() -> int:
    """Drop every live ``ht.jit`` wrapper's program cache; returns the
    total number of evicted entries."""
    n = 0
    for w in list(_LIVE_WRAPPERS):
        cache = getattr(w, "_ht_jit_cache", None)
        if cache:
            n += len(cache)
            cache.clear()
    return n


def _is_leaf(x) -> bool:
    return isinstance(x, DNDarray)


# ---------------------------------------------------------------------- #
# executable introspection (ISSUE 10)                                    #
# ---------------------------------------------------------------------- #
# The analyzer's memory pass (heat_tpu.analysis.memcheck) needs two
# facts only the COMPILED executable knows: did XLA actually honor the
# declared donations (input_output_alias), and what does the compiler's
# own buffer assignment say the program needs (memory_analysis). Both
# readers live here, next to the donation bookkeeping they audit.

# "{0}: (2, {}, may-alias)" entries inside the module header's
# input_output_alias={...} block
_ALIAS_ENTRY = re.compile(
    r"\{\s*([0-9,\s]*)\}\s*:\s*\(\s*(\d+)\s*,\s*\{([0-9,\s]*)\}\s*,\s*([a-z-]+)\s*\)"
)


def executable_input_output_aliases(compiled_or_text) -> List[Dict[str, Any]]:
    """Parsed ``input_output_alias`` map of a compiled module: one
    ``{"output_index", "param_number", "param_index", "kind"}`` entry
    per aliased buffer, empty when the executable aliases nothing —
    which is exactly how XLA reports a donation it could not use
    ("donation silently dropped", rule SL302). ``param_number`` indexes
    the module's flat parameters, i.e. the traced leaf positions
    ``ht.jit``'s donation mapping produces."""
    text = (
        compiled_or_text
        if isinstance(compiled_or_text, str)
        else compiled_or_text.as_text()
    )
    start = text.find("input_output_alias={")
    if start < 0:
        return []
    i = start + len("input_output_alias=")
    depth = 0
    end = i
    for k in range(i, len(text)):
        if text[k] == "{":
            depth += 1
        elif text[k] == "}":
            depth -= 1
            if depth == 0:
                end = k + 1
                break
    out = []
    for m in _ALIAS_ENTRY.finditer(text[i:end]):
        out.append(
            {
                "output_index": tuple(
                    int(v) for v in m.group(1).split(",") if v.strip()
                ),
                "param_number": int(m.group(2)),
                "param_index": tuple(
                    int(v) for v in m.group(3).split(",") if v.strip()
                ),
                "kind": m.group(4),
            }
        )
    return out


def executable_memory_stats(compiled) -> Optional[Dict[str, int]]:
    """The compiler's own per-device buffer assignment of a compiled
    executable (``Compiled.memory_analysis()``), normalized to plain
    ints: argument/output/temp/alias bytes. ``None`` when the backend
    does not report it — callers treat the stats as a cross-check, never
    a requirement."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    fields = {
        "argument_bytes": "argument_size_in_bytes",
        "output_bytes": "output_size_in_bytes",
        "temp_bytes": "temp_size_in_bytes",
        "alias_bytes": "alias_size_in_bytes",
    }
    out: Dict[str, int] = {}
    for key, attr in fields.items():
        v = getattr(ma, attr, None)
        if v is None:
            return None
        out[key] = int(v)
    # what the buffer assignment says one device needs live at once:
    # arguments + outputs + transients, minus the aliased reuse
    out["peak_bytes"] = max(
        0,
        out["argument_bytes"] + out["output_bytes"] + out["temp_bytes"]
        - out["alias_bytes"],
    )
    return out


class _DndSpec:
    """Hashable trace signature of a DNDarray argument: everything the
    metadata path can branch on must be part of the program cache key."""

    __slots__ = ("gshape", "dtype", "split", "device", "comm")

    def __init__(self, d: DNDarray):
        self.gshape = d.shape
        self.dtype = d.dtype
        self.split = d.split
        self.device = d.device
        self.comm = d.comm

    def _key(self):
        return (self.gshape, self.dtype, self.split, str(self.device), id(self.comm))

    def __eq__(self, other):
        return isinstance(other, _DndSpec) and self._key() == other._key()

    def __hash__(self):
        return hash(self._key())

    def rebuild(self, phys) -> DNDarray:
        return DNDarray(phys, self.gshape, self.dtype, self.split, self.device, self.comm)

    @classmethod
    def from_meta(cls, gshape, dtype, split, device, comm) -> "_DndSpec":
        """Rebuild a spec from stored metadata (serving AOT cache: output
        specs are persisted structurally — gshape/dtype/split — and get
        their device/comm from the loading process's input arrays)."""
        spec = cls.__new__(cls)
        spec.gshape = tuple(gshape)
        spec.dtype = dtype
        spec.split = split
        spec.device = device
        spec.comm = comm
        return spec


def _leaf_spec(leaf):
    """(kind, spec) — kind decides traced-vs-static; spec keys the cache."""
    if isinstance(leaf, DNDarray):
        return ("dnd", _DndSpec(leaf))
    if isinstance(leaf, jax.Array):
        # weak_type participates in jax.jit's own retrace key; omitting it
        # here would let two jax-level traces share one ht-level cache entry
        return ("jax", (leaf.shape, str(leaf.dtype), bool(leaf.aval.weak_type)))
    if isinstance(leaf, np.ndarray):
        return ("np", (leaf.shape, str(leaf.dtype)))
    # everything else is static: part of the cache key, baked into the trace
    try:
        hash(leaf)
    except TypeError:
        raise TypeError(
            f"ht.jit argument of type {type(leaf).__name__} is neither an array "
            "nor hashable — pass arrays (DNDarray/jax/numpy) or hashable statics"
        ) from None
    return ("static", leaf)


def _holds_dndarray(v) -> bool:
    """True when ``v`` is, or is a container (pytree) holding, a
    DNDarray — either way tracing bakes the buffer in as a constant."""
    try:
        leaves = jax.tree.leaves(v, is_leaf=_is_leaf)
    except Exception:
        return False
    return any(isinstance(leaf, DNDarray) for leaf in leaves)


def _warn_closure_captures(fn) -> None:
    """Warn when ``fn`` captures DNDarrays — via closure cells or global
    loads, directly or inside containers: they bake into the compiled
    program as constants, pinning their HBM buffers for the cache
    entry's lifetime and ignoring later rebinds of the Python variable
    (VERDICT r4 #7). Runs at each new-signature trace (compile-time
    cost, never per dispatch)."""
    code = getattr(fn, "__code__", None)
    if code is None:
        return
    captured = []
    for name, cell in zip(code.co_freevars, fn.__closure__ or ()):
        try:
            v = cell.cell_contents
        except ValueError:  # empty cell
            continue
        if _holds_dndarray(v):
            captured.append(name)
    # actual global LOADS only — co_names also lists attribute accesses,
    # which would false-positive on e.g. `x.T` shadowing a global `T`
    import dis

    g = getattr(fn, "__globals__", {})
    global_loads = {
        ins.argval
        for ins in dis.get_instructions(code)
        if ins.opname in ("LOAD_GLOBAL", "LOAD_NAME")
    }
    for name in sorted(global_loads):
        if name in g and _holds_dndarray(g[name]):
            captured.append(name)
    # default argument values bake in exactly the same way when the
    # caller omits them (they never reach the leaf flattening)
    for v in (fn.__defaults__ or ()):
        if _holds_dndarray(v):
            captured.append("<default argument>")
    for name, v in (fn.__kwdefaults__ or {}).items():
        if _holds_dndarray(v):
            captured.append(f"<default of {name!r}>")
    for name in captured:
        warnings.warn(
            f"ht.jit: {fn.__name__!r} closes over DNDarray {name!r} — it "
            "will be baked into the compiled program as a CONSTANT, "
            "pinning its device buffer for the cache entry's lifetime "
            "and ignoring later updates to the variable. Pass it as an "
            "argument instead.",
            stacklevel=4,
        )


def jit(fn: Optional[Callable] = None, **jit_kwargs) -> Callable:
    """Trace ``fn`` (a function over DNDarrays) into one fused XLA program.

    Usable as ``ht.jit(fn)`` or ``@ht.jit``. Additional keyword arguments
    are forwarded to ``jax.jit``.

    ``donate_argnums`` uses USER-VISIBLE positional argument indices (like
    ``jax.jit``): the wrapper maps each donated argument to the flattened
    physical leaves it contributes and donates exactly those buffers, so
    large pipelines can reuse their input HBM. Donated DNDarrays are
    invalidated by the call (same contract as jax). ``donate_argnames``
    and donating keyword arguments are not supported.

    Examples
    --------
    >>> @ht.jit
    ... def gram_norms(x):
    ...     g = ht.matmul(x, ht.transpose(x))
    ...     return ht.sqrt(ht.sum(g * g, axis=1))
    >>> y = gram_norms(a)       # one compiled program, one dispatch
    """
    if fn is None:
        return lambda f: jit(f, **jit_kwargs)
    if "donate_argnames" in jit_kwargs:
        raise TypeError(
            "ht.jit supports donate_argnums (positional) only, not donate_argnames"
        )
    donate_user = jit_kwargs.pop("donate_argnums", ())
    if isinstance(donate_user, int):
        donate_user = (donate_user,)
    donate_user = tuple(int(i) for i in donate_user)

    cache: dict = {}

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        leaves, treedef = jax.tree.flatten((args, kwargs), is_leaf=_is_leaf)
        specs = tuple(_leaf_spec(leaf) for leaf in leaves)
        key = (treedef, specs)

        entry = cache.get(key)
        is_new_entry = entry is None
        from_aot = False
        donate_positions = ()
        # the names observed_program_cache gives its lookups and launches
        with _span("ht.program.miss" if is_new_entry else "ht.program.hit", cache="ht.jit"):
            if entry is None:
                if donate_user:
                    # map USER positional args to the flattened traced-leaf
                    # positions they contribute (statics carry no buffer and
                    # are skipped) — this is the alignment the r4 limitation
                    # note said was missing
                    if any(u < 0 or u >= len(args) for u in donate_user):
                        raise ValueError(
                            f"donate_argnums {donate_user} out of range for "
                            f"{len(args)} positional arguments"
                        )
                    spans, off = [], 0
                    for a in args:
                        n = len(jax.tree.flatten(a, is_leaf=_is_leaf)[0])
                        spans.append(range(off, off + n))
                        off += n
                    traced_pos, t = {}, 0
                    for i, (kind, _) in enumerate(specs):
                        if kind != "static":
                            traced_pos[i] = t
                            t += 1
                    donate_positions = tuple(
                        traced_pos[i]
                        for u in donate_user
                        for i in spans[u]
                        if i in traced_pos
                    )
                aot = _AOT_HOOKS
                if aot is not None:
                    entry = aot.load(fn, treedef, specs, donate_user, donate_positions, jit_kwargs)
                    from_aot = entry is not None
                    if from_aot:
                        cache[key] = entry
            if entry is None:
                out_box = []

                def inner(*traced):
                    # NOTE: closes over `specs` (metadata) only — never over
                    # `leaves`, which would pin the first call's device buffers
                    # in HBM for the lifetime of the cache entry
                    it = iter(traced)
                    rebuilt = []
                    for kind, spec in specs:
                        if kind == "dnd":
                            rebuilt.append(spec.rebuild(next(it)))
                        elif kind in ("jax", "np"):
                            rebuilt.append(next(it))
                        else:
                            rebuilt.append(spec)
                    a, kw = jax.tree.unflatten(treedef, rebuilt)
                    try:
                        res = fn(*a, **kw)
                    except (
                        jax.errors.ConcretizationTypeError,
                        jax.errors.TracerArrayConversionError,
                        jax.errors.TracerBoolConversionError,
                        jax.errors.TracerIntegerConversionError,
                    ) as e:
                        raise TypeError(
                            "ht.jit: an op inside the traced function needs the array's "
                            "VALUES on the host (data-dependent output shape — unique/"
                            "nonzero/boolean-mask indexing — or a float()/int() read). "
                            "Run that op eagerly, outside ht.jit. Original: " + str(e)
                        ) from None
                    out_leaves, out_treedef = jax.tree.flatten(res, is_leaf=_is_leaf)
                    phys_out, out_meta = [], []
                    for o in out_leaves:
                        if isinstance(o, DNDarray):
                            out_meta.append(_DndSpec(o))
                            phys_out.append(o._phys)
                        else:
                            out_meta.append(None)
                            phys_out.append(o)
                    out_box.append((out_treedef, out_meta))
                    return tuple(phys_out)

                if donate_user:
                    jitted_inner = jax.jit(
                        inner, donate_argnums=donate_positions, **jit_kwargs
                    )
                    if _telemetry._ENABLED:
                        # donation decision: how many traced buffers actually
                        # get donated for this signature (statics drop out)
                        _telemetry.inc("ht.jit.donated_buffers", len(donate_positions))
                        _obs_events.emit(
                            "ht.jit.donation", fn=getattr(fn, "__name__", "<fn>"),
                            requested_args=len(donate_user),
                            donated_buffers=len(donate_positions),
                        )
                else:
                    jitted_inner = jax.jit(inner, **jit_kwargs)
                _warn_closure_captures(fn)
                entry = (jitted_inner, out_box)
                cache[key] = entry

        jitted, out_box = entry
        traced_in = [
            leaf._phys if isinstance(leaf, DNDarray) else leaf
            for leaf, (kind, _) in zip(leaves, specs)
            if kind != "static"
        ]
        # first dispatch of a new signature = trace + XLA compile (+ one
        # execution); later hits pay only program dispatch
        with _span(
            "ht.program.compile" if is_new_entry and not from_aot else "ht.program.launch",
            cache="ht.jit",
        ):
            t0 = time.perf_counter()
            phys_out = jitted(*traced_in)
            dt = time.perf_counter() - t0
        if _telemetry._ENABLED:
            _telemetry.inc("ht.jit.cache.miss" if is_new_entry else "ht.jit.cache.hit")
            if is_new_entry and from_aot:
                # An AOT-loaded entry never traces the user function —
                # the census stays honest: ht.jit.compile counts FULL
                # trace+compiles only, a served cold start records under
                # serving.aot.first_dispatch instead
                _telemetry.observe("serving.aot.first_dispatch", dt)
                _obs_events.emit(
                    "serving.aot.dispatch", fn=getattr(fn, "__name__", "<fn>"),
                    leaves=len(leaves), seconds=round(dt, 6),
                )
            elif is_new_entry:
                _telemetry.observe("ht.jit.compile", dt)
                _obs_events.emit(
                    "ht.jit.trace", fn=getattr(fn, "__name__", "<fn>"),
                    leaves=len(leaves), seconds=round(dt, 6),
                )
        if is_new_entry and not from_aot and _AOT_HOOKS is not None:
            # persist the freshly compiled program (serving AOT cache):
            # runs AFTER the first dispatch so the hooks can read concrete
            # input avals/shardings off ``traced_in``; must never raise
            _AOT_HOOKS.store(
                fn, treedef, specs, donate_user, donate_positions,
                jit_kwargs, jitted, traced_in, out_box,
            )
        if not out_box:
            # cache hit on a program jax.jit compiled earlier but whose
            # out-metadata box was lost — cannot happen (box fills on first
            # trace, same entry), guarded for safety
            raise RuntimeError("ht.jit internal: missing output metadata")
        # [-1]: if jax.jit retraced under this same ht-level key (its own
        # key is finer), the LAST trace's metadata describes this call
        out_treedef, out_meta = out_box[-1]
        rebuilt_out = [
            m.rebuild(p) if m is not None else p for m, p in zip(out_meta, phys_out)
        ]
        return jax.tree.unflatten(out_treedef, rebuilt_out)

    wrapper._ht_jit_cache = cache  # introspection/testing hook
    # donation bookkeeping for ht.analysis.check (rule SL105): which
    # user-visible positional args this wrapper donates at dispatch
    wrapper._ht_jit_donate_argnums = donate_user

    def _numcheck(*args, **kwargs):
        """Precision-flow analysis (analyzer pass 6) of the program this
        wrapper compiles for the given example arguments — compile-only
        introspection, nothing dispatches and no cache entry is made.
        ``wrapped.numcheck(x)`` == ``ht.analysis.numcheck(fn, x)`` on
        the undecorated function, so the SL604 source scan sees the
        user's code, not the wrapper."""
        from ..analysis.numcheck import numcheck as _nc

        return _nc(fn, *args, **kwargs)

    wrapper.numcheck = _numcheck
    _LIVE_WRAPPERS.add(wrapper)
    return wrapper
