"""Persistent AOT program cache — cold start becomes load-not-compile.

Every entry point today (``predict``/``transform``/``ht.jit`` linalg
programs) pays full trace + XLA compile on the first dispatch of every
process. For a serving fleet that restarts, autoscales, and rolls out
continuously, that cost is paid per replica per program — minutes of
accelerator idle at production program sizes. This module closes it
with a two-layer on-disk cache:

1. **``jax.export`` artifacts** (this module's own store): the traced,
   lowered StableHLO of a compiled program plus the ht-level output
   metadata, keyed by the SAME signature the in-process caches use —
   ``(comm, spec, impl, donation, env-gate)`` — extended with
   jax/heat_tpu version stamps, backend platform and device count. A
   warm process deserializes instead of re-tracing user code.
2. **the XLA persistent compilation cache** (``jax_compilation_cache_dir``
   pointed under the same root): on backends that support it (TPU/GPU)
   the post-optimization XLA executable is reused too, so the wrapper
   compile around a deserialized artifact is a disk read, not an XLA
   optimization pass. (CPU in this jax has no executable cache; the
   export layer still removes tracing there.)

Failure policy — the cache must NEVER be a correctness or availability
hazard: any corrupt file, version mismatch, unsupported program shape
or serialization error falls back to the normal trace-and-compile path
(counted, not raised). ``HEAT_TPU_SERVING_AOT=0`` is the escape hatch:
the hooks are never installed and ``core/jit.py`` runs its exact
pre-serving code paths.

TRUST BOUNDARY — the store directory is executable input, same class
as the Python code directory: envelopes are unpickled and their
program artifacts dispatched to the accelerator, so a writer of the
cache dir can execute code in every process that reads it. Point
``HEAT_TPU_SERVING_CACHE`` only at paths with the same write
permissions as the deployment's code (bake it into the image with the
wheels, as ``scripts/warmup.py`` is built for); never at
world-writable or untrusted shared storage. The corruption/version
checks defend against ACCIDENTS (torn writes, stale rollouts), not
against a malicious writer.

Gates
-----
- ``HEAT_TPU_SERVING_AOT``: ``0`` off (escape hatch), ``1`` on,
  unset/``auto`` = on iff ``HEAT_TPU_SERVING_CACHE`` names a directory.
- ``HEAT_TPU_SERVING_CACHE``: store root (default
  ``~/.cache/heat_tpu/aot``).

Telemetry (when enabled): ``serving.aot.{hit,miss,bypass,store,corrupt,
version_mismatch}`` counters + ``serving.aot.{load,export}`` timers.
The store keeps the same tallies in ``AOTStore.stats`` unconditionally
(the warmup CLI reports them without flipping the global switch).
"""

from __future__ import annotations

import hashlib
import inspect
import os
import pickle
import time

from typing import Any, Dict, Optional, Sequence, Tuple

import numpy as np

import jax

import importlib

# the module, not the public `jit` function that shadows it in the
# core package namespace
_ht_jit = importlib.import_module(__name__.rsplit(".", 2)[0] + ".core.jit")

from ..observability import telemetry as _telemetry
from ..observability import tracing as _tracing
from ..version import __version__

__all__ = [
    "AOTStore",
    "cache_dir",
    "configure",
    "enabled",
    "ensure_program",
    "active_store",
]

_FORMAT = 1

# env gates whose value changes the PROGRAMS the library builds are part
# of every persistent key, so a cache written under one gate combination
# never serves a process running another. Which gates those ARE is no
# longer a hand-listed prefix scan: the set derives from the registry's
# ``affects_programs`` declarations (heat_tpu/core/gates.py) — the
# serving and telemetry switches are the registered
# ``affects_programs=False`` entries the old exclusion list spelled by
# prefix. Byte-compatible with the PR 9 filter at every combination.
from ..core import gates as _gates


# the truthy spellings are the telemetry module's — one definition,
# one set of accepted values across every HEAT_TPU_* switch
_env_truthy = _telemetry._env_truthy


def _env_falsy(value: Optional[str]) -> bool:
    return (value or "").strip().lower() in ("0", "false", "off", "no")


def cache_dir() -> str:
    """The store root: ``HEAT_TPU_SERVING_CACHE`` or the user default."""
    return _gates.get(
        "HEAT_TPU_SERVING_CACHE",
        os.path.join(os.path.expanduser("~"), ".cache", "heat_tpu", "aot"),
    )


def _gate_fingerprint() -> Tuple[Tuple[str, str], ...]:
    """(name, raw value) of every program-affecting gate that is set —
    registry-derived (``gates.aot_fingerprint``), empty at defaults."""
    return _gates.aot_fingerprint()


def _runtime_stamps() -> Dict[str, Any]:
    """Version/platform stamps: hashed into every key AND stored in each
    entry's meta (the load path re-verifies them — defense in depth
    against key truncation and hand-copied cache dirs)."""
    return {
        "format": _FORMAT,
        "heat_tpu": __version__,
        "jax": jax.__version__,
        "platform": jax.default_backend(),
        "devices": int(jax.device_count()),
    }


def _envelope_stamps() -> Dict[str, Any]:
    """What every stored envelope's meta must match at load: the runtime
    stamps PLUS the registered program-affecting gate ROSTER
    (``gates.program_gate_roster``). The roster rides in the meta, never
    the key: registering a new program-affecting gate in a later version
    changes the roster, so every envelope written under the old one is
    refused as ``version_mismatch`` — the old artifacts may predate the
    gate's subsystem entirely, and a recompile is the only safe answer
    (never a stale hit)."""
    stamps = _runtime_stamps()
    stamps["gate_roster"] = _gates.program_gate_roster()
    return stamps


def _key_stamps() -> tuple:
    stamps = _runtime_stamps()
    return (
        tuple(sorted(stamps.items())),
        ("x64", bool(jax.config.jax_enable_x64)),
        ("gates", _gate_fingerprint()),
    )


def _stable_static(leaf) -> Optional[str]:
    """A process-independent string for a static leaf, or ``None`` when
    the leaf has no stable serialization (object reprs carry addresses —
    such signatures bypass the persistent cache rather than risk a
    collision)."""
    if leaf is None or isinstance(leaf, (bool, int, float, str, bytes)):
        return repr(leaf)
    if isinstance(leaf, (tuple, frozenset)):
        items = sorted(leaf, key=repr) if isinstance(leaf, frozenset) else leaf
        parts = [_stable_static(v) for v in items]
        if any(p is None for p in parts):
            return None
        return f"{type(leaf).__name__}({','.join(parts)})"
    return None


def _comm_desc(comm) -> tuple:
    """Stable communicator descriptor: what the program's collectives
    depend on (world size, axis name, tier topology) — never the
    process-local object identity the in-memory key uses."""
    try:
        size = int(comm.size)
    except Exception:
        size = -1
    axis = getattr(comm, "axis_name", None)
    try:
        topo = str(comm.topology)
    except Exception:
        topo = "flat"
    return (type(comm).__name__, size, axis, topo)


def _fn_ident(fn) -> tuple:
    """(module.qualname, source sha1) — the ``impl`` part of the key.
    The source hash invalidates entries when the function body changes
    between deployments even though the qualname did not."""
    name = f"{getattr(fn, '__module__', '?')}.{getattr(fn, '__qualname__', repr(fn))}"
    try:
        src = inspect.getsource(inspect.unwrap(fn))
        sha = hashlib.sha1(src.encode()).hexdigest()
    except (TypeError, OSError):
        sha = "nosource"
    return (name, sha)


def _input_sds(traced_in: Sequence) -> list:
    """ShapeDtypeStructs (with shardings) for ``jax.export`` tracing,
    read off the concrete arrays of the first dispatch."""
    out = []
    for a in traced_in:
        if isinstance(a, jax.ShapeDtypeStruct):
            out.append(a)
            continue
        a = np.asarray(a) if not hasattr(a, "dtype") else a
        out.append(jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=getattr(a, "sharding", None)))
    return out


class AOTStore:
    """The on-disk artifact store: one pickle envelope per program key
    (``<root>/<sha256[:40]>.aot``) holding the serialized ``jax.export``
    blob, the ht-level output metadata, and the version stamps."""

    def __init__(self, root: str):
        self.root = root
        self.stats: Dict[str, int] = {
            "hit": 0, "miss": 0, "bypass": 0, "store": 0,
            "corrupt": 0, "version_mismatch": 0,
        }

    # ------------------------------------------------------------------ #
    # keys / paths                                                       #
    # ------------------------------------------------------------------ #
    def key(self, parts: tuple) -> str:
        return hashlib.sha256(repr(parts).encode()).hexdigest()[:40]

    def path(self, key: str) -> str:
        return os.path.join(self.root, f"{key}.aot")

    def entries(self) -> list:
        try:
            return sorted(f for f in os.listdir(self.root) if f.endswith(".aot"))
        except OSError:
            return []

    def _count(self, name: str) -> None:
        self.stats[name] = self.stats.get(name, 0) + 1
        if _telemetry._ENABLED:
            _telemetry.inc(f"serving.aot.{name}")

    # ------------------------------------------------------------------ #
    # load / store                                                       #
    # ------------------------------------------------------------------ #
    def load(self, key: str) -> Optional[dict]:
        """The stored envelope for ``key``, or ``None`` (counted as
        ``miss``, ``corrupt`` — file removed best-effort — or
        ``version_mismatch``). Never raises."""
        sp = _tracing.start_span("aot.load", key=key) if _tracing._ENABLED else None
        outcome = "miss"
        try:
            path = self.path(key)
            if not os.path.exists(path):
                self._count("miss")
                return None
            t0 = time.perf_counter()
            try:
                with open(path, "rb") as f:
                    rec = pickle.load(f)
                if not isinstance(rec, dict) or "exported" not in rec or "meta" not in rec:
                    raise ValueError("malformed envelope")
            except Exception:
                outcome = "corrupt"
                self._count("corrupt")
                try:
                    os.remove(path)
                except OSError:
                    pass
                return None
            stamps = _envelope_stamps()
            if {k: rec["meta"].get(k) for k in stamps} != stamps:
                # written by another jax/heat_tpu version, platform, world
                # size, or program-affecting gate roster: recompile (and
                # overwrite) rather than trust it
                outcome = "version_mismatch"
                self._count("version_mismatch")
                return None
            outcome = "hit"
            self._count("hit")
            if _telemetry._ENABLED:
                _telemetry.observe("serving.aot.load", time.perf_counter() - t0)
            return rec
        finally:
            _tracing.end_span(sp, outcome=outcome)

    def store(self, key: str, exported_bytes: bytes, out: Optional[dict],
              extra_meta: Optional[dict] = None) -> bool:
        """Atomically persist one envelope; never raises."""
        sp = _tracing.start_span(
            "aot.store", key=key, bytes=len(exported_bytes)
        ) if _tracing._ENABLED else None
        try:
            os.makedirs(self.root, exist_ok=True)
            meta = _envelope_stamps()
            if extra_meta:
                meta.update(extra_meta)
            rec = {"format": _FORMAT, "meta": meta, "exported": exported_bytes, "out": out}
            tmp = self.path(key) + f".tmp.{os.getpid()}"
            with open(tmp, "wb") as f:
                pickle.dump(rec, f)
            os.replace(tmp, self.path(key))
            self._count("store")
            _tracing.end_span(sp, outcome="store")
            return True
        except Exception:
            self._count("bypass")
            _tracing.end_span(sp, outcome="bypass")
            return False


def _wrap_exported(exported, donate_positions: Tuple[int, ...]):
    """The dispatchable callable over a deserialized artifact. The
    ``jax.jit`` wrapper re-applies the caller's donation (buffer reuse
    must survive the round trip) and, where the backend has a persistent
    executable cache, compiles from disk."""
    if donate_positions:
        return jax.jit(exported.call, donate_argnums=tuple(donate_positions))  # shardlint: ignore[SL202] -- AOT load wrapper, private by construction
    return jax.jit(exported.call)  # shardlint: ignore[SL202] -- AOT load wrapper, private by construction


# ---------------------------------------------------------------------- #
# ht.jit hooks                                                           #
# ---------------------------------------------------------------------- #
class JitHooks:
    """The object ``core/jit.py`` consults on ht-level cache misses.
    Both methods are contractually non-raising: any failure means
    "behave as if the cache did not exist"."""

    def __init__(self, store: AOTStore):
        self.aot = store

    # -- key ----------------------------------------------------------- #
    def _key_parts(self, fn, treedef, specs, donate_user) -> Optional[tuple]:
        parts = [("htjit", _FORMAT), _fn_ident(fn), ("treedef", str(treedef))]
        for kind, spec in specs:
            if kind == "dnd":
                parts.append((
                    "dnd", tuple(spec.gshape), spec.dtype.__name__, spec.split,
                    str(spec.device), _comm_desc(spec.comm),
                ))
            elif kind in ("jax", "np"):
                parts.append((kind,) + tuple(spec))
            else:
                stable = _stable_static(spec)
                if stable is None:
                    return None
                parts.append(("static", stable))
        parts.append(("donate", tuple(donate_user)))
        return tuple(parts) + _key_stamps()

    def _rebuild_context(self, specs):
        """device/comm for rebuilding output DNDarrays in the loading
        process — taken from the first DNDarray input (outputs live on
        the same mesh the inputs do)."""
        for kind, spec in specs:
            if kind == "dnd":
                return spec.device, spec.comm
        return None

    # -- load ---------------------------------------------------------- #
    def load(self, fn, treedef, specs, donate_user, donate_positions, jit_kwargs):
        try:
            if jit_kwargs:
                self.aot._count("bypass")
                return None
            parts = self._key_parts(fn, treedef, specs, donate_user)
            if parts is None:
                self.aot._count("bypass")
                return None
            rec = self.aot.load(self.aot.key(parts))
            if rec is None:
                return None
            from jax import export as _export

            exported = _export.deserialize(rec["exported"])
            if jax.default_backend() not in exported.platforms:
                self.aot._count("bypass")
                return None
            out = rec["out"]
            out_meta = []
            ctx = self._rebuild_context(specs)
            from ..core import types as _types

            for desc in out["meta"]:
                if desc is None:
                    out_meta.append(None)
                    continue
                _tag, gshape, dtype_name, split = desc
                if ctx is None:
                    # a DNDarray output with no DNDarray input to borrow
                    # device/comm from — unreachable for stored entries
                    # (store() bypasses this shape), guarded for safety
                    self.aot._count("bypass")
                    return None
                device, comm = ctx
                out_meta.append(
                    _ht_jit._DndSpec.from_meta(
                        gshape, getattr(_types, dtype_name), split, device, comm
                    )
                )
            call = _wrap_exported(exported, donate_positions)
            return (call, [(out["treedef"], out_meta)])
        except Exception:
            self.aot._count("bypass")
            return None

    # -- store --------------------------------------------------------- #
    def store_entry_shape_ok(self, specs, out_meta) -> bool:
        if any(m is not None for m in out_meta):
            return self._rebuild_context(specs) is not None
        return True

    def store(self, fn, treedef, specs, donate_user, donate_positions,
              jit_kwargs, jitted, traced_in, out_box):
        try:
            if jit_kwargs or not out_box:
                self.aot._count("bypass")
                return
            parts = self._key_parts(fn, treedef, specs, donate_user)
            if parts is None:
                self.aot._count("bypass")
                return
            out_treedef, out_meta = out_box[-1]
            if not self.store_entry_shape_ok(specs, out_meta):
                self.aot._count("bypass")
                return
            out_desc = [
                None if m is None else ("dnd", tuple(m.gshape), m.dtype.__name__, m.split)
                for m in out_meta
            ]
            from jax import export as _export

            t0 = time.perf_counter()
            exported = _export.export(jitted)(*_input_sds(traced_in))
            blob = exported.serialize()
            if _telemetry._ENABLED:
                _telemetry.observe("serving.aot.export", time.perf_counter() - t0)
            self.aot.store(
                self.aot.key(parts), blob,
                {"treedef": out_treedef, "meta": out_desc},
                extra_meta={"kind": "htjit", "fn": _fn_ident(fn)[0]},
            )
        except Exception:
            self.aot._count("bypass")


# ---------------------------------------------------------------------- #
# generic program-level API (estimator endpoints, warmup)                #
# ---------------------------------------------------------------------- #
def ensure_program(key_parts: tuple, build, example_args: Sequence,
                   donate_argnums: Tuple[int, ...] = ()):
    """A compiled callable for the program identified by ``key_parts``.

    On a store hit the serialized artifact is deserialized (no tracing
    of ``build``'s function at all); on a miss ``build()`` supplies the
    jitted program, which is exported against ``example_args``'s
    avals/shardings and persisted for the next process. With the cache
    disabled this is exactly ``build()``.

    ``example_args`` may be concrete arrays or ``jax.ShapeDtypeStruct``s.
    Returns ``(callable, "hit"|"store"|"off"|"bypass")``.
    """
    store = active_store()
    if store is None:
        return build(), "off"
    sds_in = _input_sds(example_args)
    # donation and input avals/shardings are key material exactly as in
    # JitHooks._key_parts: a donating variant or a differently-sharded
    # endpoint must never be served the other's artifact
    key = store.key(
        (("program", _FORMAT),) + tuple(key_parts)
        + (("donate", tuple(donate_argnums)),)
        + tuple(
            ("in", tuple(s.shape), str(s.dtype), str(getattr(s, "sharding", None)))
            for s in sds_in
        )
        + _key_stamps()
    )
    rec = store.load(key)
    if rec is not None:
        try:
            from jax import export as _export

            exported = _export.deserialize(rec["exported"])
            if jax.default_backend() in exported.platforms:
                return _wrap_exported(exported, donate_argnums), "hit"
            store._count("bypass")
        except Exception:
            store._count("bypass")
    jitted = build()
    if donate_argnums:
        # symmetric with the loaded path: the fresh program donates the
        # same buffers the _wrap_exported wrapper would
        jitted = jax.jit(jitted, donate_argnums=tuple(donate_argnums))  # shardlint: ignore[SL202] -- donation wrapper over an already-built program
    try:
        from jax import export as _export

        t0 = time.perf_counter()
        exported = _export.export(jitted)(*sds_in)
        blob = exported.serialize()
        if _telemetry._ENABLED:
            _telemetry.observe("serving.aot.export", time.perf_counter() - t0)
        stored = store.store(key, blob, None, extra_meta={"kind": "program", "key": repr(key_parts)})
        return jitted, ("store" if stored else "bypass")
    except Exception:
        store._count("bypass")
        return jitted, "bypass"


# ---------------------------------------------------------------------- #
# configuration / installation                                           #
# ---------------------------------------------------------------------- #
_ACTIVE: Optional[AOTStore] = None


def active_store() -> Optional[AOTStore]:
    """The installed :class:`AOTStore`, or ``None`` when serving AOT is
    off (the escape-hatch state: ``core/jit.py`` hooks uninstalled)."""
    return _ACTIVE


def enabled() -> bool:
    return _ACTIVE is not None


_XLA_CACHE_WIRED = False
_XLA_CACHE_SAVED: Optional[tuple] = None


def _reset_xla_cache_binding() -> None:
    """jax binds its persistent-cache object on first use; re-point it
    after a config change."""
    from jax.experimental.compilation_cache import compilation_cache as _cc

    _cc.reset_cache()


def _wire_xla_cache(root: str) -> None:
    """Point jax's persistent compilation cache under the store root so
    XLA executables are reused across processes too (TPU/GPU; a no-op
    store on CPU backends without executable-cache support). Stands back
    where a directory is already configured (the entry scripts'
    ``place_compile_cache`` or ``JAX_COMPILATION_CACHE_DIR``); undone on
    disable."""
    global _XLA_CACHE_WIRED, _XLA_CACHE_SAVED
    if jax.config.jax_compilation_cache_dir is None:
        _XLA_CACHE_SAVED = (
            jax.config.jax_persistent_cache_min_compile_time_secs,
            jax.config.jax_persistent_cache_min_entry_size_bytes,
        )
        os.makedirs(os.path.join(root, "xla"), exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", os.path.join(root, "xla"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        _reset_xla_cache_binding()
        _XLA_CACHE_WIRED = True


def _unwire_xla_cache() -> None:
    global _XLA_CACHE_WIRED, _XLA_CACHE_SAVED
    if not _XLA_CACHE_WIRED:
        return
    jax.config.update("jax_compilation_cache_dir", None)
    if _XLA_CACHE_SAVED is not None:
        # the floors are global knobs a user may rely on later —
        # restore, don't leave every sub-second compile cacheable
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", _XLA_CACHE_SAVED[0]
        )
        jax.config.update(
            "jax_persistent_cache_min_entry_size_bytes", _XLA_CACHE_SAVED[1]
        )
    _reset_xla_cache_binding()
    _XLA_CACHE_WIRED = False
    _XLA_CACHE_SAVED = None


def configure(cache_dir_: Optional[str] = None, enable: bool = True) -> Optional[AOTStore]:
    """Programmatic switch: install (``enable=True``) or uninstall the
    AOT hooks. Returns the active store (or ``None``)."""
    global _ACTIVE
    if not enable:
        _ACTIVE = None
        _ht_jit.install_aot_hooks(None)
        _unwire_xla_cache()
        return None
    root = cache_dir_ or cache_dir()
    _ACTIVE = AOTStore(root)
    _ht_jit.install_aot_hooks(JitHooks(_ACTIVE))
    _wire_xla_cache(root)
    return _ACTIVE


def _auto_configure() -> None:
    """Import-time gate resolution (see module docstring). The default —
    no serving env set — leaves the hooks uninstalled: tier-1 and every
    non-serving process run the exact pre-serving code paths."""
    mode = _gates.get("HEAT_TPU_SERVING_AOT")
    if _env_falsy(mode):
        return
    if _env_truthy(mode) or (
        _gates.is_set("HEAT_TPU_SERVING_CACHE") and mode in (None, "", "auto")
    ):
        configure()


_auto_configure()
