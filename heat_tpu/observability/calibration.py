"""Self-calibrating cost lattice (ISSUE 16) — probes, profiles, and
the model-error loop closure.

PR 15 shipped the instrument: spans joined against ``tier_time_model``
with signed per-leg ``model_error``. But every price the planner uses
is still a hard-coded constant in ``core.tiers`` (ICI 200e9, DCN 25e9,
PCIe 16e9, disk 0.8e9), so the measurement is reported and then
discarded. This module closes the loop (EQuARX's lesson,
arXiv:2506.17615 — measured behavior beats static models — applied to
the whole planner; arXiv:2112.01075's decomposition arithmetic is only
as good as the per-edge bandwidths it is priced with):

1. **Probe suite** — measure each lattice edge directly, bench.py
   style (repeat, keep the floor, flag wide dispersion as
   ``measurement_suspect``): ``hbm`` via an on-device copy, ``pcie``
   via the depth-2 staging stream (``device_put`` of host windows),
   ``ici``/``dcn`` via tiny collective programs per tier group, and
   ``disk`` via a slab read (the NVMe figure the ROADMAP's runtime
   item 4 prices at ~3 GB/s vs the fsync-inclusive 0.8e9 constant the
   durable-commit path keeps).
2. **Span ingestion** — fold the spans/attribution legs an ORDINARY
   traced run already records (staging windows carry ``tier`` +
   ``bytes`` + real wall; attribution legs carry measured seconds
   against modeled bytes) into per-edge bandwidth estimates: a
   deployment calibrates itself just by running.
3. **Lattice profile** — measurements persist as a versioned
   per-(platform, topology) JSON envelope stamped like the AOT store:
   a ``format`` version, integrity-checked by a sha256 ``profile_id``
   over the canonical measurement content. ``load_profile`` NEVER
   raises: a missing file is a miss, a tampered or version-mismatched
   file is counted, evicted (best-effort unlink), and the constants
   are used — a bad profile can degrade pricing back to the defaults,
   never take the library down.
4. **Loop closure proof** — :func:`calibration_report` re-judges one
   run's spans under both price sets and reports mean |model_error|
   constants-vs-calibrated per leg; ci.sh gates that the calibrated
   error is no larger.

The profile is ACTIVATED through the registry-declared gate
``HEAT_TPU_LATTICE_PROFILE`` (``core.gates``): unset, every price is
the constant and every plan/plan_id/program byte-identical to the
pre-calibration era (``core.tiers.active_profile`` short-circuits
without even importing this module); set, ``tiers.bandwidth()/
transfer_time()/penalty()`` consult the measured edges, the planner
re-prices candidate selection, and the ``profile_id`` is stamped into
plan canonical serialization (``Schedule.calibration``) so a
recalibration is a VISIBLE plan_id invalidation.

Import-light by design: stdlib + the gate registry + ``core.tiers``
only — jax and numpy load lazily inside the probes, so the plan-dump
scripts and ``tiers`` itself can import this module on any container.
"""

from __future__ import annotations

import hashlib
import json
import os
import tempfile
import threading
import time

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from ..core import gates as _gates
from ..core import tiers as _tiers
from ..version import __version__

__all__ = [
    "PROBE_EDGES",
    "build_profile",
    "calibrate",
    "calibration_report",
    "describe_profile",
    "ingest_attribution",
    "ingest_spans",
    "load_profile",
    "probe_collective",
    "probe_disk",
    "probe_hbm",
    "probe_pcie",
    "profile_digest",
    "run_probes",
    "save_profile",
    "stats",
]

#: envelope format version — bumped on any layout change; a mismatched
#: profile is version_mismatch (evicted, constants used), exactly the
#: AOT store's discipline.
_FORMAT = 1

#: every edge the probe suite can measure (== the lattice's edge set).
PROBE_EDGES: Tuple[str, ...] = tuple(sorted(_tiers.EDGES))

#: default probe payload — big enough to amortize dispatch, small
#: enough for the CPU CI container.
_PROBE_BYTES = 32 << 20
_COLLECTIVE_BYTES = 4 << 20
_REPEATS = 3

_stats_lock = threading.Lock()
_stats: Dict[str, int] = {
    "load": 0, "hit": 0, "miss": 0, "corrupt": 0,
    "tampered": 0, "version_mismatch": 0,
}


def stats() -> Dict[str, int]:
    """Profile-loader outcome counters (AOT-store style): ``hit``,
    ``miss`` (no file), ``corrupt`` (unparseable — evicted),
    ``tampered`` (digest mismatch — evicted), ``version_mismatch``
    (format bump — evicted)."""
    with _stats_lock:
        return dict(_stats)


def _count(key: str) -> None:
    with _stats_lock:
        _stats[key] += 1


def reset_stats() -> None:
    with _stats_lock:
        for k in _stats:
            _stats[k] = 0


# --------------------------------------------------------------------- #
# the envelope                                                          #
# --------------------------------------------------------------------- #
def profile_digest(platform: str, topology: str, edges: Dict[str, Any]) -> str:
    """sha256 prefix over the canonical measurement content — format,
    platform, topology, and the per-edge records (sorted keys, compact
    separators, same discipline as ``Schedule.canonical_json``). The
    library version is stamped in the envelope but kept OUT of the
    digest: re-saving the same measurements under a new heat_tpu
    release must not silently re-key every plan."""
    content = {
        "format": _FORMAT,
        "platform": platform,
        "topology": topology,
        "edges": edges,
    }
    blob = json.dumps(content, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def build_profile(
    edges: Dict[str, Any],
    platform: Optional[str] = None,
    topology: Optional[str] = None,
) -> Dict[str, Any]:
    """Assemble the versioned envelope from per-edge records.

    ``edges``: ``{edge: {"bps": float, "method": str, "samples":
    [...], "measurement_suspect": bool}}`` — only measured edges
    appear; unmeasured edges keep their constants at pricing time
    (``tiers.bandwidth`` falls through per edge). ``platform``/
    ``topology`` default to the live jax backend and the ambient
    resolved topology when importable, else ``"unknown"``/``"flat"``.
    """
    clean: Dict[str, Dict[str, Any]] = {}
    for name in sorted(edges):
        if name not in _tiers.EDGES:
            raise ValueError(
                f"build_profile: unknown lattice edge {name!r} "
                f"(one of {PROBE_EDGES})"
            )
        rec = dict(edges[name])
        bps = float(rec["bps"])
        if not bps > 0:
            raise ValueError(f"build_profile: edge {name!r} bps must be > 0, got {bps}")
        rec["bps"] = round(bps, 1)
        if "samples" in rec:
            rec["samples"] = [round(float(s), 1) for s in rec["samples"]]
        rec.setdefault("measurement_suspect", False)
        clean[name] = rec
    if platform is None:
        try:
            import jax

            platform = jax.default_backend()
        except Exception:
            platform = "unknown"
    if topology is None:
        topology = _gates.get("HEAT_TPU_TOPOLOGY", "") or "flat"
    envelope = {
        "format": _FORMAT,
        "kind": "lattice-profile",
        "heat_tpu": __version__,
        "platform": str(platform),
        "topology": str(topology),
        "edges": clean,
        "profile_id": profile_digest(str(platform), str(topology), clean),
    }
    return envelope


def save_profile(profile: Dict[str, Any], path: str) -> str:
    """Persist an envelope atomically (``tmp.{pid}`` + ``os.replace``,
    the AOT store's write discipline) and return the path."""
    path = os.path.expanduser(str(path))
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(profile, f, sort_keys=True, indent=1)
        f.write("\n")
    os.replace(tmp, path)
    return path


def _evict(path: str) -> None:
    try:
        os.remove(path)
    except OSError:
        pass


def load_profile(path: str) -> Optional[Dict[str, Any]]:
    """Load + integrity-check a profile envelope; ``None`` on ANY
    failure — the caller falls back to the constants, never errors.

    - missing file -> ``miss``;
    - unparseable / wrong shape -> ``corrupt``: evicted (best-effort
      unlink) so the next run is a clean miss;
    - ``format`` != current -> ``version_mismatch``: evicted (a stale
      profile must be re-measured, not re-interpreted);
    - recomputed digest != stored ``profile_id`` -> ``tampered``:
      evicted (the sha256 stamp IS the trust boundary — an edited
      price must never silently re-route the planner).
    """
    _count("load")
    path = os.path.expanduser(str(path))
    if not os.path.exists(path):
        _count("miss")
        return None
    try:
        with open(path) as f:
            doc = json.load(f)
        if not isinstance(doc, dict):
            raise ValueError("not an object")
        edges = doc["edges"]
        if not isinstance(edges, dict) or not edges:
            raise ValueError("no edges")
        for name, rec in edges.items():
            if name not in _tiers.EDGES:
                raise ValueError(f"unknown edge {name!r}")
            if not float(rec["bps"]) > 0:
                raise ValueError(f"edge {name!r} bps not positive")
        fmt = doc["format"]
        platform, topology = str(doc["platform"]), str(doc["topology"])
        pid = str(doc["profile_id"])
    except Exception:
        _count("corrupt")
        _evict(path)
        return None
    if fmt != _FORMAT:
        _count("version_mismatch")
        _evict(path)
        return None
    if profile_digest(platform, topology, edges) != pid:
        _count("tampered")
        _evict(path)
        return None
    _count("hit")
    return doc


# --------------------------------------------------------------------- #
# the probe suite                                                       #
# --------------------------------------------------------------------- #
def _floor_retry(
    one: Callable[[], Tuple[int, float]], repeats: int
) -> Optional[Dict[str, Any]]:
    """bench.py's measurement discipline: run ``one`` (-> moved bytes,
    seconds) ``repeats`` times, keep the BEST bandwidth (the floor of
    the timing noise), and flag the record ``measurement_suspect``
    when the median lands below half the best — a dispersion that wide
    means the number is weather, not hardware."""
    samples: List[float] = []
    for _ in range(max(1, int(repeats))):
        nbytes, dt = one()
        if dt > 0 and nbytes > 0:
            samples.append(nbytes / dt)
    if not samples:
        return None
    best = max(samples)
    median = sorted(samples)[len(samples) // 2]
    return {
        "bps": best,
        "samples": samples,
        "measurement_suspect": bool(len(samples) < 2 or median < 0.5 * best),
    }


def _copy_probe_fn():
    """Program builder for the on-device copy probe.  Deliberately a
    bare ``jax.jit``: the probe measures the raw stream, so it must not
    route through ht.jit's donation/telemetry hooks."""
    import jax

    return jax.jit(lambda a: a + 1.0)


def probe_hbm(
    nbytes: int = _PROBE_BYTES, repeats: int = _REPEATS
) -> Optional[Dict[str, Any]]:
    """The device-memory stream edge: time an on-device elementwise
    copy (one read + one write of the operand — 2x the payload) on a
    warmed jitted program. On TPU this is the HBM stream; on the CPU
    container it is host memcpy bandwidth — either way it is the
    number ``transfer_time(_, "hbm")`` should charge THIS deployment.
    """
    import jax
    import jax.numpy as jnp

    n = max(1, int(nbytes) // 4)
    x = jnp.zeros((n,), dtype=jnp.float32)
    f = _copy_probe_fn()
    f(x).block_until_ready()  # warm the program

    def one() -> Tuple[int, float]:
        t0 = time.perf_counter()
        f(x).block_until_ready()
        return 2 * n * 4, time.perf_counter() - t0

    rec = _floor_retry(one, repeats)
    if rec:
        rec["method"] = "probe:on-device-copy"
    return rec


def probe_pcie(
    nbytes: int = _PROBE_BYTES, repeats: int = _REPEATS
) -> Optional[Dict[str, Any]]:
    """The host->device staging edge, measured the way the depth-2
    staging executor drives it: ``jax.device_put`` of a host-resident
    window, fenced. On TPU this is PCIe DMA; on CPU it is the
    host->device copy jax actually performs — the price a staged
    window really pays here."""
    import jax
    import numpy as np

    n = max(1, int(nbytes) // 4)
    host = np.zeros((n,), dtype=np.float32)
    jax.device_put(host).block_until_ready()  # warm the transfer path

    def one() -> Tuple[int, float]:
        t0 = time.perf_counter()
        jax.device_put(host).block_until_ready()
        return n * 4, time.perf_counter() - t0

    rec = _floor_retry(one, repeats)
    if rec:
        rec["method"] = "probe:device_put-stream"
    return rec


def probe_disk(
    nbytes: int = _PROBE_BYTES,
    repeats: int = _REPEATS,
    directory: Optional[str] = None,
) -> Optional[Dict[str, Any]]:
    """The host<->persistent-store edge via a SLAB READ — the
    non-durable staging figure (NVMe streams 3+ GB/s) the ROADMAP
    tracks separately from the fsync-inclusive 0.8e9 durable-commit
    constant. The OS page cache is visible to a re-read, which is
    exactly what a staging loop re-reading a hot slab sees; the floor/
    suspect discipline still flags a flapping medium."""
    buf = bytearray(max(1, int(nbytes)))
    fd, path = tempfile.mkstemp(prefix="heat_tpu_disk_probe_", dir=directory)
    try:
        with os.fdopen(fd, "wb") as f:
            f.write(bytes(len(buf)))
            f.flush()
            os.fsync(f.fileno())

        def one() -> Tuple[int, float]:
            t0 = time.perf_counter()
            with open(path, "rb", buffering=0) as f:
                got = f.readinto(buf)
            return int(got or 0), time.perf_counter() - t0

        rec = _floor_retry(one, repeats)
        if rec:
            rec["method"] = "probe:slab-read"
        return rec
    finally:
        _evict(path)


def _all_gather_probe_fn(mesh):
    """Program builder for the wire probe: a tiled all_gather over the
    probe mesh.  Bare ``jax.jit`` on purpose — routing the probe through
    ht.jit's donation/telemetry hooks would perturb the timing."""
    import jax

    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    return jax.jit(
        shard_map(
            lambda a: jax.lax.all_gather(a, "probe", tiled=True),
            mesh=mesh,
            in_specs=P("probe"),
            out_specs=P(None),
            # the installed vma checker cannot infer that a tiled
            # all_gather's result is replicated
            check_vma=False,
        )
    )


def probe_collective(
    edge: str,
    nbytes: int = _COLLECTIVE_BYTES,
    repeats: int = _REPEATS,
) -> Optional[Dict[str, Any]]:
    """The wire edges, measured with a tiny collective program per
    TIER GROUP (``core.communication.Topology``): ``ici`` runs an
    all_gather across one slice's chips (every chip of a flat mesh),
    ``dcn`` across one chip per slice — the same replica-group
    factorization the hierarchical plans exchange over. ``None`` when
    the mesh cannot express the edge (one device, or a flat topology
    asked for dcn) — the profile simply keeps the constant."""
    if edge not in ("ici", "dcn"):
        raise ValueError(f"probe_collective measures wire edges, got {edge!r}")
    import jax
    import jax.numpy as jnp

    from jax.sharding import Mesh

    from ..core import communication as _comm

    devices = jax.devices()
    topo = _comm.topology_for(len(devices), None)
    if edge == "ici":
        group = topo.chip_axis_groups()[0] if topo.tiered else list(range(len(devices)))
    else:
        if not topo.tiered:
            return None
        group = topo.slice_axis_groups()[0]
    if len(group) < 2:
        return None
    import numpy as np

    mesh_devs = np.array([devices[i] for i in group])
    mesh = Mesh(mesh_devs, ("probe",))
    g = len(group)
    n = max(g, (int(nbytes) // 4 // g) * g)  # g-divisible element count
    x = jnp.zeros((n,), dtype=jnp.float32)

    fn = _all_gather_probe_fn(mesh)
    fn(x).block_until_ready()  # warm the program
    # per-device wire traffic of an all_gather: each chip receives the
    # other (g-1) shards
    wire = (n // g) * 4 * (g - 1)

    def one() -> Tuple[int, float]:
        t0 = time.perf_counter()
        fn(x).block_until_ready()
        return wire, time.perf_counter() - t0

    rec = _floor_retry(one, repeats)
    if rec:
        rec["method"] = f"probe:all_gather[{g}dev]"
    return rec


def run_probes(
    edges: Optional[Sequence[str]] = None,
    nbytes: int = _PROBE_BYTES,
    repeats: int = _REPEATS,
) -> Dict[str, Dict[str, Any]]:
    """Run every requested probe (default: all five edges) and return
    the per-edge records. A probe that cannot run on this container
    (no second device, no slice structure) or that errors simply
    leaves its edge out — pricing falls back to the constant, the
    suite never fails."""
    out: Dict[str, Dict[str, Any]] = {}
    for edge in edges if edges is not None else PROBE_EDGES:
        try:
            if edge == "hbm":
                rec = probe_hbm(nbytes, repeats)
            elif edge == "pcie":
                rec = probe_pcie(nbytes, repeats)
            elif edge == "disk":
                rec = probe_disk(nbytes, repeats)
            elif edge in ("ici", "dcn"):
                rec = probe_collective(edge, min(nbytes, _COLLECTIVE_BYTES), repeats)
            else:
                raise ValueError(f"run_probes: unknown edge {edge!r}")
        except ValueError:
            raise
        except Exception:  # a failed probe is a missing measurement, not a crash
            rec = None
        if rec is not None:
            out[edge] = rec
    return out


# --------------------------------------------------------------------- #
# span / attribution ingestion — calibrate by just running              #
# --------------------------------------------------------------------- #
def ingest_spans(
    span_rows: Optional[List[Dict[str, Any]]] = None,
) -> Dict[str, List[float]]:
    """Per-edge bandwidth samples from the spans an ordinary traced
    run records: every REAL-wall span (not a trace-census probe)
    carrying a lattice ``tier`` and a ``bytes`` payload — the staging
    executor's ``stage_in`` windows are the canonical source — yields
    one ``bytes/dur`` sample on its edge."""
    from . import tracing as _tracing

    rows = _tracing.spans() if span_rows is None else list(span_rows)
    samples: Dict[str, List[float]] = {}
    for r in rows:
        attrs = r.get("attrs") or {}
        tier = attrs.get("tier")
        nbytes = attrs.get("bytes")
        dur = r.get("dur_s")
        if attrs.get("traced") or tier not in _tiers.EDGES:
            continue
        if not nbytes or not dur or dur <= 0:
            continue
        samples.setdefault(tier, []).append(float(nbytes) / float(dur))
    return samples


def ingest_attribution(
    reports: Sequence[Dict[str, Any]],
) -> Dict[str, List[float]]:
    """Per-edge bandwidth samples from :func:`~heat_tpu.observability.
    attribution.attribution` reports: a measured tier leg against the
    model's byte count for that tier is one ``tier_bytes/measured_s``
    sample — the per-leg join PR 15 already computes, folded back into
    a price instead of discarded."""
    samples: Dict[str, List[float]] = {}
    for rep in reports:
        model = rep.get("model") or {}
        for leg in rep.get("legs") or []:
            tier = leg.get("tier")
            measured = leg.get("measured_s")
            if tier not in _tiers.EDGES or not measured or measured <= 0:
                continue
            nbytes = model.get(f"{tier}_bytes")
            if nbytes:
                samples.setdefault(tier, []).append(float(nbytes) / float(measured))
    return samples


def _fold_samples(
    probed: Dict[str, Dict[str, Any]],
    ingested: Dict[str, List[float]],
) -> Dict[str, Dict[str, Any]]:
    """Merge probe records with ingested samples: an edge both paths
    measured keeps the probe's record and appends the ingested
    samples to its floor; an edge only the spans saw becomes a
    ``spans`` record under the same floor/suspect discipline."""
    out = {k: dict(v) for k, v in probed.items()}
    for edge, samples in ingested.items():
        samples = [s for s in samples if s > 0]
        if not samples:
            continue
        if edge in out:
            merged = list(out[edge].get("samples") or []) + samples
            best = max(merged)
            median = sorted(merged)[len(merged) // 2]
            out[edge]["samples"] = merged
            out[edge]["bps"] = best
            out[edge]["measurement_suspect"] = bool(median < 0.5 * best)
            out[edge]["method"] = f"{out[edge].get('method', 'probe')}+spans"
        else:
            best = max(samples)
            median = sorted(samples)[len(samples) // 2]
            out[edge] = {
                "bps": best,
                "samples": samples,
                "measurement_suspect": bool(len(samples) < 2 or median < 0.5 * best),
                "method": "spans",
            }
    return out


def calibrate(
    path: Optional[str] = None,
    edges: Optional[Sequence[str]] = None,
    nbytes: int = _PROBE_BYTES,
    repeats: int = _REPEATS,
    span_rows: Optional[List[Dict[str, Any]]] = None,
    include_spans: bool = True,
    platform: Optional[str] = None,
    topology: Optional[str] = None,
) -> Dict[str, Any]:
    """The full calibration pass: run the probe suite, fold in the
    span samples the current trace buffer (or ``span_rows``) carries,
    build the stamped envelope, and persist it to ``path`` when given.
    Returns the envelope (``profile_id`` included) — point
    ``HEAT_TPU_LATTICE_PROFILE`` at the saved path to activate it."""
    probed = run_probes(edges, nbytes, repeats)
    ingested = ingest_spans(span_rows) if include_spans else {}
    if edges is not None:
        ingested = {k: v for k, v in ingested.items() if k in set(edges)}
    folded = _fold_samples(probed, ingested)
    if not folded:
        raise RuntimeError(
            "calibrate: no edge could be measured on this container "
            "(no devices, no spans) — nothing to profile"
        )
    profile = build_profile(folded, platform=platform, topology=topology)
    if path:
        save_profile(profile, path)
    return profile


# --------------------------------------------------------------------- #
# loop-closure proof                                                    #
# --------------------------------------------------------------------- #
def calibration_report(
    plan,
    span_rows: Optional[List[Dict[str, Any]]] = None,
    profile: Optional[Dict[str, Any]] = None,
) -> Dict[str, Any]:
    """Does calibration actually shrink the model error? Re-judge one
    run's spans under BOTH price sets — the constants column
    (``model_error``) and the calibrated column (``calibrated_error``)
    that :func:`~heat_tpu.observability.attribution.attribution` adds
    when a profile is in reach (explicit ``profile=``, the plan's own
    ``calibration`` annotation, or the ambient gate) — and report the
    per-leg pair plus the means. ``improved`` is the CI gate's
    criterion: mean |calibrated error| <= mean |constants error| over
    every leg that carries both columns."""
    import importlib

    # the package attr `attribution` is the FUNCTION (the documented
    # call shape); the module must come via importlib
    _attribution_mod = importlib.import_module(
        "heat_tpu.observability.attribution"
    )

    rep = _attribution_mod.attribution(plan, span_rows, profile=profile)
    legs = [
        {
            "step": leg["step"],
            "tier": leg.get("tier"),
            "model_error": leg["model_error"],
            "calibrated_error": leg["calibrated_error"],
        }
        for leg in rep["legs"]
        if "model_error" in leg and "calibrated_error" in leg
    ]
    cal = (rep["model"].get("calibrated") or {})
    out: Dict[str, Any] = {
        "plan_id": rep["plan_id"],
        "profile_id": cal.get("profile_id"),
        "n_legs": len(legs),
        "legs": legs,
    }
    if legs:
        before = sum(abs(l["model_error"]) for l in legs) / len(legs)
        after = sum(abs(l["calibrated_error"]) for l in legs) / len(legs)
        out["mean_abs_error_constants"] = round(before, 4)
        out["mean_abs_error_calibrated"] = round(after, 4)
        out["improved"] = bool(after <= before)
    return out


def describe_profile(profile: Dict[str, Any]) -> str:
    """Constants-vs-measured table of one envelope — what
    ``scripts/calibrate.py`` prints (the PERF.md baseline->bound->beat
    evidence row)."""
    lines = [
        f"lattice profile {profile['profile_id']}  "
        f"platform={profile['platform']}  topology={profile['topology']}  "
        f"(format {profile['format']}, heat_tpu {profile['heat_tpu']})",
        f"  {'edge':>5}  {'constant':>12}  {'measured':>12}  {'ratio':>7}  method",
    ]
    for edge in PROBE_EDGES:
        const = _tiers.EDGES[edge][2]
        rec = profile["edges"].get(edge)
        if rec is None:
            lines.append(
                f"  {edge:>5}  {const / 1e9:>10.2f}GB  {'(constant)':>12}"
            )
            continue
        bps = float(rec["bps"])
        suspect = "  [suspect]" if rec.get("measurement_suspect") else ""
        lines.append(
            f"  {edge:>5}  {const / 1e9:>10.2f}GB  {bps / 1e9:>10.2f}GB  "
            f"{bps / const:>6.2f}x  {rec.get('method', '?')}{suspect}"
        )
    return "\n".join(lines)
