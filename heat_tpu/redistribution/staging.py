"""Out-of-core staging executor — larger-than-HBM operands (ISSUE 11).

Every array in the framework used to have to fit in HBM. Following
"Distributed linear algebra at hundreds of GB on TPUs" (arXiv:2112.09017
— host-resident operands streamed through HBM under compute), this
module opens the scenario class the reference cannot touch: operands
live on the HOST tier of the memory-tier lattice (``core.tiers``) —
pinned host RAM or an HDF5 dataset (``core.io``) — and the
pass-structured algorithms that already think in passes-over-A
(2-pass/1-pass ``hsvd_rank``, streaming ``KMeans.partial_fit``) consume
them window at a time:

- a :class:`HostArray` handle holds the host-resident operand;
- :func:`plan_staged_passes` builds a ``host-staging``
  :class:`~heat_tpu.redistribution.schedule.Schedule` whose
  ``stage_in``/``stage_out`` steps (tier ``"pcie"``) describe the
  (8,128)-tile-aligned windows each pass streams, priced by the lattice
  (``tiers.transfer_time``) and carrying a ``staging`` annotation with
  the depth-2 critical-path model;
- :func:`prove_fits` proves the window schedule's HBM slab peak within
  ``tiers.capacity("hbm")`` via ``Schedule.liveness()`` — the PR-10
  oracle, now gating execution, with ``ht.analysis.verify_plan``
  checking the same invariants symbolically;
- :func:`stream_windows` runs the depth-2 double-buffered loop:
  ``jax.device_put`` of window k+1 is issued BEFORE window k's compute
  consumes the slab, so the PCIe transfer hides under compute exactly
  like the PR-6 chunk pipelines hide copies under wire.

Gate: ``HEAT_TPU_OOC`` — ``0`` disables staging (HostArray operands
are materialized whole when they fit the HBM budget; the exact-bit
escape hatch), ``1`` forces the staged program forms even for fitting
device arrays (the CI leg), ``auto`` (default) stages HostArray
operands and leaves device arrays on their existing in-HBM paths.

BIT-IDENTITY BY CONSTRUCTION: the staged numerics are the in-HBM
numerics. The hsvd sketch passes are expressed as fixed-grain tiled
streams (``svdtools``' ``_pass1_tiles``/``_pass2_tiles``/
``_oneview_tiles`` — 512-wide tiles with explicit carries), window
extents are multiples of the same grain (only the global tail window
is ragged), and every per-tile contraction is therefore the same-shaped
dot on the same data whether the loop runs inside one in-HBM program or
across staged windows. XLA's gemm kernel choice is shape-dependent
(measured: a 128-wide tail gemm reassociates differently than the same
columns inside a 1024-wide gemm), so the shared grain — not luck — is
what the pinned staged-vs-in-HBM bit-identity sweep relies on.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core import gates as _gates
from ..observability import events as _obs_events
from ..observability import telemetry as _telemetry
from ..observability import tracing as _tracing
from .schedule import Schedule, Step
from .spec import RedistSpec

__all__ = [
    "DEFAULT_SLAB_MB",
    "GRAIN",
    "HostArray",
    "OOC_ENV",
    "SLAB_ENV",
    "golden_staged_plans",
    "materialize",
    "ooc_engaged",
    "ooc_mode",
    "plan_staged_passes",
    "prove_fits",
    "slab_bytes",
    "stream_windows",
    "window_extents",
]

OOC_ENV = "HEAT_TPU_OOC"
SLAB_ENV = "HEAT_TPU_OOC_SLAB_MB"

#: default HBM slab for the double-buffered windows (two windows in
#: flight). 256 MiB ≈ 16 ms of PCIe per window at the v5e edge — big
#: enough to amortize dispatch, small next to the 16 GiB budget.
DEFAULT_SLAB_MB = 256

#: window grain per axis: (sublane, lane) = the (8,128) TPU tile, times
#: the 64x/4x factors that make the grain match the 512-wide pass tiles
#: of the hsvd streams (``svdtools._PASS_TILE``). Window extents are
#: multiples of the grain — except the global tail — which is BOTH the
#: (8,128)-tile alignment the HBM slab layout wants AND the shared tile
#: sequence the bit-identity contract needs.
GRAIN = (512, 512)


# --------------------------------------------------------------------- #
# the gate                                                              #
# --------------------------------------------------------------------- #
def ooc_mode() -> str:
    """Resolved ``HEAT_TPU_OOC`` mode (``"0"``/``"1"``/``"auto"``).
    ``0`` disables staging everywhere (HostArray operands materialize
    whole when they fit — the exact-bit escape hatch); ``1`` forces the
    staged window pipeline even for in-HBM device operands on the
    supported paths (the CI leg: every windowed program form executes,
    and the results are pinned bit-identical to the in-HBM forms);
    ``auto`` (default) stages host-resident operands only."""
    v = _gates.get(OOC_ENV, "auto").strip().lower()
    if v in ("0", "off", "false", "no"):
        return "0"
    if v in ("1", "on", "true", "force", "yes"):
        return "1"
    return "auto"


def ooc_engaged(nbytes: int, host_resident: bool = False) -> bool:
    """Does the gate stage an operand of ``nbytes``? Mode ``1`` stages
    every supported operand; ``auto`` stages host-resident operands
    (they cannot run any other way) and leaves device arrays on the
    in-HBM paths; ``0`` never stages."""
    mode = ooc_mode()
    if mode == "0":
        return False
    if mode == "1":
        return True
    return bool(host_resident)


def slab_bytes(override: Optional[int] = None) -> int:
    """HBM slab budget for the double-buffered windows
    (``HEAT_TPU_OOC_SLAB_MB``, default 256 MiB), never more than a
    quarter of ``tiers.capacity("hbm")`` so outputs and workspace keep
    headroom under the liveness proof."""
    from ..core import tiers as _tiers

    if override is not None:
        return max(1, int(override))
    raw = _gates.get(SLAB_ENV, "")
    try:
        mb = int(raw) if raw.strip() else DEFAULT_SLAB_MB
    except ValueError:
        mb = DEFAULT_SLAB_MB
    return max(1 << 20, min(max(1, mb) << 20, _tiers.capacity("hbm") // 4))


# --------------------------------------------------------------------- #
# host-tier operands                                                    #
# --------------------------------------------------------------------- #
class HostArray:
    """A host-tier operand: data resident in (pinned) host RAM or an
    HDF5 dataset, streamed through HBM window by window instead of ever
    being materialized on device.

    Wraps any 2-D array-like with ``shape``/``dtype`` and numpy-style
    slicing — an ``np.ndarray`` (kept C-contiguous so ``stage_in``
    windows are single memcpy-class reads over PCIe) or an ``h5py``
    dataset (windows read straight off disk; ``from_hdf5``). The
    framework's staged paths (``linalg.hsvd_rank``, ``KMeans.fit``/
    ``partial_fit``) accept it wherever a pass-structured stream can
    serve the algorithm.
    """

    def __init__(self, data: Any, dtype=None):
        if isinstance(data, np.ndarray):
            data = np.ascontiguousarray(data if dtype is None else data.astype(dtype, copy=False))
        elif dtype is not None and np.dtype(getattr(data, "dtype", dtype)) != np.dtype(dtype):
            raise TypeError(
                "HostArray: dtype override is only supported for numpy inputs "
                f"(got {type(data).__name__})"
            )
        shape = tuple(int(s) for s in data.shape)
        if len(shape) != 2:
            raise ValueError(f"HostArray serves 2-D operands, got shape {shape}")
        self._data = data
        self.shape = shape
        self.dtype = np.dtype(data.dtype)

    @classmethod
    def from_hdf5(cls, path: str, dataset: str) -> "HostArray":
        """Open an HDF5 dataset as a host-tier operand — windows are
        read lazily, so operands larger than host RAM stream from disk
        (the ``PartialH5Dataset`` scenario of the reference, served by
        the lattice's host tier instead of per-rank reads)."""
        import h5py

        return cls(h5py.File(path, "r")[dataset])

    @property
    def ndim(self) -> int:
        return 2

    @property
    def nbytes(self) -> int:
        return self.shape[0] * self.shape[1] * self.dtype.itemsize

    def window(self, axis: int, start: int, stop: int) -> np.ndarray:
        """One contiguous window along ``axis`` as a host ndarray —
        what ``stage_in`` transfers."""
        sl = (slice(start, stop), slice(None)) if axis == 0 else (slice(None), slice(start, stop))
        return np.asarray(self._data[sl])

    def __repr__(self) -> str:
        return f"HostArray(shape={self.shape}, dtype={self.dtype.name}, tier=host)"


# --------------------------------------------------------------------- #
# window geometry                                                       #
# --------------------------------------------------------------------- #
def window_extents(
    shape: Tuple[int, int],
    itemsize: int,
    axis: int,
    slab: int,
    grain: Optional[int] = None,
) -> List[Tuple[int, int]]:
    """``(start, stop)`` windows along ``axis``: extents are multiples
    of the grain (``GRAIN[axis]``), each window's bytes at most half
    the ``slab`` (two windows in flight at depth 2), and only the
    global tail window is ragged — the alignment contract the
    bit-identity construction and the (8,128) slab layout share. An
    operand whose cross-extent makes even one grain exceed the slab
    still windows at one grain; the liveness proof then rejects the
    schedule rather than silently splitting below the grain."""
    extent = int(shape[axis])
    other = int(shape[1 - axis])
    g = int(GRAIN[axis] if grain is None else grain)
    per_unit = other * int(itemsize)
    per_window = max(1, (int(slab) // 2) // max(per_unit, 1))
    width = max(g, per_window // g * g)
    out: List[Tuple[int, int]] = []
    start = 0
    while start + width <= extent:
        out.append((start, start + width))
        start += width
    if start < extent or not out:
        out.append((start, extent))
    return out


def _win_bytes(shape: Tuple[int, int], itemsize: int, axis: int, win: Tuple[int, int]) -> int:
    other = int(shape[1 - axis])
    return (win[1] - win[0]) * other * int(itemsize)


# --------------------------------------------------------------------- #
# the staged plan                                                       #
# --------------------------------------------------------------------- #
def plan_staged_passes(
    shape,
    dtype,
    passes: Sequence[Dict[str, Any]],
    *,
    slab: Optional[int] = None,
    out_bytes: int = 0,
    mesh_size: int = 1,
    hbm_bytes: Optional[int] = None,
) -> Schedule:
    """Build the ``host-staging`` Schedule for a host-resident operand
    streamed by ``passes`` — each ``{"tag", "axis", "writeback"?}``
    describes one pass over the operand (the hsvd 2-pass schedule is
    ``[{"tag": "sketch", "axis": 1}, {"tag": "project", "axis": 0}]``).

    Steps: per pass, one ``stage_in`` (tier ``"pcie"``) per window —
    ``peak_bytes`` is the slab OCCUPANCY at that step (this window plus
    the depth-2 prefetch of the next) — plus a ``stage_out`` when the
    pass writes per-window results back to host. ``out_bytes`` is the
    HBM-resident working set held ACROSS the loop (sketch factors,
    centroids — the annotation's ``resident_bytes``), so
    ``Schedule.liveness_peak_bytes`` is exactly what :func:`prove_fits`
    holds under ``tiers.capacity("hbm")``.

    The ``staging`` annotation carries the lattice pricing: total pcie
    seconds (``tiers.transfer_time``), the HBM-stream compute model,
    and the depth-2 critical path ``max(pcie, hbm) + min(pcie, hbm)/n``
    (the first/last window's exposed leg) — ``model_speedup`` is the
    sequential/critical-path ratio, same convention as the overlap
    annotation. Deterministic pure Python: the golden staged plans ride
    the ci.sh determinism + verify_plan sweeps."""
    from ..core import tiers as _tiers

    shape = tuple(int(s) for s in shape)
    if len(shape) != 2:
        raise ValueError(f"plan_staged_passes serves 2-D operands, got {shape}")
    dtype = np.dtype(dtype)
    slab_b = slab_bytes(slab)
    # the hbm budget this plan was SIZED against, recorded in the
    # annotation: verify_plan proves fit against the recorded number
    # (well-formedness stays environment-independent — golden dumps pin
    # it explicitly), while prove_fits re-checks the AMBIENT capacity at
    # execution time
    hbm_cap = _tiers.capacity("hbm") if hbm_bytes is None else max(1, int(hbm_bytes))
    spec = RedistSpec.normalize(shape, dtype.name, None, None, int(mesh_size))
    host_bytes = spec.logical_bytes

    steps: List[Step] = []
    pass_meta: List[Dict[str, Any]] = []
    pcie_total = 0
    max_window = 0
    for p in passes:
        axis = int(p["axis"])
        tag = str(p.get("tag", f"pass{len(pass_meta)}"))
        writeback = bool(p.get("writeback", False))
        wins = window_extents(shape, dtype.itemsize, axis, slab_b)
        wb = [_win_bytes(shape, dtype.itemsize, axis, w) for w in wins]
        max_window = max(max_window, max(wb))
        n = len(wins)
        for k, (w, b) in enumerate(zip(wins, wb)):
            occupancy = b + (wb[k + 1] if k + 1 < n else 0)
            steps.append(
                Step(
                    "stage_in",
                    bytes_moved=b,
                    peak_bytes=occupancy,
                    detail=(
                        f"{tag}: window {k}/{n} axis-{axis} "
                        f"[{w[0]}:{w[1]}) host->hbm (depth-2 prefetch)"
                    ),
                    chunk=k,
                    overlap=tag if n > 1 else None,
                    tier="pcie",
                )
            )
            if writeback:
                steps.append(
                    Step(
                        "stage_out",
                        bytes_moved=b,
                        peak_bytes=occupancy,
                        detail=f"{tag}: window {k}/{n} result hbm->host",
                        chunk=k,
                        overlap=tag if n > 1 else None,
                        tier="pcie",
                    )
                )
            pcie_total += b * (2 if writeback else 1)
        pass_meta.append(
            {
                "tag": tag,
                "axis": axis,
                "n_windows": n,
                "window_bytes": max(wb),
                "pcie_bytes": sum(wb) * (2 if writeback else 1),
                "writeback": writeback,
            }
        )

    n_total = sum(pm["n_windows"] for pm in pass_meta)
    # lattice pricing: the streamed bytes cross pcie once per pass and
    # the compute consumes them from HBM once per pass — at depth 2 the
    # slower leg governs, the faster leg is exposed only on the
    # first/last window. Derived from the ROUNDED legs so the verifier's
    # recompute (analysis.planverify, staging invariant) reproduces the
    # numbers bit-for-bit at any plan size.
    pcie_s = round(_tiers.transfer_time(pcie_total, "pcie"), 9)
    hbm_s = round(_tiers.transfer_time(pcie_total, "hbm"), 9)
    seq_s = pcie_s + hbm_s
    cp_s = max(pcie_s, hbm_s) + min(pcie_s, hbm_s) / max(n_total, 1)
    annotation = {
        "depth": 2,
        "grain": [int(GRAIN[0]), int(GRAIN[1])],
        "passes": pass_meta,
        "n_windows": n_total,
        "window_bytes": max_window,
        "slab_bytes": slab_b,
        "resident_bytes": int(out_bytes),
        "host_bytes": host_bytes,
        "hbm_capacity_bytes": hbm_cap,
        "model": {
            "pcie_s": pcie_s,
            "hbm_s": hbm_s,
            "sequential_s": round(seq_s, 9),
            "critical_path_s": round(cp_s, 9),
            "model_speedup": round(seq_s / cp_s, 4) if cp_s else 1.0,
            "bound_gbps": round(pcie_total / cp_s / 1e9, 3) if cp_s else 0.0,
        },
    }
    sched = Schedule(
        spec,
        "host-staging",
        steps,
        slab_b,
        notes=(
            f"out-of-core staging: {len(pass_meta)} pass(es) over a "
            f"{host_bytes} B host-resident operand through a depth-2 "
            f"double-buffered HBM slab (HEAT_TPU_OOC)"
        ),
        staging=annotation,
    )
    if _telemetry._ENABLED:
        _telemetry.inc("redist.staging.planned_windows", n_total)
        _telemetry.inc("redist.staging.planned_bytes", pcie_total)
        _obs_events.emit(
            "staging.plan",
            plan_id=sched.plan_id,
            host_bytes=host_bytes,
            windows=n_total,
            slab_bytes=slab_b,
            model_bound_gbps=annotation["model"]["bound_gbps"],
        )
    return sched


def prove_fits(sched: Schedule, hbm_bytes: Optional[int] = None) -> Schedule:
    """Prove a staged window schedule fits the HBM tier BEFORE running
    it: the ``Schedule.liveness()`` peak (resident working set + the
    depth-2 slab occupancy) must sit within ``tiers.capacity("hbm")``,
    and the host-resident operand within ``tiers.capacity("host")``.
    Raises ``MemoryError`` naming the violating number — the same
    budget arithmetic ``ht.analysis.memcheck`` (SL301) and serving
    admission read, because it IS the same ``capacity()`` call."""
    from ..core import tiers as _tiers

    budget = _tiers.capacity("hbm") if hbm_bytes is None else max(1, int(hbm_bytes))
    live = sched.liveness_peak_bytes
    if live > budget:
        raise MemoryError(
            f"staged plan {sched.plan_id} needs {live} B of HBM (resident "
            f"{sched.resident_bytes} B + slab peak {sched.peak_bytes} B) "
            f"> capacity('hbm') = {budget} B — shrink HEAT_TPU_OOC_SLAB_MB "
            "or the working set"
        )
    if sched.staging and int(sched.staging["host_bytes"]) > _tiers.capacity("host"):
        raise MemoryError(
            f"staged plan {sched.plan_id} keeps {sched.staging['host_bytes']} B "
            f"on the host tier > capacity('host') = {_tiers.capacity('host')} B"
        )
    return sched


def materialize(host: HostArray, what: str = "operand"):
    """Whole-operand device materialization of a :class:`HostArray` —
    the shared ``HEAT_TPU_OOC=0`` escape hatch (and the fallback for
    algorithms staging cannot serve, e.g. a full-SVD rank budget).
    Returns a replicated DNDarray; raises ``MemoryError`` naming the
    numbers when the operand cannot fit the hbm tier — the whole reason
    staging exists."""
    from ..core import factories, tiers as _tiers

    if host.nbytes > _tiers.capacity("hbm"):
        raise MemoryError(
            f"{what}: host-resident operand is {host.nbytes} B > "
            f"tiers.capacity('hbm') = {_tiers.capacity('hbm')} B and staging "
            f"is not engaged ({OOC_ENV}={ooc_mode()!r}) — the staged window "
            "stream is the only way to run it"
        )
    return factories.array(host.window(0, 0, host.shape[0]), split=None)


# --------------------------------------------------------------------- #
# the executor                                                          #
# --------------------------------------------------------------------- #
def stream_windows(
    host: HostArray,
    axis: int,
    windows: Sequence[Tuple[int, int]],
    consume: Callable[[int, Any, Tuple[int, int]], None],
    device_put: Optional[Callable[[np.ndarray], Any]] = None,
    plan_id: Optional[str] = None,
) -> None:
    """Depth-2 double-buffered window loop: the ``jax.device_put`` of
    window ``k+1`` is ISSUED before window ``k``'s compute consumes the
    slab, so the PCIe (host->HBM) transfer of the next window rides
    under the current window's compute — the staging analog of the
    PR-6 prefetch-issue-then-consume chunk pipelines. ``consume(k,
    slab_array, (start, stop))`` runs the per-window compute.

    Under ``HEAT_TPU_TRACE`` each window gets a ``staging.stage_in``
    span (real host wall around the ``device_put``: the PCIe leg)
    and a ``staging.compute`` span around its
    consume call, tagged with ``plan_id`` (the staged plan this stream
    executes) when the caller provides it. The probes wrap the
    callables, never the loop: issue order and numerics are identical
    with the gate on or off."""
    import jax

    put = device_put or jax.device_put
    windows = list(windows)
    if not windows:
        return
    if _tracing._ENABLED:
        put, consume = _tracing.window_probes(put, consume, plan_id)
    live = _telemetry._ENABLED
    nxt = put(host.window(axis, *windows[0]))
    for k, win in enumerate(windows):
        cur = nxt
        if k + 1 < len(windows):
            # depth-2: next window's stage_in goes on the wire now
            nxt = put(host.window(axis, *windows[k + 1]))
        if live:
            _telemetry.inc("redist.staging.windows")
            _telemetry.inc(
                "redist.staging.bytes_in",
                _win_bytes(host.shape, host.dtype.itemsize, axis, win),
            )
        consume(k, cur, win)


# --------------------------------------------------------------------- #
# golden staged plans — pinned by the determinism + verify sweeps       #
# --------------------------------------------------------------------- #
def golden_staged_plans() -> List[Tuple[str, Schedule]]:
    """The (name, staged plan) matrix the ci.sh determinism leg dumps
    and ``scripts/verify_plans.py`` proves well-formed. Slab and
    working-set bytes are pinned explicitly so an ambient
    ``HEAT_TPU_OOC_SLAB_MB``/``HEAT_TPU_HBM_BYTES`` cannot make two CI
    runs diverge. The 20 GB hsvd shape is the ROADMAP scenario (an
    operand larger than one v5e chip's HBM); the 2 GB twins fit one.
    """
    from ..core import tiers as _tiers

    slab = DEFAULT_SLAB_MB << 20
    cap = _tiers.DEFAULT_HBM_BYTES  # pinned, NOT the ambient env
    hsvd2 = [{"tag": "sketch", "axis": 1}, {"tag": "project", "axis": 0}]
    return [
        (
            "staged_hsvd_20gb_2pass",
            plan_staged_passes(
                (65536, 81920), "float32", hsvd2, slab=slab,
                out_bytes=128 << 20, hbm_bytes=cap,
            ),
        ),
        (
            "staged_hsvd_2gb_2pass",
            plan_staged_passes(
                (65536, 8192), "float32", hsvd2, slab=slab,
                out_bytes=32 << 20, hbm_bytes=cap,
            ),
        ),
        (
            "staged_hsvd_2gb_1pass",
            plan_staged_passes(
                (65536, 8192),
                "float32",
                [{"tag": "dual-sketch", "axis": 1}],
                slab=slab,
                out_bytes=32 << 20,
                hbm_bytes=cap,
            ),
        ),
        (
            "staged_kmeans_2gb_stream",
            plan_staged_passes(
                (8_388_608, 64), "float32", [{"tag": "partial-fit", "axis": 0}],
                slab=slab, out_bytes=1 << 20, hbm_bytes=cap,
            ),
        ),
        # a transform-shaped pass that writes its windows back to host
        # (the stage_out leg of the verifier templates)
        (
            "staged_transform_4gb_writeback",
            plan_staged_passes(
                (131072, 8192),
                "float32",
                [{"tag": "transform", "axis": 0, "writeback": True}],
                slab=slab,
                out_bytes=0,
                hbm_bytes=cap,
            ),
        ),
    ]
