"""Communication backend for heat_tpu.

The reference backs its distributed arrays with mpi4py: a 2063-line
``MPICommunication`` wrapping every MPI collective with torch-buffer
handling (/root/reference/heat/core/communication.py:115-1994). On TPU the
model is inverted: a **single controller** drives an entire slice; data
movement is expressed as GSPMD shardings on ``jax.Array`` plus XLA
collectives (``psum``/``all_gather``/``ppermute``/``all_to_all``) inside
``shard_map`` where the schedule *is* the algorithm. Consequently this
module provides

- ``MeshCommunication``: the communicator equivalent — wraps a 1-D
  ``jax.sharding.Mesh`` over the device population, computes chunk/
  sharding geometry (the analog of ``MPICommunication.chunk`` at
  communication.py:156 and ``counts_displs_shape`` at :215), and builds
  ``NamedSharding`` specs from a heat ``split`` axis;
- resharding helpers that subsume Heat's explicit collectives: what the
  reference does with ``Allgatherv`` (split→None, dndarray.py:1406) or
  ``Alltoallv`` (split→split) is here a ``jax.device_put`` onto a new
  sharding, lowered by XLA to the same collectives over ICI;
- module-level singletons ``MPI_WORLD``-style plus ``get_comm``/``use_comm``
  (reference communication.py:2008-2059).

Derived MPI datatypes for non-contiguous buffers, CUDA-awareness sniffing
and host-staging (reference communication.py:15-25, 245-456) have no
equivalent — XLA owns layout and transport.
"""

from __future__ import annotations

import dataclasses
import math
import re

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from typing import List, Optional, Tuple, Union

from . import gates as _gates
from ..observability import events as _obs_events
from ..observability import telemetry as _telemetry
from ..observability.instrument import nbytes_of as _nbytes_of
from ..observability.tracing import span as _span

__all__ = [
    "Communication",
    "DCN_BPS",
    "DCN_PENALTY",
    "ICI_BPS",
    "MeshCommunication",
    "MPICommunication",
    "MPI_WORLD",
    "MPI_SELF",
    "TOPOLOGY_ENV",
    "Topology",
    "get_comm",
    "use_comm",
    "sanitize_comm",
    "init_distributed",
    "topology_for",
]


# --------------------------------------------------------------------- #
# two-tier topology (ISSUE 8)                                           #
# --------------------------------------------------------------------- #
from . import tiers as _tiers

#: per-chip bidirectional ICI bandwidth (v5e, docs/PERF.md multi-chip
#: analytic model) — the intra-slice tier every earlier PR priced.
#: Since ISSUE 11 the number lives in the one memory-tier cost lattice
#: (``core.tiers``); re-exported here for the established import sites.
ICI_BPS = _tiers.ICI_BPS

#: per-chip DCN bandwidth across slices (~8x slower than ICI): the
#: inter-slice tier multi-slice deployments add. No DCN hardware is
#: attached to this container — the constant feeds the same analytic
#: model + HLO-census methodology the multichip work is pinned with.
DCN_BPS = _tiers.DCN_BPS

#: cost-model penalty of a DCN-tier byte relative to an ICI-tier byte
#: (= ICI_BPS / DCN_BPS = ``tiers.penalty("dcn")``). The redistribution
#: planner prices tier="dcn" collective steps with this multiplier so
#: the byte-equivalent cost scalar keeps one unit.
DCN_PENALTY = _tiers.penalty("dcn")

#: ``HEAT_TPU_TOPOLOGY``: ``auto`` (default — read ``slice_index`` off
#: the resolved world's devices; single-slice and CPU worlds stay flat),
#: ``SxC`` (e.g. ``2x8``: force a simulated two-tier factorization of an
#: S*C-device mesh — slices are assigned to contiguous mesh positions,
#: matching the slice-major device order ``_resolve_devices`` sorts
#: into), or ``flat``/``1xN`` (explicitly one ICI domain).
TOPOLOGY_ENV = "HEAT_TPU_TOPOLOGY"

_TOPOLOGY_RE = re.compile(r"^(\d+)\s*[xX]\s*(\d+)$")


@dataclasses.dataclass(frozen=True)
class Topology:
    """Two-tier factorization of a 1-D device mesh: ``n_slices`` ICI
    domains of ``chips_per_slice`` chips each, DCN between them.

    The mesh axis is slice-major (``_resolve_devices`` sorts by
    ``(slice_index, process, id)``), so slice ``s`` owns the contiguous
    mesh positions ``[s*chips_per_slice, (s+1)*chips_per_slice)`` and a
    mesh edge ``a -> b`` stays on ICI iff ``slice_of(a) == slice_of(b)``.
    ``n_slices == 1`` is the flat single-tier world every PR before
    ISSUE 8 assumed.
    """

    n_slices: int
    chips_per_slice: int

    @property
    def size(self) -> int:
        return self.n_slices * self.chips_per_slice

    @property
    def tiered(self) -> bool:
        """More than one slice — the DCN tier exists."""
        return self.n_slices > 1

    def slice_of(self, index: int) -> int:
        """Slice owning mesh position ``index``."""
        return int(index) // self.chips_per_slice

    def crosses(self, a: int, b: int) -> bool:
        """Does the mesh edge ``a -> b`` traverse DCN?"""
        return self.slice_of(a) != self.slice_of(b)

    def spans(self, indices) -> bool:
        """Does a replica group of mesh positions span more than one
        slice (i.e. would a flat collective over it ride DCN)?"""
        slices = {self.slice_of(i) for i in indices}
        return len(slices) > 1

    # ---------------------------------------------------------------- #
    # subgroup helpers (the shard_map axis_index_groups arguments)      #
    # ---------------------------------------------------------------- #
    def chip_axis_groups(self) -> List[List[int]]:
        """Intra-slice groups: one group of ``chips_per_slice``
        neighbors per slice — collectives over these never cross DCN."""
        C = self.chips_per_slice
        return [[s * C + c for c in range(C)] for s in range(self.n_slices)]

    def slice_axis_groups(self) -> List[List[int]]:
        """Inter-slice groups: the ``chips_per_slice`` groups of
        same-chip-position peers across slices — the minimal-width DCN
        exchange pattern (each group carries exactly one chip per
        slice)."""
        C = self.chips_per_slice
        return [[s * C + c for s in range(self.n_slices)] for c in range(C)]

    def bandwidth(self, tier: str) -> float:
        """Per-chip bytes/s of ``tier`` (``"ici"``/``"dcn"``) — the
        lattice edge price (``core.tiers.bandwidth``)."""
        if tier not in ("ici", "dcn"):
            raise KeyError(tier)
        return _tiers.bandwidth(tier)

    @classmethod
    def parse(cls, text: str) -> Optional["Topology"]:
        """``"2x8"`` -> Topology(2, 8); ``None`` for unparseable text."""
        m = _TOPOLOGY_RE.match(text.strip())
        if not m:
            return None
        s, c = int(m.group(1)), int(m.group(2))
        if s < 1 or c < 1:
            return None
        return cls(s, c)

    def __str__(self) -> str:
        return f"{self.n_slices}x{self.chips_per_slice}"


def _detect_slices(mesh_size: int) -> Topology:
    """``auto`` resolution: group the RESOLVED world's devices by
    ``slice_index`` (TPU pods expose it on multi-slice deployments).

    Reads only ``MPI_WORLD``'s already-resolved device list — never
    probes the platform itself, so the pure-Python contexts that plan
    without touching a device (``scripts/redist_plans.py``, golden-plan
    tests) stay device-free and the one-shot ``init_distributed`` lazy
    window is preserved. By the time any plan EXECUTES, the world is
    resolved and a real multi-slice deployment reports its tiers.
    """
    devs = MPI_WORLD._devices_  # None until the world resolves
    if not devs or len(devs) != mesh_size:
        return Topology(1, mesh_size)
    counts: dict = {}
    for d in devs:
        counts.setdefault(getattr(d, "slice_index", 0) or 0, 0)
        counts[getattr(d, "slice_index", 0) or 0] += 1
    sizes = set(counts.values())
    if len(counts) <= 1 or len(sizes) != 1:
        # single slice, or ragged slices the 2-tier factorization does
        # not model: flat (the ragged case cannot arise on real pods)
        return Topology(1, mesh_size)
    return Topology(len(counts), next(iter(sizes)))


def topology_for(mesh_size: int, override=None) -> Topology:
    """The :class:`Topology` governing a ``mesh_size``-device mesh.

    ``override`` wins when given: a :class:`Topology`, an ``"SxC"``
    string, or ``"flat"``. Otherwise ``HEAT_TPU_TOPOLOGY`` decides —
    ``auto`` (default) reads ``slice_index`` off the resolved world's
    devices (flat on CPU/single-slice), a forced ``SxC`` simulates that
    factorization. A forced product that does not equal ``mesh_size``
    resolves FLAT: a 2x8 setting over an 8-device test mesh must not
    invent a topology the devices cannot realize (the forced-topology CI
    leg uses 2x4 on the 8-device mesh for exactly this reason).
    """
    mesh_size = int(mesh_size)
    if override is not None:
        if isinstance(override, Topology):
            t = override
        elif str(override).strip().lower() in ("flat", "1", "none"):
            return Topology(1, mesh_size)
        else:
            t = Topology.parse(str(override))
            if t is None:
                raise ValueError(
                    f"unparseable topology {override!r} (expected 'SxC', "
                    "'flat', or a Topology)"
                )
        return t if t.size == mesh_size and t.tiered else Topology(1, mesh_size)
    raw = _gates.get(TOPOLOGY_ENV, "auto").strip().lower()
    if raw in ("", "auto"):
        return _detect_slices(mesh_size)
    if raw in ("flat", "1", "none", "off", "0"):
        return Topology(1, mesh_size)
    t = Topology.parse(raw)
    if t is None or t.size != mesh_size or not t.tiered:
        return Topology(1, mesh_size)
    return t


class Communication:
    """Base class for communicators (reference: communication.py:83)."""

    @staticmethod
    def is_distributed() -> bool:
        raise NotImplementedError()

    def __init__(self) -> None:
        raise NotImplementedError()

    def chunk(self, shape, split) -> Tuple[int, Tuple[int, ...], Tuple[slice, ...]]:
        raise NotImplementedError()


def _platform_devices(device=None) -> list:
    from . import devices as _devices

    dev = _devices.sanitize_device(device)
    return dev.jax_devices()


def place(array: jax.Array, sharding) -> jax.Array:
    """``jax.device_put`` that stays correct under tracing. Inside a
    ``jax.jit`` trace (``ht.jit``, fused programs) ``jax.device_put`` on a
    Tracer is NOT a binding layout constraint — observed on jax 0.9: the
    requested sharding is silently ignored and GSPMD propagation picks its
    own layout, leaving DNDarray ``split`` metadata out of sync with the
    physical sharding. Under a trace this lowers to
    ``with_sharding_constraint`` (which IS binding); eagerly it is a plain
    ``device_put``."""
    with _span("ht.comm.place"):
        if isinstance(array, jax.core.Tracer):
            return jax.lax.with_sharding_constraint(array, sharding)
        return jax.device_put(array, sharding)


def jit_sharded_mesh(fn, mesh, sharding_thunk):
    """``jax.jit`` with ``out_shardings`` from ``sharding_thunk()`` — except
    on a ONE-device mesh, where the pin is a semantic no-op (committed
    array inputs already determine placement) and is dropped: passing
    ``out_shardings`` moves pjit dispatch off the C++ fast path, which
    dominates short elementwise programs on the single chip. Callers whose programs
    have NO committed array inputs must not use this helper.
    """
    if mesh.devices.size == 1:
        return jax.jit(fn)
    return jax.jit(fn, out_shardings=sharding_thunk())


class MeshCommunication(Communication):
    """Single-controller communicator over a 1-D JAX device mesh.

    The mesh axis (default ``'d'``) is the axis heat's ``split`` dimension
    is sharded over. ``size`` is the number of shards (devices), the role
    MPI ranks play in the reference; ``rank`` is the *process* index and is
    0 on a single host — per-rank divergent control flow does not exist in
    this model.
    """

    # _ht_epoch: the elastic runtime's world-epoch stamp (ISSUE 13,
    # heat_tpu.resilience.elastic) — set only on communicators the
    # runtime binds; unset on every other comm, so the executor's
    # fence stays a getattr-default no-op
    __slots__ = ("_devices_", "_mesh", "axis_name", "_self_like", "_ht_epoch")

    def __init__(self, devices=None, axis_name: str = "d"):
        # device resolution is LAZY when no explicit devices are given:
        # probing the platform initializes the XLA backend, which must not
        # happen at import time (the world singletons are built then) or
        # jax.distributed.initialize can never run afterwards
        self.axis_name = axis_name
        if devices is None:
            self._devices_ = None
            self._mesh = None
        else:
            self._devices_ = list(devices)
            self._mesh = Mesh(np.array(self._devices_), (axis_name,))

    def _resolve_devices(self) -> list:
        # topology-aware order: group devices by (slice, host) so that the
        # 1-D mesh axis places same-slice neighbors adjacently — ring
        # collectives (ppermute halo/sort/attention schedules) then take
        # p−2 ICI hops and cross DCN only at slice boundaries, instead of
        # hopping DCN on every step of an arbitrary interleaving. TPU pods
        # expose ``slice_index`` on multi-slice deployments; single-slice
        # and CPU worlds sort to their existing order.
        devs = _platform_devices(None)
        return sorted(
            devs,
            key=lambda d: (
                getattr(d, "slice_index", 0) or 0,
                d.process_index,
                d.id,
            ),
        )

    def _ensure(self) -> None:
        if self._devices_ is None:
            self._devices_ = list(self._resolve_devices())
            self._mesh = Mesh(np.array(self._devices_), (self.axis_name,))

    @property
    def _devices(self) -> list:
        self._ensure()
        return self._devices_

    @property
    def mesh(self) -> Mesh:
        self._ensure()
        return self._mesh

    @property
    def size(self) -> int:
        """Number of shards (mesh size) — the analog of MPI comm size."""
        return len(self._devices)

    @property
    def rank(self) -> int:
        """Index of the controlling process (0 on a single host)."""
        return jax.process_index()

    def is_distributed(self) -> bool:
        return self.size > 1

    @property
    def devices(self) -> list:
        return list(self._devices)

    @property
    def topology(self) -> Topology:
        """The two-tier :class:`Topology` governing this mesh
        (``HEAT_TPU_TOPOLOGY``; flat on single-slice/CPU worlds). For
        the world communicator ``auto`` groups the resolved devices by
        ``slice_index``; sub-communicators of a tiered world resolve
        flat unless the env forces their factorization (a Split
        sub-group has no guaranteed slice alignment)."""
        return topology_for(self.size)

    # ------------------------------------------------------------------ #
    # chunk geometry                                                     #
    # ------------------------------------------------------------------ #
    def chunk(
        self, shape, split: Optional[int], rank: Optional[int] = None, w_size: Optional[int] = None
    ) -> Tuple[int, Tuple[int, ...], Tuple[slice, ...]]:
        """Calculate the shard of ``shape`` along ``split`` owned by device
        ``rank`` (default: device 0 of this process).

        Reference semantics (communication.py:156) give the first
        ``size % w`` ranks one extra element; XLA's GSPMD uses ceil-division
        blocks with a possibly short/empty tail. We follow the XLA
        convention so that ``chunk`` agrees exactly with the placement of
        ``jax.Array`` shards on the mesh.

        Returns (offset, local_shape, slices).
        """
        shape = tuple(int(s) for s in shape)
        size = self.size if w_size is None else w_size
        if rank is None:
            rank = 0
        if split is None or size == 1:
            return 0, shape, tuple(slice(0, s) for s in shape)
        split = split % len(shape)
        n = shape[split]
        block = -(-n // size)  # ceil division
        start = min(rank * block, n)
        end = min(start + block, n)
        lshape = list(shape)
        lshape[split] = end - start
        slices = tuple(
            slice(start, end) if i == split else slice(0, s) for i, s in enumerate(shape)
        )
        return start, tuple(lshape), slices

    def counts_displs_shape(
        self, shape, split: int
    ) -> Tuple[Tuple[int, ...], Tuple[int, ...], Tuple[int, ...]]:
        """Per-device counts and displacements along ``split`` plus the
        local shape of device 0 (reference: communication.py:215).
        """
        shape = tuple(int(s) for s in shape)
        n = shape[split]
        size = self.size
        block = -(-n // size)
        counts = tuple(max(0, min(n - r * block, block)) for r in range(size))
        displs = tuple(min(r * block, n) for r in range(size))
        _, lshape, _ = self.chunk(shape, split)
        return counts, displs, lshape

    def lshape_map(self, gshape, split: Optional[int]) -> np.ndarray:
        """(size, ndim) array of every device's local shard shape — the
        analog of ``DNDarray.create_lshape_map`` (reference dndarray.py:646)
        computed from geometry instead of an Allreduce.
        """
        gshape = tuple(int(s) for s in gshape)
        out = np.tile(np.array(gshape, dtype=np.int64), (self.size, 1))
        if split is not None and len(gshape) > 0:
            counts, _, _ = self.counts_displs_shape(gshape, split % len(gshape))
            out[:, split % len(gshape)] = np.array(counts, dtype=np.int64)
        return out

    # ------------------------------------------------------------------ #
    # sharding construction                                              #
    # ------------------------------------------------------------------ #
    def spec(self, ndim: int, split: Optional[int]) -> PartitionSpec:
        """PartitionSpec placing ``split`` on the mesh axis."""
        if split is None or ndim == 0:
            return PartitionSpec()
        split = split % ndim
        return PartitionSpec(*(self.axis_name if i == split else None for i in range(ndim)))

    def sharding(self, ndim: int, split: Optional[int]) -> NamedSharding:
        """NamedSharding for an ``ndim``-dimensional array split along
        ``split`` — the declarative replacement for the reference's entire
        buffer-distribution machinery.
        """
        return NamedSharding(self.mesh, self.spec(ndim, split))

    def jit_sharded(self, fn, ndim: int, split: Optional[int]):
        """``jax.jit(fn)`` with the output sharding pinned for this mesh.
        ONLY for programs whose array inputs are committed to this mesh's
        devices (every op wrapper: the physical operands pin placement).
        Zero-array-input builders (factories/random) must keep
        ``out_shardings`` unconditionally instead.
        """
        return jit_sharded_mesh(fn, self.mesh, lambda: self.sharding(ndim, split))

    def shard(self, array: jax.Array, split: Optional[int]) -> jax.Array:
        """Lay a LOGICAL ``array`` out on the mesh according to ``split``,
        zero-padding the split dimension up to a mesh multiple first
        (see ``_padding``). Returns the physical array.

        This one call subsumes the reference's ``resplit_`` collectives
        (dndarray.py:1406-1535): split→None lowers to all-gather, None→split
        to a local slice, split→split to an all-to-all — all emitted by XLA.
        """
        from . import _padding

        if _telemetry._ENABLED:
            # metadata only (trace-safe); under a trace this fires once
            # per compile, which the event records
            nbytes = _nbytes_of(array.shape, array.dtype)
            _telemetry.inc("comm.shard.calls")
            _telemetry.inc("comm.shard.bytes", nbytes)
            _obs_events.emit(
                "comm.shard",
                shape=tuple(int(s) for s in array.shape),
                split=split,
                bytes=nbytes,
                traced=isinstance(array, jax.core.Tracer),
            )
        with _span("ht.comm.shard"):
            if split is not None:
                split = split % max(array.ndim, 1)
                if array.shape[split] == 0:
                    # zero-extent split axis: nothing to distribute, store replicated
                    return place(array, self.sharding(array.ndim, None))
                array = _padding.pad_logical(array, split, self.size)
            return place(array, self.sharding(array.ndim, split))

    def reshard_phys(
        self, phys: jax.Array, gshape, old_split: Optional[int], new_split: Optional[int]
    ) -> jax.Array:
        """Move a physical array from one split layout to another (the
        whole of the reference's split→split Isend/Irecv tiling,
        dndarray.py:1406). Routed through the redistribution planner
        (``heat_tpu.redistribution``): the movement is normalized to a
        :class:`~heat_tpu.redistribution.spec.RedistSpec`, planned under
        the peak-memory budget, and executed as the planned collective
        schedule (``HEAT_TPU_REDIST_PLANNER=0`` restores the legacy
        single device_put)."""
        if _telemetry._ENABLED:
            # the moved volume is the LOGICAL payload (every byte crosses
            # the mesh on a split change; pad rows are manufactured)
            moved = _nbytes_of(gshape, phys.dtype)
            _telemetry.inc("comm.reshard.calls")
            _telemetry.inc("comm.reshard.bytes", moved)
            _obs_events.emit(
                "comm.reshard",
                gshape=tuple(int(s) for s in gshape),
                old_split=old_split,
                new_split=new_split,
                bytes_moved=moved,
                traced=isinstance(phys, jax.core.Tracer),
            )
        from ..redistribution import executor as _redist_exec

        with _span("ht.comm.reshard"):
            return _redist_exec.resplit_phys(self, phys, gshape, old_split, new_split)

    # ------------------------------------------------------------------ #
    # communicator management                                            #
    # ------------------------------------------------------------------ #
    def Split(self, color=0, key=0):
        """MPI ``Comm.Split`` with faithful semantics, adapted to the
        single-controller model (reference wraps mpi4py's Split). In MPI
        every rank passes its own ``(color, key)``; ranks sharing a color
        form a sub-communicator ordered by ``(key, old rank)``. Here ONE
        controller owns every device, so the caller passes the full
        per-device vectors:

        - ``color``: int → all devices share it (an MPI all-same-color
          Split, i.e. a dup): returns one ``MeshCommunication``.
        - ``color``: sequence of ints, one per device → returns a dict
          ``{color: MeshCommunication}``, each group's devices ordered by
          ``(key[i], i)``; ``key`` may be a scalar or a per-device
          sequence. Devices with negative color (MPI_UNDEFINED analog)
          join no group.
        """
        size = self.size
        if isinstance(color, (int, np.integer)):
            return MeshCommunication(self._devices, self.axis_name)
        colors = [int(c) for c in color]
        if len(colors) != size:
            raise ValueError(f"color vector must have one entry per device ({size}), got {len(colors)}")
        if isinstance(key, (int, np.integer)):
            keys = [int(key)] * size
        else:
            keys = [int(k) for k in key]
            if len(keys) != size:
                raise ValueError(f"key vector must have one entry per device ({size}), got {len(keys)}")
        groups = {}
        for i, c in enumerate(colors):
            if c < 0:
                continue
            groups.setdefault(c, []).append(i)
        return {
            c: MeshCommunication(
                [self._devices[i] for i in sorted(idx, key=lambda i: (keys[i], i))],
                self.axis_name,
            )
            for c, idx in groups.items()
        }

    def __repr__(self) -> str:
        # must NOT resolve devices: a debug print before init_distributed
        # would otherwise initialize the backend and consume the one-shot
        # lazy window
        if self._devices_ is None:
            return f"MeshCommunication(unresolved, axis={self.axis_name!r})"
        return f"MeshCommunication(size={self.size}, axis={self.axis_name!r}, platform={self._devices_[0].platform if self._devices_ else '-'})"


# reference-compatible alias: programs written against the reference name
MPICommunication = MeshCommunication


# lru-cached program builders whose entries bake mesh geometry in
# (out_shardings, shard_map meshes, comm identity). A world rebuild
# (init_distributed) must clear them or pre-init configurations would
# silently reuse programs placed on the defunct single-host mesh.
_MESH_KEYED_CACHES = []


def register_mesh_cache(cached_fn) -> None:
    """Register a functools.lru_cache-wrapped program builder keyed (in
    part) on a mesh/comm; cleared when the world communicator changes."""
    _MESH_KEYED_CACHES.append(cached_fn)


def _clear_mesh_caches() -> None:
    for fn in _MESH_KEYED_CACHES:
        fn.cache_clear()


def init_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    local_device_ids=None,
) -> MeshCommunication:
    """Multi-host bootstrap — the single-controller replacement for the
    reference's ``mpirun -n N`` world creation (communication.py:2012).

    Where Heat relies on MPI to spawn one rank per process and wires them
    with mpi4py, the TPU runtime runs ONE controller per host:
    ``jax.distributed.initialize`` connects the hosts (args can also come
    from the cluster environment: TPU pods auto-detect all four), after
    which ``jax.devices()`` spans every host's chips and the world
    communicator's mesh covers the full slice — collectives ride ICI
    within a slice and DCN across slices. Call this ONCE, before any array
    creation, on every host; each host then runs the SAME program
    (SPMD single-controller-per-host, not rank-divergent control flow).

    Returns the rebuilt world communicator (also installed as the global
    default, so ``ht.array(..., split=0)`` shards over all hosts).
    """
    import jax

    kwargs = {}
    if coordinator_address is not None:
        kwargs["coordinator_address"] = coordinator_address
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    if local_device_ids is not None:
        kwargs["local_device_ids"] = local_device_ids
    try:
        jax.distributed.initialize(**kwargs)
    except RuntimeError as e:
        if "must be called before" in str(e):
            raise RuntimeError(
                "init_distributed must run before any array/device use: the "
                "world and device registry are lazy precisely so that "
                "`import heat_tpu as ht; ht.core.communication."
                "init_distributed(...)` works as the FIRST call — something "
                "touched the backend earlier in this process"
            ) from e
        raise

    # rebuild the world IN PLACE: star-imported copies of MPI_WORLD
    # (heat_tpu.MPI_WORLD, pre-init local references) must all observe the
    # new global device set — rebinding the module global would leave them
    # pointing at the stale single-host world
    MPI_WORLD.__init__()
    MPI_SELF.__init__()
    # compiled programs built before init baked the old mesh into their
    # out_shardings / shard_map meshes — drop them
    _clear_mesh_caches()

    global __default_comm
    __default_comm = MPI_WORLD
    return MPI_WORLD


class _SelfCommunication(MeshCommunication):
    """Single-device communicator — the analog of MPI_COMM_SELF."""

    def __init__(self):
        super().__init__(None)  # lazy, like the world

    def _resolve_devices(self) -> list:
        import jax as _jax

        devs = _platform_devices(None)
        # in a multi-process world jax.devices()[0] belongs to process 0;
        # MPI_COMM_SELF must be THIS process's device
        proc = _jax.process_index()
        local = [d for d in devs if d.process_index == proc]
        return (local or devs)[:1]


def _build_world() -> MeshCommunication:
    return MeshCommunication()


MPI_WORLD: MeshCommunication = _build_world()
"""Communicator spanning all devices of the default platform
(reference: communication.py:2012)."""

MPI_SELF: MeshCommunication = _SelfCommunication()
"""Single-device communicator (reference: communication.py:2013)."""

__default_comm = MPI_WORLD


def get_comm() -> MeshCommunication:
    """Retrieve the globally set default communicator
    (reference: communication.py:2019)."""
    return __default_comm


def use_comm(comm: Optional[MeshCommunication] = None) -> None:
    """Set the globally used default communicator
    (reference: communication.py:2049)."""
    global __default_comm
    if comm is None:
        comm = MPI_WORLD
    if not isinstance(comm, Communication):
        raise TypeError(f"expected a Communication object, got {type(comm)}")
    __default_comm = comm


def sanitize_comm(comm: Optional[Communication]) -> MeshCommunication:
    """Sanitize a communicator or return the global default."""
    if comm is None:
        return get_comm()
    if not isinstance(comm, Communication):
        raise TypeError(f"expected a Communication object, got {type(comm)}")
    return comm
