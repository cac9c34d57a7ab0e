"""One memory-tier cost lattice — every byte's price, one table (ISSUE 11).

Before this module the codebase priced the SAME physical object — "how
long does moving N bytes across boundary X take, and does the operand
fit on the near side?" — five separate ways:

- VMEM lane-fill amplification (PR 5, ``kernels.relayout.lane_fill``):
  the vmem↔hbm edge, expressed as a divisor on effective bytes;
- HBM copy bytes (PR 3, the planner's ``effective_bytes`` volume term):
  the same edge at full lanes;
- ICI vs DCN wire pricing (PR 8, ``communication.ICI_BPS``/``DCN_BPS``/
  ``DCN_PENALTY``): the two cross-chip edges;
- the static peak-HBM budget (PR 10, ``analysis.memcheck``'s
  ``HEAT_TPU_HBM_BYTES``): the hbm tier's CAPACITY;
- and the out-of-core item needed a SIXTH hand-rolled price for the
  host↔hbm PCIe hop.

This module makes the lattice first-class: an ordered chain of memory
tiers (``vmem → hbm → host``) and wire edges hanging off hbm
(``ici``, ``dcn``), with ONE ``bandwidth(edge)`` / ``transfer_time(
nbytes, edge)`` / ``penalty(edge)`` pricing function and ONE
``capacity(tier)`` budget, so any placement decision — a redistribution
step, an out-of-core staging window, a pipeline hand-off, a codec
choice — costs movement the same way and proves fit the same way.
arXiv:2112.01075's portable-collective decomposition generalizes across
any bandwidth-mismatched edge pair (PR 8 proved it for ici/dcn; the
host tier lands in ``redistribution.staging`` as the first new client),
and arXiv:2112.09017's host-staged TPU linear algebra is exactly the
``pcie`` edge streamed under compute.

REFACTOR CONTRACT: the constants and arithmetic here are the SAME
numbers the former call sites used (``ICI_BPS`` 200e9, ``DCN_BPS``
25e9, ``penalty("dcn")`` = 8, ``capacity("hbm")`` =
``HEAT_TPU_HBM_BYTES`` else 16 GiB) — re-derived, not re-tuned — so
every existing golden plan, plan_id, and SL301 verdict is byte-
identical to the pre-lattice era. Pinned by tier-1 parity tests and the
ci.sh determinism diffs.

Dependency-free by design (os only): the planner, the analyzers, and
the pure-Python plan dump scripts all import it without touching jax.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

from . import gates as _gates

__all__ = [
    "DCN_BPS",
    "DEFAULT_HBM_BYTES",
    "DEFAULT_HOST_BYTES",
    "DEFAULT_VMEM_BYTES",
    "DISK_BPS",
    "EDGES",
    "HBM_BPS",
    "HBM_ENV",
    "HOST_ENV",
    "ICI_BPS",
    "MEMORY_TIERS",
    "PCIE_BPS",
    "TIERS",
    "VMEM_ENV",
    "bandwidth",
    "capacity",
    "describe",
    "edge_between",
    "penalty",
    "transfer_time",
]

# --------------------------------------------------------------------- #
# the lattice                                                           #
# --------------------------------------------------------------------- #
#: every tier a byte can live on or cross, nearest (fastest) first. The
#: first three are MEMORY tiers (they hold operands and have a
#: capacity); ``ici``/``dcn`` are WIRE tiers (they only carry bytes
#: between the hbm tiers of different chips/slices).
TIERS: Tuple[str, ...] = ("vmem", "hbm", "host", "ici", "dcn")

#: the tiers with a capacity — an operand RESIDES on one of these.
MEMORY_TIERS: Tuple[str, ...] = ("vmem", "hbm", "host")

#: per-chip HBM stream bandwidth (v5e ~819 GB/s) — the vmem↔hbm edge
#: every local relayout copy pays; ``kernels.relayout.lane_fill`` is
#: this edge's efficiency term (1/lane_fill = the amplification a
#: narrow-minor tiled layout costs on it).
HBM_BPS = 819e9

#: host↔HBM PCIe bandwidth (v5e: PCIe Gen3 x16, ~16 GB/s per chip) —
#: the edge the out-of-core staging executor streams
#: (``redistribution.staging``); ~51x slower than the HBM stream, which
#: is why staged schedules are PCIe-bound and must hide the transfer
#: under compute (depth-2 double buffering).
PCIE_BPS = 16e9

#: per-chip bidirectional ICI bandwidth (v5e) — the intra-slice wire
#: every earlier PR priced. ``core.communication.ICI_BPS`` re-exports
#: this value.
ICI_BPS = 200e9

#: per-chip DCN bandwidth across slices (~8x slower than ICI) —
#: ``core.communication.DCN_BPS`` re-exports this value; no DCN
#: hardware is attached to the CPU container, the constant feeds the
#: analytic-model + HLO-census methodology (PR 8).
DCN_BPS = 25e9

#: host↔persistent-store bandwidth for a DURABLE commit (ISSUE 13: the
#: checkpoint writer's edge). Deliberately the fsync-inclusive figure —
#: ~0.8 GB/s is what a single-stream persistent-disk-class store (PD /
#: network filesystem) sustains once the commit protocol (write, fsync,
#: rename) is counted; raw NVMe page-cache streaming reaches 3+ GB/s
#: but a checkpoint is only as durable as its fsync, so pricing the
#: cache-speed figure would make every recovery-time budget optimistic
#: by ~4x. The ROADMAP disk-tier item tracks the NVMe streaming figure
#: separately for non-durable staging reads (``HostArray.from_hdf5``).
DISK_BPS = 0.8e9

#: edge name -> (near tier, far tier, bytes/s). Edge names are what
#: ``Step.tier`` carries in the Schedule IR ("ici"/"dcn" since PR 8,
#: "pcie" for the staging steps of ISSUE 11; "disk" prices the
#: checkpoint commit path of ISSUE 13).
EDGES: Dict[str, Tuple[str, str, float]] = {
    "hbm": ("vmem", "hbm", HBM_BPS),
    "pcie": ("hbm", "host", PCIE_BPS),
    "ici": ("hbm", "hbm", ICI_BPS),
    "dcn": ("hbm", "hbm", DCN_BPS),
    "disk": ("host", "disk", DISK_BPS),
}

# --------------------------------------------------------------------- #
# capacities                                                            #
# --------------------------------------------------------------------- #
#: v5e per-chip VMEM (the Pallas kernels' working set).
DEFAULT_VMEM_BYTES = 128 << 20
#: v5e per-chip HBM — the SL301 budget default (PR 10) and the staging
#: slab ceiling (ISSUE 11).
DEFAULT_HBM_BYTES = 16 << 30
#: pinned-host-RAM assumption per chip when ``HEAT_TPU_HOST_BYTES`` is
#: unset: a v5e-8 host exposes ~192 GiB over 8 chips; 48 GiB per chip
#: is the conservative two-slot figure the 20 GB hsvd scenario uses.
DEFAULT_HOST_BYTES = 48 << 30

VMEM_ENV = "HEAT_TPU_VMEM_BYTES"
#: same env the memcheck SL301 budget always read — ``capacity("hbm")``
#: IS that budget now (``analysis.memcheck.hbm_budget_bytes`` delegates
#: here).
HBM_ENV = "HEAT_TPU_HBM_BYTES"
HOST_ENV = "HEAT_TPU_HOST_BYTES"

_CAPACITY: Dict[str, Tuple[str, int]] = {
    "vmem": (VMEM_ENV, DEFAULT_VMEM_BYTES),
    "hbm": (HBM_ENV, DEFAULT_HBM_BYTES),
    "host": (HOST_ENV, DEFAULT_HOST_BYTES),
}


def capacity(tier: str) -> int:
    """Per-device byte capacity of a MEMORY tier (``vmem``/``hbm``/
    ``host``), env-overridable (``HEAT_TPU_{VMEM,HBM,HOST}_BYTES``).
    ``capacity("hbm")`` is the SL301 budget (``analysis.memcheck``), the
    serving admission limit, and the staging slab ceiling — one number,
    read one way (the exact parsing semantics ``hbm_budget_bytes`` has
    always had: unparseable values fall back to the default)."""
    if tier not in _CAPACITY:
        raise ValueError(
            f"capacity: {tier!r} is not a memory tier (one of {MEMORY_TIERS}; "
            "wire tiers 'ici'/'dcn' carry bytes, they do not hold them)"
        )
    env, default = _CAPACITY[tier]
    raw = _gates.get(env, "")
    try:
        b = int(raw) if raw.strip() else default
    except ValueError:
        b = default
    return max(1, b)


# --------------------------------------------------------------------- #
# edge pricing                                                          #
# --------------------------------------------------------------------- #
def bandwidth(edge: str) -> float:
    """Bytes/s of a lattice edge (``hbm``/``pcie``/``ici``/``dcn``/
    ``disk``)."""
    if edge not in EDGES:
        raise ValueError(f"bandwidth: unknown lattice edge {edge!r} (one of {tuple(EDGES)})")
    return EDGES[edge][2]


def transfer_time(nbytes: int, edge: str) -> float:
    """Seconds to move ``nbytes`` across ``edge`` at the lattice
    bandwidth — THE pricing function every analytic model routes
    through (``planner.tier_time_model``, the staging window model)."""
    return max(int(nbytes), 0) / bandwidth(edge)


def sparse_transfer_time(nnz: int, itemsize: int, edge: str) -> float:
    """Seconds to move a sparse operand of ``nnz`` stored elements
    across ``edge``: each element ships its value (``itemsize`` bytes)
    plus its int32 column index, the CSR/BCSR wire mass that actually
    crosses a lattice edge (the indptr/brick-row metadata is O(rows)
    and amortizes to nothing at any nnz worth pricing). The nnz-weighted
    twin of :func:`transfer_time` the planner and memcheck use when a
    DCSR/DBCSR operand crosses an edge — pricing the DENSE shape
    instead would overstate a 1%%-occupancy operand by 100x and break
    serving admission."""
    return transfer_time(max(int(nnz), 0) * (int(itemsize) + 4), edge)


def penalty(edge: str) -> int:
    """Integer cost-model penalty of one ``edge`` byte relative to one
    ICI byte (= ``ICI_BPS / bandwidth(edge)``, floored, min 1) — the
    multiplier that lets the planner's byte-equivalent cost scalar keep
    ONE unit across tiers. ``penalty("dcn")`` == the former
    ``communication.DCN_PENALTY`` == 8 exactly; ``penalty("pcie")`` ==
    12 prices a staging window's wire in the same scalar."""
    return max(1, int(ICI_BPS / bandwidth(edge)))


def edge_between(a: str, b: str) -> Optional[str]:
    """The lattice edge joining two adjacent memory tiers (``vmem``/
    ``hbm`` -> ``"hbm"``, ``hbm``/``host`` -> ``"pcie"``), or ``None``
    when the tiers are not adjacent — a placement engine walks the
    chain edge by edge (a host->vmem move is pcie THEN hbm; pricing the
    hops separately is what makes the staging schedule's depth-2
    overlap model composable)."""
    pair = {a, b}
    for name, (near, far, _) in EDGES.items():
        if near != far and {near, far} == pair:
            return name
    return None


def describe() -> str:
    """Human-readable lattice table: tiers, capacities, edges,
    bandwidths, penalties — what ``ht.core.tiers`` looks like to a
    placement decision."""
    lines = ["memory-tier lattice (vmem -> hbm -> host; ici/dcn off hbm):"]
    for tier in MEMORY_TIERS:
        env, _ = _CAPACITY[tier]
        lines.append(f"  {tier:>5}: capacity {capacity(tier)} B  ({env})")
    for name, (near, far, bps) in EDGES.items():
        lines.append(
            f"  edge {name:>4}: {near}<->{far}  {bps / 1e9:.1f} GB/s  "
            f"(penalty {penalty(name)}x vs ici)"
        )
    return "\n".join(lines)
