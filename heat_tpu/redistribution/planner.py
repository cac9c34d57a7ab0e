"""Cost-modeled redistribution planning.

Every nontrivial relayout used to be ONE monolithic collective chosen
implicitly by GSPMD (``resplit(None)`` = one full all-gather; the
split-1 reshape repartition = one full all-gather at ~0.09x HBM).
Following "Memory-efficient array redistribution through portable
collective communication" (arXiv:2112.01075), the planner instead
*decomposes* each :class:`~heat_tpu.redistribution.spec.RedistSpec`
into a bounded-footprint :class:`~heat_tpu.redistribution.schedule.Schedule`
chosen by an explicit cost model over candidate strategies:

==================  ====================================================
strategy            when / what
==================  ====================================================
``noop``            same split, same shape — nothing moves
``local``           1-device mesh (and zero-size arrays): local copy
``slice``           replicated → split: every device slices its shard,
                    no collective
``replicate``       split → replicated: the one FULL all-gather left in
                    the system, and only as this explicit strategy
``all-to-all``      split i → j whose send+recv transient fits the
                    budget: one tiled all-to-all (the pinned easy case)
``chunked-all-to-all``  the same move pipelined in C budget-sized
                    chunks: slice → all-to-all → scatter per chunk
``ring``            minimal-footprint fallback: p-1 ``ppermute`` hops,
                    one neighbor block in flight per step — chosen when
                    chunking would need more than p-1 laps
``split0-pivot``    reshape-with-repartition via a split-0 intermediate
                    (the minor-dim packing relayout): all-to-all in,
                    LOCAL row-major reshape at full lane width,
                    all-to-all out — replaces the full all-gather the
                    split-1 reshape used to compile to
``packed-pivot``    the same pivot with narrow-minor-dim stages run on
                    LANE-PACKED buffers (``heat_tpu.kernels.relayout``):
                    a tile-transposing pack folds rows into the lane
                    axis so the chunked all-to-alls and relayout copies
                    stream full VREGs; ONE unpack materializes the
                    destination's narrow layout (the single
                    lane-amplified write the requested layout makes
                    unavoidable)
``local-reshape``   reshape whose device blocks stay put (split-0 ↔
                    split-0 divisible, or replicated source): 0
                    collectives
``gather-reshape``  fallback when divisibility rules out the pivot:
                    gather → reshape → slice (the old behavior, now
                    explicit and accounted)
==================  ====================================================

Cost model: a collective step costs ``ALPHA_BYTES + bytes_moved``
(latency expressed in byte-equivalents, so step count and volume share
one unit), a local relayout copy costs its ``bytes_copied``, and BOTH
are divided by the step's ``lane_fill`` — the fraction of VREG lanes
the step's buffer layout fills (``kernels.relayout.lane_fill``,
``minor_dim/128`` below one tile). 1/lane_fill is the HBM amplification
a copy through a narrow tiled layout pays on TPU; the term is what
makes ``packed-pivot`` (one amplified write) beat ``split0-pivot``
(every stage amplified) exactly on the narrow-minor-dim specs. Among
candidates whose per-step transient peak fits the
``HEAT_TPU_REDIST_BUDGET_MB`` budget the cheapest wins; when nothing
fits, the smallest peak wins (ring is that floor for split moves).
Local copy steps (pad/slice/reshape/pack/unpack) are bounded by one
shard and are accounted but not chunkable — the budget must be at
least one destination shard.

Overlap (ISSUE 6): exchanges big enough to amortize per-lap launch
latency are chunked to the ``OVERLAP_GRAIN_BYTES`` grain even when the
budget alone would not require it, and every chunk group (and the
ppermute ring) carries a depth-2 **overlap annotation** — the modeled
critical path prices a pipelined stage pair at ``max(wire, copy)``
instead of ``wire + copy`` (arXiv:2112.09017's latency-hiding
schedules). The lap structure is gate-INDEPENDENT, so the collective
census is identical overlap-on vs overlap-off; ``HEAT_TPU_REDIST_OVERLAP``
only switches the executor between the sequential oracle and the
prefetch-issue-then-consume program form. The annotation folds into the
canonical serialization and ``plan_id``.

Wire quantization (ISSUE 7): after selection, the winning plan's
admissible collective groups are wrapped in ``quantize``/``dequantize``
codec steps (``heat_tpu.kernels.quant`` — int8 payloads with one f32
scale per 1024-element tile, ~0.251×, or the bf16 cast at 0.5×) under
the ``HEAT_TPU_WIRE_QUANT`` gate. Running the codec pass AFTER
``_select`` is what makes the census gate-invariant by construction:
the gate can change how many bytes each collective carries, never which
strategy wins or how many collectives launch. Admissibility is the
numerics-tolerance policy: float32 transient exchanges of at least
``QUANT_MIN_WIRE_BYTES`` full-width — everything else (ints, f64,
small moves, the materializing replicate/gather strategies) ships
exact-bit under every gate value.

Two-tier topology (ISSUE 8): at a tiered topology
(``HEAT_TPU_TOPOLOGY``, ``core.communication.Topology`` — ``auto``
reads ``slice_index`` off the resolved world, ``SxC`` forces a
simulated factorization) every candidate is priced per tier: a flat
collective whose replica groups span slices rides DCN (its steps carry
``tier="dcn"`` and cost ``DCN_PENALTY`` ≈ 8× per byte — the slowest
edge in the group governs the collective), and a new
``hierarchical-a2a`` strategy decomposes each cross-slice all-to-all
into an intra-slice pivot (the cheap tier carries the volume,
``L·(C-1)/C`` on ICI) plus an inter-slice exchange of pre-packed
per-slice rows (the expensive tier ships only the bytes that must
cross, ``L·(S-1)/S`` — the portable-redistribution factorization of
arXiv:2112.01075 applied across tiers). The DCN group is the first
group the wire codec targets: in hierarchical plans the admissibility
policy quantizes ONLY the ``tier="dcn"`` exchanges (the ICI hop is
wire-cheap and stays exact, halving the codec error for free). Tier
annotations and the schedule-level ``topology`` annotation fold into
the canonical serialization and ``plan_id``; with the topology unset or
``1xN`` no annotation exists and every plan is byte-identical to the
pre-topology era.

Plans are cached per ``(spec, budget, codec, topology)`` and feed the
PR-1 telemetry registry: ``redist.plan_cache.{hit,miss}``,
``redist.planned_bytes``, ``redist.steps``, ``redist.peak_bytes``.
"""

from __future__ import annotations

import threading

from typing import Dict, List, Optional, Tuple

import numpy as np

from ..core import gates as _gates
from ..observability import events as _obs_events
from ..observability import telemetry as _telemetry
from .schedule import Schedule, Step
from .spec import RedistSpec

__all__ = [
    "ALPHA_BYTES",
    "DEFAULT_BUDGET_MB",
    "OVERLAP_ENV",
    "QUANT_MIN_WIRE_BYTES",
    "WIRE_QUANT_ENV",
    "budget_bytes",
    "clear_plan_cache",
    "explain",
    "golden_specs",
    "overlap_mode",
    "plan",
    "planner_enabled",
    "quant_tolerance",
    "resolve_topology",
    "tier_time_model",
    "wire_quant_gate",
    "wire_quant_mode",
]

#: per-collective launch latency expressed in byte-equivalents (~1 MiB
#: of ICI time per collective dispatch): makes step count and byte
#: volume comparable in one scalar cost.
ALPHA_BYTES = 1 << 20

DEFAULT_BUDGET_MB = 256
_BUDGET_ENV = "HEAT_TPU_REDIST_BUDGET_MB"
_ENABLE_ENV = "HEAT_TPU_REDIST_PLANNER"
OVERLAP_ENV = "HEAT_TPU_REDIST_OVERLAP"

#: pipelinable exchanges are chunked into laps of roughly this size even
#: when the peak-memory budget alone would not require chunking — laps
#: are what the depth-2 pipeline overlaps (chunk k's relayout copy under
#: chunk k+1's wire). Gate-INDEPENDENT: the lap structure (and therefore
#: the collective census) is identical overlap-on and overlap-off; the
#: HEAT_TPU_REDIST_OVERLAP gate only controls the executor's issue order.
OVERLAP_GRAIN_BYTES = 32 << 20
_OVERLAP_MAX_LAPS = 4

WIRE_QUANT_ENV = "HEAT_TPU_WIRE_QUANT"

#: a collective GROUP (one chunk pipeline / ring / standalone exchange)
#: engages the wire codec only when its full-width payload reaches this
#: size — smaller exchanges are latency-bound (ALPHA, not bytes), and
#: keeping them exact-bit is what lets every small-array contract in
#: the suite (executor equivalence, pinned censuses, escape-hatch
#: parity) hold verbatim even under the forced HEAT_TPU_WIRE_QUANT=1
#: CI leg.
QUANT_MIN_WIRE_BYTES = 2 << 20

#: strategies whose collectives ship TRANSIENT exchange payloads — the
#: codec's domain. ``replicate``/``gather-reshape`` materialize the
#: array values compute then consumes, so they stay exact-bit always.
_QUANT_STRATEGIES = (
    "all-to-all", "chunked-all-to-all", "ring", "split0-pivot", "packed-pivot",
    "hierarchical-a2a",
)

_plan_lock = threading.Lock()
_plan_cache: Dict[Tuple[RedistSpec, int, str], Schedule] = {}
#: bounded like the executor's program caches (lru_cache(512)); planning
#: is cheap pure Python, so FIFO eviction on overflow is plenty
_PLAN_CACHE_MAX = 4096


def planner_enabled() -> bool:
    """Planner routing switch (``HEAT_TPU_REDIST_PLANNER=0`` restores
    the legacy single-device_put relayout paths)."""
    val = _gates.get(_ENABLE_ENV, "1").strip().lower()
    return val not in ("0", "false", "off", "no")


def overlap_mode() -> str:
    """Resolved ``HEAT_TPU_REDIST_OVERLAP`` mode (``"0"``/``"1"``/
    ``"auto"``). ``0`` forces every executor program (and the linalg
    collective-matmul forms) into the sequential oracle, ``1`` forces
    the software-pipelined forms everywhere they exist, and the default
    ``auto`` follows the plan's overlap annotation for redistribution
    programs (pipelining is a free reordering — bit-identical, census
    unchanged) while the linalg ring decompositions, which trade an
    all-gather/all-reduce for a byte-equivalent ppermute ring, engage
    only on the TPU backend where the latency hiding pays."""
    v = _gates.get(OVERLAP_ENV, "auto").strip().lower()
    if v in ("0", "off", "false", "no"):
        return "0"
    if v in ("1", "on", "true", "force", "yes"):
        return "1"
    return "auto"


def wire_quant_mode() -> str:
    """Parsed ``HEAT_TPU_WIRE_QUANT`` (``"0"``/``"1"``/``"bf16"``/
    ``"auto"``). ``0`` is the escape hatch (every wire stays full-width
    exact-bit — the PR 6 program forms verbatim); ``1`` forces the int8
    codec on every admissible exchange on any backend (the CI leg);
    ``bf16`` forces the cast codec the same way; the default ``auto``
    engages the lossy int8 codec only on the TPU backend — where the
    ICI wire is the modeled binding term and the pinned tolerance is
    the documented trade — and keeps every other backend exact-bit, so
    the CPU tier-1 contracts hold untouched by default."""
    v = _gates.get(WIRE_QUANT_ENV, "auto").strip().lower()
    if v in ("0", "off", "false", "no"):
        return "0"
    if v in ("1", "on", "true", "force", "yes", "int8"):
        return "1"
    if v == "bf16":
        return "bf16"
    return "auto"


def wire_quant_gate() -> Optional[str]:
    """The codec mode the current gate resolves to (``"int8"``/
    ``"bf16"``) or ``None`` when every wire stays full-width. Per-spec
    admissibility (dtype/strategy/size — the numerics-tolerance policy)
    is decided separately at planning time."""
    m = wire_quant_mode()
    if m == "0":
        return None
    if m == "1":
        return "int8"
    if m == "bf16":
        return "bf16"
    import jax

    return "int8" if jax.default_backend() == "tpu" else None


def quant_tolerance(mode: Optional[str]) -> float:
    """The per-crossing error bound the planner declares for plans it
    quantizes under ``mode`` (the ``quant.tol`` annotation value) —
    the codec's pinned tolerance, 0.0 for ``None`` (exact-bit wires).
    Read-only delegation to :func:`heat_tpu.kernels.quant.tolerance`:
    the planner annotates exactly what the codec guarantees, and the
    ``tolerance`` plan invariant (ht.analysis.check_tolerance) proves
    the dumped annotation still equals this recomputation."""
    if mode is None:
        return 0.0
    from ..kernels import quant as _quant_mod

    return float(_quant_mod.tolerance(mode))


def _dcn_penalty() -> int:
    from ..core import tiers as _tiers

    return _tiers.penalty("dcn")


def resolve_topology(mesh_size: int, override=None) -> Optional[Tuple[int, int]]:
    """``(n_slices, chips_per_slice)`` of the TIERED topology governing
    a ``mesh_size`` mesh, or ``None`` when flat (one ICI domain — every
    pre-ISSUE-8 plan). ``override``: ``None`` resolves the ambient
    ``HEAT_TPU_TOPOLOGY`` (``auto`` on the resolved world's
    ``slice_index``), ``"flat"`` forces flat, an ``"SxC"`` string /
    ``Topology`` / ``(S, C)`` tuple forces that factorization (falling
    back to flat when the product does not equal ``mesh_size``)."""
    if isinstance(override, tuple):
        S, C = int(override[0]), int(override[1])
        return (S, C) if S > 1 and S * C == int(mesh_size) else None
    from ..core import communication as _comm

    t = _comm.topology_for(mesh_size, override)
    return (t.n_slices, t.chips_per_slice) if t.tiered else None


def _topo_annotation(topo: Tuple[int, int]) -> dict:
    return {
        "n_slices": int(topo[0]),
        "chips_per_slice": int(topo[1]),
        "dcn_penalty": _dcn_penalty(),
    }


def tier_time_model(sched: Schedule) -> dict:
    """Analytic per-device wall-time split of a plan's payload over the
    lattice edges it rides (``core.tiers.transfer_time`` at the v5e
    constants; no DCN/PCIe hardware is driven on the CPU container).
    Flat plans price everything at ICI; staged plans (ISSUE 11)
    additionally carry the ``pcie`` staging traffic."""
    from ..core import tiers as _tiers

    tb = sched.tier_bytes()
    ici_s = _tiers.transfer_time(tb["ici"], "ici")
    dcn_s = _tiers.transfer_time(tb["dcn"], "dcn")
    out = {
        "ici_bytes": tb["ici"],
        "dcn_bytes": tb["dcn"],
        "ici_s": ici_s,
        "dcn_s": dcn_s,
        "total_s": ici_s + dcn_s,
    }
    if tb.get("pcie"):
        pcie_s = _tiers.transfer_time(tb["pcie"], "pcie")
        out["pcie_bytes"] = tb["pcie"]
        out["pcie_s"] = pcie_s
        out["total_s"] = ici_s + dcn_s + pcie_s
    return out


def budget_bytes() -> int:
    """Per-device peak-memory budget for redistribution transients
    (``HEAT_TPU_REDIST_BUDGET_MB``, default 256 MiB)."""
    raw = _gates.get(_BUDGET_ENV, "")
    try:
        mb = int(raw) if raw.strip() else DEFAULT_BUDGET_MB
    except ValueError:
        mb = DEFAULT_BUDGET_MB
    return max(1, mb) << 20


def clear_plan_cache() -> int:
    """Drop every cached schedule; returns the eviction count. Plans
    are pure metadata keyed on (spec, budget, codec, topology) — a
    world change can never serve a WRONG one — but a resized world
    leaves the dead world's entries unreachable, and the elastic
    runtime's eviction sweep (``heat_tpu.resilience.elastic.
    invalidate_caches``, ISSUE 13) reclaims them here."""
    with _plan_lock:
        n = len(_plan_cache)
        _plan_cache.clear()
    return n


# --------------------------------------------------------------------- #
# geometry helpers                                                      #
# --------------------------------------------------------------------- #
def _prod(shape) -> int:
    n = 1
    for s in shape:
        n *= int(s)
    return n


def _pad_extent(n: int, p: int) -> int:
    from ..core import _padding

    return _padding.pad_extent(int(n), int(p))


def _divisor_chunks(extent: int, needed: int) -> int:
    """Smallest chunk count >= ``needed`` that divides ``extent`` (chunks
    must be equal-sized for the scatter reassembly to be static)."""
    extent = max(int(extent), 1)
    needed = min(max(1, int(needed)), extent)
    for c in range(needed, extent + 1):
        if extent % c == 0:
            return c
    return extent


def _local_move_bytes(spec: RedistSpec) -> int:
    """Per-device bytes of the doubly-padded shard a split i->j move
    exchanges (source split axis padded for the source layout, dest
    split axis padded so the tiled all-to-all divides evenly)."""
    p = spec.mesh_size
    shape = list(spec.gshape)
    shape[spec.src_split] = _pad_extent(shape[spec.src_split], p)
    shape[spec.dst_split] = _pad_extent(shape[spec.dst_split], p)
    return _prod(shape) // p * spec.itemsize


# --------------------------------------------------------------------- #
# lane geometry (the kernels.relayout cost term)                         #
# --------------------------------------------------------------------- #
def _fill(minor: int) -> float:
    from ..kernels import relayout as _relayout

    return _relayout.lane_fill(minor)


def _pack_threshold() -> float:
    from ..kernels import relayout as _relayout

    return _relayout.PACK_FILL_THRESHOLD


def _shard_minor(shape, split: Optional[int], p: int) -> int:
    """Minor-dim extent of the local shard of (shape, split)."""
    if not shape:
        return 1
    loc = [int(v) for v in shape]
    if split is not None:
        loc[split] = _pad_extent(loc[split], p) // p
    return max(loc[-1], 1)


def _exchange_fill(shape, i: int, j: int, p: int) -> float:
    """Worst lane fill among the buffers a split i<->j exchange of
    ``shape`` touches (pre-exchange: split i, axis j padded;
    post-exchange: split j, axis i still padded)."""

    def minor_of(split):
        loc = [int(v) for v in shape]
        loc[i] = _pad_extent(loc[i], p)
        loc[j] = _pad_extent(loc[j], p)
        loc[split] //= p
        return max(loc[-1], 1)

    return min(_fill(minor_of(i)), _fill(minor_of(j)))


# --------------------------------------------------------------------- #
# overlap (software-pipelining) model                                   #
# --------------------------------------------------------------------- #
def _overlap_laps(L: int) -> int:
    """Lap count the pipeline wants for an exchange of ``L`` local
    bytes: ~OVERLAP_GRAIN_BYTES laps (capped) once the buffer is big
    enough that per-lap ALPHA overhead is noise, else 1 (no pipelining —
    small moves stay one collective and the pinned censuses hold)."""
    L = int(L)
    if L < 2 * OVERLAP_GRAIN_BYTES:
        return 1
    return min(_OVERLAP_MAX_LAPS, L // OVERLAP_GRAIN_BYTES)


def _lap_count(extent: int, L: int, budget: int) -> int:
    """Chunk count for a pipelinable exchange over ``extent``: the
    larger of the budget requirement and the overlap grain, rounded to a
    divisor of ``extent``. Overlap-motivated chunking is BEST-EFFORT:
    equal laps need a divisor, and an extent with no small one (a prime
    extent rounds all the way up to ``extent`` itself) must not explode
    into a million-step schedule for a move the budget was happy to run
    in one collective — past 4x the grain cap the overlap ask is
    dropped and only the budget requirement stands."""
    need_budget = -(-2 * L // budget)
    c_budget = _divisor_chunks(extent, need_budget)
    want = max(need_budget, _overlap_laps(L))
    if want <= need_budget:
        return c_budget
    c = _divisor_chunks(extent, want)
    if c > 4 * _OVERLAP_MAX_LAPS:
        return c_budget
    return c


def _overlap_group(tag: str, laps: int, wire_bytes: int, copy_bytes: int) -> Optional[dict]:
    """Critical-path model of one pipelined chunk group at depth 2.
    Sequentially each lap pays ``wire + copy`` (the collective, then the
    reassembly copy of its result); double-buffered, lap k's copy runs
    under lap k+1's wire, so the steady state costs ``max(wire, copy)``
    per stage pair and only the first wire / last copy are exposed:

        critical_path = w + (laps - 1) * max(w, c) + c
        (w = wire_bytes / laps, c = copy_bytes / laps)

    Returns ``None`` when there is nothing to pipeline (laps < 2) or the
    model shows no gain."""
    laps = int(laps)
    wire_bytes, copy_bytes = int(wire_bytes), int(copy_bytes)
    if laps < 2:
        return None
    w, c = wire_bytes // laps, copy_bytes // laps
    cp = w + (laps - 1) * max(w, c) + c
    seq = wire_bytes + copy_bytes
    if cp >= seq:
        return None
    return {
        "tag": tag,
        "laps": laps,
        "wire_bytes": wire_bytes,
        "copy_bytes": copy_bytes,
        "sequential_bytes": seq,
        "critical_path_bytes": int(cp),
    }


def _overlap_annotation(groups: List[Optional[dict]]) -> Optional[dict]:
    """Fold per-group critical-path models into the Schedule-level
    annotation (None when no group pipelines — the plan is sequential
    and serializes without the key's contents)."""
    groups = [g for g in groups if g]
    if not groups:
        return None
    seq = sum(g["sequential_bytes"] for g in groups)
    cp = sum(g["critical_path_bytes"] for g in groups)
    return {
        "depth": 2,
        "groups": groups,
        "sequential_bytes": int(seq),
        "critical_path_bytes": int(cp),
        "model_speedup": round(seq / cp, 4),
    }


# --------------------------------------------------------------------- #
# candidate builders                                                    #
# --------------------------------------------------------------------- #
def _a2a_chunk_steps(
    L: int,
    p: int,
    C: int,
    what: str,
    pad_step: Optional[Step],
    tail_slice: Optional[Step],
    lane_fill: float = 1.0,
    pipe: Optional[str] = None,
) -> List[Step]:
    """C laps of slice -> all-to-all, then a scatter reassembly (written
    in place into the destination buffer: no transient). ``lane_fill``
    annotates the collective steps with the VREG fill of the buffers
    they stream (1.0 = full lanes, the packed forms). ``pipe`` tags the
    lap steps as one software-pipelined group (C >= 2 only): the
    executor may then overlap chunk k's scatter with chunk k+1's
    collective."""
    steps: List[Step] = []
    if pad_step is not None:
        steps.append(pad_step)
    crossing = L * (p - 1) // p  # the diagonal block stays home
    if C <= 1:
        steps.append(
            Step(
                "all_to_all",
                bytes_moved=crossing,
                peak_bytes=2 * L,
                detail=what,
                lane_fill=lane_fill,
            )
        )
    else:
        for c in range(C):
            steps.append(
                Step(
                    "slice",
                    peak_bytes=L // C,
                    detail=f"chunk {c}/{C} of {what}",
                    chunk=c,
                    overlap=pipe,
                )
            )
            steps.append(
                Step(
                    "all_to_all",
                    bytes_moved=crossing // C,
                    peak_bytes=2 * L // C,
                    detail=what,
                    chunk=c,
                    lane_fill=lane_fill,
                    overlap=pipe,
                )
            )
        steps.append(
            Step(
                "concat",
                peak_bytes=0,
                detail="scatter chunks into dst shard",
                overlap=pipe,
            )
        )
    if tail_slice is not None:
        steps.append(tail_slice)
    return steps


def _a2a_group(tag: str, L: int, p: int, C: int, lane_fill: float) -> Optional[dict]:
    """Overlap group for a C-lap chunked all-to-all of ``L`` local
    bytes: wire = the crossing payload, copy = the scatter reassembly
    write of the received laps, both lane-amplified like the cost
    model's step accounting."""
    fill = max(float(lane_fill), 1e-9)
    crossing = L * (p - 1) // p
    return _overlap_group(tag, C, int(crossing / fill), int(L / fill))


# --------------------------------------------------------------------- #
# two-tier topology (ISSUE 8): tier classification + hierarchical a2a   #
# --------------------------------------------------------------------- #
def _tier_group(
    tag: str, laps: int, ici_bytes: int, dcn_bytes: int, copy_bytes: int
) -> Optional[dict]:
    """Critical-path model of one pipelined chunk group at a TIERED
    topology: a lap's ICI hop, its (penalty-priced) DCN hop, and the
    reassembly copy each occupy a different engine, so the depth-2
    steady state prices a lap at ``max(ici, dcn·penalty, copy)`` with
    the first wire legs and last copy exposed. ``wire_bytes`` is kept in
    ICI byte-equivalents (``ici + dcn·penalty``) so the schedule-level
    ``sequential_model_bytes``/``critical_path_bytes`` arithmetic is
    unit-consistent with the flat groups."""
    laps = int(laps)
    if laps < 2:
        return None
    pen = _dcn_penalty()
    ici_bytes, dcn_bytes, copy_bytes = int(ici_bytes), int(dcn_bytes), int(copy_bytes)
    wi, wd, c = ici_bytes // laps, dcn_bytes * pen // laps, copy_bytes // laps
    cp = wi + wd + c + (laps - 1) * max(wi, wd, c)
    wire_eq = ici_bytes + dcn_bytes * pen
    seq = wire_eq + copy_bytes
    if cp >= seq:
        return None
    return {
        "tag": tag,
        "laps": laps,
        "wire_bytes": int(wire_eq),
        "copy_bytes": copy_bytes,
        "ici_bytes": ici_bytes,
        "dcn_bytes": dcn_bytes,
        "dcn_penalty": pen,
        "sequential_bytes": int(seq),
        "critical_path_bytes": int(cp),
    }


def _hier_a2a_group(
    tag: str, L: int, topo: Tuple[int, int], laps: int, lane_fill: float
) -> Optional[dict]:
    """Tier group for a ``laps``-lap hierarchical all-to-all of ``L``
    local bytes at topology ``(S, C)``: the intra-slice pivot carries
    ``L·(C-1)/C`` on ICI, the inter-slice exchange ``L·(S-1)/S`` on
    DCN, and the scatter reassembly writes ``L``."""
    S, C = topo
    fill = max(float(lane_fill), 1e-9)
    return _tier_group(
        tag,
        laps,
        int(L * (C - 1) // C / fill),
        int(L * (S - 1) // S / fill),
        int(L / fill),
    )


def _with_tier(st: Step, tier: str) -> Step:
    return Step(
        st.kind,
        bytes_moved=st.bytes_moved,
        peak_bytes=st.peak_bytes,
        detail=st.detail,
        chunk=st.chunk,
        bytes_copied=st.bytes_copied,
        lane_fill=st.lane_fill,
        overlap=st.overlap,
        tier=tier,
    )


def _tier_flat(sched: Schedule, topo: Optional[Tuple[int, int]]) -> Schedule:
    """Classify a FLAT-structure candidate at a tiered topology: its
    replica groups span the whole mesh, so every collective rides DCN —
    each collective step gains ``tier="dcn"`` (the cost model then
    prices its bytes at the penalty) and the schedule carries the
    topology annotation. Structure, census, and executor program form
    are unchanged — only the price and the serialization."""
    if topo is None or not any(st.is_collective for st in sched.steps):
        return sched
    steps = [_with_tier(st, "dcn") if st.is_collective else st for st in sched.steps]
    overlap = sched.overlap
    if overlap:
        rebuilt = [
            _tier_group(g["tag"], g["laps"], 0, g["wire_bytes"], g["copy_bytes"])
            for g in overlap["groups"]
        ]
        overlap = _overlap_annotation(rebuilt)
    return Schedule(
        sched.spec,
        sched.strategy,
        steps,
        sched.budget_bytes,
        notes=sched.notes,
        overlap=overlap,
        quant=sched.quant,
        topology=_topo_annotation(topo),
    )


def _hier_chunk_steps(
    L: int,
    topo: Tuple[int, int],
    K: int,
    what: str,
    pad_step: Optional[Step],
    tail_slice: Optional[Step],
    lane_fill: float = 1.0,
    pipe: Optional[str] = None,
) -> List[Step]:
    """The hierarchical counterpart of :func:`_a2a_chunk_steps`: K laps
    of slice → intra-slice all-to-all (chip subgroups, the cheap tier
    carries the volume) → inter-slice all-to-all of pre-packed per-slice
    rows (the expensive tier ships only the bytes that must cross) →
    scatter reassembly. Census: 2·K all-to-alls, tiers ici/dcn."""
    S, C = topo
    steps: List[Step] = []
    if pad_step is not None:
        steps.append(pad_step)
    ici_cross = L * (C - 1) // C
    dcn_cross = L * (S - 1) // S
    pipe = pipe if K > 1 else None  # single-lap: nothing to pipeline

    def lap(chunk: Optional[int], l_bytes: int):
        out = []
        if chunk is not None:
            out.append(
                Step(
                    "slice",
                    peak_bytes=l_bytes,
                    detail=f"chunk {chunk}/{K} of {what}",
                    chunk=chunk,
                    overlap=pipe,
                )
            )
        out.append(
            Step(
                "all_to_all",
                bytes_moved=ici_cross // max(K, 1),
                peak_bytes=2 * l_bytes,
                detail=f"intra-slice pivot of {what} (chip subgroups)",
                chunk=chunk,
                lane_fill=lane_fill,
                overlap=pipe,
                tier="ici",
            )
        )
        out.append(
            Step(
                "all_to_all",
                bytes_moved=dcn_cross // max(K, 1),
                peak_bytes=2 * l_bytes,
                detail=(
                    f"inter-slice exchange of {what} (pre-packed per-slice "
                    "rows — minimum DCN bytes)"
                ),
                chunk=chunk,
                lane_fill=lane_fill,
                overlap=pipe,
                tier="dcn",
            )
        )
        return out

    if K <= 1:
        steps += lap(None, L)
    else:
        for c in range(K):
            steps += lap(c, L // K)
        steps.append(
            Step(
                "concat",
                peak_bytes=0,
                detail="scatter chunks into dst shard",
                overlap=pipe,
            )
        )
    if tail_slice is not None:
        steps.append(tail_slice)
    return steps


def _resplit_candidates(
    spec: RedistSpec, budget: int, topo: Optional[Tuple[int, int]] = None
) -> List[Schedule]:
    """split i -> split j candidates: (chunked) all-to-all and the ring
    — plus, at a tiered topology, the ``hierarchical-a2a`` decomposition
    (and the flat forms DCN-classified, since their replica groups span
    slices)."""
    p = spec.mesh_size
    i, j = spec.src_split, spec.dst_split
    L = _local_move_bytes(spec)
    Nj, Njp = spec.gshape[j], _pad_extent(spec.gshape[j], p)
    Ni, Nip = spec.gshape[i], _pad_extent(spec.gshape[i], p)
    pad_step = (
        Step("pad", peak_bytes=L, detail=f"pad axis {j} {Nj}->{Njp} (local)")
        if Njp != Nj
        else None
    )
    tail = (
        Step("slice", peak_bytes=L, detail=f"drop axis {i} pad {Nip}->{Ni} (local)")
        if Nip != Ni
        else None
    )
    # concat axis is the source split axis: its local extent is what the
    # chunk laps tile over. Laps come from the tighter of the budget
    # requirement and the overlap grain (pipelinable buffers chunk even
    # under a roomy budget so the executor has stages to double-buffer).
    concat_extent = Nip // p
    C = _lap_count(concat_extent, L, budget)

    what = f"split {i}->{j}"
    fill = _exchange_fill(spec.gshape, i, j, p)
    a2a = Schedule(
        spec,
        "all-to-all" if C <= 1 else "chunked-all-to-all",
        _a2a_chunk_steps(L, p, C, what, pad_step, tail, lane_fill=fill, pipe="pipe0"),
        budget,
        notes=f"C={C} chunks over local axis-{i} extent {concat_extent}" if C > 1 else "",
        overlap=_overlap_annotation([_a2a_group("pipe0", L, p, C, fill)]) if C > 1 else None,
    )

    ring_steps: List[Step] = []
    if pad_step is not None:
        ring_steps.append(pad_step)
    blk = L // p
    for d in range(1, p):
        ring_steps.append(
            Step(
                "ppermute",
                bytes_moved=blk,
                peak_bytes=2 * blk,
                detail=f"hop distance {d}: neighbor block of {what}",
                lane_fill=fill,
                overlap="ring0" if p > 2 else None,
            )
        )
    if tail is not None:
        ring_steps.append(tail)
    # ring overlap: hop d+1's ppermute flies while hop d's received
    # block is scattered into the destination (wire = copy = one
    # neighbor block per hop)
    ring_group = (
        _overlap_group(
            "ring0", p - 1, int(blk * (p - 1) / max(fill, 1e-9)),
            int(blk * (p - 1) / max(fill, 1e-9)),
        )
        if p > 2
        else None
    )
    ring = Schedule(
        spec,
        "ring",
        ring_steps,
        budget,
        notes="p-1 ppermute hops, one neighbor block in flight per step",
        overlap=_overlap_annotation([ring_group]),
    )
    if topo is None:
        return [a2a, ring]
    # tiered topology: the flat forms span slices (every collective —
    # including each +d ring hop, whose wraparound neighbors cross the
    # slice boundary — rides DCN at the penalty price), and the
    # hierarchical decomposition competes
    hier_steps = _hier_chunk_steps(
        L, topo, C, what, pad_step, tail, lane_fill=fill, pipe="pipe0"
    )
    hier = Schedule(
        spec,
        "hierarchical-a2a",
        hier_steps,
        budget,
        notes=(
            f"two-tier decomposition at {topo[0]}x{topo[1]}: intra-slice "
            "pivot (ICI carries the volume) + inter-slice exchange of "
            "pre-packed per-slice rows (minimum DCN bytes)"
            + (f"; C={C} chunks" if C > 1 else "")
        ),
        overlap=_overlap_annotation([_hier_a2a_group("pipe0", L, topo, C, fill)]),
        topology=_topo_annotation(topo),
    )
    return [_tier_flat(a2a, topo), _tier_flat(ring, topo), hier]


def _pivot_valid(spec: RedistSpec) -> bool:
    """The split-0 pivot needs the leading extents to divide the mesh on
    both sides (device blocks are then contiguous runs of the row-major
    element order, so the middle reshape is LOCAL)."""
    p = spec.mesh_size
    in0 = spec.gshape[0] if spec.gshape else 0
    out0 = spec.out_shape[0] if spec.out_shape else 0
    return (
        len(spec.gshape) >= 1
        and len(spec.out_shape) >= 1
        and in0 > 0
        and out0 > 0
        and in0 % p == 0
        and out0 % p == 0
    )


def _pivot_schedule(
    spec: RedistSpec, budget: int, topo: Optional[Tuple[int, int]] = None
) -> Schedule:
    """The split-0 pivot. ``topo`` builds the HIERARCHICAL variant
    (ISSUE 8): each stage exchange decomposes into the intra-slice +
    inter-slice pair, the strategy is named ``hierarchical-a2a``, and
    the overlap groups price laps at ``max(ici, dcn·penalty, copy)``."""
    p = spec.mesh_size
    s, t = spec.src_split, spec.dst_split
    item = spec.itemsize
    steps: List[Step] = []
    groups: List[Optional[dict]] = []
    shard = spec.size // p * item  # logical bytes per device block

    def stage(L, C, what, fill, pipe):
        if topo is None:
            groups.append(_a2a_group(pipe, L, p, C, fill) if C > 1 else None)
            return _a2a_chunk_steps(
                L, p, C, what, None, None, lane_fill=fill, pipe=pipe
            )
        groups.append(_hier_a2a_group(pipe, L, topo, C, fill))
        return _hier_chunk_steps(L, topo, C, what, None, None, lane_fill=fill, pipe=pipe)

    n_coll = 0
    if s is not None and s != 0:
        L1 = _prod(
            [_pad_extent(d, p) if ax == s else d for ax, d in enumerate(spec.gshape)]
        ) // p * item
        C1 = _lap_count(_pad_extent(spec.gshape[s], p) // p, L1, budget)
        fill_in = _exchange_fill(spec.gshape, s, 0, p)
        steps += stage(L1, C1, f"split {s}->0 (pivot in)", fill_in, "pipe0")
        n_coll += C1
        if _pad_extent(spec.gshape[s], p) != spec.gshape[s]:
            steps.append(
                Step("slice", peak_bytes=shard, detail=f"drop axis {s} pad (local)")
            )
    steps.append(
        Step(
            "reshape",
            peak_bytes=shard,
            bytes_copied=shard,
            lane_fill=min(
                _fill(spec.gshape[-1] if spec.gshape else 1),
                _fill(spec.out_shape[-1] if spec.out_shape else 1),
            ),
            detail="local row-major reshape at full minor-dim width",
        )
    )
    if t is not None and t != 0:
        out_t, out_tp = spec.out_shape[t], _pad_extent(spec.out_shape[t], p)
        L2 = _prod(
            [_pad_extent(d, p) if ax == t else d for ax, d in enumerate(spec.out_shape)]
        ) // p * item
        if out_tp != out_t:
            pad_minor = out_tp if t == len(spec.out_shape) - 1 else spec.out_shape[-1]
            steps.append(
                Step(
                    "pad",
                    peak_bytes=L2,
                    bytes_copied=L2,
                    lane_fill=_fill(pad_minor),
                    detail=f"pad axis {t} {out_t}->{out_tp} (local)",
                )
            )
        C2 = _lap_count(spec.out_shape[0] // p, L2, budget)
        fill_out = _exchange_fill(spec.out_shape, 0, t, p)
        steps += stage(L2, C2, f"split 0->{t} (pivot out)", fill_out, "pipe1")
        n_coll += C2
    if n_coll:
        strategy = "hierarchical-a2a" if topo is not None else "split0-pivot"
    else:
        strategy = "local-reshape"
    return Schedule(
        spec,
        strategy,
        steps,
        budget,
        notes="minor-dim packing: heavy copies run on the split-0 layout"
        + (
            f"; two-tier pivot stages at {topo[0]}x{topo[1]}"
            if topo is not None and n_coll
            else ""
        ),
        overlap=_overlap_annotation(groups),
        topology=_topo_annotation(topo) if topo is not None and n_coll else None,
    )


def _packed_sides(spec: RedistSpec) -> Tuple[bool, bool]:
    """(packed_in, packed_out): which pivot stages engage the
    lane-packed form — 2-D pivots whose shard minor dim fills less than
    ``kernels.relayout.PACK_FILL_THRESHOLD`` of the lane axis."""
    p = spec.mesh_size
    if (
        not spec.is_reshape
        or len(spec.gshape) != 2
        or len(spec.out_shape) != 2
        or not _pivot_valid(spec)
    ):
        return False, False
    thr = _pack_threshold()
    s, t = spec.src_split, spec.dst_split
    packed_in = s == 1 and _fill(_pad_extent(spec.gshape[1], p) // p) < thr
    packed_out = t == 1 and _fill(_pad_extent(spec.out_shape[1], p) // p) < thr
    return packed_in, packed_out


def _packed_pivot_schedule(
    spec: RedistSpec, budget: int, topo: Optional[Tuple[int, int]] = None
) -> Schedule:
    """The split-0 pivot with its narrow-minor stages rewritten on
    lane-packed buffers (``heat_tpu.kernels.relayout``): the chunked
    all-to-alls stream (p, rows·cols/p) column-grouped FLAT buffers
    (full VREGs), and the only lane-amplified copy left is the single
    unpack that materializes the destination's requested narrow layout.
    Same collective census as the direct pivot — the packing changes
    layouts, never movement. ``topo`` builds the hierarchical variant
    (strategy ``hierarchical-a2a``): the packed flat buffers decompose
    across tiers exactly like the direct ones."""
    p = spec.mesh_size
    item = spec.itemsize
    s, t = spec.src_split, spec.dst_split
    (r0, c0), (r1, c1) = spec.gshape, spec.out_shape
    c0p, c1p = _pad_extent(c0, p), _pad_extent(c1, p)
    R0, R1 = r0 // p, r1 // p
    shard = spec.size // p * item
    packed_in, packed_out = _packed_sides(spec)
    steps: List[Step] = []
    groups: List[Optional[dict]] = []

    def stage(L, C, what, fill, pipe):
        if topo is None:
            groups.append(_a2a_group(pipe, L, p, C, fill) if C > 1 else None)
            return _a2a_chunk_steps(
                L, p, C, what, None, None, lane_fill=fill, pipe=pipe
            )
        groups.append(_hier_a2a_group(pipe, L, topo, C, fill))
        return _hier_chunk_steps(L, topo, C, what, None, None, lane_fill=fill, pipe=pipe)

    if s == 1:
        L1 = r0 * c0p // p * item
        C1 = _lap_count(c0p // p, L1, budget)
        if packed_in:
            steps += stage(L1, C1, "split 1->0 (packed pivot in)", 1.0, "pipe0")
            steps.append(
                Step(
                    "unpack",
                    bytes_copied=R0 * c0 * item,
                    peak_bytes=R0 * c0p * item,
                    lane_fill=1.0,
                    detail=(
                        f"lane-unpack: ungroup {p} col-blocks, drop row pad "
                        f"{c0p}->{c0} (kernel-served flat copy)"
                    ),
                )
            )
        else:
            fill_in = _exchange_fill(spec.gshape, 1, 0, p)
            steps += stage(L1, C1, f"split {s}->0 (pivot in)", fill_in, "pipe0")
            if c0p != c0:
                steps.append(
                    Step("slice", peak_bytes=shard, detail="drop axis 1 pad (local)")
                )
    steps.append(
        Step(
            "reshape",
            peak_bytes=shard,
            lane_fill=1.0,
            detail="flat row-major view of the contiguous split-0 block (no narrow materialization)",
        )
    )
    if t == 1:
        L2 = r1 * c1p // p * item
        C2 = _lap_count(R1, L2, budget)
        if packed_out:
            steps.append(
                Step(
                    "pack",
                    bytes_copied=R1 * c1p * item,
                    peak_bytes=R1 * c1p * item,
                    lane_fill=1.0,
                    detail=(
                        f"lane-pack rows {c1}->{c1p} + group {p} col-blocks for "
                        "all-to-all (kernel-served flat copy)"
                    ),
                )
            )
            steps += stage(L2, C2, "split 0->1 (packed pivot out)", 1.0, "pipe1")
            steps.append(
                Step(
                    "unpack",
                    bytes_copied=R1 * c1p * item,
                    peak_bytes=R1 * c1p * item,
                    lane_fill=_fill(c1p // p),
                    detail=(
                        f"materialize dst shard ({r1}, {c1p // p}) — the single "
                        "lane-amplified write the requested layout costs"
                    ),
                )
            )
        else:
            if c1p != c1:
                steps.append(
                    Step(
                        "pad",
                        peak_bytes=L2,
                        bytes_copied=L2,
                        lane_fill=_fill(c1p),
                        detail=f"pad axis 1 {c1}->{c1p} (local)",
                    )
                )
            fill_out = _exchange_fill(spec.out_shape, 0, 1, p)
            steps += stage(L2, C2, f"split 0->{t} (pivot out)", fill_out, "pipe1")
    return Schedule(
        spec,
        "hierarchical-a2a" if topo is not None else "packed-pivot",
        steps,
        budget,
        notes=(
            "lane-packing pivot: collectives and heavy copies run on packed "
            "full-lane buffers (HEAT_TPU_RELAYOUT_KERNEL gates the tiled-copy kernel)"
        )
        + (
            f"; two-tier pivot stages at {topo[0]}x{topo[1]}"
            if topo is not None
            else ""
        ),
        overlap=_overlap_annotation(groups),
        topology=_topo_annotation(topo) if topo is not None else None,
    )


def _gather_reshape_schedule(spec: RedistSpec, budget: int) -> Schedule:
    p = spec.mesh_size
    logical = spec.logical_bytes
    steps = [
        Step(
            "all_gather",
            bytes_moved=logical * (p - 1) // p,
            peak_bytes=logical,
            lane_fill=_fill(_shard_minor(spec.gshape, spec.src_split, p)),
            detail="replicate the full operand (fallback: pivot divisibility failed)"
            if spec.is_reshape
            else "explicit replicate",
        )
    ]
    if spec.is_reshape:
        steps.append(
            Step(
                "reshape",
                peak_bytes=logical,
                bytes_copied=logical,
                lane_fill=min(
                    _fill(spec.gshape[-1] if spec.gshape else 1),
                    _fill(spec.out_shape[-1] if spec.out_shape else 1),
                ),
                detail="replicated reshape",
            )
        )
    if spec.dst_split is not None:
        steps.append(
            Step(
                "slice",
                peak_bytes=spec.dst_shard_bytes,
                bytes_copied=spec.dst_shard_bytes,
                lane_fill=_fill(_shard_minor(spec.out_shape, spec.dst_split, p)),
                detail=f"slice dst shard (split {spec.dst_split})",
            )
        )
    return Schedule(
        spec,
        "gather-reshape" if spec.is_reshape else "replicate",
        steps,
        budget,
        notes="full all-gather — the only strategy that materializes the logical array",
    )


def _cost(s: Schedule) -> int:
    """Byte-equivalent cost: ALPHA per collective launch, plus every
    step's lane-amplified HBM traffic (payload + local relayout copy
    writes, divided by the step's VREG lane fill). A ``tier="dcn"``
    collective's bytes are priced at ``DCN_PENALTY`` (≈ 8×, the
    ICI/DCN bandwidth ratio) — the tier term that makes
    ``hierarchical-a2a`` beat the slice-spanning flat forms exactly on
    the big cross-slice moves (ISSUE 8)."""
    pen = _dcn_penalty() if s.topology else 1
    total = 0
    for st in s.steps:
        eff = st.effective_bytes
        if st.tier == "dcn":
            eff *= pen
        total += (ALPHA_BYTES if st.is_collective else 0) + eff
    return total


def _select(candidates: List[Schedule]) -> Schedule:
    feasible = [c for c in candidates if c.within_budget]
    if feasible:
        return min(feasible, key=_cost)
    # nothing fits: degrade to the smallest footprint and say so —
    # rebuilt (not mutated) so plan_id stays the sha1 of the canonical
    # serialization, notes included
    best = min(candidates, key=lambda c: c.peak_bytes)
    notes = (best.notes + "; " if best.notes else "") + (
        f"over budget: peak {best.peak_bytes} B > {best.budget_bytes} B "
        "(smallest-footprint candidate chosen)"
    )
    return Schedule(
        best.spec, best.strategy, best.steps, best.budget_bytes,
        notes=notes, overlap=best.overlap, topology=best.topology,
    )


# --------------------------------------------------------------------- #
# wire quantization (ISSUE 7): the codec pass over a selected plan      #
# --------------------------------------------------------------------- #
def _quantize_schedule(sched: Schedule, mode: Optional[str]) -> Schedule:
    """Wrap the admissible collective groups of a SELECTED plan in
    ``quantize``/``dequantize`` codec steps (``heat_tpu.kernels.quant``)
    and scale their ``bytes_moved`` to the encoded wire size.

    Runs AFTER strategy selection, on the winner only: the gate can
    therefore never flip which strategy (or how many collectives) a
    spec plans to — censuses and lap structure are identical gate-on vs
    gate-off by construction, which is the invariant every golden pin
    relies on. The numerics-tolerance policy lives here: float32
    payloads only (ints/bools/f64 are never lossy on the wire — they
    ship exact-bit), transient-exchange strategies only (replicate/
    gather-reshape materialize consumed values), and only groups
    shipping at least ``QUANT_MIN_WIRE_BYTES`` full-width (smaller
    exchanges are latency-bound and stay exact). The overlap groups'
    critical-path models are rebuilt on the encoded wire bytes — the
    codec shrinks the ``wire`` leg of ``max(wire, copy)``, which is
    exactly the ICI-bound rows' binding term.

    Tiered plans (ISSUE 8): in a ``hierarchical-a2a`` plan only the
    ``tier="dcn"`` exchanges are codec-eligible — the inter-slice hop
    is the wire-bound leg the decomposition isolated, and it is the
    FIRST group the codec targets; the intra-slice pivot is wire-cheap
    and stays exact (half the codec error for free). Slice-spanning
    FLAT plans quantize all their collectives exactly as before — every
    byte of theirs rides DCN anyway."""
    if mode is None:
        return sched
    spec = sched.spec
    if spec.dtype != "float32" or sched.strategy not in _QUANT_STRATEGIES:
        return sched
    from ..kernels import quant as _quant

    p = spec.mesh_size
    item = spec.itemsize
    hier = sched.strategy == "hierarchical-a2a"
    # the number of independently encoded wire rows per exchange: the
    # destination count of the collective's replica groups — the S
    # slices for the hierarchical DCN hop, the p devices otherwise
    n_dest = int(sched.topology["n_slices"]) if hier else p
    groups: Dict[str, List[int]] = {}
    for idx, st in enumerate(sched.steps):
        if not st.is_collective:
            continue
        if hier and st.tier != "dcn":
            continue  # the ICI pivot ships exact (see docstring)
        key = st.overlap if st.overlap is not None else f"_solo{idx}"
        groups.setdefault(key, []).append(idx)
    sent_of: Dict[int, int] = {}
    for key, idxs in groups.items():
        if sum(sched.steps[i].bytes_moved for i in idxs) < QUANT_MIN_WIRE_BYTES:
            continue
        for i in idxs:
            st = sched.steps[i]
            if st.kind == "ppermute":
                # one neighbor block per hop
                sent_of[i] = _quant.wire_bytes(st.bytes_moved // item, mode)
            else:
                # crossing payload = (n_dest-1) per-destination blocks,
                # each encoded independently (the executor's wire rows)
                blk_elems = st.bytes_moved // (n_dest - 1) // item
                sent_of[i] = (n_dest - 1) * _quant.wire_bytes(blk_elems, mode)
    if not sent_of:
        return sched

    raw_total = sched.bytes_moved
    new_steps: List[Step] = []
    for i, st in enumerate(sched.steps):
        if i not in sent_of:
            new_steps.append(st)
            continue
        sent = sent_of[i]
        raw = st.bytes_moved
        if st.kind == "ppermute":
            full_local = raw
            enc_write = sent
        else:
            # incl. the resident diagonal block
            full_local = raw * n_dest // (n_dest - 1)
            enc_write = sent * n_dest // (n_dest - 1)
        new_steps.append(
            Step(
                "quantize",
                bytes_copied=enc_write,
                peak_bytes=enc_write,
                detail=(
                    f"{mode}-encode wire blocks ({_quant.TILE}-elem tile "
                    f"scales): {raw} B -> {sent} B on the wire "
                    f"(saved {raw - sent} B)"
                ),
                chunk=st.chunk,
                overlap=st.overlap,
            )
        )
        new_steps.append(
            Step(
                st.kind,
                bytes_moved=sent,
                peak_bytes=st.peak_bytes,
                detail=st.detail + f" [{mode} wire]",
                chunk=st.chunk,
                lane_fill=1.0,  # encoded payloads are dense flat byte streams
                overlap=st.overlap,
                tier=st.tier,
            )
        )
        new_steps.append(
            Step(
                "dequantize",
                bytes_copied=0 if st.overlap else full_local,
                peak_bytes=0 if st.overlap else full_local,
                detail=(
                    f"{mode}-decode received blocks"
                    + (
                        " (full-width write rides the group's reassembly copy)"
                        if st.overlap
                        else f" ({full_local} B full-width write)"
                    )
                ),
                chunk=st.chunk,
                overlap=st.overlap,
            )
        )

    new_overlap = sched.overlap
    if sched.overlap:
        rebuilt = []
        for g in sched.overlap["groups"]:
            idxs = [i for i in groups.get(g["tag"], []) if i in sent_of]
            if not idxs:
                rebuilt.append(g)
                continue
            wire_new = sum(sent_of[i] for i in idxs)
            if "ici_bytes" in g:
                # tiered group: the codec shrinks only the DCN leg (the
                # ICI pivot ships exact in hierarchical plans; in
                # slice-spanning flat plans the ICI leg is 0)
                rebuilt.append(
                    _tier_group(
                        g["tag"], g["laps"], g["ici_bytes"], wire_new,
                        g["copy_bytes"],
                    )
                )
            else:
                rebuilt.append(
                    _overlap_group(g["tag"], g["laps"], wire_new, g["copy_bytes"])
                )
        new_overlap = _overlap_annotation(rebuilt)

    sent_total = raw_total - sum(
        sched.steps[i].bytes_moved for i in sent_of
    ) + sum(sent_of.values())
    ann = {
        "mode": mode,
        "tol": _quant.tolerance(mode),
        "bytes_raw": int(raw_total),
        "bytes_sent": int(sent_total),
        "ratio": round(sent_total / raw_total, 4) if raw_total else 1.0,
        "min_group_bytes": QUANT_MIN_WIRE_BYTES,
    }
    notes = sched.notes + ("; " if sched.notes else "") + (
        f"{mode} wire codec on {len(sent_of)} collective step(s) "
        f"(kernels.quant, tol {ann['tol']})"
    )
    return Schedule(
        spec,
        sched.strategy,
        new_steps,
        sched.budget_bytes,
        notes=notes,
        overlap=new_overlap,
        quant=ann,
        topology=sched.topology,
    )


# --------------------------------------------------------------------- #
# the planner                                                           #
# --------------------------------------------------------------------- #
def _build(
    spec: RedistSpec, budget: int, topo: Optional[Tuple[int, int]] = None
) -> Schedule:
    p = spec.mesh_size

    if spec.is_reshape:
        if spec.gshape == spec.reshape_to and spec.src_split == spec.dst_split:
            return Schedule(spec, "noop", [], budget)
        if p <= 1 or spec.size == 0:
            return Schedule(
                spec,
                "local",
                [Step("reshape", peak_bytes=spec.logical_bytes, detail="single-shard reshape")],
                budget,
            )
        if spec.src_split is None:
            steps = [
                Step("reshape", peak_bytes=spec.logical_bytes, detail="replicated reshape")
            ]
            if spec.dst_split is not None:
                steps.append(
                    Step(
                        "slice",
                        peak_bytes=spec.dst_shard_bytes,
                        detail=f"slice dst shard (split {spec.dst_split})",
                    )
                )
            return Schedule(spec, "local-reshape", steps, budget)
        if spec.dst_split is None:
            return _tier_flat(_gather_reshape_schedule(spec, budget), topo)
        candidates = []
        if _pivot_valid(spec):
            candidates.append(_tier_flat(_pivot_schedule(spec, budget), topo))
            if any(_packed_sides(spec)):
                candidates.append(
                    _tier_flat(_packed_pivot_schedule(spec, budget), topo)
                )
            if topo is not None:
                # the hierarchical pivot variants (ISSUE 8): every stage
                # exchange decomposed across tiers
                candidates.append(_pivot_schedule(spec, budget, topo=topo))
                if any(_packed_sides(spec)):
                    candidates.append(_packed_pivot_schedule(spec, budget, topo=topo))
        candidates.append(_tier_flat(_gather_reshape_schedule(spec, budget), topo))
        return _select(candidates)

    # pure resplit
    if spec.src_split == spec.dst_split:
        return Schedule(spec, "noop", [], budget)
    if p <= 1 or spec.size == 0:
        return Schedule(spec, "local", [], budget)
    if spec.src_split is None:
        return Schedule(
            spec,
            "slice",
            [
                Step(
                    "slice",
                    peak_bytes=spec.dst_shard_bytes,
                    detail=f"local shard slice (split {spec.dst_split})",
                )
            ],
            budget,
        )
    if spec.dst_split is None:
        return _tier_flat(_gather_reshape_schedule(spec, budget), topo)
    return _select(_resplit_candidates(spec, budget, topo))


def plan(
    spec: RedistSpec,
    budget: Optional[int] = None,
    quant: Optional[str] = None,
    topology=None,
) -> Schedule:
    """Plan ``spec`` under ``budget`` bytes (default: the env knob).

    ``quant`` pins the wire codec explicitly — ``"0"`` plans the
    full-width exact-bit schedule, ``"int8"``/``"bf16"`` force that
    codec through the admissibility policy, and the default ``None``
    resolves the ``HEAT_TPU_WIRE_QUANT`` gate (:func:`wire_quant_gate`).
    ``topology`` pins the two-tier topology the same way (ISSUE 8):
    ``None`` resolves the ambient ``HEAT_TPU_TOPOLOGY``, ``"flat"``
    forces one ICI domain (the pre-topology plans, byte-identical), an
    ``"SxC"`` string / ``(S, C)`` tuple forces a simulated
    factorization. Plans are cached per (spec, budget, resolved codec,
    resolved topology) — all four are part of the canonical
    serialization and plan_id, so a gate flip can never serve a stale
    plan. Cache hits/misses and the planned byte/step/peak totals feed
    the telemetry registry."""
    b = budget_bytes() if budget is None else int(budget)
    if quant is None:
        qmode = wire_quant_gate()
    elif quant in ("0", "off", None):
        qmode = None
    else:
        from ..kernels.quant import MODES as _MODES

        if quant not in _MODES:
            raise ValueError(f"plan: unknown wire codec {quant!r}")
        qmode = quant
    topo = resolve_topology(spec.mesh_size, topology)
    key = (spec, b, qmode or "0", topo)
    with _plan_lock:
        cached = _plan_cache.get(key)
    if cached is not None:
        if _telemetry._ENABLED:
            _telemetry.inc("redist.plan_cache.hit")
        return cached
    sched = _quantize_schedule(_build(spec, b, topo), qmode)
    with _plan_lock:
        if len(_plan_cache) >= _PLAN_CACHE_MAX:
            _plan_cache.pop(next(iter(_plan_cache)))
        _plan_cache[key] = sched
    if _telemetry._ENABLED:
        _telemetry.inc("redist.plan_cache.miss")
        _telemetry.inc("redist.planned_bytes", sched.bytes_moved)
        _telemetry.inc("redist.steps", sched.n_steps)
        _telemetry.inc("redist.peak_bytes", sched.peak_bytes)
        _obs_events.emit(
            "redist.plan",
            plan_id=sched.plan_id,
            strategy=sched.strategy,
            spec=repr(sched.spec),
            steps=sched.n_steps,
            collectives=sched.collective_counts(),
            peak_bytes=sched.peak_bytes,
            budget_bytes=b,
            overlap_depth=sched.overlap_depth,
            critical_path_model=(
                sched.overlap["model_speedup"] if sched.overlap else None
            ),
            quant=sched.quant["mode"] if sched.quant else None,
            wire_bytes_saved=sched.wire_bytes_raw - sched.wire_bytes_sent,
            topology=f"{topo[0]}x{topo[1]}" if topo else None,
            dcn_bytes=sched.tier_bytes()["dcn"] if topo else 0,
        )
    return sched


def explain(arr, axis=None, *, reshape=None, new_split=None, topology=None) -> Schedule:
    """The chosen redistribution plan for ``arr`` — without executing it.

    ``explain(arr, axis)`` plans the resplit to ``axis``;
    ``explain(arr, reshape=shape, new_split=...)`` plans the
    reshape-with-repartition (``new_split`` defaults the same way
    ``ht.reshape`` defaults it). ``topology`` overrides the ambient
    ``HEAT_TPU_TOPOLOGY`` (``"flat"``, ``"SxC"``, a ``Topology``, or an
    ``(S, C)`` tuple) — what-if planning for a mesh factorization this
    process is not running on. Returns the
    :class:`~heat_tpu.redistribution.schedule.Schedule` the executor
    would compile — strategy, steps, per-step peak-memory accounting,
    plan id.
    """
    from ..core.dndarray import DNDarray
    from ..core.stride_tricks import sanitize_axis

    if not planner_enabled():
        raise RuntimeError(
            "explain: the redistribution planner is disabled "
            f"({_ENABLE_ENV}=0) — resplit/reshape run the legacy "
            "one-collective paths, so there is no plan to show. Unset "
            f"{_ENABLE_ENV} to re-enable planner routing."
        )
    if not isinstance(arr, DNDarray):
        raise TypeError(f"explain expects a DNDarray, got {type(arr)}")
    if arr._is_planar:
        raise TypeError(
            "explain: planar-complex arrays take the legacy relayout path "
            "(the planner routes real/physical layouts only)"
        )
    if reshape is not None:
        # THE resolver the public call uses — explain must build its
        # spec from exactly the (shape, new_split) ht.reshape executes
        from ..core.manipulations import _normalize_reshape_args

        shape, new_split = _normalize_reshape_args(arr, (tuple(reshape),) if isinstance(
            reshape, (tuple, list)
        ) else (reshape,), new_split)
        spec = RedistSpec.normalize(
            arr.gshape,
            np.dtype(arr._phys.dtype).name,
            arr.split,
            new_split,
            arr.comm.size,
            reshape_to=shape,
        )
    else:
        axis = sanitize_axis(arr.gshape, axis)
        spec = RedistSpec.normalize(
            arr.gshape, np.dtype(arr._phys.dtype).name, arr.split, axis, arr.comm.size
        )
    return plan(spec, topology=topology)


# --------------------------------------------------------------------- #
# golden matrix — pinned by tier-1 and the ci.sh determinism leg        #
# --------------------------------------------------------------------- #
def golden_specs() -> List[Tuple[str, RedistSpec]]:
    """The (name, spec) matrix whose plans are golden: strategies and
    step counts are pinned in ``tests/test_redistribution.py`` and the
    serialized plans must be byte-identical run-to-run (ci.sh diffs two
    runs of ``scripts/redist_plans.py``)."""
    S = RedistSpec.normalize
    return [
        ("noop_same_split", S((64, 48), "float32", 1, 1, 8)),
        ("resplit_0_to_1_p8", S((64, 48), "float32", 0, 1, 8)),
        ("resplit_1_to_0_p8", S((64, 48), "float32", 1, 0, 8)),
        ("resplit_0_to_1_int32_p4", S((64, 48), "int32", 0, 1, 4)),
        ("resplit_uneven_p8", S((63, 48), "float32", 0, 1, 8)),
        ("resplit_3d_1_to_2_p8", S((16, 24, 40), "float32", 1, 2, 8)),
        ("replicate_p8", S((64, 48), "float32", 0, None, 8)),
        ("slice_from_replicated_p8", S((64, 48), "float32", None, 1, 8)),
        ("mesh1_resplit", S((64, 48), "float32", 0, 1, 1)),
        ("resplit_chunked_2gb_p8", S((32768, 16384), "float32", 0, 1, 8)),
        ("resplit_ring_8gb_p8", S((131072, 16384), "float32", 0, 1, 8)),
        ("reshape_pivot_p8", S((40960, 40), "float32", 1, 1, 8, reshape_to=(20480, 80))),
        ("reshape_split0_local_p8", S((64, 48), "float32", 0, 0, 8, reshape_to=(32, 96))),
        (
            "reshape_gather_fallback_p8",
            S((1000, 26), "float32", 1, 1, 8, reshape_to=(26, 1000)),
        ),
        (
            "reshape_split1_1gb_p8",
            S((1000, 250000), "float32", 1, 1, 8, reshape_to=(10_000_000, 25)),
        ),
        # the reverse of the 1 GB bench move: narrow minor on the SOURCE
        # side, so the packed pivot engages its lane-unpack stage
        (
            "reshape_packed_rev_p8",
            S((10_000_000, 25), "float32", 1, 1, 8, reshape_to=(1000, 250000)),
        ),
        # lane-friendly companion (minor dims >= 128 end to end): the
        # cost model must keep the DIRECT pivot — packing gains nothing
        (
            "reshape_lane_1gb_p8",
            S((65536, 4096), "float32", 1, 1, 8, reshape_to=(131072, 2048)),
        ),
        # ISSUE 8: the 2x8-acceptance pair — mesh-16 variants of the two
        # 1 GB rows, covered flat here and tiered by the --topology 2x8
        # determinism dump + tests/test_topology.py. The reshape uses the
        # flat-order-preserving 16-divisible view of the 1 GB payload
        # (1000 % 16 != 0 rules the bench shape's pivot out at p=16;
        # (16000, 15625) is the same row-major element order).
        ("resplit_1gb_p16", S((1000, 250000), "float32", 0, 1, 16)),
        (
            "reshape_split1_1gb_p16",
            S((16000, 15625), "float32", 1, 1, 16, reshape_to=(10_000_000, 25)),
        ),
    ]
