"""Schedule IR — the inspectable, golden-testable plan representation.

A :class:`Schedule` is a strategy name plus an ordered list of
:class:`Step`\\ s (slice → collective → concat), each carrying

- ``bytes_moved`` — the per-device payload the step ships across the
  mesh (0 for local copy steps), and
- ``peak_bytes`` — the per-device TRANSIENT buffer the step needs on
  top of the resident source/destination shards (send+recv buffers for
  collectives, the output buffer for local relayout copies).

``Schedule.peak_bytes`` (max over steps) is what the planner holds
under the ``HEAT_TPU_REDIST_BUDGET_MB`` budget by chunking collectives;
``Schedule.collective_counts()`` is the exact HLO collective census the
executor's compiled program must match — tier-1 pins that equality for
the golden specs (arXiv:2112.01075's "the schedule is checkable before
it runs").

Plans serialize canonically (``canonical_json``): byte-identical
run-to-run for the same spec + budget, since the ``plan_id`` derived
from that serialization keys the executor's program cache.

ISSUE 6 adds the **overlap annotation**: steps that belong to a
software-pipelined chunk group carry an ``overlap`` tag, and the
schedule carries a modeled critical-path account per group — at pipeline
depth 2 a stage pair costs ``max(wire, copy)`` instead of ``wire +
copy``, because chunk k's relayout copy runs while chunk k+1's
collective is on the wire (arXiv:2112.09017's latency-hiding schedules
applied to the chunk pipelines of arXiv:2112.01075). The annotation is
part of the canonical serialization (and therefore of ``plan_id``); the
executor consults it (plus the ``HEAT_TPU_REDIST_OVERLAP`` gate) to
decide whether to emit the prefetch-issue-then-consume program form.
Pipelining never changes WHAT moves — census and numerics are
bit-identical overlap-on vs overlap-off by construction.

ISSUE 7 adds the **wire-codec steps**: under ``HEAT_TPU_WIRE_QUANT``
the planner wraps admissible collective groups in ``quantize``/
``dequantize`` steps (``heat_tpu.kernels.quant`` — int8/bf16 payloads,
scale per (8,128) tile), scales the collectives' ``bytes_moved`` to
the encoded wire bytes, and attaches a schedule-level ``quant``
annotation ({mode, tol, bytes_raw, bytes_sent, ratio}). The codec
changes HOW MANY BYTES each collective carries, never how many
collectives launch: the census (and the lap/pipe structure) is
identical gate-on vs gate-off by construction, while the canonical
serialization — and therefore the ``plan_id`` and every program cache
key derived from it — distinguishes the quantized plan.

ISSUE 8 adds the **tier annotations**: at a two-tier topology
(``HEAT_TPU_TOPOLOGY``, ``core.communication.Topology``) every
collective step carries a ``tier`` — ``"ici"`` when its replica groups
stay within one slice, ``"dcn"`` when they span slices — and the
schedule carries a ``topology`` annotation ({n_slices,
chips_per_slice, dcn_penalty}; the per-tier byte split is derived from
the steps via :meth:`Schedule.tier_bytes`, so the codec pass can
re-scale ``bytes_moved`` without staling the annotation). The cost
model prices a DCN byte at ``dcn_penalty`` (= ICI/DCN bandwidth ≈ 8)
ICI bytes, ``describe()`` renders the per-tier byte/time split, and
both annotations fold into the canonical serialization and
``plan_id``.
CRITICALLY, both are *conditional* keys: a flat-topology plan
serializes without them, byte-identical to the pre-ISSUE-8 plans — the
``HEAT_TPU_TOPOLOGY`` unset/1xN escape hatch is exact by construction.
"""

from __future__ import annotations

import hashlib
import json

from typing import Any, Dict, List, Optional, Tuple

from .spec import RedistSpec

__all__ = ["Step", "Schedule", "COLLECTIVE_STEP_KINDS", "STAGING_STEP_KINDS"]

# step kind -> HLO collective op it must compile to (1:1). Every other
# kind is a local copy/view and must emit NO collective.
COLLECTIVE_STEP_KINDS: Dict[str, str] = {
    "all_to_all": "all-to-all",
    "all_gather": "all-gather",
    "ppermute": "collective-permute",
}

# ``pack``/``unpack`` are the lane-packing relayout copies
# (heat_tpu.kernels.relayout): pack folds narrow rows into the lane
# axis so the collective steps run on full-VREG buffers; unpack
# materializes the destination's narrow layout in ONE copy.
# ``quantize``/``dequantize`` are the wire-codec copies
# (heat_tpu.kernels.quant): quantize encodes the collective's
# per-destination blocks to the int8/bf16 wire format, dequantize
# restores full width on the receive side (riding the group's
# reassembly copy in the pipelined forms).
_LOCAL_STEP_KINDS = (
    "slice", "pad", "reshape", "concat", "pack", "unpack",
    "quantize", "dequantize",
)

# ``stage_in``/``stage_out`` (ISSUE 11) are the out-of-core staging
# transfers (``redistribution.staging``): one (8,128)-tile-aligned
# window of a host-resident operand device_put into / fetched out of
# the double-buffered HBM slab. They MOVE bytes — across the host<->HBM
# PCIe edge of the memory-tier lattice (``core.tiers``), carried as
# ``tier="pcie"`` — but launch NO mesh collective, so the HLO collective
# census is untouched by staging.
STAGING_STEP_KINDS = ("stage_in", "stage_out")


class Step:
    """One schedule step.

    Attributes
    ----------
    kind : ``all_to_all`` | ``all_gather`` | ``ppermute`` | ``slice`` |
        ``pad`` | ``reshape`` | ``concat`` | ``pack`` | ``unpack`` |
        ``quantize`` | ``dequantize`` | ``stage_in`` | ``stage_out``.
    bytes_moved : per-device payload crossing the mesh (collectives) or
        the host<->HBM PCIe edge (``stage_in``/``stage_out``; 0 for
        local steps).
    bytes_copied : per-device HBM bytes a LOCAL relayout copy writes
        (0 for views, collectives, and steps whose copy rides another
        step's accounting).
    peak_bytes : per-device transient buffer bytes of this step.
    lane_fill : fraction of VREG lanes the step's dominant buffer
        layout fills (``kernels.relayout.lane_fill`` of its minor dim);
        1/lane_fill is the HBM amplification the cost model charges.
    detail : short human-readable description of what the step does.
    chunk : chunk index when the step is one lap of a chunked pipeline.
    overlap : pipeline-group tag (e.g. ``"pipe0"``) when the step is one
        lap of a software-pipelined chunk group — chunk k's local work
        overlaps chunk k+1's collective inside the group; ``None`` for
        steps the executor issues sequentially.
    tier : ``"ici"`` / ``"dcn"`` at a two-tier topology (ISSUE 8):
        which wire a collective step's replica groups ride — ``"ici"``
        for intra-slice subgroups, ``"dcn"`` when the groups span
        slices; ``"pcie"`` on the staging steps (ISSUE 11), the
        host<->HBM edge of the memory-tier lattice. ``None`` for local
        steps and every flat-topology plan (the key is then omitted
        from the serialization, keeping flat plans byte-identical to
        the pre-topology era).
    """

    __slots__ = (
        "kind", "bytes_moved", "bytes_copied", "peak_bytes", "lane_fill",
        "detail", "chunk", "overlap", "tier",
    )

    def __init__(
        self,
        kind: str,
        bytes_moved: int = 0,
        peak_bytes: int = 0,
        detail: str = "",
        chunk: Optional[int] = None,
        bytes_copied: int = 0,
        lane_fill: float = 1.0,
        overlap: Optional[str] = None,
        tier: Optional[str] = None,
    ):
        if (
            kind not in COLLECTIVE_STEP_KINDS
            and kind not in _LOCAL_STEP_KINDS
            and kind not in STAGING_STEP_KINDS
        ):
            raise ValueError(f"unknown step kind {kind!r}")
        if tier not in (None, "ici", "dcn", "pcie"):
            raise ValueError(f"unknown tier {tier!r} (expected 'ici'/'dcn'/'pcie'/None)")
        if kind in STAGING_STEP_KINDS and tier != "pcie":
            raise ValueError(
                f"staging step {kind!r} must ride the pcie edge (got tier={tier!r})"
            )
        if tier == "pcie" and kind not in STAGING_STEP_KINDS:
            raise ValueError(f"tier 'pcie' is reserved for staging steps (got {kind!r})")
        self.kind = kind
        self.bytes_moved = int(bytes_moved)
        self.bytes_copied = int(bytes_copied)
        self.peak_bytes = int(peak_bytes)
        self.lane_fill = float(lane_fill)
        self.detail = detail
        self.chunk = chunk
        self.overlap = overlap
        self.tier = tier

    @property
    def is_collective(self) -> bool:
        return self.kind in COLLECTIVE_STEP_KINDS

    @property
    def effective_bytes(self) -> int:
        """Lane-amplified HBM traffic the cost model charges this step:
        (payload + local copy writes) / lane_fill."""
        return int((self.bytes_moved + self.bytes_copied) / max(self.lane_fill, 1e-9))

    def as_dict(self) -> Dict[str, Any]:
        d = {
            "kind": self.kind,
            "bytes_moved": self.bytes_moved,
            "bytes_copied": self.bytes_copied,
            "peak_bytes": self.peak_bytes,
            "lane_fill": self.lane_fill,
            "detail": self.detail,
            "chunk": self.chunk,
            "overlap": self.overlap,
        }
        # conditional: a flat-topology plan must serialize byte-identically
        # to the pre-ISSUE-8 era, so untier'd steps carry no key at all
        if self.tier is not None:
            d["tier"] = self.tier
        return d

    def __repr__(self) -> str:
        c = f"[{self.chunk}]" if self.chunk is not None else ""
        t = f", tier={self.tier}" if self.tier else ""
        return f"Step({self.kind}{c}, moved={self.bytes_moved}, peak={self.peak_bytes}{t})"


class Schedule:
    """An ordered redistribution plan for one :class:`RedistSpec`.

    ``overlap`` (optional) is the software-pipelining annotation the
    planner attaches when the plan's chunk groups can hide local copy
    work under collective wire time::

        {
          "depth": 2,                      # pipeline depth (double-buffer)
          "groups": [{"tag": "pipe0", "laps": C,
                      "wire_bytes": ..., "copy_bytes": ...,
                      "sequential_bytes": wire + copy,
                      "critical_path_bytes": w + (C-1)*max(w, c) + c}, ...],
          "sequential_bytes":   sum of group sequential models,
          "critical_path_bytes": sum of group critical paths,
          "model_speedup":      sequential / critical-path,
        }

    The annotation is cost MODEL, not movement: an overlapped program
    launches exactly the same collectives in the same order, so census
    and numerics are identical to the sequential form.
    """

    def __init__(
        self,
        spec: RedistSpec,
        strategy: str,
        steps: List[Step],
        budget_bytes: int,
        notes: str = "",
        overlap: Optional[Dict[str, Any]] = None,
        quant: Optional[Dict[str, Any]] = None,
        topology: Optional[Dict[str, Any]] = None,
        staging: Optional[Dict[str, Any]] = None,
    ):
        self.spec = spec
        self.strategy = strategy
        self.steps: List[Step] = list(steps)
        self.budget_bytes = int(budget_bytes)
        self.notes = notes
        self.overlap = overlap
        self.quant = quant
        self.topology = topology
        # ISSUE 11: the out-of-core staging annotation
        # (redistribution.staging) — {depth, axis, window_bytes,
        # n_windows, slab_bytes, resident_bytes, host_bytes, grain}.
        # Conditional like quant/topology: non-staged plans serialize
        # without the key, byte-identical to the pre-staging era.
        self.staging = staging
        self.plan_id = hashlib.sha1(
            self.canonical_json(with_plan_id=False).encode()
        ).hexdigest()[:12]

    # ------------------------------------------------------------------ #
    # accounting                                                         #
    # ------------------------------------------------------------------ #
    @property
    def peak_bytes(self) -> int:
        """Max per-device transient footprint over all steps."""
        return max((s.peak_bytes for s in self.steps), default=0)

    @property
    def bytes_moved(self) -> int:
        """Total per-device payload shipped across the mesh."""
        return sum(s.bytes_moved for s in self.steps)

    @property
    def bytes_copied(self) -> int:
        """Total per-device local relayout copy writes."""
        return sum(s.bytes_copied for s in self.steps)

    @property
    def effective_bytes(self) -> int:
        """Lane-amplified HBM traffic of the whole plan — the volume
        term of the planner's cost model."""
        return sum(s.effective_bytes for s in self.steps)

    @property
    def n_steps(self) -> int:
        return len(self.steps)

    @property
    def n_collectives(self) -> int:
        return sum(1 for s in self.steps if s.is_collective)

    @property
    def within_budget(self) -> bool:
        return self.peak_bytes <= self.budget_bytes

    @property
    def wire_bytes_sent(self) -> int:
        """Per-device bytes that actually cross the mesh: the current
        steps' payload sum — the encoded wire bytes when the plan
        carries a ``quant`` annotation, else :attr:`bytes_moved`."""
        return self.bytes_moved

    @property
    def wire_bytes_raw(self) -> int:
        """Per-device full-width payload the same movement would ship
        without the wire codec (== :attr:`wire_bytes_sent` for
        unquantized plans)."""
        return int(self.quant["bytes_raw"]) if self.quant else self.bytes_moved

    @property
    def overlap_depth(self) -> int:
        """Pipeline depth the executor runs the chunk groups at: 2
        (double-buffered) when the plan carries an overlap annotation,
        1 (sequential) otherwise."""
        return int(self.overlap["depth"]) if self.overlap else 1

    @property
    def critical_path_bytes(self) -> int:
        """Modeled byte-equivalent time of the plan's movement under
        depth-2 pipelining: the non-pipelined steps at face value plus
        each overlap group's ``max(wire, copy)``-per-stage-pair critical
        path (equals :attr:`sequential_model_bytes` when nothing
        pipelines)."""
        base = self.sequential_model_bytes
        if not self.overlap:
            return base
        return base - int(self.overlap["sequential_bytes"]) + int(
            self.overlap["critical_path_bytes"]
        )

    @property
    def sequential_model_bytes(self) -> int:
        """Modeled byte-equivalent time with every stage serialized —
        the lane-amplified traffic (:attr:`effective_bytes`) plus the
        overlap groups' reassembly-copy terms the per-step accounting
        folds into the group model rather than ``bytes_copied``."""
        extra = 0
        if self.overlap:
            group_wire = sum(int(g["wire_bytes"]) for g in self.overlap["groups"])
            extra = int(self.overlap["sequential_bytes"]) - group_wire
        return self.effective_bytes + extra

    @property
    def topo_key(self) -> Optional[Tuple[int, int]]:
        """``(n_slices, chips_per_slice)`` of a tiered plan, ``None``
        for flat — the hashable form the executor's program cache keys
        carry."""
        if not self.topology:
            return None
        return (int(self.topology["n_slices"]), int(self.topology["chips_per_slice"]))

    # ------------------------------------------------------------------ #
    # liveness (ISSUE 10): the per-step live-byte account memcheck and   #
    # the plan verifier reason over                                      #
    # ------------------------------------------------------------------ #
    @property
    def resident_bytes(self) -> int:
        """Per-device bytes RESIDENT for the whole redistribution: the
        source shard being consumed plus the destination shard being
        built. ``peak_bytes`` deliberately excludes them (it budgets the
        chunkable transients); the liveness view adds them back so the
        number is comparable with a whole-program peak-HBM estimate
        (``ht.analysis.memcheck``).

        STAGED plans (ISSUE 11) override this with the annotation's
        ``resident_bytes``: the operand itself lives on the HOST tier,
        so only the outputs held across the window loop are
        HBM-resident — the slab transients ride ``peak_bytes`` like any
        other transient, and ``liveness_peak_bytes`` is exactly the
        number the staging executor proves under
        ``tiers.capacity("hbm")`` before running."""
        if self.staging is not None:
            return int(self.staging["resident_bytes"])
        return int(self.spec.src_shard_bytes) + int(self.spec.dst_shard_bytes)

    def liveness(self) -> List[Dict[str, int]]:
        """Per-step live-byte account: ``{"kind", "transient_bytes",
        "live_bytes"}`` per step, where ``live_bytes`` = resident source
        + destination shards + this step's transient. The recomputed
        ``max(transient_bytes)`` must equal :attr:`peak_bytes` — one of
        the invariants ``ht.analysis.verify_plan`` proves."""
        resident = self.resident_bytes
        return [
            {
                "kind": s.kind,
                "transient_bytes": int(s.peak_bytes),
                "live_bytes": resident + int(s.peak_bytes),
            }
            for s in self.steps
        ]

    @property
    def liveness_peak_bytes(self) -> int:
        """Max ``live_bytes`` over the steps (``resident_bytes`` for an
        empty plan) — the schedule-level analog of memcheck's static
        peak estimate."""
        return self.resident_bytes + self.peak_bytes

    def tier_bytes(self) -> Dict[str, int]:
        """Per-tier payload split: ``{"ici": B, "dcn": B}`` over the
        collectives (flat plans — every pre-topology schedule — report
        all movement as ``"ici"``: one ICI domain is tier 0 by
        definition), plus a ``"pcie"`` entry when the plan stages
        windows across the host edge (ISSUE 11; the key is present only
        on staged plans, so established ``{"ici", "dcn"}`` consumers
        are unchanged)."""
        out = {"ici": 0, "dcn": 0}
        for s in self.steps:
            if s.is_collective:
                out[s.tier or "ici"] += s.bytes_moved
            elif s.kind in STAGING_STEP_KINDS:
                out["pcie"] = out.get("pcie", 0) + s.bytes_moved
        return out

    # ------------------------------------------------------------------ #
    # congruence hooks (ISSUE 14): the per-step group structure the     #
    # progress replay (ht.analysis.check_progress / verify_plan's       #
    # ``progress`` invariant) reasons over. Properties/methods only —   #
    # like the liveness hooks, they never touch the canonical           #
    # serialization, so plan bytes and plan_ids are unchanged.          #
    # ------------------------------------------------------------------ #
    def collective_group_structure(self) -> List[Dict[str, Any]]:
        """Per-collective-step symbolic group structure: ``{"kind",
        "tier", "chunk", "n_groups", "group_size"}`` — the subgroup
        shape each collective's participants must agree on. Flat plans
        ride ONE group of ``mesh_size``; at a hierarchical topology the
        ``ici`` halves ride ``n_slices`` groups of ``chips_per_slice``
        and the ``dcn`` halves ``chips_per_slice`` groups of
        ``n_slices`` — both partitions of the mesh by construction
        (``S·C == p``), which is exactly what the progress replay
        re-proves on dumped plans (and what the MPMD stage-graph
        verifier will consume per stage)."""
        p = int(self.spec.mesh_size)
        S = C = None
        if self.topology:
            S = int(self.topology["n_slices"])
            C = int(self.topology["chips_per_slice"])
        out: List[Dict[str, Any]] = []
        for s in self.steps:
            if not s.is_collective:
                continue
            if s.tier == "ici" and S is not None:
                n_groups, group_size = S, C
            elif s.tier == "dcn" and S is not None and self.strategy == "hierarchical-a2a":
                n_groups, group_size = C, S
            else:
                n_groups, group_size = 1, p
            out.append(
                {
                    "kind": s.kind,
                    "tier": s.tier,
                    "chunk": s.chunk,
                    "n_groups": n_groups,
                    "group_size": group_size,
                }
            )
        return out

    def overlap_lap_chunks(self, tag: str) -> List[Optional[int]]:
        """The chunk indices of one overlap group's collective laps, in
        issue order (a hierarchical lap's ici/dcn pair contributes one
        entry). The depth-2 double buffer consumes lap k-1 at issue of
        lap k, so a well-formed group reads ``[0, 1, ..., laps-1]`` (or
        all ``None`` for the ring's positional hops) — the invariant
        the progress replay checks on every golden dump."""
        lap_mult = 2 if self.strategy == "hierarchical-a2a" else 1
        tagged = [s for s in self.steps if s.is_collective and s.overlap == tag]
        return [
            tagged[i * lap_mult].chunk for i in range(len(tagged) // lap_mult)
        ]

    # ------------------------------------------------------------------ #
    # tolerance hooks (ISSUE 17): the per-step error bounds the         #
    # ``tolerance`` invariant (ht.analysis.check_tolerance /            #
    # verify_plan) composes end-to-end. Properties/methods only — like  #
    # the congruence hooks above, they never touch the canonical        #
    # serialization, so plan bytes and plan_ids are unchanged.          #
    # ------------------------------------------------------------------ #
    @property
    def quant_tolerance(self) -> float:
        """The schedule-level declared error bound: the wire codec's
        pinned tolerance when the plan carries a quant annotation
        (``2^-7`` int8, ``2^-8`` bf16 — kernels/quant.py), 0.0 for an
        unquantized plan (every step exact-bit)."""
        return float(self.quant["tol"]) if self.quant else 0.0

    def step_tolerances(self) -> List[float]:
        """Per-step relative error contribution, step-aligned with
        ``self.steps``: ``tolerance(mode)`` on each quantize step (the
        lossy rounding happens at encode; the wire and the dequantize
        are exact given the encoded blocks), 0.0 everywhere else —
        collectives move bits verbatim, staging/relayout/overlap steps
        are exact-bit copies. ``compose_tolerance`` over the steps one
        payload element traverses recovers the end-to-end bound the
        ``tolerance`` invariant proves equal to ``quant_tolerance``."""
        mode = self.quant.get("mode") if self.quant else None
        if mode is None:
            return [0.0] * len(self.steps)
        from ..kernels import quant as _quant

        tol = _quant.tolerance(mode)
        return [
            tol if s.kind == "quantize" else 0.0 for s in self.steps
        ]

    def collective_counts(self) -> Dict[str, int]:
        """{HLO op name: count} the executed program must launch —
        directly comparable with
        ``ht.observability.collective_counts(...).counts``."""
        out: Dict[str, int] = {}
        for s in self.steps:
            if s.is_collective:
                op = COLLECTIVE_STEP_KINDS[s.kind]
                out[op] = out.get(op, 0) + 1
        return out

    # ------------------------------------------------------------------ #
    # serialization                                                      #
    # ------------------------------------------------------------------ #
    def as_dict(self, with_plan_id: bool = True) -> Dict[str, Any]:
        d: Dict[str, Any] = {
            "spec": self.spec.as_dict(),
            "strategy": self.strategy,
            "budget_bytes": self.budget_bytes,
            "steps": [s.as_dict() for s in self.steps],
            "peak_bytes": self.peak_bytes,
            "bytes_moved": self.bytes_moved,
            "bytes_copied": self.bytes_copied,
            "collective_counts": self.collective_counts(),
            "within_budget": self.within_budget,
            "notes": self.notes,
            "overlap": self.overlap,
            "quant": self.quant,
        }
        # conditional (ISSUE 8): flat plans serialize without the key so
        # their bytes — and plan_ids — match the pre-topology era exactly
        if self.topology is not None:
            d["topology"] = self.topology
        # conditional (ISSUE 11): same contract for the staging
        # annotation — non-staged plans stay byte-identical
        if self.staging is not None:
            d["staging"] = self.staging
        if with_plan_id:
            d["plan_id"] = self.plan_id
        return d

    def canonical_json(self, with_plan_id: bool = True) -> str:
        """Deterministic serialization — byte-identical run-to-run for
        the same (spec, budget); ci.sh diffs two runs of the golden
        matrix against each other."""
        return json.dumps(
            self.as_dict(with_plan_id=with_plan_id),
            sort_keys=True,
            separators=(",", ":"),
        )

    def describe(self) -> str:
        """Human-readable rendering of the plan: one line per step with
        its movement/copy accounting and pipeline tag, plus the overlap
        annotation's modeled critical-path arithmetic — what
        ``ht.redistribution.explain(...)`` shows when printed."""
        groups = {g["tag"]: g for g in (self.overlap or {}).get("groups", [])}
        lines = [
            f"plan {self.plan_id}  strategy={self.strategy}  "
            f"depth={self.overlap_depth}  {self.spec!r}"
        ]
        for k, s in enumerate(self.steps):
            chunk = f"[{s.chunk}]" if s.chunk is not None else ""
            pipe = f"  pipe={s.overlap}" if s.overlap else ""
            tier = f"  tier={s.tier}" if s.tier else ""
            g = groups.get(s.overlap)
            if g and s.is_collective and "ici_bytes" in g:
                # tiered group (ISSUE 8): a pipelined lap is priced at
                # max(ici wire, penalty-scaled dcn wire, copy)
                wi = g["ici_bytes"] // g["laps"]
                wd = g["dcn_bytes"] * g["dcn_penalty"] // g["laps"]
                c = g["copy_bytes"] // g["laps"]
                model = (
                    f"  model=max(ici {wi}, dcn {wd}, copy {c})={max(wi, wd, c)} B-eq"
                )
            elif g and s.is_collective:
                # per-step modeled time under depth-2 pipelining: this
                # lap's wire overlaps the previous lap's reassembly copy
                w = g["wire_bytes"] // g["laps"]
                c = g["copy_bytes"] // g["laps"]
                model = f"  model=max(wire {w}, copy {c})={max(w, c)} B"
            else:
                model = f"  model={s.effective_bytes} B"
            lines.append(
                f"  [{k:2d}] {s.kind}{chunk}  moved={s.bytes_moved}  "
                f"copied={s.bytes_copied}  peak={s.peak_bytes}{tier}{pipe}{model}"
                + (f"  -- {s.detail}" if s.detail else "")
            )
        if self.overlap:
            o = self.overlap
            lines.append(
                f"  overlap: depth={o['depth']} groups={len(o['groups'])} "
                f"critical_path={o['critical_path_bytes']} B vs "
                f"sequential={o['sequential_bytes']} B "
                f"(model_speedup={o['model_speedup']}x)"
            )
        else:
            lines.append("  overlap: none (sequential schedule)")
        if self.quant:
            q = self.quant
            lines.append(
                f"  quant: {q['mode']} wire codec  "
                f"raw={q['bytes_raw']} B -> sent={q['bytes_sent']} B "
                f"(saved {q['bytes_raw'] - q['bytes_sent']} B, "
                f"ratio {q['ratio']}, tol {q['tol']})"
            )
        else:
            lines.append("  quant: none (full-width wire)")
        if self.topology:
            t = self.topology
            tb = self.tier_bytes()
            lines.append(
                f"  topology: {t['n_slices']}x{t['chips_per_slice']} two-tier  "
                f"ici={tb['ici']} B  dcn={tb['dcn']} B "
                f"(dcn priced {t['dcn_penalty']}x — "
                f"time-eq {tb['ici'] + tb['dcn'] * t['dcn_penalty']} B)"
            )
        if self.staging:
            sg = self.staging
            passes = ", ".join(
                f"{p['tag']}(axis {p['axis']}: {p['n_windows']}w"
                + ("+wb" if p.get("writeback") else "")
                + ")"
                for p in sg["passes"]
            )
            model = sg["model"]
            lines.append(
                f"  staging: depth={sg['depth']} [{passes}]  "
                f"{sg['n_windows']} window(s) x <= {sg['window_bytes']} B "
                f"over pcie  slab={sg['slab_bytes']} B  "
                f"hbm-resident={sg['resident_bytes']} B  "
                f"host-resident={sg['host_bytes']} B  "
                f"model: pcie {model['pcie_s']}s / critical path "
                f"{model['critical_path_s']}s ({model['bound_gbps']} GB/s)"
            )
        if self.notes:
            lines.append(f"  notes: {self.notes}")
        return "\n".join(lines)

    def __repr__(self) -> str:
        kinds = [
            s.kind + (f"[{s.chunk}]" if s.chunk is not None else "") for s in self.steps
        ]
        ov = f", overlap=depth{self.overlap_depth}" if self.overlap else ""
        qt = f", quant={self.quant['mode']}" if self.quant else ""
        tp = (
            f", topo={self.topology['n_slices']}x{self.topology['chips_per_slice']}"
            if self.topology
            else ""
        )
        return (
            f"Schedule({self.strategy}, plan={self.plan_id}, {self.spec!r}, "
            f"steps={kinds}, peak={self.peak_bytes}B/{self.budget_bytes}B{ov}{qt}{tp})"
        )
