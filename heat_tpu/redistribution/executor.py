"""Schedule execution — lowering plans to jitted ``shard_map`` programs.

The planner's :class:`~heat_tpu.redistribution.schedule.Schedule` is the
contract; this module compiles it to exactly the collectives it lists
(tier-1 pins ``ht.observability.collective_counts`` == the plan's census
for the golden specs). One program per ``(comm, spec, budget)``, cached
and registered with ``communication.register_mesh_cache`` so world
rebuilds drop programs baked onto a defunct mesh.

Every program body runs under ``jax.named_scope("redist_plan_<id>")``:
the plan id lands in the HLO ``op_name`` metadata of every collective
the program launches, which is how shardlint (``analysis/ircheck``)
recognizes planner-issued reshards and reports them at info severity
with the plan attached instead of flagging the subsystem's own programs
(see ``analysis/boundaries.PLANNER_MODULES``).

Padding discipline (see ``core/_padding``): programs take the physical
(src-split-padded) array and return the physical dst-split-padded array;
pads along the exchanged axes are added/dropped with LOCAL copies inside
the same program, so the zero-pad invariant holds on the way out.

Software pipelining (ISSUE 6): the chunk/hop loops come in two issue
orders — the sequential oracle (lap k's collective, then lap k's
relayout copy: exactly the PR 5 program form, which is what the
``HEAT_TPU_REDIST_OVERLAP=0`` escape hatch restores) and the depth-2
pipelined form (prefetch-issue lap k+1's collective, THEN consume lap
k), selected per-execution by the plan's overlap annotation under the
gate (``_overlap_active``) and baked into the program cache key. Both
forms launch identical collectives and write identical (disjoint)
regions: census and numerics are bit-identical, pinned by
``tests/test_overlap.py``.

Wire quantization (ISSUE 7): when the plan carries ``quantize``/
``dequantize`` codec steps (``HEAT_TPU_WIRE_QUANT``), the same
chunk/hop loops ship encoded int8/bf16 payloads
(``heat_tpu.kernels.quant``): ``issue`` encodes the lap's
per-destination blocks and launches the SAME collective on the wire
buffer, ``consume`` decodes and scatters — so in the pipelined form
the dequantize copy rides under the next chunk's wire exactly like the
reassembly copy it replaces. The codec choice is part of every program
cache key (a gate flip rebuilds, never reuses), the census is
unchanged by construction, and with no codec the code paths are
byte-for-byte the PR 6 forms (the ``=0`` escape hatch is exact-bit).

Two-tier topology (ISSUE 8): a ``hierarchical-a2a`` plan's chunk laps
run the decomposed exchange — an intra-slice all-to-all over the
topology's chip subgroups (``axis_index_groups``; the cheap tier
carries the volume), then an inter-slice all-to-all over the slice
subgroups shipping only the pre-packed per-slice rows that must cross
DCN. The received blocks are placed EXACTLY where the flat all-to-all
would place them, so the output is bit-identical to the flat program
for any input; the codec (when the plan carries codec steps) engages
on the inter-slice hop only, the plan's first-target group. The
topology is part of every program cache key, and with a flat plan
(``HEAT_TPU_TOPOLOGY`` unset/1xN) the code paths are byte-for-byte
the PR 7 forms.
"""

from __future__ import annotations

import contextlib
import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from typing import Optional, Tuple

from jax import shard_map
from ..observability import telemetry as _telemetry
from ..observability import tracing as _tracing
from . import planner as _planner
from .schedule import Schedule
from .spec import RedistSpec

__all__ = ["execute", "resplit_phys", "reshape_phys", "clear_program_cache"]


def _pad_extent(n: int, p: int) -> int:
    from ..core import _padding

    return _padding.pad_extent(int(n), int(p))


def _plan_scope(plan_id: str):
    """The ``redist_plan_<id>`` named scope every program body runs
    under — IFF this module is registered in
    ``analysis/boundaries.PLANNER_MODULES``. The registration is the
    live switch: deregistering the executor stops the stamping, and
    shardlint's SL101/SL102 findings on its collectives revert from
    info+plan_id back to warning/error severity."""
    from ..analysis import boundaries as _boundaries

    if "redistribution/executor.py" in _boundaries.PLANNER_MODULES:
        return jax.named_scope(f"redist_plan_{plan_id}")
    return contextlib.nullcontext()


def _axis_spec(axis_name: str, ndim: int, split: Optional[int]) -> P:
    if split is None:
        return P(*(None,) * ndim)
    return P(*(axis_name if k == split else None for k in range(ndim)))


def _a2a_chunks(sched: Schedule) -> Tuple[int, int]:
    """(before, after) all_to_all LAP counts around the plan's
    ``reshape`` step — the chunk counts of the pivot's two collective
    groups, both structural (a move plan has no reshape step: everything
    lands in ``before``). The executor re-derives C from the schedule
    itself so program and plan cannot disagree, and from step KINDS, not
    the human-readable detail text. A hierarchical lap (ISSUE 8) emits
    an ici + dcn all_to_all PAIR: counting the non-``"ici"`` steps
    counts each lap once for flat (tier None / ``"dcn"``) and
    hierarchical plans alike."""
    before = after = 0
    seen_reshape = False
    for st in sched.steps:
        if st.kind == "reshape":
            seen_reshape = True
        elif st.kind == "all_to_all" and st.tier != "ici":
            if seen_reshape:
                after += 1
            else:
                before += 1
    return before, after


def _run_laps(indices, issue, consume, state, pipelined: bool, span_attrs=None):
    """The depth-2 double-buffer skeleton every chunk/hop loop shares.
    ``issue(k)`` launches lap k's collective (laps are independent —
    each slices from the source), ``consume(state, result, k)`` folds
    lap k's received buffer into the output. Sequential: issue lap k,
    consume lap k — exactly the PR 5 program form the
    ``HEAT_TPU_REDIST_OVERLAP=0`` escape hatch restores. Pipelined:
    prefetch-issue lap k+1 BEFORE consuming lap k, so the reassembly
    copy runs while the next collective is on the wire. Same
    collectives, disjoint writes: bit-identical either way.
    (``kernels.cmatmul.ring_all_gather`` keeps its own loop — its hops
    are CHAINED through the travelling block, a different dependence
    structure.)

    Under ``HEAT_TPU_TRACE`` the (issue, consume) pair is wrapped with
    one span per lap call (``span_attrs``: step kind + tier from the
    call site; plan_id rides the executor's ambient tracing context).
    The wrappers decorate the CALLABLES, never the loop: the issue
    order, the traced computation, and the compiled program bytes are
    identical with the gate on or off."""
    if _tracing._ENABLED:
        issue, consume = _tracing.lap_probes(issue, consume, span_attrs)
    idx = list(indices)
    if not pipelined or len(idx) < 2:
        for k in idx:
            state = consume(state, issue(k), k)
        return state
    prev = issue(idx[0])
    for i in range(1, len(idx)):
        nxt = issue(idx[i])  # lap i on the wire ...
        state = consume(state, prev, idx[i - 1])  # ... while i-1 relayouts
        prev = nxt
    return consume(state, prev, idx[-1])


def _quant_flags(sched: Schedule) -> Tuple[Optional[str], bool, bool]:
    """(mode, quant_in, quant_out): which collective groups of the plan
    run on encoded wire payloads, re-derived from step KINDS around the
    plan's ``reshape`` step (the executor/plan-cannot-disagree rule the
    chunk counts and packed flags already follow). A move/ring plan has
    no reshape step: its codec steps all land in ``quant_in``."""
    mode = sched.quant["mode"] if sched.quant else None
    seen_reshape = False
    qin = qout = False
    for st in sched.steps:
        if st.kind == "reshape":
            seen_reshape = True
        elif st.kind == "quantize":
            if seen_reshape:
                qout = True
            else:
                qin = True
    return mode, qin, qout


def _wire_a2a_blocks(chunk, axis_name: str, p: int, s_ax: int, codec: str):
    """The codec form of one tiled all-to-all lap: split ``chunk`` into
    its p per-destination blocks along ``s_ax``, encode each block as
    one wire row, and launch the SAME single all-to-all on the int8
    buffer. Returns the raw received wire rows — the caller decodes in
    ``consume`` so the full-width write rides under the next lap's
    collective in the pipelined form."""
    from ..kernels import quant as _quant

    m = jnp.moveaxis(chunk, s_ax, 0)
    blocks = m.reshape(p, -1)
    wire = _quant.encode_blocks(blocks, codec)
    return lax.all_to_all(wire, axis_name, 0, 0, tiled=True)


def _hier_groups(topo: Tuple[int, int]) -> Tuple[list, list]:
    """(chip_groups, slice_groups) ``axis_index_groups`` of a slice-major
    two-tier mesh — delegated to ``core.communication.Topology`` so the
    executor's subgroup structure can never drift from the planner's
    tier classification."""
    from ..core.communication import Topology

    t = Topology(*topo)
    return t.chip_axis_groups(), t.slice_axis_groups()


def _chunked_all_to_all(
    x, axis_name: str, p: int, split_axis: int, concat_axis: int, C: int,
    pipelined: bool = False, codec: Optional[str] = None,
    topo: Optional[Tuple[int, int]] = None,
):
    """Tiled all-to-all in C equal chunks along the concat axis, chunk
    results scattered (in place) into the destination-layout buffer.
    C == 1 is the direct single-collective form.

    ``pipelined`` switches the lap loop between the two issue orders of
    the SAME collectives (bit-identical output — the scatters write
    disjoint regions):

    - sequential (the oracle/floor, ``HEAT_TPU_REDIST_OVERLAP=0``):
      issue lap c, scatter lap c — EXACTLY the PR 5 program form, so the
      escape hatch restores the previously shipped schedule (no added
      barriers; XLA keeps whatever freedom it already had);
    - pipelined (depth 2): prefetch-issue lap c+1's all-to-all, THEN
      scatter lap c — the received chunk's relayout copy runs while the
      next chunk is on the wire (the ``nn/attention.py`` ring trick
      applied to the chunk pipeline; XLA's async collective pair
      brackets the independent copy work).

    ``codec`` (ISSUE 7) switches every lap onto the encoded wire:
    ``issue`` packs the lap's p destination blocks through
    ``kernels.quant.encode_blocks`` and launches ONE all-to-all on the
    int8 buffer (census unchanged); ``consume`` decodes and scatters,
    so the full-width dequantize write sits in the consume slot and
    rides under the next lap's wire when pipelined. ``codec=None`` is
    byte-for-byte the PR 6 program form.

    ``topo=(S, C)`` (ISSUE 8) runs each lap HIERARCHICALLY: an
    intra-slice all-to-all over the chip subgroups redistributes by
    destination chip (ICI carries the volume), then an inter-slice
    all-to-all over the slice subgroups ships the pre-packed per-slice
    rows (minimum DCN bytes; the codec — when given — encodes exactly
    this hop). The received per-source blocks are placed where the flat
    all-to-all would place them: bit-identical output by construction."""
    if topo is None and codec is None:
        if C <= 1:
            return lax.all_to_all(x, axis_name, split_axis, concat_axis, tiled=True)
    from ..kernels import quant as _quant  # noqa: F401 (codec path only)

    x2 = jnp.moveaxis(x, concat_axis, 0)
    s_ax = split_axis + 1 if split_axis < concat_axis else split_axis
    Bc = x2.shape[0]
    C = max(C, 1)
    step = Bc // C
    out_shape = (Bc * p,) + tuple(
        d // p if k + 1 == s_ax else d for k, d in enumerate(x2.shape[1:])
    )

    if topo is not None:
        S_t, C_t = topo
        g_chip, g_slice = _hier_groups(topo)
        chunk_shape = (step,) + tuple(x2.shape[1:])
        B = chunk_shape[s_ax] // p
        vshape = chunk_shape[:s_ax] + (S_t, C_t, B) + chunk_shape[s_ax + 1 :]
        # the phase-2 buffer with the S axis moved to front (the wire rows)
        rest = vshape[:s_ax] + vshape[s_ax + 1 :]
        n_loc = 1
        for d in rest:
            n_loc *= d

        def _phase1(chunk):
            # destination-flat order (s'·C_t + c') factored as (S, C, B);
            # phase 1 (ICI): within each slice, destination-chip block c'
            # goes to chip c'; index c on that axis becomes SOURCE chip
            return lax.all_to_all(
                chunk.reshape(vshape), axis_name, s_ax + 1, s_ax + 1,
                tiled=True, axis_index_groups=g_chip,
            )

        def _place(out, r, c):
            # r: (..., p*B at s_ax, ...) in (s_src, c_src)-major order ==
            # the flat source-device order; place each source block where
            # the flat all-to-all's scatter puts it
            for q in range(p):
                piece = lax.slice_in_dim(r, q * B, (q + 1) * B, axis=s_ax)
                out = lax.dynamic_update_slice_in_dim(
                    out, piece, q * Bc + c * step, axis=0
                )
            return out

        if codec is None:

            def issue(c):
                chunk = lax.slice_in_dim(x2, c * step, (c + 1) * step, axis=0)
                v = _phase1(chunk)
                # phase 2 (DCN): same-chip peers across slices exchange
                # the destination-slice rows — already packed per slice,
                # so only the genuinely crossing bytes travel
                v = lax.all_to_all(
                    v, axis_name, s_ax, s_ax, tiled=True,
                    axis_index_groups=g_slice,
                )
                return v.reshape(
                    chunk_shape[:s_ax] + (p * B,) + chunk_shape[s_ax + 1 :]
                )

            consume = _place

        else:

            def issue(c):
                chunk = lax.slice_in_dim(x2, c * step, (c + 1) * step, axis=0)
                m = jnp.moveaxis(_phase1(chunk), s_ax, 0)
                wire = _quant.encode_blocks(m.reshape(S_t, n_loc), codec)
                # the encoded DCN hop; the decode sits in consume so the
                # full-width dequantize write rides under the next lap's
                # wire at depth 2, exactly like the flat codec form
                return lax.all_to_all(
                    wire, axis_name, 0, 0, tiled=True, axis_index_groups=g_slice
                )

            def consume(out, w, c):
                dec = _quant.decode_blocks(w, n_loc, codec).astype(x.dtype)
                v = jnp.moveaxis(dec.reshape((S_t,) + rest), 0, s_ax)
                r = v.reshape(
                    chunk_shape[:s_ax] + (p * B,) + chunk_shape[s_ax + 1 :]
                )
                return _place(out, r, c)

    elif codec is None:

        def issue(c):
            chunk = lax.slice_in_dim(x2, c * step, (c + 1) * step, axis=0)
            return lax.all_to_all(chunk, axis_name, s_ax, 0, tiled=True)  # (p*step, ...)

        def consume(out, r, c):
            for s in range(p):
                piece = lax.slice_in_dim(r, s * step, (s + 1) * step, axis=0)
                out = lax.dynamic_update_slice_in_dim(
                    out, piece, s * Bc + c * step, axis=0
                )
            return out

    else:
        S = x2.shape[s_ax]
        rest = tuple(x2.shape[1:s_ax]) + tuple(x2.shape[s_ax + 1 :])
        part_m_shape = (S // p, step) + rest
        n_loc = (S // p) * step
        for d in rest:
            n_loc *= d

        def issue(c):
            chunk = lax.slice_in_dim(x2, c * step, (c + 1) * step, axis=0)
            return _wire_a2a_blocks(chunk, axis_name, p, s_ax, codec)

        def consume(out, w, c):
            dec = _quant.decode_blocks(w, n_loc, codec).astype(x.dtype)
            for q in range(p):
                part = jnp.moveaxis(dec[q].reshape(part_m_shape), 0, s_ax)
                out = lax.dynamic_update_slice_in_dim(
                    out, part, q * Bc + c * step, axis=0
                )
            return out

    out = _run_laps(
        range(C), issue, consume, jnp.zeros(out_shape, x.dtype), pipelined,
        {"step": "all_to_all", "tier": "ici+dcn" if topo is not None else "ici"},
    )
    return jnp.moveaxis(out, 0, concat_axis)


def _packed_flags(sched: Schedule) -> Tuple[bool, bool]:
    """(packed_in, packed_out) — which pivot stages the plan runs on
    lane-packed buffers, re-derived from step KINDS around the plan's
    ``reshape`` step so program and plan cannot disagree."""
    seen_reshape = False
    packed_in = packed_out = False
    for st in sched.steps:
        if st.kind == "reshape":
            seen_reshape = True
        elif st.kind == "unpack" and not seen_reshape:
            packed_in = True
        elif st.kind == "pack" and seen_reshape:
            packed_out = True
    return packed_in, packed_out


def _chunked_a2a_flat(
    x, axis_name: str, p: int, C: int, pipelined: bool = False,
    codec: Optional[str] = None, topo: Optional[Tuple[int, int]] = None,
):
    """Tiled all-to-all of a ``(p, M)`` column-grouped FLAT buffer
    (``kernels.relayout.pack_rows`` layout): row d is the block bound
    for device d; the result's row q is the block received from device
    q. Both faces are lane-full wide buffers — the packed pivot's
    collective form. ``C > 1`` chunks equal column laps (C | M);
    ``pipelined`` prefetch-issues lap c+1 before placing lap c (same
    issue-order contract as :func:`_chunked_all_to_all`). ``codec``
    ships each lap's rows encoded (the buffer is already
    destination-major, so the wire rows ARE its rows); the decode sits
    in the consume slot. ``topo`` runs each lap hierarchically (ISSUE
    8): the row axis factors as (S, C) destination blocks — intra-slice
    exchange on the chip factor, inter-slice on the slice factor
    (codec-encoded when given) — and the received rows land in the same
    source-major order as the flat form: bit-identical."""
    if topo is None and codec is None:
        if C <= 1:
            return lax.all_to_all(x, axis_name, 0, 0, tiled=True)
    from ..kernels import quant as _quant  # noqa: F401 (codec path only)

    M = x.shape[1]
    C = max(C, 1)
    step = M // C

    if topo is not None:
        S_t, C_t = topo
        g_chip, g_slice = _hier_groups(topo)

        def _phase1(chunk):
            # rows (p, step) factored (S, C, step); intra-slice a2a on
            # the destination-chip factor
            return lax.all_to_all(
                chunk.reshape(S_t, C_t, step), axis_name, 1, 1, tiled=True,
                axis_index_groups=g_chip,
            )

        if codec is None:

            def issue(c):
                chunk = lax.slice_in_dim(x, c * step, (c + 1) * step, axis=1)
                v = lax.all_to_all(
                    _phase1(chunk), axis_name, 0, 0, tiled=True,
                    axis_index_groups=g_slice,
                )
                return v.reshape(p, step)

            def consume(out, r, c):
                return lax.dynamic_update_slice_in_dim(out, r, c * step, axis=1)

        else:

            def issue(c):
                chunk = lax.slice_in_dim(x, c * step, (c + 1) * step, axis=1)
                wire = _quant.encode_blocks(
                    _phase1(chunk).reshape(S_t, C_t * step), codec
                )
                # encoded DCN hop; decode sits in consume so the
                # full-width write rides under the next lap's wire
                return lax.all_to_all(
                    wire, axis_name, 0, 0, tiled=True, axis_index_groups=g_slice
                )

            def consume(out, w, c):
                dec = _quant.decode_blocks(w, C_t * step, codec).astype(x.dtype)
                return lax.dynamic_update_slice_in_dim(
                    out, dec.reshape(p, step), c * step, axis=1
                )

    elif codec is None:

        def issue(c):
            chunk = lax.slice_in_dim(x, c * step, (c + 1) * step, axis=1)
            return lax.all_to_all(chunk, axis_name, 0, 0, tiled=True)

        def consume(out, r, c):
            return lax.dynamic_update_slice_in_dim(out, r, c * step, axis=1)

    else:

        def issue(c):
            chunk = lax.slice_in_dim(x, c * step, (c + 1) * step, axis=1)
            wire = _quant.encode_blocks(chunk, codec)
            return lax.all_to_all(wire, axis_name, 0, 0, tiled=True)

        def consume(out, w, c):
            dec = _quant.decode_blocks(w, step, codec).astype(x.dtype)
            return lax.dynamic_update_slice_in_dim(out, dec, c * step, axis=1)

    return _run_laps(
        range(C), issue, consume, jnp.zeros_like(x), pipelined,
        {"step": "all_to_all", "tier": "ici+dcn" if topo is not None else "ici"},
    )


def _ring_exchange(
    x, axis_name: str, p: int, split_axis: int, concat_axis: int,
    pipelined: bool = False, codec: Optional[str] = None,
):
    """The same split i->j move as p-1 ppermute hops: at distance d every
    device ships ONE neighbor block, so only 2·(local/p) bytes are in
    flight per step — the minimal-footprint schedule. ``pipelined``
    prefetch-issues hop d+1's ppermute before scattering hop d's
    received block (hops slice independently from ``x``, so the rotation
    is a pure reorder: same hops, bit-identical output). ``codec``
    encodes each hop's neighbor block before the ppermute and decodes
    in the place slot — same hops, quarter the wire."""
    from ..kernels import quant as _quant  # noqa: F401 (codec path only)

    r = lax.axis_index(axis_name)
    S = x.shape[split_axis]
    Bs = S // p
    Bc = x.shape[concat_axis]
    out_shape = tuple(
        d * p if k == concat_axis else (Bs if k == split_axis else d)
        for k, d in enumerate(x.shape)
    )
    blk_shape = tuple(Bs if k == split_axis else d for k, d in enumerate(x.shape))
    blk_elems = 1
    for d in blk_shape:
        blk_elems *= d

    def hop(d):
        blk = lax.dynamic_slice_in_dim(x, ((r + d) % p) * Bs, Bs, axis=split_axis)
        if codec is not None:
            blk = _quant.encode_blocks(blk.reshape(1, blk_elems), codec)
        return lax.ppermute(blk, axis_name, [(s, (s + d) % p) for s in range(p)])

    def place(out, recv, d):
        if codec is not None:
            recv = (
                _quant.decode_blocks(recv, blk_elems, codec)
                .astype(x.dtype)
                .reshape(blk_shape)
            )
        return lax.dynamic_update_slice_in_dim(
            out, recv, ((r - d) % p) * Bc, axis=concat_axis
        )

    out = jnp.zeros(out_shape, x.dtype)
    own = lax.dynamic_slice_in_dim(x, r * Bs, Bs, axis=split_axis)
    out = lax.dynamic_update_slice_in_dim(out, own, r * Bc, axis=concat_axis)
    return _run_laps(
        range(1, p), hop, place, out, pipelined, {"step": "ppermute", "tier": "ici"}
    )


# --------------------------------------------------------------------- #
# program builders (one compiled program per (comm, spec, budget))      #
# --------------------------------------------------------------------- #
@functools.lru_cache(maxsize=512)
def _move_program(
    comm, spec: RedistSpec, budget: int, pipelined: bool = False,
    wire: Optional[str] = None, topo: Optional[Tuple[int, int]] = None,
):
    """split i -> split j (all-to-all / chunked / ring / hierarchical)
    on the physical array: pad dst axis (local) -> shard_map exchange ->
    drop src-axis pad (local). ``pipelined`` selects the depth-2
    prefetch-issue form of the chunk/hop loops (same collectives,
    bit-identical output) and is part of the program cache key —
    flipping the ``HEAT_TPU_REDIST_OVERLAP`` gate rebuilds the program.
    ``wire`` (the plan's codec mode, cache-keyed the same way) compiles
    the encoded-payload loop forms when the plan carries codec steps.
    ``topo`` (the plan's topology key, ISSUE 8) compiles the
    hierarchical exchange when the plan's strategy decomposed across
    tiers — and pins the internal re-plan to the same topology, so the
    stamped plan_id always matches the plan the caller executes."""
    sched = _planner.plan(
        spec, budget, quant=wire or "0", topology=topo if topo else "flat"
    )
    mesh, axis_name = comm.mesh, comm.axis_name
    p = spec.mesh_size
    i, j = spec.src_split, spec.dst_split
    ndim = len(spec.gshape)
    Ni, Nj = spec.gshape[i], spec.gshape[j]
    Nip, Njp = _pad_extent(Ni, p), _pad_extent(Nj, p)
    C = max(_a2a_chunks(sched)[0], 1)
    ring = sched.strategy == "ring"
    hier = sched.topo_key if sched.strategy == "hierarchical-a2a" else None
    codec, qin, _ = _quant_flags(sched)
    codec = codec if qin else None

    def body(xl):
        if ring:
            return _ring_exchange(
                xl, axis_name, p, split_axis=j, concat_axis=i,
                pipelined=pipelined, codec=codec,
            )
        return _chunked_all_to_all(
            xl, axis_name, p, split_axis=j, concat_axis=i, C=C,
            pipelined=pipelined, codec=codec, topo=hier,
        )

    mapped = shard_map(
        body,
        mesh=mesh,
        in_specs=(_axis_spec(axis_name, ndim, i),),
        out_specs=_axis_spec(axis_name, ndim, j),
        check_vma=False,
    )

    def fn(phys):
        with _plan_scope(sched.plan_id):
            x = phys
            if Njp != Nj:  # local: axis j is unsharded in the src layout
                widths = [(0, 0)] * ndim
                widths[j] = (0, Njp - Nj)
                x = jnp.pad(x, widths)
            y = mapped(x)
            if Nip != Ni:  # local: axis i is unsharded in the dst layout
                y = lax.slice_in_dim(y, 0, Ni, axis=i)
            return y

    return jax.jit(fn)


@functools.lru_cache(maxsize=512)
def _pivot_program(
    comm, spec: RedistSpec, budget: int, pipelined: bool = False,
    wire: Optional[str] = None, topo: Optional[Tuple[int, int]] = None,
):
    """Reshape-with-repartition through the split-0 pivot: all-to-all to
    the flat-contiguous split-0 layout, LOCAL row-major reshape (the
    minor-dim packing copy runs at full width), all-to-all out. Both
    chunk groups run ``pipelined`` as decorated prefetch-issue loops;
    each engages the wire codec independently per the plan's codec
    steps (``wire`` keys the cache); ``topo`` compiles both stage
    exchanges hierarchically when the plan decomposed across tiers."""
    sched = _planner.plan(
        spec, budget, quant=wire or "0", topology=topo if topo else "flat"
    )
    mesh, axis_name = comm.mesh, comm.axis_name
    p = spec.mesh_size
    s, t = spec.src_split, spec.dst_split
    in_shape, out_shape = spec.gshape, spec.out_shape
    ndim_in, ndim_out = len(in_shape), len(out_shape)
    n_in, n_out = _a2a_chunks(sched)
    C1, C2 = max(n_in, 1), max(n_out, 1)
    hier = sched.topo_key if sched.strategy == "hierarchical-a2a" else None
    codec, qin, qout = _quant_flags(sched)

    def body(xl):
        y = xl
        if s is not None and s != 0:
            y = _chunked_all_to_all(
                y, axis_name, p, split_axis=0, concat_axis=s, C=C1,
                pipelined=pipelined, codec=codec if qin else None, topo=hier,
            )
            in_s, in_sp = in_shape[s], _pad_extent(in_shape[s], p)
            if in_sp != in_s:
                y = lax.slice_in_dim(y, 0, in_s, axis=s)
        local_rows = out_shape[0] // p
        y = y.reshape((local_rows,) + tuple(out_shape[1:]))
        if t is not None and t != 0:
            out_t, out_tp = out_shape[t], _pad_extent(out_shape[t], p)
            if out_tp != out_t:
                widths = [(0, 0)] * ndim_out
                widths[t] = (0, out_tp - out_t)
                y = jnp.pad(y, widths)
            y = _chunked_all_to_all(
                y, axis_name, p, split_axis=t, concat_axis=0, C=C2,
                pipelined=pipelined, codec=codec if qout else None, topo=hier,
            )
        return y

    mapped = shard_map(
        body,
        mesh=mesh,
        in_specs=(_axis_spec(axis_name, ndim_in, s),),
        out_specs=_axis_spec(axis_name, ndim_out, t),
        check_vma=False,
    )

    def fn(phys):
        with _plan_scope(sched.plan_id):
            return mapped(phys)

    return jax.jit(fn)


def _relayout_impls(
    spec: RedistSpec, sched: Schedule
) -> Tuple[Optional[str], Optional[str]]:
    """The (unpack-in, pack-out) kernel implementations serving a
    packed-pivot plan, decided EAGERLY at program-build time and baked
    into the program cache key: flipping ``HEAT_TPU_RELAYOUT_KERNEL``
    rebuilds the program."""
    from ..kernels import relayout as _relayout

    packed_in, packed_out = _packed_flags(sched)
    p = spec.mesh_size
    (r0, c0), (r1, c1) = spec.gshape, spec.out_shape
    c0p, c1p = _pad_extent(c0, p), _pad_extent(c1, p)
    impl_in = (
        _relayout.decide("unpack", r0 // p, c0p, c0, p, spec.dtype)
        if packed_in
        else None
    )
    impl_out = (
        _relayout.decide("pack", r1 // p, c1, c1p, p, spec.dtype)
        if packed_out
        else None
    )
    return impl_in, impl_out


@functools.lru_cache(maxsize=512)
def _packed_pivot_program(
    comm, spec: RedistSpec, budget: int, impl_in, impl_out,
    pipelined: bool = False, wire: Optional[str] = None,
    topo: Optional[Tuple[int, int]] = None,
):
    """The lane-packing pivot (``packed-pivot``): narrow-minor stages
    run on (p, rows·cols/p) column-grouped FLAT buffers so the chunked
    all-to-alls stream full VREGs; the pack/unpack tile-transposing
    copies are served by ``heat_tpu.kernels.relayout`` (XLA formulation
    or the Pallas tiled-copy kernel per ``impl_*``), and the only
    lane-amplified write left is the final dst-shard materialization.
    Same collective census as the direct pivot."""
    from ..kernels import relayout as _relayout

    sched = _planner.plan(
        spec, budget, quant=wire or "0", topology=topo if topo else "flat"
    )
    mesh, axis_name = comm.mesh, comm.axis_name
    p = spec.mesh_size
    s, t = spec.src_split, spec.dst_split
    (r0, c0), (r1, c1) = spec.gshape, spec.out_shape
    c0p, c1p = _pad_extent(c0, p), _pad_extent(c1, p)
    R0, R1 = r0 // p, r1 // p
    cs0, cs1 = c0p // p, c1p // p
    n_in, n_out = _a2a_chunks(sched)
    C1, C2 = max(n_in, 1), max(n_out, 1)
    packed_in, packed_out = _packed_flags(sched)
    hier = sched.topo_key if sched.strategy == "hierarchical-a2a" else None
    codec, qin, qout = _quant_flags(sched)
    codec_in = codec if qin else None
    codec_out = codec if qout else None

    def body(xl):
        if s == 1:
            if packed_in:
                grouped = xl.reshape(p, R0 * cs0)  # free row-block grouping
                recv = _chunked_a2a_flat(
                    grouped, axis_name, p, C1, pipelined=pipelined,
                    codec=codec_in, topo=hier,
                )
                flat = _relayout.unpack_rows(recv, R0, c0p, c0, p, impl=impl_in)
            else:
                y = _chunked_all_to_all(
                    xl, axis_name, p, split_axis=0, concat_axis=1, C=C1,
                    pipelined=pipelined, codec=codec_in, topo=hier,
                )
                if c0p != c0:
                    y = lax.slice_in_dim(y, 0, c0, axis=1)
                flat = y.reshape(R0 * c0)
        else:  # s == 0: the shard already is a contiguous flat block
            flat = xl.reshape(-1)
        if t == 1:
            if packed_out:
                grouped = _relayout.pack_rows(flat, R1, c1, c1p, p, impl=impl_out)
                recv = _chunked_a2a_flat(
                    grouped, axis_name, p, C2, pipelined=pipelined,
                    codec=codec_out, topo=hier,
                )
                # rows arrive in global order: the reshape IS the single
                # lane-amplified materialization of the requested layout
                return recv.reshape(r1, cs1)
            y = flat.reshape(R1, c1)
            if c1p != c1:
                y = jnp.pad(y, ((0, 0), (0, c1p - c1)))
            return _chunked_all_to_all(
                y, axis_name, p, split_axis=1, concat_axis=0, C=C2,
                pipelined=pipelined, codec=codec_out, topo=hier,
            )
        return flat.reshape(R1, c1)

    mapped = shard_map(
        body,
        mesh=mesh,
        in_specs=(_axis_spec(axis_name, 2, s),),
        out_specs=_axis_spec(axis_name, 2, t),
        check_vma=False,
    )

    def fn(phys):
        with _plan_scope(sched.plan_id):
            return mapped(phys)

    return jax.jit(fn)


@functools.lru_cache(maxsize=512)
def _gather_reshape_program(
    comm, spec: RedistSpec, budget: int, topo: Optional[Tuple[int, int]] = None
):
    """The explicit fallback: replicate the physical operand (ONE
    all-gather), drop pads, reshape, re-pad and slice out the dst shard.
    Also serves the replicated-source reshape (no gather: the constraint
    on an already-replicated operand is a no-op). ``topo`` only pins the
    internal re-plan (the tier annotation changes the stamped plan_id,
    never the program form — a full gather spans slices either way)."""
    from ..core import _padding

    sched = _planner.plan(spec, budget, topology=topo if topo else "flat")
    mesh, axis_name = comm.mesh, comm.axis_name
    s, t = spec.src_split, spec.dst_split
    out_shape = spec.out_shape
    ndim_out = max(len(out_shape), 1)

    def fn(phys):
        with _plan_scope(sched.plan_id):
            full = lax.with_sharding_constraint(
                phys, comm.sharding(max(phys.ndim, 1), None)
            )
            logical = _padding.unpad(full, spec.gshape, s)
            r = jnp.reshape(logical, out_shape) if spec.is_reshape else logical
            rp = _padding.pad_logical(r, t, comm.size)
            return lax.with_sharding_constraint(rp, comm.sharding(ndim_out, t))

    return comm.jit_sharded(fn, ndim_out, t)


@functools.lru_cache(maxsize=512)
def _local_reshape_program(comm, spec: RedistSpec, budget: int):
    """Zero-collective reshape paths: 1-device meshes and replicated
    sources (the dst distribution is a local slice). No topo key: a
    collective-free plan carries no tier annotation, so its plan_id is
    topology-independent by construction."""
    from ..core import _padding

    sched = _planner.plan(spec, budget)
    s, t = spec.src_split, spec.dst_split
    out_shape = spec.out_shape
    ndim_out = max(len(out_shape), 1)

    def fn(phys):
        with _plan_scope(sched.plan_id):
            logical = _padding.unpad(phys, spec.gshape, s)
            r = jnp.reshape(logical, out_shape)
            rp = _padding.pad_logical(r, t, comm.size)
            return lax.with_sharding_constraint(rp, comm.sharding(ndim_out, t))

    return comm.jit_sharded(fn, ndim_out, t)


def clear_program_cache() -> None:
    _move_program.cache_clear()
    _pivot_program.cache_clear()
    _packed_pivot_program.cache_clear()
    _gather_reshape_program.cache_clear()
    _local_reshape_program.cache_clear()


# a world rebuild (init_distributed) invalidates every program: the
# mesh (and the comm identity in the cache key) baked into them is gone
from ..core.communication import register_mesh_cache as _register_mesh_cache

_register_mesh_cache(_move_program)
_register_mesh_cache(_pivot_program)
_register_mesh_cache(_packed_pivot_program)
_register_mesh_cache(_gather_reshape_program)
_register_mesh_cache(_local_reshape_program)


# --------------------------------------------------------------------- #
# execution                                                             #
# --------------------------------------------------------------------- #
def _overlap_active(sched: Schedule) -> bool:
    """Does this execution run the software-pipelined program form?
    ``HEAT_TPU_REDIST_OVERLAP=0`` forces the sequential oracle, ``=1``
    forces pipelining, and the default ``auto`` follows the plan's own
    overlap annotation (the planner's modeled depth decision). Either
    way the plan — and therefore the collective census — is the same;
    only the issue order inside the chunk loops changes."""
    mode = _planner.overlap_mode()
    if mode == "0":
        return False
    if mode == "1":
        return True
    return sched.overlap is not None


def _reshard_direct(comm, phys, gshape, src, dst):
    """The legacy relayout (unpad -> repad -> placement): still the
    lowering for the no-collective strategies, where GSPMD's local
    slice IS the schedule."""
    from ..core import _padding

    logical = _padding.unpad(phys, tuple(gshape), src)
    return comm.shard(logical, dst)


def execute(comm, phys, spec: RedistSpec, sched: Optional[Schedule] = None):
    """Run the planned redistribution of ``phys`` (a physical array laid
    out per ``spec.src_split``) and return the dst-layout physical
    array. Trace-safe: under a trace the cached jitted programs inline
    and the eager placements lower to sharding constraints."""
    # world-epoch fence (ISSUE 13): an in-flight collective entering on
    # a communicator the elastic runtime stamped for a world that has
    # since re-resolved raises the typed WorldChangedError instead of
    # hanging on devices that are gone. Zero-cost by construction when
    # no communicator was ever stamped (the default and the
    # HEAT_TPU_RESILIENCE=0 escape hatch: one empty-dict truthiness
    # check), so the pre-resilience dispatch path is untouched.
    from ..resilience import elastic as _elastic

    _elastic.check_world(comm)
    if sched is None:
        sched = _planner.plan(spec)
    else:
        # the program builders compile the PLANNER's schedule for
        # (spec, budget, codec, topology) — a hand-built/modified
        # Schedule would be silently ignored, so refuse it instead (a
        # caller-provided sched pins ITS codec AND topology: passing a
        # quantized or tiered plan executes that program regardless of
        # the ambient gates)
        planned = _planner.plan(
            spec, sched.budget_bytes,
            quant=sched.quant["mode"] if sched.quant else "0",
            topology=sched.topo_key if sched.topo_key else "flat",
        )
        if planned.plan_id != sched.plan_id:
            raise ValueError(
                f"execute: schedule {sched.plan_id} is not the planner's "
                f"plan for {spec!r} under budget {sched.budget_bytes} B "
                f"(expected {planned.plan_id}); executor programs compile "
                "from the plan cache, not from caller-provided schedules"
            )
    if _telemetry._ENABLED:
        _telemetry.inc("redist.execute.calls")
    strategy = sched.strategy
    budget = sched.budget_bytes
    wire = sched.quant["mode"] if sched.quant else None
    topo = sched.topo_key
    # a program only HAS a pipelined issue order when the plan carries
    # tagged laps (chunk groups / ring hops): single-collective plans and
    # the barrier strategies (replicate/gather-reshape/local-reshape)
    # must neither count as pipelined executions nor compile a second,
    # identical program under the pipelined cache key
    pipeable = any(st.overlap for st in sched.steps)
    pipelined = _overlap_active(sched) and pipeable
    if _telemetry._ENABLED and strategy not in ("noop", "local", "slice"):
        _telemetry.inc(
            "redist.overlap.pipelined" if pipelined else "redist.overlap.sequential"
        )
        if sched.n_collectives:
            # bytes-on-wire accounting (ISSUE 7): raw = full-width
            # payload of the plan's collectives, sent = what actually
            # crosses the mesh (the encoded bytes under the codec)
            raw, sent = sched.wire_bytes_raw, sched.wire_bytes_sent
            _telemetry.inc("redist.wire.bytes_raw", raw)
            _telemetry.inc("redist.wire.bytes_sent", sent)
            _telemetry.inc("redist.wire.saved", raw - sent)
        if topo is not None and sched.n_collectives:
            # per-tier wire accounting (ISSUE 8)
            tb = sched.tier_bytes()
            _telemetry.inc("redist.tier.ici_bytes", tb["ici"])
            _telemetry.inc("redist.tier.dcn_bytes", tb["dcn"])
    def _dispatch():
        if strategy == "noop":
            return phys
        if strategy in ("slice",) or (strategy == "local" and not spec.is_reshape):
            # no-collective placements: GSPMD's local slice IS the schedule,
            # and with no collective there is nothing for shardlint to flag
            return _reshard_direct(comm, phys, spec.gshape, spec.src_split, spec.dst_split)
        if strategy == "replicate":
            # the explicit full all-gather runs as a stamped program too, so
            # its SL102 finding reports as info with the plan id attached
            return _gather_reshape_program(comm, spec, budget, topo)(phys)
        if strategy in ("all-to-all", "chunked-all-to-all", "ring"):
            return _move_program(comm, spec, budget, pipelined, wire, topo)(phys)
        if strategy == "hierarchical-a2a":
            # the tiered decomposition (ISSUE 8): pivot-family when the plan
            # carries a reshape step, plain move otherwise; packed when the
            # plan carries pack/unpack steps — all re-derived from step
            # KINDS so program and plan cannot disagree
            if spec.is_reshape:
                if any(st.kind in ("pack", "unpack") for st in sched.steps):
                    if _telemetry._ENABLED:
                        _telemetry.inc("redist.relayout.packed")
                    impl_in, impl_out = _relayout_impls(spec, sched)
                    return _packed_pivot_program(
                        comm, spec, budget, impl_in, impl_out, pipelined, wire, topo
                    )(phys)
                if _telemetry._ENABLED:
                    _telemetry.inc("redist.relayout.direct")
                return _pivot_program(comm, spec, budget, pipelined, wire, topo)(phys)
            return _move_program(comm, spec, budget, pipelined, wire, topo)(phys)
        if strategy == "split0-pivot":
            if _telemetry._ENABLED:
                _telemetry.inc("redist.relayout.direct")
            return _pivot_program(comm, spec, budget, pipelined, wire, topo)(phys)
        if strategy == "packed-pivot":
            if _telemetry._ENABLED:
                _telemetry.inc("redist.relayout.packed")
            impl_in, impl_out = _relayout_impls(spec, sched)
            return _packed_pivot_program(
                comm, spec, budget, impl_in, impl_out, pipelined, wire, topo
            )(phys)
        if strategy == "gather-reshape":
            return _gather_reshape_program(comm, spec, budget, topo)(phys)
        if strategy in ("local-reshape", "local"):
            if spec.src_split == 0 and spec.dst_split == 0 and spec.mesh_size > 1:
                # divisible split-0 <-> split-0: device blocks stay put
                return _pivot_program(comm, spec, budget, pipelined, wire, topo)(phys)
            return _local_reshape_program(comm, spec, budget)(phys)
        raise ValueError(f"unknown strategy {strategy!r} (plan {sched.plan_id})")

    if not _tracing._ENABLED:
        return _dispatch()
    # span tracing (ISSUE 15): one host-side `redist.execute` span per
    # plan execution, with the plan_id as ambient context so the
    # per-lap probes inside the (possibly now-tracing) program body
    # inherit it. On a program-cache hit the body never re-traces, so
    # the lap spans fire once per compile — span census == plan
    # structure, pinned in tier-1.
    with _tracing.span(
        "redist.execute",
        plan_id=sched.plan_id,
        strategy=strategy,
        step="execute",
        pipelined=pipelined,
        n_steps=sched.n_steps,
        n_collectives=sched.n_collectives,
    ):
        with _tracing.context(plan_id=sched.plan_id):
            return _dispatch()


def resplit_phys(comm, phys, gshape, src: Optional[int], dst: Optional[int]):
    """Planner-routed split change of a physical array — the engine
    under ``DNDarray.resplit``/``resplit_`` and
    ``MeshCommunication.reshard_phys``."""
    gshape = tuple(int(v) for v in gshape)
    if (
        not _planner.planner_enabled()
        or phys.ndim != len(gshape)  # planar-complex plane pairs: legacy path
        or any(v == 0 for v in gshape)
    ):
        return _reshard_direct(comm, phys, gshape, src, dst)
    spec = RedistSpec.normalize(gshape, np.dtype(phys.dtype).name, src, dst, comm.size)
    return execute(comm, phys, spec)


def reshape_phys(comm, phys, in_gshape, in_split, out_shape, out_split):
    """Planner-routed reshape-with-repartition of a physical array — the
    engine under ``ht.reshape(..., new_split=...)``."""
    in_gshape = tuple(int(v) for v in in_gshape)
    out_shape = tuple(int(v) for v in out_shape)
    spec = RedistSpec.normalize(
        in_gshape,
        np.dtype(phys.dtype).name,
        in_split,
        out_split,
        comm.size,
        reshape_to=out_shape,
    )
    return execute(comm, phys, spec)
