"""Distributed QR decomposition.

API parity with /root/reference/heat/core/linalg/qr.py (``qr`` at qr.py:17:
tiled CAQR on ``SquareDiagTiles`` — per-tile-column local torch QR plus
Householder merges of tile rows across ranks, ``__split0_r_calc`` :314,
``__split0_merge_tile_rows`` :482, ``__split0_q_loop`` :667; split=1 panel
broadcast loop ``__split1_qr_loop`` :858).

TPU-native redesign: the split=0 tall-skinny case is **TSQR**
(communication-avoiding QR — the same algorithm family the reference's
CAQR cites at qr.py:49-58) expressed as ONE ``shard_map``:

    per-shard local QR  →  all_gather of the tiny R factors
    →  merge QR of the stacked R's  →  local Q update (MXU matmul)

One grouped-all-gather level at small meshes (p·n² floats); composite
meshes of 16+ devices run a TWO-LEVEL group tree — two grouped
all-gathers carrying (s + p/s)·n² floats (see ``_tsqr_fn``) — everything
else is local MXU work, the whole thing one XLA program. The reference's
``tiles_per_proc`` knob tuned CPU cache blocking; XLA tiles for the MXU
itself, so the knob is accepted for API parity and ignored.

Pad-safety: TSQR runs on the physical (zero-padded) array — zero rows
contribute zero R rows, so R is exact; Q's pad rows are re-masked to zero
afterwards (see ``_padding``).
"""

from __future__ import annotations

import collections

import numpy as np

import jax
import jax.numpy as jnp

from jax.sharding import NamedSharding, PartitionSpec
from typing import Optional, Tuple, Union

from .. import types
from .. import _padding
from jax import shard_map as _shard_map
from ..communication import MeshCommunication
from ..dndarray import DNDarray
from ..sanitation import sanitize_in
from ...observability.instrument import observed_program_cache

__all__ = ["qr"]

QR = collections.namedtuple("QR", "Q, R")


def _tsqr_group_size(p: int) -> int:
    """Group width for the two-level merge: the largest divisor of p not
    exceeding √p (1 when p is prime — single-level)."""
    best = 1
    s = 2
    while s * s <= p:
        if p % s == 0:
            best = s
        s += 1
    return best


def _tsqr_grouping(p: int, topo=None) -> int:
    """Level-1 group width ``s`` of the TSQR merge tree (1 = flat
    single-level). At a TIERED topology (ISSUE 8) the tree groups
    SLICE-MAJOR: ``s = chips_per_slice``, so every level-0/1 merge —
    the gathers that carry ``s·K²`` bytes per member — stays inside one
    ICI domain, and only the ``n_slices`` group-R factors (``G·K²``
    bytes) cross DCN at level 2. The two-level tree then engages at ANY
    tiered mesh width, not just ≥ 16: crossing DCN with the full
    ``p·K²`` flat gather would pay the ~8× tier penalty on ``(p-1)/p``
    of the bytes for no reason. Flat topologies keep the pre-ISSUE-8
    rule (√p divisor grouping from 16 devices up) so every pinned
    census holds verbatim."""
    if topo is not None:
        S, C = topo
        if S > 1 and C > 1 and S * C == p:
            return C
    return _tsqr_group_size(p) if p >= _TSQR_TWO_LEVEL_MIN_P else 1


# single-level at small meshes (the merge term is noise there and the HLO
# contract stays one all-gather); two-level from this width up
_TSQR_TWO_LEVEL_MIN_P = 16


def _tsqr_ring_active() -> bool:
    """Does TSQR run its collective-matmul merge — the R-factor
    all-gather decomposed into a ppermute ring whose landed blocks are
    stacked as they arrive (``kernels.cmatmul.ring_all_gather``)? Gated
    by ``HEAT_TPU_REDIST_OVERLAP`` (forced by ``=1``, off at ``=0``,
    TPU-only under ``auto``); the assembled stack is element-identical
    to the all-gather's, so Q/R are bit-identical either way."""
    from ...kernels import cmatmul as _cm

    return _cm.ring_enabled()


def _tsqr_kernel(p: int, axis_name: str, calc_q: bool, ring: bool = False, topo=None):
    """The per-device body of TSQR over a ``p``-wide mesh axis, to be run
    under ``shard_map`` on a row block: ``_tsqr_fn`` wraps it into a
    program of its own, the distributed hSVD's merge calls it inside its
    one program (``svdtools._dist_rank_fn``).

    p < 16 (or prime p): the flat schedule — ONE all-gather of the p R
    factors, one stacked merge QR. p ≥ 16 with a divisor s ≤ √p: the
    TWO-LEVEL tree (docs/PERF.md names the flat merge's (p·r)² growth as
    the mesh-width wall) — R factors all-gather WITHIN each of the p/s
    groups (s·K² bytes), each group merges to a group-R, the p/s group-Rs
    all-gather ACROSS groups (p/s·K² bytes), one final merge: ICI bytes
    and replicated merge FLOPs drop from p·K² / p·K³ to
    (s + p/s)·K² / (s + p/s)·K³ — 4× at p=64, 8× at p=256, exactly the
    point PERF's model said a two-level tree becomes necessary. Q update
    composes the two tiny block factors: Q = Q_local · Q2[j] · Q3[g].

    ``ring=True`` (the collective-matmul form, ISSUE 6): each gather —
    flat, and both levels of the tree — runs as a ppermute ring that
    stacks blocks as they land instead of after the all-gather barrier,
    overlapping the assembly copies (and, on TPU, the local QR epilogue)
    with the wire. Byte-equivalent movement ((size-1)·K·cols per level),
    identical merge inputs, bit-identical Q/R.

    ``topo=(S, C)`` (ISSUE 8): slice-major grouping — level-1 groups
    are exactly the slices (``s = C``), so the heavy gathers never
    cross DCN and only the tiny cross-group gather (G = n_slices
    group-Rs) rides the expensive tier.

    The Q updates (``q1 @ q2_i``) run at ``precision="highest"``: their
    contraction is ``cols`` wide, and at the MXU's default precision (one
    bf16 pass) Q came back orthonormal to 3e-3 only (PERF.md, PR 25)."""
    s = _tsqr_grouping(p, topo)
    two_level = s > 1
    from ...kernels import cmatmul as _cm

    def ring_gather(x, size, pos, perm):
        # only called from the ring branches below
        with _cm.stamp_scope("tsqr"):
            return _cm.ring_all_gather(x, axis_name, size, pos, perm, pipelined=True)

    def kernel(a):
        # a: local shard (lrows, cols)
        q1, r1 = jnp.linalg.qr(a, mode="reduced")
        k = q1.shape[1]
        if not two_level:
            i = jax.lax.axis_index(axis_name)
            if ring:
                # the complete flat p-ring (one source/target per device
                # — the SL502 congruence contract, built in one place)
                rs = ring_gather(r1, p, i, _cm.grouped_ring_perm(1, p))
            else:
                rs = jax.lax.all_gather(r1, axis_name)  # (p, k, cols)
            q2, r = jnp.linalg.qr(rs.reshape(-1, rs.shape[-1]), mode="reduced")
            if not calc_q:
                return r
            q2_i = jax.lax.dynamic_slice_in_dim(q2, i * k, k)
            return jnp.matmul(q1, q2_i, precision="highest"), r

        G = p // s
        i = jax.lax.axis_index(axis_name)
        g = i // s   # group id
        j = i % s    # position within group
        # level 1: gather the s member R's within each group
        if ring:
            rs1 = ring_gather(r1, s, j, _cm.grouped_ring_perm(G, s))
        else:
            groups1 = [[gg * s + jj for jj in range(s)] for gg in range(G)]
            rs1 = jax.lax.all_gather(r1, axis_name, axis_index_groups=groups1)
        q2, r_g = jnp.linalg.qr(rs1.reshape(-1, rs1.shape[-1]), mode="reduced")
        k2 = q2.shape[1]
        # level 2: every group's R_g is replicated within the group, so
        # gathering across same-j columns hands every device all G of them
        if ring:
            rs2 = ring_gather(r_g, G, g, _cm.grouped_ring_perm(G, s, across=True))
        else:
            groups2 = [[gg * s + jj for gg in range(G)] for jj in range(s)]
            rs2 = jax.lax.all_gather(r_g, axis_name, axis_index_groups=groups2)
        q3, r = jnp.linalg.qr(rs2.reshape(-1, rs2.shape[-1]), mode="reduced")
        if not calc_q:
            return r
        q2_j = jax.lax.dynamic_slice_in_dim(q2, j * k, k)
        q3_g = jax.lax.dynamic_slice_in_dim(q3, g * k2, k2)
        q23 = jnp.matmul(q2_j, q3_g, precision="highest")
        return jnp.matmul(q1, q23, precision="highest"), r

    return kernel


@observed_program_cache("qr.tsqr")
def _tsqr_fn(
    mesh, axis_name: str, lrows: int, cols: int, jdtype: str, calc_q: bool,
    ring: bool = False, topo=None,
):
    """Compiled TSQR over the mesh for physical shard shape (lrows, cols):
    ``_tsqr_kernel`` as one ``shard_map`` program."""
    kernel = _tsqr_kernel(mesh.devices.size, axis_name, calc_q, ring, topo)
    in_specs = PartitionSpec(axis_name, None)
    if calc_q:
        out_specs = (PartitionSpec(axis_name, None), PartitionSpec(None, None))
    else:
        out_specs = PartitionSpec(None, None)
    return jax.jit(
        _shard_map(
            kernel, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
        )
    )


def qr(
    a: DNDarray,
    tiles_per_proc: int = 1,
    calc_q: bool = True,
    overwrite_a: bool = False,
) -> QR:
    """QR decomposition of a 2-D DNDarray (reference: qr.py:17).

    Returns ``QR(Q, R)`` with Q orthonormal and R upper-triangular
    (``QR(None, R)`` when ``calc_q=False``). split=0 runs TSQR over the
    mesh; split=1/None run XLA's QR on the (sharded) global array.
    ``tiles_per_proc`` is accepted for reference-API parity; XLA performs
    its own MXU tiling.
    """
    sanitize_in(a)
    if a.ndim != 2:
        raise ValueError(f"qr requires a 2-dimensional array, got {a.ndim}")
    if not isinstance(calc_q, bool):
        raise TypeError(f"calc_q must be a bool, got {type(calc_q)}")
    if not isinstance(tiles_per_proc, (int, np.integer)) or isinstance(tiles_per_proc, bool):
        raise TypeError(f"tiles_per_proc must be an int, got {type(tiles_per_proc)}")
    if tiles_per_proc != 1:
        import warnings

        # reference code tunes this against CPU cache blocking; here XLA
        # owns MXU tiling — a silent no-op would surprise ported callers
        warnings.warn(
            "tiles_per_proc is accepted for reference-API parity but has no "
            "effect: XLA performs its own MXU tiling (TSQR replaces tiled CAQR)",
            UserWarning,
            stacklevel=2,
        )
    if not isinstance(overwrite_a, bool):
        raise TypeError(f"overwrite_a must be a bool, got {type(overwrite_a)}")

    dtype = a.dtype
    if types.heat_type_is_exact(dtype):
        dtype = types.float32
    jt = dtype.jax_type()
    m, n = a.shape
    comm: MeshCommunication = a.comm

    # TSQR applies to tall matrices (m >= n): the stacked R merge is then a
    # strict reduction and R comes out (n, n); wide matrices take the
    # gathered XLA path
    use_tsqr = a.split == 0 and comm.is_distributed() and m >= n and n <= 4096

    if use_tsqr:
        phys = a._phys.astype(jt)
        lrows = phys.shape[0] // comm.size
        topo_t = comm.topology
        fn = _tsqr_fn(
            comm.mesh, comm.axis_name, lrows, n, np.dtype(jt).name, calc_q,
            ring=_tsqr_ring_active(),
            topo=(topo_t.n_slices, topo_t.chips_per_slice) if topo_t.tiered else None,
        )
        if calc_q:
            q_phys, r = fn(phys)
            # restore the zero-pad invariant on Q (see module docstring)
            q_phys = _padding.mask_phys(q_phys, (m, q_phys.shape[1]), 0)
            k = int(q_phys.shape[1])
            q_arr = DNDarray(q_phys, (m, k), dtype, 0, a.device, comm)
        else:
            r = fn(phys)
            q_arr = None
        r_arr = DNDarray(
            _place(r, comm.sharding(2, None)), tuple(int(s) for s in r.shape), dtype, None, a.device, comm
        )
        return QR(q_arr, r_arr)

    # split=1 / replicated: XLA QR on the logical global array (GSPMD
    # partitions the panel updates; the reference's split=1 loop at
    # qr.py:858 broadcasts panels rank-by-rank instead)
    arr = a.larray.astype(jt)
    if calc_q:
        q, r = jnp.linalg.qr(arr, mode="reduced")
        q_gshape = tuple(int(s) for s in q.shape)
        r_gshape = tuple(int(s) for s in r.shape)
        q_split = a.split
        q_arr = DNDarray(
            comm.shard(q, q_split) if q_split is not None else q,
            q_gshape,
            dtype,
            q_split,
            a.device,
            comm,
        )
        r_split = 1 if a.split == 1 else None
        r_arr = DNDarray(
            comm.shard(r, r_split) if r_split is not None else r,
            r_gshape,
            dtype,
            r_split,
            a.device,
            comm,
        )
        return QR(q_arr, r_arr)
    r = jnp.linalg.qr(arr, mode="r")
    r_gshape = tuple(int(s) for s in r.shape)
    r_split = 1 if a.split == 1 else None
    r_arr = DNDarray(
        comm.shard(r, r_split) if r_split is not None else r, r_gshape, dtype, r_split, a.device, comm
    )
    return QR(None, r_arr)


DNDarray.qr = qr

from ..communication import register_mesh_cache
from ..communication import place as _place

# entries bake mesh geometry: cleared when init_distributed rebuilds the world
register_mesh_cache(_tsqr_fn)
