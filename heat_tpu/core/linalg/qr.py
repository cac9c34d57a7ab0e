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

The factorization of one device's own rows (the whole call on one device,
level 0 of TSQR on many) is ``_local_qr``: where ``_gram_serves`` says so (a
TPU, f32 or f64, at least twice as many rows as columns) it is the Gram form
``_gram_qr``, whose work over the tall operand is matrix products for the MXU
(a Cholesky-QR with a second pass; shifted repair steps decided on the device
for what a Gram matrix cannot factor); elsewhere XLA's Householder QR.

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
from . import _pallas_qr
from jax import shard_map as _shard_map
from ..communication import MeshCommunication
from ..dndarray import DNDarray
from ..sanitation import sanitize_in
from ...observability import telemetry as _telemetry
from ...observability.instrument import observed_program_cache
from ...observability.tracing import call_span as _call_span, span as _span

__all__ = ["qr"]

QR = collections.namedtuple("QR", "Q, R")


# --------------------------------------------------------------------- #
# the factorization of one device's own rows                             #
# --------------------------------------------------------------------- #
_HI = jax.lax.Precision.HIGHEST
# The MXU runs an f32 product as one, three or six bf16 passes. What each
# tall product of the Gram form needs (PERF.md, PR 34, has the chip's readings):
# - the first Gram matrix only preconditions: any R1 gives A = Q1 R1 as exactly
#   as the product A R1^-1 is computed, and what R1 leaves of Q1^T Q1 - I the
#   second pass measures and repairs: three passes;
# - the products that make a Q_k, and the Gram matrix that the last Cholesky
#   factors, decide the residual and the orthogonality: six passes;
# - the last product is Q + Q (R^-1 - I) with R^-1 - I small: three.
# Where they run: as the two kernels of _pallas_qr where _panels_serve says so
# (six passes as Mosaic's HIGHEST, three as sums over bf16 parts the kernel
# splits off itself), else as XLA's whole products at these precisions
# (_gram_of, _times). The kernels skip
# the blocks of a Gram matrix under its diagonal blocks and those of R^-1 there:
# the first are what the mirror holds, the second exact zeros (_upper_inverse
# ends in triu), so what is left out is no approximation.
_TALL = collections.namedtuple("_TALL", "gram_first, apply, gram, finish")
_TALL_PRECISION = _TALL(jax.lax.Precision.HIGH, _HI, _HI, jax.lax.Precision.HIGH)
_PASSES = {jax.lax.Precision.DEFAULT: 1, jax.lax.Precision.HIGH: 3, _HI: 6}  # the bf16 passes of an f32 product
_BLOCK_BYTES = 32 << 20  # a row block of the tall passes: 8192 x 1024 f32
_ORTH_OK = 0.1  # ||Q^T Q - I||_F up to which one unshifted Cholesky step leaves Q orthonormal to rounding
_MAX_REPAIRS = 8  # repair steps at most: each gains a factor of about 1 / sqrt(n eps) in the condition number
_LADDER = 12  # shifts tried: 0, then n eps max(diag G) times 1, 10, ..., 1e10


def _gram_serves(m: int, n: int, dtype) -> bool:
    """Does the Gram form factor an ``m x n`` block of ``dtype`` here? On a
    TPU (its products are what the MXU is for; on a CPU LAPACK's Householder
    QR is the better one), in f32 or f64, from twice as many rows as columns
    (the block the repair perturbs is ``2n x n``)."""
    return (
        jax.default_backend() == "tpu"
        and np.dtype(dtype) in (np.dtype(np.float32), np.dtype(np.float64))
        and n >= 1
        and m >= 2 * n
    )


def _panels_serve(m: int, n: int, dtype) -> bool:
    """Do the tall products of an ``m x n`` block of ``dtype`` run as the
    kernels of ``_pallas_qr``, which do only the blocks that symmetry and the
    triangle leave? On a TPU with x64 off (Mosaic refuses 64-bit traces), in
    f32, at the shapes the kernels take (``_pallas_qr.serves``: ``n`` a
    multiple of 128 up to 1024, more than one of their row blocks). Every
    other input keeps XLA's whole products (``_gram_of``, ``_times``)."""
    return (
        jax.default_backend() == "tpu"
        and not jax.config.jax_enable_x64
        and np.dtype(dtype) == np.dtype(np.float32)
        and _pallas_qr.serves(m, n)
    )


def _row_blocks(m: int, n: int, itemsize: int) -> Tuple[int, int]:
    """(rows of a block, whole blocks): ``_BLOCK_BYTES`` a block, rows a multiple of 8."""
    b = min(m, max(8, _BLOCK_BYTES // (n * itemsize) // 8 * 8))
    return b, m // b


def _gram_of(x, precision):
    """``x^T x``: with ``_times`` every product over a tall operand."""
    return jax.lax.dot_general(x, x, (((0,), (0,)), ((), ())), precision=precision)


def _times(x, w, precision):
    return jnp.matmul(x, w, precision=precision)


def _gram(a, precision):
    """``A^T A``, a row block at a time (the sum of the blocks' products; the
    kernel's where ``_panels_serve`` says so)."""
    m, n = a.shape
    if _panels_serve(m, n, a.dtype):
        return _pallas_qr.gram(a, _PASSES[precision])
    b, nb = _row_blocks(m, n, a.dtype.itemsize)
    if b == m:  # one block: no loop
        return _gram_of(a, precision)
    g = jax.lax.fori_loop(
        0, nb,
        lambda i, g: g + _gram_of(jax.lax.dynamic_slice_in_dim(a, i * b, b), precision),
        jnp.zeros((n, n), a.dtype),
    )
    return g + _gram_of(a[nb * b:], precision) if m > nb * b else g


def _apply(src, q, w, precision, gram_precision=None, finish=False):
    """``q <- src w`` a row block at a time, in place where ``src`` is ``q``
    (``q`` None: a new array), and with ``gram_precision`` the Gram matrix of
    the new ``q`` from the same blocks. ``finish``: ``w`` is close to the
    identity, so the product is ``src + src (w - I)``. ``w`` is upper
    triangular: where ``_panels_serve`` says so the kernel does its blocks on
    and over the diagonal alone."""
    m, n = src.shape
    in_place = src is q
    if finish:
        w = w - jnp.eye(n, dtype=w.dtype)
    want_gram = gram_precision is not None
    if _panels_serve(m, n, src.dtype):
        return _pallas_qr.apply(src, w, _PASSES[precision], gram_passes=_PASSES[gram_precision] if want_gram else None,
                                finish=finish, in_place=in_place)
    b, nb = _row_blocks(m, n, src.dtype.itemsize)

    def one(blk):
        x = _times(blk, w, precision)
        x = blk + x if finish else x
        return x, (_gram_of(x, gram_precision) if want_gram else None)

    g0 = jnp.zeros((n, n), src.dtype) if want_gram else None
    if b == m:  # one block: no loop
        x, g = one(src)
        return x, g
    if q is None:
        q = jnp.zeros_like(src)

    def step(i, carry):
        q, g = carry
        x, gx = one(jax.lax.dynamic_slice_in_dim(q if in_place else src, i * b, b))
        return jax.lax.dynamic_update_slice_in_dim(q, x, i * b, 0), (g + gx if want_gram else g)

    q, g = jax.lax.fori_loop(0, nb, step, (q, g0))
    if m > nb * b:
        x, gx = one((q if in_place else src)[nb * b:])
        q = jax.lax.dynamic_update_slice_in_dim(q, x, nb * b, 0)
        g = g + gx if want_gram else g
    return q, g


def _factor(g):
    """``(L, shifted)`` with ``L L^T = G + s I`` for the least shift of the
    ladder 0, c, 10 c, ... (c = n eps max(diag G)) whose Cholesky factor is
    finite with no pivot under half of max(c, s): a pivot at the level of
    the rounding of G is noise, and what it divides would be too."""
    n = g.shape[0]
    eps = jnp.finfo(g.dtype).eps
    eye = jnp.eye(n, dtype=g.dtype)
    c = jnp.maximum(n * eps * jnp.max(jnp.diagonal(g)), jnp.finfo(g.dtype).tiny * 2.0 ** 40)

    def attempt(j):
        s = jnp.where(j == 0, 0.0, c * 10.0 ** (j - 1).astype(g.dtype)).astype(g.dtype)
        low = jnp.linalg.cholesky(g + s * eye)
        d = jnp.diagonal(low)
        return low, jnp.all(jnp.isfinite(low)) & (jnp.min(d * d) >= 0.5 * jnp.maximum(c, s))

    def again(state):
        j, _, ok = state
        return ~ok & (j < _LADDER)

    def nxt(state):
        j = state[0] + 1
        return (j, *attempt(j))

    j, low, _ = jax.lax.while_loop(again, nxt, (jnp.int32(0), *attempt(jnp.int32(0))))
    return low, j > 0


def _upper_inverse(low):
    """``R^-1`` of ``R = L^T``, upper triangular."""
    eye = jnp.eye(low.shape[0], dtype=low.dtype)
    return jnp.triu(jax.lax.linalg.triangular_solve(low, eye, left_side=True, lower=True, transpose_a=True))


def _perturb(q, g, amp):
    """``q`` with ``amp`` x a fixed Gaussian ``2n x n`` block added to its
    first rows (spectral norm ``amp`` x 1: about ``(sqrt(2n) + sqrt(n))`` x
    the entries' size), and its Gram matrix. ``amp`` 0 changes nothing. A
    direction in which ``q`` has no component at all (a zero or repeated
    column) no Cholesky step can scale up; after this it has one of about
    0.2 ``amp``, and ``A + E = Q R`` with ``||E|| <= amp ||R||``: a backward
    error at the level of the precision."""
    n = g.shape[0]
    top = q[: 2 * n]
    noise = jax.random.normal(jax.random.key(0), top.shape, q.dtype) * (amp / ((2 * n) ** 0.5 + n ** 0.5))
    cross = jnp.matmul(top.T, noise, precision=_HI)
    g = g + cross + cross.T + _gram_of(noise, _HI)
    return jax.lax.dynamic_update_slice_in_dim(q, top + noise, 0, 0), g


def _gram_qr(a, calc_q: bool = True, tall: _TALL = _TALL_PRECISION):
    """Thin QR of a tall block by Cholesky steps on Gram matrices.

    ``R1 = chol(A^T A)``, ``Q1 = A R1^-1``, ``R2 = chol(Q1^T Q1)``, ``Q = Q1
    R2^-1``, ``R = R2 R1`` (Cholesky-QR with a second pass): every flop over
    the tall operand is a matrix product, a row block at a time, ``Q`` made in
    place in its own array, nothing else of ``A``'s size; where
    ``_panels_serve`` says so the products are kernels that do only the blocks
    the Gram matrices' symmetry and the triangle of ``R^-1`` leave and write
    ``Q`` themselves (``_pallas_qr``). That is right while
    ``cond(A)^2 eps`` is well under 1. What it cannot factor it sees on the
    device, with no host read: a Cholesky factor that breaks down or has a
    pivot at the rounding level takes the least shift of a ladder
    (``_factor``), and while the Gram matrix of ``Q_k`` is a shifted one or
    is further than ``_ORTH_OK`` from the identity, one more step ``Q_k <-
    Q_k R_k^-1`` runs (a ``while_loop``; none on well-conditioned input). A
    ``Q_k`` whose own Gram matrix still needs a shift has directions with next
    to nothing in them (rank-deficient to rounding, which no Cholesky step can
    scale up): ``_perturb`` gives them something, once. So
    ``Q`` is orthonormal and ``A = Q R`` to rounding for every finite input;
    ``R`` is upper triangular with exact zeros below a positive diagonal."""
    n = a.shape[1]
    eps = jnp.finfo(a.dtype).eps
    with jax.named_scope("qr.tall.gram"):
        g = _gram(a, tall.gram_first)
    with jax.named_scope("qr.small.factor"):
        low, _ = _factor(g)
        r = low.T
        w = _upper_inverse(low)
    with jax.named_scope("qr.tall.apply"):
        q, g = _apply(a, None, w, tall.apply, tall.gram)

    def probe(g):
        low, shifted = _factor(g)
        off = jnp.sqrt(jnp.sum(jnp.square(g - jnp.eye(n, dtype=g.dtype))))
        return low, shifted, shifted | ~(off <= _ORTH_OK)

    def more(state):
        return state[-1] & (state[3] < _MAX_REPAIRS)

    def repair(state):
        q, g, r, k, perturbed, _, shifted, _ = state
        with jax.named_scope("qr.small.repair"):
            amp = jnp.where(shifted & ~perturbed, 8.0 * eps, 0.0).astype(q.dtype)
            q, g = _perturb(q, g, amp)
            low, _ = _factor(g)
            r = jnp.triu(jnp.matmul(low.T, r, precision=_HI))
            w = _upper_inverse(low)
        with jax.named_scope("qr.tall.repair"):
            q, g = _apply(q, q, w, tall.apply, tall.gram)
        with jax.named_scope("qr.small.repair"):
            return (q, g, r, k + 1, perturbed | (amp > 0), *probe(g))

    with jax.named_scope("qr.small.factor"):
        state = (q, g, r, jnp.int32(0), jnp.bool_(False), *probe(g))
    q, g, r, _, _, low, _, _ = jax.lax.while_loop(more, repair, state)
    with jax.named_scope("qr.small.factor"):
        r = jnp.triu(jnp.matmul(low.T, r, precision=_HI))
        w = _upper_inverse(low)
    if not calc_q:
        return None, r
    with jax.named_scope("qr.tall.finish"):
        q, _ = _apply(q, q, w, tall.finish, finish=True)
    return q, r


def _local_qr(a, calc_q: bool = True):
    """Thin QR of one device's ``m x n`` rows, ``m >= n``, real floating, as a
    traceable function: ``(Q or None, R)``. The one-device program
    (``_local_qr_fn``) and level 0 of ``_tsqr_kernel`` both call it; the form
    follows backend, dtype and shape (``_gram_serves``)."""
    if _gram_serves(a.shape[0], a.shape[1], a.dtype):
        return _gram_qr(a, calc_q)
    if calc_q:
        return jnp.linalg.qr(a, mode="reduced")
    return None, jnp.linalg.qr(a, mode="r")


@observed_program_cache("qr.local")
def _local_qr_fn(m: int, n: int, jdtype: str, calc_q: bool):
    """The one-device (or replicated) call as one jitted program."""
    return jax.jit(lambda a: _local_qr(a, calc_q))


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
        # a: local shard (lrows, cols); a shard wider than tall keeps XLA's QR
        if a.shape[0] >= a.shape[1] and not jnp.iscomplexobj(a):
            q1, r1 = _local_qr(a, calc_q)
        else:
            q1, r1 = jnp.linalg.qr(a, mode="reduced")
        k = r1.shape[0]
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
    with _call_span("ht.call.qr"):
        with _span("ht.call.qr.prepare"):
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
            # one device's own rows (or every device's copy of them): the local factorization
            local = (
                not use_tsqr
                and (a.split is None or not comm.is_distributed())
                and m >= n >= 1
                and not jnp.issubdtype(jt, jnp.complexfloating)
                and not a._is_planar
            )
            arr = a._phys.astype(jt) if use_tsqr else a.larray.astype(jt)

        if use_tsqr:
            lrows = arr.shape[0] // comm.size
            topo_t = comm.topology
            fn = _tsqr_fn(
                comm.mesh, comm.axis_name, lrows, n, np.dtype(jt).name, calc_q,
                ring=_tsqr_ring_active(),
                topo=(topo_t.n_slices, topo_t.chips_per_slice) if topo_t.tiered else None,
            )
            _count_form(lrows, n, jt)
            if calc_q:
                q_phys, r = fn(arr)
                # restore the zero-pad invariant on Q (see module docstring)
                q_phys = _padding.mask_phys(q_phys, (m, q_phys.shape[1]), 0)
                k = int(q_phys.shape[1])
                q_arr = DNDarray(q_phys, (m, k), dtype, 0, a.device, comm)
            else:
                r = fn(arr)
                q_arr = None
            r_arr = DNDarray(
                _place(r, comm.sharding(2, None)), tuple(int(s) for s in r.shape), dtype, None, a.device, comm
            )
            return QR(q_arr, r_arr)

        if local:
            _count_form(m, n, jt)
            q, r = _local_qr_fn(m, n, np.dtype(jt).name, calc_q)(arr)
        elif calc_q:
            # split=1, wide or complex: XLA QR on the logical global array (GSPMD
            # partitions the panel updates; the reference's split=1 loop at
            # qr.py:858 broadcasts panels rank-by-rank instead)
            q, r = jnp.linalg.qr(arr, mode="reduced")
        else:
            q, r = None, jnp.linalg.qr(arr, mode="r")
        with _span("ht.call.qr.wrap"):
            r_split = 1 if a.split == 1 else None
            r_arr = DNDarray(
                comm.shard(r, r_split) if r_split is not None else r,
                tuple(int(s) for s in r.shape), dtype, r_split, a.device, comm,
            )
            if q is None:
                return QR(None, r_arr)
            q_arr = DNDarray(
                comm.shard(q, a.split) if a.split is not None else q,
                tuple(int(s) for s in q.shape), dtype, a.split, a.device, comm,
            )
            return QR(q_arr, r_arr)


def _count_form(rows: int, n: int, jt) -> None:
    """Which form of the local factorization this call's program has (what a
    repair did is on the device, and in the trace)."""
    if rows >= n:
        gram = _gram_serves(rows, n, jt)
        _telemetry.inc("qr.local.gram" if gram else "qr.local.householder")
        if gram:
            _telemetry.inc("qr.tall.kernel" if _panels_serve(rows, n, jt) else "qr.tall.xla")


DNDarray.qr = qr

from ..communication import register_mesh_cache
from ..communication import place as _place

# entries bake mesh geometry: cleared when init_distributed rebuilds the world
register_mesh_cache(_tsqr_fn)
