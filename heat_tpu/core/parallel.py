"""Explicit SPMD primitives: halo exchange and ring pipelines.

The reference realizes its stencil and ring-pipeline patterns with
hand-rolled MPI point-to-point schedules:

- halo exchange — ``DNDarray.get_halo`` (reference dndarray.py:386-454)
  Isend/Irecvs boundary slices between prev/next populated ranks; consumed
  by ``signal.convolve`` (signal.py:125-127) and ``statistics.percentile``
  (statistics.py:1615);
- ring pipeline — ``spatial.distance._dist`` (reference distance.py:208-477)
  keeps a stationary block per rank and circulates a moving block rank→rank
  for ``(size+1)//2`` iterations, exploiting symmetry when X ≡ Y. This is
  exactly the ring-attention schedule.

Here both are ONE jitted ``shard_map`` program each, built on
``lax.ppermute`` over the mesh axis — the TPU-native form where the
neighbor exchange rides ICI and XLA overlaps it with local compute. These
primitives operate on *physical* (padded) arrays; callers own the
logical/pad bookkeeping (see ``_padding``).
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map
from jax.lax import pcast

from typing import Callable, Optional, Tuple

from . import types
from ..kernels.sort import block_sort as _local_block_sort, _mode as _sort_kernel_mode
from ..observability.tracing import span as _span

__all__ = ["halo_exchange", "ring_pairwise", "distributed_sort", "distributed_topk"]


# ---------------------------------------------------------------------- #
# distributed top-k                                                      #
# ---------------------------------------------------------------------- #
@functools.lru_cache(maxsize=64)
def _topk_program(mesh: Mesh, axis_name: str, ndim: int, split: int, k: int, largest: bool, idx_dtype: str):
    """shard_map top-k along the sharded axis: each shard reduces its
    block to its local k candidates (with GLOBAL positions), the tiny
    (p·k) candidate set is all-gathered over ICI, and the final top-k
    runs replicated — the reference's iterative rank-merge
    (manipulations.py:3981) without moving anything but candidates."""
    p = mesh.devices.size
    spec = P(*(axis_name if i == split else None for i in range(ndim)))
    out_spec = P(*(None for _ in range(ndim)))
    idt = jnp.dtype(idx_dtype)

    def body(x):
        r = lax.axis_index(axis_name)
        moved = jnp.moveaxis(x, split, -1)
        B = moved.shape[-1]
        kk = min(k, B)
        work = moved if largest else -moved
        lv, li = lax.top_k(work, kk)
        gi = li.astype(idt) + r.astype(idt) * jnp.asarray(B, idt)
        # candidate sets are tiny: gather them everywhere
        cv = lax.all_gather(lv, axis_name, axis=0)   # (p, ..., kk)
        ci = lax.all_gather(gi, axis_name, axis=0)
        cv = jnp.moveaxis(cv, 0, -2).reshape(moved.shape[:-1] + (p * kk,))
        ci = jnp.moveaxis(ci, 0, -2).reshape(moved.shape[:-1] + (p * kk,))
        fv, fsel = lax.top_k(cv, k)
        fi = jnp.take_along_axis(ci, fsel, axis=-1)
        if not largest:
            fv = -fv
        return jnp.moveaxis(fv, -1, split), jnp.moveaxis(fi, -1, split)

    fn = shard_map(body, mesh=mesh, in_specs=(spec,), out_specs=(out_spec, out_spec), check_vma=False)
    return jax.jit(fn)


def distributed_topk(
    phys: jax.Array,
    mesh: Mesh,
    axis_name: str,
    split: int,
    k: int,
    largest: bool = True,
):
    """Gather-free top-k along the sharded axis of a physical array.
    Caller pre-fills pad rows with the appropriate sentinel (∓inf /
    type-min/max). Returns replicated (values, global positions)."""
    idx_dtype = "int32" if phys.shape[split] < 2**31 else "int64"
    prog = _topk_program(mesh, axis_name, phys.ndim, split, int(k), bool(largest), idx_dtype)
    return prog(phys)


# ---------------------------------------------------------------------- #
# halo exchange                                                          #
# ---------------------------------------------------------------------- #
@functools.lru_cache(maxsize=256)
def _halo_program(mesh: Mesh, axis_name: str, ndim: int, split: int, halo_prev: int, halo_next: int):
    """shard_map program attaching prev/next halos to every shard along
    ``split``. Boundary shards receive zero halos (``ppermute`` zero-fills
    pairs with no source — the analog of the reference's "no neighbor"
    case)."""
    p = mesh.devices.size
    spec = P(*(axis_name if i == split else None for i in range(ndim)))

    def body(x):
        parts = []
        if halo_prev > 0:
            # each shard's trailing rows travel to its next neighbor, i.e.
            # shard r receives the tail of shard r-1 as its prev-halo
            tail = lax.slice_in_dim(x, x.shape[split] - halo_prev, x.shape[split], axis=split)
            parts.append(lax.ppermute(tail, axis_name, [(i, i + 1) for i in range(p - 1)]))
        parts.append(x)
        if halo_next > 0:
            head = lax.slice_in_dim(x, 0, halo_next, axis=split)
            parts.append(lax.ppermute(head, axis_name, [(i + 1, i) for i in range(p - 1)]))
        return jnp.concatenate(parts, axis=split) if len(parts) > 1 else parts[0]

    fn = shard_map(body, mesh=mesh, in_specs=(spec,), out_specs=spec)
    return jax.jit(fn)


def halo_exchange(
    phys: jax.Array,
    mesh: Mesh,
    axis_name: str,
    split: int,
    halo_prev: int,
    halo_next: int,
) -> jax.Array:
    """Attach halos of ``halo_prev``/``halo_next`` rows along ``split`` to
    every shard of the physical array ``phys`` (block size B → B+hp+hn).

    Returns a physical array sharded the same way whose per-device block is
    ``[prev-halo | local block | next-halo]``; outermost halos are zero.
    The halo sizes must not exceed the block size (the reference raises the
    same way when ``halo_size`` exceeds the smallest chunk,
    dndarray.py:386-454).
    """
    p = mesh.devices.size
    block = phys.shape[split] // p
    if max(halo_prev, halo_next) > block:
        raise ValueError(
            f"halo size ({halo_prev}/{halo_next}) exceeds the shard block size ({block})"
        )
    if halo_prev == 0 and halo_next == 0:
        return phys
    return _halo_program(mesh, axis_name, phys.ndim, split, int(halo_prev), int(halo_next))(phys)


# ---------------------------------------------------------------------- #
# ring pipeline                                                          #
# ---------------------------------------------------------------------- #
@functools.lru_cache(maxsize=64)
def _ring_program(
    mesh: Mesh,
    axis_name: str,
    metric_key: str,
    x_shape: Tuple[int, ...],
    y_shape: Tuple[int, ...],
    jdtype: str,
    steps: int,
):
    """shard_map ring: stationary local X block, moving Y block circulated
    ``steps`` times with ``ppermute`` (reference distance.py:262-359). The
    result column block written at step t is the one Y block originated at
    device (r + t) mod p."""
    p = mesh.devices.size
    metric = _METRICS[metric_key]
    by = y_shape[0] // p

    def body(x_loc, y_loc):
        r = lax.axis_index(axis_name)
        # the scan carry is updated with device-varying blocks each step, so
        # its initial value must be marked varying over the mesh axis
        out = pcast(jnp.zeros((x_loc.shape[0], p * by), dtype=jdtype), axis_name, to="varying")

        def step(carry, t):
            y_cur, acc = carry
            blk = metric(x_loc, y_cur).astype(jdtype)  # (bx, by) — MXU matmul inside
            src = (r + t) % p
            acc = lax.dynamic_update_slice(acc, blk, (0, src * by))
            # rotate: device i receives the block currently on device i+1
            y_nxt = lax.ppermute(y_cur, axis_name, [((i + 1) % p, i) for i in range(p)])
            return (y_nxt, acc), None

        (_, out), _ = lax.scan(step, (y_loc, out), jnp.arange(steps))
        return out

    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis_name, None), P(axis_name, None)),
        out_specs=P(axis_name, None),
    )
    return jax.jit(fn)


def _euclidean(x, y):
    # quadratic-expansion form: the inner product rides the MXU
    x2 = jnp.sum(x * x, axis=1, keepdims=True)
    y2 = jnp.sum(y * y, axis=1, keepdims=True).T
    return jnp.sqrt(jnp.maximum(x2 + y2 - 2.0 * (x @ y.T), 0.0))

def _sqeuclidean(x, y):
    x2 = jnp.sum(x * x, axis=1, keepdims=True)
    y2 = jnp.sum(y * y, axis=1, keepdims=True).T
    return jnp.maximum(x2 + y2 - 2.0 * (x @ y.T), 0.0)

def _euclidean_direct(x, y):
    d = x[:, None, :] - y[None, :, :]
    return jnp.sqrt(jnp.sum(d * d, axis=-1))

def _sqeuclidean_direct(x, y):
    d = x[:, None, :] - y[None, :, :]
    return jnp.sum(d * d, axis=-1)

def _manhattan(x, y):
    return jnp.sum(jnp.abs(x[:, None, :] - y[None, :, :]), axis=-1)


_METRICS = {
    "euclidean": _euclidean,
    "sqeuclidean": _sqeuclidean,
    "euclidean_direct": _euclidean_direct,
    "sqeuclidean_direct": _sqeuclidean_direct,
    "manhattan": _manhattan,
}


# ---------------------------------------------------------------------- #
# distributed sort                                                       #
# ---------------------------------------------------------------------- #
@functools.lru_cache(maxsize=64)
def _oddeven_sort_values_program(mesh: Mesh, axis_name: str, ndim: int, split: int, sort_impl: str = "0"):
    """Values-only variant of the odd-even sort: no index operand rides the
    ``ppermute``s, halving per-round collective volume (the hot
    percentile/median path needs only sorted values). Tie consistency
    between partners comes from concatenating in GLOBAL RANK ORDER on both
    sides (lower-ranked partner's block first) + a stable sort — both
    partners then order the identical sequence identically."""
    p = mesh.devices.size
    spec = P(*(axis_name if i == split else None for i in range(ndim)))

    def body(v):
        r = lax.axis_index(axis_name)
        B = v.shape[split]
        (v,) = _local_block_sort((v,), dimension=split, num_keys=1, is_stable=True, impl=sort_impl)
        for t in range(p):
            start = t % 2
            pairs = [(a, a + 1) for a in range(start, p - 1, 2)]
            if not pairs:
                continue
            perm = [(a, b) for a, b in pairs] + [(b, a) for a, b in pairs]
            pv = lax.ppermute(v, axis_name, perm)
            last = pairs[-1][1]
            in_pair = (r >= start) & (r <= last)
            is_low = in_pair & (((r - start) % 2) == 0)
            a_blk = jnp.where(is_low, v, pv)
            b_blk = jnp.where(is_low, pv, v)
            (mv,) = _local_block_sort(
                (jnp.concatenate([a_blk, b_blk], axis=split),),
                dimension=split,
                num_keys=1,
                is_stable=True,
                impl=sort_impl,
            )
            lo = lax.slice_in_dim(mv, 0, B, axis=split)
            hi = lax.slice_in_dim(mv, B, 2 * B, axis=split)
            v = jnp.where(in_pair, jnp.where(is_low, lo, hi), v)
        return v

    fn = shard_map(body, mesh=mesh, in_specs=(spec,), out_specs=spec, check_vma=False)
    return jax.jit(fn)


@functools.lru_cache(maxsize=64)
def _oddeven_sort_program(mesh: Mesh, axis_name: str, ndim: int, split: int, idx_dtype: str, sort_impl: str = "0"):
    """shard_map odd-even block merge-split sort along ``split``.

    The reference's distributed sort (manipulations.py:2428) is a
    sample-sort: local sort, splitter election, Alltoallv partition
    exchange. Alltoallv's variable counts are the wrong shape for XLA —
    bucket sizes are data-dependent. The TPU-native formulation is the
    odd-even block merge-split network (Baudet–Stevenson): after one local
    sort, ``p`` rounds of a STATIC neighbor pattern where paired shards
    exchange blocks over ICI (``ppermute``), jointly sort the 2B rows, and
    keep the low/high half. Every shape is static, every round compiles to
    one collective-permute + one fused local sort, and the network is
    provably sorted after ``p`` rounds for any input.

    Ties are broken by the global position index carried as a second sort
    key, so both partners compute the *same* total order of their union —
    without this, equal keys could be duplicated or dropped at the block
    boundary (the two partners concatenate in different orders).

    Returns (values, indices): indices are the pre-sort global positions
    along ``split`` (argsort semantics). Other dims are batch lanes.
    """
    p = mesh.devices.size
    spec = P(*(axis_name if i == split else None for i in range(ndim)))
    idt = jnp.dtype(idx_dtype)

    def body(v):
        r = lax.axis_index(axis_name)
        B = v.shape[split]
        # global position of every local row along the split axis
        i = lax.broadcasted_iota(idt, v.shape, split) + r.astype(idt) * jnp.asarray(B, idt)
        v, i = _local_block_sort((v, i), dimension=split, num_keys=2, is_stable=False, impl=sort_impl)
        for t in range(p):
            start = t % 2
            pairs = [(a, a + 1) for a in range(start, p - 1, 2)]
            if not pairs:
                continue
            perm = [(a, b) for a, b in pairs] + [(b, a) for a, b in pairs]
            pv = lax.ppermute(v, axis_name, perm)
            pi = lax.ppermute(i, axis_name, perm)
            mv, mi = _local_block_sort(
                (jnp.concatenate([v, pv], axis=split), jnp.concatenate([i, pi], axis=split)),
                dimension=split,
                num_keys=2,
                is_stable=False,
                impl=sort_impl,
            )
            lo_v = lax.slice_in_dim(mv, 0, B, axis=split)
            hi_v = lax.slice_in_dim(mv, B, 2 * B, axis=split)
            lo_i = lax.slice_in_dim(mi, 0, B, axis=split)
            hi_i = lax.slice_in_dim(mi, B, 2 * B, axis=split)
            last = pairs[-1][1]
            in_pair = (r >= start) & (r <= last)
            is_low = in_pair & (((r - start) % 2) == 0)
            v = jnp.where(in_pair, jnp.where(is_low, lo_v, hi_v), v)
            i = jnp.where(in_pair, jnp.where(is_low, lo_i, hi_i), i)
        return v, i

    # check_vma=False: the local block sort may be a pallas_call, whose
    # outputs carry no varying-mesh-axes annotation
    fn = shard_map(body, mesh=mesh, in_specs=(spec,), out_specs=(spec, spec), check_vma=False)
    return jax.jit(fn)


@functools.lru_cache(maxsize=64)
def _columnsort_program(mesh: Mesh, axis_name: str, ndim: int, split: int, idx_dtype: Optional[str], sort_impl: str = "0"):
    """Leighton columnsort along ``split``: the O(1)-collective-round
    distributed sort (VERDICT r4 #2 — replaces the O(p)-round odd-even
    schedule at scale).

    The reference's sample sort (manipulations.py:2428) does local sort →
    splitter election → ONE Alltoallv. Alltoallv's variable counts are
    data-dependent shapes XLA cannot compile, and sample-sort bucket sizes
    are adversarially unbounded (sorted input sends a whole shard to one
    bucket). Columnsort keeps the one-shot-exchange structure with fully
    STATIC shapes and a determinism guarantee no splitter scheme has:

      1. sort each shard                     (local)
      2. "deal" rows round-robin to shards   (one tiled ``all_to_all``)
      3. sort each shard                     (local)
      4. inverse deal                        (one tiled ``all_to_all``)
      5. sort each shard                     (local)
      6-8. boundary cleanup: each shard jointly sorts the half-shard
           windows it shares with its ring neighbors (two half-shard
           ``ppermute``s + two local sorts, replacing the shift/unshift
           columns of the textbook form; ring ends keep their already-
           sorted halves, so no ±inf fill columns are materialized)

    Total: 2 all-to-alls + 2 half-shard permutes ≈ 3 shard-volumes of ICI
    bytes and 4 collective rounds, independent of p — vs the odd-even
    network's p rounds × p shard-volumes. Provably sorted for ANY input
    when B ≥ 2(p-1)² and p | B (Leighton '85); ``distributed_sort`` gates
    on exactly that and keeps odd-even as the small-shard fallback.

    Ties: the global pre-sort position rides as a second lexicographic
    sort key (``num_keys=2``), making every element distinct — the same
    total order the odd-even program uses, and the argsort contract.
    """
    p = mesh.devices.size
    spec = P(*(axis_name if i == split else None for i in range(ndim)))
    idt = jnp.dtype(idx_dtype) if idx_dtype is not None else None
    nk = 2 if idt is not None else 1

    def body(v):
        rk = lax.axis_index(axis_name)
        a = jnp.moveaxis(v, split, 0)
        B = a.shape[0]
        arrs = [a]
        if idt is not None:
            gi = lax.broadcasted_iota(idt, a.shape, 0) + rk.astype(idt) * jnp.asarray(B, idt)
            arrs.append(gi)

        def srt(ts):
            return list(_local_block_sort(tuple(ts), dimension=0, num_keys=nk, is_stable=True, impl=sort_impl))

        def deal(ts):
            out = []
            for t in ts:
                m = t.reshape((B // p, p) + t.shape[1:])
                m = jnp.moveaxis(m, 1, 0).reshape((B,) + t.shape[1:])
                out.append(lax.all_to_all(m, axis_name, 0, 0, tiled=True))
            return out

        def undeal(ts):
            out = []
            for t in ts:
                y = lax.all_to_all(t, axis_name, 0, 0, tiled=True)
                y = y.reshape((p, B // p) + t.shape[1:])
                out.append(jnp.moveaxis(y, 0, 1).reshape((B,) + t.shape[1:]))
            return out

        arrs = srt(arrs)                    # 1: local sort
        arrs = srt(deal(arrs))              # 2-3: deal + sort
        arrs = srt(undeal(arrs))            # 4-5: undeal + sort
        # 6-8: each shard owns final rows [r·B, (r+1)·B); the half-shard
        # window shared with each neighbor is jointly re-sorted on both
        # sides (identical input → identical order, no send-back hop)
        h = B // 2
        fwd = [(i, i + 1) for i in range(p - 1)]
        bwd = [(i + 1, i) for i in range(p - 1)]
        tops = [lax.slice_in_dim(t, 0, B - h, axis=0) for t in arrs]
        bots = [lax.slice_in_dim(t, B - h, B, axis=0) for t in arrs]
        recv_prev = [lax.ppermute(t, axis_name, fwd) for t in bots]
        recv_next = [lax.ppermute(t, axis_name, bwd) for t in tops]
        sc_own = srt([jnp.concatenate([rp, tp], axis=0) for rp, tp in zip(recv_prev, tops)])
        sc_next = srt([jnp.concatenate([bt, rn], axis=0) for bt, rn in zip(bots, recv_next)])
        first, last = rk == 0, rk == p - 1
        new = []
        for top, bot, so, sn in zip(tops, bots, sc_own, sc_next):
            # ring ends: ppermute zero-fills the missing neighbor, so keep
            # the already-sorted boundary halves verbatim instead
            up = jnp.where(first, top, lax.slice_in_dim(so, h, B, axis=0))
            dn = jnp.where(last, bot, lax.slice_in_dim(sn, 0, h, axis=0))
            new.append(jnp.concatenate([up, dn], axis=0))
        res = tuple(jnp.moveaxis(t, 0, split) for t in new)
        return res[0] if idt is None else res

    out_specs = spec if idt is None else (spec, spec)
    fn = shard_map(body, mesh=mesh, in_specs=(spec,), out_specs=out_specs, check_vma=False)
    return jax.jit(fn)


def _columnsort_applicable(p: int, B: int) -> bool:
    """Leighton's validity bound (B ≥ 2(p-1)², p | B) plus profitability:
    at p ≤ 2 the odd-even network is already ≤ 2 rounds."""
    return p > 2 and B % p == 0 and B >= 2 * (p - 1) ** 2


def distributed_sort(
    phys: jax.Array,
    mesh: Mesh,
    axis_name: str,
    split: int,
    with_indices: bool = True,
):
    """Ascending sort of the physical array ``phys`` along its sharded
    axis ``split`` without gathering — the explicit-SPMD replacement for
    the reference's sample-sort + Alltoallv (manipulations.py:2428).

    Large shards (B ≥ 2(p-1)², p | B) take the columnsort program — the
    one-shot-exchange structure of the reference's sample sort with O(1)
    collective rounds and ~3 shard-volumes of ICI bytes, but fully static
    shapes; anything smaller falls back to the odd-even block merge-split
    network (p rounds, provably sorted at any shape).

    The caller owns pad semantics: pad rows must already hold a
    maximal sentinel (NaN for floats, type-max for ints) so they sink to
    the global tail — the canonical pad location. Returns physical
    (values, indices), indices being pre-sort global positions (pads get
    positions ≥ the logical extent, so callers can re-zero them); with
    ``with_indices=False``, returns only values via a program whose
    collectives carry half the volume.
    """
    p = mesh.devices.size
    B = -(-phys.shape[split] // p)  # physical rows per shard
    if _columnsort_applicable(p, B):
        idx_dtype = None if not with_indices else (
            "int32" if phys.shape[split] < 2**31 else "int64"
        )
        prog = _columnsort_program(
            mesh, axis_name, phys.ndim, split, idx_dtype, _sort_kernel_mode()
        )
        return prog(phys)
    if not with_indices:
        return _oddeven_sort_values_program(
            mesh, axis_name, phys.ndim, split, _sort_kernel_mode()
        )(phys)
    idx_dtype = "int32" if phys.shape[split] < 2**31 else "int64"
    prog = _oddeven_sort_program(
        mesh, axis_name, phys.ndim, split, idx_dtype, _sort_kernel_mode()
    )
    return prog(phys)


def ring_pairwise(
    x_phys: jax.Array,
    y_phys: jax.Array,
    mesh: Mesh,
    axis_name: str,
    metric: str = "euclidean",
    symmetric: bool = False,
) -> jax.Array:
    """All-pairs ``metric`` between row blocks of ``x_phys`` and
    ``y_phys`` (both physical, split along axis 0) via an explicit
    ``ppermute`` ring. Output is physical, split along axis 0, with the
    column extent equal to ``y_phys``'s padded row extent.

    ``symmetric=True`` (valid only for X ≡ Y with a symmetric metric) runs
    ``p//2 + 1`` ring steps instead of ``p`` and fills the uncomputed
    blocks from the transpose — the reference's symmetry-skipping of half
    the ring (distance.py:300-359). The transposed fill is a logical-level
    ``where`` whose cross-shard movement XLA lowers to an all-to-all.
    """
    if metric not in _METRICS:
        raise ValueError(f"unknown metric {metric!r}; options: {sorted(_METRICS)}")
    p = mesh.devices.size
    steps = (p // 2 + 1) if (symmetric and p > 1) else p
    prog = _ring_program(
        mesh,
        axis_name,
        metric,
        tuple(x_phys.shape),
        tuple(y_phys.shape),
        np.dtype(jnp.result_type(x_phys.dtype, y_phys.dtype)).name,
        steps,
    )
    out = prog(x_phys, y_phys)
    if steps < p:
        # block (r, c) was computed iff (c - r) mod p < steps; the rest is
        # D[c, r].T by symmetry
        bx = x_phys.shape[0] // p
        by = y_phys.shape[0] // p
        row_blk = jax.lax.broadcasted_iota(jnp.int32, out.shape, 0) // bx
        col_blk = jax.lax.broadcasted_iota(jnp.int32, out.shape, 1) // by
        computed = ((col_blk - row_blk) % p) < steps
        out = jnp.where(computed, out, out.T)
    return out

# ---------------------------------------------------------------------- #
# distributed stream compaction (bool-mask select / nonzero / unique)    #
# ---------------------------------------------------------------------- #
# The reference serves data-dependent-shape ops with rank-local results
# (nonzero: indexing.py local nonzero + split-offset; unique:
# manipulations.py:3202 local unique + allgather of the small sets; mask
# getitem: dndarray.py:827 rank-local selection). Uneven rank-local
# shapes don't exist under GSPMD's even-block invariant, so the TPU-native
# schedule is: (1) a per-shard count+compact program (static shapes,
# candidates padded to the shard extent), (2) ONE tiny host read of the
# per-shard counts — the same world-sync the reference's Allgather of
# local sizes performs, (3) a balanced-redistribution program that
# all-gathers only the C = max-count candidate PREFIXES (bounded by the
# output size, never the input) and assembles even split=0 blocks. No
# full all-gather of the operand ever appears in the HLO.


# per-device budget for the balanced gather's (p, cap, ...) intermediate;
# beyond it the gather runs in bounded rounds (tests shrink this to force
# the chunked path on small inputs)
_GATHER_BUDGET_BYTES = 64 << 20


def _host_counts(counts: jax.Array) -> np.ndarray:
    """Read the tiny per-shard count vector to the host — the one world
    sync these schedules need (the analog of the reference's size
    Allgather). Cross-process worlds cannot ``device_get`` a globally
    sharded array; the allgather of a (p,) int vector is negligible."""
    with _span("ht.sync.read", what="unique.counts"):
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            return np.asarray(multihost_utils.process_allgather(counts, tiled=True))
        return np.asarray(jax.device_get(counts))


@functools.lru_cache(maxsize=64)
def _mask_compact_program(
    mesh: Mesh, axis_name: str, blk_shape, rows: bool, jdtype: str
):
    """Per-shard count + fixed-capacity compaction. ``blk_shape`` is the
    local block; ``rows=True`` selects axis-0 rows by a 1-D mask block,
    else flattened elements by a same-shape mask block. The mask must
    already be False in pad slots. Outputs: candidates padded to the
    block extent (selected entries front-packed, garbage beyond the
    count) and the per-shard count."""
    L = blk_shape[0] if rows else int(np.prod(blk_shape))
    spec_x = P(*(axis_name if i == 0 else None for i in range(len(blk_shape))))
    spec_m = P(axis_name) if rows else spec_x
    out_trailing = blk_shape[1:] if rows else ()
    spec_c = P(*((axis_name,) + (None,) * len(out_trailing)))

    def body(x_blk, m_blk):
        if rows:
            flat_m = m_blk
            data = x_blk
        else:
            flat_m = m_blk.reshape(-1)
            data = x_blk.reshape(-1)
        c = jnp.sum(flat_m.astype(jnp.int32))
        idx = jnp.nonzero(flat_m, size=L, fill_value=L)[0]
        pad_row = jnp.zeros((1,) + data.shape[1:], dtype=data.dtype)
        cand = jnp.concatenate([data, pad_row])[idx]
        return cand, c.reshape(1)

    fn = shard_map(
        body, mesh=mesh, in_specs=(spec_x, spec_m),
        out_specs=(spec_c, P(axis_name)), check_vma=False,
    )
    return jax.jit(fn)


@functools.lru_cache(maxsize=64)
def _balanced_gather_program(
    mesh: Mesh, axis_name: str, cand_blk_shape, cap: int, b_out: int, jdtype: str,
    chunk: int,
):
    """Assemble even split=0 blocks of the compacted stream: all-gather
    the first ``cap`` candidates of every shard (cap = max per-shard
    count ≤ output size) plus the count vector, compute exclusive
    prefixes, and let each output shard take its ``b_out`` rows. The
    total count arrives as a RUNTIME scalar — only (cap, b_out, chunk)
    shape the program, so the p distinct totals per block size share one
    compilation.

    ``chunk=0`` gathers all ``cap`` candidate rows at once — peak
    per-device memory (p, cap, ...), fine for sparse selections. For
    DENSE selections (cap approaching the local block extent) that
    buffer is ~the whole operand replicated per device, so
    ``_compact_gather`` switches to ``chunk>0``: the gather runs in
    ``ceil(cap/chunk)`` rounds of (p, chunk, ...) — same total ICI
    bytes, bounded live memory."""
    trailing = cand_blk_shape[1:]
    spec_c = P(*((axis_name,) + (None,) * len(trailing)))

    def prefix_index(cnt_blk):
        counts = lax.all_gather(cnt_blk, axis_name).reshape(-1)   # (p,)
        cum = jnp.cumsum(counts)
        r = lax.axis_index(axis_name)
        g = r * b_out + jax.lax.broadcasted_iota(jnp.int32, (b_out,), 0)
        q = jnp.searchsorted(cum, g, side="right").astype(jnp.int32)
        qc = jnp.minimum(q, counts.shape[0] - 1)
        li = g - (cum[qc] - counts[qc])
        return g, qc, li

    if chunk <= 0 or chunk >= cap:
        def body(cand_blk, cnt_blk, n_total):
            g, qc, li = prefix_index(cnt_blk)
            allc = lax.all_gather(cand_blk[:cap], axis_name)      # (p, cap, ...)
            flat = allc.reshape((-1,) + trailing)
            rows_out = flat[jnp.clip(qc * cap + li, 0, flat.shape[0] - 1)]
            keep = (g < n_total).reshape((-1,) + (1,) * len(trailing))
            return jnp.where(keep, rows_out, jnp.zeros_like(rows_out))
    else:
        rounds = -(-cap // chunk)

        def body(cand_blk, cnt_blk, n_total):
            g, qc, li = prefix_index(cnt_blk)
            padded = cand_blk[:cap]
            if rounds * chunk > cap:
                pad = jnp.zeros((rounds * chunk - cap,) + trailing, dtype=padded.dtype)
                padded = jnp.concatenate([padded, pad])
            out0 = jnp.zeros((b_out,) + trailing, dtype=cand_blk.dtype)

            def round_body(i, out):
                c0 = i * chunk
                blkc = lax.dynamic_slice_in_dim(padded, c0, chunk, axis=0)
                allc = lax.all_gather(blkc, axis_name)            # (p, chunk, ...)
                flat = allc.reshape((-1,) + trailing)
                lin = li - c0
                sel = (lin >= 0) & (lin < chunk)
                rows = flat[
                    jnp.clip(qc * chunk + jnp.clip(lin, 0, chunk - 1), 0, flat.shape[0] - 1)
                ]
                selb = sel.reshape((-1,) + (1,) * len(trailing))
                return jnp.where(selb, rows, out)

            out = lax.fori_loop(0, rounds, round_body, out0)
            keep = (g < n_total).reshape((-1,) + (1,) * len(trailing))
            return jnp.where(keep, out, jnp.zeros_like(out))

    fn = shard_map(
        body, mesh=mesh, in_specs=(spec_c, P(axis_name), P()), out_specs=spec_c,
        check_vma=False,
    )
    return jax.jit(fn)


def _compact_gather(cand, counts, mesh, axis_name, empty_trailing):
    """Shared postlude of the compaction schedules: read the tiny count
    vector (the one host sync), size the capacity/output block, and run
    the balanced gather. Returns ``(result_phys, n_total)``."""
    p = mesh.devices.size
    counts_host = _host_counts(counts)
    n_total = int(counts_host.sum())
    if n_total == 0:
        return jnp.zeros((0,) + tuple(empty_trailing), dtype=cand.dtype), 0
    cap = int(counts_host.max())
    b_out = -(-n_total // p)
    # bound the gathered intermediate: one-shot all-gather is (p, cap, ...)
    # per device — for dense selections that is ~the whole operand
    # replicated. Above the budget, run the gather in rounds of
    # (p, chunk, ...) instead (same ICI bytes, bounded live memory).
    row_bytes = max(int(np.prod(cand.shape[1:])), 1) * cand.dtype.itemsize
    chunk = 0
    if p * cap * row_bytes > _GATHER_BUDGET_BYTES:
        chunk = max(_GATHER_BUDGET_BYTES // (p * row_bytes), 1)
    gather = _balanced_gather_program(
        mesh, axis_name,
        tuple(s // p if i == 0 else s for i, s in enumerate(cand.shape)),
        cap, b_out, np.dtype(cand.dtype).name, chunk,
    )
    return gather(cand, counts, jnp.int32(n_total)), n_total


def compact_select(
    data_phys: jax.Array,
    mask_phys: jax.Array,
    mesh: Mesh,
    axis_name: str,
    rows: bool,
):
    """Gather-free selection of masked elements (or axis-0 rows) from a
    split=0 physical array into an even split=0 physical result.

    Returns ``(result_phys, n_selected)`` — the count read-back is the
    one small host sync (the analog of the reference's size Allgather).
    """
    p = mesh.devices.size
    prog = _mask_compact_program(
        mesh, axis_name,
        tuple(s // p if i == 0 else s for i, s in enumerate(data_phys.shape)),
        rows, np.dtype(data_phys.dtype).name,
    )
    cand, counts = prog(data_phys, mask_phys)
    return _compact_gather(
        cand, counts, mesh, axis_name,
        tuple(data_phys.shape[1:]) if rows else (),
    )


@functools.lru_cache(maxsize=64)
def _nonzero_compact_program(mesh: Mesh, axis_name: str, blk_shape, n_split: int, jdtype: str):
    """Per-shard nonzero: count + front-packed GLOBAL coordinates
    (reference indexing.py nonzero returns rank-local results shifted by
    the split offset — same coordinates, even blocks here)."""
    L = int(np.prod(blk_shape))
    b0 = blk_shape[0]
    ndim = len(blk_shape)
    spec = P(*(axis_name if i == 0 else None for i in range(ndim)))

    def body(x_blk):
        r = lax.axis_index(axis_name)
        valid0 = (r * b0 + jax.lax.broadcasted_iota(jnp.int32, (b0,), 0)) < n_split
        m = (x_blk != 0) & jnp.broadcast_to(
            valid0.reshape((b0,) + (1,) * (ndim - 1)), blk_shape
        )
        flat = m.reshape(-1)
        c = jnp.sum(flat.astype(jnp.int32))
        idx = jnp.nonzero(flat, size=L, fill_value=0)[0]
        coords = list(jnp.unravel_index(idx, blk_shape))
        coords[0] = coords[0] + (r * b0).astype(coords[0].dtype)
        cand = jnp.stack(coords, axis=1).astype(types.index_jax_type())  # (L, ndim)
        return cand, c.reshape(1)

    fn = shard_map(
        body, mesh=mesh, in_specs=(spec,),
        out_specs=(P(axis_name, None), P(axis_name)), check_vma=False,
    )
    return jax.jit(fn)


def distributed_nonzero(phys: jax.Array, n_split: int, mesh: Mesh, axis_name: str):
    """Gather-free nonzero of a split=0 physical array → even split=0
    physical (nnz, ndim) int64 coordinates plus the count (one small host
    sync for the per-shard counts)."""
    p = mesh.devices.size
    blk = tuple(s // p if i == 0 else s for i, s in enumerate(phys.shape))
    cand, counts = _nonzero_compact_program(
        mesh, axis_name, blk, n_split, np.dtype(phys.dtype).name
    )(phys)
    return _compact_gather(cand, counts, mesh, axis_name, (phys.ndim,))


def _sorted_dedup(flat, valid):
    """Shared dedup core of the unique schedules: lexicographic
    ``lax.sort`` over (invalid-flag, value) sinks every invalid slot past
    the valid ones, then duplicate-marking compacts the survivors to the
    front. NaNs sort last among valid entries and collapse to ONE (the
    ``differs`` mask treats NaN==NaN as equal), matching ``np.unique``'s
    equal_nan semantics (numpy ≥ 1.21).

    Returns (compacted values — garbage past the count, count)."""
    L = flat.shape[0]
    invalid = (~valid).astype(jnp.int8)
    inv_s, s = lax.sort((invalid, flat), num_keys=2, is_stable=True)
    first = jax.lax.broadcasted_iota(jnp.int32, (L,), 0) == 0
    prev = jnp.concatenate([s[:1], s[:-1]])
    differs = s != prev
    if jnp.issubdtype(s.dtype, jnp.floating):
        differs = differs & ~(jnp.isnan(s) & jnp.isnan(prev))
    keep = (inv_s == 0) & (first | differs)
    c = jnp.sum(keep.astype(jnp.int32))
    idx = jnp.nonzero(keep, size=L, fill_value=L)[0]
    return jnp.concatenate([s, s[:1]])[idx], c


@functools.lru_cache(maxsize=64)
def _local_unique_program(mesh: Mesh, axis_name: str, blk_shape, n_split: int, jdtype: str):
    """Per-shard sorted unique with fixed capacity (see ``_sorted_dedup``
    for the dedup semantics)."""
    b0 = blk_shape[0]
    spec = P(*(axis_name if i == 0 else None for i in range(len(blk_shape))))

    def body(x_blk):
        r = lax.axis_index(axis_name)
        valid0 = (r * b0 + jax.lax.broadcasted_iota(jnp.int32, (b0,), 0)) < n_split
        valid = jnp.broadcast_to(
            valid0.reshape((b0,) + (1,) * (len(blk_shape) - 1)), blk_shape
        ).reshape(-1)
        cand, c = _sorted_dedup(x_blk.reshape(-1), valid)
        return cand, c.reshape(1)

    fn = shard_map(body, mesh=mesh, in_specs=(spec,), out_specs=(P(axis_name), P(axis_name)), check_vma=False)
    return jax.jit(fn)


@functools.lru_cache(maxsize=64)
def _unique_merge_program(mesh: Mesh, axis_name: str, p: int, cap: int, jdtype: str):
    """Merge the per-shard unique candidate prefixes: all-gather the
    (p·cap) candidate set, re-sort with validity keys, deduplicate —
    replicated output (the reference Bcasts its merged set the same way).

    Memory note: the merged unique set is REPLICATED by contract (as in
    the reference), so for inputs whose values are mostly distinct the
    (p·cap) gather is ~the whole operand per device — that is the
    output's own footprint, not avoidable by chunking. ``unique`` is a
    small-alphabet/sparse-result op at scale."""

    def body(cand_blk, cnt_blk):
        allc = lax.all_gather(cand_blk[:cap], axis_name).reshape(-1)   # (p*cap,)
        counts = lax.all_gather(cnt_blk, axis_name).reshape(-1)
        pos = jax.lax.broadcasted_iota(jnp.int32, (p * cap,), 0)
        valid = (pos % cap) < counts[pos // cap]
        return _sorted_dedup(allc, valid)

    fn = shard_map(
        body, mesh=mesh, in_specs=(P(axis_name), P(axis_name)),
        out_specs=(P(), P()), check_vma=False,
    )
    return jax.jit(fn)


def _sorted_dedup_rows(mat, valid):
    """Rows analog of :func:`_sorted_dedup`: lexicographic ``lax.sort``
    over (invalid-flag, col_0, …, col_{R-1}) — column 0 is the primary
    key, invalid rows sink past every valid one — then duplicate-marking
    compacts the surviving FIRST occurrences to the front. ``mat`` is
    the (L, R) SORTABLE-uint bit view of the rows
    (``kernels.sort.to_sortable`` per element), so unsigned comparison
    IS value order and the collapsed tie classes (−0.0 with +0.0, every
    NaN payload) dedupe exactly like the framework's flat unique.

    Returns (compacted rows — garbage past the count, count)."""
    L, R = mat.shape
    invalid = (~valid).astype(jnp.int8)
    sorted_ops = lax.sort(
        (invalid,) + tuple(mat[:, j] for j in range(R)),
        num_keys=R + 1,
        is_stable=True,
    )
    inv_s = sorted_ops[0]
    s = jnp.stack(sorted_ops[1:], axis=1)  # (L, R) rows back together
    first = jax.lax.broadcasted_iota(jnp.int32, (L,), 0) == 0
    prev = jnp.concatenate([s[:1], s[:-1]], axis=0)
    differs = jnp.any(s != prev, axis=1)
    keep = (inv_s == 0) & (first | differs)
    c = jnp.sum(keep.astype(jnp.int32))
    idx = jnp.nonzero(keep, size=L, fill_value=L)[0]
    pad = jnp.zeros((1, R), dtype=s.dtype)
    return jnp.concatenate([s, pad], axis=0)[idx], c


@functools.lru_cache(maxsize=64)
def _local_unique_rows_program(
    mesh: Mesh, axis_name: str, blk_shape, n_split: int, jdtype: str
):
    """Per-shard sorted ROWS-unique with fixed capacity — the axis-mode
    counterpart of ``_local_unique_program`` (ISSUE 11 satellite: the
    gather-free ``unique(axis=)``)."""
    b0 = blk_shape[0]

    def body(x_blk):
        r = lax.axis_index(axis_name)
        valid = (r * b0 + jax.lax.broadcasted_iota(jnp.int32, (b0,), 0)) < n_split
        cand, c = _sorted_dedup_rows(x_blk, valid)
        return cand, c.reshape(1)

    fn = shard_map(
        body, mesh=mesh, in_specs=(P(axis_name, None),),
        out_specs=(P(axis_name, None), P(axis_name)), check_vma=False,
    )
    return jax.jit(fn)


@functools.lru_cache(maxsize=64)
def _unique_rows_merge_program(mesh: Mesh, axis_name: str, p: int, cap: int, jdtype: str):
    """Merge the per-shard unique ROW-candidate prefixes: all-gather the
    (p·cap, R) candidate rows — the candidate set, never the operand —
    re-sort lexicographically with validity keys, deduplicate;
    replicated output like the flat merge."""

    def body(cand_blk, cnt_blk):
        allc = lax.all_gather(cand_blk[:cap], axis_name)     # (p, cap, R)
        allc = allc.reshape(p * cap, cand_blk.shape[1])
        counts = lax.all_gather(cnt_blk, axis_name).reshape(-1)
        pos = jax.lax.broadcasted_iota(jnp.int32, (p * cap,), 0)
        valid = (pos % cap) < counts[pos // cap]
        return _sorted_dedup_rows(allc, valid)

    fn = shard_map(
        body, mesh=mesh, in_specs=(P(axis_name, None), P(axis_name)),
        out_specs=(P(), P()), check_vma=False,
    )
    return jax.jit(fn)


def distributed_unique_rows(
    phys: jax.Array, n_split: int, mesh: Mesh, axis_name: str
):
    """Sorted unique ROWS of a split=0 (n, R) SORTABLE-uint matrix
    without gathering the operand (the sorted-split formulation the
    VERDICT backlog asked for): per-shard lexicographic sorted-unique
    compaction, one tiny count sync, and a merge over only the
    candidate prefixes. The operand itself never crosses the mesh —
    the only all-gathers carry the (p·cap, R) candidate set.

    Returns the merged unique rows (replicated, sliced to the true
    count)."""
    p = mesh.devices.size
    blk = (phys.shape[0] // p, phys.shape[1])
    cand, counts = _local_unique_rows_program(
        mesh, axis_name, blk, n_split, np.dtype(phys.dtype).name
    )(phys)
    counts_host = _host_counts(counts)
    cap = max(int(counts_host.max()), 1)
    merged, total = _unique_rows_merge_program(
        mesh, axis_name, p, cap, np.dtype(phys.dtype).name
    )(cand, counts)
    with _span("ht.sync.read", what="unique.total"):
        n_unique = int(jax.device_get(total))
    return merged[:n_unique]


def distributed_unique(
    phys: jax.Array, n_split: int, mesh: Mesh, axis_name: str
):
    """Sorted unique of a split=0 physical array without gathering the
    operand: local sorted-unique per shard, then a merge over only the
    candidate prefixes (reference manipulations.py:3202's
    local-unique + Allgather + re-unique, with static shapes).

    Returns the merged unique values as a replicated jax array (sliced
    to the true count — one small host sync for the two counts)."""
    p = mesh.devices.size
    blk = tuple(s // p if i == 0 else s for i, s in enumerate(phys.shape))
    cand, counts = _local_unique_program(
        mesh, axis_name, blk, n_split, np.dtype(phys.dtype).name
    )(phys)
    counts_host = _host_counts(counts)
    cap = max(int(counts_host.max()), 1)
    merged, total = _unique_merge_program(
        mesh, axis_name, p, cap, np.dtype(phys.dtype).name
    )(cand, counts)
    with _span("ht.sync.read", what="unique.total"):
        n_unique = int(jax.device_get(total))
    return merged[:n_unique]


__all__ += [
    "compact_select", "distributed_unique", "distributed_unique_rows",
    "distributed_nonzero",
]


from .communication import register_mesh_cache

# entries bake mesh geometry: cleared when init_distributed rebuilds the world
register_mesh_cache(_halo_program)
register_mesh_cache(_topk_program)
register_mesh_cache(_ring_program)
register_mesh_cache(_oddeven_sort_program)
register_mesh_cache(_oddeven_sort_values_program)
register_mesh_cache(_columnsort_program)
register_mesh_cache(_mask_compact_program)
register_mesh_cache(_balanced_gather_program)
register_mesh_cache(_nonzero_compact_program)
register_mesh_cache(_local_unique_program)
register_mesh_cache(_unique_merge_program)
register_mesh_cache(_local_unique_rows_program)
register_mesh_cache(_unique_rows_merge_program)
