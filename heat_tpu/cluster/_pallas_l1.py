"""Pallas TPU kernels of the L1 family (KMedians, KMedoids): the three passes
over f32 ``X`` that one iteration is made of, none of which holds anything
of ``X``'s size besides ``X`` and the label vector.

``X`` is tiled as KMeans' pass tiles it (``_pallas``): the chip keeps a tall
``f32[n, d]`` with ``d < 128`` feature-major, ``x.T`` is a bitcast, a grid
step takes a block ``(d, tn)`` with the rows on the lanes.

    assign   lab = first argmin_c sum_j |x_j - c_cj|      VPU, k x d a row: no matmul form
             counts (int32), sum of the least distances   lane partials
    count    key = order-preserving int32 image of x      3 VPU ops
             thr = thr0[lab] + t * step,  t < T           k selects, then T = _N_THR compares
             out[t, c, j] += #{rows of c: key_j < thr}    one-hot dot on the MXU, exact
    next     out[c, j] = min{key_j > at[lab, j]}          the successor, by cluster

``count`` is one digit of a radix selection (``_kcluster._cluster_medians``
drives it): every per-cluster, per-feature order statistic at once, for a
price that does not grow with ``k`` beyond the ``k`` selects. The counts of a
tile (at most ``tn`` < 2^24) are exact in the f32 accumulator of the dot and
are added up as int32.

Each kernel is one read of ``X`` and is named for its phase:
``kmedians.assign.pass``, ``kmedians.select.pass``. The benchmark's readers
count reads of ``X`` by these names (``docs/API.md``, observability).
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map as _shard_map
from jax.sharding import PartitionSpec as P

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._pallas import _VMEM_LIMIT, _lane_partials, _pick_tn, _round_up, lloyd_pass_serves

_VMEM = pltpu.VMEM
_SMEM = pltpu.SMEM
_I32_MAX = np.iinfo(np.int32).max

__all__ = ["L1Passes", "l1_passes", "l1_passes_serve"]


# bits of the key a counting pass settles: 2**bits - 1 thresholds a pass,
# 32 / bits passes for f32. At 18.75M x 64 on a v5e a pass of 3 thresholds
# reads at the rate of a bare read (6.4 ms: the k selects of a row's own
# threshold and three one-hot dots hide under it), one of 7 takes 9.3 ms and
# one of 15 15.3, one of 1 still 7.2: two bits are the fewest ms a bit
# (3.2; three bits 3.1 with an uneven first pass; builder's chip runs, PR 32)
_RADIX_BITS = 2
_N_THR = 2 ** _RADIX_BITS - 1


def _key_type(dtype):
    """(integer type, bits) of the order-preserving image of a float type."""
    bits = 8 * np.dtype(dtype).itemsize
    return (jnp.int64 if bits == 64 else jnp.int32), bits


def _flip(b, bits: int):
    """Sign-magnitude <-> two's complement, its own inverse: negative floats
    order backwards as integers, so their magnitude bits are flipped."""
    return b ^ ((b >> (bits - 1)) & ((1 << (bits - 1)) - 1))


def _to_key(x: jax.Array) -> jax.Array:
    """Integers with the order of the floats: ``a < b`` as floats iff
    ``key(a) < key(b)`` (``-0.0`` just under ``0.0``, NaNs beyond the
    infinities)."""
    ktype, bits = _key_type(x.dtype)
    raw = jax.lax.bitcast_convert_type(x, jnp.dtype(f"int{bits}"))
    return _flip(raw, bits).astype(ktype)


def _from_key(key: jax.Array, dtype) -> jax.Array:
    _, bits = _key_type(dtype)
    return jax.lax.bitcast_convert_type(_flip(key, bits).astype(jnp.dtype(f"int{bits}")), dtype)


class L1Passes(NamedTuple):
    """The passes an L1 iteration is made of, on whole (global) arrays.
    ``_kcluster`` holds the ``jax.numpy`` form of the same three."""

    assign: Callable      # (arr, centers) -> labels int32 (n,), counts int32 (k,), fun f32 ()
    count_below: Callable  # (arr, labels, thr0 (k, d), step ()) -> int32 (T, k, d)
    next_above: Callable  # (arr, labels, at (k, d)) -> (k, d) keys, the type's max where none


def l1_passes_serve(backend: str, dtype, shape, k: int, split, devices: int = 1) -> bool:
    """The gate, a pure function of what the code sees in its input: where
    KMeans' pass serves (a TPU, f32, ``d`` a multiple of 8 under 128, one
    device or equal split-0 shards: ``_pallas.lloyd_pass_serves``) and
    ``2 <= k <= 32`` (the assignment and the selects are unrolled over
    ``k``; at ``k = 1`` the chip's compiler aborts on the assignment, whose
    least distance is then the bare sublane reduction)."""
    return 2 <= k <= 32 and lloyd_pass_serves(backend, dtype, shape, k, split, devices)


def _grid_kernel(tile, n: int, tn: int, n_acc: int, init):
    """``tile(refs, valid)`` over the grid: the accumulators (the last
    ``n_acc`` refs) set to ``init`` at step 0; ``valid`` is ``None`` on a
    whole tile and the (1, tn) mask of the rows that exist on the last."""
    steps = pl.cdiv(n, tn)
    tail = n - (steps - 1) * tn

    def kernel(*refs):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _init():
            for ref in refs[len(refs) - n_acc:]:
                ref[...] = jnp.full(ref.shape, init, ref.dtype)

        if tail == tn:
            tile(refs, None)
            return

        @pl.when(i < steps - 1)
        def _whole():
            tile(refs, None)

        @pl.when(i == steps - 1)
        def _last():
            # what the last block holds past row n is unspecified
            tile(refs, jax.lax.broadcasted_iota(jnp.int32, (1, tn), 1) < tail)

    return kernel


def _own(lab, table_ref, k: int):
    """``table[:, lab]``: each row's own column of a (d, k8) table, as (d, tn)."""
    sel = table_ref[:, 0:1]
    for c in range(1, k):
        sel = jnp.where(lab == c, table_ref[:, c:c + 1], sel)
    return sel


def _call(kernel, name: str, n: int, tn: int, in_specs, out_shape, out_specs, interpret: bool):
    return pl.pallas_call(
        kernel, grid=(pl.cdiv(n, tn),), in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT),
        name=name, interpret=interpret,
    )


def _vpu_tn(n: int, d: int, k8: int) -> int:
    """Rows a grid step of the two passes that are all VPU (assign, next): a
    quarter of the 2 MiB tile the streaming passes take. Their (d, tn)
    temporaries then stay near the registers: at 18.75M x 64 the assignment
    reads 11.7 ms at 2048 rows, 12.4 at 4096, 16.4 at 8192, 13.8 at 1024
    (the successor 12.6 / 15.8 / 17.2 / 14.5), while a counting pass is
    fastest on the whole tile (7.3 ms at 8192, 8.1 at 4096; builder's chip
    runs, PR 32)."""
    tn = _pick_tn(n, d, k8)
    return tn if n <= 1024 else max(1024, tn // 4096 * 1024)


def _table(values, k8: int, dtype):
    """A (k, d) table as the kernels read it: (d, k8), a cluster a column."""
    return jnp.pad(values.astype(dtype), ((0, k8 - values.shape[0]), (0, 0))).T


def _const(shape):
    return pl.BlockSpec(shape, lambda i: (0,) * len(shape), memory_space=_VMEM)


@functools.lru_cache(maxsize=64)
def _assign_program(n: int, d: int, k: int, interpret: bool):
    k8, tn = _round_up(k, 8), _vpu_tn(n, d, _round_up(k, 8))

    def tile(refs, valid):
        xt_ref, ct_ref, lab_ref, cnt_ref, fun_ref = refs
        x = xt_ref[...]  # (d, tn) f32
        to = lambda c: jnp.sum(jnp.abs(x - ct_ref[:, c:c + 1]), axis=0, keepdims=True)  # (1, tn)
        best, lab = to(0), jnp.zeros((1, tn), jnp.int32)
        for c in range(1, k):
            dist = to(c)
            lab = jnp.where(dist < best, c, lab)  # strictly less: the first of equals, as argmin has it
            best = jnp.minimum(dist, best)
        onehot = jax.lax.broadcasted_iota(jnp.int32, (k8, tn), 0) == lab
        if valid is not None:
            onehot = onehot & valid
            best = jnp.where(valid, best, 0.0)
        cnt_ref[...] += _lane_partials(jnp.where(onehot, 1, 0), tn)
        fun_ref[...] += _lane_partials(best, tn)
        lab_ref[...] = lab.reshape(tn)

    call = _call(
        _grid_kernel(tile, n, tn, 2, 0), "kmedians.assign.pass", n, tn,
        [pl.BlockSpec((d, tn), lambda i: (0, i), memory_space=_VMEM), _const((d, k8))],
        [jax.ShapeDtypeStruct((n,), jnp.int32), jax.ShapeDtypeStruct((k8, 128), jnp.int32),
         jax.ShapeDtypeStruct((1, 128), jnp.float32)],
        [pl.BlockSpec((tn,), lambda i: (i,), memory_space=_VMEM), _const((k8, 128)), _const((1, 128))],
        interpret,
    )

    def run(x, centers):
        labels, cnt, fun = call(x.T, _table(centers, k8, jnp.float32))  # x.T: a bitcast of the feature-major array
        return labels, jnp.sum(cnt[:k], axis=1), jnp.sum(fun)

    return run


@functools.lru_cache(maxsize=64)
def _count_program(n: int, d: int, k: int, interpret: bool):
    k8, tn = _round_up(k, 8), _pick_tn(n, d, _round_up(k, 8))

    def tile(refs, valid):
        step_ref, xt_ref, lab_ref, thr_ref, out_ref = refs
        key = _to_key(xt_ref[...])
        lab = lab_ref[...].reshape(1, tn)
        thr = _own(lab, thr_ref, k)
        onehot = jax.lax.broadcasted_iota(jnp.int32, (k8, tn), 0) == lab
        if valid is not None:
            onehot = onehot & valid
        onehot = jnp.where(onehot, 1.0, 0.0).astype(jnp.bfloat16)
        step = step_ref[0]
        for t in range(_N_THR):
            below = jnp.where(key < thr + t * step, 1.0, 0.0).astype(jnp.bfloat16)  # (d, tn)
            out_ref[t] += jax.lax.dot_general(
                onehot, below, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            ).astype(jnp.int32)  # (k8, d)

    call = _call(
        _grid_kernel(tile, n, tn, 1, 0), "kmedians.select.pass", n, tn,
        [pl.BlockSpec(memory_space=_SMEM), pl.BlockSpec((d, tn), lambda i: (0, i), memory_space=_VMEM),
         pl.BlockSpec((tn,), lambda i: (i,), memory_space=_VMEM), _const((d, k8))],
        jax.ShapeDtypeStruct((_N_THR, k8, d), jnp.int32), _const((_N_THR, k8, d)), interpret,
    )

    def run(x, labels, thr0, step):
        return call(jnp.reshape(step, (1,)).astype(jnp.int32), x.T, labels, _table(thr0, k8, jnp.int32))[:, :k]

    return run


@functools.lru_cache(maxsize=64)
def _next_program(n: int, d: int, k: int, interpret: bool):
    k8, tn = _round_up(k, 8), _vpu_tn(n, d, _round_up(k, 8))

    def tile(refs, valid):
        xt_ref, lab_ref, at_ref, out_ref = refs
        key = _to_key(xt_ref[...])
        lab = lab_ref[...].reshape(1, tn)
        above = jnp.where(key > _own(lab, at_ref, k), key, _I32_MAX)
        for c in range(k):
            mine = lab == c if valid is None else (lab == c) & valid
            m = jnp.where(mine, above, _I32_MAX)
            acc = m[:, :128]
            for j in range(1, tn // 128):
                acc = jnp.minimum(acc, m[:, j * 128:(j + 1) * 128])
            out_ref[c * d:(c + 1) * d, :] = jnp.minimum(out_ref[c * d:(c + 1) * d, :], acc)

    call = _call(
        _grid_kernel(tile, n, tn, 1, _I32_MAX), "kmedians.select.pass", n, tn,
        [pl.BlockSpec((d, tn), lambda i: (0, i), memory_space=_VMEM),
         pl.BlockSpec((tn,), lambda i: (i,), memory_space=_VMEM), _const((d, k8))],
        jax.ShapeDtypeStruct((k * d, 128), jnp.int32), _const((k * d, 128)), interpret,
    )

    def run(x, labels, at):
        return jnp.min(call(x.T, labels, _table(at, k8, jnp.int32)), axis=1).reshape(k, d)

    return run


@functools.lru_cache(maxsize=64)
def l1_passes(k: int, shape, mesh=None, axis_name=None, interpret: bool = False) -> L1Passes:
    """The three passes for ``arr`` of ``shape``. On one device they are called bare. With a ``mesh`` they run
    under ``shard_map``, as KMeans' pass does: with an ``axis_name``,
    ``arr`` and the labels are split 0 over it in equal shards, each device
    passes over its rows, and the counts are ``psum``med (the successor:
    ``pmin``) before any bracket narrows; without one ``arr`` is replicated
    and every device runs the whole pass."""
    n, d = int(shape[0]), int(shape[1])
    p = mesh.devices.size if axis_name is not None else 1
    assign = _assign_program(n // p, d, k, interpret)
    count = _count_program(n // p, d, k, interpret)
    nxt = _next_program(n // p, d, k, interpret)
    if mesh is None:
        return L1Passes(assign, count, nxt)
    rows, vec = P(axis_name, None), P(axis_name)
    if axis_name is not None:
        local_assign, local_count, local_next = assign, count, nxt

        def assign(arr, centers):
            labels, cnt, fun = local_assign(arr, centers)
            return labels, jax.lax.psum(cnt, axis_name), jax.lax.psum(fun, axis_name)

        count = lambda *a: jax.lax.psum(local_count(*a), axis_name)
        nxt = lambda *a: jax.lax.pmin(local_next(*a), axis_name)
    sm = functools.partial(_shard_map, mesh=mesh, check_vma=False)
    return L1Passes(
        sm(assign, in_specs=(rows, P()), out_specs=(vec, P(), P())),
        sm(count, in_specs=(rows, vec, P(), P()), out_specs=P()),
        sm(nxt, in_specs=(rows, vec, P()), out_specs=P()),
    )
