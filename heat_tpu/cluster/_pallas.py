"""Pallas TPU kernel: one Lloyd iteration of KMeans in ONE read of f32 ``X``.

The XLA form of the step (``kmeans._lloyd_step``) is two streams an
iteration over a bf16 copy of ``X`` that the compiler hoists out of the
loop, an ``x2`` pass and two small reductions. This pass reads the f32
rows once, as they lie, and returns everything an iteration needs.

The chip keeps a tall ``f32[n, d]`` with ``d < 128`` FEATURE-MAJOR
(``{0,1:T(8,128)}``: rows on the lanes, the only compact form of a narrow
f32 array under (8, 128) tiles), so ``x.T`` is a bitcast and the kernel
works on blocks ``(d, tn)`` of it — rows on all 128 lanes, the k centres
on the sublanes:

    per (d x tn) tile:  xb  = bf16(xt)                          cast in VMEM
                        g   = C_bf16 (k x d) . xb               MXU, f32 acc
                        d2  = max(x2 + c2 - 2 g, 0)             x2 from the f32 tile
                        lab = first argmin over the k sublanes  VPU
                        sums  += onehot (k x tn) . xb^T         MXU, f32 acc
                        counts, inertia += lane partials        VPU

The operands and accumulation are those of the default-precision
``arr @ centers.T`` and ``onehot.T @ arr`` of the XLA step. The ``(k, d)``
sums and two ``(·, 128)`` lane-partial blocks stay in VMEM over the grid;
only the last tile is masked (``n`` need not divide by anything). With
``labels=True`` the same pass also writes the assignment: the label pass
of a fit.

A row-major ``(tm, d)`` kernel stood here until PR 28. It was opt-in and
never ran: the compiler put a 128-lane-padded row-major copy of ``X`` in
front of the loop for it (9.6 GB at the north-star shard).

MEASURED (TPU v5e, 18 750 000 x 64 f32, k 8; builder's chip runs, PR 28,
PERF.md section 6): the pass 6.34 ms an iteration inside the fit (757 GB/s,
92 % of the HBM peak, what a kernel that only adds the tile up reads too),
the XLA step's loop 9.02; a whole ``fit`` of ten iterations 72.0 ms
against 98.0. Not the MXU but the read bounds it, from 4096 to 32768 rows
a step and up to k = 64; at k = 128 it is 8.5 ms (XLA: 47).
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map as _shard_map
from jax.sharding import PartitionSpec as P

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core._pallas_select import _VMEM_LIMIT, _lane_partials, _pick_tn, _round_up, tall_narrow_serves

_VMEM = pltpu.VMEM

__all__ = ["fused_lloyd_step", "lloyd_pass_program", "lloyd_pass_serves"]

# k up to the widest tile of X (d < 128): the (k, tn) distance block is then no
# taller than the (d, tn) tile it is made from. How a grid step is sized
# (_pick_tn, _TILE_BYTES of the _VMEM_LIMIT asked for) is the tall narrow
# kernels' own, in core/_pallas_select.py
_K_MAX = 128


def lloyd_pass_serves(backend: str, dtype, shape, k: int, split, devices: int = 1) -> bool:
    """The gate: does one fit's Lloyd step run the fused pass? A pure
    function of what the code sees in its input.

    Yes where the kernels over ``(d, tn)`` blocks of ``x.T`` serve
    (``_pallas_select.tall_narrow_serves``: the backend a TPU, x64 off, its
    platform default, since Mosaic refuses 64-bit traces; the data f32 and
    2-D; ``d`` a multiple of 8 under 128, where the chip keeps the array
    feature-major and ``x.T`` is free, while at ``d >= 128`` it is row-major
    and the XLA step stays until a cell asks; ``X`` on one device or split 0
    over ``devices`` with equal shards, ``n`` a multiple of them: the array
    is then its physical self, no pad rows) and ``k <= 128`` (``_K_MAX``:
    the distance block no taller than the widest tile of ``X``, so the VMEM
    budget holds at ``tn >= 4096``). Everything else runs the XLA step."""
    return 1 <= k <= _K_MAX and tall_narrow_serves(backend, dtype, shape, split, devices)


def _make_kernel(n: int, k: int, k8: int, tn: int, labels: bool):
    steps = pl.cdiv(n, tn)
    tail = n - (steps - 1) * tn  # rows of the last tile that exist

    def tile(xt_ref, c_ref, c2_ref, out_refs, masked: bool):
        sums_ref, cnt_ref, ine_ref = out_refs[:3]
        x = xt_ref[...]  # (d, tn) f32
        if masked:
            # what the last block holds past row n is unspecified: zero it
            # before any arithmetic, drop it from every accumulator below
            valid = jax.lax.broadcasted_iota(jnp.int32, (1, tn), 1) < tail
            x = jnp.where(valid, x, 0.0)
        xb = x.astype(jnp.bfloat16)
        g = jnp.dot(c_ref[...], xb, preferred_element_type=jnp.float32)  # (k8, tn)
        x2 = jnp.sum(x * x, axis=0, keepdims=True)  # (1, tn)
        d2 = jnp.maximum(x2 + c2_ref[...] - 2.0 * g, 0.0)
        dmin = jnp.min(d2, axis=0, keepdims=True)
        # first index of the minimum, as argmin has it
        row = jax.lax.broadcasted_iota(jnp.int32, (k8, tn), 0)
        lab = jnp.min(jnp.where(d2 == dmin, row, k - 1), axis=0, keepdims=True)
        onehot = row == lab
        if masked:
            onehot = onehot & valid
            dmin = jnp.where(valid, dmin, 0.0)
        onehot = jnp.where(onehot, 1.0, 0.0)  # (k8, tn) f32
        sums_ref[...] += jax.lax.dot_general(
            onehot.astype(jnp.bfloat16), xb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (k8, d)
        cnt_ref[...] += _lane_partials(onehot, tn)
        ine_ref[...] += _lane_partials(dmin, tn)
        if labels:
            out_refs[3][...] = lab.reshape(tn)

    def kernel(xt_ref, c_ref, c2_ref, *out_refs):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _init():
            for ref in out_refs[:3]:
                ref[...] = jnp.zeros_like(ref)

        if tail == tn:
            tile(xt_ref, c_ref, c2_ref, out_refs, False)
            return

        @pl.when(i < steps - 1)
        def _whole():
            tile(xt_ref, c_ref, c2_ref, out_refs, False)

        @pl.when(i == steps - 1)
        def _last():
            tile(xt_ref, c_ref, c2_ref, out_refs, True)

    return kernel


@functools.lru_cache(maxsize=64)
def lloyd_pass_program(n: int, d: int, k: int, labels: bool = False, interpret: bool = False,
                       tn: int = 0):
    """The pass over ONE device's rows: ``(x (n, d) f32, centers (k, d))
    -> (sums (k, d), counts (k,), inertia ()[, labels (n,) int32])``, all
    f32. Traceable; ``interpret=True`` runs it on the CPU for the tests."""
    k8 = _round_up(k, 8)
    tn = tn or _pick_tn(n, d, k8)
    const = lambda i: (0, 0)
    out_shape = [
        jax.ShapeDtypeStruct((k8, d), jnp.float32),
        jax.ShapeDtypeStruct((k8, 128), jnp.float32),
        jax.ShapeDtypeStruct((1, 128), jnp.float32),
    ]
    out_specs = [pl.BlockSpec(s.shape, const, memory_space=_VMEM) for s in out_shape]
    if labels:
        out_shape.append(jax.ShapeDtypeStruct((n,), jnp.int32))
        out_specs.append(pl.BlockSpec((tn,), lambda i: (i,), memory_space=_VMEM))
    call = pl.pallas_call(
        _make_kernel(n, k, k8, tn, labels),
        grid=(pl.cdiv(n, tn),),
        in_specs=[
            pl.BlockSpec((d, tn), lambda i: (0, i), memory_space=_VMEM),
            pl.BlockSpec((k8, d), const, memory_space=_VMEM),
            pl.BlockSpec((k8, 1), const, memory_space=_VMEM),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT
        ),
        name="kmeans_lloyd_pass",
        interpret=interpret,
    )

    def run(x, centers):
        with jax.named_scope("kmeans.lloyd_pass"):
            c = centers.astype(jnp.float32)
            # pad centres sit at "infinity": never the nearest
            c2 = jnp.pad(jnp.sum(c * c, axis=1), (0, k8 - k), constant_values=np.finfo(np.float32).max)
            cb = jnp.pad(c, ((0, k8 - k), (0, 0))).astype(jnp.bfloat16)
            out = call(x.T, cb, c2[:, None])  # x.T: a bitcast of the feature-major array
            res = (out[0][:k], jnp.sum(out[1][:k], axis=1), jnp.sum(out[2]))
            return res + (out[3],) if labels else res

    return run


@functools.lru_cache(maxsize=64)
def fused_lloyd_step(k: int, shape, mesh=None, axis_name=None, interpret: bool = False):
    """The Lloyd step on the fused pass: ``step(arr, centers) ->
    (new_centers, shift, inertia)``, and ``step.assign(arr, centers) ->
    labels`` (int32), the same pass with its label output, for the fit's
    final assignment. On one device the pass is called bare. With a
    ``mesh`` it runs under ``shard_map`` (Mosaic kernels are not
    partitioned automatically): with an ``axis_name``, ``arr`` is split 0
    over it in equal shards, each device passes over its rows and the
    ``(k, d)`` sums, counts and inertia are ``psum``med; without one
    ``arr`` is replicated and every device runs the whole pass."""
    n, d = int(shape[0]), int(shape[1])
    p = mesh.devices.size if axis_name is not None else 1
    stats = lloyd_pass_program(n // p, d, k, False, interpret)
    with_labels = lloyd_pass_program(n // p, d, k, True, interpret)
    assign = lambda arr, centers: with_labels(arr, centers)[3]
    if mesh is not None:
        if axis_name is not None:
            local = stats
            stats = lambda arr, centers: jax.lax.psum(local(arr, centers), axis_name)
        specs = dict(mesh=mesh, in_specs=(P(axis_name, None), P()), check_vma=False)
        stats = _shard_map(stats, out_specs=P(), **specs)
        assign = _shard_map(assign, out_specs=P(axis_name), **specs)

    def step(arr, centers):
        sums, counts, inertia = stats(arr, centers)
        counts = counts[:, None]
        new_centers = jnp.where(counts > 0, sums / jnp.maximum(counts, 1), centers)
        shift = jnp.sum((new_centers - centers) ** 2)
        return new_centers, shift, inertia

    step.assign = assign
    return step
