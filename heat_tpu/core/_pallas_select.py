"""Pallas TPU kernels of the exact counting selection (``_selection`` drives
them): order statistics of the columns of a tall narrow f32 ``X`` by passes
that count, never a sort, and nothing of ``X``'s size besides ``X``.

Two ways to say which rows count for which target. **By label** (KMedians,
KMedoids: ``k`` targets a feature, a row counts for its own cluster's; the
kernels read an int32 label a row). **For all rows** (``ht.percentile`` along
the sample axis: ``q`` targets a feature, every row counts for each; no label
array is made). The same passes in both:

``X`` is tiled as KMeans' pass tiles it: the chip keeps a tall ``f32[n, d]``
with ``d < 128`` feature-major, ``x.T`` is a bitcast, a grid step takes a
block ``(d, tn)`` with the rows on the lanes (``_pick_tn``).

    count    key = order-preserving int32 image of x      3 VPU ops
             by label: thr = thr0[lab] + t * step, t < T  k selects, then T = _N_THR compares
                       out[t, c, j] += #{rows of c: key_j < thr}    one-hot dot on the MXU, exact
             all rows: out[t, i, j] += #{rows: key_j < thr0[i, j] + t * step}    q x T compares, lane partials
    first    (all rows) the first digit, whose three thresholds are every target's, and the column's NaN flag
    next     out[c, j] = min{key_j > at[c, j]}            the successor (by label: of the row's own cluster)
    gather   off = key - base,  0 <= off < 2**bits        the keys still in a target's window:
             kept <- target << _LABEL_SHIFT | off         sorted slots, lane by lane

``count`` is one digit of a radix selection: every order statistic asked for
at once, for a price that does not grow with ``k`` beyond the ``k`` selects
(by label) and grows by three compares a target (all rows). The counts of a
tile (at most ``tn`` < 2^24) are exact in the f32 accumulator of the dot and
are added up as int32.

After a few digits a bracket holds a few keys in a thousand, and the other
digits are counted on them (and on those of the bracket above, for the upper
of two neighbouring ranks; a target whose newest bracket holds under
``_WINDOW_MIN_KEYS`` keys, because its value lies near zero where f32 keys
are sparse, keeps the window of an earlier, wider bracket). How many digits
that takes the counts say themselves: after every digit they give the keys
each target's window holds, and the selection goes on counting on ``X`` while
some feature's windows hold more than the slots below are made for
(``crowded``: over one row in ``_GATHER_MOST_OF_X``), from the
``_WINDOW_FIRST_DIGIT``-th digit to the ``_MOST_DIGITS_ON_X``-th: eight digits
on unit blobs near zero, eleven on the same blobs around 10, twelve around
100. The chip has no vector scatter, so
``gather`` folds the lanes: a block's ``tn / 128`` lane chunks ``(d, 128)``
go, one after the other, into ``_SLOTS`` ascending slots ``(d, 128)`` by a
chain of min / max (a kept key, or the type's max, ripples to its place; what
falls off the end is a spill), and the slots of ``_KEPT_STEPS`` grid steps go
the same way into the ``_KEPT_SLOTS`` slots of one output block. The kept
array is ``int32[d, kept_lanes]``: at 18 750 000 x 64, 64 x 294 912, 75 MB,
1.6 % of ``X``. A kept key carries its target (the row's label; for all rows
the first target whose window holds the key) above its offset, so one
comparison with ``c << _LABEL_SHIFT | off`` says target and side:
``kept_below`` counts a feature's kept keys under each of ``q`` such
thresholds, ``kept_above`` finds the least one over each
(``kept_by_target``, ``kept_under`` and ``kept_next`` speak in targets and
offsets: the format stays in this module). A spill (rows sorted by a
feature, many equal values) sends the selection back to ``X`` for its last
digits, from the digit it had reached (``_selection.order_statistics``); where
the windows are still ``crowded`` after ``_MOST_DIGITS_ON_X`` digits (many
equal values, values beyond a thousand noise widths from zero), or where two
targets' windows overlap without being the same (``window_owners``: all rows,
neighbouring ranks), ``gather`` is told to skip: every grid step asks for the
first block, so nothing more is read, and does nothing.

The kernels are named by the caller's prefix (``kmedians.select``,
``percentile.select``): every kernel that reads all of ``X`` is
``<prefix>.pass`` (``count``, ``first``, ``next`` and ``gather``), so one op
of such a name is one whole read of ``X``, and the benchmark's readers count
reads of ``X`` by these names (``docs/API.md``, observability). The two kernels
over the kept keys are ``<prefix>.candidates``: they read no ``X``.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map as _shard_map
from jax.sharding import PartitionSpec as P

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_VMEM = pltpu.VMEM
_SMEM = pltpu.SMEM
_I32_MAX = np.iinfo(np.int32).max

__all__ = ["SelectPasses", "crowded", "gather_pays", "kept_by_target", "kept_lanes", "kept_next", "kept_under",
           "select_passes", "tall_narrow_serves", "window_owners"]


# ---------------------------------------------------------------------- #
# how a tall narrow f32 array is tiled (KMeans' pass, the L1 assignment,  #
# the selection and the scalers' transform share it)                      #
# ---------------------------------------------------------------------- #
# A grid step takes 2 MiB of f32 X (8192 rows at d 64: 4096 to 32768 read alike
# on the chip, 2048 6 % slower; PERF.md, PR 28), fewer where the tile twice (two
# pipeline buffers), its bf16 copy, its square and five (k, tn) f32 temporaries
# would pass _TILE_BYTES of the _VMEM_LIMIT asked for (k 128 at d 120: 4.2 KB a
# row, 4096 rows)
_VMEM_LIMIT = 32 * 1024 * 1024
_TILE_BYTES = 24 * 1024 * 1024
_TILE_X_BYTES = 2 * 1024 * 1024


def tall_narrow_serves(backend: str, dtype, shape, split, devices: int = 1) -> bool:
    """Do the kernels over ``(d, tn)`` blocks of ``x.T`` serve this array?
    A pure function of what the code sees in its input: a TPU (x64 off, its
    platform default: Mosaic refuses 64-bit traces), f32 and 2-D, ``d`` a
    multiple of 8 under 128 (there the chip keeps the array feature-major
    and ``x.T`` is free; at ``d >= 128`` it is row-major), and ``X`` on one
    device or split 0 over ``devices`` in equal shards (``n`` a multiple of
    them: the array is then its physical self, no pad rows)."""
    if backend != "tpu" or jax.config.jax_enable_x64:
        return False
    if np.dtype(dtype) != np.float32 or len(shape) != 2:
        return False
    n, d = int(shape[0]), int(shape[1])
    if d % 8 or not 8 <= d < 128 or n < 1:
        return False
    if split is None or devices == 1:
        return True
    return split == 0 and n % devices == 0


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _pick_tn(n: int, d: int, k8: int) -> int:
    """Rows a grid step: a multiple of 1024, the tiling of the 1-D label
    output, that keeps the step's VMEM inside ``_TILE_BYTES``. Up to 1024
    rows the chip tiles that output by the power of two that holds it
    (128 at least), and the one block has to be just that."""
    if n <= 1024:
        return max(128, 1 << (n - 1).bit_length())
    per_row = d * (4 * 2 + 2 + 4) + k8 * 4 * 5
    tn = max(1024, min(_TILE_X_BYTES // (4 * d), _TILE_BYTES // per_row) // 1024 * 1024)
    return min(tn, _round_up(n, 1024))


def _lane_partials(v, tn: int):
    """(r, tn) -> (r, 128): the tile's 128-lane groups added up, the
    cross-lane sum left to the caller (once a pass, not once a tile)."""
    acc = v[:, :128]
    for j in range(1, tn // 128):
        acc = acc + v[:, j * 128:(j + 1) * 128]
    return acc


# bits of the key a counting pass settles: 2**bits - 1 thresholds a pass,
# 32 / bits passes for f32. At 18.75M x 64 on a v5e a pass of 3 thresholds
# reads at the rate of a bare read (6.44 ms: the k selects of a row's own
# threshold and three one-hot dots hide under it; ledger, PR 32). Against a
# pass of 3 that still took 7.3 ms (an earlier form of this kernel; builder's
# chip runs, PR 32): one of 7 took 9.25, one of 15 15.3, one of 1 7.2, so two
# bits were the fewest ms a bit (3.2; three bits 3.1 with an uneven first
# pass), and every threshold past the third is VPU time the read cannot hide
_RADIX_BITS = 2
_N_THR = 2 ** _RADIX_BITS - 1

# ``gather`` keeps the keys of each row's own window: a bracket of its (cluster, feature) pair and the one above it.
# The bracket is the pair's newest with _WINDOW_MIN_KEYS keys in it, so that the upper middle value is in the window too
# (a median near zero lies where f32 keys are sparse: its ninth bracket holds a handful of 2.3 M keys, its seventh a
# hundred), and no earlier than the fourth, so that an offset fits under the label. A kept key is one int32: its offset
# in the window with the label above it, from bit _LABEL_SHIFT.
_WINDOW_MIN_KEYS = 32
_WINDOW_FIRST_DIGIT = 4
_LABEL_SHIFT = 26  # a window of the fourth digit is 2 ** 25 keys; five bits of label above it
_MOST_CLUSTERS = 32  # the last one's kept keys end under the type's max, which is what an empty slot holds
assert 32 - _RADIX_BITS * _WINDOW_FIRST_DIGIT < _LABEL_SHIFT and (_MOST_CLUSTERS << _LABEL_SHIFT) - 1 <= _I32_MAX
# targets a feature that one selection for all rows finds in the same passes (``ht.percentile`` batches a longer ``q``):
# every target is three compares a key in a counting pass and five VPU ops a key in the gathering pass, unrolled
_MOST_TARGETS = 8
# Per grid step the tn / 128 lane chunks fold onto _SLOTS sorted slots (d, 128), and _KEPT_STEPS steps fold their slots
# onto the _KEPT_SLOTS slots of one output block. Both are sized for the densest windows the rule below lets through, at
# 18.75M x 64, k 8, tn 8192. A window is two brackets, so where a feature's brackets hold a share s of its rows a lane
# position of a step (64 rows) holds 128 s kept keys on average and one of a block of 16 steps 2048 s; over its slots by
# Poisson, summed over the 18.75M positions of a pass (1.17M of blocks). Passes that spilled of ten on the cell's data
# after eight digits (unit blobs near zero: the densest feature one row in 970 to 1 270, the mean one in 1 680 to 2 250;
# builder's chip runs, PR 37, five iterations of two seeds) against that reckoning, by slots / steps / kept slots: 4 / 8 /
# 8 six (expected 0.53 spills a pass); 6 / 8 / 8 two (0.12: the block's eight slots, a lane position of eight steps
# expects one key); 5 / 16 / 16 none (0.006, one pass in 150); 6 / 16 / 12 none (0.008); 6 / 8 / 12 and 6 / 16 / 16 none
# (9e-5: one pass in 10 ** 4). Where every feature is as dense as the rule allows, one row in 768, 6 / 16 / 16 expects
# 0.012 + 0.005. What a slot costs: the pass takes 10.67 ms with 4 slots (ledger, PR 33 to 36), 12.63 with 6 and blocks
# of 8 / 8, 13.26 with blocks of 16 / 16 (the fold of six slots onto sixteen; builder's chip runs, PR 37, device trace;
# alone on the host's clock 11.7, 13.8, 14.4, and 13.2 with 5 / 16 / 16), against 6.44 for the counting pass it stands in
# for. The kept array is as large as with 8 / 8 (75 MB) and an op over it takes what it took (0.25 ms at 24 thresholds a
# feature); 8 / 12 keeps 113 MB
_SLOTS = 6
_KEPT_STEPS = 16
_KEPT_SLOTS = 16
# rows a cluster from which the gather pays. A window holds a number of keys that does not grow with n (32 to a few
# hundred), so what is kept of X goes as k / n, and under some 2 ** 16 rows a cluster a lane position holds more keys
# than slots now and then: the selection then ends on X and the gathering pass was for nothing. Five iterations, ms,
# selection on X to its end / with the gather (builder's chip runs, PR 33, nine digits and four slots; d 64 unless said):
# k 8: 262 144 rows 11.2 / 11.9, 524 288 20.1 / 13.8, 1 048 576 38.0 / 25.6, 18 750 000 640.2 / 420.9; k 16: 1 048 576
# 55.8 / 59.7, 4 194 304 213.0 / 140.6; k 32: 4 194 304 392.3 / 280.0; k 4, d 16: 1 048 576 10.5 / 7.2; k 2, d 8:
# 2 097 152 14.0 / 13.6
_GATHER_MIN_ROWS_A_CLUSTER = 1 << 17
# and the share of a feature's rows that its windows' brackets may hold for the gathering pass to run: the rule by which
# the selection stops counting on X. Every digit leaves the windows a quarter of what they held, and the counting passes
# say how much that is, so the selection gathers after the first digit that leaves no feature over one row in
# _GATHER_MOST_OF_X. The densest feature's brackets hold, by digits counted (builder's chip runs, PR 37, 18.75M x 64, k 8):
# the cell's blobs, seven: one row in 240 to 310, eight: 970 to 1 270, nine: 3 800 to 5 000; the same blobs around 10
# (f32 keys lie sixteen times as dense there, and every feature alike), nine: 130 to 150, ten: 520 to 600, eleven: 2 060
# to 2 370; around 100, eleven: 260 to 300, twelve: 1 030 to 1 190. Where the limit lies: a spill after eight digits
# costs the eight counting passes left and the successor (64 ms), and one digit fewer on X saves 6.44 ms less the 2.6 that
# two more slots and the larger blocks cost the gathering pass, so a spill has to stay rarer than one pass in 17 however the features lie. With
# every feature at one row in 768 it is one in 60 (above); at one in 640, one in 10; at one in 512 three in two (blobs
# around 10 after ten digits, one row in 517 to 597 of every feature: a block of 16 / 16 spilled in one pass of three).
# On the cell's data (2 000 simulated seeds) one seed in a thousand has a feature over one in 768 after eight digits and
# counts a ninth; none fits after seven
_GATHER_MOST_OF_X = 768
# and the digits after which it stops counting whatever the windows hold. The gathering pass and the ops over what it
# keeps cost two counting passes, the successor pass that they save costs two, so a gather after any digit before the
# last is cheaper than the end on X; but windows that are crowded after twelve digits (2 ** 9 keys, 2 ** 8 a bracket) are
# crowded by equal values or values a thousand noise widths from zero (blobs around 1 000 would fit after fourteen),
# which is where lane positions spill whatever the counts say (a sorted column, one repeated value), and a gathering
# pass that spills is 13 ms for nothing. Stopping here costs such data nothing: the digits left are counted on X either way
_MOST_DIGITS_ON_X = 12
assert _WINDOW_FIRST_DIGIT <= _MOST_DIGITS_ON_X <= 32 // _RADIX_BITS  # the kernels are f32's


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


class SelectPasses(NamedTuple):
    """The passes the selection is made of, on whole (global) arrays. By
    label every pass over ``arr`` takes the rows' ``labels`` after it; for
    all rows none does, and ``first`` is there. ``_selection`` holds the
    ``jax.numpy`` form of the first two; the last three are ``None`` where
    the selection stays on ``X`` to its end."""

    count_below: Callable  # (arr[, labels], thr0 (k, d), step ()) -> int32 (T, k, d)
    next_above: Callable  # (arr[, labels], at (k, d)) -> (k, d) keys, the type's max where none
    gather: Optional[Callable] = None      # (arr[, labels], base (k, d), bits (k, d), skip bool ()) -> kept int32 (d, m), spilled ()
    kept_below: Optional[Callable] = None  # (kept, thr (q, d)) -> int32 (q, d): kept[j] < thr[i, j]
    kept_above: Optional[Callable] = None  # (kept, at (q, d)) -> int32 (q, d): least kept[j] > at[i, j]
    first: Optional[Callable] = None       # all rows: (arr) -> keys under the first digit's thresholds int32 (T, d), NaN in the column bool (d,)


def _grid_kernel(tile, n: int, tn: int, n_acc: int, init, before=None, skips: bool = False):
    """``tile(refs, valid)`` over the grid: the accumulators (the last
    ``n_acc`` refs) set to ``init`` at step 0; ``valid`` is ``None`` on a
    whole tile and the (1, tn) mask of the rows that exist on the last.
    ``before(refs, i, when)`` runs first in every step ``i``. With ``skips``
    the first ref is a scalar, and where it is not 0 no step does anything
    (``when`` is ``pl.when`` with that in it: no ``cond`` around the rest)."""
    steps = pl.cdiv(n, tn)
    tail = n - (steps - 1) * tn

    def kernel(*refs):
        i = pl.program_id(0)
        if skips:
            go, refs = refs[0][0] == 0, refs[1:]
        when = lambda cond: pl.when(cond & go if skips else cond)

        @when(i == 0)
        def _init():
            for ref in refs[len(refs) - n_acc:]:
                ref[...] = jnp.full(ref.shape, init, ref.dtype)

        if before is not None:
            before(refs, i, when)
        whole = steps - 1 if tail < tn else steps  # the steps whose tile is whole
        if whole == steps and not skips:
            tile(refs, None)
            return

        @when(i < whole)
        def _whole():
            tile(refs, None)

        if whole == steps:
            return

        @when(i == steps - 1)
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


def _call(kernel, name: str, n: int, tn: int, in_specs, out_shape, out_specs, interpret: bool, prefetch: int = 0):
    """``kernel`` over the blocks of ``tn`` rows; its first ``prefetch``
    operands are scalars that the index maps are given too."""
    grid = dict(grid=(pl.cdiv(n, tn),), in_specs=in_specs, out_specs=out_specs)
    if prefetch:
        grid = dict(grid_spec=pltpu.PrefetchScalarGridSpec(num_scalar_prefetch=prefetch, **grid))
    return pl.pallas_call(
        kernel, out_shape=out_shape, **grid,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT),
        name=name, interpret=interpret,
    )


def _vpu_tn(n: int, d: int, k8: int) -> int:
    """Rows a grid step of the two passes by label that are all VPU (the L1
    assignment, next): a quarter of the 2 MiB tile the streaming passes
    take. Their (d, tn) temporaries then stay near the registers: at 18.75M
    x 64 the assignment reads 11.7 ms at 2048 rows, 12.4 at 4096, 16.4 at
    8192, 13.8 at 1024 (the successor 12.6 / 15.8 / 17.2 / 14.5), while a
    counting pass is fastest on the whole tile (7.3 ms at 8192, 8.1 at 4096;
    builder's chip runs, PR 32)."""
    tn = _pick_tn(n, d, k8)
    return tn if n <= 1024 else max(1024, tn // 4096 * 1024)


def _lane_least(v, tn: int):
    """(r, tn) -> (r, 128): the least of the tile's 128-lane groups, lane by lane."""
    acc = v[:, :128]
    for j in range(1, tn // 128):
        acc = jnp.minimum(acc, v[:, j * 128:(j + 1) * 128])
    return acc


def _table(values, k8: int, dtype):
    """A (k, d) table as the kernels read it: (d, k8), a cluster a column."""
    return jnp.pad(values.astype(dtype), ((0, k8 - values.shape[0]), (0, 0))).T


def _const(shape):
    return pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape), memory_space=_VMEM)


def _x_block(d: int, tn: int):
    return pl.BlockSpec((d, tn), lambda i: (0, i), memory_space=_VMEM)


# ---------------------------------------------------------------------- #
# the passes over X, by label                                             #
# ---------------------------------------------------------------------- #
@functools.lru_cache(maxsize=64)
def _count_program(n: int, d: int, k: int, interpret: bool, name: str):
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
        _grid_kernel(tile, n, tn, 1, 0), f"{name}.pass", n, tn,
        [pl.BlockSpec(memory_space=_SMEM), _x_block(d, tn),
         pl.BlockSpec((tn,), lambda i: (i,), memory_space=_VMEM), _const((d, k8))],
        jax.ShapeDtypeStruct((_N_THR, k8, d), jnp.int32), _const((_N_THR, k8, d)), interpret,
    )

    def run(x, labels, thr0, step):
        return call(jnp.reshape(step, (1,)).astype(jnp.int32), x.T, labels, _table(thr0, k8, jnp.int32))[:, :k]

    return run


@functools.lru_cache(maxsize=64)
def _next_program(n: int, d: int, k: int, interpret: bool, name: str):
    k8, tn = _round_up(k, 8), _vpu_tn(n, d, _round_up(k, 8))

    def tile(refs, valid):
        xt_ref, lab_ref, at_ref, out_ref = refs
        key = _to_key(xt_ref[...])
        lab = lab_ref[...].reshape(1, tn)
        above = jnp.where(key > _own(lab, at_ref, k), key, _I32_MAX)
        for c in range(k):
            mine = lab == c if valid is None else (lab == c) & valid
            least = _lane_least(jnp.where(mine, above, _I32_MAX), tn)
            out_ref[c * d:(c + 1) * d, :] = jnp.minimum(out_ref[c * d:(c + 1) * d, :], least)

    call = _call(
        _grid_kernel(tile, n, tn, 1, _I32_MAX), f"{name}.pass", n, tn,
        [_x_block(d, tn), pl.BlockSpec((tn,), lambda i: (i,), memory_space=_VMEM), _const((d, k8))],
        jax.ShapeDtypeStruct((k * d, 128), jnp.int32), _const((k * d, 128)), interpret,
    )

    def run(x, labels, at):
        return jnp.min(call(x.T, labels, _table(at, k8, jnp.int32)), axis=1).reshape(k, d)

    return run


def kept_lanes(n: int, d: int, k: int) -> int:
    """Lanes of the array ``gather`` keeps of ``n`` rows: ``_KEPT_SLOTS``
    x 128 for every ``_KEPT_STEPS`` grid steps (16 x 128 of 16 x 8192 rows
    at ``d`` 64: 1.6 % of ``X``)."""
    return pl.cdiv(pl.cdiv(n, _pick_tn(n, d, _round_up(k, 8))), _KEPT_STEPS) * _KEPT_SLOTS * 128


def _insert(slots, v):
    """``v`` into the ascending ``slots``, lane by lane; returns what fell
    off their end (the largest)."""
    for s in range(len(slots)):
        slots[s], v = jnp.minimum(slots[s], v), jnp.maximum(slots[s], v)
    return v


@functools.lru_cache(maxsize=64)
def _gather_program(n: int, d: int, k: int, interpret: bool, name: str, labelled: bool = True):
    """The gathering pass; by label (``labelled``) a row's window is its own
    cluster's, for all rows a key goes to the first of the ``k`` targets
    whose window holds it (windows of two targets either are the same or do
    not meet: ``window_owners``)."""
    tn = _pick_tn(n, d, _round_up(k, 8))
    width = _KEPT_SLOTS * 128
    empty = lambda: jnp.full((d, 128), _I32_MAX, jnp.int32)

    group = min(tn, 1024)  # rows a turn of the loop: a tile of the 1-D label block, eight lane chunks
    tail = n - (pl.cdiv(n, tn) - 1) * tn

    def offset_in(key, window):
        """(is the key in the window, its offset there); a window's bits
        ride in the five lowest of its base, which are zero."""
        bits = window & 31
        off = key - (window - bits)
        return jax.lax.shift_right_logical(off, bits) == 0, off  # 0 <= off < 2 ** bits

    def tile(refs, valid):
        if labelled:
            xt_ref, lab_ref, base_ref, kept_ref, spill_ref = refs
        else:
            xt_ref, base_ref, kept_ref, spill_ref = refs

        def fold(g, carry):
            slots, spill = list(carry[:-1]), carry[-1]
            start = pl.multiple_of(g * group, group)
            if labelled:
                lab = lab_ref[pl.ds(start, group)].reshape(1, group)
                if valid is not None:  # the last block: its rows from ``tail`` on do not exist
                    lab = jnp.where(start + jax.lax.broadcasted_iota(jnp.int32, (1, group), 1) < tail, lab, -1)
            elif valid is not None:
                exists = start + jax.lax.broadcasted_iota(jnp.int32, (1, group), 1) < tail
            for j in range(group // 128):
                keys = lambda: _to_key(xt_ref[:, pl.ds(pl.multiple_of(start + j * 128, 128), 128)])
                if labelled:  # spelled out, not through ``offset_in``: the order of its ops is KMedians' program's
                    own = lab[:, j * 128:(j + 1) * 128]  # (1, 128)
                    base = base_ref[0:d, :]
                    for c in range(1, k):
                        base = jnp.where(own == c, base_ref[c * d:(c + 1) * d, :], base)
                    bits = base & 31  # a window's bits ride in the five lowest of its base, which are zero
                    off = keys() - (base - bits)
                    inside = jax.lax.shift_right_logical(off, bits) == 0  # 0 <= off < 2 ** bits
                    if valid is not None:
                        inside = inside & (own >= 0)
                    v = jnp.where(inside, off | (own << _LABEL_SHIFT), _I32_MAX)
                else:
                    key, v = keys(), empty()
                    for c in range(k):  # the least label wins: the first of the targets that share a window
                        inside, off = offset_in(key, base_ref[c * d:(c + 1) * d, :])
                        v = jnp.minimum(v, jnp.where(inside, off | (c << _LABEL_SHIFT), _I32_MAX))
                    if valid is not None:
                        v = jnp.where(exists[:, j * 128:(j + 1) * 128], v, _I32_MAX)
                spill = jnp.minimum(spill, _insert(slots, v))
            return (*slots, spill)

        *slots, spill = jax.lax.fori_loop(0, tn // group, fold, (empty(),) * (_SLOTS + 1))
        for v in slots:
            for s in range(_KEPT_SLOTS):
                held = kept_ref[:, s * 128:(s + 1) * 128]
                kept_ref[:, s * 128:(s + 1) * 128] = jnp.minimum(held, v)
                v = jnp.maximum(held, v)
            spill = jnp.minimum(spill, v)
        spill_ref[...] = jnp.minimum(spill_ref[...], spill)

    def fresh_block(refs, i, when):
        @when(i % _KEPT_STEPS == 0)
        def _():
            refs[-2][...] = jnp.full((d, width), _I32_MAX, jnp.int32)

    # told to skip, every step asks for the first block: nothing is read after it, and what comes out means nothing
    at = lambda i, skip_ref: jnp.where(skip_ref[0] == 0, i, 0)
    labels_block = [pl.BlockSpec((tn,), lambda i, skip: (at(i, skip),), memory_space=_VMEM)] if labelled else []
    call = _call(
        _grid_kernel(tile, n, tn, 1, _I32_MAX, fresh_block, skips=True), f"{name}.pass", n, tn,
        [pl.BlockSpec((d, tn), lambda i, skip: (0, at(i, skip)), memory_space=_VMEM), *labels_block, _const((k * d, 128))],
        [jax.ShapeDtypeStruct((d, kept_lanes(n, d, k)), jnp.int32), jax.ShapeDtypeStruct((d, 128), jnp.int32)],
        [pl.BlockSpec((d, width), lambda i, skip: (0, at(i, skip) // _KEPT_STEPS), memory_space=_VMEM), _const((d, 128))],
        interpret, prefetch=1,
    )

    def run(x, *args):
        *labels, base, bits, skip = args
        # a cluster's windows along all 128 lanes: the kernel loads them, it does not broadcast
        lanes = jnp.broadcast_to((base | bits).astype(jnp.int32)[:, :, None], (k, d, 128)).reshape(k * d, 128)
        kept, spill = call(jnp.reshape(skip, (1,)).astype(jnp.int32), x.T, *labels, lanes)
        return kept, skip | (jnp.min(spill) < _I32_MAX)

    return run


# ---------------------------------------------------------------------- #
# the passes over X, for all rows                                         #
# ---------------------------------------------------------------------- #
@functools.lru_cache(maxsize=64)
def _first_program(n: int, d: int, interpret: bool, name: str):
    """The first digit for all rows: its bracket is the whole key range for
    every target, so its ``_N_THR`` thresholds are compared once a key, not
    once a target; the pass also says which columns hold a NaN (of either
    sign: as keys they lie beyond the infinities, at both ends)."""
    tn = _pick_tn(n, d, 8)
    step = 1 << (32 - _RADIX_BITS)

    def tile(refs, valid):
        xt_ref, cnt_ref, nan_ref = refs
        x = xt_ref[...]
        key, nan = _to_key(x), x != x
        if valid is not None:
            nan = nan & valid
        nan_ref[...] += _lane_partials(jnp.where(nan, 1, 0), tn)
        for t in range(_N_THR):
            below = key < (t + 1) * step - (1 << 31)
            if valid is not None:
                below = below & valid
            cnt_ref[t * d:(t + 1) * d, :] += _lane_partials(jnp.where(below, 1, 0), tn)

    call = _call(
        _grid_kernel(tile, n, tn, 2, 0), f"{name}.pass", n, tn, [_x_block(d, tn)],
        [jax.ShapeDtypeStruct((_N_THR * d, 128), jnp.int32), jax.ShapeDtypeStruct((d, 128), jnp.int32)],
        [_const((_N_THR * d, 128)), _const((d, 128))], interpret,
    )

    def run(x):
        cnt, nan = call(x.T)
        return jnp.sum(cnt.reshape(_N_THR, d, 128), axis=2, dtype=jnp.int32), jnp.sum(nan, axis=1, dtype=jnp.int32)

    return run


@functools.lru_cache(maxsize=64)
def _count_all_program(n: int, d: int, q: int, interpret: bool, name: str):
    tn = _pick_tn(n, d, 8)

    def tile(refs, valid):
        step_ref, xt_ref, thr_ref, out_ref = refs
        key, step = _to_key(xt_ref[...]), step_ref[0]
        for i in range(q):
            for t in range(_N_THR):
                below = key < thr_ref[:, i:i + 1] + t * step  # (d, tn) against the feature's threshold
                if valid is not None:
                    below = below & valid
                rows = slice((t * q + i) * d, (t * q + i + 1) * d)
                out_ref[rows, :] += _lane_partials(jnp.where(below, 1, 0), tn)

    call = _call(
        _grid_kernel(tile, n, tn, 1, 0), f"{name}.pass", n, tn,
        [pl.BlockSpec(memory_space=_SMEM), _x_block(d, tn), _const((d, q))],
        jax.ShapeDtypeStruct((_N_THR * q * d, 128), jnp.int32), _const((_N_THR * q * d, 128)), interpret,
    )

    def run(x, thr0, step):
        out = call(jnp.reshape(step, (1,)).astype(jnp.int32), x.T, thr0.astype(jnp.int32).T)
        return jnp.sum(out.reshape(_N_THR, q, d, 128), axis=3, dtype=jnp.int32)

    return run


@functools.lru_cache(maxsize=64)
def _next_all_program(n: int, d: int, q: int, interpret: bool, name: str):
    tn = _pick_tn(n, d, 8)

    def tile(refs, valid):
        xt_ref, at_ref, out_ref = refs
        key = _to_key(xt_ref[...])
        for i in range(q):
            above = key > at_ref[:, i:i + 1]
            if valid is not None:
                above = above & valid
            rows = slice(i * d, (i + 1) * d)
            out_ref[rows, :] = jnp.minimum(out_ref[rows, :], _lane_least(jnp.where(above, key, _I32_MAX), tn))

    call = _call(
        _grid_kernel(tile, n, tn, 1, _I32_MAX), f"{name}.pass", n, tn, [_x_block(d, tn), _const((d, q))],
        jax.ShapeDtypeStruct((q * d, 128), jnp.int32), _const((q * d, 128)), interpret,
    )

    def run(x, at):
        return jnp.min(call(x.T, at.astype(jnp.int32).T), axis=1).reshape(q, d)

    return run


# ---------------------------------------------------------------------- #
# the kept keys                                                           #
# ---------------------------------------------------------------------- #
@functools.lru_cache(maxsize=64)
def _kept_program(m: int, d: int, q: int, above: bool, interpret: bool, name: str):
    """A pass over the kept array ``(d, m)`` against ``q`` thresholds a
    feature: how many of a feature's kept lie under each, or (``above``)
    the least kept over each. Empty slots hold the type's max: under no
    threshold, over every one."""
    tm = _KEPT_SLOTS * 128  # one block of ``gather`` a step: no block is cut

    def tile(refs, _):
        kept_ref, thr_ref, out_ref = refs
        kept = kept_ref[...]
        for i in range(q):
            thr, rows = thr_ref[:, i:i + 1], slice(i * d, (i + 1) * d)
            if above:
                out_ref[rows, :] = jnp.minimum(out_ref[rows, :], _lane_least(jnp.where(kept > thr, kept, _I32_MAX), tm))
            else:
                out_ref[rows, :] += _lane_partials(jnp.where(kept < thr, 1, 0), tm)

    call = _call(
        _grid_kernel(tile, m, tm, 1, _I32_MAX if above else 0), f"{name}.candidates", m, tm,
        [pl.BlockSpec((d, tm), lambda i: (0, i), memory_space=_VMEM), _const((d, q))],
        jax.ShapeDtypeStruct((q * d, 128), jnp.int32), _const((q * d, 128)), interpret,
    )

    def run(kept, thr):
        out = call(kept, thr.astype(jnp.int32).T).reshape(q, d, 128)
        return jnp.min(out, axis=2) if above else jnp.sum(out, axis=2, dtype=jnp.int32)

    return run


def _kept_key(off, owner=None):
    """What ``gather`` keeps of a key ``off`` above the base of its window,
    for ``off`` of shape (..., k, d): the keys of one target compare by
    offset, and every key of a target lies under the next one's zero. The
    target is the row of ``off``; with ``owner`` (k, d), the target that
    keeps the window's keys (``window_owners``)."""
    label = jnp.arange(off.shape[-2], dtype=jnp.int32)[:, None] if owner is None else owner
    return (label << _LABEL_SHIFT) + off.astype(jnp.int32)


def window_owners(base, wide):
    """For all rows, where two targets' windows may meet: ``owner`` (k, d)
    int32, the first target with the same window (``gather`` keeps a key
    once, under the least label whose window holds it, and the targets that
    share the window read that one's keys), and whether some feature has two
    windows that overlap without being the same (neighbouring ranks in
    neighbouring brackets: a key of both would be kept for one only, so the
    gathering pass is told to skip and the selection ends on ``X``)."""
    k = base.shape[0]
    same = (base[:, None] == base[None]) & (wide[:, None] == wide[None])  # (k, k, d)
    owner = jnp.argmax(same, axis=1).astype(jnp.int32)  # the first of the equals
    # [a, a + 2 ** wa) and [b, b + 2 ** wb) with a <= b meet iff b - a < 2 ** wa (b - a >= 0 cannot wrap: both are keys
    # of one type and a window never runs past the last key)
    gap = base[None] - base[:, None]  # (k, k, d): b - a
    meet = (gap >= 0) & (jax.lax.shift_right_logical(gap, jnp.broadcast_to(wide[:, None], gap.shape)) == 0)
    clash = jnp.any(meet & ~same & (jnp.arange(k)[:, None, None] != jnp.arange(k)[None, :, None]))
    return owner, clash


def kept_by_target(passes: SelectPasses, kept, k: int, owner=None):
    """``(ahead, in_window)``, each int32 (k, d): a target's kept keys of
    the targets before its own (what a count under one of its thresholds
    holds besides its own), and its own; with ``owner``, those of the
    target that keeps its window's keys."""
    d = kept.shape[0]
    # where each cluster's kept keys start; the last one's end is the type's max (``k << _LABEL_SHIFT`` is 2 ** 31 at
    # k 32), over every kept key and under no empty slot
    starts = jnp.concatenate([_kept_key(jnp.zeros((k, d), jnp.int32)), jnp.full((1, d), _I32_MAX, jnp.int32)])
    ahead = passes.kept_below(kept, starts)
    ahead, in_window = ahead[:k], ahead[1:] - ahead[:k]
    if owner is None:
        return ahead, in_window
    return jnp.take_along_axis(ahead, owner, axis=0), jnp.take_along_axis(in_window, owner, axis=0)


def kept_under(passes: SelectPasses, kept, off, ahead, owner=None):
    """int32 (q, k, d): a target's kept keys under the offset ``off[i]`` of
    its window."""
    q, k, d = off.shape
    return passes.kept_below(kept, _kept_key(off, owner).reshape(q * k, d)).reshape(q, k, d) - ahead


def kept_next(passes: SelectPasses, kept, off, owner=None):
    """int32 (k, d): the least offset over ``off`` among a target's kept keys
    (no offset of a window where it keeps none)."""
    return passes.kept_above(kept, _kept_key(off, owner)) - _kept_key(jnp.zeros_like(off), owner)


def gather_pays(n: int, k: int) -> bool:
    """Can finishing on the kept keys beat the remaining passes over ``X``?
    It trades the counting passes of the digits the selection has not
    counted on ``X`` when it stops (``crowded`` says when: eight of sixteen
    on unit blobs near zero, at least four, twelve at most) and the
    successor pass for one gathering pass and as many small ops, and wins
    where the slots do not spill: from ``_GATHER_MIN_ROWS_A_CLUSTER`` rows a
    cluster (a target, for all rows) on one device, on data whose windows
    fit the slots by the ``_MOST_DIGITS_ON_X``-th digit."""
    return n >= k * _GATHER_MIN_ROWS_A_CLUSTER


def crowded(held, n: int):
    """Do the windows hold more keys than the slots are made for?
    ``held`` (k, d) are the keys in each pair's bracket, as the counting
    passes over the ``n`` rows gave them (summed over the devices of a split
    array, so that every device reads the same answer): in some feature
    more than one row in ``_GATHER_MOST_OF_X``. The selection asks after
    every digit it counts on ``X``: while the answer is yes it counts
    another, up to ``_MOST_DIGITS_ON_X``, and then tells ``gather`` to
    skip."""
    return jnp.max(jnp.sum(held, axis=0)) > n // _GATHER_MOST_OF_X


@functools.lru_cache(maxsize=64)
def select_passes(shape, k: int, labelled: bool, name: str, mesh=None, axis_name=None,
                  interpret: bool = False) -> SelectPasses:
    """The selection's passes for ``arr`` of ``shape`` and ``k`` targets a
    feature, by label or for all rows, their kernels named ``<name>.pass``
    and ``<name>.candidates``; those over the kept keys where
    ``gather_pays`` for one device's rows. On one device they are called
    bare. With a ``mesh`` they run under ``shard_map``, as KMeans' pass
    does: with an ``axis_name``, ``arr`` (and the labels) are split 0 over
    it in equal shards, each device passes over its rows (and keeps their
    keys), and the counts are ``psum``med (the successors: ``pmin``, the
    spill flag: ``pmax``) before any bracket narrows; without one ``arr``
    is replicated and every device runs the whole pass."""
    n, d = int(shape[0]), int(shape[1])
    p = mesh.devices.size if axis_name is not None else 1
    first = None
    if labelled:
        count = _count_program(n // p, d, k, interpret, name)
        nxt = _next_program(n // p, d, k, interpret, name)
    else:
        count = _count_all_program(n // p, d, k, interpret, name)
        nxt = _next_all_program(n // p, d, k, interpret, name)
        first = _first_program(n // p, d, interpret, name)
    gather = below = above = None
    if gather_pays(n // p, k):
        m = kept_lanes(n // p, d, k)
        gather = _gather_program(n // p, d, k, interpret, name, labelled)
        below = lambda kept, thr: _kept_program(m, d, thr.shape[0], False, interpret, name)(kept, thr)
        above = lambda kept, at: _kept_program(m, d, at.shape[0], True, interpret, name)(kept, at)
    if mesh is None:
        return SelectPasses(count, nxt, gather, below, above, first)
    rows, vec, lanes = P(axis_name, None), P(axis_name), P(None, axis_name)
    if axis_name is not None:
        local_count, local_next, local_first = count, nxt, first
        local_gather, local_below, local_above = gather, below, above

        def gather(*a):
            kept, spilled = local_gather(*a)
            return kept, jax.lax.pmax(spilled.astype(jnp.int32), axis_name) > 0

        count = lambda *a: jax.lax.psum(local_count(*a), axis_name)
        nxt = lambda *a: jax.lax.pmin(local_next(*a), axis_name)
        below = lambda *a: jax.lax.psum(local_below(*a), axis_name)
        above = lambda *a: jax.lax.pmin(local_above(*a), axis_name)
        first = lambda arr: jax.lax.psum(local_first(arr), axis_name)
    sm = functools.partial(_shard_map, mesh=mesh, check_vma=False)
    on_x = (rows, vec) if labelled else (rows,)
    over_kept = (
        sm(gather, in_specs=(*on_x, P(), P(), P()), out_specs=(lanes, P())),
        sm(below, in_specs=(lanes, P()), out_specs=P()),
        sm(above, in_specs=(lanes, P()), out_specs=P()),
    ) if gather_pays(n // p, k) else (None, None, None)
    return SelectPasses(
        sm(count, in_specs=(*on_x, P(), P()), out_specs=P()),
        sm(nxt, in_specs=(*on_x, P()), out_specs=P()),
        *over_kept,
        None if labelled else sm(first, in_specs=(rows,), out_specs=P()),
    )
