"""Pallas TPU kernels: the products of ``qr._gram_qr`` over the tall operand,
doing only the blocks that symmetry and the triangle leave.

The Gram form of the local QR (``qr._gram_qr``) has four products over an
``m x n`` operand, ``m`` a million: ``A^T A``, ``Q1 = A R1^-1`` with ``Q1^T
Q1`` from the same blocks, and the finish ``Q = Q1 + Q1 (R2^-1 - I)``. A Gram
matrix is symmetric and ``R^-1`` upper triangular with exact zeros below its
diagonal, so in column panels of ``p`` = 128 (the MXU's width) 28 of each
product's 64 blocks are known before it runs. Both kernels walk a 1-D grid of row blocks (``tm x n``
f32, double-buffered by the pipeline), keep the ``n x n`` operands resident in
VMEM, and for panel ``k`` multiply only the leading ``(k + 1) p`` columns:

    gram:   G[:(k+1)p, kp:(k+1)p] += x[:, :(k+1)p]^T x[:, kp:(k+1)p]
    apply:  y[:, kp:(k+1)p]        = x[:, :(k+1)p] W[:(k+1)p, kp:(k+1)p]  (+ x[:, kp:(k+1)p])

and the Gram matrix of ``y`` from the block just made, while it is in VMEM:
36 of 64 blocks at ``n`` 1024. The blocks left out are
never read or made: they are exactly zero (``W``) or exactly what the mirror
holds (``G``; ``gram`` returns the block upper triangle mirrored, a full
symmetric matrix). ``apply`` writes each block of its output itself (no
memset) and, with ``in_place``, over its input (``input_output_aliases``).

Precision is stated in bf16 passes, as the MXU runs an f32 product: six
(f32 accuracy) are Mosaic's ``HIGHEST`` on the f32 operands; Mosaic has no
``HIGH``, so for three the kernel splits its operands itself (``x = x0 + x1``
in bf16 parts, ``x0 y0 + x0 y1 + x1 y0`` with f32 accumulation: what ``HIGH``
multiplies), and for one it multiplies the first parts. A Gram matrix is
summed in two levels (``_FOLD_ROWS``).

MEASURED (TPU v5e, 1 048 576 x 1024 f32; builder's chip runs, PR 35, PERF.md
section 6), inside the one program of ``ht.linalg.qr``: ``A^T A`` at three
passes 20.9 ms (XLA's whole product 35.5), ``A R^-1`` with its Gram matrix at
six passes each 77.3 (152.4), the finish at three 19.5 (41.3 with its block
copies): 90 to 97 % of the MXU's peak for the blocks and passes paid. Panels
of 256: 22.5, 89.4, 22.0. ``HIGHEST`` and a three-part split of both operands
run alike (80.25 / 80.24 ms at 512 rows a step); 512 and 128 rows a step are
2 % slower than 256, 1024 12 %.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["gram", "apply", "serves"]

_F32, _BF16 = jnp.float32, jnp.bfloat16
_PANEL = 128  # columns of a panel: the MXU's width
_BLOCK_BYTES = 1 << 20  # a row block in VMEM: 256 x 1024 f32
_FOLD_ROWS = 8192  # rows whose Gram matrix is summed apart before it joins the whole one
_N_MAX = 1024  # columns up to which the n x n operands (two to four of them, some twice) stay resident in VMEM
_PARAMS = pltpu.CompilerParams(dimension_semantics=("arbitrary",), vmem_limit_bytes=64 << 20)
# Below six passes a product is a sum over bf16 parts that the kernel splits off its f32 operands: how many parts, and
# the pairs of them it multiplies, least term first. Six passes are Mosaic's own HIGHEST on the f32 operands.
_HI = jax.lax.Precision.HIGHEST
_TERMS = {1: ((0, 0),), 3: ((1, 0), (0, 1), (0, 0))}


def row_block(n: int) -> int:
    """Rows of a grid step's block of an ``m x n`` f32 operand."""
    return max(8, _BLOCK_BYTES // (4 * n) // 8 * 8)


def serves(m: int, n: int) -> bool:
    """Do the kernels take an ``m x n`` f32 operand? Where the columns fall
    into whole panels with the ``n x n`` operands resident in VMEM, from two
    row blocks on (the last may be ragged)."""
    return n % _PANEL == 0 and 0 < n <= _N_MAX and m > row_block(n)


def _n_parts(passes: int) -> int:
    return 0 if passes == 6 else 1 + max(i for i, _ in _TERMS[passes])


def split(x, passes: int):
    """``x`` (f32) as what a product of ``passes`` passes multiplies: itself
    for six, else its leading bf16 parts (``x = x0 + x1 + ...``)."""
    if passes == 6:
        return [x]
    parts = [x.astype(_BF16)]
    for _ in range(1, _n_parts(passes)):
        x = x - parts[-1].astype(_F32)
        parts.append(x.astype(_BF16))
    return parts


def _dot(a, b, passes: int):
    """``a b`` at ``passes`` passes, from what ``split`` made of the two."""
    if passes == 6:
        return jnp.dot(a[0], b[0], precision=_HI, preferred_element_type=_F32)
    acc = None
    for i, j in _TERMS[passes]:
        t = jnp.dot(a[i], b[j], preferred_element_type=_F32)
        acc = t if acc is None else acc + t
    return acc


def _add_upper_gram(x, g_ref, acc_ref, passes: int, steps: int):
    """``g_ref += x^T x`` on the block upper triangle, panel by panel, through
    ``acc_ref``: the blocks of ``_FOLD_ROWS`` rows are summed there first, so
    that the sum over a million rows is one of a hundred terms and not of
    thousands (max ``|Q^T Q - I|`` read 2.6e-6 with one level, PERF.md, PR 35)."""
    i, every = pl.program_id(0), max(1, _FOLD_ROWS // x.shape[0])

    @pl.when(i == 0)
    def _():
        g_ref[...] = jnp.zeros_like(g_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    parts = split(x, passes)
    parts_t = [q.T for q in parts]
    for k in range(x.shape[1] // _PANEL):
        w, cols = (k + 1) * _PANEL, slice(k * _PANEL, (k + 1) * _PANEL)
        acc_ref[:w, cols] += _dot([q[:w] for q in parts_t], [q[:, cols] for q in parts], passes)

    @pl.when(((i + 1) % every == 0) | (i == steps - 1))
    def _():
        g_ref[...] += acc_ref[...]
        acc_ref[...] = jnp.zeros_like(acc_ref)


def _valid_rows(x, m: int, tm: int):
    """``x`` with the rows of the last block that lie past row ``m`` zeroed
    (what a block holds there is unspecified)."""
    left = m - pl.program_id(0) * tm
    return jnp.where(jax.lax.broadcasted_iota(jnp.int32, x.shape, 0) < left, x, 0.0)


def _mirrored(g):
    """The full symmetric matrix of a block upper triangle."""
    return jnp.triu(g) + jnp.triu(g, 1).T


@functools.lru_cache(maxsize=32)
def _gram_call(m: int, n: int, passes: int, interpret: bool):
    tm = row_block(n)
    steps = pl.cdiv(m, tm)

    def kernel(x_ref, g_ref, acc_ref):
        x = x_ref[...]
        _add_upper_gram(_valid_rows(x, m, tm) if m % tm else x, g_ref, acc_ref, passes, steps)

    return pl.pallas_call(
        kernel,
        grid=(steps,),
        in_specs=[pl.BlockSpec((tm, n), lambda i: (i, 0), memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((n, n), lambda i: (0, 0), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n, n), _F32),
        scratch_shapes=[pltpu.VMEM((n, n), _F32)],
        compiler_params=_PARAMS,
        name="qr.tall.gram",
        interpret=interpret,
    )


def gram(x, passes: int, interpret: bool = False):
    """``x^T x`` of a tall f32 ``x``, at ``passes`` bf16 passes."""
    return _mirrored(_gram_call(*x.shape, passes, interpret)(x))


@functools.lru_cache(maxsize=32)
def _apply_call(m: int, n: int, passes: int, gram_passes, finish: bool, in_place: bool, interpret: bool):
    tm = row_block(n)
    steps, n_w, n_g = pl.cdiv(m, tm), _n_parts(passes), 2 if gram_passes else 0

    def kernel(x_ref, w_ref, y_ref, *refs):
        gram_refs, w_scratch = refs[:n_g], refs[n_g:]
        if w_scratch:
            # w's parts once, here: XLA drops a rounding to bf16 and back, so split outside they would all be w's first
            @pl.when(pl.program_id(0) == 0)
            def _():
                for ref, part in zip(w_scratch, split(w_ref[...], passes)):
                    ref[...] = part

        x = x_ref[...]
        parts, w_parts = split(x, passes), w_scratch or [w_ref]
        for k in range(n // _PANEL):
            w, cols = (k + 1) * _PANEL, slice(k * _PANEL, (k + 1) * _PANEL)
            y = _dot([q[:, :w] for q in parts], [r[:w, cols] for r in w_parts], passes)
            y_ref[:, cols] = x[:, cols] + y if finish else y
        if gram_passes:
            y = y_ref[...]
            _add_upper_gram(_valid_rows(y, m, tm) if m % tm else y, *gram_refs, gram_passes, steps)

    rows = pl.BlockSpec((tm, n), lambda i: (i, 0), memory_space=pltpu.VMEM)
    whole = pl.BlockSpec((n, n), lambda i: (0, 0), memory_space=pltpu.VMEM)
    out_shape, out_specs = [jax.ShapeDtypeStruct((m, n), _F32)], [rows]
    if gram_passes:
        out_shape.append(jax.ShapeDtypeStruct((n, n), _F32))
        out_specs.append(whole)
    return pl.pallas_call(
        kernel,
        grid=(steps,),
        in_specs=[rows, whole],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((n, n), _F32)] * (n_g // 2) + [pltpu.VMEM((n, n), _BF16)] * n_w,
        input_output_aliases={0: 0} if in_place else {},
        compiler_params=_PARAMS,
        name="qr.tall.apply",
        interpret=interpret,
    )


def apply(x, w, passes: int, gram_passes=None, finish: bool = False, in_place: bool = False, interpret: bool = False):
    """``(y, y^T y or None)`` with ``y = x w`` (``finish``: ``x + x w``) for
    a tall f32 ``x`` and an upper triangular ``w``, whose blocks under the
    diagonal blocks are never read; ``in_place``: ``y`` takes ``x``'s array."""
    out = _apply_call(*x.shape, passes, gram_passes, finish, in_place, interpret)(x, w)
    return out[0], (_mirrored(out[1]) if gram_passes else None)
