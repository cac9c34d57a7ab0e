"""Pallas TPU kernel: fused row-sketch + Frobenius accumulation.

The hSVD sketch (`svdtools._sketched_uds_both`) is pass-bound: what it
costs is the number of streaming reads of A at HBM speed, and the least
is two (``w = g @ A``, then ``z = A @ qw``). The norm ``‖A‖²_F`` would
be a third: it touches every element of A independently of the row
sketch, and XLA does NOT fuse the two (a dot and a reduction over the
same operand lower to separate reads). This kernel streams each
(TM × TN) tile of A through VMEM once and feeds it to BOTH consumers:

    per tile:  w[:, tile_n] += g[:, tile_m] @ A_tile      (MXU)
               norm_partial[tile_n] += Σ A_tile²          (VPU)

so pass 1 is one read (757 GB/s at the 4.29 GB north-star shard). Pass 2
needs no kernel: as ONE dot XLA reads f32 A at the same speed
(``_sketched_uds_both`` says why it must be one dot there, not a loop).

Grid layout is the canonical accumulator pattern: the contraction
dimension (m) is the INNER grid axis, so the ``w`` output block and the
per-column norm partial stay resident in VMEM across all m-steps and are
written back once per n-tile.

Gates: TPU backend, x64 off (platform default), f32 operands, tile-
divisible shapes, l ≤ 32 (the sketch width is ~25). Everything else
falls back to the XLA formulation, which is also the numerical oracle
(tests assert ≤1e-4 relative agreement; the kernel accumulates the dot
in f32 like the DEFAULT-precision XLA path)."""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

try:  # pragma: no cover — present in all TPU-capable jax builds
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _VMEM = pltpu.VMEM
except Exception:  # pragma: no cover
    pl = None
    _VMEM = None

__all__ = ["sketch_with_norm", "dual_sketch_with_norm"]

_L_PAD = 32  # sketch-width rows padded to a full sublane multiple

# one-view sketch widths: the co-range sketch ℓ ≈ 2k̂+1 needs more rows
_L2_PAD = 64   # row-sketch width cap for the dual kernel
_K_PAD = 32    # column-sketch width cap


@functools.lru_cache(maxsize=32)
def _fused_call(m: int, n: int, tm: int, tn: int):
    grid = (n // tn, m // tm)

    def kernel(g_ref, a_ref, w_ref, np_ref):
        i_n = pl.program_id(0)
        i_m = pl.program_id(1)

        @pl.when(i_m == 0)
        def _init_w():
            w_ref[...] = jnp.zeros_like(w_ref)

        # the norm block is CONSTANT across the whole grid (resident in
        # VMEM for the entire run); init exactly once
        @pl.when((i_m == 0) & (i_n == 0))
        def _init_norm():
            np_ref[...] = jnp.zeros_like(np_ref)

        a = a_ref[...]
        w_ref[...] += jnp.dot(g_ref[...], a, preferred_element_type=jnp.float32)
        # broadcast-accumulate over a full (8,128) tile — Mosaic rejects
        # scalar/sub-tile VMEM stores; every entry carries the total
        np_ref[...] = np_ref[...] + jnp.sum(a * a)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((_L_PAD, tm), lambda i_n, i_m: (0, i_m), memory_space=_VMEM),
            pl.BlockSpec((tm, tn), lambda i_n, i_m: (i_m, i_n), memory_space=_VMEM),
        ],
        out_specs=[
            pl.BlockSpec((_L_PAD, tn), lambda i_n, i_m: (0, i_n), memory_space=_VMEM),
            pl.BlockSpec((8, 128), lambda i_n, i_m: (0, 0), memory_space=_VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((_L_PAD, n), jnp.float32),
            jax.ShapeDtypeStruct((8, 128), jnp.float32),
        ],
    )


def _pick_tile(extent: int, candidates=(1024, 512, 256, 128)) -> int:
    for c in candidates:
        if extent % c == 0:
            return c
    return 0


@functools.lru_cache(maxsize=32)
def _dual_call(m: int, n: int, tm: int, tn: int):
    """One-view kernel: each (tm × tn) tile of A feeds THREE consumers in
    a single HBM read — the row sketch ``w += g @ A`` (MXU), the column
    sketch ``y += A @ Ω`` (MXU), and the Frobenius partial (VPU). This is
    what makes the single-pass hSVD actually single-pass: XLA lowers the
    two matmuls as two separate streams over A.

    Residency plan (grid = m outer, n inner; VMEM ≈ 16 MB):
    - ``y`` block (tm, K_PAD): the canonical accumulator — n is the inner
      axis, so the block stays resident across its contraction steps;
    - ``w`` (L2_PAD, n): its contraction axis is m (the OUTER axis), so a
      tiled block would be revisited non-consecutively and lose its
      accumulation — instead the WHOLE w lives in VMEM for the entire run
      (constant block index; ≤ 2 MB at the north-star n=8192) and each
      step accumulates into its n-tile slice;
    - the norm tile is the same constant (8, 128) block as sketch_with_norm.
    """
    grid = (m // tm, n // tn)

    def kernel(g_ref, om_ref, a_ref, w_ref, y_ref, np_ref):
        i_m = pl.program_id(0)
        i_n = pl.program_id(1)

        @pl.when((i_m == 0) & (i_n == 0))
        def _init_w_norm():
            w_ref[...] = jnp.zeros_like(w_ref)
            np_ref[...] = jnp.zeros_like(np_ref)

        @pl.when(i_n == 0)
        def _init_y():
            y_ref[...] = jnp.zeros_like(y_ref)

        a = a_ref[...]
        sl = pl.dslice(i_n * tn, tn)
        w_ref[:, sl] += jnp.dot(g_ref[...], a, preferred_element_type=jnp.float32)
        y_ref[...] += jnp.dot(a, om_ref[...], preferred_element_type=jnp.float32)
        np_ref[...] = np_ref[...] + jnp.sum(a * a)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((_L2_PAD, tm), lambda i_m, i_n: (0, i_m), memory_space=_VMEM),
            pl.BlockSpec((tn, _K_PAD), lambda i_m, i_n: (i_n, 0), memory_space=_VMEM),
            pl.BlockSpec((tm, tn), lambda i_m, i_n: (i_m, i_n), memory_space=_VMEM),
        ],
        out_specs=[
            pl.BlockSpec((_L2_PAD, n), lambda i_m, i_n: (0, 0), memory_space=_VMEM),
            pl.BlockSpec((tm, _K_PAD), lambda i_m, i_n: (i_m, 0), memory_space=_VMEM),
            pl.BlockSpec((8, 128), lambda i_m, i_n: (0, 0), memory_space=_VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((_L2_PAD, n), jnp.float32),
            jax.ShapeDtypeStruct((m, _K_PAD), jnp.float32),
            jax.ShapeDtypeStruct((8, 128), jnp.float32),
        ],
    )


def dual_sketch_serviceable(l_total: int, k_hat: int, m: int, n: int) -> bool:
    """Shape-level predicate: would ``dual_sketch_with_norm`` serve this
    signature ON THE TPU BACKEND? Callers use it to refuse a
    ``single_pass`` request whose fallback would stream A three times —
    strictly worse than the 2-pass default the user opted out of."""
    if l_total > _L2_PAD or k_hat > _K_PAD:
        return False
    if _L2_PAD * n * 4 > 4 * 1024 * 1024:
        return False
    return bool(_pick_tile(m, (512, 256, 128)) and _pick_tile(n))


def dual_sketch_with_norm(g: jax.Array, omega: jax.Array, a: jax.Array):
    """Fused ``(g @ a, a @ omega, ‖a‖²_F)`` in ONE pass over ``a`` — the
    one-view (single-pass) hSVD's data movement — or None when the gates
    don't hold (the caller's XLA formulation is the fallback and the
    numerical oracle). Traceable; same gate style as sketch_with_norm.
    ``g``: (ℓ, m) row-sketch operator, ``omega``: (n, k̂) column-sketch
    operator, ℓ ≤ 64, k̂ ≤ 32."""
    if pl is None or jax.default_backend() != "tpu" or jax.config.jax_enable_x64:
        return None
    if a.dtype != jnp.float32 or g.dtype != jnp.float32 or omega.dtype != jnp.float32:
        return None
    if g.ndim != 2 or omega.ndim != 2 or a.ndim != 2:
        return None
    if g.shape[1] != a.shape[0] or omega.shape[0] != a.shape[1]:
        return None
    l, m = g.shape
    n, k_hat = omega.shape
    if l > _L2_PAD or k_hat > _K_PAD:
        return None
    # w stays whole in VMEM: bound its footprint (2 MB at n=8192) plus
    # the tile working set well under the ~16 MB budget
    if _L2_PAD * n * 4 > 4 * 1024 * 1024:
        return None
    tm, tn = _pick_tile(m, (512, 256, 128)), _pick_tile(n)
    if not tm or not tn:
        return None
    g_pad = jnp.pad(g, ((0, _L2_PAD - l), (0, 0))) if l < _L2_PAD else g
    om_pad = (
        jnp.pad(omega, ((0, 0), (0, _K_PAD - k_hat))) if k_hat < _K_PAD else omega
    )
    w_pad, y_pad, norm_tile = _dual_call(m, n, tm, tn)(g_pad, om_pad, a)
    return w_pad[:l], y_pad[:, :k_hat], norm_tile[0, 0]


def sketch_with_norm(g: jax.Array, a: jax.Array):
    """Fused ``(g @ a, ‖a‖²_F)`` in ONE pass over ``a``, or None when the
    kernel's gates don't hold (caller falls back to the two-pass XLA
    form). Traceable (pallas_call is a primitive), so it works inside the
    jitted sketch programs."""
    if pl is None or jax.default_backend() != "tpu" or jax.config.jax_enable_x64:
        return None
    if a.dtype != jnp.float32 or g.dtype != jnp.float32:
        return None
    if g.ndim != 2 or a.ndim != 2 or g.shape[1] != a.shape[0]:
        return None
    l, m = g.shape
    n = a.shape[1]
    if l > _L_PAD:
        return None
    tm, tn = _pick_tile(m), _pick_tile(n)
    if not tm or not tn:
        return None
    g_pad = jnp.pad(g, ((0, _L_PAD - l), (0, 0))) if l < _L_PAD else g
    w_pad, norm_tile = _fused_call(m, n, tm, tn)(g_pad, a)
    return w_pad[:l], norm_tile[0, 0]
