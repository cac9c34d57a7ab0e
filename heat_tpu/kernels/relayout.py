"""Lane-packing relayout engine: repartition copies at full VREG width.

ROADMAP ``reshape``: the split=1 1 GB repartition reads/writes its
operand at ~0.09 of HBM because the (10M, 25) target's minor dimension
fills only 25/128 lanes of every output VREG tile — the copy streams
(8, 128) tiles that are 80% pad. The fix is the tile-level instance of
arXiv:2112.01075's layout-vs-movement separation: plan the relayout as
cheap LOCAL layout changes around minimal collectives, where "cheap"
means every heavy copy runs on a lane-full representation.

This module is the layout half. A narrow-minor-dim shard ``(R, C)``
(``C`` ≪ 128 lanes) is *packed* by a tile-transposing copy that folds
rows into the lane axis — the flat row-major bytes are regrouped into a
``(p, R·C/p)``-shaped buffer whose minor dimension is huge, so every
VREG the collective and relayout steps touch is full. The
redistribution planner's chunked all-to-all / pivot / local-reshape
steps then run on the packed bytes, and the destination layout is
materialized by ONE unpack copy (the single lane-amplified write the
user's requested layout makes unavoidable).

Two primitives, each a pure permutation + zero-pad (bit-identical
between formulations by construction):

* ``pack_rows(x, rows, c_in, c_out, p)`` — flat ``(rows·c_in,)`` →
  grouped ``(p, rows·c_out/p)``: right-pad every ``c_in``-element row
  to ``c_out`` and gather each of the ``p`` column blocks contiguous
  (the send layout of a split-0 → split-last all-to-all).
* ``unpack_rows(x, rows, c_in, c_out, p)`` — the inverse: ungroup the
  ``p`` column blocks back into full-width rows and drop the per-row
  pad tail.

Each primitive has an **XLA formulation** (reshape/pad/transpose — the
portable reference) and a **Pallas tiled-copy kernel** that streams
flat VMEM blocks and performs the narrow-shape reinterpretation in
registers, so both HBM faces of the copy are full-lane 1-D streams
(``interpret=True`` runs the identical kernel logic on CPU, so tier-1
exercises it without a TPU). Dispatch: ``HEAT_TPU_RELAYOUT_KERNEL=0``
forces the XLA formulation everywhere, ``=1`` forces the Pallas kernel
where serviceable, and the default ``auto`` stays on the XLA
formulation: the installed TPU compiler refuses the kernels' in-register
reshape (``AUTO_REFUSAL``), so they are off the ``auto`` path until a
toolchain accepts them.

``lane_fill`` is the cost-model term the redistribution planner learns
from this module: the fraction of VREG lanes a buffer with the given
minor dimension fills (``minor / pad128(minor)``), i.e. the reciprocal
of the HBM amplification a copy through that layout pays.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core import gates as _gates

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_VMEM = pltpu.VMEM

__all__ = [
    "LANES",
    "SUBLANES",
    "PACK_FILL_THRESHOLD",
    "kernel_mode",
    "lane_fill",
    "last_decisions",
    "pack_rows",
    "pallas_serviceable",
    "unpack_rows",
]

#: VREG lane width (f32): the minor-dim quantum of TPU tiled layouts
LANES = 128
#: VREG sublane count (f32): the second-minor quantum
SUBLANES = 8

#: a relayout stage engages the packed form only when its buffer fills
#: less than this fraction of the lane axis — near-full minors gain
#: nothing from a repack and would pay the extra pack/unpack pass
PACK_FILL_THRESHOLD = 0.5

#: elements per Pallas block (both faces), bounding VMEM residency
_BLOCK_ELEMS = 1 << 16
_MAX_BLOCK_ROWS = 4096


def _mode() -> str:
    v = _gates.get("HEAT_TPU_RELAYOUT_KERNEL", "auto").strip().lower()
    if v in ("0", "off", "false"):
        return "0"
    if v in ("1", "on", "true", "force"):
        return "1"
    return "auto"


def kernel_mode() -> str:
    """The resolved ``HEAT_TPU_RELAYOUT_KERNEL`` mode (``"0"``/``"1"``/
    ``"auto"``) — introspection for tests and bench records. (Cache
    staleness on env flips is handled one level down: the executor keys
    its packed programs on the DECIDED ``impl_in``/``impl_out`` strings
    from :func:`decide`, which this mode feeds.)"""
    return _mode()


def _inc(name: str) -> None:
    from ..observability import telemetry

    telemetry.inc(name)


def lane_fill(minor: int) -> float:
    """Fraction of VREG lanes a buffer with minor dimension ``minor``
    fills once tiled to the 128-lane quantum — the planner's lane-fill
    cost term (``minor_dim/128`` below one full tile). 1/fill is the
    HBM amplification a copy through that layout pays."""
    minor = int(minor)
    if minor <= 0:
        return 1.0
    padded = -(-minor // LANES) * LANES
    return minor / padded


# ---------------------------------------------------------------------- #
# XLA formulations (the portable reference and the autotune floor)       #
# ---------------------------------------------------------------------- #
def _pack_rows_xla(x: jax.Array, rows: int, c_in: int, c_out: int, p: int):
    cpp = c_out // p
    xb = x.reshape(rows, c_in)
    if c_out != c_in:
        xb = jnp.pad(xb, ((0, 0), (0, c_out - c_in)))
    return jnp.transpose(xb.reshape(rows, p, cpp), (1, 0, 2)).reshape(p, rows * cpp)


def _unpack_rows_xla(x: jax.Array, rows: int, c_in: int, c_out: int, p: int):
    cpp = c_in // p
    xb = jnp.transpose(x.reshape(p, rows, cpp), (1, 0, 2)).reshape(rows, c_in)
    if c_out != c_in:
        xb = xb[:, :c_out]
    return xb.reshape(rows * c_out)


# ---------------------------------------------------------------------- #
# Pallas tiled-copy kernels                                              #
# ---------------------------------------------------------------------- #
def _block_rows(rows: int, c_max: int) -> int:
    """Largest divisor of ``rows`` whose block stays VMEM-resident.
    The grid iterates ``rows // B`` blocks; equal blocks keep the
    BlockSpecs static."""
    cap = max(1, min(rows, _MAX_BLOCK_ROWS, _BLOCK_ELEMS // max(c_max, 1)))
    best = 1
    for b in range(1, cap + 1):
        if rows % b == 0:
            best = b
    return best


@functools.lru_cache(maxsize=32)
def _pack_call(n_blocks: int, b: int, c_in: int, c_out: int, p: int, dtype_name: str, interpret: bool):
    """Tile-transposing pack: every grid step streams one flat
    ``(1, b·c_in)`` VMEM block in and one ``(p, b·c_out/p)`` block out —
    both HBM faces are wide; the narrow ``(b, c_in)`` shape exists only
    in registers."""
    cpp = c_out // p
    dt = jnp.dtype(dtype_name)

    def kernel(i_ref, o_ref):
        xb = i_ref[...].reshape(b, c_in)
        if c_out != c_in:
            xb = jnp.concatenate([xb, jnp.zeros((b, c_out - c_in), dt)], axis=1)
        o_ref[...] = jnp.transpose(xb.reshape(b, p, cpp), (1, 0, 2)).reshape(p, b * cpp)

    return pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[pl.BlockSpec((1, 1, b * c_in), lambda g: (g, 0, 0), memory_space=_VMEM)],
        out_specs=pl.BlockSpec((p, b * cpp), lambda g: (0, g), memory_space=_VMEM),
        out_shape=jax.ShapeDtypeStruct((p, n_blocks * b * cpp), dt),
        interpret=interpret,
    )


@functools.lru_cache(maxsize=32)
def _unpack_call(n_blocks: int, b: int, c_in: int, c_out: int, p: int, dtype_name: str, interpret: bool):
    cpp = c_in // p
    dt = jnp.dtype(dtype_name)

    def kernel(i_ref, o_ref):
        xb = jnp.transpose(i_ref[...].reshape(p, b, cpp), (1, 0, 2)).reshape(b, c_in)
        if c_out != c_in:
            xb = xb[:, :c_out]
        o_ref[...] = xb.reshape(1, 1, b * c_out)

    return pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[pl.BlockSpec((p, b * cpp), lambda g: (0, g), memory_space=_VMEM)],
        out_specs=pl.BlockSpec((1, 1, b * c_out), lambda g: (g, 0, 0), memory_space=_VMEM),
        out_shape=jax.ShapeDtypeStruct((n_blocks, 1, b * c_out), dt),
        interpret=interpret,
    )


def pallas_serviceable(rows: int, c_in: int, c_out: int, p: int) -> bool:
    """Shape-level predicate: would the Pallas tiled-copy kernel serve
    this pack/unpack? (A 1-row block always divides ``rows``, so this
    is mostly a VMEM-residency gate.)"""
    if rows <= 0 or p <= 0:
        return False
    c_max = max(c_in, c_out)
    return 0 < c_max <= _BLOCK_ELEMS


def _pack_rows_pallas(x, rows, c_in, c_out, p):
    b = _block_rows(rows, max(c_in, c_out))
    interpret = jax.default_backend() != "tpu"
    return _pack_call(rows // b, b, c_in, c_out, p, jnp.dtype(x.dtype).name, interpret)(
        x.reshape(rows // b, 1, b * c_in)
    )


def _unpack_rows_pallas(x, rows, c_in, c_out, p):
    b = _block_rows(rows, max(c_in, c_out))
    interpret = jax.default_backend() != "tpu"
    out = _unpack_call(rows // b, b, c_in, c_out, p, jnp.dtype(x.dtype).name, interpret)(x)
    return out.reshape(rows * c_out)


# ---------------------------------------------------------------------- #
# dispatch (HEAT_TPU_RELAYOUT_KERNEL; the XLA formulation serves auto)   #
# ---------------------------------------------------------------------- #
#: why ``auto`` never picks the Pallas kernels on the TPU backend: the
#: installed Mosaic (jax 0.9.0 / libtpu 0.0.34) refuses the in-register
#: reinterpretation the kernels exist for. Pinned by
#: tests/test_chip_compile.py, which fails when a toolchain accepts it.
AUTO_REFUSAL = (
    "Mosaic: infer-vector-layout: unsupported shape cast "
    "(tpu.reshape of a flat lane vector to a narrow-minor block)"
)

_DECISIONS: dict = {}


def last_decisions() -> dict:
    """Copy of the dispatcher's path decisions and their reasons:
    {(op, rows, c_in, c_out, p, dtype): {"impl": …, "why": …}}."""
    return {k: dict(v) for k, v in _DECISIONS.items()}


def decide(op: str, rows: int, c_in: int, c_out: int, p: int, dtype_name: str) -> str:
    """The implementation (``"xla"``/``"pallas"``) serving this
    pack/unpack signature under the current mode. Called eagerly by the
    executor at program-build time so the decision is part of the
    program cache key. ``=1`` forces the Pallas kernel (interpret mode
    off-TPU; on the TPU backend the compiler's refusal then raises);
    ``auto`` stays on the XLA formulation, see :data:`AUTO_REFUSAL`."""
    mode = _mode()
    if mode == "0":
        impl, why = "xla", "gate=0"
    elif mode == "1" and pallas_serviceable(rows, c_in, c_out, p):
        impl, why = "pallas", "gate=1"
    elif mode == "1":
        _inc("relayout.kernel.fallback")
        impl, why = "xla", "gate=1: shape not serviceable"
    else:
        impl, why = "xla", f"auto: kernel off auto — {AUTO_REFUSAL}"
    _DECISIONS[(op, rows, c_in, c_out, p, dtype_name)] = {"impl": impl, "why": why}
    return impl


def pack_rows(x: jax.Array, rows: int, c_in: int, c_out: int, p: int, impl: str | None = None) -> jax.Array:
    """Flat ``(rows·c_in,)`` → grouped ``(p, rows·c_out/p)``: every
    ``c_in``-element row is right-padded with zeros to ``c_out`` and
    the ``p`` column blocks are gathered contiguous (the send layout of
    the packed split-0 → split-minor all-to-all). ``c_out % p == 0``,
    ``c_out ≥ c_in``. Pure permutation + zero-pad: the XLA and Pallas
    formulations are bit-identical by construction."""
    if c_out % p or c_out < c_in:
        raise ValueError(f"pack_rows: need p | c_out and c_out >= c_in, got {c_in}->{c_out} over p={p}")
    if impl is None:
        impl = decide("pack", rows, c_in, c_out, p, jnp.dtype(x.dtype).name)
    if impl == "pallas":
        _inc("relayout.kernel.hit")
        return _pack_rows_pallas(x, rows, c_in, c_out, p)
    return _pack_rows_xla(x, rows, c_in, c_out, p)


def unpack_rows(x: jax.Array, rows: int, c_in: int, c_out: int, p: int, impl: str | None = None) -> jax.Array:
    """Inverse of :func:`pack_rows`: grouped ``(p, rows·c_in/p)`` →
    flat ``(rows·c_out,)`` with the per-row pad tail dropped
    (``c_in % p == 0``, ``c_out ≤ c_in``)."""
    if c_in % p or c_out > c_in:
        raise ValueError(f"unpack_rows: need p | c_in and c_out <= c_in, got {c_in}->{c_out} over p={p}")
    if impl is None:
        impl = decide("unpack", rows, c_in, c_out, p, jnp.dtype(x.dtype).name)
    if impl == "pallas":
        _inc("relayout.kernel.hit")
        return _unpack_rows_pallas(x, rows, c_in, c_out, p)
    return _unpack_rows_xla(x, rows, c_in, c_out, p)
