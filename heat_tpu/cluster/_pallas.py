"""Pallas TPU kernel: fused distance + argmin + accumulate for KMeans.

The native-kernel layer SURVEY §7 plans ("custom Pallas kernels for hot
spots — fused distance+argmin for KMeans"). The reference's Lloyd update
(kmeans.py:74-100) materializes the (n × k) distance matrix and a one-hot
assignment matrix; the fused jnp step (`kmeans._lloyd_step`) still writes
both through HBM. This kernel streams row tiles of X through VMEM once per
iteration and never materializes either:

    per (TM × d) tile:  d² = ‖x‖² + ‖c‖² − 2 x·cᵀ   (MXU)
                        labels = argmin d²            (VPU)
                        acc   += onehotᵀ · [x | 1 | min d²]  (MXU)

The single (k × d+2) accumulator carries cluster sums, counts and
per-cluster inertia; HBM traffic is exactly one read of X per iteration —
the bandwidth lower bound.

MEASURED OUTCOME (TPU v5e, n=1M d=64 k=8): the XLA-fused jnp Lloyd step
runs at 1.14 ms/iter ≈ 225 GB/s — already at the HBM bandwidth bound —
while this kernel reaches 6.8 ms (k=8 lanes waste 15/16 of the VPU; the
(k × d+2) matmul underfills the MXU). Exactly the guide's rule: don't
hand-schedule what the compiler already fuses. The kernel is therefore
OPT-IN (``use_pallas=True``), kept as the validated native-kernel path
(numerics match the jnp step to 2e-6) and as the scaffold for shapes
where XLA's fusion does fall short (very large k, fused multi-metric).
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_VMEM = pltpu.VMEM

__all__ = ["fused_assign_program", "pallas_available"]


def pallas_available() -> bool:
    """True when the backend can execute the compiled kernel (gate for the
    opt-in path; auto-selection stays on the XLA-fused formulation, which
    measures at the bandwidth bound — see module docstring)."""
    return (
        jax.default_backend() == "tpu"
        and jax.device_count() == 1
        and not jax.config.jax_enable_x64  # Mosaic rejects x64-mode traces
    )


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _make_kernel(tm: int, n: int, k: int):
    def kernel(x_ref, c_ref, acc_ref):
        # every scalar is pinned to a ≤32-bit dtype: x64 mode would
        # otherwise leak int64/float64 into the kernel, which Mosaic rejects
        f1 = jnp.float32(1.0)
        f0 = jnp.float32(0.0)
        i = pl.program_id(0)
        # the grid's last tile may reach past row n: what it reads there is
        # unspecified, so those rows are zeroed before any arithmetic
        # (0 * NaN would poison the accumulator matmul) and masked below
        row_ids = jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
        valid = (i.astype(jnp.int32) * jnp.int32(tm) + row_ids) < jnp.int32(n)
        x = jnp.where(valid, x_ref[:].astype(jnp.float32), f0)  # (TM, d)
        c = c_ref[:].astype(jnp.float32)          # (k, d)
        x2 = jnp.sum(x * x, axis=1, keepdims=True)
        c2 = jnp.sum(c * c, axis=1, keepdims=True).T
        d2 = x2 + c2 - jnp.float32(2.0) * jnp.dot(x, c.T, preferred_element_type=jnp.float32)
        d2 = jnp.maximum(d2, f0)                  # (TM, k)
        dmin = jnp.min(d2, axis=1, keepdims=True)
        # first-argmin via min-reduction over indices (Mosaic's argmin
        # primitive rejects the int64 index dtype x64 mode implies)
        col_ids = jax.lax.broadcasted_iota(jnp.int32, (tm, k), 1)
        labels = jnp.min(
            jnp.where(d2 == dmin, col_ids, jnp.int32(k)), axis=1, keepdims=True
        )
        onehot = col_ids == labels
        onehot = jnp.where(valid & onehot, f1, f0)
        ones = jnp.where(valid, f1, f0)
        # [x | 1 | min d²]: one MXU matmul yields sums, counts AND
        # per-cluster inertia in a single (k, d+2) accumulator
        xe = jnp.concatenate([x, ones, jnp.where(valid, dmin, f0)], axis=1)
        part = jnp.dot(onehot.T, xe, preferred_element_type=jnp.float32)

        @pl.when(i == 0)
        def _init():
            acc_ref[:] = jnp.zeros_like(acc_ref)

        acc_ref[:] += part

    return kernel


@functools.lru_cache(maxsize=64)
def fused_assign_program(n: int, d: int, k: int, jdtype: str, interpret: bool = False):
    """Compiled fused-assignment pass: (x (n,d), centers (k,d)) →
    (sums (k,d) f32, counts (k,) f32, inertia () f32)."""
    tm = max(8, min(1024, _round_up(min(n, 1024), 8)))
    kernel = _make_kernel(tm, n, k)
    call = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(n, tm),),
        in_specs=[
            pl.BlockSpec((tm, d), lambda i: (i, 0), memory_space=_VMEM),
            pl.BlockSpec((k, d), lambda i: (0, 0), memory_space=_VMEM),
        ],
        out_specs=pl.BlockSpec((k, d + 2), lambda i: (0, 0), memory_space=_VMEM),
        out_shape=jax.ShapeDtypeStruct((k, d + 2), jnp.float32),
        interpret=interpret,
    )

    def run(x, centers):
        # x64 is off on TPU by platform policy, so Mosaic's grid/index
        # machinery traces with 32-bit scalars; the forced-x64
        # configuration is gated out in pallas_available. The last tile
        # is masked in the kernel: no padded copy of x is made
        acc = call(x.astype(jnp.dtype(jdtype)), centers.astype(jnp.dtype(jdtype)))
        return acc[:, :d], acc[:, d], jnp.sum(acc[:, d + 1])

    return jax.jit(run)
