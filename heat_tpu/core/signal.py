"""Signal processing: distributed convolution.

API parity with /root/reference/heat/core/signal.py (``convolve``). The
reference implements 1-D convolution by exchanging halos of size
``v.size//2`` between neighboring ranks (signal.py:125-127: ``get_halo`` +
``array_with_halos``) followed by a local conv1d — the canonical stencil
pattern. Here the same dataflow is ONE jitted ``shard_map`` program: each
shard ``ppermute``s its head to the previous neighbor (the halo exchange)
and runs a local valid-mode convolution; all three modes reduce to the
same program over a zero-extended logical input. Kernels larger than the
shard block fall back to the sharded global convolution (the reference
raises in that regime; we stay correct).
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from . import types
from .dndarray import DNDarray

__all__ = ["convolve"]


@functools.lru_cache(maxsize=128)
def _conv_program(mesh: Mesh, axis_name: str, n_phys: int, k: int, jdtype: str):
    """One-shot stencil program: right-halo exchange (k-1 rows from the
    next shard via ``ppermute``) + local valid conv. Shard r produces
    outputs [r·B, (r+1)·B) of the zero-extended convolution."""
    p = mesh.devices.size

    def body(x, w):
        x = x.reshape(-1)  # (B,) local block
        w = w.reshape(-1)  # (k,) replicated
        if p > 1 and k > 1:
            head = x[: k - 1]
            halo = lax.ppermute(head, axis_name, [(i + 1, i) for i in range(p - 1)])
            ext = jnp.concatenate([x, halo])
        elif k > 1:
            ext = jnp.concatenate([x, jnp.zeros((k - 1,), dtype=x.dtype)])
        else:
            ext = x
        # TPU matmul default is bf16 accumulation — the reference computes
        # in full precision, so request it explicitly
        return jnp.convolve(ext, w, mode="valid", precision=lax.Precision.HIGHEST)

    fn = shard_map(body, mesh=mesh, in_specs=(P(axis_name), P()), out_specs=P(axis_name))
    return jax.jit(fn)


def convolve(a: DNDarray, v: DNDarray, mode: str = "full") -> DNDarray:
    """1-D convolution of ``a`` with kernel ``v`` (reference:
    signal.py convolve; modes full/same/valid)."""
    from . import factories

    if not isinstance(a, DNDarray):
        a = factories.array(a)
    if not isinstance(v, DNDarray):
        v = factories.array(v)
    if a.ndim != 1 or v.ndim != 1:
        raise ValueError("only 1-dimensional input arrays are allowed")
    if mode not in ("full", "same", "valid"):
        raise ValueError(f"unsupported mode {mode!r}, use full/same/valid")
    if mode == "same" and v.shape[0] % 2 == 0:
        raise ValueError("mode 'same' cannot be used with even-sized kernel")
    if a.shape[0] < v.shape[0]:
        a, v = v, a

    n, k = a.shape[0], v.shape[0]
    promoted = types.promote_types(a.dtype, v.dtype)
    if types.heat_type_is_exact(promoted):
        compute = types.promote_types(promoted, types.float32)
    else:
        compute = promoted
    arr = a.larray.astype(compute.jax_type())
    ker = v.larray.astype(compute.jax_type())

    # zero-extension turning every mode into sliding valid windows:
    # out[g] = sum_s a_ext[g+s] * v[k-1-s] = full[g + (k-1) - left], so
    # 'same' needs left = k - 1 - (k-1)//2 = k//2 — the operand swap above
    # can make k even even though even *kernels* were rejected pre-swap
    # (reference signal.py:195 handles the post-swap even case the same way)
    left = {"full": k - 1, "same": k // 2, "valid": 0}[mode]
    right = {"full": k - 1, "same": k - 1 - k // 2, "valid": 0}[mode]
    out_len = n + left + right - (k - 1)

    comm = a.comm
    split = a.split
    block = -(-(n + left + right) // comm.size)
    if split is not None and comm.size > 1 and k - 1 <= block:
        work = jnp.pad(arr, (left, right)) if (left or right) else arr
        phys = comm.shard(work, 0)
        prog = _conv_program(
            comm.mesh, comm.axis_name, int(phys.shape[0]), int(k),
            np.dtype(compute.jax_type()).name,
        )
        result = prog(phys, ker)[:out_len]
    else:
        result = jnp.convolve(arr, ker, mode=mode, precision=lax.Precision.HIGHEST)

    if types.heat_type_is_exact(promoted):
        result = jnp.round(result).astype(promoted.jax_type())

    gshape = (int(out_len),)
    if split is not None:
        result = comm.shard(result, 0)
    return DNDarray(
        result, gshape, types.canonical_heat_type(result.dtype), split, a.device, a.comm
    )

from .communication import register_mesh_cache

# entries bake mesh geometry: cleared when init_distributed rebuilds the world
register_mesh_cache(_conv_program)
