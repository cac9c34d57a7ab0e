"""Dense linear algebra basics.

API parity with /root/reference/heat/core/linalg/basics.py (``matmul`` at
basics.py:421-1097, ``dot`` at :244, ``inv`` at :310, ``det`` at :158,
``norm``/``matrix_norm``/``vector_norm`` at :1113-1389, ``outer`` at
:1390, ``trace`` at :1641, ``transpose`` at :2056, ``tril``/``triu`` at
:2126-2240). The reference implements matmul as an explicit block-cyclic
SUMMA with Ibcast/Isend rings (basics.py:664-1097); here the contraction is
a sharded ``jnp.matmul``/``einsum`` under GSPMD — XLA emits the equivalent
collective schedule over ICI, and the MXU does the block math. The split
rules of the reference (result split by operand splits, basics.py:421-436)
are preserved as output sharding constraints.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from typing import List, Optional, Tuple, Union

from .. import types
from .._operations import __binary_op as _binary_op, _spanned
from ..communication import sanitize_comm
from ..dndarray import DNDarray
from ..sanitation import sanitize_in
from ..stride_tricks import sanitize_axis

__all__ = [
    "cross",
    "det",
    "dot",
    "inv",
    "matmul",
    "matrix_norm",
    "norm",
    "outer",
    "projection",
    "trace",
    "transpose",
    "tril",
    "triu",
    "vdot",
    "vecdot",
    "vector_norm",
]


@functools.lru_cache(maxsize=128)
def _cmatmul_program(
    mesh, axis_name: str, m: int, lk: int, n: int, jdtype: str, precision,
    pipelined: bool,
):
    """Compiled collective-matmul program for the contraction-split case
    (``a.split == 1``, ``b.split == 0``): ``C = Σ_q A_q B_q`` as a
    ppermute reduce-scatter ring whose per-hop partial block matmul
    (MXU) rides under the in-flight hop (ICI), then a ring gather of
    the reduced row chunks (``kernels.cmatmul.ring_matmul_reduce``).
    Replicated output, consistent across devices (each chunk is summed
    once, in fixed ring order) and bit-identical between the sequential
    and pipelined issue orders."""
    from ...kernels import cmatmul as _cm
    from jax import shard_map as _shard_map
    from jax.sharding import PartitionSpec as _P

    p = mesh.devices.size
    # jdtype rides only in the lru_cache key: operands arrive pre-cast

    def kernel(a_loc, b_loc):
        with _cm.stamp_scope("matmul"):
            return _cm.ring_matmul_reduce(
                a_loc, b_loc, axis_name, p, precision=precision, pipelined=pipelined
            )

    mapped = _shard_map(
        kernel,
        mesh=mesh,
        in_specs=(_P(None, axis_name), _P(axis_name, None)),
        out_specs=_P(None, None),
        check_vma=False,
    )

    def fn(a_phys, b_phys):
        out = mapped(a_phys, b_phys)
        return out if out.shape[0] == m else out[:m]

    return jax.jit(fn)


def _collective_matmul_eligible(a: DNDarray, b: DNDarray) -> bool:
    """The collective-matmul form serves exactly the contraction-split
    2-D case — ``a`` column-split against ``b`` row-split, the one
    matmul whose GSPMD schedule is a full-reduction barrier. Gated by
    ``kernels.cmatmul.ring_enabled`` (``HEAT_TPU_REDIST_OVERLAP``)."""
    return (
        a.ndim == 2
        and b.ndim == 2
        and a.split == 1
        and b.split == 0
        and not a._is_planar
        and not b._is_planar
        and a.comm.is_distributed()
    )


def _wrap(result: jax.Array, split: Optional[int], ref: DNDarray) -> DNDarray:
    comm = ref.comm
    gshape = tuple(int(s) for s in result.shape)
    if split is not None and result.ndim > 0:
        split = split % result.ndim
        result = comm.shard(result, split)
    else:
        split = None
    return DNDarray(
        result,
        gshape,
        types.canonical_heat_type(result.dtype),
        split,
        ref.device,
        ref.comm,
    )


def cross(a: DNDarray, b: DNDarray, axisa: int = -1, axisb: int = -1, axisc: int = -1, axis: int = -1) -> DNDarray:
    """Cross product of 3-element vectors (reference: basics.py cross)."""
    sanitize_in(a), sanitize_in(b)
    promoted = types.promote_types(a.dtype, b.dtype).jax_type()
    result = jnp.cross(
        a.larray.astype(promoted), b.larray.astype(promoted), axisa=axisa, axisb=axisb, axisc=axisc
    )
    split = a.split if a.split is not None else b.split
    if split is not None and split >= result.ndim:
        split = None
    return _wrap(result, split, a)


# past this order a distributed 2-D operand's inv/det runs the blocked
# ring-LU suite (factorizations.py) instead of handing the sharded
# logical array to XLA's one-device LU kernel — which GSPMD serves by
# gathering and replicating the whole operand (the SL102/SL106 shape
# the shardlint golden fixture pins)
_BLOCKED_MIN_N = 512


def _blocked_linalg_eligible(a: DNDarray) -> bool:
    return (
        a.ndim == 2
        and not a._is_planar
        and a.split in (0, 1)
        and a.comm.is_distributed()
        and int(a.shape[0]) >= _BLOCKED_MIN_N
    )


def det(a: DNDarray) -> DNDarray:
    """Determinant of (batched) square matrices (reference: basics.py:158
    implements distributed LU with row bcasts).

    Distributed 2-D operands of order >= ``_BLOCKED_MIN_N`` run the
    blocked ring-lookahead LU (``factorizations._lu_factor``) and read
    the determinant off ``sign · prod(diag(U))`` — no gather-and-
    replicate of the operand (ISSUE 19). Smaller or batched operands
    keep XLA's on-device LU."""
    sanitize_in(a)
    if a.ndim < 2 or a.shape[-1] != a.shape[-2]:
        raise ValueError(f"expected square matrix, got shape {a.shape}")
    if _blocked_linalg_eligible(a):
        from .factorizations import _lu_factor

        _pvec, _l, u, sign = _lu_factor(a)
        jt = u.dtype.jax_type()
        result = sign.astype(jt) * jnp.prod(jnp.diagonal(u.larray))
        return _wrap(result, None, a)
    arr = a.larray
    if types.heat_type_is_exact(a.dtype):
        arr = arr.astype(jnp.float32)
    result = jnp.linalg.det(arr)
    split = a.split if a.split is not None and a.split < a.ndim - 2 else None
    return _wrap(result, split, a)


def dot(a: DNDarray, b: DNDarray, out: Optional[DNDarray] = None) -> Union[DNDarray, float]:
    """Dot product following numpy semantics (reference: basics.py:244)."""
    sanitize_in(a), sanitize_in(b)
    if a._is_planar or b._is_planar:
        from .. import complex_planar as _cp

        if out is not None:
            raise _cp.policy_error("ht.dot with out= on complex operands")
        return _cp.dot(a, b)
    if a.ndim == 1 and b.ndim == 1:
        # inner product: local mul + sum; all-reduce over split emitted by XLA
        promoted = types.promote_types(a.dtype, b.dtype).jax_type()
        result = jnp.dot(a.larray.astype(promoted), b.larray.astype(promoted))
        ret = _wrap(result, None, a)
        if out is not None:
            out.larray = ret.larray
            return out
        return ret
    if a.ndim == 2 and b.ndim == 2:
        ret = matmul(a, b)
        if out is not None:
            out.larray = ret.larray
            return out
        return ret
    raise NotImplementedError("ht.dot not implemented for given dimensions")


def inv(a: DNDarray) -> DNDarray:
    """Inverse of (batched) square matrices (reference: basics.py:310
    distributed Gauss-Jordan).

    Distributed 2-D operands of order >= ``_BLOCKED_MIN_N`` factor once
    through the blocked ring-lookahead LU and back-substitute the
    identity block-column-wise (``factorizations._solve_factored``) —
    the operand and its inverse stay split the whole way, replacing the
    gather-and-replicate ``jnp.linalg.inv`` path (ISSUE 19; see
    MIGRATING.md). Smaller or batched operands keep XLA's on-device
    kernel."""
    sanitize_in(a)
    if a.ndim < 2 or a.shape[-1] != a.shape[-2]:
        raise ValueError(f"expected square matrix, got shape {a.shape}")
    if _blocked_linalg_eligible(a):
        from .. import factories
        from .factorizations import _lu_factor, _solve_factored

        pvec, l_arr, u_arr, _sign = _lu_factor(a)
        rhs = factories.eye(
            (int(a.shape[0]),) * 2, dtype=l_arr.dtype, split=0,
            device=a.device, comm=a.comm,
        )
        x = _solve_factored("lu", rhs, l_arr, u_arr, pvec)
        return x if x.split == a.split else x.resplit(a.split)
    arr = a.larray
    if types.heat_type_is_exact(a.dtype):
        arr = arr.astype(jnp.float32)
    result = jnp.linalg.inv(arr)
    return _wrap(result, a.split, a)


def matmul(
    a: DNDarray, b: DNDarray, allow_resplit: bool = False, precision=None
) -> DNDarray:
    """Matrix product of two DNDarrays (reference: basics.py:421).

    Reference schedule: case analysis over (a.split, b.split) with a
    block-cyclic SUMMA ring of Ibcast/Isend (basics.py:664-1097). Here the
    global contraction is handed to XLA with sharded operands; GSPMD
    partitions the einsum and inserts the collectives (the same
    all-gather/reduce-scatter dataflow SUMMA hand-codes), scheduled onto
    ICI with compute/comm overlap.

    Result split follows the reference rules (basics.py:421-436):
    a.split=0 → out split 0; b.split=1 → out split 1;
    a.split=1, b.split=0 → replicated (full reduction).
    """
    sanitize_in(a), sanitize_in(b)
    if a.ndim < 1 or b.ndim < 1:
        raise ValueError("matmul requires at least 1-dimensional operands")

    if a._is_planar or b._is_planar:
        from .. import complex_planar as _cp

        return _cp.matmul(a, b, precision=precision)
    promoted = types.promote_types(a.dtype, b.dtype)

    from ...kernels import cmatmul as _cm

    if _collective_matmul_eligible(a, b) and _cm.ring_enabled():
        # the collective-matmul form (ISSUE 6): the contraction-split
        # product's reduction decomposed into a ppermute ring so each
        # partial block matmul lands under the in-flight hop, instead of
        # GSPMD's full-reduction barrier. HEAT_TPU_REDIST_OVERLAP=0 is
        # the escape hatch back to the barrier schedule below.
        jt = promoted.jax_type()
        comm = a.comm
        fn = _cmatmul_program(
            comm.mesh,
            comm.axis_name,
            int(a.shape[0]),
            int(a._phys.shape[1]) // comm.size,
            int(b.shape[1]),
            np.dtype(jt).name,
            precision,
            True,
        )
        return _wrap(fn(a._phys.astype(jt), b._phys.astype(jt)), None, a)

    arr_a = a.larray.astype(promoted.jax_type())
    arr_b = b.larray.astype(promoted.jax_type())

    # precision: None = chip default (bf16 MXU passes for f32, the same
    # trade torch-CUDA's tf32 default makes); "highest" forces f32-exact
    # accumulation at ~3x the MXU passes. jax.default_matmul_precision
    # also applies as ambient context.
    result = jnp.matmul(arr_a, arr_b, precision=precision)

    # output split per reference rules, generalized to batched dims
    out_ndim = result.ndim
    split = None
    if a.ndim >= 2 and a.split == a.ndim - 2:
        split = out_ndim - 2
    elif b.ndim >= 2 and b.split == b.ndim - 1:
        split = out_ndim - 1
    elif a.split is not None and a.ndim > 2 and a.split < a.ndim - 2:
        split = a.split
    elif b.split is not None and b.ndim > 2 and b.split < b.ndim - 2:
        split = b.split
    return _wrap(result, split, a)


def matrix_norm(
    a: DNDarray,
    axis: Optional[Tuple[int, int]] = None,
    keepdims: bool = False,
    ord: Union[int, str, None] = None,
) -> DNDarray:
    """Matrix norm (reference: basics.py:1113)."""
    sanitize_in(a)
    if axis is None:
        if a.ndim < 2:
            raise ValueError("matrix_norm requires at least 2 dimensions")
        axis = (a.ndim - 2, a.ndim - 1)
    ax = sanitize_axis(a.shape, axis)
    if not isinstance(ax, tuple) or len(ax) != 2:
        raise ValueError("axis must be a 2-tuple")
    arr = a.larray
    if types.heat_type_is_exact(a.dtype):
        arr = arr.astype(jnp.float32)
    result = jnp.linalg.matrix_norm(
        jnp.moveaxis(arr, ax, (-2, -1)), ord=ord if ord is not None else "fro", keepdims=False
    )
    if keepdims:
        result = jnp.expand_dims(jnp.expand_dims(result, ax[0]), ax[1] if ax[1] > ax[0] else ax[1])
        result = jnp.broadcast_to(result, tuple(1 if i in ax else s for i, s in enumerate(a.shape)))
    split = a.split if a.split is not None and a.split not in ax else None
    if split is not None and not keepdims:
        split = split - sum(1 for x in ax if x < split)
    return _wrap(result, split, a)


def norm(
    a: DNDarray,
    dim: Optional[Union[int, Tuple[int, ...]]] = None,
    ord: Union[int, float, str, None] = None,
    keepdim: bool = False,
    axis=None,
    keepdims=None,
) -> DNDarray:
    """Vector or matrix norm (reference: basics.py:1238)."""
    sanitize_in(a)
    if axis is not None:
        dim = axis
    if keepdims is not None:
        keepdim = keepdims
    if dim is None and ord is None:
        return vector_norm(a.flatten() if a.ndim != 1 else a, keepdims=False)
    if isinstance(dim, tuple) and len(dim) == 2:
        return matrix_norm(a, axis=dim, keepdims=keepdim, ord=ord)
    if dim is None and a.ndim == 2 and ord is not None and ord not in (2, -2):
        return matrix_norm(a, keepdims=keepdim, ord=ord)
    return vector_norm(a, axis=dim, keepdims=keepdim, ord=2 if ord is None else ord)


def outer(a: DNDarray, b: DNDarray, out: Optional[DNDarray] = None, split: Optional[int] = None) -> DNDarray:
    """Outer product of two vectors (reference: basics.py:1390 implements a
    Bcast ring per rank; the sharded broadcast product is the same
    dataflow)."""
    sanitize_in(a), sanitize_in(b)
    if a._is_planar or b._is_planar:
        from .. import complex_planar as _cp

        if out is not None:
            raise _cp.policy_error("ht.outer with out= on complex operands")
        return _cp.outer(a, b, split=split)
    promoted = types.promote_types(a.dtype, b.dtype).jax_type()
    result = jnp.outer(a.larray.astype(promoted), b.larray.astype(promoted))
    if split is None:
        split = 0 if (a.split is not None or b.split is not None) else None
    ret = _wrap(result, split, a)
    if out is not None:
        out.larray = ret.larray.astype(out.dtype.jax_type())
        return out
    return ret


def projection(a: DNDarray, b: DNDarray) -> DNDarray:
    """Projection of vector a onto vector b (reference: basics.py)."""
    sanitize_in(a), sanitize_in(b)
    if a.ndim != 1 or b.ndim != 1:
        raise RuntimeError(f"projection requires 1-D vectors, got {a.ndim}, {b.ndim}")
    scale = dot(a, b) / dot(b, b)
    return _wrap(scale.larray * b.larray, b.split, b)


def trace(a: DNDarray, offset: int = 0, axis1: int = 0, axis2: int = 1, dtype=None, out=None) -> DNDarray:
    """Sum along diagonals (reference: basics.py:1641)."""
    sanitize_in(a)
    if a.ndim < 2:
        raise ValueError("trace requires at least 2 dimensions")
    result = jnp.trace(a.larray, offset=offset, axis1=axis1, axis2=axis2)
    if dtype is not None:
        result = result.astype(types.canonical_heat_type(dtype).jax_type())
    ax = sanitize_axis(a.shape, (axis1, axis2))
    split = a.split if a.split is not None and a.split not in ax else None
    if split is not None:
        split = split - sum(1 for x in ax if x < split)
    ret = _wrap(result, split, a)
    if a.ndim == 2:
        # scalar result: reference returns a Python-scalar-like 0-dim array
        pass
    if out is not None:
        out.larray = ret.larray
        return out
    return ret


def transpose(a: DNDarray, axes: Optional[List[int]] = None) -> DNDarray:
    """Permute array dimensions (reference: basics.py:2056 — local permute
    plus split remap; identical here, with the sharding constraint moved)."""
    sanitize_in(a)
    if axes is None:
        axes = tuple(reversed(range(a.ndim)))
    else:
        axes = tuple(sanitize_axis(a.shape, int(ax)) for ax in axes)
        if sorted(axes) != list(range(a.ndim)):
            raise ValueError(f"axes do not match array dimensions, got {axes}")
    if a._is_planar:
        from .. import complex_planar as _cp

        return _cp.transpose(a, axes)
    result = jnp.transpose(a.larray, axes)
    split = axes.index(a.split) if a.split is not None else None
    return _wrap(result, split, a)


def tril(m: DNDarray, k: int = 0) -> DNDarray:
    """Lower triangle (reference: basics.py:2126)."""
    sanitize_in(m)
    arr = m.larray
    if m.ndim == 1:
        arr = jnp.tile(arr, (arr.shape[0], 1))
        result = jnp.tril(arr, k=k)
        split = 0 if m.split is not None else None
        return _wrap(result, split, m)
    return _wrap(jnp.tril(arr, k=k), m.split, m)


def triu(m: DNDarray, k: int = 0) -> DNDarray:
    """Upper triangle (reference: basics.py:2183)."""
    sanitize_in(m)
    arr = m.larray
    if m.ndim == 1:
        arr = jnp.tile(arr, (arr.shape[0], 1))
        result = jnp.triu(arr, k=k)
        split = 0 if m.split is not None else None
        return _wrap(result, split, m)
    return _wrap(jnp.triu(arr, k=k), m.split, m)


def vdot(x1: DNDarray, x2: DNDarray) -> DNDarray:
    """Conjugated dot product of flattened arrays (reference: basics.py)."""
    sanitize_in(x1), sanitize_in(x2)
    if x1._is_planar or x2._is_planar:
        from .. import complex_planar as _cp

        return _cp.vdot(x1, x2)
    promoted = types.promote_types(x1.dtype, x2.dtype).jax_type()
    result = jnp.vdot(x1.larray.astype(promoted), x2.larray.astype(promoted))
    return _wrap(result, None, x1)


def vecdot(x1: DNDarray, x2: DNDarray, axis: Optional[int] = None, keepdims: bool = False) -> DNDarray:
    """Vector dot product along ``axis`` (reference: basics.py vecdot)."""
    sanitize_in(x1), sanitize_in(x2)
    if axis is None:
        axis = -1
    if x1._is_planar or x2._is_planar:
        from .. import complex_planar as _cp

        return _cp.vecdot(x1, x2, axis=axis, keepdims=keepdims)
    promoted = types.promote_types(x1.dtype, x2.dtype).jax_type()
    prod = jnp.conj(x1.larray.astype(promoted)) * x2.larray.astype(promoted)
    result = jnp.sum(prod, axis=axis, keepdims=keepdims)
    out_ndim = result.ndim
    split = x1.split if x1.split is not None else x2.split
    if split is not None:
        norm_axis = axis % max(prod.ndim, 1)
        if split == norm_axis:
            split = None
        elif not keepdims and split > norm_axis:
            split -= 1
        if split is not None and split >= out_ndim:
            split = None
    return _wrap(result, split, x1)


def vector_norm(
    x: DNDarray,
    axis: Optional[Union[int, Tuple[int, ...]]] = None,
    keepdims: bool = False,
    ord: Union[int, float, None] = 2,
) -> DNDarray:
    """Vector norm (reference: basics.py:1316)."""
    sanitize_in(x)
    arr = x.larray
    if types.heat_type_is_exact(x.dtype):
        arr = arr.astype(jnp.float32)
    ax = sanitize_axis(x.shape, axis)
    result = jnp.linalg.vector_norm(arr, axis=ax, keepdims=keepdims, ord=2 if ord is None else ord)
    if ax is None:
        split = None
    else:
        axes = (ax,) if isinstance(ax, int) else ax
        split = x.split
        if split is not None:
            if split in axes:
                split = None
            elif keepdims:
                pass
            else:
                split = split - sum(1 for a in axes if a < split)
    return _wrap(result, split, x)


# neither goes through the generic op wrappers: a dispatch span of its own
matmul = _spanned("ht.op.matmul", matmul)
transpose = _spanned("ht.op.transpose", transpose)

DNDarray.transpose = transpose
DNDarray.__matmul__ = lambda self, other: matmul(self, other)

from ..communication import register_mesh_cache as _register_mesh_cache

# collective-matmul programs bake mesh geometry: cleared when
# init_distributed rebuilds the world
_register_mesh_cache(_cmatmul_program)
