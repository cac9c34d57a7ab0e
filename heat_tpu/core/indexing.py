"""Indexing functions.

API parity with /root/reference/heat/core/indexing.py (``nonzero``,
``where``). ``nonzero`` in the reference returns a split=0 result of the
local nonzero plus rank offsets (indexing.py nonzero); the output shape is
data-dependent, so it is evaluated eagerly here.
"""

from __future__ import annotations

import jax.numpy as jnp

from . import types
from . import _operations
from .dndarray import DNDarray
from ..observability.tracing import span as _span
from .sanitation import sanitize_in

__all__ = ["nonzero", "where"]


def nonzero(x: DNDarray) -> DNDarray:
    """Indices of non-zero elements as an (nnz, ndim) array, split=0 when
    x is distributed (reference: indexing.py nonzero — rank-local results
    plus split offset). Distributed inputs run the gather-free per-shard
    count + balanced-compaction schedule (``parallel.distributed_nonzero``);
    the operand is never all-gathered."""
    sanitize_in(x)
    comm = x.comm
    if (
        x.split is not None
        and x.ndim > 0
        and comm.is_distributed()
        and 0 not in x.gshape  # zero-extent arrays are stored replicated
    ):
        from . import parallel as _parallel

        arr = x if x.split == 0 else x.resplit(0)
        phys, nnz = _parallel.distributed_nonzero(
            arr._phys, int(arr.gshape[0]), comm.mesh, comm.axis_name
        )
        gshape = (nnz, x.ndim)
        if nnz == 0:
            return DNDarray(comm.shard(phys, 0), gshape, types.int64, 0, x.device, comm)
        return DNDarray(phys, gshape, types.int64, 0, x.device, comm)
    with _span("ht.sync.read", what="nonzero.eager"):  # jnp.nonzero reads the count: its shape is the data's
        idx = jnp.nonzero(x.larray)
    stacked = jnp.stack(idx, axis=1) if x.ndim > 0 else jnp.zeros((0, 0), dtype=types.index_jax_type())
    stacked = stacked.astype(types.index_jax_type())
    split = 0 if x.split is not None else None
    gshape = tuple(int(s) for s in stacked.shape)
    if split is not None:
        stacked = x.comm.shard(stacked, split)
    return DNDarray(stacked, gshape, types.int64, split, x.device, x.comm)


def where(cond: DNDarray, x=None, y=None) -> DNDarray:
    """Ternary where / nonzero (reference: indexing.py where)."""
    if x is None and y is None:
        return nonzero(cond)
    if x is None or y is None:
        raise TypeError("either both or neither of x and y should be given")
    sanitize_in(cond)
    x_t = x if isinstance(x, DNDarray) else None
    y_t = y if isinstance(y, DNDarray) else None
    promoted = types.result_type(x, y)
    jt = promoted.jax_type()
    xv = x.larray.astype(jt) if isinstance(x, DNDarray) else x
    yv = y.larray.astype(jt) if isinstance(y, DNDarray) else y
    result = jnp.where(cond.larray, xv, yv)
    split = cond.split
    if split is None:
        for t in (x_t, y_t):
            if t is not None and t.split is not None and t.ndim == result.ndim:
                split = t.split
                break
    gshape = tuple(int(s) for s in result.shape)
    if split is not None and split < result.ndim:
        result = cond.comm.shard(result, split)
    else:
        split = None
    return DNDarray(
        result, gshape, types.canonical_heat_type(result.dtype), split, cond.device, cond.comm
    )
