"""Generic distributed operation wrappers.

API parity with /root/reference/heat/core/_operations.py: ``__binary_op``
(_operations.py:22), ``__cum_op`` (:204), ``__local_op`` (:305),
``__reduce_op`` (:378). The reference versions interleave type promotion
with explicit redistribution (`sanitize_distribution`) and MPI collectives
(`Allreduce` when the reduction axis includes the split,
_operations.py:466-471; `Exscan` for cumulative ops).

TPU execution model: every wrapper routes through a CACHED JITTED CALLABLE
operating on the PHYSICAL (padded) arrays — one compiled XLA program per
(op, shape, dtype, split) configuration, with dtype casts, pad-neutral
refills and the zero-pad restore all fused into the same program and the
output sharding pinned via ``out_shardings``. Uneven shapes therefore pay
no per-op unpad→op→repad round trip, and a dispatch is one jitted call on
an already-sharded array. The reference's collectives appear implicitly: a
reduction over the sharded axis lowers to the same all-reduce over ICI.

Irregular cases (``where=``, non-hashable kwargs, ops that change rank
unexpectedly) fall back to an eager logical-array path with identical
semantics.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from typing import Callable, Optional, Union

from . import types
from . import _padding
from .dndarray import DNDarray
from .stride_tricks import broadcast_shape, sanitize_axis
from ..observability.instrument import observed_program_cache
from ..observability.tracing import span as _span

__all__ = []


def _as_dndarray(x, reference: DNDarray) -> DNDarray:
    """Promote scalars / array-likes to DNDarray on the reference's comm."""
    from . import factories

    if isinstance(x, DNDarray):
        return x
    return factories.array(
        x, device=reference.device, comm=reference.comm, split=None
    )


def _kw_key(kwargs: Optional[dict]):
    """Hashable snapshot of an op's kwargs, or None when not cacheable."""
    if not kwargs:
        return ()
    try:
        items = tuple(sorted(kwargs.items()))
        hash(items)
        return items
    except TypeError:
        return None


def _kw_split(kwargs: Optional[dict]):
    """Partition kwargs into (static_items, dyn_names, dyn_dtypes) +
    dyn_values. Float/complex scalars and arrays become TRACED arguments —
    baking them into the program cache key would recompile per value
    (e.g. ``ht.clip(x, max=hi)`` in a loop) and leak dead executables.
    Ints/bools/strings stay static: jnp ops require them at trace time
    (axis, decimals, mode). Returns None when uncacheable."""
    static = []
    dyn_names = []
    dyn_vals = []
    try:
        for k in sorted(kwargs or {}):
            v = kwargs[k]
            if v is None or isinstance(v, (bool, int, str, bytes)):
                static.append((k, v))
            elif isinstance(v, (float, complex)):
                dyn_names.append(k)
                dyn_vals.append(v)
            elif isinstance(v, (np.ndarray, jax.Array)):
                dyn_names.append(k)
                dyn_vals.append(v)
            elif isinstance(v, tuple):
                hash(v)
                static.append((k, v))
            else:
                return None
    except TypeError:
        return None
    dyn_dtypes = tuple(np.result_type(v).name for v in dyn_vals)
    return (tuple(static), tuple(dyn_names), dyn_dtypes), tuple(dyn_vals)


_mask_tail = _padding.mask_tail


def _pad_operand(arr, out_ndim: int, split: int, pext: int):
    """Align an operand's split-dim extent to the physical extent. A
    replicated operand carries the logical extent; pad it (shapes are
    static under trace, so this resolves at compile time). Extent-1
    dims broadcast as-is."""
    ndim = getattr(arr, "ndim", 0)
    dim = split - (out_ndim - ndim)
    if dim < 0:
        return arr
    ext = arr.shape[dim]
    if ext in (1, pext):
        return arr
    widths = [(0, 0)] * ndim
    widths[dim] = (0, pext - ext)
    return jnp.pad(arr, widths)


# neutral elements for pad refill when a reduction touches the split axis;
# "min"/"max" resolve against the input dtype inside the traced program
_REDUCE_NEUTRAL = {}


def _register_neutrals():
    table = [
        (("sum", "nansum"), 0),
        (("prod", "nanprod"), 1),
        (("min", "amin", "nanmin"), "max"),
        (("max", "amax", "nanmax"), "min"),
        (("all",), True),
        (("any",), False),
    ]
    for names, neutral in table:
        for name in names:
            fn = getattr(jnp, name, None)
            if fn is not None:
                _REDUCE_NEUTRAL[fn] = neutral


_register_neutrals()


def _resolve_neutral(tag, dtype):
    if tag == "max":
        return jnp.inf if jnp.issubdtype(dtype, jnp.inexact) else jnp.iinfo(dtype).max if jnp.issubdtype(dtype, jnp.integer) else True
    if tag == "min":
        return -jnp.inf if jnp.issubdtype(dtype, jnp.inexact) else jnp.iinfo(dtype).min if jnp.issubdtype(dtype, jnp.integer) else False
    return tag


# --------------------------------------------------------------------- #
# cached jitted executors                                               #
# --------------------------------------------------------------------- #
@observed_program_cache("op.binary", maxsize=4096)
def _binary_callable(op, comm, out_ndim, split, n, pext, cast, scalar1, scalar2, kw):
    """One compiled program: cast → align pads → op → restore zero pad.
    ``scalar1/2`` record which operands arrived as Python scalars — those
    keep their weak dtype so promotion matches eager numpy/jnp semantics."""
    def fn(a, b):
        if cast is not None:
            jt = jnp.dtype(cast)
            if not scalar1:
                a = a.astype(jt)
            if not scalar2:
                b = b.astype(jt)
        if split is not None:
            a = _pad_operand(a, out_ndim, split, pext)
            b = _pad_operand(b, out_ndim, split, pext)
        r = op(a, b, **dict(kw))
        if split is not None and pext != n:
            r = _mask_tail(r, split, n)
        return r

    return comm.jit_sharded(fn, out_ndim, split)


@observed_program_cache("op.unary", maxsize=4096)
def _unary_callable(op, comm, ndim, split, n, pext, cast, static_kw, dyn_names):
    def fn(arr, *dyn):
        kwargs = dict(static_kw)
        kwargs.update(zip(dyn_names, dyn))
        if cast is not None:
            arr = arr.astype(jnp.dtype(cast))
        r = op(arr, **kwargs)
        if split is not None and pext != n:
            r = _mask_tail(r, split, n)
        return r

    return comm.jit_sharded(fn, ndim, split)


@observed_program_cache("op.reduce", maxsize=4096)
def _reduce_callable(op, comm, split, n, pext, axes, keepdims, neutral, out_ndim, out_split, out_n, out_pext, kw):
    def fn(arr):
        if split is not None and pext != n and neutral is not None:
            arr = _mask_tail(arr, split, n, _resolve_neutral(neutral, arr.dtype))
        r = op(arr, axis=axes, keepdims=keepdims, **dict(kw))
        if not isinstance(r, jax.Array) and not hasattr(r, "ndim"):
            r = jnp.asarray(r)
        if out_split is not None and out_pext != out_n:
            r = _mask_tail(r, out_split, out_n)
        return r

    return comm.jit_sharded(fn, out_ndim, out_split)


@observed_program_cache("op.cum", maxsize=1024)
def _cum_callable(op, comm, ndim, split, n, pext, axis, cast):
    def fn(arr):
        if cast is not None:
            arr = arr.astype(jnp.dtype(cast))
        r = op(arr, axis=axis)
        if split is not None and pext != n:
            r = _mask_tail(r, split, n)
        return r

    return comm.jit_sharded(fn, ndim, split)


@functools.lru_cache(maxsize=4096)
def _local_probe_keeps_shape(op, shape, dtype, cast, static_kw, dyn_names, dyn_dtypes, dyn_shapes) -> bool:
    """True iff ``op`` maps an array of (shape, dtype[, cast]) to the same
    shape — the condition for running it on the physical array."""
    def probe(a, *dyn):
        kwargs = dict(static_kw)
        kwargs.update(zip(dyn_names, dyn))
        if cast is not None:
            a = a.astype(jnp.dtype(cast))
        return op(a, **kwargs)

    try:
        structs = [
            jax.ShapeDtypeStruct(sh, jnp.dtype(dt))
            for sh, dt in zip(dyn_shapes, dyn_dtypes)
        ]
        res = jax.eval_shape(probe, jax.ShapeDtypeStruct(shape, jnp.dtype(dtype)), *structs)
    except Exception:
        return False
    return hasattr(res, "shape") and tuple(res.shape) == tuple(shape)


def _phys_meta(x: DNDarray):
    """(logical n, physical ext) along the split axis, or (None, None)."""
    if x.split is None:
        return None, None
    return x.gshape[x.split], x._phys.shape[x.split]


# --------------------------------------------------------------------- #
# wrappers                                                              #
# --------------------------------------------------------------------- #
def __binary_op(
    operation: Callable,
    t1: Union[DNDarray, int, float],
    t2: Union[DNDarray, int, float],
    out: Optional[DNDarray] = None,
    where: Optional[DNDarray] = None,
    fn_kwargs: Optional[dict] = None,
) -> DNDarray:
    """Generic elementwise binary operation (reference: _operations.py:22).

    Promotes types on the torch/XLA lattice, broadcasts, resolves the
    output split by the dominant-operand rule (reference
    _operations.py:147-168) and executes ONE cached jitted program on the
    physical arrays; distribution matching is a resharding constraint
    instead of explicit redistribution.
    """
    fn_kwargs = fn_kwargs or {}

    if not isinstance(t1, DNDarray) and not isinstance(t2, DNDarray):
        raise TypeError(f"at least one operand must be a DNDarray, got {type(t1)}, {type(t2)}")

    ref = t1 if isinstance(t1, DNDarray) else t2

    scalar1 = not isinstance(t1, DNDarray)
    scalar2 = not isinstance(t2, DNDarray)

    promoted = types.result_type(t1, t2)
    # complex platform policy at the PROMOTION point: a real array times a
    # complex python scalar would otherwise enqueue a complex program
    # before the output DNDarray's constructor check — and one enqueued
    # complex op poisons the unsupporting backend for the whole process.
    # Under the planar policy the whole op routes to plane arithmetic.
    if types.heat_type_is_complexfloating(types.degrade64(promoted)):
        from . import complex_planar as _cp

        if _cp.is_planar(t1) or _cp.is_planar(t2) or _cp.active():
            return _cp.binary(operation, t1, t2, out=out, where=where, fn_kwargs=fn_kwargs)
        types.check_complex_platform(types.degrade64(promoted))
    jt = promoted.jax_type()

    # non-DNDarray array-likes become concrete arrays up front
    a1 = t1 if scalar1 else None
    a2 = t2 if scalar2 else None
    if scalar1 and not isinstance(t1, (int, float, complex, bool)):
        a1 = jnp.asarray(np.asarray(t1))
        scalar1 = False
    if scalar2 and not isinstance(t2, (int, float, complex, bool)):
        a2 = jnp.asarray(np.asarray(t2))
        scalar2 = False

    shape1 = () if a1 is not None and scalar1 else tuple(t1.shape) if isinstance(t1, DNDarray) else tuple(np.shape(a1))
    shape2 = () if a2 is not None and scalar2 else tuple(t2.shape) if isinstance(t2, DNDarray) else tuple(np.shape(a2))
    output_shape = broadcast_shape(shape1, shape2)
    out_ndim = len(output_shape)

    def _out_split(t):
        if not isinstance(t, DNDarray) or t.split is None:
            return None
        return t.split + (out_ndim - t.ndim)

    s1 = _out_split(t1)
    s2 = _out_split(t2)
    if s1 is not None and s2 is not None and s1 != s2:
        # align t2 to t1's split (reference redistributes the non-dominant operand)
        tgt = s1 - (out_ndim - t2.ndim)
        if tgt >= 0:
            t2 = t2.resplit(tgt)
        s2 = _out_split(t2)
    output_split = s1 if s1 is not None else s2
    # a broadcast dimension of extent 1 cannot carry the split; a
    # zero-extent output is stored replicated (comm.shard convention),
    # so pinning a split sharding on it would conflict
    if output_split is not None and output_shape[output_split] <= 1:
        output_split = None

    comm = ref.comm
    device = ref.device
    kw = _kw_key(fn_kwargs)

    if where is None and kw is not None:
        # fast path: one jitted program over physical operands
        n = output_shape[output_split] if output_split is not None else 0
        pext = _padding.pad_extent(n, comm.size) if output_split is not None else 0

        def _operand(t, a, is_scalar):
            if is_scalar or not isinstance(t, DNDarray):
                return a
            if output_split is not None and t.split is not None:
                if t.split + (out_ndim - t.ndim) == output_split:
                    # a logical extent-1 dim must BROADCAST; its physical
                    # pad extent would pair row-by-row instead
                    if t.gshape[t.split] == 1 and t._phys.shape[t.split] != 1:
                        return t.larray
                    return t._phys
            # replicated operand, operand split off the output split, or
            # output_split nulled (extent-1): the physical pad would either
            # fail to broadcast or leak pad rows — feed the logical view
            return t.larray

        x1 = _operand(t1, a1, scalar1)
        x2 = _operand(t2, a2, scalar2)
        prog = _binary_callable(
            operation, comm, out_ndim, output_split, n, pext, np.dtype(jt).name,
            scalar1, scalar2, kw,
        )
        result = prog(x1, x2)
        res_type = types.canonical_heat_type(result.dtype)
        if out is not None:
            from .sanitation import sanitize_out

            sanitize_out(out, output_shape, output_split, device)
            if out.split == output_split:
                out._set_phys(result.astype(out.dtype.jax_type()))
            else:
                out.larray = _padding.unpad(result, output_shape, output_split).astype(
                    out.dtype.jax_type()
                )
            return out
        return DNDarray(result, output_shape, res_type, output_split, device, comm)

    # eager fallback (where= masking, or uncacheable kwargs)
    b1 = a1 if scalar1 else (t1.larray.astype(jt) if isinstance(t1, DNDarray) else a1.astype(jt))
    b2 = a2 if scalar2 else (t2.larray.astype(jt) if isinstance(t2, DNDarray) else a2.astype(jt))
    result = operation(b1, b2, **fn_kwargs)

    if where is not None:
        w = where.larray if isinstance(where, DNDarray) else jnp.asarray(where)
        base = out.larray.astype(result.dtype) if out is not None else jnp.zeros_like(result)
        result = jnp.where(w, result, base)

    if output_split is not None:
        result = comm.shard(result, output_split)

    res_type = types.canonical_heat_type(result.dtype)
    if out is not None:
        from .sanitation import sanitize_out

        sanitize_out(out, output_shape, output_split, device)
        out.larray = _padding.unpad(result, output_shape, output_split).astype(out.dtype.jax_type())
        return out

    return DNDarray(result, output_shape, res_type, output_split, device, comm)


def __cum_op(
    operation: Callable,
    x: DNDarray,
    axis: int,
    out: Optional[DNDarray] = None,
    dtype=None,
) -> DNDarray:
    """Generic cumulative op (reference: _operations.py:204 — local cumop +
    ``Exscan`` + combine). A jnp cumulative op on the sharded array lowers
    to the same scan-with-carry across shards. Pad rows sit at the global
    tail, so the logical prefix of the cumulation is unaffected; the output
    pad is re-zeroed inside the program.
    """
    from .sanitation import sanitize_in

    sanitize_in(x)
    if isinstance(x, DNDarray) and x._is_planar:
        from . import complex_planar as _cp

        return _cp.cum(operation, x, axis, out=out, dtype=dtype)
    axis = sanitize_axis(x.shape, axis)
    if axis is None:
        raise NotImplementedError("cumulative operation over flattened array: ravel first")

    cast = None
    if dtype is not None:
        dtype = types.canonical_heat_type(dtype)
        cast = np.dtype(dtype.jax_type()).name

    comm = x.comm
    n, pext = _phys_meta(x)
    prog = _cum_callable(operation, comm, x.ndim, x.split, n, pext, axis, cast)
    result = prog(x._phys)
    res_type = types.canonical_heat_type(result.dtype)

    if out is not None:
        from .sanitation import sanitize_out

        sanitize_out(out, x.shape, x.split, x.device)
        if out.split == x.split:
            out._set_phys(result.astype(out.dtype.jax_type()))
        else:
            out.larray = _padding.unpad(result, x.shape, x.split).astype(out.dtype.jax_type())
        return out
    return DNDarray(result, x.shape, res_type, x.split, x.device, comm)


def __local_op(
    operation: Callable,
    x: DNDarray,
    out: Optional[DNDarray] = None,
    no_cast: bool = False,
    **kwargs,
) -> DNDarray:
    """Generic pure-local elementwise op (reference: _operations.py:305) —
    no communication; sharding is preserved by XLA elementwise semantics.
    Runs as one cached jitted program on the physical array (cast and
    zero-pad restore fused in).
    """
    from .sanitation import sanitize_in

    sanitize_in(x)
    if isinstance(x, DNDarray) and x._is_planar:
        from . import complex_planar as _cp

        return _cp.local(operation, x, out, kwargs)
    cast = None
    if not no_cast and types.heat_type_is_exact(x.dtype):
        promoted = types.promote_types(x.dtype, types.float32)
        cast = np.dtype(promoted.jax_type()).name

    ks = _kw_split(kwargs)
    if ks is None:
        # uncacheable kwargs: eager logical path
        return _local_op_eager(operation, x, out, cast, **kwargs)
    (static_kw, dyn_names, dyn_dtypes), dyn_vals = ks

    comm = x.comm
    n, pext = _phys_meta(x)
    dyn_shapes = tuple(tuple(np.shape(v)) for v in dyn_vals)
    if not _local_probe_keeps_shape(
        operation, tuple(x._phys.shape), np.dtype(x._phys.dtype).name, cast,
        static_kw, dyn_names, dyn_dtypes, dyn_shapes,
    ):
        return _local_op_eager(operation, x, out, cast, **kwargs)

    prog = _unary_callable(operation, comm, x.ndim, x.split, n, pext, cast, static_kw, dyn_names)
    result = prog(x._phys, *dyn_vals)
    res_type = types.canonical_heat_type(result.dtype)

    if out is not None:
        from .sanitation import sanitize_out

        sanitize_out(out, x.shape, x.split, x.device)
        if out.split == x.split:
            out._set_phys(result.astype(out.dtype.jax_type()))
        else:
            out.larray = _padding.unpad(result, x.shape, x.split).astype(out.dtype.jax_type())
        return out
    return DNDarray(result, x.shape, res_type, x.split, x.device, x.comm)


def _local_op_eager(operation, x, out, cast, **kwargs):
    arr = x.larray
    if cast is not None:
        arr = arr.astype(jnp.dtype(cast))
    result = operation(arr, **kwargs)
    res_type = types.canonical_heat_type(result.dtype)
    split = x.split if result.ndim == x.ndim else None
    output_shape = tuple(int(s) for s in result.shape)
    if split is not None:
        result = x.comm.shard(result, split)
    if out is not None:
        from .sanitation import sanitize_out

        sanitize_out(out, output_shape, split, x.device)
        out.larray = _padding.unpad(result, output_shape, split).astype(out.dtype.jax_type())
        return out
    return DNDarray(result, output_shape, res_type, split, x.device, x.comm)


def __reduce_op(
    partial_op: Callable,
    x: DNDarray,
    axis: Optional[Union[int, tuple]] = None,
    neutral=None,
    out: Optional[DNDarray] = None,
    keepdims: bool = False,
    **kwargs,
) -> DNDarray:
    """Generic reduction (reference: _operations.py:378 — local partial
    reduce followed by ``Allreduce`` when ``split in axis``,
    _operations.py:466-471). The jnp reduction over the sharded physical
    array makes XLA emit that same all-reduce over ICI; pad rows are
    refilled with the op's neutral element inside the compiled program
    when the reduction touches the split axis.
    """
    from .sanitation import sanitize_in

    sanitize_in(x)
    if isinstance(x, DNDarray) and x._is_planar:
        from . import complex_planar as _cp

        return _cp.reduce(partial_op, x, axis=axis, keepdims=keepdims, out=out, kwargs=kwargs)
    axis = sanitize_axis(x.shape, axis)

    kwargs.pop("out", None)
    kw = _kw_key(kwargs)

    # output split bookkeeping
    split = x.split
    if split is None or axis is None:
        output_split = None
    else:
        axes = (axis,) if isinstance(axis, int) else tuple(axis)
        if split in axes:
            output_split = None
        elif keepdims:
            output_split = split
        else:
            output_split = split - sum(1 for a in axes if a < split)

    comm = x.comm
    n, pext = _phys_meta(x)
    touches_split = split is not None and (
        axis is None or split in ((axis,) if isinstance(axis, int) else tuple(axis))
    )
    if neutral is None:
        neutral = _REDUCE_NEUTRAL.get(partial_op)

    if kw is None or (touches_split and pext != n and neutral is None):
        # eager logical fallback: unknown neutral with a real pad region
        result = partial_op(x.larray, axis=axis, keepdims=keepdims, **kwargs)
        if not isinstance(result, jax.Array):
            result = jnp.asarray(result)
        output_shape = tuple(int(s) for s in result.shape)
        if output_split is not None:
            result = comm.shard(result, output_split)
        res_type = types.canonical_heat_type(result.dtype)
        if out is not None:
            from .sanitation import sanitize_out

            sanitize_out(out, output_shape, output_split, x.device)
            out.larray = _padding.unpad(result, output_shape, output_split).astype(out.dtype.jax_type())
            return out
        return DNDarray(result, output_shape, res_type, output_split, x.device, comm)

    # fast path: compute output geometry statically
    in_shape = x.gshape
    if axis is None:
        output_shape = (1,) * x.ndim if keepdims else ()
    else:
        axes = (axis,) if isinstance(axis, int) else tuple(axis)
        if keepdims:
            output_shape = tuple(1 if i in axes else s for i, s in enumerate(in_shape))
        else:
            output_shape = tuple(s for i, s in enumerate(in_shape) if i not in axes)
    out_ndim = len(output_shape)
    out_n = output_shape[output_split] if output_split is not None else 0
    out_pext = _padding.pad_extent(out_n, comm.size) if output_split is not None else 0

    axes_key = axis if (axis is None or isinstance(axis, int)) else tuple(axis)
    prog = _reduce_callable(
        partial_op, comm, split, n, pext, axes_key, keepdims,
        neutral if (touches_split and pext != n) else None,
        out_ndim, output_split, out_n, out_pext, kw,
    )
    result = prog(x._phys)
    res_type = types.canonical_heat_type(result.dtype)

    if out is not None:
        from .sanitation import sanitize_out

        sanitize_out(out, output_shape, output_split, x.device)
        if out.split == output_split:
            out._set_phys(result.astype(out.dtype.jax_type()))
        else:
            out.larray = _padding.unpad(result, output_shape, output_split).astype(out.dtype.jax_type())
        return out
    return DNDarray(result, output_shape, res_type, output_split, x.device, comm)


def _spanned(name: str, op: Callable) -> Callable:
    """``op`` under one dispatch span ``name``: lookup, call and wrapping."""

    @functools.wraps(op)
    def dispatch(*args, **kwargs):
        with _span(name):
            return op(*args, **kwargs)

    return dispatch


__binary_op = _spanned("ht.op.binary", __binary_op)
__cum_op = _spanned("ht.op.cum", __cum_op)
__local_op = _spanned("ht.op.unary", __local_op)
__reduce_op = _spanned("ht.op.reduce", __reduce_op)

from .communication import register_mesh_cache

# entries bake mesh geometry: cleared when init_distributed rebuilds the world
register_mesh_cache(_binary_callable)
register_mesh_cache(_unary_callable)
register_mesh_cache(_reduce_callable)
register_mesh_cache(_cum_callable)
