"""Array manipulation operations.

API parity with /root/reference/heat/core/manipulations.py (37 exports;
the comm-heaviest module of the reference with 26 collective call-sites:
``concatenate`` at manipulations.py:390 harmonizes splits + redistributes,
``reshape`` at :1994 repartitions via Alltoallv with a ``new_split`` kw,
``sort`` at :2428 is a distributed sample-sort with an Alltoallv partition
exchange, ``unique`` at :3202, ``topk`` at :3981, ``roll`` at :2156,
``pad`` at :1328). Here each op computes on the logical global array and
re-establishes the output sharding; XLA emits the data movement (the
all-to-all a reshape-with-new-split needs) over ICI. ``sort`` along the
split axis runs ``core.parallel.distributed_sort`` — an odd-even block
merge-split network of ``ppermute`` exchanges (gather-free); off-split
sorts are lane-local XLA sorts.
"""

from __future__ import annotations

import functools as _functools

import numpy as np

import jax
import jax.numpy as jnp

from typing import Iterable, List, Optional, Sequence, Tuple, Union

from . import types
from . import _operations
from .communication import sanitize_comm
from .dndarray import DNDarray
from ..observability.tracing import span as _span
from .sanitation import sanitize_in, sanitize_sequence
from .stride_tricks import broadcast_shape, sanitize_axis, sanitize_shape

__all__ = [
    "balance",
    "broadcast_arrays",
    "broadcast_to",
    "collect",
    "column_stack",
    "concatenate",
    "diag",
    "diagonal",
    "dsplit",
    "expand_dims",
    "flatten",
    "flip",
    "fliplr",
    "flipud",
    "hsplit",
    "hstack",
    "moveaxis",
    "pad",
    "ravel",
    "redistribute",
    "repeat",
    "reshape",
    "resplit",
    "roll",
    "rot90",
    "row_stack",
    "shape",
    "sort",
    "split",
    "squeeze",
    "stack",
    "swapaxes",
    "tile",
    "topk",
    "unique",
    "vsplit",
    "vstack",
]


def _wrap(result: jax.Array, split: Optional[int], ref: DNDarray, dtype=None) -> DNDarray:
    """Construct an output DNDarray: capture logical shape, shard, wrap."""
    gshape = tuple(int(s) for s in result.shape)
    if split is not None and result.ndim > 0:
        split = split % result.ndim
        result = ref.comm.shard(result, split)
    else:
        split = None
    return DNDarray(
        result,
        gshape,
        dtype if dtype is not None else types.canonical_heat_type(result.dtype),
        split,
        ref.device,
        ref.comm,
    )


def balance(array: DNDarray, copy: bool = False) -> DNDarray:
    """Out-of-place balance (reference: manipulations.py balance). GSPMD
    layouts are canonical — returns the array (or a copy)."""
    sanitize_in(array)
    if copy:
        from . import memory

        return memory.copy(array)
    return array


def broadcast_arrays(*arrays: DNDarray) -> List[DNDarray]:
    """Broadcast arrays against each other (reference: manipulations.py
    broadcast_arrays)."""
    if not arrays:
        return []
    for a in arrays:
        sanitize_in(a)
    target = broadcast_shape(*[a.shape for a in arrays]) if len(arrays) > 1 else arrays[0].shape
    return [broadcast_to(a, target) for a in arrays]


def broadcast_to(x: DNDarray, shape: Tuple[int, ...]) -> DNDarray:
    """Broadcast to a new shape (reference: manipulations.py broadcast_to)."""
    sanitize_in(x)
    shape = sanitize_shape(shape)
    result = jnp.broadcast_to(x.larray, shape)
    split = x.split
    if split is not None:
        split = split + (len(shape) - x.ndim)
    return _wrap(result, split, x, dtype=x.dtype)


def collect(arr: DNDarray, target_rank: int = 0) -> DNDarray:
    """Gather the whole array onto one device (reference: manipulations.py
    collect / dndarray.collect_)."""
    sanitize_in(arr)
    out = arr.__copy__()
    out.collect_(target_rank)
    return out


def column_stack(arrays: Sequence[DNDarray]) -> DNDarray:
    """Stack 1-D/2-D arrays as columns (reference: manipulations.py
    column_stack)."""
    arrays = sanitize_sequence(arrays)
    ref = arrays[0]
    result = jnp.column_stack([a.larray for a in arrays])
    split = ref.split if ref.ndim >= 2 else (0 if ref.split is not None else None)
    return _wrap(result, split, ref)


@_functools.lru_cache(maxsize=1024)
def _concat_program(comm, metas, axis, out_split, jdtype):
    """One compiled program for concatenate: per-input unpad + cast →
    concatenate → output pad, out-sharding pinned (the reference's split
    harmonization + redistribution, manipulations.py:390, fused)."""
    from . import _padding

    def fn(*phys):
        logicals = [
            _padding.unpad(p_, gshape, split).astype(jnp.dtype(jdtype))
            for p_, (gshape, split) in zip(phys, metas)
        ]
        r = jnp.concatenate(logicals, axis=axis)
        return _padding.pad_logical(r, out_split, comm.size)

    ndim = len(metas[0][0])
    return comm.jit_sharded(fn, ndim, out_split)


def concatenate(arrays: Sequence[DNDarray], axis: int = 0) -> DNDarray:
    """Join arrays along an existing axis (reference: manipulations.py:390
    — split harmonization + redistribution; here jnp.concatenate on the
    logical arrays + one resharding)."""
    arrays = sanitize_sequence(arrays)
    if len(arrays) < 1:
        raise ValueError("need at least one array to concatenate")
    for a in arrays:
        sanitize_in(a)
    ref = arrays[0]
    axis = sanitize_axis(ref.shape, axis)
    if any(a._is_planar for a in arrays):
        from . import complex_planar as _cp

        return _cp.concat(arrays, axis)
    out_dtype = arrays[0].dtype
    for a in arrays[1:]:
        out_dtype = types.promote_types(out_dtype, a.dtype)
    jt = out_dtype.jax_type()
    split = next((a.split for a in arrays if a.split is not None), None)
    if (
        split is not None
        and all(x.ndim == ref.ndim for x in arrays)
        and all(x.size != 0 for x in arrays)
    ):
        out_shape = list(ref.shape)
        out_shape[axis] = sum(a.shape[axis] for a in arrays)
        metas = tuple((a.gshape, a.split) for a in arrays)
        prog = _concat_program(ref.comm, metas, axis, split, np.dtype(jt).name)
        phys = prog(*[a._phys for a in arrays])
        return DNDarray(phys, tuple(out_shape), out_dtype, split, ref.device, ref.comm)
    result = jnp.concatenate([a.larray.astype(jt) for a in arrays], axis=axis)
    return _wrap(result, split, ref, dtype=out_dtype)


def diag(a: DNDarray, offset: int = 0) -> DNDarray:
    """Extract or construct a diagonal (reference: manipulations.py diag)."""
    sanitize_in(a)
    if a.ndim == 1:
        result = jnp.diag(a.larray, k=offset)
        split = a.split
        return _wrap(result, split, a, dtype=a.dtype)
    return diagonal(a, offset=offset)


def diagonal(a: DNDarray, offset: int = 0, dim1: int = 0, dim2: int = 1) -> DNDarray:
    """Return the diagonal along dim1/dim2 (reference: manipulations.py
    diagonal)."""
    sanitize_in(a)
    if a.ndim < 2:
        raise ValueError("diagonal requires at least 2 dimensions")
    result = jnp.diagonal(a.larray, offset=offset, axis1=dim1, axis2=dim2)
    ax = sanitize_axis(a.shape, (dim1, dim2))
    split = a.split
    if split is not None:
        if split in ax:
            split = result.ndim - 1
        else:
            split = split - sum(1 for x in ax if x < split)
    return _wrap(result, split, a, dtype=a.dtype)


def dsplit(x: DNDarray, indices_or_sections) -> List[DNDarray]:
    """Split along axis 2 (reference: manipulations.py dsplit)."""
    return split(x, indices_or_sections, axis=2)


def expand_dims(a: DNDarray, axis: int) -> DNDarray:
    """Insert a new axis (reference: manipulations.py expand_dims)."""
    sanitize_in(a)
    axis = sanitize_axis(tuple(a.shape) + (1,), axis)
    if a._is_planar:
        from . import complex_planar as _cp

        return _cp.expand_dims(a, axis)
    result = jnp.expand_dims(a.larray, axis)
    split = a.split
    if split is not None and axis <= split:
        split += 1
    return _wrap(result, split, a, dtype=a.dtype)


def flatten(a: DNDarray) -> DNDarray:
    """Collapse into one dimension (reference: manipulations.py flatten —
    resplits to 0)."""
    sanitize_in(a)
    if a._is_planar:
        from . import complex_planar as _cp

        return _cp.flatten(a)
    result = jnp.ravel(a.larray)
    split = 0 if a.split is not None else None
    return _wrap(result, split, a, dtype=a.dtype)


def flip(a: DNDarray, axis: Optional[Union[int, Tuple[int, ...]]] = None) -> DNDarray:
    """Reverse element order along axis (reference: manipulations.py flip)."""
    sanitize_in(a)
    axis = sanitize_axis(a.shape, axis)
    if a._is_planar:
        from . import complex_planar as _cp

        return _cp.flip(a, axis)
    result = jnp.flip(a.larray, axis=axis)
    return _wrap(result, a.split, a, dtype=a.dtype)


def fliplr(a: DNDarray) -> DNDarray:
    """Flip along axis 1."""
    if a.ndim < 2:
        raise IndexError("expected at least 2-dimensional input")
    return flip(a, 1)


def flipud(a: DNDarray) -> DNDarray:
    """Flip along axis 0."""
    return flip(a, 0)


def hsplit(x: DNDarray, indices_or_sections) -> List[DNDarray]:
    """Split horizontally (reference: manipulations.py hsplit)."""
    if x.ndim < 2:
        return split(x, indices_or_sections, axis=0)
    return split(x, indices_or_sections, axis=1)


def hstack(arrays: Sequence[DNDarray]) -> DNDarray:
    """Stack horizontally (reference: manipulations.py hstack)."""
    arrays = sanitize_sequence(arrays)
    axis = 0 if arrays[0].ndim == 1 else 1
    return concatenate(arrays, axis=axis)


def moveaxis(x: DNDarray, source, destination) -> DNDarray:
    """Move axes to new positions (reference: manipulations.py moveaxis)."""
    sanitize_in(x)
    if isinstance(source, int):
        source = (source,)
    if isinstance(destination, int):
        destination = (destination,)
    source = [sanitize_axis(x.shape, s) for s in source]
    destination = [sanitize_axis(x.shape, d) for d in destination]
    if len(source) != len(destination):
        raise ValueError("source and destination must have the same number of elements")
    perm = [n for n in range(x.ndim) if n not in source]
    for dest, src in sorted(zip(destination, source)):
        perm.insert(dest, src)
    from .linalg import transpose

    return transpose(x, perm)


def pad(
    array: DNDarray,
    pad_width,
    mode: str = "constant",
    constant_values=0,
) -> DNDarray:
    """Pad the array (reference: manipulations.py:1328)."""
    sanitize_in(array)
    if mode not in ("constant",):
        raise NotImplementedError(f"pad mode {mode!r} not supported (reference supports constant)")
    # normalize pad_width like numpy/reference
    if isinstance(pad_width, int):
        widths = [(pad_width, pad_width)] * array.ndim
    else:
        pw = list(pad_width)
        if len(pw) and isinstance(pw[0], int):
            if len(pw) == 1:
                widths = [(pw[0], pw[0])] * array.ndim
            elif len(pw) == 2 and array.ndim == 1:
                widths = [tuple(pw)]
            else:
                raise ValueError(f"invalid pad_width {pad_width}")
        else:
            widths = [tuple(p) if not isinstance(p, int) else (p, p) for p in pw]
            if len(widths) == 1:
                widths = widths * array.ndim
            elif len(widths) < array.ndim:
                # reference pads trailing dimensions
                widths = [(0, 0)] * (array.ndim - len(widths)) + widths
    result = jnp.pad(array.larray, widths, constant_values=constant_values)
    return _wrap(result, array.split, array, dtype=array.dtype)


def ravel(a: DNDarray) -> DNDarray:
    """Flatten (view semantics where possible; reference:
    manipulations.py ravel)."""
    return flatten(a)


def redistribute(arr: DNDarray, lshape_map=None, target_map=None) -> DNDarray:
    """Out-of-place redistribute (reference: manipulations.py redistribute).
    GSPMD layouts are canonical — validates and returns a copy."""
    sanitize_in(arr)
    out = arr.__copy__()
    out.redistribute_(lshape_map=lshape_map, target_map=target_map)
    return out


def repeat(a, repeats, axis: Optional[int] = None) -> DNDarray:
    """Repeat elements (reference: manipulations.py repeat)."""
    from . import factories

    if not isinstance(a, DNDarray):
        a = factories.array(a)
    if isinstance(repeats, DNDarray):
        repeats = repeats.larray
    elif isinstance(repeats, (list, tuple, np.ndarray)):
        repeats = jnp.asarray(np.asarray(repeats))
    result = jnp.repeat(a.larray, repeats, axis=axis)
    if axis is None:
        split = 0 if a.split is not None else None
    else:
        split = a.split
    return _wrap(result, split, a, dtype=a.dtype)


@_functools.lru_cache(maxsize=1024)
def _reshape_program(comm, in_gshape, in_split, out_shape, out_split):
    """LEGACY reshape-with-repartition program (one monolithic
    unpad → reshape → pad with the output sharding pinned — XLA chose
    the collective, a full all-gather for the split-1 case). Kept as the
    ``HEAT_TPU_REDIST_PLANNER=0`` escape hatch; the live path plans a
    bounded-footprint schedule via ``heat_tpu.redistribution``."""
    from . import _padding

    def fn(phys):
        logical = _padding.unpad(phys, in_gshape, in_split)
        r = jnp.reshape(logical, out_shape)
        return _padding.pad_logical(r, out_split, comm.size)

    return comm.jit_sharded(fn, len(out_shape), out_split)


def _normalize_reshape_args(a, shape, new_split):
    """Shared shape/-1/``new_split`` resolution for :func:`reshape` AND
    ``ht.redistribution.explain(reshape=...)`` — ONE resolver, so the
    plan ``explain`` shows is built from exactly the (shape, new_split)
    the public call executes."""
    if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
        shape = tuple(shape[0])
    shape = list(shape)
    # resolve -1 placeholder
    neg = [i for i, s in enumerate(shape) if s == -1]
    if len(neg) > 1:
        raise ValueError("can only specify one unknown dimension")
    if neg:
        known = int(np.prod([s for s in shape if s != -1])) if len(shape) > 1 else 1
        if known == 0 or a.size % known != 0:
            raise ValueError(f"cannot reshape array of size {a.size} into shape {tuple(shape)}")
        shape[neg[0]] = a.size // known
    shape = sanitize_shape(tuple(shape))
    if int(np.prod(shape)) != a.size:
        raise ValueError(f"cannot reshape array of size {a.size} into shape {tuple(shape)}")
    if new_split is None:
        new_split = a.split
        if new_split is not None and new_split >= len(shape):
            # fewer output dims than the old split axis: clamp to the last
            new_split = len(shape) - 1
    return shape, sanitize_axis(shape, new_split)


def reshape(a: DNDarray, *shape, **kwargs) -> DNDarray:
    """Reshape without changing data (reference: manipulations.py:1994 —
    Alltoallv repartition with ``new_split`` kw; one jitted
    reshape+repartition program, the all-to-all emitted by XLA)."""
    sanitize_in(a)
    new_split = kwargs.pop("new_split", None)
    if kwargs:
        raise TypeError(f"reshape got unexpected keyword arguments {list(kwargs)}")
    shape, new_split = _normalize_reshape_args(a, shape, new_split)
    if a._is_planar:
        from . import complex_planar as _cp

        return _cp.reshape(a, tuple(shape), new_split)
    if new_split is not None and len(shape) > 0 and a.ndim > 0 and a.size != 0:
        # zero-SIZE arrays take the eager path: XLA stores them replicated,
        # which a pinned out_sharding cannot express
        from .. import redistribution as _redist

        if _redist.planner_enabled():
            # planner-routed repartition (cost-modeled schedule: split-0
            # pivot / lane-packed pivot / chunked all-to-all instead of
            # the monolithic gather — narrow-minor-dim targets run their
            # relayout copies on packed full-lane buffers via
            # heat_tpu.kernels.relayout, HEAT_TPU_RELAYOUT_KERNEL
            # gating the tiled-copy kernel);
            # ht.redistribution.explain(a, reshape=shape, new_split=...)
            # shows the chosen plan
            phys = _redist.reshape_phys(
                a.comm, a._phys, a.gshape, a.split, tuple(shape), new_split
            )
        else:
            prog = _reshape_program(a.comm, a.gshape, a.split, tuple(shape), new_split)
            phys = prog(a._phys)
        return DNDarray(phys, tuple(shape), a.dtype, new_split, a.device, a.comm)
    result = jnp.reshape(a.larray, shape)
    return _wrap(result, new_split, a, dtype=a.dtype)


def resplit(arr: DNDarray, axis: Optional[int] = None) -> DNDarray:
    """Out-of-place resplit (reference: manipulations.py:3479)."""
    sanitize_in(arr)
    return arr.resplit(axis)


def roll(x: DNDarray, shift, axis=None) -> DNDarray:
    """Roll elements along axis (reference: manipulations.py:2156 — ring
    Isend/Irecv; here jnp.roll, the ppermute emitted by XLA)."""
    sanitize_in(x)
    if x._is_planar:
        from . import complex_planar as _cp

        return _cp.roll(x, shift, axis)
    result = jnp.roll(x.larray, shift, axis=axis)
    return _wrap(result, x.split, x, dtype=x.dtype)


def rot90(m: DNDarray, k: int = 1, axes: Sequence[int] = (0, 1)) -> DNDarray:
    """Rotate 90° in the axes plane (reference: manipulations.py rot90)."""
    sanitize_in(m)
    axes = tuple(axes)
    if len(axes) != 2 or axes[0] == axes[1]:
        raise ValueError("len(axes) must be 2 with distinct elements")
    ax = sanitize_axis(m.shape, axes)
    if m._is_planar:
        from . import complex_planar as _cp

        return _cp.rot90(m, k, ax)
    result = jnp.rot90(m.larray, k=k, axes=axes)
    split = m.split
    if split is not None and k % 2 == 1 and split in ax:
        # the two plane axes swap extents
        split = ax[0] if split == ax[1] else ax[1]
    return _wrap(result, split, m, dtype=m.dtype)


def row_stack(arrays: Sequence[DNDarray]) -> DNDarray:
    """Stack rows (reference: manipulations.py row_stack)."""
    return vstack(arrays)


def shape(a: DNDarray) -> Tuple[int, ...]:
    """Global shape (reference: manipulations.py shape)."""
    sanitize_in(a)
    return a.gshape


def _takes_distributed_sort(a: DNDarray, axis: int) -> bool:
    return (
        a.split is not None
        and axis == a.split
        and a.comm.size > 1
        and a.dtype not in (types.complex64, types.complex128)
    )


def _sort_sentinel_fill(a: DNDarray, axis: int) -> jax.Array:
    """Physical array with pad rows set to the dtype's maximal sentinel so
    they sink to the global tail (= canonical pad location) during a
    distributed sort. NaN sorts after +inf in XLA's total order; real NaNs
    stay ahead of pads (position tie-break / stable order)."""
    from . import _padding

    phys = a._phys
    if phys.shape[axis] == a.gshape[axis]:
        return phys
    jt = a.dtype.jax_type()
    if jnp.issubdtype(jt, jnp.floating):
        sentinel = jnp.nan
    elif jnp.issubdtype(jt, jnp.bool_):
        sentinel = True
    else:
        sentinel = jnp.iinfo(jt).max
    return _padding.mask_phys(phys, a.gshape, axis, fill=sentinel)


def _sorted_values(a: DNDarray, axis: int):
    """Gather-free sorted VALUES along the split axis, or None when the
    layout doesn't admit it. Runs the half-traffic values-only program
    (no index operand in the ppermutes) — the percentile/median hot path."""
    if not _takes_distributed_sort(a, axis):
        return None
    from . import _padding
    from . import parallel

    phys = _sort_sentinel_fill(a, axis)
    sv = parallel.distributed_sort(
        phys, a.comm.mesh, a.comm.axis_name, axis, with_indices=False
    )
    sv = _padding.mask_phys(sv, a.gshape, axis, 0)
    return DNDarray(sv, a.gshape, a.dtype, axis, a.device, a.comm)


def sort(a: DNDarray, axis: int = -1, descending: bool = False, out=None):
    """Sort along an axis; returns (values, indices) (reference:
    manipulations.py:2428 — distributed sample-sort with Alltoallv).

    When the sort axis IS the split axis and the mesh has >1 device, this
    runs ``parallel.distributed_sort`` — an odd-even block merge-split
    network of ``ppermute`` exchanges that never gathers the array (the
    explicit-SPMD replacement for the reference's Alltoallv sample-sort).
    Otherwise (non-split axis: every lane is shard-local) XLA's sort on
    the sharded array is already collective-free.
    """
    sanitize_in(a)
    if a._is_planar:
        from . import complex_planar as _cp

        raise _cp.policy_error("ht.sort on a complex array (complex has no total order)")
    axis = sanitize_axis(a.shape, axis)
    if axis is None:
        axis = a.ndim - 1
    if _takes_distributed_sort(a, axis):
        from . import _padding
        from . import parallel

        phys = _sort_sentinel_fill(a, axis)
        sv, si = parallel.distributed_sort(phys, a.comm.mesh, a.comm.axis_name, axis)
        sv = _padding.mask_phys(sv, a.gshape, axis, 0)
        si = _padding.mask_phys(si.astype(types.index_jax_type()), a.gshape, axis, 0)
        vals = DNDarray(sv, a.gshape, a.dtype, axis, a.device, a.comm)
        idx = DNDarray(si, a.gshape, types.canonical_heat_type(si.dtype), axis, a.device, a.comm)
        if descending:
            vals, idx = flip(vals, axis), flip(idx, axis)
    elif a.dtype in (types.complex64, types.complex128):
        # lax.sort has no complex key support — the two-pass path stays
        arr = a.larray
        indices = jnp.argsort(arr, axis=axis, descending=descending, stable=True)
        values = jnp.take_along_axis(arr, indices, axis=axis)
        vals = _wrap(values, a.split, a, dtype=a.dtype)
        idx = _wrap(indices.astype(types.index_jax_type()), a.split, a)
    else:
        # the fused values+argsort local sort (heat_tpu.kernels.sort):
        # ONE pass returning values AND stable argsort indices together —
        # argsort + take_along_axis costs a second sort-sized gather pass
        # (measured 3.2x the sort floor on v5e), and stable-DESCENDING
        # rides the same single pass on the complemented key transform
        # (the old two-pass "keep tie order" route is gone). Kernel paths
        # (radix / blocked columnsort) engage behind capability gates
        # with lax.sort as the oracle; HEAT_TPU_SORT_KERNEL=0 forces the
        # oracle everywhere.
        from .. import kernels as _kernels

        values, indices = _kernels.local_sort(
            a.larray, axis=axis, descending=descending
        )
        vals = _wrap(values, a.split, a, dtype=a.dtype)
        idx = _wrap(indices.astype(types.index_jax_type()), a.split, a)
    if out is not None:
        out.larray = vals.larray
        return out, idx
    return vals, idx


def split(x: DNDarray, indices_or_sections, axis: int = 0) -> List[DNDarray]:
    """Split into sub-arrays (reference: manipulations.py split)."""
    sanitize_in(x)
    axis = sanitize_axis(x.shape, axis)
    if isinstance(indices_or_sections, DNDarray):
        indices_or_sections = indices_or_sections.numpy()
    if isinstance(indices_or_sections, (list, tuple, np.ndarray)):
        sections = [int(i) for i in np.asarray(indices_or_sections).ravel()]
        parts = jnp.split(x.larray, sections, axis=axis)
    else:
        n = int(indices_or_sections)
        if x.shape[axis] % n != 0:
            raise ValueError("array split does not result in an equal division")
        parts = jnp.split(x.larray, n, axis=axis)
    return [_wrap(p, x.split, x, dtype=x.dtype) for p in parts]


def squeeze(x: DNDarray, axis: Optional[Union[int, Tuple[int, ...]]] = None) -> DNDarray:
    """Remove size-1 dimensions (reference: manipulations.py squeeze)."""
    sanitize_in(x)
    axis = sanitize_axis(x.shape, axis)
    if axis is None:
        axes = tuple(i for i, s in enumerate(x.shape) if s == 1)
    else:
        axes = (axis,) if isinstance(axis, int) else axis
        for ax in axes:
            if x.shape[ax] != 1:
                raise ValueError(
                    f"Dimension along axis {ax} is not 1 for shape {x.shape}"
                )
    if x._is_planar:
        from . import complex_planar as _cp

        return _cp.squeeze(x, axes)
    result = jnp.squeeze(x.larray, axis=axes)
    split = x.split
    if split is not None:
        if split in axes:
            split = None
        else:
            split = split - sum(1 for ax in axes if ax < split)
    return _wrap(result, split, x, dtype=x.dtype)


def stack(arrays: Sequence[DNDarray], axis: int = 0, out=None) -> DNDarray:
    """Join arrays along a new axis (reference: manipulations.py stack)."""
    arrays = sanitize_sequence(arrays)
    if len(arrays) < 2:
        raise ValueError(f"stack expects at least 2 arrays, got {len(arrays)}")
    for a in arrays:
        sanitize_in(a)
    ref = arrays[0]
    for a in arrays[1:]:
        if tuple(a.shape) != tuple(ref.shape):
            raise ValueError(
                f"all input arrays must have the same shape, got {a.shape} != {ref.shape}"
            )
    if any(a._is_planar for a in arrays):
        from . import complex_planar as _cp

        if out is not None:
            raise _cp.policy_error("stack with out= on complex arrays")
        return _cp.stack_new_axis(arrays, axis)
    out_dtype = ref.dtype
    for a in arrays[1:]:
        out_dtype = types.promote_types(out_dtype, a.dtype)
    jt = out_dtype.jax_type()
    result = jnp.stack([a.larray.astype(jt) for a in arrays], axis=axis)
    split = ref.split
    if split is not None:
        norm_axis = axis % result.ndim
        if norm_axis <= split:
            split += 1
    ret = _wrap(result, split, ref, dtype=out_dtype)
    if out is not None:
        out.larray = ret.larray
        return out
    return ret


def swapaxes(x: DNDarray, axis1: int, axis2: int) -> DNDarray:
    """Interchange two axes (reference: manipulations.py swapaxes)."""
    from .linalg import transpose

    axis1 = sanitize_axis(x.shape, axis1)
    axis2 = sanitize_axis(x.shape, axis2)
    perm = list(range(x.ndim))
    perm[axis1], perm[axis2] = perm[axis2], perm[axis1]
    return transpose(x, perm)


def tile(x: DNDarray, reps: Sequence[int]) -> DNDarray:
    """Construct by repeating x (reference: manipulations.py tile)."""
    sanitize_in(x)
    if isinstance(reps, DNDarray):
        reps = reps.numpy().tolist()
    reps = [int(r) for r in (reps if isinstance(reps, (list, tuple, np.ndarray)) else [reps])]
    result = jnp.tile(x.larray, reps)
    split = x.split
    if split is not None:
        split = split + (result.ndim - x.ndim)
    return _wrap(result, split, x, dtype=x.dtype)


def topk(a: DNDarray, k: int, dim: int = -1, largest: bool = True, sorted: bool = True, out=None):
    """k largest/smallest elements along dim; returns (values, indices)
    (reference: manipulations.py:3981 — iterative merge across ranks).

    Along the split axis this runs ``parallel.distributed_topk``: local
    per-shard top-k, all_gather of the tiny (p·k) candidate set, final
    merge — no global gather. Off-split dims are shard-local XLA top_k.
    """
    sanitize_in(a)
    dim = sanitize_axis(a.shape, dim)
    split = a.split
    if (
        split is not None
        and dim == split
        and a.comm.size > 1
        and k <= a.gshape[dim]
        and a.dtype not in (types.complex64, types.complex128)
    ):
        from . import _padding
        from . import parallel

        phys = a._phys
        n = a.gshape[dim]
        jt = a.dtype.jax_type()
        if phys.shape[dim] != n:
            # pads must lose: fill with the worst value for the direction
            sentinel = _operations._resolve_neutral("min" if largest else "max", jt)
            phys = _padding.mask_phys(phys, a.gshape, dim, fill=sentinel)
        fv, fi = parallel.distributed_topk(phys, a.comm.mesh, a.comm.axis_name, dim, k, largest)
        gshape = tuple(k if i == dim else s for i, s in enumerate(a.gshape))
        vals = DNDarray(fv, gshape, a.dtype, None, a.device, a.comm)
        idx = DNDarray(
            fi.astype(types.index_jax_type()), gshape, types.canonical_heat_type(jnp.int64), None, a.device, a.comm
        )
    else:
        arr = a.larray
        moved = jnp.moveaxis(arr, dim, -1)
        if largest:
            values, indices = jax.lax.top_k(moved, k)
        else:
            values, indices = jax.lax.top_k(-moved, k)
            values = -values
        values = jnp.moveaxis(values, -1, dim)
        indices = jnp.moveaxis(indices, -1, dim)
        vals = _wrap(values, split, a, dtype=a.dtype)
        idx = _wrap(indices.astype(types.index_jax_type()), split, a)
    if out is not None:
        if not isinstance(out, tuple) or len(out) != 2:
            raise TypeError("out must be a (values, indices) tuple of DNDarrays")
        out[0].larray = vals.larray
        out[1].larray = idx.larray
        return out
    return vals, idx


def _lex_searchsorted_rows(sorted_rows, queries):
    """Index of each query ROW in a lexicographically sorted row set
    (every query must be present): a vectorized lower-bound binary
    search — ``log2(nu)`` steps of O(n·R) work, the rows edition of the
    flat path's ``searchsorted``. The naive pairwise-equality tensor
    would be O(n·nu·R) — an OOM in exactly the large-operand regime
    this subsystem targets. Comparison runs on the SORTABLE-uint bit
    view, so unsigned order is value order."""
    nu = int(sorted_rows.shape[0])
    n = queries.shape[0]
    lo = jnp.zeros((n,), dtype=jnp.int32)
    hi = jnp.full((n,), nu, dtype=jnp.int32)

    def body(_, state):
        lo, hi = state
        mid = (lo + hi) // 2
        pivot = jnp.take(sorted_rows, jnp.minimum(mid, nu - 1), axis=0)  # (n, R)
        diff = pivot != queries
        has = jnp.any(diff, axis=1)
        first = jnp.argmax(diff, axis=1)
        pv = jnp.take_along_axis(pivot, first[:, None], axis=1)[:, 0]
        qv = jnp.take_along_axis(queries, first[:, None], axis=1)[:, 0]
        lt = has & (pv < qv)  # pivot <lex query
        searching = lo < hi
        lo = jnp.where(searching & lt, mid + 1, lo)
        hi = jnp.where(searching & ~lt, mid, hi)
        return lo, hi

    steps = max(nu.bit_length(), 1)
    lo, _ = jax.lax.fori_loop(0, steps, body, (lo, hi))
    return lo


def _unique_axis_distributed(a: DNDarray, axis: int, return_inverse: bool):
    """Gather-free distributed ``unique(axis=)`` — the sorted-split
    rows formulation (``parallel.distributed_unique_rows``): move the
    requested axis to the front, resplit to rows, bit-view each slice
    through the ``kernels.sort`` monotone transform, and run per-shard
    lexicographic sorted-unique + candidate-prefix merge. Only the
    small candidate set is ever gathered. Returns ``NotImplemented``
    when the formulation cannot serve (untransformable dtype, slices
    wider than 256 elements) — the caller falls back to the eager path."""
    from . import parallel as _parallel
    from ..kernels import sort as _ksort

    rest = tuple(s for i, s in enumerate(a.gshape) if i != axis)
    R = 1
    for s in rest:
        R *= int(s)
    if R == 0 or R > 256:
        return NotImplemented
    arr = a if axis == 0 else moveaxis(a, axis, 0)
    if arr.split != 0:
        arr = arr.resplit(0)
    phys = arr._phys
    is_bool = phys.dtype == jnp.bool_
    if is_bool:
        phys = phys.astype(jnp.uint8)
    if not _ksort.transformable(phys.dtype):
        return NotImplemented
    n = int(arr.gshape[0])
    u = _ksort.to_sortable(phys.reshape(phys.shape[0], R))  # local flatten
    merged_u = _parallel.distributed_unique_rows(
        u, n, arr.comm.mesh, arr.comm.axis_name
    )
    vals_flat = _ksort.from_sortable(merged_u, phys.dtype)
    if is_bool:
        vals_flat = vals_flat.astype(jnp.bool_)
    nu = int(vals_flat.shape[0])
    vals = vals_flat.reshape((nu,) + rest)
    if axis != 0:
        vals = jnp.moveaxis(vals, 0, axis)
    out = _wrap(vals, 0 if a.split is not None else None, a, dtype=a.dtype)
    if not return_inverse:
        return out
    # inverse: each LOGICAL slice's position in the lex-sorted unique
    # set, found shard-wise by the rows lower-bound binary search
    # against the small replicated set (no collective; O(n·R·log nu)
    # like the flat path's searchsorted — bit-view, so NaN/−0 classes
    # match their collapsed representative)
    u_log = _ksort.to_sortable(
        (arr.larray.astype(jnp.uint8) if is_bool else arr.larray).reshape(n, R)
    )
    inv_phys = _lex_searchsorted_rows(merged_u, u_log).astype(types.index_jax_type())
    inv = _wrap(jnp.asarray(inv_phys), 0 if a.split is not None else None, a)
    return out, inv


def unique(a: DNDarray, sorted: bool = False, return_inverse: bool = False, axis: Optional[int] = None):
    """Unique elements (reference: manipulations.py:3202 — local unique +
    allgather of the small sets + re-unique).

    Distributed unique is gather-free in BOTH modes: flat unique is a
    per-shard sorted-unique compaction, one tiny count sync, and a merge
    over only the candidate prefixes (``parallel.distributed_unique``);
    ``axis`` mode (slices-unique) runs the same sorted-split formulation
    on ROWS (ISSUE 11 satellite / VERDICT backlog) — slices are
    bit-viewed through the ``kernels.sort`` monotone transform, sorted
    lexicographically per shard, deduplicated, and only the candidate
    prefixes are gathered (``parallel.distributed_unique_rows``) — the
    operand itself is never all-gathered, and tier-1 pins the census.
    Tie semantics match the framework's flat unique (−0.0 with +0.0
    collapse; all NaN payloads collapse to the canonical quiet NaN —
    ``jnp.unique`` behavior). The single-device path, untransformable
    dtypes (complex; f64 without x64), and very wide slices (> 256
    elements — the lexicographic sort keys one operand per element) use
    eager ``jnp.unique`` (data-dependent output shape)."""
    sanitize_in(a)
    if axis is not None:
        axis = sanitize_axis(a.shape, axis)
        if a.ndim == 1:
            axis = None  # 1-D slices ARE the elements: np.unique semantics
    comm = a.comm
    if (
        axis is not None
        and a.split is not None
        and comm.is_distributed()
        and 0 not in a.gshape
    ):
        out = _unique_axis_distributed(a, axis, return_inverse)
        if out is not NotImplemented:
            return out
    if (
        axis is None
        and a.split is not None
        and comm.is_distributed()
        and 0 not in a.gshape  # zero-extent arrays are stored replicated
    ):
        from . import parallel as _parallel

        arr = a if a.split == 0 else a.resplit(0)
        phys = arr._phys
        is_bool = phys.dtype == jnp.bool_
        if is_bool:
            phys = phys.astype(jnp.uint8)
        values = _parallel.distributed_unique(
            phys, int(arr.gshape[0]), comm.mesh, comm.axis_name
        )
        if is_bool:
            values = values.astype(jnp.bool_)
        vals = _wrap(values, 0, a, dtype=a.dtype)
        if return_inverse:
            # searchsorted into the small replicated unique set — binary
            # search per element, computed shard-wise under GSPMD (the
            # replicated u needs no collective)
            q = a.larray.reshape(-1)
            inv_phys = jnp.searchsorted(values.astype(phys.dtype), q)
            if jnp.issubdtype(values.dtype, jnp.floating):
                # NaN queries: searchsorted compares False against
                # everything and returns len(values), but the unique set
                # collapses NaNs into ONE slot sorted LAST — remap so the
                # inverse reconstructs like np.unique's (ADVICE r3)
                inv_phys = jnp.where(jnp.isnan(q), values.shape[0] - 1, inv_phys)
            inv_phys = inv_phys.astype(types.index_jax_type())
            # the inverse is as long as the (flattened) input and computed
            # shard-wise from it: carry the input's distribution instead
            # of declaring a replicated wrapper over a sharded buffer
            inv = _wrap(jnp.asarray(inv_phys), 0 if a.split is not None else None, a)
            return vals, inv
        return vals
    with _span("ht.sync.read", what="unique.eager"):  # jnp.unique reads the count: its shape is the data's
        if return_inverse:
            values, inverse = jnp.unique(a.larray, return_inverse=True, axis=axis)
        else:
            values = jnp.unique(a.larray, axis=axis)
    split = 0 if a.split is not None else None
    vals = _wrap(values, split, a, dtype=a.dtype)
    if return_inverse:
        inv = _wrap(jnp.asarray(inverse), None, a)
        return vals, inv
    return vals


def vsplit(x: DNDarray, indices_or_sections) -> List[DNDarray]:
    """Split vertically (reference: manipulations.py vsplit)."""
    return split(x, indices_or_sections, axis=0)


def vstack(arrays: Sequence[DNDarray]) -> DNDarray:
    """Stack vertically (reference: manipulations.py vstack)."""
    arrays = sanitize_sequence(arrays)
    arrays = [a if a.ndim > 1 else reshape(a, (1, a.shape[0]) if a.ndim == 1 else (1,)) for a in arrays]
    return concatenate(arrays, axis=0)


# method attachment (reference attaches these on DNDarray)
DNDarray.flip = flip
DNDarray.tile = tile
DNDarray.repeat = repeat
DNDarray.sort = sort
DNDarray.topk = topk
DNDarray.unique = unique
DNDarray.concatenate = lambda self, others, axis=0: concatenate([self] + list(others), axis)
DNDarray.moveaxis = moveaxis
DNDarray.swapaxes = swapaxes
DNDarray.broadcast_to = broadcast_to

from .communication import register_mesh_cache

# entries bake mesh geometry: cleared when init_distributed rebuilds the world
register_mesh_cache(_reshape_program)
register_mesh_cache(_concat_program)
