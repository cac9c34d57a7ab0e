"""Statistical operations.

API parity with /root/reference/heat/core/statistics.py (20 exports).
Distribution notes from the reference: ``mean``/``var`` (statistics.py:892/
:1851) combine local moments with an Allreduce (Welford-style merge in
``__moment_w_axis`` :1224); ``argmax``/``argmin`` use custom MPI reduction
ops carrying a value∥index payload (:1369); ``percentile`` (:1407) runs a
distributed sort plus halo exchange. On TPU all of these are single jnp
reductions over the sharded global array — XLA emits the same combine
collectives — so the hand-built merge machinery disappears. ``percentile`` /
``median`` along the sample axis of a 2-D array do not sort where
``_selection_form`` says so: they count (``_selection.order_statistics``, the
exact radix selection KMedians' medians come from, for all rows).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from typing import Optional, Tuple, Union

from . import types
from . import _operations
from . import _pallas_select, _selection
from .dndarray import DNDarray
from .sanitation import sanitize_in
from .stride_tricks import sanitize_axis
from ..observability import telemetry as _telemetry
from ..observability.instrument import observed_program_cache
from ..observability.tracing import call_span as _call_span, span as _span

__all__ = [
    "argmax",
    "argmin",
    "average",
    "bincount",
    "bucketize",
    "cov",
    "digitize",
    "histc",
    "histogram",
    "kurtosis",
    "max",
    "maximum",
    "mean",
    "median",
    "min",
    "minimum",
    "percentile",
    "skew",
    "std",
    "var",
]


def _argmax_i64(a, axis=None, keepdims=False):
    # module-level (NOT a per-call lambda): the cached-jit layer keys
    # programs on op identity, so a fresh callable per call would
    # retrace+recompile every invocation
    return jnp.argmax(a, axis=axis, keepdims=keepdims).astype(types.index_jax_type())


def _argmin_i64(a, axis=None, keepdims=False):
    return jnp.argmin(a, axis=axis, keepdims=keepdims).astype(types.index_jax_type())


def argmax(x: DNDarray, axis: Optional[int] = None, out=None, **kwargs) -> DNDarray:
    """Indices of maximum values (reference: statistics.py argmax — MPI
    value∥index custom op; here a sharded jnp.argmax)."""
    return _operations.__reduce_op(
        _argmax_i64,
        x,
        axis=axis,
        out=out,
        keepdims=kwargs.get("keepdims", False),
    )


def argmin(x: DNDarray, axis: Optional[int] = None, out=None, **kwargs) -> DNDarray:
    """Indices of minimum values."""
    return _operations.__reduce_op(
        _argmin_i64,
        x,
        axis=axis,
        out=out,
        keepdims=kwargs.get("keepdims", False),
    )


def average(x: DNDarray, axis=None, weights: Optional[DNDarray] = None, returned: bool = False):
    """Weighted average (reference: statistics.py average)."""
    sanitize_in(x)
    if weights is None:
        result = mean(x, axis)
        if returned:
            from . import factories

            n = x.size if axis is None else np.prod([x.shape[a] for a in (
                (axis,) if isinstance(axis, int) else tuple(axis)
            )])
            weights_sum = factories.full_like(result, float(n))
            return result, weights_sum
        return result
    sanitize_in(weights)
    axis_s = sanitize_axis(x.shape, axis)
    w = weights.larray
    arr = x.larray
    if types.heat_type_is_exact(x.dtype):
        arr = arr.astype(jnp.float32)
    if w.ndim != arr.ndim and axis_s is not None and isinstance(axis_s, int):
        if w.shape != (x.shape[axis_s],):
            raise ValueError("Length of weights not compatible with specified axis.")
        shape = [1] * arr.ndim
        shape[axis_s] = w.shape[0]
        w = w.reshape(shape)
    wsum = jnp.sum(w * jnp.ones_like(arr), axis=axis_s)
    with _span("ht.sync.read", what="average.weights"):
        no_weight = bool(jnp.any(wsum == 0))
    if no_weight:
        raise ZeroDivisionError("Weights sum to zero, can't be normalized")
    result = jnp.sum(arr * w, axis=axis_s) / wsum
    res = _wrap_reduce(result, x, axis_s, False)
    if returned:
        wret = _wrap_reduce(jnp.broadcast_to(wsum, result.shape), x, axis_s, False)
        return res, wret
    return res


def _wrap_reduce(result: jax.Array, x: DNDarray, axis, keepdims: bool) -> DNDarray:
    """Split bookkeeping for a reduction result computed outside
    __reduce_op."""
    split = x.split
    if split is None or axis is None:
        out_split = None
    else:
        axes = (axis,) if isinstance(axis, int) else tuple(axis)
        if split in axes:
            out_split = None
        elif keepdims:
            out_split = split
        else:
            out_split = split - sum(1 for a in axes if a < split)
    gshape = tuple(int(s) for s in result.shape)
    if out_split is not None and result.ndim > 0:
        result = x.comm.shard(result, out_split)
    else:
        out_split = None
    return DNDarray(
        result, gshape, types.canonical_heat_type(result.dtype), out_split, x.device, x.comm
    )


def bincount(x: DNDarray, weights: Optional[DNDarray] = None, minlength: int = 0) -> DNDarray:
    """Count occurrences of non-negative ints (reference: statistics.py
    bincount — local bincount + Allreduce; the sharded sum here)."""
    sanitize_in(x)
    if x.ndim != 1:
        raise ValueError("bincount expects a 1-d array")
    arr = x.larray
    with _span("ht.sync.read", what="bincount.range"):
        lowest, highest = (int(jnp.min(arr)), int(jnp.max(arr))) if arr.size else (0, -1)
    if lowest < 0:
        raise ValueError("bincount requires non-negative input values")
    w = weights.larray if isinstance(weights, DNDarray) else weights
    # jnp.bincount requires static length: compute it eagerly
    if arr.shape[0] == 0:
        length = minlength
    else:
        length = int(builtins_max(highest + 1, minlength)) if arr.size else minlength
    result = jnp.bincount(arr, weights=w, length=length if length > 0 else None)
    gshape = tuple(int(s) for s in result.shape)
    return DNDarray(
        result, gshape, types.canonical_heat_type(result.dtype), None, x.device, x.comm
    )


import builtins

builtins_max = builtins.max


def bucketize(input: DNDarray, boundaries, out_int32: bool = False, right: bool = False, out=None) -> DNDarray:
    """Index of the bucket each element falls into (reference:
    statistics.py bucketize, torch semantics)."""
    sanitize_in(input)
    b = boundaries.larray if isinstance(boundaries, DNDarray) else jnp.asarray(np.asarray(boundaries))
    # torch semantics: right=False -> x <= boundaries[i] (numpy side='left' is
    # boundaries[i-1] < x), right=True -> boundaries[i-1] <= x < boundaries[i]
    result = jnp.searchsorted(b, input.larray, side="left" if not right else "right")
    result = result.astype(jnp.int32 if out_int32 else types.index_jax_type())
    ret = _wrap_reduce(result, input, None, False)
    ret._DNDarray__split = input.split
    if input.split is not None:
        ret._set_phys(input.comm.shard(result, input.split))
    if out is not None:
        out.larray = ret.larray
        return out
    return ret


def cov(m: DNDarray, y: Optional[DNDarray] = None, rowvar: bool = True, bias: bool = False, ddof: Optional[int] = None) -> DNDarray:
    """Covariance matrix estimate (reference: statistics.py cov)."""
    sanitize_in(m)
    if ddof is not None and not isinstance(ddof, int):
        raise TypeError("ddof must be integer")
    arr = m.larray.astype(jnp.float64 if m.dtype is types.float64 else jnp.float32)
    if y is not None:
        sanitize_in(y)
        yarr = y.larray.astype(arr.dtype)
        result = jnp.cov(arr, yarr, rowvar=rowvar, bias=bias, ddof=ddof)
    else:
        result = jnp.cov(arr, rowvar=rowvar, bias=bias, ddof=ddof)
    gshape = tuple(int(s) for s in result.shape)
    return DNDarray(
        result, gshape, types.canonical_heat_type(result.dtype), None, m.device, m.comm
    )


def digitize(x: DNDarray, bins, right: bool = False) -> DNDarray:
    """Indices of the bins each value belongs to (numpy semantics;
    reference: statistics.py digitize)."""
    sanitize_in(x)
    b = bins.larray if isinstance(bins, DNDarray) else jnp.asarray(np.asarray(bins))
    result = jnp.digitize(x.larray, b, right=right).astype(types.index_jax_type())
    ret = _wrap_reduce(result, x, None, False)
    if x.split is not None:
        ret._DNDarray__split = x.split
        ret._set_phys(x.comm.shard(result, x.split))
    return ret


def histc(input: DNDarray, bins: int = 100, min: float = 0.0, max: float = 0.0, out=None) -> DNDarray:
    """Histogram with equal-width bins in [min, max] (torch semantics;
    reference: statistics.py histc)."""
    sanitize_in(input)
    arr = input.larray
    if types.heat_type_is_exact(input.dtype):
        arr = arr.astype(jnp.float32)
    lo, hi = float(min), float(max)
    if lo == 0.0 and hi == 0.0:
        lo = float(jnp.min(arr)) if arr.size else 0.0
        hi = float(jnp.max(arr)) if arr.size else 0.0
    if lo == hi:
        lo, hi = lo - 1e-6, hi + 1e-6
    mask = (arr >= lo) & (arr <= hi)
    hist, _ = jnp.histogram(jnp.where(mask, arr, jnp.asarray(np.nan, arr.dtype)), bins=bins, range=(lo, hi))
    result = hist.astype(arr.dtype)
    gshape = tuple(int(s) for s in result.shape)
    return DNDarray(
        result, gshape, types.canonical_heat_type(result.dtype), None, input.device, input.comm
    )


def histogram(a: DNDarray, bins: int = 10, range=None, normed=None, weights=None, density=None):
    """NumPy-style histogram; returns (hist, bin_edges) (reference:
    statistics.py histogram — ``normed`` rejected the same way,
    statistics.py:716)."""
    if normed is not None:
        raise NotImplementedError("'normed' is not supported")
    sanitize_in(a)
    arr = a.larray
    w = weights.larray if isinstance(weights, DNDarray) else weights
    hist, edges = jnp.histogram(arr, bins=bins, range=range, weights=w, density=density)
    h = DNDarray(
        hist, tuple(int(s) for s in hist.shape), types.canonical_heat_type(hist.dtype), None, a.device, a.comm
    )
    e = DNDarray(
        edges, tuple(int(s) for s in edges.shape), types.canonical_heat_type(edges.dtype), None, a.device, a.comm
    )
    return h, e


def __moments(x: DNDarray, axis, power: int):
    """(m2, m_power): central moments from one mean/centering pass (the
    single-pass replacement for the reference's Welford merge,
    statistics.py:1224)."""
    arr = x.larray
    if types.heat_type_is_exact(x.dtype):
        arr = arr.astype(jnp.float32)
    mu = jnp.mean(arr, axis=axis, keepdims=True)
    centered = arr - mu
    m2 = jnp.mean(centered**2, axis=axis)
    mk = jnp.mean(centered**power, axis=axis)
    return m2, mk


def _axis_count(x: DNDarray, axis) -> int:
    """Number of elements reduced over ``axis``."""
    if axis is None:
        return x.size
    axes = (axis,) if isinstance(axis, int) else tuple(axis)
    return int(np.prod([x.shape[a] for a in axes]))


def kurtosis(x: DNDarray, axis: Optional[int] = None, unbiased: bool = True, Fischer: bool = True) -> DNDarray:
    """Kurtosis (Fisher's definition subtracts 3) (reference:
    statistics.py kurtosis)."""
    sanitize_in(x)
    axis = sanitize_axis(x.shape, axis)
    m2, m4 = __moments(x, axis, 4)
    n = _axis_count(x, axis)
    if unbiased:
        g2 = m4 / (m2**2)
        result = ((n - 1) / ((n - 2) * (n - 3))) * ((n + 1) * g2 - 3 * (n - 1))
        if Fischer:
            pass  # bias-corrected excess kurtosis already excess
        else:
            result = result + 3
    else:
        result = m4 / (m2**2)
        if Fischer:
            result = result - 3
    return _wrap_reduce(jnp.asarray(result), x, axis, False)


def max(x: DNDarray, axis=None, out=None, keepdims=None) -> DNDarray:
    """Maximum along axis (reference: statistics.py max)."""
    return _operations.__reduce_op(
        jnp.max, x, axis=axis, out=out, keepdims=bool(keepdims) if keepdims else False
    )


def maximum(x1: DNDarray, x2: DNDarray, out=None) -> DNDarray:
    """Elementwise maximum (reference: statistics.py maximum)."""
    return _operations.__binary_op(jnp.maximum, x1, x2, out)


def mean(x: DNDarray, axis=None, keepdims: bool = False) -> DNDarray:
    """Arithmetic mean (reference: statistics.py:892 — local moments +
    Allreduce combine; here one sharded jnp.mean). ``keepdims`` is a
    numpy-style superset of the reference signature, matching this
    module's var/std/min/max/median."""
    sanitize_in(x)
    if x._is_planar:
        from . import complex_planar as _cp

        return _cp.reduce(jnp.mean, x, axis=axis, keepdims=bool(keepdims))
    axis = sanitize_axis(x.shape, axis)
    arr = x.larray
    if types.heat_type_is_exact(x.dtype):
        arr = arr.astype(jnp.float32)
    result = jnp.mean(arr, axis=axis, keepdims=bool(keepdims))
    return _wrap_reduce(jnp.asarray(result), x, axis, bool(keepdims))


# rows a device from which the ``jax.numpy`` form of the selection stands in for the distributed sort of a split
# array. On the CPU mesh of eight (host clock, warm, three percentiles of an f32 array; builder's runs, PR 38): 8 000 x
# 6: 37 ms against the sort's 9; 80 000 x 16: 70 against 94; 800 000 x 16: 211 against 1 315 (its first call 0.3-0.5 s
# against 0.5-1.5: the network's program is the larger one)
_SELECT_MIN_ROWS_A_DEVICE = 1 << 13


def _selection_form(backend: str, dtype, shape, axis, split, devices: int, targets: int) -> str:
    """Which form ``percentile`` takes, a pure function of what it sees in
    its input: ``"pallas"``, the counting selection on the chip's kernels
    (2-D along axis 0 where ``_pallas_select.tall_narrow_serves``: a TPU, f32,
    ``d`` a multiple of 8 under 128, one device or equal split-0 shards; and
    ``gather_pays`` for the targets of one batch on a device's rows, at
    least ``2 ** 17`` rows a target: under that the sort of a few MB is as
    fast); ``"xla"``, the same selection on its ``jax.numpy`` passes (2-D f32
    / f64 along a split axis 0 in equal shards over more than one device,
    from ``_SELECT_MIN_ROWS_A_DEVICE`` rows a device on: seventeen
    elementwise passes whose counts are all-reduced, in place of the
    odd-even sort network); ``"sort"`` everywhere else."""
    if len(shape) != 2 or axis != 0 or shape[0] < 1 or shape[1] < 1:
        return "sort"
    batch = targets if targets < _pallas_select._MOST_TARGETS else _pallas_select._MOST_TARGETS  # (``min`` is ht's here)
    if (_pallas_select.tall_narrow_serves(backend, dtype, shape, split, devices)
            and _pallas_select.gather_pays(shape[0] // (devices if split == 0 else 1), batch)):
        return "pallas"
    if split == 0 and devices > 1 and shape[0] % devices == 0 and shape[0] // devices >= _SELECT_MIN_ROWS_A_DEVICE \
            and np.dtype(dtype) in (np.float32, np.float64):
        return "xla"
    return "sort"


def _percentile_select_body(shape, jdtype: str, on_chip: bool, ranks, weights, out_shape, mesh, axis_name,
                            interpret: bool = False):
    """The traced body of ``_percentile_select_program``, for a larger
    program to call (``preprocessing._robust_fit_transform_program``):
    ``arr (n, d) -> (len(ranks), d)``: for every ``(lo, hi)`` of ``ranks``
    (0-based, host-static, ``hi`` is ``lo`` or ``lo + 1``) and its weight
    ``w`` the value ``v[lo] + w * (v[hi] - v[lo])`` of every column's order
    statistics ``v`` (``w`` 0.0: ``v[lo]``, 1.0: ``v[hi]``, both as they
    are), NaN where the column holds one. The pairs
    that differ are the targets of ``_selection.order_statistics`` for all
    rows, ``_MOST_TARGETS`` to a batch, every batch in the same passes over
    ``arr`` whatever it holds; the first digit's pass, which also looks for
    NaNs, is made once for all batches. With a ``mesh`` and an
    ``axis_name``, ``arr`` is split 0 over it in equal shards and the counts
    are summed over the devices before a bracket narrows."""
    n, d = int(shape[0]), int(shape[1])
    targets = sorted(set(ranks))
    most = _pallas_select._MOST_TARGETS
    batches = [targets[i:i + most] for i in range(0, len(targets), most)]
    at = {pair: i for i, pair in enumerate(targets)}

    def passes_for(q: int):
        if on_chip:
            return _pallas_select.select_passes((n, d), q, False, "percentile.select", mesh, axis_name, interpret)
        return _selection.passes_xla()

    def run(arr):
        with jax.named_scope("percentile.select"):
            under, nans = passes_for(len(batches[0])).first(arr)
            lows, highs = [], []
            for batch in batches:
                lower = jnp.asarray([lo for lo, _ in batch], jnp.int32)[:, None]
                upper = jnp.asarray([hi for _, hi in batch], jnp.int32)[:, None]
                first_under = jnp.broadcast_to(under[:, None, :], (under.shape[0], len(batch), d))
                low, high = _selection.order_statistics(
                    arr, lower, upper, jnp.full((len(batch), 1), n, jnp.int32), passes_for(len(batch)), None, first_under)
                lows.append(_pallas_select._from_key(low, arr.dtype))
                highs.append(_pallas_select._from_key(high, arr.dtype))
            vlo, vhi = jnp.concatenate(lows), jnp.concatenate(highs)
            rows = []
            for pair, w in zip(ranks, weights):
                lo, hi = vlo[at[pair]], vhi[at[pair]]
                rows.append(lo if w == 0.0 else hi if w == 1.0 else lo + jnp.asarray(w, arr.dtype) * (hi - lo))
            return jnp.where(nans[None, :] > 0, jnp.nan, jnp.stack(rows)).reshape(out_shape)

    return run


@observed_program_cache("percentile.select", maxsize=64)
def _percentile_select_program(shape, jdtype: str, on_chip: bool, ranks, weights, out_shape, mesh, axis_name,
                               interpret: bool = False):
    """``_percentile_select_body`` as one jitted program: ``ht.percentile``'s
    own, where ``_form_of`` says so."""
    return jax.jit(_percentile_select_body(shape, jdtype, on_chip, ranks, weights, out_shape, mesh, axis_name, interpret))


def _form_of(x: DNDarray, axis, qv: np.ndarray) -> str:
    """Which form the percentiles ``qv`` along ``axis`` of ``x`` take: the
    one test of ``percentile`` and ``RobustScaler.fit_transform`` (2-D, along
    axis 0, not planar, then ``_selection_form`` on the distinct ``q``)."""
    if axis != 0 or x.ndim != 2 or x._is_planar:
        return "sort"
    return _selection_form(jax.default_backend(), x.dtype.jax_type(), x.gshape, axis, x.split, x.comm.size,
                           len(set(qv.tolist())))


def _selection_key(x: DNDarray, qv: np.ndarray, interpolation: str, form: str, out_shape) -> tuple:
    """``_percentile_select_program``'s key for the ``len(qv)`` percentiles of
    every column of a 2-D ``x`` as ``out_shape``: the ranks that bracket
    each and the upper one's weight, reckoned on the host, and how ``x``
    lies. The program's operand is ``_selection_operand(x)``."""
    n = x.gshape[0]
    pos = qv / 100.0 * (n - 1)
    lo = np.floor(pos).astype(np.int64)
    hi = np.ceil(pos).astype(np.int64)
    if interpolation == "lower":
        w = np.zeros_like(pos)
    elif interpolation == "higher":
        w = np.ones_like(pos)
    elif interpolation == "nearest":
        w = (np.rint(pos).astype(np.int64) != lo).astype(np.float64)
    elif interpolation == "midpoint":
        w = np.where(hi > lo, 0.5, 0.0)
    else:  # linear
        w = pos - lo
    split_over = x.split == 0 and x.comm.size > 1
    mesh = x.comm.mesh if form == "pallas" and x.comm.size > 1 else None
    return (
        tuple(x.gshape), np.dtype(x.dtype.jax_type()).name, form == "pallas",
        tuple(zip(lo.tolist(), hi.tolist())), tuple(w.tolist()), tuple(out_shape), mesh,
        x.comm.axis_name if mesh is not None and split_over else None,
        form == "pallas" and jax.default_backend() != "tpu",  # the kernels off the chip (the tests): interpret mode
    )


def _selection_operand(x: DNDarray) -> jax.Array:
    """``x`` as a program of the selection takes it: the shards as they lie
    where it is split 0 over the mesh (equal ones: a form's condition)."""
    return x._phys if x.split == 0 and x.comm.size > 1 else x.larray


def _count_selection(form: str) -> None:
    """The counters of one call that took the selection's ``form``."""
    _telemetry.inc(f"percentile.select.{form}")
    if form == "pallas":
        _telemetry.inc("percentile.select.gather")


def _percentile_by_selection(x: DNDarray, qv: np.ndarray, interpolation: str, form: str, out_shape) -> jax.Array:
    """The ``len(qv)`` percentiles of every column of a 2-D ``x`` by the
    counting selection, as ``out_shape``, in one program
    (``_percentile_select_program``)."""
    prog = _percentile_select_program(*_selection_key(x, qv, interpolation, form, out_shape))
    _count_selection(form)
    return prog(_selection_operand(x))


def median(x: DNDarray, axis: Optional[int] = None, keepdims: bool = False) -> DNDarray:
    """Median = 50th percentile (reference: statistics.py:1018)."""
    return percentile(x, 50.0, axis=axis, keepdims=keepdims)


def min(x: DNDarray, axis=None, out=None, keepdims=None) -> DNDarray:
    """Minimum along axis."""
    return _operations.__reduce_op(
        jnp.min, x, axis=axis, out=out, keepdims=bool(keepdims) if keepdims else False
    )


def minimum(x1: DNDarray, x2: DNDarray, out=None) -> DNDarray:
    """Elementwise minimum."""
    return _operations.__binary_op(jnp.minimum, x1, x2, out)


def percentile(
    x: DNDarray,
    q,
    axis: Optional[int] = None,
    out=None,
    interpolation: str = "linear",
    keepdims: bool = False,
) -> DNDarray:
    """q-th percentile: along the sample axis of a tall 2-D array an exact counting selection, no sort.

    (Reference: statistics.py:1407 — distributed sort + halo + Allgather of
    index maps.) Along axis 0 of a 2-D array, where ``_selection_form`` says so, the two
    order statistics that bracket every ``q`` are found by the exact counting
    selection of ``core/_selection.py`` (the one KMedians' medians come from;
    here every row counts for every target): no sort, no copy of ``x``,
    nothing of its size allocated, all ``q`` of the call in the same passes
    over ``x`` (up to ``_pallas_select._MOST_TARGETS`` distinct rank pairs a
    batch), one jitted program a call (``RobustScaler.fit_transform`` traces
    the same body into its own one program, with the statistics and the
    transform behind it; ``RobustScaler.fit`` is a call of this function). On a TPU that is f32 with a multiple
    of 8 under 128 columns, on one device or in equal split-0 shards, from
    ``2 ** 17`` rows a target and device on (the kernels of
    ``core/_pallas_select.py``); on any backend, f32 / f64 split 0 in equal
    shards over more than one device (the ``jax.numpy`` form of the same
    passes; the counts are summed over the devices before a bracket
    narrows). The result is that of the sorted column bit for bit for
    ``lower``, ``higher`` and ``nearest``, and ``v[lo] + frac * (v[hi] -
    v[lo])`` in the array's precision for ``linear`` and ``midpoint``; a
    column that holds a NaN gives NaN.

    Everywhere else the values are sorted: when the reduction axis is the
    split axis, the gather-free ``ht.sort`` (odd-even ppermute network,
    ``core.parallel``) and a fetch of only the two bracketing ranks per q —
    the TPU analog of the reference's sorted-halo rank lookup; other axes use
    XLA's lane-local percentile on the sharded array."""
    with _call_span("ht.call.percentile"):
        with _span("ht.call.percentile.prepare"):
            sanitize_in(x)
            axis = sanitize_axis(x.shape, axis)
            if interpolation not in ("linear", "lower", "higher", "midpoint", "nearest"):
                raise ValueError(f"unknown interpolation {interpolation}")
            # q stays a HOST value: the bracketing ranks must be static (they
            # shape the program), and round-tripping a python float through
            # jnp.asarray turns it into a tracer under ht.jit (jax inserts a
            # convert op for the unavailable f64), breaking np.asarray below
            if isinstance(q, (DNDarray, jax.Array)):
                q_dev = q.larray if isinstance(q, DNDarray) else q
                if isinstance(q_dev, jax.core.Tracer):
                    raise TypeError(
                        "percentile: q must be statically known (host value); a "
                        "traced q would make the output shape data-dependent"
                    )
                # declared host boundary "percentile-q" (analysis/boundaries.py):
                # the ONLY whitelisted sync in core/ — pinned by tier-1
                with _span("ht.sync.read", what="percentile.q"):
                    q_host = np.asarray(jax.device_get(q_dev), dtype=np.float64)
            else:
                q_host = np.asarray(q, dtype=np.float64)
            scalar_q = q_host.ndim == 0
            qv = np.atleast_1d(q_host)
            if np.any(qv < 0.0) or np.any(qv > 100.0):
                raise ValueError("percentiles must be in the range [0, 100]")
            eff_axis = axis
            if eff_axis is None and x.ndim == 1:
                eff_axis = 0
            form = _form_of(x, eff_axis, qv)
        if form != "sort":
            shape = (x.gshape[1],) if scalar_q else (len(qv), x.gshape[1])
            if keepdims:
                shape = shape[:-1] + (1, shape[-1])
            result = _percentile_by_selection(x, qv, interpolation, form, shape)
            with _span("ht.call.percentile.wrap"):
                ret = DNDarray(result, shape, types.canonical_heat_type(result.dtype), None, x.device, x.comm)
                if out is not None:
                    out.larray = ret.larray
                    return out
                return ret
        _telemetry.inc("percentile.select.sort")
        sorted_x = None
        if (
            eff_axis is not None
            and x.split == eff_axis
            and x.comm.size > 1
            and x.dtype not in (types.complex64, types.complex128)
        ):
            from . import manipulations

            sorted_x = manipulations._sorted_values(x, eff_axis)
        if sorted_x is not None:
            sarr = sorted_x.larray
            if types.heat_type_is_exact(x.dtype):
                sarr = sarr.astype(jnp.float32)
            n = x.gshape[eff_axis]
            pos = qv / 100.0 * (n - 1)
            lo = np.floor(pos).astype(np.int64)
            hi = np.ceil(pos).astype(np.int64)
            # ranks are host-static: only two cross-shard row fetches per q
            vlo = jnp.take(sarr, jnp.asarray(lo), axis=eff_axis)
            vhi = jnp.take(sarr, jnp.asarray(hi), axis=eff_axis)
            if interpolation == "lower":
                res = vlo
            elif interpolation == "higher":
                res = vhi
            elif interpolation == "midpoint":
                res = (vlo + vhi) / 2
            elif interpolation == "nearest":
                nearest = np.rint(pos).astype(np.int64)
                res = jnp.take(sarr, jnp.asarray(nearest), axis=eff_axis)
            else:  # linear
                frac = jnp.asarray(pos - lo, dtype=sarr.dtype)
                fshape = [1] * sarr.ndim
                fshape[eff_axis] = len(qv)
                res = vlo + frac.reshape(fshape) * (vhi - vlo)
            if jnp.issubdtype(sarr.dtype, jnp.floating):
                # NaNs sort to the tail, so a lane contains one iff its last
                # logical element is NaN — propagate like numpy does
                vlast = jnp.expand_dims(jnp.take(sarr, n - 1, axis=eff_axis), eff_axis)
                res = jnp.where(jnp.isnan(vlast), jnp.nan, res)
            # numpy/jnp put the q dim first
            result = jnp.moveaxis(res, eff_axis, 0)
            if scalar_q:
                result = jnp.squeeze(result, axis=0)
            if keepdims:
                # axis=None only reaches here for 1-D input (eff_axis 0)
                result = jnp.expand_dims(
                    result, (axis if axis is not None else 0) + (0 if scalar_q else 1)
                )
        else:
            arr = x.larray
            if types.heat_type_is_exact(x.dtype):
                arr = arr.astype(jnp.float32)
            # q rides in the widest available float (NOT arr.dtype: a bf16 q
            # would round 99.9 to 100.0 and return the maximum)
            result = jnp.percentile(
                arr, jnp.asarray(q_host, dtype=types.wide_jax_type("f")), axis=axis,
                method=interpolation, keepdims=keepdims,
            )
        # result has leading q dims when q is a vector
        ret = _wrap_reduce(jnp.asarray(result), x, axis, keepdims) if scalar_q else DNDarray(
            result,
            tuple(int(s) for s in result.shape),
            types.canonical_heat_type(result.dtype),
            None,
            x.device,
            x.comm,
        )
        if out is not None:
            out.larray = ret.larray
            return out
        return ret


def skew(x: DNDarray, axis: Optional[int] = None, unbiased: bool = True) -> DNDarray:
    """Sample skewness (reference: statistics.py skew)."""
    sanitize_in(x)
    axis = sanitize_axis(x.shape, axis)
    m2, m3 = __moments(x, axis, 3)
    n = _axis_count(x, axis)
    g1 = m3 / (m2**1.5)
    if unbiased:
        result = g1 * np.sqrt(n * (n - 1)) / (n - 2)
    else:
        result = g1
    return _wrap_reduce(jnp.asarray(result), x, axis, False)


def std(x: DNDarray, axis=None, ddof: int = 0, **kwargs) -> DNDarray:
    """Standard deviation (reference: statistics.py std)."""
    v = var(x, axis, ddof, **kwargs)
    from . import exponential

    return exponential.sqrt(v)


def var(x: DNDarray, axis=None, ddof: int = 0, **kwargs) -> DNDarray:
    """Variance (reference: statistics.py:1851 — Welford merge across
    ranks; here one sharded reduction)."""
    sanitize_in(x)
    if not isinstance(ddof, int):
        raise ValueError(f"ddof must be integer, is {type(ddof)}")
    if ddof < 0:
        raise ValueError(f"Expected ddof >= 0, got {ddof}")
    bessel = kwargs.get("bessel", None)
    if bessel is not None:
        ddof = 1 if bessel else 0
    axis = sanitize_axis(x.shape, axis)
    keepdims = kwargs.get("keepdims", False)
    if x._is_planar:
        from . import complex_planar as _cp

        return _cp.var(x, axis=axis, ddof=ddof, keepdims=bool(keepdims))
    arr = x.larray
    if types.heat_type_is_exact(x.dtype):
        arr = arr.astype(jnp.float32)
    result = jnp.var(arr, axis=axis, ddof=ddof, keepdims=keepdims)
    return _wrap_reduce(jnp.asarray(result), x, axis, keepdims)


from .communication import register_mesh_cache  # noqa: E402  (mesh-keyed program cache)

register_mesh_cache(_percentile_select_program)

DNDarray.argmax = argmax
DNDarray.argmin = argmin
DNDarray.average = average
DNDarray.max = max
DNDarray.min = min
DNDarray.mean = mean
DNDarray.median = median
DNDarray.percentile = percentile
DNDarray.std = std
DNDarray.var = var
DNDarray.kurtosis = kurtosis
DNDarray.skew = skew
