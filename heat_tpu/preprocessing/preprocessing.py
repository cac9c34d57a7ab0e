"""Feature scaling transformers.

API parity with /root/reference/heat/preprocessing/preprocessing.py
(``StandardScaler`` :49, ``MinMaxScaler`` :158, ``Normalizer`` :284,
``MaxAbsScaler`` :358, ``RobustScaler`` :444). All statistics are sharded
reductions over the sample axis (mean/var/min/max — one all-reduce each in
the reference's terms), ``RobustScaler``'s order statistics one
``ht.percentile`` call (along the sample axis an exact counting selection,
no sort, where ``statistics._selection_form`` says so), and its
``transform`` / ``inverse_transform`` one program each (``_affine``: one
read and one write of the table). ``RobustScaler.fit_transform`` is ONE
program where the selection serves (``_robust_fit_transform_program``: the
bodies of the selection, the two statistics and the transform in one
launch); ``fit`` and ``transform`` called apart, and ``fit_transform`` where
``percentile`` would sort, are the staged three.

Every scaler accepts ``copy`` for reference parity and does not read it:
arrays are immutable here, so a transform always returns a new array and the
caller's stays as it was.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map as _shard_map
from jax.sharding import PartitionSpec as P

from typing import Optional, Tuple

from ..core import _pallas_select as _tiles, statistics, types
from ..core.base import BaseEstimator, TransformMixin
from ..core.communication import register_mesh_cache
from ..core.dndarray import DNDarray
from ..core.sanitation import sanitize_in
from ..observability import telemetry as _telemetry
from ..observability.instrument import observed_program_cache
from ..observability.tracing import call_span as _call_span

__all__ = ["StandardScaler", "MinMaxScaler", "Normalizer", "MaxAbsScaler", "RobustScaler"]


def _float_of(x: DNDarray):
    return x.dtype if types.heat_type_is_inexact(x.dtype) else types.float32


class StandardScaler(BaseEstimator, TransformMixin):
    """Standardize features to zero mean and unit variance (reference:
    preprocessing.py:49). ``copy`` is accepted for reference parity and not
    read: a transform returns a new array, the caller's stays."""

    def __init__(self, copy: bool = True, with_mean: bool = True, with_std: bool = True):
        self.copy = copy
        self.with_mean = with_mean
        self.with_std = with_std
        self.mean_ = None
        self.var_ = None

    def fit(self, x: DNDarray, sample_weight=None) -> "StandardScaler":
        sanitize_in(x)
        self.mean_ = statistics.mean(x, axis=0) if self.with_mean or self.with_std else None
        if self.with_std:
            self.var_ = statistics.var(x, axis=0)
        return self

    def transform(self, x: DNDarray) -> DNDarray:
        sanitize_in(x)
        arr = x.larray.astype(_float_of(x).jax_type())
        if self.with_mean and self.mean_ is not None:
            arr = arr - self.mean_.larray
        if self.with_std and self.var_ is not None:
            scale = jnp.sqrt(self.var_.larray)
            arr = arr / jnp.where(scale > 0, scale, 1.0)
        return _like(x, arr)

    def inverse_transform(self, y: DNDarray) -> DNDarray:
        sanitize_in(y)
        arr = y.larray
        if self.with_std and self.var_ is not None:
            scale = jnp.sqrt(self.var_.larray)
            arr = arr * jnp.where(scale > 0, scale, 1.0)
        if self.with_mean and self.mean_ is not None:
            arr = arr + self.mean_.larray
        return _like(y, arr)


@functools.lru_cache(maxsize=64)
def _affine_pass(n: int, d: int, inverse: bool, interpret: bool):
    """The chip's form of ``_affine`` over one device's rows of a tall
    narrow f32 table, tiled as the selection's passes tile it (blocks
    ``(d, tn)`` of ``x.T``, a bitcast of the feature-major array): one read
    and one write, the division a division. Named ``scaler.transform.pass``
    (``docs/API.md``, observability)."""
    tn = _tiles._pick_tn(n, d, 8)

    def kernel(xt_ref, shift_ref, scale_ref, yt_ref):
        x = xt_ref[...]
        yt_ref[...] = x * scale_ref[...] + shift_ref[...] if inverse else (x - shift_ref[...]) / scale_ref[...]

    block = _tiles._x_block(d, tn)
    call = _tiles._call(kernel, "scaler.transform.pass", n, tn, [block, _tiles._const((d, 1)), _tiles._const((d, 1))],
                        jax.ShapeDtypeStruct((d, n), jnp.float32), block, interpret)
    return lambda x, shift, scale: call(x.T, shift[:, None], scale[:, None]).T


def _affine_body(shape, jdtype: str, out_jdtype: str, inverse: bool, shifts: bool, scales: bool, on_chip: bool,
                 mesh=None, axis_name=None, interpret: bool = False):
    """The traced body of ``_affine_program``, for a larger program to call
    (``_robust_fit_transform_program``): ``(arr, shift, scale) -> (arr -
    shift) / scale`` in ``out_jdtype`` (``inverse``: ``arr * scale +
    shift``), ``shift`` and ``scale`` one value a feature (ignored without
    ``shifts`` / ``scales``): one read of the table with the cast fused into
    it and one write. ``on_chip`` (``_affine_key`` decides:
    ``tall_narrow_serves``) it is the kernel ``_affine_pass``, under
    ``shard_map`` on a ``mesh`` as the selection's passes are; elsewhere the
    same expression left to XLA."""
    out_dtype = jnp.dtype(out_jdtype)
    if on_chip:
        n, d = int(shape[0]), int(shape[1])
        run_pass = _affine_pass(n // (mesh.devices.size if axis_name is not None else 1), d, inverse, interpret)
        if mesh is not None:
            run_pass = _shard_map(run_pass, mesh=mesh, check_vma=False, in_specs=(P(axis_name, None), P(), P()),
                                  out_specs=P(axis_name, None))

    def run(arr, shift, scale):
        with jax.named_scope("scaler.transform"):
            arr = arr.astype(out_dtype)
            if on_chip:
                d = arr.shape[1]
                return run_pass(arr, shift.astype(out_dtype) if shifts else jnp.zeros((d,), out_dtype),
                                scale.astype(out_dtype) if scales else jnp.ones((d,), out_dtype))
            if inverse:
                arr = arr * scale if scales else arr
                return arr + shift if shifts else arr
            arr = arr - shift if shifts else arr
            return arr / scale if scales else arr

    return run


@observed_program_cache("scaler.transform", maxsize=64)
def _affine_program(shape, jdtype: str, out_jdtype: str, inverse: bool, shifts: bool, scales: bool, on_chip: bool,
                    mesh=None, axis_name=None, interpret: bool = False):
    """``_affine_body`` as ONE jitted program a transform."""
    return jax.jit(_affine_body(shape, jdtype, out_jdtype, inverse, shifts, scales, on_chip, mesh, axis_name, interpret))


register_mesh_cache(_affine_program)


def _affine_key(x: DNDarray, inverse: bool, shift_dtype, scale_dtype) -> tuple:
    """``_affine_program``'s key for ``x`` and statistics of the two dtypes
    (``None``: no such statistic): the result's dtype (the third), whether
    the kernel serves (``on_chip``) and over which mesh."""
    out = jnp.result_type(_float_of(x).jax_type(), *(t for t in (shift_dtype, scale_dtype) if t is not None))
    devices = x.comm.size
    on_chip = out == jnp.float32 and _tiles.tall_narrow_serves(
        jax.default_backend(), x.dtype.jax_type(), x.gshape, x.split, devices)
    mesh = x.comm.mesh if on_chip and devices > 1 else None
    return (tuple(x.gshape), np.dtype(x.dtype.jax_type()).name, np.dtype(out).name, inverse,
            shift_dtype is not None, scale_dtype is not None, on_chip, mesh,
            x.comm.axis_name if mesh is not None and x.split == 0 else None)


def _affine(x: DNDarray, shift, scale, inverse: bool = False) -> DNDarray:
    """``(x - shift) / scale`` (``inverse``: ``x * scale + shift``) along the
    feature axis as one program (``_affine_program``); ``shift`` / ``scale``
    may be ``None``. The result has ``x``'s split."""
    key = _affine_key(x, inverse, *(None if a is None else a.dtype for a in (shift, scale)))
    none = np.zeros((), key[2])  # of the result's dtype; a host value: the program ignores it, no op dispatched to make it
    # (where the kernel serves a split table its shards are equal: ``larray`` is then the physical array itself)
    return _like(x, _affine_program(*key)(x.larray, none if shift is None else shift, none if scale is None else scale))


def _like(x: DNDarray, arr) -> DNDarray:
    gshape = tuple(int(s) for s in arr.shape)
    split = x.split
    if split is not None:
        arr = x.comm.shard(arr, split)
    return DNDarray(
        arr, gshape, types.canonical_heat_type(arr.dtype), split, x.device, x.comm
    )


class MinMaxScaler(BaseEstimator, TransformMixin):
    """Scale features to a given range (reference: preprocessing.py:158).
    ``copy`` is accepted for reference parity and not read: a transform
    returns a new array, the caller's stays."""

    def __init__(self, feature_range: Tuple[float, float] = (0.0, 1.0), copy: bool = True, clip: bool = False):
        if feature_range[0] >= feature_range[1]:
            raise ValueError(f"minimum of feature_range must be smaller than maximum, got {feature_range}")
        self.feature_range = feature_range
        self.copy = copy
        self.clip = clip
        self.data_min_ = None
        self.data_max_ = None
        self.data_range_ = None
        self.min_ = None
        self.scale_ = None

    def fit(self, x: DNDarray) -> "MinMaxScaler":
        sanitize_in(x)
        self.data_min_ = statistics.min(x, axis=0)
        self.data_max_ = statistics.max(x, axis=0)
        rng = self.data_max_.larray - self.data_min_.larray
        rng = jnp.where(rng > 0, rng, 1.0)
        lo, hi = self.feature_range
        scale = (hi - lo) / rng
        self.scale_ = scale
        self.min_ = lo - self.data_min_.larray * scale
        self.data_range_ = rng
        return self

    def transform(self, x: DNDarray) -> DNDarray:
        sanitize_in(x)
        arr = x.larray.astype(jnp.result_type(self.scale_.dtype))
        arr = arr * self.scale_ + self.min_
        if self.clip:
            arr = jnp.clip(arr, self.feature_range[0], self.feature_range[1])
        return _like(x, arr)

    def inverse_transform(self, y: DNDarray) -> DNDarray:
        sanitize_in(y)
        arr = (y.larray - self.min_) / self.scale_
        return _like(y, arr)


class Normalizer(BaseEstimator, TransformMixin):
    """Normalize samples to unit norm (reference: preprocessing.py:284).
    ``copy`` is accepted for reference parity and not read: a transform
    returns a new array, the caller's stays."""

    def __init__(self, norm: str = "l2", copy: bool = True):
        if norm not in ("l1", "l2", "max"):
            raise NotImplementedError(f"unsupported norm {norm}")
        self.norm = norm
        self.copy = copy

    def fit(self, x: DNDarray) -> "Normalizer":
        return self

    def transform(self, x: DNDarray) -> DNDarray:
        sanitize_in(x)
        arr = x.larray.astype(_float_of(x).jax_type())
        if self.norm == "l2":
            norms = jnp.sqrt(jnp.sum(arr * arr, axis=1, keepdims=True))
        elif self.norm == "l1":
            norms = jnp.sum(jnp.abs(arr), axis=1, keepdims=True)
        else:
            norms = jnp.max(jnp.abs(arr), axis=1, keepdims=True)
        arr = arr / jnp.where(norms > 0, norms, 1.0)
        return _like(x, arr)


class MaxAbsScaler(BaseEstimator, TransformMixin):
    """Scale by the per-feature maximum absolute value (reference:
    preprocessing.py:358). ``copy`` is accepted for reference parity and not
    read: a transform returns a new array, the caller's stays."""

    def __init__(self, copy: bool = True):
        self.copy = copy
        self.max_abs_ = None
        self.scale_ = None

    def fit(self, x: DNDarray) -> "MaxAbsScaler":
        sanitize_in(x)
        arr = x.larray
        max_abs = jnp.max(jnp.abs(arr), axis=0)
        self.max_abs_ = max_abs
        self.scale_ = jnp.where(max_abs > 0, max_abs, 1.0)
        return self

    def transform(self, x: DNDarray) -> DNDarray:
        sanitize_in(x)
        arr = x.larray.astype(_float_of(x).jax_type()) / self.scale_
        return _like(x, arr)

    def inverse_transform(self, y: DNDarray) -> DNDarray:
        sanitize_in(y)
        return _like(y, y.larray * self.scale_)


def _robust_stats_body(centering: bool, scaling: bool):
    """The traced body of ``_robust_stats_program``, for a larger program to
    call: ``percentiles (q, ...) -> (center, iqr)``, the rows of
    ``RobustScaler``'s percentiles as the scaler keeps them (the range's two
    first, the median last; a range of 0 scales by 1)."""

    def run(pct):
        iqr = pct[1] - pct[0] if scaling else None
        return pct[-1] if centering else None, None if iqr is None else jnp.where(iqr > 0, iqr, 1.0)

    return run


@observed_program_cache("scaler.robust_stats", maxsize=16)
def _robust_stats_program(centering: bool, scaling: bool):
    """``_robust_stats_body`` as one small program: no op of
    ``RobustScaler.fit`` is dispatched by itself."""
    return jax.jit(_robust_stats_body(centering, scaling))


@observed_program_cache("scaler.robust_fit_transform", maxsize=64)
def _robust_fit_transform_program(select_key: tuple, centering: bool, scaling: bool, affine_key: tuple):
    """``arr -> (y, center, iqr)``: ``RobustScaler.fit_transform`` as ONE
    jitted program, keyed like its parts (``statistics._selection_key``, the
    two flags, ``_affine_key``) and made of their bodies under their own
    scopes: the counting selection of the scaler's percentiles, the two
    statistics, the transform by them. The outputs and the temporaries of
    the whole call are placed at one launch and nothing returns to the host
    between the fit and the transform; the device ops keep the names they
    have in the staged programs. ``center`` / ``iqr`` are ``None`` without
    ``centering`` / ``scaling``."""
    select = statistics._percentile_select_body(*select_key)
    stats = _robust_stats_body(centering, scaling)
    affine = _affine_body(*affine_key)

    def run(arr):
        center, iqr = stats(select(arr))
        return affine(arr, center, iqr), center, iqr

    return jax.jit(run)


register_mesh_cache(_robust_fit_transform_program)


class RobustScaler(BaseEstimator, TransformMixin):
    """Scale by median and IQR: one ``ht.percentile`` call a fit (a counting selection on a tall table), one program a transform, one a ``fit_transform``.

    (Reference: preprocessing.py:444 — uses the distributed percentile.)
    ``fit`` is one ``ht.percentile(x, [q_min,
    q_max, 50], axis=0)`` (the median only with ``with_centering``, the
    range only with ``with_scaling``): along the sample axis of a tall table
    an exact counting selection that finds all three in the same passes, not
    three sorts (``statistics.percentile`` says where). ``transform`` and
    ``inverse_transform`` are one program each. ``fit_transform`` is ONE
    program (the selection, the two statistics and the transform in one
    launch, the same values bit for bit) where that selection serves and a
    flag is set; where ``percentile`` would sort (small, integer, complex
    tables, other widths) it is ``fit(x).transform(x)``, the staged three
    programs, as ``fit`` and ``transform`` called by themselves always are.
    ``copy`` is accepted and not read."""

    def __init__(
        self,
        quantile_range: Tuple[float, float] = (25.0, 75.0),
        copy: bool = True,
        with_centering: bool = True,
        with_scaling: bool = True,
        unit_variance: bool = False,
    ):
        q_min, q_max = quantile_range
        if not 0 <= q_min <= q_max <= 100:
            raise ValueError(f"invalid quantile range {quantile_range}")
        if unit_variance:
            raise NotImplementedError("unit_variance rescaling is not yet supported (reference parity)")
        self.quantile_range = quantile_range
        self.copy = copy
        self.with_centering = with_centering
        self.with_scaling = with_scaling
        self.unit_variance = unit_variance
        self.center_ = None
        self.iqr_ = None

    def _percentiles(self) -> list:
        """What a fit asks of ``x``: the range's two (``with_scaling``), then the median (``with_centering``)."""
        return (list(self.quantile_range) if self.with_scaling else []) + ([50.0] if self.with_centering else [])

    def _keep(self, x: DNDarray, center, iqr) -> None:
        """Set ``center_`` (a ``DNDarray``; left alone without ``with_centering``) and ``iqr_`` (a ``jax.Array``)."""
        self.iqr_ = iqr
        if self.with_centering:
            self.center_ = DNDarray(center, tuple(center.shape), types.canonical_heat_type(center.dtype), None, x.device,
                                    x.comm)

    def fit(self, x: DNDarray) -> "RobustScaler":
        with _call_span("ht.call.robustscaler.fit"):
            sanitize_in(x)
            q = self._percentiles()
            if q:
                pct = statistics.percentile(x, q, axis=0)
                self._keep(x, *_robust_stats_program(self.with_centering, self.with_scaling)(pct.larray))
            return self

    def fit_transform(self, x: DNDarray) -> DNDarray:
        """``fit(x).transform(x)`` as ONE program where the fit's percentiles
        come from the counting selection (``statistics._form_of``: what
        ``ht.percentile`` itself asks), the staged three elsewhere; the same
        ``center_``, ``iqr_`` and result bit for bit."""
        with _call_span("ht.call.robustscaler.fit_transform"):
            sanitize_in(x)
            qv = np.asarray(self._percentiles(), np.float64)
            form = statistics._form_of(x, 0, qv) if qv.size else "sort"
            if form == "sort":
                _telemetry.inc("scaler.fit_transform.staged")
                return self.fit(x).transform(x)
            _telemetry.inc("scaler.fit_transform.fused")
            stat = x.dtype.jax_type()  # the selection's values have the table's dtype
            prog = _robust_fit_transform_program(
                statistics._selection_key(x, qv, "linear", form, (qv.size, x.gshape[1])), self.with_centering,
                self.with_scaling, _affine_key(x, False, stat if self.with_centering else None,
                                               stat if self.with_scaling else None))
            statistics._count_selection(form)
            y, center, iqr = prog(statistics._selection_operand(x))
            self._keep(x, center, iqr)
            return _like(x, y)

    def _shift_and_scale(self):
        return (self.center_.larray if self.with_centering and self.center_ is not None else None,
                self.iqr_ if self.with_scaling and self.iqr_ is not None else None)

    def transform(self, x: DNDarray) -> DNDarray:
        with _call_span("ht.call.robustscaler.transform"):
            sanitize_in(x)
            return _affine(x, *self._shift_and_scale())

    def inverse_transform(self, y: DNDarray) -> DNDarray:
        with _call_span("ht.call.robustscaler.inverse_transform"):
            sanitize_in(y)
            return _affine(y, *self._shift_and_scale(), inverse=True)
