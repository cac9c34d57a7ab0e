"""Full SVD.

The reference ships only a stub raising toward hSVD
(/root/reference/heat/core/linalg/svd.py:10). Here ``svd`` is a real
composition over the suite's matmul-native primitives (ISSUE 19):

- ``method="qr"`` — tall split-0 operands factor via TSQR (the grouped
  ring all-gather of the R blocks on ICI) followed by an SVD of the
  small replicated R: ``A = QR, R = U_R Σ Vᴴ ⇒ U = Q·U_R``. The
  operand itself is never gathered — only the ``(p, n, n)`` R stack
  moves.
- ``method="polar"`` — the factorization-suite composition
  ``A = U_p H`` (Newton–Schulz :func:`~.factorizations.polar`, a pure
  ppermute-ring program) then ``H = V Σ Vᴴ`` (eigh of the small
  replicated Hermitian factor), giving ``A = (U_p V) Σ Vᴴ``. The
  distributed census is collective-permute ONLY — zero all-gathers of
  anything, which is the pinned contract for operands whose ``n`` is
  past the TSQR merge gate.
- ``method="auto"`` — qr while the TSQR gate admits ``n``
  (``n <= 4096``), polar past it.

``compute_uv=False`` never forms U or V: the TSQR path stops at the
R factor's singular values; host-resident (:class:`HostArray`)
operands stream row windows through the PR-11 depth-2 staged
double-buffer accumulating the Gram matrix ``G = AᴴA`` and return
``sqrt(eigvalsh(G))`` without the operand ever being device-resident.

Documented tolerance (pinned in tests/test_factorizations.py): for
float32 well-conditioned operands both methods match
``jnp.linalg.svd``'s singular values to ``rtol=1e-4`` and reconstruct
``‖A - U Σ Vᴴ‖_F / ‖A‖_F <= 1e-4``; singular VECTORS match up to the
usual per-column unitary phase. The Gram values-only paths square the
condition number — singular values below ``‖A‖·sqrt(eps)`` are noise
there, the price of the single-pass stream.

``full_matrices=True`` raises :class:`FullMatricesNotSupported` — the
orthogonal complement is a dense ``m × m`` replicated factor no
distributed schedule here can afford; use ``hsvd_rank``/``hsvd_rtol``
for rank-truncated factors or ``ht.linalg.eigh`` on the Gram/covariance
matrix when only the column space is needed.
"""

from __future__ import annotations

import collections

import numpy as np

import jax
import jax.numpy as jnp

from .. import types
from .. import _padding
from ..dndarray import DNDarray
from ..sanitation import sanitize_in
from ._lapack import safe_svd, safe_svdvals
from ...observability.tracing import span as _span

__all__ = ["FullMatricesNotSupported", "svd"]

SVD = collections.namedtuple("SVD", "U, S, Vh")

# the TSQR merge gate (qr.py): past this column count the stacked R
# blocks outgrow the merge and svd switches to the polar composition
_TSQR_MAX_N = 4096

_METHODS = ("auto", "qr", "polar")


class FullMatricesNotSupported(NotImplementedError):
    """``svd(full_matrices=True)`` — the full orthonormal basis is a
    dense ``m × m`` (resp. ``n × n``) REPLICATED factor: for the
    distributed operands this module serves it does not fit any
    schedule the planner could price. Alternatives, by what the caller
    actually needs:

    - rank-truncated factors: ``ht.linalg.hsvd_rank`` /
      ``ht.linalg.hsvd_rtol`` (hierarchical, distributed, streamed);
    - the column-space spectrum: ``ht.linalg.eigh`` on the Gram or
      covariance matrix (matmul-native, ISSUE 19);
    - the reduced factors: ``full_matrices=False`` (this function).
    """


def _values_dnd(s, dtype, ref: DNDarray) -> DNDarray:
    return DNDarray(s, (int(s.shape[0]),), dtype, None, ref.device, ref.comm)


def _gram_svdvals_arr(g, jt):
    """Descending singular values from a replicated Gram matrix."""
    w = jnp.linalg.eigvalsh(g)  # ascending
    return jnp.sqrt(jnp.clip(w[::-1], 0, None)).astype(jt)


def _host_svdvals(host, jt):
    """Values-only SVD of a host-resident operand: one staged pass of
    row windows accumulating the Gram matrix on device (the window
    stream is the hsvd "sketch" pass shape with a rank-n resident), no
    device materialization of the operand. Descending values, local."""
    from ...redistribution import staging as _staging

    m, n = (int(s) for s in host.shape)
    itemsize = np.dtype(jt).itemsize
    sched = _staging.plan_staged_passes(
        (m, n), jt, [{"tag": "gram", "axis": 0}],
        out_bytes=n * n * itemsize,
    )
    wins = _staging.window_extents((m, n), itemsize, 0, _staging.slab_bytes())
    acc = jnp.zeros((n, n), jt)

    def consume(_k, slab, _ext):
        nonlocal acc
        w = jnp.asarray(slab).astype(jt)
        acc = acc + jnp.matmul(
            jnp.conjugate(w.T), w, precision="highest"
        )

    _staging.stream_windows(host, 0, wins, consume, plan_id=sched.plan_id)
    return _gram_svdvals_arr(acc, jt)


def svd(
    A,
    full_matrices: bool = False,
    compute_uv: bool = True,
    method: str = "auto",
):
    """Singular value decomposition ``A = U·diag(S)·Vh`` (reduced form).

    ``method`` selects the distributed schedule: ``"qr"`` (TSQR + small
    SVD of R), ``"polar"`` (Newton–Schulz polar + eigh of H — zero
    all-gathers), or ``"auto"`` (qr while ``n`` fits the TSQR merge,
    polar past it). Replicated operands use XLA's SVD directly.
    ``compute_uv=False`` returns only the descending singular values and
    never forms U/V; a host-resident :class:`HostArray` operand is
    served by a staged Gram pass (values only). See the module
    docstring for the documented tolerances and
    :class:`FullMatricesNotSupported` for the ``full_matrices=True``
    contract.
    """
    from . import basics
    from .qr import qr as _qr

    if method not in _METHODS:
        raise ValueError(f"method must be one of {_METHODS}, got {method!r}")

    from ...redistribution.staging import HostArray

    if isinstance(A, HostArray):
        return _svd_host(A, full_matrices, compute_uv, method)

    sanitize_in(A)
    if A.ndim != 2:
        raise ValueError(f"svd requires a 2-dimensional array, got {A.ndim}")

    dtype = A.dtype
    if types.heat_type_is_exact(dtype):
        dtype = types.float32
    jt = dtype.jax_type()
    m, n = (int(s) for s in A.shape)
    comm = A.comm

    # values-only first: no U/V is ever formed on these paths, and
    # full_matrices is meaningless without them
    if not compute_uv:
        if A.split == 1 and comm.is_distributed() and n > m:
            return svd(
                basics.transpose(A, None),
                full_matrices=False, compute_uv=False, method=method,
            )
        if A.split is not None and comm.is_distributed():
            a0 = A if A.split == 0 else A.resplit(0)
            use_qr = method == "qr" or (method == "auto" and n <= _TSQR_MAX_N)
            if m >= n and use_qr:
                _, r = _qr(
                    a0 if a0.dtype == dtype else a0.astype(dtype), calc_q=False
                )
                return _values_dnd(safe_svdvals(r.larray), dtype, A)
            if m >= n:
                # past the TSQR gate (or method="polar"): ring Gram —
                # one ppermute-ring X^H X, eigvalsh of the small result
                from .factorizations import _ring_xhy

                a0 = a0 if a0.dtype == dtype else a0.astype(dtype)
                g = _ring_xhy(a0, a0)
                return _values_dnd(_gram_svdvals_arr(g, jt), dtype, A)
        return _values_dnd(safe_svdvals(A.larray.astype(jt)), dtype, A)

    if full_matrices:
        raise FullMatricesNotSupported(
            "svd(full_matrices=True): the full orthonormal basis is a dense "
            f"replicated ({m}, {m}) factor no distributed schedule here can "
            "hold — use full_matrices=False for the reduced factors, "
            "ht.linalg.hsvd_rank/hsvd_rtol for rank-truncated ones, or "
            "ht.linalg.eigh on the Gram matrix for the spectrum"
        )

    if comm.is_distributed() and A.split is not None:
        if A.split == 1 and n > m:
            # wide: svd(Aᵀ) and swap factors
            u_t, s, vh_t = svd(
                basics.transpose(A, None),
                full_matrices=False, compute_uv=True, method=method,
            )
            return SVD(basics.transpose(vh_t, None), s, basics.transpose(u_t, None))
        a0 = A if A.split == 0 else A.resplit(0)
        a0 = a0 if a0.dtype == dtype else a0.astype(dtype)
        use_qr = method == "qr" or (method == "auto" and n <= _TSQR_MAX_N)
        if use_qr:
            q, r = _qr(a0, calc_q=True)
            u_r, s, vh = safe_svd(r.larray, full_matrices=False)
            k = int(u_r.shape[1])
            u_phys = _padding.mask_phys(q._phys @ u_r, (m, k), 0)
            U = DNDarray(u_phys, (m, k), dtype, 0, A.device, comm)
            S = _values_dnd(s, dtype, A)
            Vh = DNDarray(
                vh, tuple(int(x) for x in vh.shape), dtype, None, A.device, comm
            )
            return SVD(U, S, Vh)
        return _svd_polar(a0, dtype, jt)

    arr = A.larray.astype(jt)
    u, s, vh = safe_svd(arr, full_matrices=False)
    split_u = A.split if A.split == 0 else None
    split_vh = 1 if A.split == 1 else None
    U = DNDarray(
        comm.shard(u, split_u) if split_u is not None else u,
        tuple(int(x) for x in u.shape),
        dtype,
        split_u,
        A.device,
        comm,
    )
    S = _values_dnd(s, dtype, A)
    Vh = DNDarray(
        comm.shard(vh, split_vh) if split_vh is not None else vh,
        tuple(int(x) for x in vh.shape),
        dtype,
        split_vh,
        A.device,
        comm,
    )
    return SVD(U, S, Vh)


def _svd_polar(a0: DNDarray, dtype, jt):
    """The polar composition: ``A = U_p H`` (ppermute-ring Newton–
    Schulz), ``H = V Σ Vᴴ`` (eigh of the small replicated Hermitian
    factor, descending reorder), ``U = U_p V`` (split-0 × replicated —
    a local shard matmul, no collective). Census: collective-permute
    only; the operand is never gathered."""
    from . import basics
    from .factorizations import polar as _polar

    m, n = (int(s) for s in a0.shape)
    comm = a0.comm
    u_p, h = _polar(a0)
    w, v = jnp.linalg.eigh(h.larray)  # ascending
    s = jnp.clip(w[::-1], 0, None).astype(jt)
    v_desc = v[:, ::-1]
    v_dnd = DNDarray(v_desc, (n, n), dtype, None, a0.device, comm)
    U = basics.matmul(u_p, v_dnd, precision="highest")
    if U.split != 0:
        U = U.resplit(0)
    Vh = DNDarray(
        jnp.conjugate(v_desc.T), (n, n), dtype, None, a0.device, comm
    )
    return SVD(U, _values_dnd(s, dtype, a0), Vh)


def _svd_host(host, full_matrices: bool, compute_uv: bool, method: str):
    """HostArray operand: the values-only staged Gram pass when the
    pass structure allows (no U/V), the materialize escape hatch when
    the operand fits HBM anyway, and a typed redirect to hsvd when
    factors of a genuinely out-of-core operand are asked for."""
    from ...redistribution import staging as _staging
    from .. import factories

    dtype = types.canonical_heat_type(host.dtype)
    if types.heat_type_is_exact(dtype):
        dtype = types.float32
    jt = dtype.jax_type()
    if not compute_uv:
        if not _staging.ooc_engaged(host.nbytes, host_resident=True):
            a = _staging.materialize(host, what="svd operand")
            return svd(a, compute_uv=False, method=method)
        s = _host_svdvals(host, jt)
        with _span("ht.sync.read", what="svd.values"):
            s_host = np.asarray(jax.device_get(s))
        return factories.array(s_host, split=None)
    if full_matrices:
        raise FullMatricesNotSupported(
            "svd(full_matrices=True) on a host-resident operand: use "
            "full_matrices=False, or ht.linalg.hsvd_rank/hsvd_rtol for "
            "rank-truncated factors"
        )
    if not _staging.ooc_engaged(host.nbytes, host_resident=True):
        a = _staging.materialize(host, what="svd operand")
        return svd(a, compute_uv=True, method=method)
    raise NotImplementedError(
        "svd(compute_uv=True) of a host-resident operand needs a "
        "multi-pass factor stream — use ht.linalg.hsvd_rank/hsvd_rtol "
        "(staged 2-pass hierarchical SVD) for out-of-core factors, or "
        "compute_uv=False for the staged values-only Gram pass"
    )
