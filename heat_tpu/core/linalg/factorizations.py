"""Matmul-native distributed dense factorizations (ISSUE 19).

The paper's thesis (arXiv:2112.09017) is that dense factorizations on
TPU pods should be *recast as matmul chains* — the MXU plus the ICI
all-gather/ppermute rings are the whole machine — rather than ported
from the panel-factor/broadcast CPU playbook. This module is that suite:

- :func:`polar` — Newton–Schulz polar decomposition. Every iteration is
  two ring matmuls (``kernels.cmatmul.ring_matmul_reduce``): the Gram
  sweep ``X^H X`` and the update ``X(1.5 I - 0.5 G)``, with a
  Frobenius-residual convergence carry inside one ``while_loop``. No
  transcendental, no pivoting — the factorization the paper calls out as
  "the" TPU-native primitive.
- :func:`eigh` — symmetric/Hermitian eigendecomposition via polar-based
  spectral divide-and-conquer: ``S = sign(A - μI)`` from the polar
  factor, the two spectral projectors ``(I ∓ S)/2``, subspaces via TSQR
  of projector-range probes, then recursion on the (resplit-0)
  sub-operands. Everything except two tiny host reads of projector
  traces (declared in ``analysis/boundaries``) stays on-device.
- :func:`cholesky` / :func:`lu` / :func:`solve` — blocked right-looking
  factorizations with the panel column assembled by the cmatmul
  all-gather ring and the trailing update as a local MXU matmul under
  the in-flight hops (the lookahead form); block triangular solves ride
  a ppermute ring broadcast (:func:`heat_tpu.kernels.cmatmul.ring_bcast`).
- :func:`svd` composition lives in ``svd.py``: polar + eigh for the
  factored form, Gram eigenvalues for ``compute_uv=False``.

Movement contract: every solver launches ONLY ``collective-permute``
chains, pre-declared as a :class:`~heat_tpu.redistribution.schedule.Schedule`
(``_factorization_plan``) whose ``plan_id`` stamps the kernel's
``redist_plan_<id>`` named scope — shardlint downgrades the planned
movement to info severity, and tests pin program census == plan census.
Sequential (``HEAT_TPU_REDIST_OVERLAP=0``) and pipelined (``=1``) issue
orders are bit-identical: the rings only place, select, or accumulate in
one fixed order (see ``kernels/cmatmul.py``).

Accumulation is pinned f32-exact (``precision="highest"`` on every
internal contraction) per the numcheck SL601 contract.
"""

from __future__ import annotations

import collections
import functools

import numpy as np

import jax
import jax.numpy as jnp

from jax import lax
from jax.scipy.linalg import solve_triangular
from jax.sharding import PartitionSpec as _PS

from typing import Optional, Tuple

from .. import types
from .. import _padding
from jax import shard_map as _shard_map
from ..dndarray import DNDarray
from ..sanitation import sanitize_in
from ...kernels import cmatmul as _cm
from ...observability.tracing import span as _span
from . import basics

__all__ = [
    "Eigh",
    "LU",
    "Polar",
    "cholesky",
    "eigh",
    "golden_factorization_plans",
    "lu",
    "polar",
    "solve",
    "solve_endpoint",
]

Polar = collections.namedtuple("Polar", "U, H")
Eigh = collections.namedtuple("Eigh", "eigenvalues, eigenvectors")
LU = collections.namedtuple("LU", "perm, L, U")

# blocked inv/det rewiring engages above this order (below it the local
# XLA kernels win on launch overhead); eigh recursion resplits
# sub-operands at/above this order — tests shrink it to exercise the
# recursion at toy sizes
_EIGH_RESPLIT_MIN_N = 512
_EIGH_MAX_DEPTH = 16

_POLAR_MAXITER = 64


def _ct(x: jax.Array) -> jax.Array:
    """Conjugate transpose — THE inner-product convention of the suite
    (PR 5 fixed plain-transpose bugs in exactly these contractions)."""
    return jnp.conjugate(jnp.swapaxes(x, -1, -2))


def _ct_dnd(a: DNDarray) -> DNDarray:
    """Conjugate transpose at the DNDarray level, split axis remapped."""
    res = jnp.conjugate(jnp.swapaxes(a.larray, -1, -2))
    split = None
    if a.split is not None:
        split = {0: 1, 1: 0}.get(a.split, a.split)
    return basics._wrap(res, split, a)


def _solver_dtype(a: DNDarray):
    dt = a.dtype
    if types.heat_type_is_exact(dt):
        dt = types.float32
    return dt


def _real_eps(jt) -> float:
    return float(jnp.finfo(np.dtype(jt)).eps)


# ---------------------------------------------------------------------- #
# plans: the pre-declared collective schedules                           #
# ---------------------------------------------------------------------- #
@functools.lru_cache(maxsize=256)
def _factorization_plan(kind: str, gshape: Tuple[int, ...], dtype: str,
                        p: int, budget: Optional[int] = None):
    """The :class:`Schedule` a factorization program launches — built
    BEFORE execution, registered with observability, and stamped into
    the kernel's ``redist_plan_<id>`` named scope.

    Census contract (pinned in tests/test_factorizations.py; the HLO
    text counts a ``while_loop`` body's collectives ONCE, which is how
    the iteration-bearing polar plan stays static):

    - ``polar``     : ``5(p-1)`` collective-permutes — norm ring (p-1),
      Gram ring inside the Newton–Schulz body (2(p-1), counted once),
      final ``H = U^H A`` ring (2(p-1)).
    - ``cholesky``  : ``p(p-1)`` — one panel-column gather ring per lap.
    - ``lu``        : ``(2p-1)(p-1)`` — the gather rings plus a
      ``ring_bcast`` of the pivoted U panel row on every non-final lap.
    - ``solve-chol`` / ``solve-lu`` : ``2(p-1)^2`` — one block
      broadcast/gather ring per non-terminal lap of each sweep.
    """
    from ...redistribution import planner as _planner
    from ...redistribution.schedule import Schedule, Step
    from ...redistribution.spec import RedistSpec

    if budget is None:
        budget = _planner.budget_bytes()
    spec = RedistSpec.normalize(gshape, dtype, 0, 0, p)
    t = np.dtype(dtype).itemsize
    steps = []

    def hop(payload, detail, chunk):
        steps.append(Step(
            "ppermute", bytes_moved=int(payload), peak_bytes=2 * int(payload),
            detail=detail, chunk=chunk,
        ))

    if kind == "polar":
        m, n = gshape
        mc = -(-n // p)
        rt = np.dtype(dtype).itemsize // (2 if np.dtype(dtype).kind == "c" else 1)
        for d in range(p - 1):
            hop(rt, "frobenius-norm partial ring", d)
        for d in range(p - 1):
            hop(mc * n * t, "newton-schulz gram reduce-scatter ring "
                            "(while body; HLO census counts once)", d)
        for d in range(p - 1):
            hop(mc * n * t, "newton-schulz gram chunk gather ring (while body)", d)
        for d in range(p - 1):
            hop(mc * n * t, "hermitian factor H=U^H A reduce-scatter ring", d)
        for d in range(p - 1):
            hop(mc * n * t, "hermitian factor H chunk gather ring", d)
        notes = (f"newton-schulz polar ({m}x{n}): every iteration reships the "
                 f"gram ring payload; the schedule prices the static program "
                 f"(while-body collectives once), maxiter={_POLAR_MAXITER}")
    elif kind == "cholesky":
        n = gshape[0]
        nb = -(-n // p)
        for k in range(p):
            for d in range(p - 1):
                hop(nb * nb * t, f"panel column gather ring (lap {k})", k)
        notes = (f"blocked right-looking cholesky ({n}x{n}, nb={nb}): panel "
                 f"column assembled by gather ring, trailing update local MXU "
                 f"under the hops")
    elif kind == "lu":
        n = gshape[0]
        nb = -(-n // p)
        n_pad = nb * p
        for k in range(p):
            for d in range(p - 1):
                hop(nb * nb * t, f"panel column gather ring (lap {k})", k)
        for k in range(p - 1):
            trail = n_pad - (k + 1) * nb
            for d in range(p - 1):
                hop(nb * trail * t, f"pivoted U panel row bcast ring (lap {k})", k)
        notes = (f"blocked right-looking LU ({n}x{n}, nb={nb}): block-local "
                 f"partial pivoting; U panel row broadcast around the ring, "
                 f"trailing update local MXU under the hops")
    elif kind in ("solve-chol", "solve-lu"):
        n, nrhs = gshape
        nb = -(-n // p)
        for k in range(p - 1):
            for d in range(p - 1):
                hop(nb * nrhs * t, f"forward-sweep block ring (lap {k})", k)
        for k in range(p - 1):
            for d in range(p - 1):
                hop(nb * nrhs * t, f"backward-sweep block ring (lap {k})", k)
        notes = (f"block triangular solve ({n}x{n}, nrhs={nrhs}, nb={nb}, "
                 f"{kind.split('-')[1]} factors): broadcast/gather ring per "
                 f"non-terminal lap of each sweep")
    else:
        raise ValueError(f"unknown factorization plan kind {kind!r}")
    return Schedule(spec, f"factorization-{kind}", steps, budget, notes=notes)


def golden_factorization_plans():
    """Named plans at pinned shapes/budget — the determinism fixture
    consumed by ``scripts/redist_plans.py`` (plan_ids must be stable
    across runs and machines)."""
    from ...redistribution import planner as _planner

    b = _planner.DEFAULT_BUDGET_MB << 20
    return [
        ("polar_f32_65536x1024_p8",
         _factorization_plan("polar", (65536, 1024), "float32", 8, budget=b)),
        ("cholesky_f32_8192_p8",
         _factorization_plan("cholesky", (8192, 8192), "float32", 8, budget=b)),
        ("lu_f32_8192_p8",
         _factorization_plan("lu", (8192, 8192), "float32", 8, budget=b)),
        ("solve_chol_f32_8192x256_p8",
         _factorization_plan("solve-chol", (8192, 256), "float32", 8, budget=b)),
        ("solve_lu_f32_8192x256_p8",
         _factorization_plan("solve-lu", (8192, 256), "float32", 8, budget=b)),
    ]


def _runtime_plan(kind, gshape, jt, comm):
    """Build the plan a public solver is about to execute."""
    from ...redistribution import planner as _planner

    return _factorization_plan(
        kind, tuple(int(s) for s in gshape), np.dtype(jt).name, comm.size,
        budget=_planner.budget_bytes(),
    )


# ---------------------------------------------------------------------- #
# Newton–Schulz polar                                                    #
# ---------------------------------------------------------------------- #
@functools.lru_cache(maxsize=64)
def _polar_program(mesh, axis_name: str, lrows: int, n: int, jdtype: str,
                   maxiter: int, tol: float, pipelined: bool, plan_id: str):
    """Compiled distributed Newton–Schulz polar iteration for split-0
    physical shards of shape ``(lrows, n)``.

    Every step is a ring matmul: the Gram sweep ``G = X^H X`` is
    ``ring_matmul_reduce`` over the row shards (contraction axis = the
    split axis), the update ``X(1.5 I - 0.5 G)`` a local MXU matmul
    against the replicated ``G``. The convergence carry is
    ``err = ||G - I||_F / sqrt(n)`` measured BEFORE the update (one-step
    lag: the exit iterate is one step better than the test), inside one
    ``while_loop`` — so the HLO collective census is static regardless
    of iteration count. f32-exact accumulation everywhere
    (``precision="highest"``, numcheck SL601)."""
    p = mesh.devices.size
    jt = np.dtype(jdtype)
    rt = np.dtype(jnp.finfo(jt).dtype)
    perm = _cm.grouped_ring_perm(1, p)

    def kernel(a_loc):
        with jax.named_scope(f"redist_plan_{plan_id}"), _cm.stamp_scope("polar"):
            i = lax.axis_index(axis_name)
            # Frobenius norm of the operand: scalar partials around the
            # ring (replicated-identical: one fixed summation order)
            part = jnp.sum(
                jnp.real(jnp.conjugate(a_loc) * a_loc)
            ).astype(rt)
            stacked = _cm.ring_all_gather(part, axis_name, p, i, perm,
                                          pipelined=pipelined)
            nrm = jnp.sqrt(jnp.sum(stacked))
            tiny = jnp.asarray(jnp.finfo(rt).tiny, rt)
            x0 = a_loc / jnp.maximum(nrm, tiny).astype(jt)
            eye = jnp.eye(n, dtype=jt)

            def gram(x):
                g = _cm.ring_matmul_reduce(
                    _ct(x), x, axis_name, p, precision="highest",
                    pipelined=pipelined,
                )
                return g[:n]

            def cond(carry):
                it, _, err = carry
                return jnp.logical_and(it < maxiter, err > tol)

            def body(carry):
                it, x, _ = carry
                g = gram(x)
                err = (jnp.linalg.norm(g - eye) / np.sqrt(n)).astype(rt)
                xn = jnp.matmul(x, 1.5 * eye - 0.5 * g, precision="highest")
                return it + 1, xn, err

            carry0 = (jnp.asarray(0, jnp.int32), x0, jnp.asarray(jnp.inf, rt))
            _, u_loc, _ = lax.while_loop(cond, body, carry0)
            h = _cm.ring_matmul_reduce(
                _ct(u_loc), a_loc, axis_name, p, precision="highest",
                pipelined=pipelined,
            )[:n]
            h = 0.5 * (h + _ct(h))
            return u_loc, h

    mapped = _shard_map(
        kernel, mesh=mesh,
        in_specs=(_PS(axis_name, None),),
        out_specs=(_PS(axis_name, None), _PS(None, None)),
        check_vma=False,
    )
    return jax.jit(mapped)


@functools.lru_cache(maxsize=64)
def _polar_local_program(m: int, n: int, jdtype: str, maxiter: int, tol: float):
    """Single-program twin of :func:`_polar_program`: same scaled
    iteration, same convergence carry, plain matmuls."""
    jt = np.dtype(jdtype)
    rt = np.dtype(jnp.finfo(jt).dtype)

    def fn(a):
        tiny = jnp.asarray(jnp.finfo(rt).tiny, rt)
        nrm = jnp.linalg.norm(a).astype(rt)
        x0 = a / jnp.maximum(nrm, tiny).astype(jt)
        eye = jnp.eye(n, dtype=jt)

        def cond(carry):
            it, _, err = carry
            return jnp.logical_and(it < maxiter, err > tol)

        def body(carry):
            it, x, _ = carry
            g = jnp.matmul(_ct(x), x, precision="highest")
            err = (jnp.linalg.norm(g - eye) / np.sqrt(n)).astype(rt)
            xn = jnp.matmul(x, 1.5 * eye - 0.5 * g, precision="highest")
            return it + 1, xn, err

        carry0 = (jnp.asarray(0, jnp.int32), x0, jnp.asarray(jnp.inf, rt))
        _, u, _ = lax.while_loop(cond, body, carry0)
        h = jnp.matmul(_ct(u), a, precision="highest")
        return u, 0.5 * (h + _ct(h))

    return jax.jit(fn)


def polar(a: DNDarray, side: str = "right", maxiter: int = _POLAR_MAXITER,
          tol: Optional[float] = None) -> Polar:
    """Polar decomposition ``A = U H`` (``side="right"``, ``m >= n``) or
    ``A = H U`` (``side="left"``, ``m <= n``) by the scaled Newton–Schulz
    iteration — U has orthonormal columns/rows, H is Hermitian positive
    semi-definite and replicated.

    Distributed split-0 operands run the ring-matmul program (split-1
    resplits first); the collective schedule is pre-declared and
    registered (see :func:`_factorization_plan`). Convergence: the
    iteration stops when ``||X^H X - I||_F / sqrt(n) <= tol`` (default
    ``50·eps`` of the real dtype) or after ``maxiter`` steps.
    """
    sanitize_in(a)
    if a._is_planar:
        from .. import complex_planar as _cp

        raise _cp.policy_error("ht.linalg.polar on planar complex operands")
    if a.ndim != 2:
        raise ValueError(f"polar requires a 2-dimensional array, got {a.ndim}")
    if side not in ("left", "right"):
        raise ValueError(f"side must be 'left' or 'right', got {side!r}")
    m, n = (int(s) for s in a.shape)
    if side == "left":
        if m > n:
            raise ValueError(
                f"side='left' requires m <= n, got {a.shape}; use side='right'"
            )
        u1, h1 = polar(_ct_dnd(a), side="right", maxiter=maxiter, tol=tol)
        return Polar(_ct_dnd(u1), h1)
    if m < n:
        raise ValueError(
            f"side='right' requires m >= n, got {a.shape}; use side='left'"
        )
    dtype = _solver_dtype(a)
    jt = dtype.jax_type()
    if tol is None:
        tol = 50.0 * _real_eps(jt)
    if a.split == 1:
        a = a.resplit(0)
    comm = a.comm
    if a.split == 0 and comm.is_distributed():
        sched = _runtime_plan("polar", (m, n), jt, comm)
        phys = a._phys.astype(jt)
        lrows = int(phys.shape[0]) // comm.size
        fn = _polar_program(
            comm.mesh, comm.axis_name, lrows, n, np.dtype(jt).name,
            int(maxiter), float(tol), _cm.ring_enabled(), sched.plan_id,
        )
        u_phys, h = fn(phys)
        u_phys = _padding.mask_phys(u_phys, (m, n), 0)
        u_arr = DNDarray(u_phys, (m, n), dtype, 0, a.device, comm)
        h_arr = DNDarray(
            _place(h, comm.sharding(2, None)), (n, n), dtype, None,
            a.device, comm,
        )
        return Polar(u_arr, h_arr)
    fn = _polar_local_program(m, n, np.dtype(jt).name, int(maxiter), float(tol))
    u, h = fn(a.larray.astype(jt))
    return Polar(basics._wrap(u, a.split, a), basics._wrap(h, None, a))


# ---------------------------------------------------------------------- #
# blocked right-looking Cholesky / LU with ring lookahead                #
# ---------------------------------------------------------------------- #
def _pad_seed_diag(w, i, nb, n, n_pad, jt):
    """Column-pad a local row block to the square padded order and seed
    ones on the pad diagonal: the padded matrix is ``diag(A, I)``, whose
    factors are ``diag(L, I)`` / ``diag(L, I)·diag(U, I)`` — pad rows and
    columns never couple into the real block, and the pad identity is
    sliced away by the ``[:, :n]`` epilogue."""
    if n_pad != n:
        w = jnp.pad(w, ((0, 0), (0, n_pad - n)))
    rows = i * nb + jnp.arange(nb)
    cols = jnp.arange(n_pad)
    mask = (rows[:, None] == cols[None, :]) & (cols[None, :] >= n)
    return jnp.where(mask, jnp.asarray(1, jt), w)


@functools.lru_cache(maxsize=64)
def _blocked_factor_program(mesh, axis_name: str, n: int, jdtype: str,
                            kind: str, pipelined: bool, plan_id: str):
    """Compiled blocked right-looking factorization (``kind`` in
    ``cholesky``/``lu``) over split-0 shards, one block row of order
    ``nb = ceil(n/p)`` per device.

    Per lap ``k``: the panel column is assembled by the cmatmul
    all-gather ring (devices above the panel contribute zeros), the
    diagonal block factors locally REPLICATED (every device runs the
    same tiny ``nb×nb`` kernel on the same bits — no broadcast needed),
    the off-diagonal L blocks come from ONE triangular solve against the
    whole gathered column, and the trailing update is a local MXU matmul
    riding under the next lap's hops. LU adds block-local partial
    pivoting (pivot search confined to the ``nb`` rows of the diagonal
    block — the paper's trade: no cross-device pivot swaps, documented
    growth-factor caveat) and a :func:`ring_bcast` of the pivoted U
    panel row."""
    p = mesh.devices.size
    jt = np.dtype(jdtype)
    nb = -(-n // p)
    n_pad = nb * p
    perm = _cm.grouped_ring_perm(1, p)

    def chol_kernel(a_loc):
        with jax.named_scope(f"redist_plan_{plan_id}"), _cm.stamp_scope("cholesky"):
            i = lax.axis_index(axis_name)
            w = _pad_seed_diag(a_loc, i, nb, n, n_pad, jt)
            lout = jnp.zeros((nb, n_pad), jt)
            for k in range(p):
                contrib = jnp.where(
                    i >= k, w[:, k * nb:(k + 1) * nb], jnp.zeros((nb, nb), jt)
                )
                col = _cm.ring_all_gather(contrib, axis_name, p, i, perm,
                                          pipelined=pipelined)
                lkk = jnp.linalg.cholesky(col[k])
                s = col.reshape(p * nb, nb)
                # the whole block column in one solve: X·L_kk^H = S, rows
                # above the panel are zero by the gather gate
                lcol = _ct(solve_triangular(lkk, _ct(s), lower=True))
                my_l = lax.dynamic_slice_in_dim(lcol, i * nb, nb, axis=0)
                my_l = jnp.where(i == k, lkk, my_l)
                lout = lout.at[:, k * nb:(k + 1) * nb].set(my_l)
                if k + 1 < p:
                    trail = lcol[(k + 1) * nb:]
                    w = w.at[:, (k + 1) * nb:].add(
                        -jnp.matmul(my_l, _ct(trail), precision="highest")
                    )
            return lout

    def lu_kernel(a_loc):
        with jax.named_scope(f"redist_plan_{plan_id}"), _cm.stamp_scope("lu"):
            i = lax.axis_index(axis_name)
            w = _pad_seed_diag(a_loc, i, nb, n, n_pad, jt)
            lout = jnp.zeros((nb, n_pad), jt)
            uout = jnp.zeros((nb, n_pad), jt)
            perm_loc = jnp.arange(nb, dtype=jnp.int32)
            detsign = jnp.asarray(1, jnp.int32)
            for k in range(p):
                contrib = jnp.where(
                    i >= k, w[:, k * nb:(k + 1) * nb], jnp.zeros((nb, nb), jt)
                )
                col = _cm.ring_all_gather(contrib, axis_name, p, i, perm,
                                          pipelined=pipelined)
                lu_pk, piv, pk = lax.linalg.lu(col[k])
                lkk = jnp.tril(lu_pk, -1) + jnp.eye(nb, dtype=jt)
                ukk = jnp.triu(lu_pk)
                detsign = detsign * jnp.prod(
                    jnp.where(piv != jnp.arange(nb, dtype=piv.dtype), -1, 1)
                ).astype(jnp.int32)
                # block-local pivoting: device k permutes its rows (and the
                # already-written L columns + provenance) before the panel
                # column is consumed
                w = jnp.where(i == k, w[pk, :], w)
                lout = jnp.where(i == k, lout[pk, :], lout)
                perm_loc = jnp.where(i == k, perm_loc[pk], perm_loc)
                s = col.reshape(p * nb, nb)
                # zero the diagonal block before the right-solve, then write
                # L_kk exactly — no rounding junk on the unit panel
                sz = lax.dynamic_update_slice(
                    s, jnp.zeros((nb, nb), jt), (k * nb, 0)
                )
                lcol = _ct(solve_triangular(_ct(ukk), _ct(sz), lower=True))
                lcol = lax.dynamic_update_slice(lcol, lkk, (k * nb, 0))
                my_l = lax.dynamic_slice_in_dim(lcol, i * nb, nb, axis=0)
                lout = lout.at[:, k * nb:(k + 1) * nb].set(my_l)
                uout = jnp.where(
                    i == k, uout.at[:, k * nb:(k + 1) * nb].set(ukk), uout
                )
                if k + 1 < p:
                    cand_u = solve_triangular(
                        lkk, w[:, (k + 1) * nb:], lower=True, unit_diagonal=True
                    )
                    urow = _cm.ring_bcast(cand_u, axis_name, p, k, perm,
                                          pipelined=pipelined)
                    uout = jnp.where(
                        i == k, uout.at[:, (k + 1) * nb:].set(cand_u), uout
                    )
                    w = w.at[:, (k + 1) * nb:].add(
                        -jnp.matmul(my_l, urow, precision="highest")
                    )
            gperm = i * nb + perm_loc
            return lout, uout, gperm, detsign

    if kind == "cholesky":
        mapped = _shard_map(
            chol_kernel, mesh=mesh, in_specs=(_PS(axis_name, None),),
            out_specs=_PS(axis_name, None), check_vma=False,
        )

        def fn(a_phys):
            return mapped(a_phys)[:, :n]

    elif kind == "lu":
        mapped = _shard_map(
            lu_kernel, mesh=mesh, in_specs=(_PS(axis_name, None),),
            out_specs=(_PS(axis_name, None), _PS(axis_name, None),
                       _PS(axis_name), _PS()),
            check_vma=False,
        )

        def fn(a_phys):
            lout, uout, gperm, detsign = mapped(a_phys)
            return lout[:, :n], uout[:, :n], gperm, detsign

    else:
        raise ValueError(f"unknown factorization kind {kind!r}")
    return jax.jit(fn)


def _check_square(a: DNDarray, what: str):
    if a._is_planar:
        from .. import complex_planar as _cp

        raise _cp.policy_error(f"{what} on planar complex operands")
    if a.ndim != 2 or a.shape[0] != a.shape[1]:
        raise ValueError(f"{what} requires a square 2-D matrix, got {a.shape}")


def cholesky(a: DNDarray) -> DNDarray:
    """Cholesky factor ``L`` (lower triangular, ``A = L L^H``) of a
    Hermitian positive-definite matrix.

    Distributed split-0/1 operands run the blocked right-looking ring
    program (``p(p-1)`` collective-permutes, pre-declared plan); local
    operands use XLA's kernel. Only the lower triangle of ``A`` is read.
    """
    sanitize_in(a)
    _check_square(a, "ht.linalg.cholesky")
    dtype = _solver_dtype(a)
    jt = dtype.jax_type()
    if a.split == 1:
        a = a.resplit(0)
    comm = a.comm
    n = int(a.shape[0])
    if a.split == 0 and comm.is_distributed():
        sched = _runtime_plan("cholesky", (n, n), jt, comm)
        fn = _blocked_factor_program(
            comm.mesh, comm.axis_name, n, np.dtype(jt).name, "cholesky",
            _cm.ring_enabled(), sched.plan_id,
        )
        l_phys = fn(a._phys.astype(jt))
        l_phys = _padding.mask_phys(l_phys, (n, n), 0)
        return DNDarray(l_phys, (n, n), dtype, 0, a.device, comm)
    result = jnp.linalg.cholesky(a.larray.astype(jt))
    return basics._wrap(result, a.split, a)


def _lu_factor(a: DNDarray):
    """Factor ``A[perm] = L U`` → ``(perm, L, U, sign)`` with ``sign``
    the (replicated, int32) parity of the permutation — the internal
    form :func:`lu`, :func:`solve` and the ``det`` rewiring share.
    Pivoting is block-local (within each device's ``ceil(n/p)`` rows) in
    the distributed form."""
    sanitize_in(a)
    _check_square(a, "ht.linalg.lu")
    dtype = _solver_dtype(a)
    jt = dtype.jax_type()
    if a.split == 1:
        a = a.resplit(0)
    comm = a.comm
    n = int(a.shape[0])
    if a.split == 0 and comm.is_distributed():
        sched = _runtime_plan("lu", (n, n), jt, comm)
        fn = _blocked_factor_program(
            comm.mesh, comm.axis_name, n, np.dtype(jt).name, "lu",
            _cm.ring_enabled(), sched.plan_id,
        )
        l_phys, u_phys, perm_phys, sign = fn(a._phys.astype(jt))
        l_phys = _padding.mask_phys(l_phys, (n, n), 0)
        u_phys = _padding.mask_phys(u_phys, (n, n), 0)
        perm_phys = _padding.mask_phys(perm_phys, (n,), 0)
        return (
            DNDarray(perm_phys, (n,), types.int32, 0, a.device, comm),
            DNDarray(l_phys, (n, n), dtype, 0, a.device, comm),
            DNDarray(u_phys, (n, n), dtype, 0, a.device, comm),
            sign,
        )
    lu_p, piv, pk = lax.linalg.lu(a.larray.astype(jt))
    nloc = lu_p.shape[-1]
    l_arr = jnp.tril(lu_p, -1) + jnp.eye(nloc, dtype=jt)
    u_arr = jnp.triu(lu_p)
    sign = jnp.prod(
        jnp.where(piv != jnp.arange(nloc, dtype=piv.dtype), -1, 1)
    ).astype(jnp.int32)
    return (
        basics._wrap(pk.astype(jnp.int32), a.split, a),
        basics._wrap(l_arr, a.split, a),
        basics._wrap(u_arr, a.split, a),
        sign,
    )


def lu(a: DNDarray) -> LU:
    """LU factorization with partial pivoting: ``LU(perm, L, U)`` such
    that ``A[perm] = L @ U`` (``L`` unit lower, ``U`` upper triangular).

    The distributed form pivots BLOCK-LOCALLY — the pivot search is
    confined to each device's block row, so no pivot row ever crosses
    the wire (the matmul-native trade; element growth can exceed the
    global-pivoting bound on adversarial operands). ``perm`` is the
    row-provenance vector: row ``r`` of ``L @ U`` is row ``perm[r]`` of
    ``A``."""
    perm, l_arr, u_arr, _ = _lu_factor(a)
    return LU(perm, l_arr, u_arr)


# ---------------------------------------------------------------------- #
# block triangular solves                                                #
# ---------------------------------------------------------------------- #
@functools.lru_cache(maxsize=64)
def _blocked_solve_program(mesh, axis_name: str, n: int, nrhs: int,
                           jdtype: str, kind: str, pipelined: bool,
                           plan_id: str):
    """Compiled block back/forward-substitution against distributed
    factors (``kind`` in ``chol``/``lu``), split-0 RHS of ``nrhs``
    columns.

    Forward sweep: each lap's diagonal solve happens on the owning
    device and the solved block rides a :func:`ring_bcast` to the
    devices still accumulating (every other device's candidate solve is
    discarded — SPMD congruence at the cost of one tiny redundant
    ``nb×nb`` solve, never a wrong bit). Backward sweep: Cholesky's
    ``L^H x = y`` runs gather-sum form (each device keeps its own
    solution block; the partial products ride ONE all-gather ring per
    lap, summed in fixed stack order), LU's ``U x = y`` the descending
    broadcast form. Census: ``2(p-1)^2`` collective-permutes either way.
    """
    p = mesh.devices.size
    jt = np.dtype(jdtype)
    nb = -(-n // p)
    n_pad = nb * p
    perm = _cm.grouped_ring_perm(1, p)
    zero = jnp.zeros((), jnp.int32)

    def chol_kernel(l_loc, b_loc):
        with jax.named_scope(f"redist_plan_{plan_id}"), _cm.stamp_scope("solve"):
            i = lax.axis_index(axis_name)
            big_l = _pad_seed_diag(l_loc, i, nb, n, n_pad, jt)
            diag_i = lax.dynamic_slice(big_l, (zero, i * nb), (nb, nb))
            acc = b_loc
            yout = jnp.zeros((nb, nrhs), jt)
            for k in range(p):
                cand = solve_triangular(diag_i, acc, lower=True)
                if k + 1 < p:
                    y_k = _cm.ring_bcast(cand, axis_name, p, k, perm,
                                         pipelined=pipelined)
                else:
                    y_k = cand
                yout = jnp.where(i == k, cand, yout)
                if k + 1 < p:
                    acc = acc - jnp.matmul(
                        big_l[:, k * nb:(k + 1) * nb], y_k, precision="highest"
                    )
            xout = jnp.zeros((nb, nrhs), jt)
            for k in range(p - 1, -1, -1):
                if k + 1 < p:
                    contrib = jnp.where(
                        i > k,
                        jnp.matmul(_ct(big_l[:, k * nb:(k + 1) * nb]), xout,
                                   precision="highest"),
                        jnp.zeros((nb, nrhs), jt),
                    )
                    stacked = _cm.ring_all_gather(contrib, axis_name, p, i,
                                                  perm, pipelined=pipelined)
                    ssum = jnp.sum(stacked, axis=0)
                else:
                    ssum = jnp.zeros((nb, nrhs), jt)
                cand = solve_triangular(_ct(diag_i), yout - ssum, lower=False)
                xout = jnp.where(i == k, cand, xout)
            return xout

    def lu_kernel(l_loc, u_loc, perm_loc, b_loc):
        with jax.named_scope(f"redist_plan_{plan_id}"), _cm.stamp_scope("solve"):
            i = lax.axis_index(axis_name)
            big_l = l_loc if n_pad == n else jnp.pad(l_loc, ((0, 0), (0, n_pad - n)))
            big_u = _pad_seed_diag(u_loc, i, nb, n, n_pad, jt)
            diag_l = lax.dynamic_slice(big_l, (zero, i * nb), (nb, nb))
            diag_u = lax.dynamic_slice(big_u, (zero, i * nb), (nb, nb))
            # apply the block-local row permutation to the RHS; pad slots
            # clamp to row 0 (garbage confined to pad rows: the factors'
            # pad columns are zero against real rows, and the output pad
            # is re-masked by the wrapper)
            loc = jnp.clip(perm_loc.astype(jnp.int32) - i * nb, 0, nb - 1)
            acc = b_loc[loc]
            yout = jnp.zeros((nb, nrhs), jt)
            for k in range(p):
                cand = solve_triangular(diag_l, acc, lower=True,
                                        unit_diagonal=True)
                if k + 1 < p:
                    y_k = _cm.ring_bcast(cand, axis_name, p, k, perm,
                                         pipelined=pipelined)
                else:
                    y_k = cand
                yout = jnp.where(i == k, cand, yout)
                if k + 1 < p:
                    acc = acc - jnp.matmul(
                        big_l[:, k * nb:(k + 1) * nb], y_k, precision="highest"
                    )
            xout = jnp.zeros((nb, nrhs), jt)
            acc2 = yout
            for k in range(p - 1, -1, -1):
                cand = solve_triangular(diag_u, acc2, lower=False)
                if k > 0:
                    x_k = _cm.ring_bcast(cand, axis_name, p, k, perm,
                                         pipelined=pipelined)
                else:
                    x_k = cand
                xout = jnp.where(i == k, cand, xout)
                if k > 0:
                    acc2 = acc2 - jnp.matmul(
                        big_u[:, k * nb:(k + 1) * nb], x_k, precision="highest"
                    )
            return xout

    if kind == "chol":
        mapped = _shard_map(
            chol_kernel, mesh=mesh,
            in_specs=(_PS(axis_name, None), _PS(axis_name, None)),
            out_specs=_PS(axis_name, None), check_vma=False,
        )
    elif kind == "lu":
        mapped = _shard_map(
            lu_kernel, mesh=mesh,
            in_specs=(_PS(axis_name, None), _PS(axis_name, None),
                      _PS(axis_name), _PS(axis_name, None)),
            out_specs=_PS(axis_name, None), check_vma=False,
        )
    else:
        raise ValueError(f"unknown solve kind {kind!r}")
    return jax.jit(mapped)


def _apply_factor_local(kind, b_arr, l_arr, u_arr=None, perm_arr=None):
    """Local (replicated) triangular-solve chain — shared by the local
    :func:`solve` path, the serving endpoint and the staged HostArray
    stream on 1-device worlds."""
    if kind == "chol":
        y = solve_triangular(l_arr, b_arr, lower=True)
        return solve_triangular(_ct(l_arr), y, lower=False)
    y = solve_triangular(l_arr, b_arr[perm_arr], lower=True, unit_diagonal=True)
    return solve_triangular(u_arr, y, lower=False)


def _solve_factored(kind, b: DNDarray, l_arr: DNDarray,
                    u_arr: Optional[DNDarray] = None,
                    pvec: Optional[DNDarray] = None) -> DNDarray:
    """Run the distributed block triangular solve against pre-computed
    factors. ``b`` may be 1-D or 2-D; output split 0."""
    comm = l_arr.comm
    n = int(l_arr.shape[0])
    jt = l_arr.dtype.jax_type()
    b0 = b if b.split == 0 else b.resplit(0)
    vec = b0.ndim == 1
    b_phys = b0._phys.astype(jt)
    if vec:
        b_phys = b_phys[:, None]
    nrhs = int(b_phys.shape[1])
    sched = _runtime_plan("solve-" + kind, (n, nrhs), jt, comm)
    fn = _blocked_solve_program(
        comm.mesh, comm.axis_name, n, nrhs, np.dtype(jt).name, kind,
        _cm.ring_enabled(), sched.plan_id,
    )
    if kind == "chol":
        x_phys = fn(l_arr._phys.astype(jt), b_phys)
    else:
        x_phys = fn(l_arr._phys.astype(jt), u_arr._phys.astype(jt),
                    pvec._phys, b_phys)
    x_phys = _padding.mask_phys(x_phys, (n, nrhs), 0)
    if vec:
        return DNDarray(x_phys[:, 0], (n,), l_arr.dtype, 0, b.device, comm)
    return DNDarray(x_phys, (n, nrhs), l_arr.dtype, 0, b.device, comm)


def solve(a: DNDarray, b, assume_a: str = "gen"):
    """Solve ``A x = b`` for square ``A``.

    ``assume_a="gen"`` factors through the blocked :func:`lu`,
    ``assume_a="pos"`` through :func:`cholesky` — for distributed
    operands both chains are blocked ring programs with pre-declared
    collective plans (NO gather-and-replicate of the operand; see
    docs/MIGRATING.md). ``b`` may be a vector, a matrix of RHS columns,
    or a :class:`~heat_tpu.redistribution.staging.HostArray` of RHS
    columns — the host form streams column windows through the staged
    double-buffer (PR 11) and returns a HostArray of solutions.
    """
    from ...redistribution import staging as _staging

    if isinstance(b, _staging.HostArray):
        return _solve_host_rhs(a, b, assume_a=assume_a)
    sanitize_in(a)
    sanitize_in(b)
    _check_square(a, "ht.linalg.solve")
    if b._is_planar:
        from .. import complex_planar as _cp

        raise _cp.policy_error("ht.linalg.solve on planar complex operands")
    if assume_a not in ("gen", "pos"):
        raise ValueError(f"assume_a must be 'gen' or 'pos', got {assume_a!r}")
    n = int(a.shape[0])
    if b.ndim not in (1, 2) or int(b.shape[0]) != n:
        raise ValueError(
            f"b must be (n,) or (n, nrhs) with n={n}, got {b.shape}"
        )
    comm = a.comm
    distributed = comm.is_distributed() and (
        a.split is not None or b.split is not None
    )
    if distributed:
        if assume_a == "pos":
            l_arr = cholesky(a)
            return _solve_factored("chol", b, l_arr)
        pvec, l_arr, u_arr, _sign = _lu_factor(a)
        return _solve_factored("lu", b, l_arr, u_arr, pvec)
    dtype = _solver_dtype(a)
    jt = dtype.jax_type()
    arr_a = a.larray.astype(jt)
    arr_b = b.larray.astype(jt)
    if assume_a == "pos":
        c = jnp.linalg.cholesky(arr_a)
        res = _apply_factor_local("chol", arr_b if b.ndim == 2 else arr_b[:, None], c)
        res = res if b.ndim == 2 else res[:, 0]
    else:
        res = jnp.linalg.solve(arr_a, arr_b)
    return basics._wrap(res, b.split if b.split is not None else a.split, a)


# ---------------------------------------------------------------------- #
# symmetric eigensolver: polar-based spectral divide-and-conquer         #
# ---------------------------------------------------------------------- #
def _projector_rank(p_arr: jax.Array) -> int:
    """Host read of a spectral projector's rank (= its trace, an
    integer up to polar convergence error). This is the ONE data-
    dependent boundary of the eigensolver — declared in
    ``analysis/boundaries.DATA_DEPENDENT_BOUNDARIES`` so commcheck
    reports the sync as a known algorithmic decision point, not a
    stray host round-trip."""
    tr = jnp.real(jnp.trace(p_arr))
    with _span("ht.sync.read", what="eigh.projector_rank"):
        return int(np.round(float(np.asarray(jax.device_get(tr)))))


def _range_probe(n: int, k: int, depth: int, branch: int, jt) -> jax.Array:
    """Deterministic Gaussian range probe for the projector subspace —
    keyed by (n, k, depth, branch) so every run, device and issue order
    draws the same bits (the suite's bit-identity contract extends
    through the randomized range finder)."""
    key = jax.random.key(0xE16)
    for t in (n, k, depth, branch):
        key = jax.random.fold_in(key, t)
    rt = np.dtype(jnp.finfo(np.dtype(jt)).dtype)
    om = jax.random.normal(key, (n, k), rt)
    if np.dtype(jt).kind == "c":
        om = om + 1j * jax.random.normal(jax.random.fold_in(key, 7), (n, k), rt)
    return om.astype(jt)


def _eigh_local(a: DNDarray):
    w, v = jnp.linalg.eigh(a.larray)
    return w, basics._wrap(v, a.split, a)


def _ring_xhy(x: DNDarray, y: DNDarray) -> jax.Array:
    """Replicated ``X^H Y`` for split-0 operands via the cmatmul ring
    program — the contraction axis IS the split axis, so this is the
    collective-matmul case. Used unconditionally by the eigensolver's
    Rayleigh-Ritz compression: the overlap knob only picks the ring's
    sequential vs pipelined issue order (bit-identical), never the
    GSPMD barrier reduction (whose summation order differs)."""
    comm = x.comm
    jt = x.dtype.jax_type()
    kx, ky = int(x.shape[1]), int(y.shape[1])
    fn = basics._cmatmul_program(
        comm.mesh, comm.axis_name, kx, int(x._phys.shape[0]) // comm.size,
        ky, np.dtype(jt).name, "highest", _cm.ring_enabled(),
    )
    return fn(_ct(x._phys.astype(jt)), y._phys.astype(jt))


def _eigh_branch(a: DNDarray, proj: DNDarray, k: int, depth: int, branch: int):
    """One side of the spectral split: subspace basis from TSQR of
    projector-range probes (one refinement pass), Rayleigh-Ritz
    compression ``Q^H A Q`` (a ring matmul when overlap is on — the
    contraction-split case), then recursion or a local solve."""
    from .qr import qr as _qr

    jt = a.dtype.jax_type()
    n = int(a.shape[0])
    om = basics._wrap(_range_probe(n, k, depth, branch, jt), None, a)
    q = _qr(basics.matmul(proj, om, precision="highest"), calc_q=True).Q
    q = _qr(basics.matmul(proj, q, precision="highest"), calc_q=True).Q
    bq = basics.matmul(a, q, precision="highest")
    a_sub = _ring_xhy(q, bq)
    sub_l = 0.5 * (a_sub + _ct(a_sub))
    if k >= _EIGH_RESPLIT_MIN_N and a.comm.is_distributed():
        # recurse on the split-0 sub-operand — the resplit rides the
        # redistribution planner like any other movement
        sub = basics._wrap(sub_l, None, a).resplit(0)
        w, v = _eigh_dc(sub, depth + 1)
        u = basics.matmul(q, v)
    else:
        w, v = jnp.linalg.eigh(sub_l)
        u = basics.matmul(q, basics._wrap(v, None, a))
    return w, u


def _eigh_dc(a: DNDarray, depth: int):
    """Spectral divide-and-conquer on a Hermitian split-0 operand:
    shift by the diagonal median, ``S = sign(A - μI)`` via
    :func:`polar`, split the spectrum across the two projectors
    ``(I ∓ S)/2``, solve each side in its subspace, merge sorted."""
    comm = a.comm
    n = int(a.shape[0])
    if (not comm.is_distributed()) or a.split != 0 or n < 4 \
            or depth >= _EIGH_MAX_DEPTH:
        return _eigh_local(a)
    jt = a.dtype.jax_type()
    mu = jnp.median(jnp.real(jnp.diagonal(a.larray))).astype(jt)
    eye = jnp.eye(n, dtype=jt)
    shifted = basics._wrap(a.larray - mu * eye, 0, a)
    s_u, _ = polar(shifted)
    proj_lo = basics._wrap(0.5 * (eye - s_u.larray), 0, a)
    k = _projector_rank(proj_lo.larray)
    if k <= 0 or k >= n:
        # degenerate split (spectrum clustered at the shift): the
        # documented fallback is the local solve
        return _eigh_local(a)
    w1, u1 = _eigh_branch(a, proj_lo, k, depth, 0)
    proj_hi = basics._wrap(0.5 * (eye + s_u.larray), 0, a)
    w2, u2 = _eigh_branch(a, proj_hi, n - k, depth, 1)
    w_all = jnp.concatenate([w1, w2])
    order = jnp.argsort(w_all)
    v_phys = jnp.concatenate([u1._phys, u2._phys], axis=1)[:, order]
    v = DNDarray(v_phys, (n, n), a.dtype, 0, a.device, comm)
    return w_all[order], v


def eigh(a: DNDarray, UPLO: str = "L") -> Eigh:
    """Eigendecomposition of a Hermitian matrix: ``Eigh(eigenvalues,
    eigenvectors)``, eigenvalues ascending (replicated), eigenvectors
    split 0 in the distributed form.

    Distributed operands run polar-based spectral divide-and-conquer —
    the whole solve is matmul chains (Newton–Schulz polar + TSQR +
    Rayleigh-Ritz), recursing through the redistribution planner on
    sub-operands of order ``>= _EIGH_RESPLIT_MIN_N``. Only the ``UPLO``
    triangle of ``A`` is read."""
    sanitize_in(a)
    _check_square(a, "ht.linalg.eigh")
    if UPLO not in ("L", "U"):
        raise ValueError(f"UPLO must be 'L' or 'U', got {UPLO!r}")
    dtype = _solver_dtype(a)
    jt = dtype.jax_type()
    if a.split == 1:
        a = a.resplit(0)
    comm = a.comm
    if a.split == 0 and comm.is_distributed():
        arr = a.larray.astype(jt)
        if UPLO == "L":
            herm = jnp.tril(arr) + _ct(jnp.tril(arr, -1))
        else:
            herm = jnp.triu(arr) + _ct(jnp.triu(arr, 1))
        a_h = basics._wrap(herm, 0, a)
        if a_h.dtype != dtype:
            a_h = DNDarray(a_h._phys, a_h.shape, dtype, a_h.split, a.device, comm)
        w, v = _eigh_dc(a_h, 0)
        return Eigh(basics._wrap(w, None, a), v)
    w, v = jnp.linalg.eigh(a.larray.astype(jt), UPLO=UPLO)
    return Eigh(basics._wrap(w, None, a), basics._wrap(v, a.split, a))


# ---------------------------------------------------------------------- #
# HostArray RHS: the staged-window solve stream                          #
# ---------------------------------------------------------------------- #
def _solve_host_rhs(a: DNDarray, b, assume_a: str = "gen"):
    """Solve against a host-resident RHS panel: factor once, then
    stream column windows of ``b`` through the depth-2 staged
    double-buffer (PR 11), solving each window with the blocked
    program and writing the solutions back to host memory. Returns a
    :class:`HostArray` of solutions. When the RHS fits HBM comfortably
    (``ooc_engaged`` false) the escape hatch materializes and takes the
    ordinary :func:`solve` path."""
    from ...redistribution import staging as _staging

    sanitize_in(a)
    _check_square(a, "ht.linalg.solve")
    if assume_a not in ("gen", "pos"):
        raise ValueError(f"assume_a must be 'gen' or 'pos', got {assume_a!r}")
    n = int(a.shape[0])
    if len(b.shape) != 2 or int(b.shape[0]) != n:
        raise ValueError(
            f"HostArray b must be (n, nrhs) with n={n}, got {b.shape}"
        )
    comm = a.comm
    if not _staging.ooc_engaged(b.nbytes, host_resident=True):
        bd = basics._wrap(
            jnp.asarray(_staging.materialize(b, what="solve rhs")),
            0 if comm.is_distributed() else None, a,
        )
        return solve(a, bd, assume_a=assume_a)
    dtype = _solver_dtype(a)
    jt = dtype.jax_type()
    nrhs = int(b.shape[1])
    distributed = comm.is_distributed() and a.split is not None
    if assume_a == "pos":
        kind = "chol"
        if distributed:
            l_arr, u_arr, pvec = cholesky(a), None, None
        else:
            l_loc = jnp.linalg.cholesky(a.larray.astype(jt))
            u_loc = perm_loc = None
    else:
        kind = "lu"
        if distributed:
            pvec, l_arr, u_arr, _sign = _lu_factor(a)
        else:
            lu_p, piv, pk = lax.linalg.lu(a.larray.astype(jt))
            l_loc = jnp.tril(lu_p, -1) + jnp.eye(n, dtype=jt)
            u_loc = jnp.triu(lu_p)
            perm_loc = pk
    itemsize = np.dtype(jt).itemsize
    sched = _staging.plan_staged_passes(
        (n, nrhs), jt, [{"tag": "solve", "axis": 1, "writeback": True}],
        out_bytes=0, mesh_size=comm.size,
    )
    wins = _staging.window_extents((n, nrhs), itemsize, 1, _staging.slab_bytes())
    out = np.empty((n, nrhs), np.dtype(jt))

    def consume(_k, slab, ext):
        start, stop = ext
        win = jnp.asarray(slab).astype(jt)
        if distributed:
            bd = basics._wrap(win, 0, a)
            if kind == "chol":
                x = _solve_factored("chol", bd, l_arr)
            else:
                x = _solve_factored("lu", bd, l_arr, u_arr, pvec)
            with _span("ht.sync.read", what="solve.window"):
                out[:, start:stop] = np.asarray(jax.device_get(x.larray))
        else:
            x = _apply_factor_local(kind, win, l_loc, u_loc, perm_loc)
            with _span("ht.sync.read", what="solve.window"):
                out[:, start:stop] = np.asarray(jax.device_get(x))

    _staging.stream_windows(b, 1, wins, consume, plan_id=sched.plan_id)
    return _staging.HostArray(out)


# ---------------------------------------------------------------------- #
# serving endpoint                                                       #
# ---------------------------------------------------------------------- #
def solve_endpoint(fac, buckets=(8, 32, 128), name: str = "solve",
                   donate: bool = False):
    """A serving :class:`Endpoint` over pre-computed factors: batches of
    RHS vectors ``(b, n)`` are solved by the triangular chain against
    the resident factors (``fac`` is the :func:`cholesky` L or the
    :func:`lu` namedtuple). Programs are AOT-cached per bucket; the
    dispatcher's HBM admission check is armed with the memcheck-priced
    static peak."""
    from ...analysis import memcheck as _memcheck
    from ...serving.dispatcher import program_endpoint as _program_endpoint

    if isinstance(fac, LU):
        kind = "lu"
        l_arr = fac.L
        extras = (fac.L.larray, fac.U.larray, fac.perm.larray)
    elif isinstance(fac, DNDarray):
        kind = "chol"
        l_arr = fac
        extras = (fac.larray,)
    else:
        raise TypeError(
            f"fac must be a cholesky factor DNDarray or an LU namedtuple, "
            f"got {type(fac)}"
        )
    n = int(l_arr.shape[0])
    jt = l_arr.dtype.jax_type()

    def build():
        if kind == "chol":
            def run(batch, l_loc):
                x = _apply_factor_local("chol", batch.astype(l_loc.dtype).T, l_loc)
                return x.T
        else:
            def run(batch, l_loc, u_loc, perm_loc):
                x = _apply_factor_local(
                    "lu", batch.astype(l_loc.dtype).T, l_loc, u_loc, perm_loc
                )
                return x.T
        return jax.jit(run)  # shardlint: ignore[SL202] -- serving program body; the endpoint cache owns wrapping/donation (aot_cache precedent)

    peak = None
    try:
        rep = _memcheck(build(), jnp.zeros((max(buckets), n), jt), *extras)
        peak = rep.context.get("static_peak_bytes")
    except Exception:
        peak = None
    return _program_endpoint(
        build, (n,), np.dtype(jt), buckets,
        key=("linalg.solve_endpoint", kind, n, np.dtype(jt).name),
        extra_args=extras, donate=donate, name=name, static_peak_bytes=peak,
    )


from ..communication import place as _place
from ..communication import register_mesh_cache as _register_mesh_cache

# compiled factorization programs bake mesh geometry: cleared when
# init_distributed rebuilds the world
_register_mesh_cache(_polar_program)
_register_mesh_cache(_blocked_factor_program)
_register_mesh_cache(_blocked_solve_program)
