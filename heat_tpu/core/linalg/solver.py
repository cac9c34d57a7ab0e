"""Iterative solvers.

API parity with /root/reference/heat/core/linalg/solver.py (``cg`` :14,
``lanczos`` :67). The reference iterates in Python with an MPI-synchronized
convergence check each step; on TPU that pattern costs a device→host sync
per iteration. Here each solver is ONE jitted program: ``cg`` runs a
``lax.while_loop`` whose convergence test stays on device, ``lanczos`` a
``lax.scan`` over steps with masked full reorthogonalization against the
pre-allocated Krylov basis. The per-iteration dot-product all-reduces are
emitted by XLA from the sharded matvecs — the same collectives the
reference issues explicitly.

DIRECT solves live elsewhere (ISSUE 19): ``ht.linalg.solve`` is the
blocked-triangular back-substitution over the ring Cholesky/LU factors
in :mod:`.factorizations` (re-exported at the ``ht.linalg`` root), with
``assume_a="pos"`` for s.p.d. systems — prefer it over ``cg`` when the
system is dense and factorable; ``cg`` remains the matrix-free /
iterative option.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from typing import Optional, Tuple

from .. import factories
from .. import types
from ..dndarray import DNDarray
from ..sanitation import sanitize_in

__all__ = ["cg", "lanczos"]


@functools.lru_cache(maxsize=64)
def _cg_program(n: int, jdtype: str, maxit: int, tol: float):
    """One jitted CG solve: while_loop with on-device convergence (no
    host round trip per iteration, unlike the reference's per-step
    ``sqrt(rsnew) < tol`` Python check, solver.py:45)."""
    eps = jnp.asarray(tol, dtype=jdtype) ** 2

    def solve(A, b, x0):
        r0 = b - A @ x0
        rs0 = r0 @ r0

        def cond(state):
            i, x, r, p, rsold = state
            return (i < maxit) & (rsold >= eps)

        def step(state):
            i, x, r, p, rsold = state
            Ap = A @ p
            alpha = rsold / (p @ Ap)
            x = x + alpha * p
            r = r - alpha * Ap
            rsnew = r @ r
            p = r + (rsnew / rsold) * p
            return (i + 1, x, r, p, rsnew)

        _, x, _, _, _ = lax.while_loop(cond, step, (0, x0, r0, r0, rs0))
        return x

    return jax.jit(solve)


def cg(A: DNDarray, b: DNDarray, x0: DNDarray, out: Optional[DNDarray] = None) -> DNDarray:
    """Conjugate gradients for s.p.d. ``A x = b`` (reference: solver.py:14)."""
    if not isinstance(A, DNDarray) or not isinstance(b, DNDarray) or not isinstance(x0, DNDarray):
        raise TypeError(f"A, b, x0 need to be DNDarrays, got {type(A)}, {type(b)}, {type(x0)}")
    if A.ndim != 2:
        raise RuntimeError("A needs to be a 2D matrix")
    if b.ndim != 1:
        raise RuntimeError("b needs to be a 1D vector")
    if x0.ndim != 1:
        raise RuntimeError("c needs to be a 1D vector")

    dtype = types.promote_types(
        types.promote_types(A.dtype, b.dtype),
        types.promote_types(x0.dtype, types.float32),
    )
    jt = dtype.jax_type()
    n = b.shape[0]
    prog = _cg_program(n, np.dtype(jt).name, int(n), 1e-10)
    x = prog(A.larray.astype(jt), b.larray.astype(jt), x0.larray.astype(jt))

    result = DNDarray(
        b.comm.shard(x, b.split), (n,), dtype, b.split, b.device, b.comm
    )
    if out is not None:
        out.larray = result.larray
        return out
    return result


@functools.lru_cache(maxsize=64)
def _lanczos_program(n: int, m: int, jdtype: str, breakdown_tol: float,
                     matvec=None):
    """One jitted Lanczos run: scan over the m steps; each step does the
    matvec, masked full reorthogonalization against the basis so far
    (reference solver.py:245-255 Gram-Schmidts every new vector), and a
    ``lax.cond``-free invariant-subspace restart via a select on a fresh
    random direction (reference draws a random vector on breakdown).

    ``matvec`` generalizes the operator: ``None`` keeps the dense
    ``A @ v`` (trace-identical to before the parameter existed — the
    default program is byte-for-byte the same); otherwise ``A`` may be
    any jit-flattenable pytree of operator components and each step
    applies ``matvec(A, v)`` (graph/spectral.py passes the DBCSR
    Laplacian this way). Callables hash by identity, so callers must
    pass a cached/module-level function, not a fresh lambda per call."""
    tol = breakdown_tol
    mv = (lambda A, x: A @ x) if matvec is None else matvec

    # inner products are CONJUGATED (x^H y) so the same program is the
    # hermitian-Lanczos on native complex inputs (CPU/GPU worlds); on
    # real dtypes conj is the identity and the recursion is unchanged.
    # Norms take .real — v^H v is real by construction, and the sqrt
    # must not promote through a complex dtype.
    def run(A, v0, key):
        V0 = jnp.zeros((n, m), dtype=jdtype).at[:, 0].set(v0)
        w0 = mv(A, v0)
        a0 = jnp.conj(v0) @ w0
        w0 = w0 - a0 * v0
        alpha0 = jnp.zeros((m,), dtype=jdtype).at[0].set(a0)
        beta0 = jnp.zeros((m,), dtype=jdtype)

        def step(carry, i):
            V, w, alpha, beta = carry
            b_i = jnp.sqrt((jnp.conj(w) @ w).real)
            invariant = b_i < tol
            # normal candidate (safe divide) vs random restart direction
            vi = jnp.where(invariant, jax.random.normal(jax.random.fold_in(key, i), (n,), dtype=jdtype), w / jnp.where(invariant, 1.0, b_i).astype(jdtype))
            # full reorthogonalization against columns < i (masked)
            proj = jnp.conj(V).T @ vi
            proj = jnp.where(jnp.arange(m) < i, proj, 0.0)
            vi = vi - V @ proj
            vi = vi / jnp.sqrt((jnp.conj(vi) @ vi).real).astype(jdtype)
            V = lax.dynamic_update_slice_in_dim(V, vi[:, None], i, axis=1)
            w = mv(A, vi)
            a_i = jnp.conj(vi) @ w
            v_prev = lax.dynamic_slice_in_dim(V, i - 1, 1, axis=1)[:, 0]
            w = w - a_i * vi - b_i.astype(jdtype) * v_prev
            alpha = alpha.at[i].set(a_i)
            beta = beta.at[i].set(b_i)
            return (V, w, alpha, beta), None

        (V, _, alpha, beta), _ = lax.scan(step, (V0, w0, alpha0, beta0), jnp.arange(1, m))
        return V, alpha, beta

    return jax.jit(run)


@functools.lru_cache(maxsize=32)
def _tridiag_program(m: int, jdtype: str):
    """(alpha, beta) -> tridiagonal T, on device (no host round trip)."""

    @jax.jit
    def build(alpha, beta):
        return (
            jnp.diag(alpha)
            + jnp.diag(beta[1:], 1)
            + jnp.diag(beta[1:], -1)
        ).astype(jdtype)

    return build


def lanczos(
    A: DNDarray,
    m: int,
    v0: Optional[DNDarray] = None,
    V_out: Optional[DNDarray] = None,
    T_out: Optional[DNDarray] = None,
) -> Tuple[DNDarray, DNDarray]:
    """Lanczos tridiagonalization of a symmetric matrix (reference:
    solver.py:67): returns (V, T) with A ≈ V T Vᵀ after m steps; feeds
    ``cluster.Spectral``.
    """
    from . import basics

    if not isinstance(A, DNDarray):
        raise TypeError(f"A needs to be a DNDarray, got {type(A)}")
    if not isinstance(m, (int, float, np.integer)):
        raise TypeError(f"m must be int, got {type(m)}")
    m = int(m)
    if A.ndim != 2 or A.shape[0] != A.shape[1]:
        raise RuntimeError("A needs to be a square matrix")

    n = A.shape[0]
    dtype = A.dtype if types.heat_type_is_inexact(A.dtype) else types.float32
    jt = dtype.jax_type()

    if v0 is None:
        from .. import random as _random

        vr = _random.rand(n, split=A.split, device=A.device, comm=A.comm).astype(dtype)
        v0 = vr / basics.norm(vr)
    else:
        if v0.split != A.split:
            v0 = v0.resplit(A.split)
        v0 = v0.astype(dtype)

    if m == 1:
        w = basics.matmul(A, v0)
        # conjugated inner product (v0^H A v0) via the .numpy() host
        # funnel: native complex inputs keep their (real-valued, but
        # complex-typed) Rayleigh quotient instead of crashing in
        # float(); real inputs are numerically unchanged
        a0 = np.asarray(basics.vdot(v0, w).numpy())
        alpha = np.array([a0])
        beta = np.zeros(1, dtype=alpha.real.dtype)
        V_arr = v0.larray[:, None]
        T_np = np.diag(alpha) + np.diag(beta[1:], 1) + np.diag(beta[1:], -1)
        T_arr = None
    else:
        prog = _lanczos_program(n, m, np.dtype(jt).name, 1e-10)
        # breakdown-restart directions come from a dedicated fixed stream:
        # drawing from the global heat stream here would (a) consume
        # randomness even in the common no-breakdown case — perturbing any
        # seeded pipeline relative to the reference, which only draws ON
        # breakdown — and (b) block on a host read-back per call
        key = jax.random.key(0x1A2C05)
        V_arr, alpha_d, beta_d = prog(A.larray.astype(jt), v0.larray, key)
        # T assembles ON DEVICE: a host device_get of alpha/beta here would
        # be a blocking sync per call (one the reference's torch path does
        # not pay)
        T_arr = _tridiag_program(m, np.dtype(jt).name)(alpha_d, beta_d)

    V = DNDarray(
        A.comm.shard(V_arr, A.split if A.split in (0, None) else 0),
        (n, m),
        dtype,
        A.split if A.split in (0, None) else 0,
        A.device,
        A.comm,
    )
    if T_arr is None:
        T = factories.array(T_np, dtype=dtype, comm=A.comm, device=A.device)
    else:
        T = DNDarray(
            A.comm.shard(T_arr, None), (m, m), dtype, None, A.device, A.comm
        )

    if V_out is not None:
        V_out.larray = V.larray
        V = V_out
    if T_out is not None:
        T_out.larray = T.larray
        T = T_out
    return V, T
