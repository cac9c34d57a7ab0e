"""Lasso regression.

API parity with /root/reference/heat/regression/lasso.py (``Lasso`` :15:
coordinate-descent soft-threshold fit :121-172 using ``ht.matmul`` per
feature). Same cyclic coordinate descent here; each coordinate update is a
sharded matvec (one all-reduce when the sample axis is split).
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from typing import Optional

from ..core import types
from ..core.base import BaseEstimator, RegressionMixin
from ..core.dndarray import DNDarray
from ..core.sanitation import sanitize_in

__all__ = ["Lasso"]


@functools.lru_cache(maxsize=64)
def _cd_program(m: int, max_iter: int):
    """Whole coordinate-descent fit as ONE compiled program: per-fit
    closures would recompile on every ``fit`` call, and baking lam/tol in
    as constants would recompile per regularization value — they are
    TRACED scalars, so a regularization-path sweep reuses one executable
    (jit retraces per operand shape/dtype, so neither needs a key).
    Sweeps run as a fori_loop over coordinates; convergence is a
    while_loop with the tol test on device (no host sync per sweep)."""

    def sweep(X, yarr, col_msq, lam, th):
        def body(j, th):
            resid = yarr - X @ th + X[:, j] * th[j]
            rho = jnp.mean(X[:, j] * resid)
            denom = jnp.maximum(col_msq[j], 1e-30)
            unpenalized = rho / denom
            penalized = jnp.where(
                rho < -lam,
                (rho + lam) / denom,
                jnp.where(rho > lam, (rho - lam) / denom, 0.0),
            )
            new_j = jnp.where(j == 0, unpenalized, penalized)
            return th.at[j].set(new_j)

        return jax.lax.fori_loop(0, m, body, th)

    def run(X, yarr, col_msq, lam, tol, theta0):
        def cond(state):
            it, th, diff = state
            return (it < max_iter) & (diff >= tol)

        def body(state):
            it, th, _ = state
            nt = sweep(X, yarr, col_msq, lam, th)
            return (it + 1, nt, jnp.max(jnp.abs(nt - th)))

        return jax.lax.while_loop(
            cond, body, (0, theta0, jnp.asarray(jnp.inf, theta0.dtype))
        )

    return jax.jit(run)


class Lasso(BaseEstimator, RegressionMixin):
    """L1-regularized least squares via cyclic coordinate descent
    (reference: lasso.py:15). ``theta`` includes the intercept (feature 0,
    unpenalized), matching the reference."""

    def __init__(self, lam: Optional[float] = 0.1, max_iter: Optional[int] = 100, tol: Optional[float] = 1e-6):
        self.__lam = lam
        self.max_iter = max_iter
        self.tol = tol
        self.__theta = None
        self.n_iter = None

    @property
    def lam(self) -> float:
        return self.__lam

    @lam.setter
    def lam(self, arg: float):
        self.__lam = arg

    @property
    def coef_(self) -> Optional[DNDarray]:
        return None if self.__theta is None else self.__theta[1:]

    @property
    def intercept_(self) -> Optional[DNDarray]:
        return None if self.__theta is None else self.__theta[0]

    @property
    def theta(self):
        return self.__theta

    def soft_threshold(self, rho):
        """Soft-threshold operator (reference: lasso.py soft_threshold)."""
        if isinstance(rho, DNDarray):
            val = rho.larray
            out = jnp.where(val < -self.__lam, val + self.__lam, jnp.where(val > self.__lam, val - self.__lam, 0.0))
            return DNDarray(out, rho.shape, rho.dtype, rho.split, rho.device, rho.comm)
        if rho < -self.__lam:
            return rho + self.__lam
        if rho > self.__lam:
            return rho - self.__lam
        return 0.0

    def rmse(self, gt: DNDarray, yest: DNDarray) -> float:
        """Root mean squared error (reference: lasso.py rmse)."""
        diff = gt.larray.ravel() - yest.larray.ravel()
        return float(jnp.sqrt(jnp.mean(diff**2)))

    def fit(self, x: DNDarray, y: DNDarray) -> "Lasso":
        """Coordinate-descent fit (reference: lasso.py:121-172)."""
        sanitize_in(x)
        sanitize_in(y)
        if x.ndim != 2:
            raise ValueError(f"x needs to be 2-dimensional, got {x.ndim}")
        if y.ndim > 2 or (y.ndim == 2 and y.shape[1] != 1):
            raise ValueError(f"y needs to be 1-D or (n, 1), got {y.shape}")

        arr = x.larray.astype(jnp.float32 if x.dtype is not types.float64 else jnp.float64)
        yarr = y.larray.reshape(-1).astype(arr.dtype)
        n, f = arr.shape
        # prepend intercept column
        X = jnp.concatenate([jnp.ones((n, 1), dtype=arr.dtype), arr], axis=1)
        m = f + 1
        theta = jnp.zeros((m,), dtype=arr.dtype)
        # mean-scale statistics: the reference thresholds the per-sample
        # mean correlation against lam (reference lasso.py:121-172), so lam
        # is sample-size independent
        col_msq = jnp.mean(X * X, axis=0)
        prog = _cd_program(m, int(self.max_iter))
        n_iter_dev, theta, _ = prog(
            X, yarr, col_msq,
            jnp.asarray(self.__lam, arr.dtype), jnp.asarray(self.tol, arr.dtype),
            theta,
        )
        self.n_iter = int(n_iter_dev)

        from ..core import factories

        self.__theta = factories.array(
            np.asarray(theta).reshape(-1, 1), comm=x.comm, device=x.device
        )
        return self

    def predict(self, x: DNDarray) -> DNDarray:
        """Linear prediction with intercept (reference: lasso.py predict)."""
        sanitize_in(x)
        if self.__theta is None:
            raise RuntimeError("fit needs to be called before predict")
        theta = self.__theta.larray.reshape(-1)
        arr = x.larray.astype(theta.dtype)
        yest = arr @ theta[1:] + theta[0]
        gshape = (x.shape[0],)
        split = 0 if x.split is not None else None
        if split is not None:
            yest = x.comm.shard(yest, split)
        return DNDarray(
            yest, gshape, types.canonical_heat_type(yest.dtype), split, x.device, x.comm
        )
