"""Logical operations and predicates.

API parity with /root/reference/heat/core/logical.py (14 exports).
``all``/``any``/``allclose`` in the reference perform a local test plus an
``Allreduce`` with LAND/LOR; the jnp reduction over the sharded array emits
the identical collective.
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from typing import Optional, Union

from . import _operations
from . import types
from .dndarray import DNDarray
from ..observability.tracing import span as _span

__all__ = [
    "all",
    "allclose",
    "any",
    "isclose",
    "isfinite",
    "isinf",
    "isnan",
    "isneginf",
    "isposinf",
    "logical_and",
    "logical_not",
    "logical_or",
    "logical_xor",
    "signbit",
]


def all(x: DNDarray, axis=None, out=None, keepdims: bool = False) -> DNDarray:
    """True where all elements (along ``axis``) evaluate to True
    (reference: logical.py all — local test + LAND Allreduce)."""
    return _operations.__reduce_op(jnp.all, x, axis=axis, out=out, keepdims=keepdims)


def allclose(x: DNDarray, y: DNDarray, rtol: float = 1e-05, atol: float = 1e-08, equal_nan: bool = False) -> bool:
    """Scalar verdict: all elements of x and y within tolerances
    (reference: logical.py allclose)."""
    close = isclose(x, y, rtol=rtol, atol=atol, equal_nan=equal_nan)
    with _span("ht.sync.read", what="allclose"):
        return bool(jnp.all(close.larray))


def any(x: DNDarray, axis=None, out=None, keepdims: bool = False) -> DNDarray:
    """True where any element evaluates to True (LOR reduction)."""
    return _operations.__reduce_op(jnp.any, x, axis=axis, out=out, keepdims=keepdims)


def isclose(x, y, rtol: float = 1e-05, atol: float = 1e-08, equal_nan: bool = False) -> DNDarray:
    """Elementwise tolerance comparison."""
    return _operations.__binary_op(
        jnp.isclose, x, y, fn_kwargs={"rtol": rtol, "atol": atol, "equal_nan": equal_nan}
    )


def isfinite(x: DNDarray) -> DNDarray:
    """Elementwise finiteness test."""
    return _operations.__local_op(jnp.isfinite, x, None, no_cast=True)


def isinf(x: DNDarray) -> DNDarray:
    """Elementwise infinity test."""
    return _operations.__local_op(jnp.isinf, x, None, no_cast=True)


def isnan(x: DNDarray) -> DNDarray:
    """Elementwise NaN test."""
    return _operations.__local_op(jnp.isnan, x, None, no_cast=True)


def isneginf(x: DNDarray, out=None) -> DNDarray:
    """Elementwise -inf test."""
    return _operations.__local_op(jnp.isneginf, x, out, no_cast=True)


def isposinf(x: DNDarray, out=None) -> DNDarray:
    """Elementwise +inf test."""
    return _operations.__local_op(jnp.isposinf, x, out, no_cast=True)


def logical_and(t1, t2) -> DNDarray:
    """Elementwise logical AND."""
    return _operations.__binary_op(jnp.logical_and, t1, t2)


def logical_not(t: DNDarray, out=None) -> DNDarray:
    """Elementwise logical NOT."""
    return _operations.__local_op(jnp.logical_not, t, out, no_cast=True)


def logical_or(t1, t2) -> DNDarray:
    """Elementwise logical OR."""
    return _operations.__binary_op(jnp.logical_or, t1, t2)


def logical_xor(t1, t2) -> DNDarray:
    """Elementwise logical XOR."""
    return _operations.__binary_op(jnp.logical_xor, t1, t2)


def signbit(x: DNDarray, out=None) -> DNDarray:
    """True where the sign bit is set."""
    return _operations.__local_op(jnp.signbit, x, out, no_cast=True)


DNDarray.all = all
DNDarray.any = any
DNDarray.allclose = allclose
DNDarray.isclose = isclose
