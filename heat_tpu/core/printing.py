"""Rank-aware printing of DNDarrays.

API parity with /root/reference/heat/core/printing.py (``local_printing``
at printing.py:30, ``global_printing`` at :62, ``print0`` at :100,
``set_printoptions`` at :150, gather-based ``_torch_data`` at :208).
Under a single controller the "gather to rank 0" disappears — the global
array is addressable; large arrays are summarized via numpy printoptions
so no full device-to-host transfer happens for huge arrays.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from ..observability.tracing import span as _span

__all__ = ["get_printoptions", "global_printing", "local_printing", "print0", "set_printoptions"]

# printing profiles mirroring torch defaults (reference printing.py:14-28)
__PRINT_OPTIONS = {
    "precision": 4,
    "threshold": 1000,
    "edgeitems": 3,
    "linewidth": 120,
    "sci_mode": None,
}

LOCAL_PRINT = False


def get_printoptions() -> dict:
    """View of the current print options (reference: printing.py:44)."""
    return dict(__PRINT_OPTIONS)


def local_printing() -> None:
    """Print the process-local data only (reference: printing.py:30)."""
    global LOCAL_PRINT
    LOCAL_PRINT = True


def global_printing() -> None:
    """Print the global array (default; reference: printing.py:62)."""
    global LOCAL_PRINT
    LOCAL_PRINT = False


def print0(*args, **kwargs) -> None:
    """Print from the controlling process only (reference: printing.py:100).
    Single-controller: a plain print."""
    import jax

    if jax.process_index() == 0:
        print(*args, **kwargs)


def set_printoptions(
    precision=None,
    threshold=None,
    edgeitems=None,
    linewidth=None,
    profile=None,
    sci_mode=None,
) -> None:
    """Configure printing (reference: printing.py:150)."""
    if profile is not None:
        if profile == "default":
            __PRINT_OPTIONS.update(precision=4, threshold=1000, edgeitems=3, linewidth=120)
        elif profile == "short":
            __PRINT_OPTIONS.update(precision=2, threshold=1000, edgeitems=2, linewidth=120)
        elif profile == "full":
            __PRINT_OPTIONS.update(precision=4, threshold=float("inf"), edgeitems=3, linewidth=120)
        else:
            raise ValueError(f"unknown profile {profile}")
    if precision is not None:
        __PRINT_OPTIONS["precision"] = int(precision)
    if threshold is not None:
        __PRINT_OPTIONS["threshold"] = threshold
    if edgeitems is not None:
        __PRINT_OPTIONS["edgeitems"] = int(edgeitems)
    if linewidth is not None:
        __PRINT_OPTIONS["linewidth"] = int(linewidth)
    if sci_mode is not None:
        __PRINT_OPTIONS["sci_mode"] = bool(sci_mode)


def __str__(dndarray) -> str:
    """String representation: torch-style metadata plus summarized data
    (reference: printing.py:187 __str__)."""
    from . import types

    opts = __PRINT_OPTIONS
    summarized = False
    if dndarray._is_planar:
        # planar complex: format the host complex64 assembly through the
        # shared block below (dtype.kind 'c' passes the biufc check).
        # Large arrays edge-slice the PLANE VIEW on device first — a full
        # numpy() here would allgather the whole array to render ~6 items
        if dndarray.size > opts["threshold"] and dndarray.ndim > 0:
            data = _planar_summarized(dndarray, opts["edgeitems"])
            summarized = True
        else:
            data = dndarray.numpy()
    elif LOCAL_PRINT:
        arr = dndarray.larray
        with _span("ht.sync.read", what="print"):
            data = np.asarray(arr.addressable_shards[0].data) if arr.addressable_shards else np.asarray(arr)
    else:
        # summarize without materializing huge arrays on host
        if dndarray.size > opts["threshold"] and dndarray.ndim > 0:
            data = _summarized_numpy(dndarray, opts["edgeitems"])
            summarized = True
        else:
            data = dndarray.numpy()
    if data.dtype.kind not in "biufc":  # e.g. ml_dtypes bfloat16
        data = data.astype(np.float32)
    # a pre-sliced edge block must still render with ellipses
    threshold = 1 if summarized and data.size > 1 else opts["threshold"]
    with np.printoptions(
        precision=opts["precision"],
        threshold=threshold,
        edgeitems=opts["edgeitems"],
        linewidth=opts["linewidth"],
        suppress=not opts["sci_mode"] if opts["sci_mode"] is not None else True,
    ):
        body = np.array2string(data, separator=", ")
    dtype_name = dndarray.dtype.__name__
    return f"DNDarray({body}, dtype=ht.{dtype_name}, device={dndarray.device}, split={dndarray.split})"


def _edge_take(arr, shape, edgeitems: int):
    """Select the displayed edge slices of ``arr`` along each dim of the
    LOGICAL ``shape`` (trailing extra axes ride along) — the one place
    the edge-selection rule lives. Host ndarrays stay on host (a complex
    host array must never round-trip through the device in planar mode)."""
    on_host = isinstance(arr, np.ndarray)
    for d, s in enumerate(shape):
        if s > 2 * edgeitems + 1:
            ix = np.r_[0 : edgeitems + 1, s - edgeitems : s]
        else:
            ix = np.arange(s)
        arr = np.take(arr, ix, axis=d) if on_host else jnp.take(arr, jnp.asarray(ix), axis=d)
    return arr


def _planar_summarized(dndarray, edgeitems: int) -> np.ndarray:
    """Edge slices of a planar complex array, selected from the plane
    view ON DEVICE (same selection as ``_summarized_numpy``; only the
    displayed items reach the host) and assembled to complex64. In a
    multi-process world the plane array spans non-addressable devices,
    which ``np.asarray`` cannot fetch — fall back to the allgathering
    ``numpy()`` export there."""
    from . import complex_planar as _cp

    view = _cp._planar_view(dndarray)  # (gshape..., 2)
    if jax.process_count() > 1 and not view.is_fully_addressable:
        return _edge_take(dndarray.numpy(), dndarray.shape, edgeitems)
    sub = _edge_take(view, dndarray.shape, edgeitems)
    with _span("ht.sync.read", what="print"):
        return _cp.assemble_host(np.asarray(sub))


def _summarized_numpy(dndarray, edgeitems: int) -> np.ndarray:
    """Fetch only the displayed edge slices to host (the analog of the
    reference's threshold-summarized gather, printing.py:208)."""
    with _span("ht.sync.read", what="print"):
        return np.asarray(_edge_take(dndarray.larray, dndarray.shape, edgeitems))
