"""Op ``robust_scale``: ``ht.preprocessing.RobustScaler().fit_transform(x)`` on
the KMeans configuration's Gaussian blobs, which live on the chips: every
feature centred on its median and scaled by its interquartile range, the
statistics by ``ht.percentile`` along the sample axis.

The data are the ``kmeans_fit`` op's own (upstream's cb scales the table that
is clustered next). ``work_bytes`` is ``X`` once a completed call;
``least_bytes`` three times the chip's rows of ``X``: no quantile is known
before ``X`` is read once, and the table is then read once more and written
once, whatever implements it.

The plain reference is here: the order statistics by ``lax.sort`` of the table,
a block of feature columns at a time so that it fits beside ``X`` (as
``kmedians_fit`` sorts), the two bracketing rows taken by index, the
interpolation in f32, then ``(x - c) / s``, which ``check`` never holds: it
folds the comparison with ``y`` into one reduction. It never goes through
``ht``.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

import heat_tpu as ht
from benchmarks.ops.kmeans_fit import blobs

SORT_COLUMNS = 8  # feature columns a sort: 2 x 600 MB of operands at the north-star shard


def make(cfg: dict, chips: int, key) -> dict:
    comm = ht.MPI_WORLD
    if comm.size != chips:
        raise RuntimeError(f"ht.MPI_WORLD spans {comm.size} devices, the cell asks for {chips}")
    d = cfg["features"]
    xj, _ = blobs(cfg["rows_per_chip"], d, cfg["data"]["blobs"], cfg["data"]["center_scale"], key, comm)
    x = ht.array(xj, split=0)
    del xj
    return {"cfg": cfg, "chips": chips, "x": x, "bytes": cfg["rows_per_chip"] * chips * d * 4}


def call(state: dict) -> dict:
    rs = ht.preprocessing.RobustScaler(quantile_range=tuple(state["cfg"]["quantile_range"]))
    y = rs.fit_transform(state["x"])
    return {"y": y, "center": rs.center_, "iqr": rs.iqr_, "rs": rs}


def work_bytes(state: dict, out: dict) -> int:
    """Bytes of input one completed call turned into a result: ``X`` once."""
    return state["bytes"]


def least_bytes(state: dict, out: dict) -> int:
    """The fewest bytes one chip must move for one call: its rows of ``X``
    read once for the statistics (no quantile is known before), read once
    more and written once for the scaled table."""
    return 3 * state["bytes"] // state["chips"]


def ranks(n: int, q) -> tuple:
    """The two 0-based ranks that bracket each percentile of ``n`` values,
    and the weight of the upper one: numpy's ``linear`` rule, on the host."""
    pos = np.asarray(q, np.float64) / 100.0 * (n - 1)
    lo = np.floor(pos).astype(np.int64)
    return lo, np.ceil(pos).astype(np.int64), (pos - lo).astype(np.float32)


@functools.partial(jax.jit, static_argnames="image")
def _percentiles(xs, lo, hi, frac, image=None):
    """(len(lo), d): ``v[lo] + frac * (v[hi] - v[lo])`` of every column's
    sorted values ``v``, a block of columns at a time. With an ``image``
    type (the control: ``bfloat16``) the values are cast to it first and
    the arithmetic stays in it."""
    cols = []
    for j in range(0, xs.shape[1], SORT_COLUMNS):
        blk = xs[:, j:j + SORT_COLUMNS]
        vals = jax.lax.sort(blk if image is None else blk.astype(image), dimension=0)
        vlo, vhi = vals[lo], vals[hi]
        cols.append((vlo + frac.astype(vals.dtype)[:, None] * (vhi - vlo)).astype(jnp.float32))
    return jnp.concatenate(cols, axis=1)


def statistics(state: dict, image=None) -> dict:
    """``center`` (the medians) and ``iqr`` (the range between the two
    quantiles; 1 where it is 0) of the table, by sorting."""
    q_min, q_max = state["cfg"]["quantile_range"]
    lo, hi, frac = ranks(state["x"].shape[0], [q_min, 50.0, q_max])
    pct = _percentiles(state["x"].larray, jnp.asarray(lo), jnp.asarray(hi), jnp.asarray(frac), image=image)
    iqr = pct[2] - pct[0]
    return {"center": pct[1], "iqr": jnp.where(iqr > 0, iqr, 1.0)}


def reference(state: dict) -> dict:
    return jax.block_until_ready(statistics(state))


@jax.jit
def _errors(xs, y, back, center, iqr, want_center, want_iqr):
    want_y = (xs - want_center) / want_iqr  # never held: it fuses into the two reductions
    return {
        "finite": jnp.all(jnp.isfinite(y)) & jnp.all(jnp.isfinite(center)) & jnp.all(jnp.isfinite(iqr)),
        "center_err": jnp.max(jnp.abs(center - want_center)) / jnp.max(jnp.abs(want_center)),
        "iqr_err": jnp.max(jnp.abs(iqr - want_iqr)) / jnp.max(jnp.abs(want_iqr)),
        "y_err": jnp.max(jnp.abs(y - want_y)) / jnp.max(jnp.abs(want_y)),
        "round_trip_err": jnp.max(jnp.abs(back - xs)) / jnp.max(jnp.abs(xs)),
    }


def check(state: dict, out: dict, ref: dict) -> dict:
    g = state["cfg"]["guarantees"]
    # the way back is held to its guarantee here, outside the window: one more table
    back = out["rs"].inverse_transform(out["y"])
    e = _errors(state["x"].larray, out["y"].larray, back.larray, out["center"].larray, out["iqr"],
                ref["center"], ref["iqr"])
    del back
    e = {"y_shape": list(out["y"].shape), "y_split": out["y"].split, "finite": bool(e["finite"]),
         **{k: float(e[k]) for k in ("center_err", "iqr_err", "y_err", "round_trip_err")}}
    misses = []
    if not e["finite"]:
        misses.append("y, center_ or iqr_ is not finite")
    if (e["y_shape"], e["y_split"]) != (list(state["x"].shape), state["x"].split):
        misses.append(f"y has shape {e['y_shape']} and split {e['y_split']}")
    for key, what, of in (("center_err", "center_", "max|reference|"), ("iqr_err", "iqr_", "max|reference|"),
                          ("y_err", "y", "max|reference y|"), ("round_trip_err", "inverse_transform(y)", "max|x|")):
        limit = g[key.replace("_err", "")]
        if not e[key] <= limit:
            misses.append(f"{what} off by {e[key]:.3e} of {of} > {limit:.1e}")
    return {"measured": e, "misses": misses}
