"""Op ``kmedians_fit``: ``ht.cluster.KMedians(n_clusters=k, init=<array>,
max_iter=..., tol=0.0).fit(x)`` on the KMeans configuration's Gaussian blobs,
which live on the chips.

The data and the two byte counts are the ``kmeans_fit`` op's own (upstream's cb
runs ``kmedians`` right after ``kmeans`` on the same data): ``work_bytes`` is ``X`` once for every iteration the fit
ran, ``least_bytes`` the chip's rows of ``X`` once an iteration and once more
for the labels, what any schedule must read. A selection that counts reads
``X`` more often than that, and ``hbm_roofline_pct`` says so.

The plain reference is here: L1 Lloyd steps in ``jax.numpy``, the medians by
``lax.sort`` of (label, value), a block of feature columns at a time so that
it fits beside ``X``. It never goes through ``ht``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import heat_tpu as ht
from benchmarks.ops.kmeans_fit import least_bytes, make, work_bytes  # noqa: F401 (the op's interface)

SORT_COLUMNS = 8  # feature columns a sort: 2 x 600 MB of operands at the north-star shard


def call(state: dict) -> dict:
    km = ht.cluster.KMedians(
        n_clusters=state["k"], init=state["init"], max_iter=state["max_iter"], tol=state["cfg"]["tol"]
    ).fit(state["x"])
    return {"centers": km.cluster_centers_, "labels": km.labels_, "km": km}


def finish(state: dict, out: dict) -> None:
    """The caller reads how many iterations ran: a read of a device scalar."""
    out["n_iter"] = out["km"].n_iter_


def _l1(xs, c):
    """(k, n) L1 distances, one centre at a time: nothing of n x k x d."""
    return jax.lax.map(lambda ci: jnp.sum(jnp.abs(xs - ci), axis=1), c)


@functools.partial(jax.jit, static_argnames="k")
def _medians(xs, labels, c, k: int):
    """Coordinate-wise medians by cluster, as ``numpy.median`` has them: the
    rows sorted by (label, value) column by column, the middle one (the mean
    of the two middle ones) of each cluster's run; an empty cluster keeps
    its centre."""
    n, d = xs.shape
    counts = jnp.sum((labels[:, None] == jnp.arange(k)).astype(jnp.int32), axis=0)
    first = jnp.cumsum(counts) - counts
    lo = jnp.clip(first + (counts - 1) // 2, 0, n - 1)
    hi = jnp.clip(first + counts // 2, 0, n - 1)
    cols = []
    for j in range(0, d, SORT_COLUMNS):
        blk = xs[:, j:j + SORT_COLUMNS]
        _, vals = jax.lax.sort((jnp.broadcast_to(labels[:, None], blk.shape), blk), dimension=0, num_keys=2)
        cols.append(0.5 * vals[lo] + 0.5 * vals[hi])
    return jnp.where(counts[:, None] > 0, jnp.concatenate(cols, axis=1), c)


@jax.jit
def _labels(xs, c):
    dist = _l1(xs, c)
    return jnp.argmin(dist, axis=0).astype(jnp.int32), jnp.sum(jnp.min(dist, axis=0))


def reference(state: dict) -> dict:
    """``max_iter`` plain L1 Lloyd steps from the same init, then the labels
    and the functional value (sum of the L1 distances) of where they end."""
    xs, c = state["x"].larray, state["init"].larray
    for _ in range(state["max_iter"]):
        labels, _ = _labels(xs, c)
        c = _medians(xs, labels, c, state["k"])
    labels, value = _labels(xs, c)
    return jax.block_until_ready({"centers": c, "labels": labels, "value": value})


@jax.jit
def _errors(got, got_labels, got_value, want, want_labels, want_value):
    return {
        "finite": jnp.all(jnp.isfinite(got)),
        "center_err": jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)),
        "labels_agree": jnp.sum((got_labels == want_labels).astype(jnp.int32)) / got_labels.shape[0],
        "value_err": jnp.abs(got_value / want_value - 1.0),
    }


def check(state: dict, out: dict, ref: dict) -> dict:
    g = state["cfg"]["guarantees"]
    # inertia_ is the fit's own functional value, from its label pass: read here, outside the call
    e = _errors(out["centers"].larray, out["labels"].larray.astype(jnp.int32), jnp.float32(out["km"].inertia_),
                ref["centers"], ref["labels"], ref["value"])
    e = {"n_iter": int(out["n_iter"]), "labels_shape": list(out["labels"].shape), "finite": bool(e["finite"]),
         "center_err": float(e["center_err"]), "labels_agree": float(e["labels_agree"]),
         "value_err": float(e["value_err"])}
    misses = []
    if g["all_iterations"] and e["n_iter"] != state["max_iter"]:
        misses.append(f"n_iter_ {e['n_iter']}, max_iter {state['max_iter']}: the fit stopped early")
    if not e["finite"]:
        misses.append("centres are not finite")
    if e["labels_shape"] != [state["x"].shape[0]]:
        misses.append(f"labels_ has shape {e['labels_shape']}")
    if not e["center_err"] <= g["centers"]:
        misses.append(f"centres off by {e['center_err']:.3e} of max|reference| > {g['centers']:.1e}")
    if not e["labels_agree"] >= g["labels"]:
        misses.append(f"labels agree on {e['labels_agree']:.6f} of the rows < {g['labels']}")
    if not e["value_err"] <= g["value"]:
        misses.append(f"functional value off by {e['value_err']:.3e} relative > {g['value']:.1e}")
    return {"measured": e, "misses": misses}
