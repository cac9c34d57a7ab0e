"""Op ``kmeans_fit``: ``ht.cluster.KMeans(n_clusters=k, init=<array>,
max_iter=..., tol=0.0).fit(x)`` on Gaussian blobs that live on the chips.

The generator and the plain Lloyd reference are copies of ``chip_smoke.py``'s
(``blobs``, ``lloyd``), which PR 22 proved on the chip. Two differences: the
blobs' ``center_scale`` comes from the configuration (at chip_smoke's 4.0 the
fit reaches an exact fixed point after two iterations and ``tol=0.0`` stops
it), and the chunked fill runs under ``shard_map``, so each chip fills its
own rows and the generator's temporaries stay a fraction of its share.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import heat_tpu as ht

HI = jax.lax.Precision.HIGHEST


def blobs(rows_per_chip: int, d: int, k: int, center_scale: float, key, comm):
    """``k`` Gaussian blobs (unit noise, centres ``center_scale`` * N(0, 1)),
    each chip's rows filled chunk by chunk into one buffer. The key is an
    argument of the program, so every seed runs the same one."""
    kc, kd = jax.random.split(key)
    centers = center_scale * jax.random.normal(kc, (k, d), jnp.float32)
    chunks = next(c for c in (125, 100, 50, 20, 10, 5, 4, 2, 1) if rows_per_chip % c == 0)
    rows = rows_per_chip // chunks
    axis = comm.axis_name

    @jax.jit
    @functools.partial(jax.shard_map, mesh=comm.mesh, in_specs=(P(), P()), out_specs=P(axis, None),
                       check_vma=False)
    def make(cen, kd):
        mine = jax.random.fold_in(kd, jax.lax.axis_index(axis))

        def body(i, buf):
            ky, kn = jax.random.split(jax.random.fold_in(mine, i))
            y = jax.random.randint(ky, (rows,), 0, k)
            blk = cen[y] + jax.random.normal(kn, (rows, d), jnp.float32)
            return jax.lax.dynamic_update_slice(buf, blk, (i * rows, 0))

        return jax.lax.fori_loop(0, chunks, body, jnp.zeros((rows_per_chip, d), jnp.float32))

    return make(centers, kd), centers


def make(cfg: dict, chips: int, key) -> dict:
    comm = ht.MPI_WORLD
    if comm.size != chips:
        raise RuntimeError(f"ht.MPI_WORLD spans {comm.size} devices, the cell asks for {chips}")
    d, k = cfg["features"], cfg["n_clusters"]
    kx, ki = jax.random.split(key)
    xj, centers = blobs(cfg["rows_per_chip"], d, k, cfg["data"]["center_scale"], kx, comm)
    init = centers + cfg["data"]["init_noise"] * jax.random.normal(ki, (k, d), jnp.float32)
    x = ht.array(xj, split=0)
    del xj
    return {"cfg": cfg, "chips": chips, "x": x, "init": ht.array(init), "k": k,
            "max_iter": cfg["max_iter"], "bytes": cfg["rows_per_chip"] * chips * d * 4}


def call(state: dict) -> dict:
    km = ht.cluster.KMeans(
        n_clusters=state["k"], init=state["init"], max_iter=state["max_iter"], tol=state["cfg"]["tol"]
    ).fit(state["x"])
    return {"centers": km.cluster_centers_, "labels": km.labels_, "km": km}


def finish(state: dict, out: dict) -> None:
    """The caller reads how many iterations ran: a read of a device scalar."""
    out["n_iter"] = out.pop("km").n_iter_


def work_bytes(state: dict, out: dict) -> int:
    """Bytes of input one completed fit turned into a result: ``X`` once
    for every Lloyd iteration it ran."""
    return state["bytes"] * out["n_iter"]


def least_bytes(state: dict, out: dict) -> int:
    """The fewest bytes one chip must read for one fit: its rows of ``X``
    once an iteration (assign and update in one stream) and once more for
    the labels of the final centres."""
    return (out["n_iter"] + 1) * state["bytes"] // state["chips"]


def _sq_dist(xs, c):
    return (
        jnp.sum(xs * xs, axis=1, keepdims=True)
        - 2.0 * jnp.matmul(xs, c.T, precision=HI)
        + jnp.sum(c * c, axis=1)[None, :]
    )


@jax.jit
def _inertia(xs, c):
    return jnp.sum(jnp.maximum(jnp.min(_sq_dist(xs, c), axis=1), 0.0))


def reference(state: dict) -> dict:
    """``max_iter`` plain Lloyd steps from the same init at precision
    highest, and the inertia of where they end."""
    k, iters = state["k"], state["max_iter"]

    @jax.jit
    def lloyd(xs, c):
        for _ in range(iters):
            onehot = jax.nn.one_hot(jnp.argmin(_sq_dist(xs, c), axis=1), k, dtype=jnp.float32)
            counts = jnp.sum(onehot, axis=0)[:, None]
            c = jnp.where(counts > 0, jnp.matmul(onehot.T, xs, precision=HI) / jnp.maximum(counts, 1.0), c)
        return c

    xs = state["x"].larray
    centers = lloyd(xs, state["init"].larray)
    return jax.block_until_ready({"centers": centers, "inertia": _inertia(xs, centers)})


@jax.jit
def _errors(xs, got, want, want_inertia):
    return {
        "finite": jnp.all(jnp.isfinite(got)),
        "center_err": jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)),
        "inertia_err": jnp.abs(_inertia(xs, got) / want_inertia - 1.0),
    }


def check(state: dict, out: dict, ref: dict) -> dict:
    g = state["cfg"]["guarantees"]
    e = _errors(state["x"].larray, out["centers"].larray, ref["centers"], ref["inertia"])
    e = {"n_iter": int(out["n_iter"]), "labels_shape": list(out["labels"].shape),
         "finite": bool(e["finite"]), "center_err": float(e["center_err"]), "inertia_err": float(e["inertia_err"])}
    misses = []
    if g["all_iterations"] and e["n_iter"] != state["max_iter"]:
        misses.append(f"n_iter_ {e['n_iter']}, max_iter {state['max_iter']}: the fit stopped early")
    if not e["finite"]:
        misses.append("centres are not finite")
    if e["labels_shape"] != [state["x"].shape[0]]:
        misses.append(f"labels_ has shape {e['labels_shape']}")
    if not e["center_err"] <= g["centers"]:
        misses.append(f"centres off by {e['center_err']:.3e} of max|reference| > {g['centers']:.1e}")
    if not e["inertia_err"] <= g["inertia"]:
        misses.append(f"inertia off by {e['inertia_err']:.3e} relative > {g['inertia']:.1e}")
    return {"measured": e, "misses": misses}
