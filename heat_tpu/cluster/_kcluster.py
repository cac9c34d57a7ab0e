"""Shared k-clustering machinery.

API parity with /root/reference/heat/cluster/_kcluster.py (``_KCluster``:
init strategies ``random``/``probability_based`` (k-means++) with
per-centroid Bcast from the owning rank at _kcluster.py:100-187; assignment
= cdist + argmin at :196-209). Here initialization samples/percolates on
the sharded global array (no rank-owned rows — the controller indexes the
global array and XLA fetches the row), and the whole fit — seeding, the
Lloyd-style ``while_loop``, the label pass — is one jitted program. The
iteration is the subclass's step: for KMeans on a TPU one fused pass over
f32 ``X`` that also serves the label pass (``_pallas``; PERF.md section
6, PR 28), otherwise XLA: distances by the quadratic expansion, masked
per-cluster reductions lowering to one all-reduce over the mesh.

The L1 family (KMedians, KMedoids) shares ``l1_step_for``: an L1 assignment
that never holds ``n x k x d``, and ``_cluster_medians``, every
per-cluster, per-feature median at once by the exact counting selection of
``core/_selection.py`` (the one ``ht.percentile`` runs along the sample axis;
here by label: a row counts for its own cluster's medians) instead of sorting
k masked copies.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from typing import Callable, Optional, Union

from ..core import random as ht_random, types
from ..core.base import BaseEstimator, ClusteringMixin
from ..core.dndarray import DNDarray
from ..core.sanitation import sanitize_in
from ..core.communication import place as _place
from ..observability import telemetry as _telemetry
from ..observability.instrument import observed_program_cache
from ..observability.tracing import call_span as _call_span, span as _span
from ..core import _selection
from ..core._pallas_select import _from_key
from ..core._selection import _members
from . import _pallas_l1

__all__ = ["_KCluster"]


def _seed_key(k: int) -> jax.Array:
    """Derive the seeding PRNG key from the global heat stream and advance
    it by the k draws the ++-seeding consumes. The single source of truth
    for BOTH the composite ``_kmeanspp`` path and the fused fit — they
    must derive identically or seeded results diverge between paths."""
    state = ht_random.get_state()
    key = jax.random.fold_in(jax.random.PRNGKey(int(state[1])), int(state[2]))
    ht_random.set_state((state[0], state[1], state[2] + k, 0, 0.0))
    return key


def make_fit_loop(step, jdtype: str, tol: float, max_iter: int, returns_inertia: bool):
    """Whole-fit while_loop with on-device convergence (no host sync per
    iteration). ``step(arr, centers)`` returns (new_centers, shift[,
    inertia]). Shared by the k-cluster family; callers lru-cache the
    jitted result per configuration."""

    def run(arr, centers0):
        big = jnp.asarray(jnp.inf, dtype=jnp.dtype(jdtype))
        zero = jnp.asarray(0.0, dtype=jnp.dtype(jdtype))

        def cond(state):
            return (state[0] < max_iter) & (state[2] > tol)

        if returns_inertia:
            def body(state):
                it, centers, _, _ = state
                new_centers, shift, inertia = step(arr, centers)
                return (it + 1, new_centers, shift, inertia)

            it, centers, _, inertia = jax.lax.while_loop(
                cond, body, (0, centers0, big, zero)
            )
            return centers, it, inertia

        def body(state):
            it, centers, _ = state
            new_centers, shift = step(arr, centers)
            return (it + 1, new_centers, shift)

        it, centers, _ = jax.lax.while_loop(cond, body, (0, centers0, big))
        return centers, it

    return jax.jit(run)


@observed_program_cache("kcluster.fused_fit", maxsize=64)
def _fused_fit_program(step, k: int, shape, jdtype: str, tol: float, max_iter: int,
                       returns_inertia: bool, metric: str, seeded: bool):
    """The ENTIRE fit — ++-seeding (when ``seeded``), the convergence
    while_loop, and the final label assignment — as ONE jitted program:
    a single dispatch per fit. The eager composite paid 3-4 dispatches
    (seeding, loop, assignment, functional value), which dominated fit
    time for cb-scale inputs. ``init_arg`` is a PRNG
    key when ``seeded`` else the (k, d) initial centers.

    A step may offer the final assignment itself, from its own pass:
    ``step.assign(arr, centers)`` gives the labels where the step
    ``returns_inertia`` (KMeans' fused pass: the loop's last inertia is the
    value), else (labels, functional value) (``_l1_step``). Every other
    step gets ``_labels_and_value``: ``_pairwise`` + argmin."""
    loop = make_fit_loop(step, jdtype, tol, max_iter, returns_inertia)
    seed_prog = _kmeanspp_program(k, shape, jdtype) if seeded else None
    assign = getattr(step, "assign", None)

    @jax.jit
    def run(arr, init_arg):
        centers0 = seed_prog(arr, init_arg) if seeded else init_arg.astype(arr.dtype)
        res = loop(arr, centers0)
        centers, n_iter = res[0], res[1]
        if assign is None:
            labels, fun = _labels_and_value(arr, centers, metric)
        elif returns_inertia:
            labels, fun = assign(arr, centers), None
        else:
            labels, fun = assign(arr, centers)
        return centers, n_iter, labels.astype(types.index_jax_type()), res[2] if returns_inertia else fun

    return run


def _labels_and_value(arr: jax.Array, centers: jax.Array, metric: str):
    """Label of the nearest centre by ``_pairwise`` and the functional value:
    the sum of the least distances (manhattan) or of their squares."""
    d = _KCluster._pairwise(arr, centers, metric)
    least = jnp.min(d, axis=1)
    return jnp.argmin(d, axis=1), jnp.sum(least if metric == "manhattan" else least ** 2)


@observed_program_cache("kcluster.predict", maxsize=64)
def _predict_program(metric: str, eval_fv: bool):
    """The fused label-assignment program ``(arr, centers) -> labels[,
    functional value]`` — ONE dispatch for the whole predict path
    (distances on the MXU, argmin, optional functional value), where
    the eager composite paid one per op. Shared by eager ``predict``
    and the serving endpoints (ISSUE 9), so a served request is
    bit-identical to an eager one by construction; shapes retrace under
    the same cached program."""

    def run(arr, centers):
        labels, fun = _labels_and_value(arr, centers, metric)
        labels = labels.astype(types.index_jax_type())
        return (labels, fun) if eval_fv else labels

    return jax.jit(run)


def serving_spec(metric: str, centers: jax.Array, comm=None) -> dict:
    """The serving-endpoint description of a k-cluster predict program
    (consumed by ``ht.serving.estimator_endpoint`` and the warmup CLI's
    declared set — both must derive identical AOT cache keys, which is
    why the key lives here, next to the program)."""
    k, d = int(centers.shape[0]), int(centers.shape[1])
    return {
        # the jitted program itself: jax.export takes no proxy
        "build": lambda: _predict_program(metric, False).program,
        "args": (centers,),
        "key": ("kcluster-predict", metric, k, d, str(np.dtype(centers.dtype))),
        "feature_shape": (d,),
        "dtype": np.dtype(centers.dtype),
        "comm": comm,
        "name": "kcluster-predict",
    }


@functools.lru_cache(maxsize=64)
def _kmeanspp_program(k: int, shape, jdtype: str):
    """Compiled greedy k-means++ seeding: (arr, key) -> (k, d) centers.
    A ``fori_loop`` over the k steps keeps the traced program size
    constant in k (an unrolled loop would compile k copies of the
    (L, n, d) candidate-distance computation)."""
    n = shape[0]
    n_candidates = 2 + int(np.log(max(k, 2)))

    def run(arr, key):
        keys = jax.random.split(key, k)
        first = jax.random.randint(keys[0], (), 0, n)
        centers0 = jnp.zeros((k, arr.shape[1]), dtype=arr.dtype).at[0].set(arr[first])
        d2_0 = jnp.sum((arr - centers0[0]) ** 2, axis=1)

        def body(i, state):
            centers, d2 = state
            probs = d2 / jnp.maximum(jnp.sum(d2), 1e-30)
            cand = jax.random.choice(keys[i], n, shape=(n_candidates,), p=probs)
            cand_pts = jnp.take(arr, cand, axis=0)  # (L, d)
            cand_d2 = jnp.sum((arr[None, :, :] - cand_pts[:, None, :]) ** 2, axis=2)  # (L, n)
            potentials = jnp.sum(jnp.minimum(d2[None, :], cand_d2), axis=1)  # (L,)
            best = jnp.argmin(potentials)
            centers = centers.at[i].set(cand_pts[best])
            d2 = jnp.minimum(d2, cand_d2[best])
            return (centers, d2)

        centers, _ = jax.lax.fori_loop(1, k, body, (centers0, d2_0))
        return centers

    return jax.jit(run)


# ---------------------------------------------------------------------- #
# the L1 family: assignment and per-cluster medians without a copy of X  #
# ---------------------------------------------------------------------- #
def _l1_passes_xla(k: int) -> "_pallas_l1.L1Passes":
    """The three passes of an L1 iteration in plain ``jax.numpy``
    (``_pallas_l1`` holds the chip's form of the same three): the assignment
    here, the selection's two from ``_selection.passes_xla``. Nothing is
    larger than ``X``: the clusters are walked, not broadcast. Under a mesh
    the sums over the split sample axis lower to all-reduces."""

    def assign(arr, centers):
        dist = jax.lax.map(lambda c: jnp.sum(jnp.abs(arr - c), axis=1), centers.astype(arr.dtype))  # (k, n)
        labels = jnp.argmin(dist, axis=0).astype(jnp.int32)
        return labels, jnp.sum(_members(labels, k), axis=0), jnp.sum(jnp.min(dist, axis=0))

    return _pallas_l1.L1Passes(assign, *_selection.passes_xla(k)[:2])  # no gather: the selection ends on X


def _cluster_medians(arr: jax.Array, labels: jax.Array, k: int, prev: jax.Array,
                     counts: Optional[jax.Array] = None, passes=None) -> jax.Array:
    """The (k, d) coordinate-wise medians of the rows of ``arr`` by label:
    exactly what ``jnp.nanmedian`` of each cluster's rows gives (the middle
    order statistic, the mean of the two middle ones for an even count); an
    empty cluster keeps its row of ``prev``. ``arr`` holds no NaN.

    The two middle order statistics, ranks ``(count - 1) // 2`` and
    ``count // 2`` of a cluster's rows, come from
    ``_selection.order_statistics`` by label: all k x d at once by passes
    that count, exact, with the passes given (``l1_step_for``: the chip's
    kernels or ``jax.numpy``)."""
    if passes is None:
        passes = _l1_passes_xla(k)
    labels = labels.astype(jnp.int32)
    if counts is None:
        counts = jnp.sum(_members(labels, k), axis=0)
    counts = counts.astype(jnp.int32)[:, None]
    lower = jnp.maximum(counts - 1, 0) // 2  # rank of the lower middle, 0-based
    upper = counts // 2
    low, high = _selection.order_statistics(arr, lower, upper, counts, passes, labels)
    med = 0.5 * _from_key(low, arr.dtype) + 0.5 * _from_key(high, arr.dtype)
    return jnp.where(counts > 0, med, prev.astype(arr.dtype))


def _snap_to_members(arr: jax.Array, labels: jax.Array, k: int, centers: jax.Array,
                     counts: jax.Array, prev: jax.Array) -> jax.Array:
    """Each centre moved to the row of its own cluster nearest to it in L1
    (the first of equals); an empty cluster keeps its row of ``prev``. One
    cluster at a time, so nothing is larger than ``X``."""
    # on x.T, and the row picked by a masked sum: on the chip a tall narrow
    # array lies feature-major, and a reduction along ``arr`` itself or a row
    # sliced out of it costs this loop a row-major copy of X (9.6 GB at the
    # north-star shard; tests/test_chip_compile.py)
    xt = arr.T
    rows = jax.lax.broadcasted_iota(jnp.int32, (1, arr.shape[0]), 1)

    def one(args):
        c, centre = args
        dist = jnp.where(labels == c, jnp.sum(jnp.abs(xt - centre[:, None]), axis=0), jnp.inf)
        return jnp.sum(jnp.where(rows == jnp.argmin(dist).astype(jnp.int32), xt, 0), axis=1)

    snapped = jax.lax.map(one, (jnp.arange(k), centers))
    return jnp.where(counts[:, None] > 0, snapped, prev.astype(arr.dtype))


@functools.lru_cache(maxsize=64)
def _l1_step(name: str, k: int, shape, jdtype: str, split, mesh, axis_name, snap: bool):
    """One L1 iteration for ``_fit_fused`` (``l1_step_for`` says what it
    is), cached by where the data lies; ``step.on_chip`` says which form of
    the passes it got."""
    devices = 1 if mesh is None else mesh.devices.size
    on_chip = _pallas_l1.l1_passes_serve(jax.default_backend(), jdtype, shape, k, split, devices)
    if on_chip:
        where = (mesh, axis_name if split == 0 else None) if devices > 1 else ()
        passes = _pallas_l1.l1_passes(k, tuple(shape), *where)
    else:
        passes = _l1_passes_xla(k)

    def step(arr, centers):
        with jax.named_scope(f"{name}.assign"):
            labels, counts, _ = passes.assign(arr, centers)
        with jax.named_scope(f"{name}.select"):
            new_centers = _cluster_medians(arr, labels, k, centers, counts, passes)
            if snap:
                new_centers = _snap_to_members(arr, labels, k, new_centers, counts, centers)
        return new_centers, jnp.sum((new_centers - centers) ** 2)

    def assign(arr, centers):
        with jax.named_scope(f"{name}.assign"):
            labels, _, fun = passes.assign(arr, centers)
        return labels, fun

    step.assign = assign
    step.on_chip = on_chip
    step.gathers = passes.gather is not None
    return step


def l1_step_for(x: DNDarray, name: str, snap: bool = False):
    """The ``step_factory`` of the L1 family for ``_fit_fused``, bound to
    where ``x`` lies: ``step(arr, centers) -> (new_centers, shift)`` is the
    L1 assignment, then every cluster's coordinate-wise median
    (``_cluster_medians``) and, with ``snap``, the member nearest to it
    (KMedoids); ``step.assign`` is the fit's label pass. Which form
    of the passes runs (``_pallas_l1`` on a TPU for tall narrow f32,
    ``jax.numpy`` elsewhere) reads backend, dtype, shape and split only, and
    is counted once a fit: ``<name>.step.select.pallas`` / ``.xla``, and
    ``<name>.step.select.gather`` where the step was built with the
    gathering pass (``_pallas_l1.gather_pays``)."""

    def factory(k: int, shape, jdtype: str):
        step = _l1_step(name, k, tuple(shape), jdtype, x.split, x.comm.mesh, x.comm.axis_name, snap)
        _telemetry.inc(f"{name}.step.select." + ("pallas" if step.on_chip else "xla"))
        if step.gathers:
            _telemetry.inc(f"{name}.step.select.gather")
        return step

    return factory


class _KCluster(BaseEstimator, ClusteringMixin):
    """Base class for k-statistics clustering (reference: _kcluster.py)."""

    def __init__(
        self,
        metric: Callable,
        n_clusters: int,
        init: Union[str, DNDarray],
        max_iter: int,
        tol: float,
        random_state: Optional[int],
    ):
        self.n_clusters = n_clusters
        self.init = init
        self.max_iter = max_iter
        self.tol = tol
        self.random_state = random_state

        self._metric = metric
        self._cluster_centers = None
        self._labels = None
        self._inertia = None
        self._n_iter = None

        # ISSUE 13 satellite — seed/stream state is EXPLICIT MODEL
        # state. The old contract ("the ctor reseeds the GLOBAL stream,
        # every init advances it") meant two same-seed models created
        # then fitted in sequence drew DIFFERENT inits, and a
        # checkpoint could not capture "where this model's stream is".
        # New contract: ``random_state`` establishes a model-PRIVATE
        # (seed, counter=0) stream; only this model's inits advance it,
        # the global stream is never touched. Two same-seed models —
        # fresh or restored from the same checkpoint — therefore draw
        # identical inits. A model WITHOUT a random_state keeps the
        # legacy global-stream draws (``_rng_state is None``).
        # Single-model seeded results are unchanged: the first init
        # still draws from (seed, counter=0) exactly as before.
        self._rng_state = (
            None if random_state is None
            else ("Threefry", int(random_state), 0, 0, 0.0)
        )

    def _with_stream(self, fn):
        """Run ``fn()`` against the model's private RNG stream when one
        exists (``random_state`` given, or restored from a checkpoint),
        else against the global stream (legacy). The private stream is
        swapped into the global slot for the draw and the ADVANCED
        state captured back — so ``_seed_key``/``randperm`` derivations
        stay byte-identical to the pre-satellite code at equal
        (seed, counter), and the outer global stream is untouched."""
        if self._rng_state is None:
            return fn()
        outer = ht_random.get_state()
        ht_random.set_state(self._rng_state)
        try:
            return fn()
        finally:
            self._rng_state = ht_random.get_state()
            ht_random.set_state(outer)

    @property
    def rng_state(self):
        """The model's explicit RNG stream state — ``("Threefry", seed,
        counter, 0, 0.0)`` for seeded models (checkpoint material), or
        ``None`` for models on the legacy global stream."""
        return self._rng_state

    @rng_state.setter
    def rng_state(self, state) -> None:
        self._rng_state = None if state is None else tuple(state)

    @property
    def cluster_centers_(self) -> DNDarray:
        """Coordinates of the cluster centers."""
        return self._cluster_centers

    @property
    def labels_(self) -> DNDarray:
        """Label of each sample point."""
        return self._labels

    @property
    def inertia_(self) -> float:
        """Sum of squared distances of samples to their closest center.
        Stored as a lazy device scalar by fit; the first access pays the
        host read and caches the float."""
        if self._inertia is None:
            return None
        if not isinstance(self._inertia, float):
            with _span("ht.sync.read", what="inertia_"):
                self._inertia = float(self._inertia)
        return self._inertia

    @property
    def n_iter_(self) -> int:
        """Number of iterations run (lazy device scalar; see inertia_)."""
        if self._n_iter is None:
            return None
        if not isinstance(self._n_iter, int):
            with _span("ht.sync.read", what="n_iter_"):
                self._n_iter = int(self._n_iter)
        return self._n_iter

    # ------------------------------------------------------------------ #
    # initialization (reference: _kcluster.py:87-187)                    #
    # ------------------------------------------------------------------ #
    def _initialize_cluster_centers(self, x: DNDarray) -> None:
        k = self.n_clusters
        n, d = x.shape
        arr = x.larray
        if types.heat_type_is_exact(x.dtype):
            arr = arr.astype(jnp.float32)

        if isinstance(self.init, DNDarray):
            if self.init.shape != (k, d):
                raise ValueError(
                    f"passed centroids need to be of shape ({k}, {d}), got {self.init.shape}"
                )
            centers = self.init.larray.astype(arr.dtype)
        elif isinstance(self.init, str) and self.init == "random":
            # k observations drawn at random from the data (reference:
            # per-centroid rank-owned row + Bcast; here a global gather)
            idx = self._with_stream(
                lambda: ht_random.randperm(n, comm=x.comm).larray[:k]
            )
            centers = jnp.take(arr, idx, axis=0)
        elif isinstance(self.init, str) and self.init in ("probability_based", "kmeans++", "k-means++"):
            centers = self._kmeanspp(arr, k)
        else:
            raise ValueError(f"initialization needs to be 'random', 'probability_based' or a DNDarray, got {self.init}")

        # centers are replicated (small k×d)
        self._cluster_centers = DNDarray(
            _place(centers, x.comm.sharding(2, None)),
            (k, d),
            types.canonical_heat_type(centers.dtype),
            None,
            x.device,
            x.comm,
        )

    def _kmeanspp(self, arr: jax.Array, k: int) -> jax.Array:
        """Greedy k-means++ seeding on the sharded global array (reference:
        _kcluster.py:123-187 draws one candidate per step with per-centroid
        owner-rank broadcasts; here the sklearn-style greedy variant draws
        2+log(k) candidates per step and keeps the one minimizing the
        potential — markedly more robust seeding at negligible cost).
        The whole seeding is ONE jitted program (the eager unrolled loop
        cost ~20 dispatches)."""
        prog = _kmeanspp_program(k, tuple(arr.shape), np.dtype(arr.dtype).name)
        return prog(arr, self._with_stream(lambda: _seed_key(k)))

    # ------------------------------------------------------------------ #
    # assignment (reference: _kcluster.py:196-209)                       #
    # ------------------------------------------------------------------ #
    _assignment_metric = "euclidean"

    def _assign_to_cluster(self, x: DNDarray, eval_functional_value: bool = False) -> DNDarray:
        """Label of the closest center for every sample, using the
        subclass's assignment metric (reference passes cdist or manhattan
        into _KCluster; kmedians/kmedoids use L1)."""
        sanitize_in(x)
        arr = x.larray
        if types.heat_type_is_exact(x.dtype):
            arr = arr.astype(jnp.float32)
        c = self._cluster_centers.larray
        prog = _predict_program(self._assignment_metric, eval_functional_value)
        if eval_functional_value:
            # L1/L2 functional value (lazy device scalar, read by inertia_)
            labels, self._inertia = prog(arr, c)
        else:
            labels = prog(arr, c)
        gshape = (x.shape[0],)
        split = 0 if x.split is not None else None
        if split is not None:
            labels = x.comm.shard(labels, split)
        # same index-output dtype convention as _fit_fused / sort / topk
        return DNDarray(
            labels, gshape, types.canonical_heat_type(labels.dtype), split,
            x.device, x.comm,
        )

    @staticmethod
    def _pairwise(arr: jax.Array, c: jax.Array, metric: str = "euclidean") -> jax.Array:
        """Pairwise sample×center distances: Euclidean via the MXU-friendly
        quadratic expansion, or Manhattan for the L1 family."""
        if metric == "manhattan":
            return jnp.sum(jnp.abs(arr[:, None, :] - c[None, :, :]), axis=-1)
        x2 = jnp.sum(arr * arr, axis=1, keepdims=True)
        c2 = jnp.sum(c * c, axis=1, keepdims=True).T
        return jnp.sqrt(jnp.maximum(x2 + c2 - 2.0 * (arr @ c.T), 0.0))

    def _update_centroids(self, x: DNDarray, matching_centroids: DNDarray):
        raise NotImplementedError()

    def fit(self, x: DNDarray):
        raise NotImplementedError()

    # ------------------------------------------------------------------ #
    # shared fused fit driver                                            #
    # ------------------------------------------------------------------ #
    def _fit_fused(self, x: DNDarray, step_factory, returns_inertia: bool):
        """Run the whole fit as one compiled program (see
        ``_fused_fit_program``). ``step_factory(k, shape, jdtype)`` returns
        the per-iteration update (Lloyd / median / medoid)."""
        sanitize_in(x)
        if x.ndim != 2:
            raise ValueError(f"input needs to be 2-dimensional, got {x.ndim}")
        k = self.n_clusters
        arr = x.larray
        if types.heat_type_is_exact(x.dtype):
            arr = arr.astype(jnp.float32)

        seeded = isinstance(self.init, str) and self.init in (
            "probability_based", "kmeans++", "k-means++",
        )
        with _span("ht.call.kmeans.init"):
            if seeded:
                # the SHARED derivation keeps seeded results identical between
                # the fused fit and the composite _kmeanspp path
                init_arg = self._with_stream(lambda: _seed_key(k))
            else:
                self._initialize_cluster_centers(x)
                init_arg = self._cluster_centers.larray

        with _span("ht.call.kmeans.program"):
            step = step_factory(k, tuple(arr.shape), np.dtype(arr.dtype).name)
            prog = _fused_fit_program(
                step, k, tuple(arr.shape), np.dtype(arr.dtype).name,
                float(self.tol), int(self.max_iter), returns_inertia,
                self._assignment_metric, seeded,
            )
            centers, n_iter_dev, labels, inertia_dev = prog(arr, init_arg)

        with _span("ht.call.kmeans.wrap"):
            self._n_iter = n_iter_dev  # lazy device scalars; properties read them
            self._inertia = inertia_dev
            self._cluster_centers = DNDarray(
                _place(centers, x.comm.sharding(2, None)),
                (k, x.shape[1]),
                types.canonical_heat_type(centers.dtype),
                None,
                x.device,
                x.comm,
            )
            gshape = (x.shape[0],)
            split = 0 if x.split is not None else None
            if split is not None:
                labels = x.comm.shard(labels, split)
            # index-output dtype convention (ADVICE r4): like sort/topk/unique
            # indices, labels declare the PHYSICAL buffer's canonical type —
            # int64 in x64 mode, int32 under the TPU degrade policy — so
            # index-valued outputs expose one consistent logical dtype
            self._labels = DNDarray(
                labels, gshape, types.canonical_heat_type(labels.dtype), split,
                x.device, x.comm,
            )
        return self

    def predict(self, x: DNDarray) -> DNDarray:
        """Labels of the closest cluster center for new data (reference:
        _kcluster.py predict). One fused program dispatch (see
        ``_predict_program``)."""
        with _call_span("ht.call.kmeans.predict"):
            sanitize_in(x)
            if self._cluster_centers is None:
                raise RuntimeError("fit needs to be called before predict")
            return self._assign_to_cluster(x)

    def serving_program(self) -> dict:
        """The endpoint description ``ht.serving.estimator_endpoint``
        consumes: the fitted predict program, its replicated model state
        (the centers), and the persistent AOT cache key parts."""
        if self._cluster_centers is None:
            raise RuntimeError("fit needs to be called before serving")
        return serving_spec(
            self._assignment_metric,
            self._cluster_centers.larray,
            comm=self._cluster_centers.comm,
        )
