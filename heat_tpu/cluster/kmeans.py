"""K-Means clustering.

API parity with /root/reference/heat/cluster/kmeans.py (``KMeans``; Lloyd
update via masked mean at kmeans.py:74-100, issuing k Allreduces per
iteration — reference call stack SURVEY §3.4). Here one Lloyd fit is
ONE jit-compiled program. On a TPU, for tall narrow f32 data, a Lloyd
iteration is one Pallas pass that reads the f32 rows once
(``_pallas``: distances on the MXU, argmin, one-hot sums, counts and
inertia from the same tile; 6.5 ms at 18.75M x 64 on a v5e, the rate a
bare read of the array gets — PERF.md section 6, PR 28). Elsewhere it is
the XLA formulation: the distance matrix by the quadratic expansion, the
per-cluster sums a one-hot matmul whose reduction over the sharded
sample axis lowers to ONE all-reduce of a (k × d) buffer, convergence a
scalar.

ISSUE 11 adds the STREAMING form: ``partial_fit`` (sklearn
MiniBatchKMeans-style running-mean updates, one fused program per
batch) and, through it, fits over HOST-RESIDENT operands — a
``ht.redistribution.staging.HostArray`` larger than HBM streams
(8,128)-aligned windows through the depth-2 double-buffered staging
slab, each window one ``partial_fit`` batch.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from typing import Optional, Union

from ..core import types
from ..core.dndarray import DNDarray
from ..core.sanitation import sanitize_in
from ._kcluster import _KCluster
from ..core.communication import place as _place
from ..observability import telemetry as _telemetry
from ..observability.instrument import observed_program_cache
from ..observability.tracing import call_span as _call_span
from . import _pallas

__all__ = ["KMeans"]


@observed_program_cache("kmeans.lloyd_step", maxsize=64)
def _lloyd_step(k: int, shape, jdtype: str, split=None, mesh=None, axis_name=None):
    """One Lloyd iteration as a pure function: (x, centers) →
    (new_centers, shift², inertia).

    Which form runs is decided by ``_pallas.lloyd_pass_serves`` from the
    backend, dtype, shape and split, nothing else. Where it says yes (a
    TPU, f32, ``d`` a multiple of 8 under 128, ``k`` ≤ 128, ``x`` on one
    device or split 0 in equal shards over ``mesh``) the step is ONE read
    of the f32 rows (``_pallas.fused_lloyd_step``; under ``shard_map``
    with one ``psum`` of the (k, d) sums, counts and inertia across
    chips), and it offers ``step.assign`` for the fit's label pass.
    Everywhere else it is the XLA formulation below: two streams an
    iteration over a bf16 copy of ``x`` that the chip's compiler hoists
    out of the loop, 9.0 ms an iteration at 18.75M x 64 on a v5e against
    the pass's 6.5 (PERF.md section 6, PR 28).
    """
    devices = 1 if mesh is None else mesh.devices.size
    if _pallas.lloyd_pass_serves(jax.default_backend(), jdtype, shape, k, split, devices):
        where = (mesh, axis_name if split == 0 else None) if devices > 1 else ()
        return _pallas.fused_lloyd_step(k, tuple(shape), *where)

    @jax.jit
    def step(arr, centers):
        x2 = jnp.sum(arr * arr, axis=1, keepdims=True)
        c2 = jnp.sum(centers * centers, axis=1, keepdims=True).T
        d2 = jnp.maximum(x2 + c2 - 2.0 * (arr @ centers.T), 0.0)
        labels = jnp.argmin(d2, axis=1)
        onehot = jax.nn.one_hot(labels, k, dtype=arr.dtype)  # (n, k)
        sums = onehot.T @ arr  # (k, d) — one all-reduce over the mesh
        counts = jnp.sum(onehot, axis=0)  # (k,)
        new_centers = jnp.where(
            counts[:, None] > 0, sums / jnp.maximum(counts[:, None], 1), centers
        )
        shift = jnp.sum((new_centers - centers) ** 2)
        inertia = jnp.sum(jnp.min(d2, axis=1))
        return new_centers, shift, inertia

    return step


def _lloyd_step_for(x: DNDarray):
    """``_lloyd_step`` bound to where ``x`` lies, as the ``step_factory``
    ``_fit_fused`` asks for; counts which form the fit got
    (``kmeans.step.fused`` / ``kmeans.step.xla``, once a fit)."""

    def factory(k: int, shape, jdtype: str):
        step = _lloyd_step(k, shape, jdtype, x.split, x.comm.mesh, x.comm.axis_name)
        fused = getattr(step, "assign", None) is not None
        _telemetry.inc("kmeans.step.fused" if fused else "kmeans.step.xla")
        return step

    return factory


@observed_program_cache("kmeans.partial_fit_step", maxsize=64)
def _partial_fit_step(k: int, shape, jdtype: str):
    """One STREAMING minibatch update as a pure jitted function:
    ``(arr, centers, counts) -> (new_centers, new_counts, inertia)``.

    The standard running-mean update (sklearn MiniBatchKMeans with
    per-center counts): every center is the mean of ALL samples ever
    assigned to it, so one epoch over a stream of disjoint batches
    touches each sample once — the pass-structured form the out-of-core
    staging executor feeds window by window. Same program shape as the
    Lloyd step: distances on the MXU, the per-cluster sums ONE one-hot
    matmul (a single all-reduce on a sharded batch), inertia a scalar.
    """

    @jax.jit
    def step(arr, centers, counts):
        x2 = jnp.sum(arr * arr, axis=1, keepdims=True)
        c2 = jnp.sum(centers * centers, axis=1, keepdims=True).T
        d2 = jnp.maximum(x2 + c2 - 2.0 * (arr @ centers.T), 0.0)
        labels = jnp.argmin(d2, axis=1)
        onehot = jax.nn.one_hot(labels, k, dtype=arr.dtype)  # (n, k)
        sums = onehot.T @ arr  # (k, d) — one all-reduce over the mesh
        # counts accumulate in f32 REGARDLESS of the data dtype: a bf16
        # running count saturates at 256 and the stream silently
        # overweights late batches (f32 additions are exact to 16M)
        bcounts = jnp.sum(onehot.astype(jnp.float32), axis=0)  # (k,)
        new_counts = counts + bcounts
        # running mean: n_c·c + Σ_batch, renormalized by the new count —
        # the mix runs in f32 (exact no-op for f32 data)
        new_centers = jnp.where(
            new_counts[:, None] > 0,
            (centers.astype(jnp.float32) * counts[:, None] + sums.astype(jnp.float32))
            / jnp.maximum(new_counts[:, None], 1),
            centers.astype(jnp.float32),
        ).astype(arr.dtype)
        inertia = jnp.sum(jnp.min(d2, axis=1))
        return new_centers, new_counts, inertia

    return step


class KMeans(_KCluster):
    """K-Means with Lloyd's algorithm (reference: kmeans.py:17).

    Parameters follow the reference: n_clusters, init
    ('random' | 'probability_based'/'kmeans++' | DNDarray), max_iter, tol,
    random_state.
    """

    def __init__(
        self,
        n_clusters: int = 8,
        init: Union[str, DNDarray] = "random",
        max_iter: int = 300,
        tol: float = 1e-4,
        random_state: Optional[int] = None,
    ):
        if isinstance(init, str) and init == "kmeans++":
            init = "probability_based"
        super().__init__(
            metric=lambda x, y: None,
            n_clusters=n_clusters,
            init=init,
            max_iter=max_iter,
            tol=tol,
            random_state=random_state,
        )
        # streaming state (partial_fit): samples-per-center running
        # counts — None until the first batch initializes the centers
        self._partial_counts = None

    def _update_centroids(self, x: DNDarray, matching_centroids: DNDarray) -> DNDarray:
        """Masked-mean centroid update (reference: kmeans.py:74-100) —
        exposed for API parity; ``fit`` uses the fused jitted step."""
        arr = x.larray
        if types.heat_type_is_exact(x.dtype):
            arr = arr.astype(jnp.float32)
        labels = matching_centroids.larray
        onehot = jax.nn.one_hot(labels, self.n_clusters, dtype=arr.dtype)
        sums = onehot.T @ arr
        counts = jnp.sum(onehot, axis=0)
        centers = self._cluster_centers.larray
        new_centers = jnp.where(counts[:, None] > 0, sums / jnp.maximum(counts[:, None], 1), centers)
        return DNDarray(
            _place(new_centers, x.comm.sharding(2, None)),
            tuple(int(s) for s in new_centers.shape),
            types.canonical_heat_type(new_centers.dtype),
            None,
            x.device,
            x.comm,
        )

    def fit(self, x, ckpt=None, _watcher=None, _chaos=None) -> "KMeans":
        """Run Lloyd iterations to convergence (reference: kmeans.py:102).
        Seeding, the convergence while_loop and the final assignment run
        as ONE compiled program — a single dispatch per fit (see
        ``_kcluster._fused_fit_program``).

        ``x`` may be a ``ht.redistribution.staging.HostArray`` (ISSUE
        11): the fit then STREAMS the host-resident operand — one epoch
        of :meth:`partial_fit` windows through the staging slab (the
        documented streaming-k-means algorithm; ``labels_`` stays unset
        — call :meth:`predict` batch-wise). With ``HEAT_TPU_OOC=0`` a
        fitting host operand materializes whole and runs the exact
        in-HBM Lloyd fit instead.

        ``ckpt`` (ISSUE 13, streaming path only): a
        ``ht.resilience.CheckpointConfig`` — the window stream commits
        a checkpoint every ``ckpt.every`` windows (centers, counts, the
        explicit RNG stream state, the window cursor and the slab the
        windows derive from) and, when a committed checkpoint for
        ``ckpt.tag`` already exists, RESUMES from it: the remaining
        windows replay with the recorded slab, so the resumed fit is
        bit-identical to an uninterrupted one — on the original world
        or a re-resolved (shrunk/grown) one. ``_watcher``/``_chaos``
        are the elastic runtime's hooks (``ht.resilience.elastic_fit``
        drives them); with ``HEAT_TPU_RESILIENCE=0`` a ``ckpt`` is
        ignored EVERYWHERE — including the unstreamable-input errors
        below, which only fire when the runtime is live — and the exact
        pre-resilience paths run."""
        from ..redistribution import staging as _staging

        if ckpt is not None:
            from ..resilience import checkpoint as _ckpt_mod

            if not _ckpt_mod.resilience_enabled(explicit=True):
                ckpt = None  # the documented escape hatch: ckpt is inert
        if isinstance(x, _staging.HostArray):
            if not _staging.ooc_engaged(x.nbytes, host_resident=True):
                if ckpt is not None:
                    raise ValueError(
                        "KMeans.fit(ckpt=): checkpointed resume rides the "
                        "streaming window path, which HEAT_TPU_OOC=0 "
                        "disables — unset the gate or drop ckpt="
                    )
                with _call_span("ht.call.kmeans.fit"):
                    x = _staging.materialize(x, what="KMeans.fit")
                    return self._fit_fused(x, _lloyd_step_for(x), returns_inertia=True)
            return self._partial_fit_stream(
                x, ckpt=ckpt, watcher=_watcher, chaos=_chaos, fresh=True
            )
        if ckpt is not None:
            raise ValueError(
                "KMeans.fit(ckpt=): the fused in-HBM Lloyd fit runs as ONE "
                "device program with no host cut points to checkpoint at — "
                "stream a staging.HostArray (or drive partial_fit batches) "
                "to checkpoint mid-fit"
            )
        with _call_span("ht.call.kmeans.fit"):
            return self._fit_fused(x, _lloyd_step_for(x), returns_inertia=True)

    # ------------------------------------------------------------------ #
    # streaming / out-of-core (ISSUE 11)                                 #
    # ------------------------------------------------------------------ #
    def partial_fit(self, x) -> "KMeans":
        """Incremental fit on ONE batch (sklearn MiniBatchKMeans-style;
        no reference analog): the first call initializes the centers
        from the batch with the configured ``init``, every call folds
        the batch into the per-center running means — one fused program
        dispatch per batch (``_partial_fit_step``). A
        ``staging.HostArray`` batch streams its windows through the
        staging executor, each window one update (with
        ``HEAT_TPU_OOC=0`` it materializes whole — one update — when it
        fits). ``inertia_`` reports the LAST batch's functional value."""
        from ..redistribution import staging as _staging

        if isinstance(x, _staging.HostArray):
            if not _staging.ooc_engaged(x.nbytes, host_resident=True):
                return self._partial_fit_batch(
                    _staging.materialize(x, what="KMeans.partial_fit")
                )
            return self._partial_fit_stream(x)
        return self._partial_fit_batch(x)

    def _partial_fit_batch(self, x: DNDarray) -> "KMeans":
        sanitize_in(x)
        if x.ndim != 2:
            raise ValueError(f"input needs to be 2-dimensional, got {x.ndim}")
        k = self.n_clusters
        arr = x.larray
        if types.heat_type_is_exact(x.dtype):
            arr = arr.astype(jnp.float32)
        if self._cluster_centers is None:
            self._initialize_cluster_centers(x)
        if self._partial_counts is None:
            # fresh stream — also the partial_fit-after-fit() case, which
            # continues refining the FITTED centers from count zero
            # (sklearn MiniBatchKMeans.partial_fit semantics)
            self._partial_counts = jnp.zeros((k,), dtype=jnp.float32)
        centers = self._cluster_centers.larray.astype(arr.dtype)
        step = _partial_fit_step(k, tuple(arr.shape), np.dtype(arr.dtype).name)
        centers, self._partial_counts, self._inertia = step(
            arr, centers, self._partial_counts
        )
        self._cluster_centers = DNDarray(
            _place(centers, x.comm.sharding(2, None)),
            (k, x.shape[1]),
            types.canonical_heat_type(centers.dtype),
            None,
            x.device,
            x.comm,
        )
        return self

    def _partial_fit_stream(self, host, ckpt=None, watcher=None, chaos=None,
                            fresh: bool = False) -> "KMeans":
        """One epoch of ``partial_fit`` windows over a host-resident
        operand: the window schedule is planned as a ``host-staging``
        Schedule (axis-0 windows), PROVEN to fit ``capacity("hbm")``,
        and executed depth-2 double-buffered — window k+1's
        ``device_put`` rides under window k's fused update.

        The elastic hooks (ISSUE 13, all optional and ALL inert under
        ``HEAT_TPU_RESILIENCE=0`` — the gate governs every hook, not
        just checkpointing, so the escape hatch runs the exact
        pre-resilience stream): ``ckpt`` commits/resumes the window
        cursor + model state; ``watcher`` is polled after each window
        (a world change raises the typed ``WorldChangedError``);
        ``chaos`` injects the declared faults. Poisoned state is
        caught by the finite-state validation AT COMMIT CADENCE — a
        host sync per window would stall the depth-2 window pipeline;
        validating immediately before each save preserves the
        invariant that matters (poisoned state
        is never COMMITTED: restore lands behind the poisoned window
        and replays it clean)."""
        from ..core import factories
        from ..redistribution import staging as _staging
        from ..resilience import checkpoint as _ckpt_mod, elastic as _elastic

        enabled_rt = _ckpt_mod.resilience_enabled(
            explicit=ckpt is not None or watcher is not None or chaos is not None
        )
        engaged = enabled_rt and ckpt is not None
        guarded = enabled_rt and (
            engaged or watcher is not None or chaos is not None
        )
        start = 0
        slab_override = None
        if fresh:
            # fit() is a FRESH fit: drop any previous streaming state
            # (partial_fit is the API that continues a stream)
            self._cluster_centers = None
            self._partial_counts = None
        if engaged:
            found = _ckpt_mod.restore_latest(ckpt.directory, tag=ckpt.tag)
            if found is not None:
                _step, state, _meta = found
                saved_shape = state.get("host_shape")
                if saved_shape is not None and (
                    tuple(saved_shape) != tuple(host.shape)
                    or str(state.get("host_dtype")) != str(host.dtype)
                ):
                    raise ValueError(
                        f"checkpoint tag {ckpt.tag!r} was written for a "
                        f"{tuple(saved_shape)}/{state.get('host_dtype')} "
                        f"operand but this fit streams {host.shape}/"
                        f"{host.dtype} — resuming would adopt another "
                        "dataset's cursor; use a fresh tag"
                    )
                self._load_stream_state(state)
                start = int(state["window_index"])
                slab_override = int(state["slab_bytes"])
        sched = _staging.plan_staged_passes(
            host.shape,
            host.dtype,
            [{"tag": "partial-fit", "axis": 0}],
            out_bytes=self.n_clusters * host.shape[1] * 8 + (1 << 20),
            slab=slab_override,
        )
        _staging.prove_fits(sched)
        slab = int(sched.staging["slab_bytes"])
        wins = _staging.window_extents(host.shape, host.dtype.itemsize, 0, slab)
        n_win = len(wins)
        if start >= n_win:
            return self  # the committed checkpoint already covers the epoch
        put = None
        if guarded and chaos is not None:
            chaos.bind_offset(start)
            put = chaos.poison_put()

        def _validate(k):
            if not _elastic._finite_state(self):
                raise _elastic.CollectivePoisoned(
                    f"window {k}: non-finite centers after the update — "
                    "poisoned exchange; restore from the last committed "
                    "checkpoint and replay"
                )

        def consume(j, slab_arr, win):
            k = start + j
            self._partial_fit_batch(factories.array(slab_arr, split=None))
            if not guarded:
                return
            if engaged and ((k + 1) % ckpt.every == 0 or k == n_win - 1):
                _validate(k)  # never COMMIT poisoned state
                path = _ckpt_mod.save(
                    self._stream_checkpoint_state(k + 1, slab, host),
                    tag=ckpt.tag, step=k + 1, directory=ckpt.directory,
                )
                _ckpt_mod.prune(ckpt.directory, ckpt.tag, ckpt.keep)
                if chaos is not None:
                    chaos.after_checkpoint(path, k + 1)
            elif chaos is not None:
                # chaos without checkpoints (drills/tests): detect at
                # every window — there is no commit cadence to ride
                _validate(k)
            if watcher is not None:
                evt = watcher.poll(k)
                if evt is not None:
                    raise _elastic.WorldChangedError(
                        evt.kind,
                        old_size=evt.detail.get("old_size"),
                        new_size=len(evt.devices),
                        epoch=_elastic.world_epoch(),
                    )

        rng0 = self._rng_state
        try:
            _staging.stream_windows(host, 0, wins[start:], consume, device_put=put,
                                    plan_id=sched.plan_id)
        except BaseException:
            if guarded:
                # a failed guarded stream rewinds the model's private
                # stream to where THIS attempt started: a retry with no
                # committed checkpoint then re-inits IDENTICALLY (when a
                # checkpoint exists, restore overwrites the stream
                # anyway) — the bit-reproducible-resume contract holds
                # even for failures before the first commit
                self._rng_state = rng0
            raise
        return self

    # -- checkpoint material (ISSUE 13) -------------------------------- #
    def _stream_checkpoint_state(self, window_index: int, slab_bytes: int,
                                 host) -> dict:
        """What a mid-stream checkpoint must capture to resume
        bit-reproducibly: centers, running counts, the EXPLICIT RNG
        stream state, the window cursor + slab the window geometry
        derives from (a resumed stream must replay the SAME windows —
        the running-mean update is batch-boundary dependent), and the
        OPERAND IDENTITY (shape/dtype) so a same-tag resume against a
        different dataset fails typed instead of adopting a foreign
        cursor."""
        state = {
            "centers": self._cluster_centers,
            "rng_state": self._rng_state,
            "window_index": int(window_index),
            "slab_bytes": int(slab_bytes),
            "n_clusters": int(self.n_clusters),
            "host_shape": [int(s) for s in host.shape],
            "host_dtype": str(host.dtype),
        }
        if self._partial_counts is not None:
            state["counts"] = self._partial_counts
        return state

    def _load_stream_state(self, state: dict) -> None:
        """Adopt a restored checkpoint's model state — the arrays
        arrive already re-sharded onto the CURRENT world."""
        if int(state.get("n_clusters", self.n_clusters)) != self.n_clusters:
            raise ValueError(
                f"checkpoint carries n_clusters={state.get('n_clusters')} "
                f"but this model has {self.n_clusters}"
            )
        self._cluster_centers = state["centers"]
        self._partial_counts = state.get("counts")
        rng = state.get("rng_state")
        self._rng_state = tuple(rng) if rng is not None else None
