"""Async micro-batching dispatcher — many callers, one accelerator.

Every entry point today handles exactly one caller at a time: a process
serving a thousand concurrent ``predict`` calls would run a thousand
bucket-1 programs back to back, paying per-dispatch overhead on each
and leaving the MXU idle between them. The dispatcher closes that gap
with the standard serving shape:

- **bounded queue** (:class:`~heat_tpu.serving.admission.AdmissionControl`):
  submit returns a ``Future`` immediately or raises the typed
  :class:`~heat_tpu.serving.admission.ServingOverloaded` — overload is
  backpressure, never an unbounded backlog;
- **pad-to-bucket coalescing**: the worker drains whatever is queued,
  concatenates it into one batch, and pads up to the smallest declared
  bucket size — so the accelerator sees a handful of fixed shapes (each
  AOT-cacheable, see ``aot_cache``) instead of one program per request
  count;
- **donation-aware double buffering**: each batch stages into a fresh
  host buffer and device placement while the previous batch executes,
  and the worker issues batch k+1 BEFORE fencing batch k — depth-2
  pipelining, so an endpoint program that donates its input slab
  (buffer reuse) never races the staging of the next batch;
- **per-request latency + queue-depth telemetry**: ``serving.request.
  latency`` (p50/p95/p99 via the sharded registry) and ``serving.queue.
  depth`` samples, plus always-on local tallies in
  :meth:`Dispatcher.stats`.

Span tracing (ISSUE 15, ``HEAT_TPU_TRACE``): the full request
lifecycle — ``serving.submit`` (validation + enqueue), ``serving.queue``
(enqueue → batch collection), ``serving.batch`` (a detached span
bracketing one batch dispatch → resolve, parenting its
``serving.dispatch`` / ``serving.fence`` / ``serving.resolve`` phase
spans), and ``serving.request`` (submit → future resolution, per
request). Every probe is one module-bool read when the gate is off.
Shed and drain events additionally land in the always-on flight
recorder, and a shed request's :class:`ServingOverloaded` carries the
recorder tail (``exc.flight_tail``) for post-mortems.

Host-sync budget (shardlint SL106/SL201): the dispatch→result hot path
contains ZERO ``jax.device_get`` — futures resolve with device arrays
(lazy per-request slices of the batch result) after a completion FENCE
(``block_until_ready``), which synchronizes but never transfers. The
caller decides if and when values cross to the host.
"""

from __future__ import annotations

import collections
import queue
import threading
import time
import weakref

from concurrent.futures import Future
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .admission import AdmissionControl, ServingOverloaded
from . import aot_cache as _aot
from ..observability import telemetry as _telemetry
from ..observability import tracing as _tracing
from ..resilience import elastic as _elastic

__all__ = [
    "Dispatcher", "Endpoint", "estimator_endpoint", "live_dispatchers",
    "program_endpoint",
]

_LAT_CAP = 4096  # local latency reservoir (stats() works with telemetry off)

#: every started dispatcher, weakly — what `ht.observability.
#: prometheus_text()` walks to render per-dispatcher gauges without the
#: serving layer handing it a handle
_LIVE: "weakref.WeakSet" = weakref.WeakSet()


def live_dispatchers() -> List["Dispatcher"]:
    """The currently-running dispatchers (weakly tracked from
    :meth:`Dispatcher.start`), name-sorted — the Prometheus exposition
    walks this."""
    return sorted((d for d in list(_LIVE) if d.running), key=lambda d: d.name)


class _Request:
    __slots__ = ("payload", "rows", "future", "t_submit", "t_submit_pc", "deadline")

    def __init__(self, payload, rows, future, t_submit, deadline, t_submit_pc=None):
        self.payload = payload
        self.rows = rows
        self.future = future
        self.t_submit = t_submit
        # perf_counter twin of t_submit, taken only when tracing is live
        # (span timestamps must share tracing's clock domain)
        self.t_submit_pc = t_submit_pc
        self.deadline = deadline


class Endpoint:
    """A servable program family: one callable per declared bucket size
    over ``(bucket, *feature_shape)`` batches.

    Parameters
    ----------
    programs : ``{bucket: callable}`` — each maps a placed
        ``(bucket, *feature_shape)`` device array (plus ``extra_args``)
        to an array/pytree whose every leaf has leading dim ``bucket``.
    feature_shape / dtype : per-sample trailing shape and input dtype
        (requests are cast on submit).
    extra_args : arrays appended to every program call (e.g. the fitted
        cluster centers) — replicated model state, not batched data.
    place : host batch -> device array (default: ``jnp.asarray``); an
        estimator endpoint shards over its communicator's mesh here.
    static_peak_bytes : optional static peak-HBM estimate of the
        endpoint's largest-bucket program (``ht.analysis.memcheck`` →
        ``context["static_peak_bytes"]``). When set, the dispatcher's
        admission control rejects submissions whose program statically
        cannot fit with a typed
        ``ServingOverloaded(reason="hbm-estimate")`` instead of letting
        the dispatch OOM; ``None`` (the default) skips the check.
    """

    def __init__(self, programs: Dict[int, Callable], feature_shape: Tuple[int, ...],
                 dtype, extra_args: tuple = (), place: Optional[Callable] = None,
                 name: str = "endpoint", static_peak_bytes: Optional[int] = None):
        if not programs:
            raise ValueError("an Endpoint needs at least one bucket program")
        self.programs = dict(programs)
        self.buckets = tuple(sorted(int(b) for b in programs))
        if any(b < 1 for b in self.buckets):
            raise ValueError(f"bucket sizes must be >= 1, got {self.buckets}")
        self.feature_shape = tuple(int(s) for s in feature_shape)
        self.dtype = np.dtype(dtype)
        self.extra_args = tuple(extra_args)
        self.place = place if place is not None else (lambda batch: jnp.asarray(batch))
        self.name = name
        self.static_peak_bytes = (
            None if static_peak_bytes is None else int(static_peak_bytes)
        )
        # epoch fence (ISSUE 14, commcheck SL504): the bucket programs
        # are compiled against THIS world — record its epoch so a
        # dispatch racing a world re-resolution fails typed
        # (WorldChangedError) instead of hanging on devices that are
        # gone. Zero-cost until the elastic runtime engages; the
        # drain/resume contract swaps in a re-warmed Endpoint whose
        # token is fresh.
        self._world_token = _elastic.capture_epoch()

    @property
    def max_rows(self) -> int:
        return self.buckets[-1]

    def bucket_for(self, rows: int) -> int:
        for b in self.buckets:
            if rows <= b:
                return b
        raise ValueError(f"{rows} rows exceed the largest bucket {self.max_rows}")

    def run(self, batch: np.ndarray):
        """Pad to bucket, place, and issue (asynchronously) the bucket's
        program. Returns ``(out, rows)``."""
        _elastic.check_epoch(self._world_token, what=f"endpoint {self.name!r}")
        rows = batch.shape[0]
        bucket = self.bucket_for(rows)
        if bucket > rows:
            pad = np.zeros((bucket - rows,) + self.feature_shape, dtype=self.dtype)
            batch = np.concatenate([batch, pad], axis=0)
        placed = self.place(batch)
        return self.programs[bucket](placed, *self.extra_args), bucket


class Dispatcher:
    """The micro-batching request loop over one :class:`Endpoint`.

    Use as a context manager (or ``start()``/``stop()``)::

        with ht.serving.Dispatcher(endpoint, max_queue=128) as d:
            fut = d.submit(x_batch)          # (n, *feature_shape), n >= 1
            labels = fut.result(timeout=5)   # device array, n rows

    ``submit`` raises :class:`ServingOverloaded` when the bounded queue
    is full; requests whose deadline passes while queued are shed with
    the same exception on their future.
    """

    def __init__(self, endpoint: Endpoint, admission: Optional[AdmissionControl] = None,
                 max_queue: int = 64, poll_s: float = 0.02, name: Optional[str] = None):
        self.endpoint = endpoint
        self.admission = admission or AdmissionControl(max_queue=max_queue)
        self.name = name or endpoint.name
        self._q: "queue.Queue[_Request]" = queue.Queue(maxsize=self.admission.max_queue)
        # worker-owned batching state; the only client touches are the
        # post-join sweep in stop() and the raced-stop sweep in submit(),
        # both of which run strictly AFTER the worker exited
        self._carry: collections.deque = collections.deque()  # racecheck: guarded-by(worker-loop; clients sweep only after join)
        self._poll_s = float(poll_s)
        # monotonic shutdown flag: written by stop() BEFORE _stop.set(),
        # read by the worker only after it observes _stop — the Event is
        # the fence
        self._drain_on_stop = True  # racecheck: guarded-by(_stop event ordering)
        # elastic failover (ISSUE 13): drain() pauses collection, the
        # worker fences the in-flight batch, sheds the queue typed, and
        # parks until resume(). The reason is written by drain() BEFORE
        # _pause.set() and read by the worker only after it observes
        # _pause — same fence discipline as _drain_on_stop.
        self._pause = threading.Event()
        self._drained = threading.Event()
        self._pause_reason = "resize"  # racecheck: guarded-by(_pause event ordering)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._lat: collections.deque = collections.deque(maxlen=_LAT_CAP)
        self._counts = {"requests": 0, "batches": 0, "rejected": 0, "shed": 0,
                        "padded_rows": 0, "rows": 0}
        self._counts_lock = threading.Lock()
        self._depth_max = 0

    # ------------------------------------------------------------------ #
    # lifecycle                                                          #
    # ------------------------------------------------------------------ #
    def start(self) -> "Dispatcher":
        if self._thread is not None and self._thread.is_alive():
            return self
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._worker, name=f"ht-serving-{self.name}", daemon=True
        )
        self._thread.start()
        _LIVE.add(self)
        return self

    def stop(self, drain: bool = True, timeout: float = 30.0) -> None:
        """Stop the worker; with ``drain`` (default) queued requests are
        served first, otherwise they fail with
        :class:`ServingOverloaded` (``reason="shutdown"``)."""
        self._drain_on_stop = drain  # racecheck: guarded-by(_stop event ordering)
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=timeout)
            if self._thread.is_alive():
                # drain still in progress past the timeout: keep the
                # handle (a later stop() can join again) and do NOT
                # sweep — the live worker still owns the queue
                return
            self._thread = None
        # post-join sweep: a submit() that raced the worker's final
        # drain pass may have enqueued after the last get — its future
        # would otherwise never resolve
        self._fail_queued("shutdown")

    def _fail_queued(self, reason: str = "shutdown") -> int:
        leftovers = list(self._carry)
        self._carry.clear()
        while True:
            try:
                leftovers.append(self._q.get_nowait())
            except queue.Empty:
                break
        if leftovers:
            # post-mortem breadcrumb + tail: a mass shed is exactly the
            # moment the last-N-things record matters
            _tracing.flight_record("serving.shed", reason, len(leftovers))
            tail = _tracing.flight_tail()
            for r in leftovers:
                if not r.future.done():
                    exc = ServingOverloaded(reason, queue_depth=len(leftovers))
                    exc.flight_tail = tail
                    r.future.set_exception(exc)
        return len(leftovers)

    # ------------------------------------------------------------------ #
    # elastic failover (ISSUE 13)                                        #
    # ------------------------------------------------------------------ #
    def drain(self, reason: str = "resize", timeout: float = 30.0) -> bool:
        """Fence and shed for a world change: the worker completes (and
        resolves) the in-flight batch, every QUEUED request's future
        fails typed — ``ServingOverloaded(reason="resize")`` by default,
        which load balancers treat as "fail over to another replica",
        extending the PR 9 shutdown contract — and the worker parks.
        New ``submit`` calls are rejected with the same reason until
        :meth:`resume`. Returns True once the worker confirms the drain
        (False on timeout; the pause stays armed either way)."""
        self._pause_reason = reason  # racecheck: guarded-by(_pause event ordering)
        self._drained.clear()
        self._pause.set()
        _tracing.flight_record("serving.drain", reason, self._q.qsize())
        if _telemetry._ENABLED:
            _telemetry.inc("serving.drain.count")
        if not self.running:
            # no worker to confirm: sweep here (nothing can be in flight)
            self._fail_queued(reason)
            self._drained.set()
            return True
        return self._drained.wait(timeout)

    def resume(self, endpoint: Optional[Endpoint] = None) -> None:
        """Unpark after a :meth:`drain` — optionally swapping in an
        endpoint rebuilt against the re-resolved world (its bucket
        programs come through ``aot_cache.ensure_program``, so a store
        warmed for that world serves them without compiling)."""
        if endpoint is not None:
            # written only while the worker is parked behind _pause
            self.endpoint = endpoint  # racecheck: guarded-by(_pause event ordering)
        self._drained.clear()
        self._pause.clear()

    def __enter__(self) -> "Dispatcher":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    # ------------------------------------------------------------------ #
    # client side                                                        #
    # ------------------------------------------------------------------ #
    def submit(self, x, deadline_s: Optional[float] = None) -> Future:
        """Enqueue one request: ``x`` is ``(n, *feature_shape)`` (or one
        unbatched sample) with ``1 <= n <=`` the largest bucket. Returns
        a ``Future`` resolving to the n-row device-array result."""
        if not self.running:
            raise RuntimeError("dispatcher is not running — call start() or use a with block")
        if self._pause.is_set():
            # draining for a world change: fail fast with the drain
            # reason so the load balancer fails over immediately
            with self._counts_lock:
                self._counts["rejected"] += 1
            if _telemetry._ENABLED:
                _telemetry.inc("serving.admission.rejected")
            raise ServingOverloaded(self._pause_reason, queue_depth=self._q.qsize())
        x = np.asarray(x, dtype=self.endpoint.dtype)
        if x.shape == self.endpoint.feature_shape:
            x = x[None]
        if x.shape[1:] != self.endpoint.feature_shape:
            raise ValueError(
                f"request shape {x.shape} does not match endpoint feature shape "
                f"(n, {', '.join(map(str, self.endpoint.feature_shape))})"
            )
        rows = int(x.shape[0])
        if rows < 1 or rows > self.endpoint.max_rows:
            raise ValueError(
                f"request rows {rows} outside [1, {self.endpoint.max_rows}] "
                "(the endpoint's largest bucket)"
            )
        # memory admission (ISSUE 10): an endpoint that DECLARES its
        # static peak (ht.analysis.memcheck) is rejected typed when the
        # program cannot fit the per-device HBM budget — a dispatch that
        # would OOM must never reach the accelerator
        peak = self.endpoint.static_peak_bytes
        if self.admission.over_memory(peak):
            with self._counts_lock:
                self._counts["rejected"] += 1
            if _telemetry._ENABLED:
                _telemetry.inc("serving.admission.rejected")
            raise self.admission.reject_memory(peak)
        now = time.monotonic()
        sp = _tracing.start_span(
            "serving.submit", endpoint=self.name, rows=rows
        ) if _tracing._ENABLED else None
        req = _Request(
            x, rows, Future(), now, self.admission.deadline_for(now, deadline_s),
            t_submit_pc=(time.perf_counter() if sp is not None else None),
        )
        try:
            try:
                self._q.put_nowait(req)
            except queue.Full:
                with self._counts_lock:
                    self._counts["rejected"] += 1
                if _telemetry._ENABLED:
                    _telemetry.inc("serving.admission.rejected")
                raise self.admission.reject(self._q.qsize()) from None
            if not self.running:
                # TOCTOU with stop(): the worker exited (and its post-stop
                # sweep may already have run) between the running check
                # above and the put — sweep our own enqueue so the future
                # resolves typed instead of hanging. If the final drain
                # already served it, the future holds a result and passes
                # through untouched.
                self._fail_queued("shutdown")  # submit raced stop()
                exc = req.future.exception() if req.future.done() else None
                if exc is not None:
                    raise exc
            depth = self._q.qsize()
            with self._counts_lock:
                self._counts["requests"] += 1
                if depth > self._depth_max:
                    self._depth_max = depth
            if _telemetry._ENABLED:
                _telemetry.inc("serving.requests")
                _telemetry.observe("serving.queue.depth", float(depth))
            return req.future
        finally:
            _tracing.end_span(sp)

    def call(self, x, timeout: Optional[float] = 60.0, deadline_s: Optional[float] = None):
        """``submit(...).result(timeout)`` convenience."""
        return self.submit(x, deadline_s=deadline_s).result(timeout=timeout)

    def stats(self) -> dict:
        """Always-on local tallies (works with global telemetry off):
        counters plus p50/p95/p99 request latency and max observed
        depth."""
        with self._counts_lock:
            lat = sorted(self._lat)
            out = dict(self._counts)
            out["queue_depth_max"] = self._depth_max
        # the SAME nearest-rank rule the telemetry registry uses, so
        # stats() and serving.request.latency report identical
        # percentiles over identical samples
        out["p50_s"] = _telemetry._percentile(lat, 0.50)
        out["p95_s"] = _telemetry._percentile(lat, 0.95)
        out["p99_s"] = _telemetry._percentile(lat, 0.99)
        return out

    # ------------------------------------------------------------------ #
    # worker side                                                        #
    # ------------------------------------------------------------------ #
    def _collect(self, block: bool = True):
        """Drain up to one max-bucket's worth of queued requests (deadline
        shedding applied at dequeue), or ``None`` this poll. With
        ``block=False`` (a batch is in flight) an empty queue returns
        immediately so the fence never waits out a poll interval."""
        reqs, rows = [], 0
        limit = self.endpoint.max_rows
        while self._carry and rows + self._carry[0].rows <= limit:
            r = self._carry.popleft()
            reqs.append(r)
            rows += r.rows
        if not reqs:
            try:
                r = self._q.get(timeout=self._poll_s) if block else self._q.get_nowait()
                reqs.append(r)
                rows += r.rows
            except queue.Empty:
                return None
        while rows < limit:
            try:
                r = self._q.get_nowait()
            except queue.Empty:
                break
            if rows + r.rows > limit:
                self._carry.append(r)  # head of the NEXT batch
                break
            reqs.append(r)
            rows += r.rows
        now = time.monotonic()
        live = []
        for r in reqs:
            if self.admission.expired(r.deadline, now):
                with self._counts_lock:
                    self._counts["shed"] += 1
                _tracing.flight_record("serving.shed", "deadline", self._q.qsize())
                if _telemetry._ENABLED:
                    _telemetry.inc("serving.admission.shed")
                exc = self.admission.shed(r.deadline, self._q.qsize())
                exc.flight_tail = _tracing.flight_tail()
                r.future.set_exception(exc)
            else:
                live.append(r)
        return live or None

    def _dispatch(self, reqs):
        """Stage (fresh host buffer + device placement) and ISSUE one
        padded batch — asynchronous: the fence happens in ``_resolve``,
        after the NEXT batch has been issued (depth-2 double buffering;
        a donated input slab is therefore never re-staged while its
        program still runs)."""
        batch_sp = None
        if _tracing._ENABLED:
            # detached: the batch lifecycle outlives this call frame —
            # _resolve closes it after the fence, with another batch's
            # dispatch span possibly opening in between
            batch_sp = _tracing.start_span(
                "serving.batch", detached=True, endpoint=self.name, n_reqs=len(reqs)
            )
            now_pc = time.perf_counter()
            for r in reqs:
                if r.t_submit_pc is not None:
                    _tracing.add_span(
                        "serving.queue", r.t_submit_pc, now_pc,
                        parent_id=batch_sp.id, rows=r.rows,
                    )
        batch = np.concatenate([r.payload for r in reqs], axis=0)
        rows = batch.shape[0]
        try:
            with _tracing.span(
                "serving.dispatch",
                parent_id=None if batch_sp is None else batch_sp.id,
                endpoint=self.name, rows=rows,
            ):
                out, bucket = self.endpoint.run(batch)
        except Exception as e:  # program build/placement failure: fail the batch, not the loop
            for r in reqs:
                if not r.future.done():
                    r.future.set_exception(e)
            _tracing.end_span(batch_sp, status="error")
            return None
        if batch_sp is not None:
            batch_sp.attrs["bucket"] = bucket
            batch_sp.attrs["rows"] = rows
        with self._counts_lock:
            self._counts["batches"] += 1
            self._counts["rows"] += rows
            self._counts["padded_rows"] += bucket - rows
        if _telemetry._ENABLED:
            _telemetry.inc("serving.batches")
            _telemetry.inc("serving.batch.rows", rows)
            _telemetry.inc("serving.batch.padded_rows", bucket - rows)
            _telemetry.observe("serving.queue.depth", float(self._q.qsize()))
        return (out, reqs, batch_sp)

    def _resolve(self, inflight) -> None:
        """Fence the batch (completion, not transfer — no device_get) and
        resolve each request's future with its lazy device-array slice.
        A poisoned batch (execution error surfacing at the fence) fails
        its own requests, never the worker loop."""
        out, reqs, batch_sp = inflight
        parent = None if batch_sp is None else batch_sp.id
        try:
            with _tracing.span("serving.fence", parent_id=parent, endpoint=self.name), \
                    _tracing.span("ht.sync.wait", what="serving.fence"):
                jax.block_until_ready(out)
        except Exception as e:
            for r in reqs:
                if not r.future.done():
                    r.future.set_exception(e)
            _tracing.end_span(batch_sp, status="error")
            return
        t_done = time.monotonic()
        t_done_pc = time.perf_counter() if _tracing._ENABLED else 0.0
        resolve_sp = _tracing.start_span(
            "serving.resolve", parent_id=parent, endpoint=self.name
        ) if _tracing._ENABLED else None
        off = 0
        for r in reqs:
            lo, hi = off, off + r.rows
            off = hi
            try:
                sl = jax.tree.map(lambda a: a[lo:hi], out)
                if not r.future.done():  # client may have cancel()ed
                    r.future.set_result(sl)
            except Exception as e:  # a bad output leaf fails ITS request only
                if not r.future.done():
                    r.future.set_exception(e)
                continue
            lat = t_done - r.t_submit
            if r.t_submit_pc is not None:
                _tracing.add_span(
                    "serving.request", r.t_submit_pc, t_done_pc,
                    parent_id=parent, endpoint=self.name, rows=r.rows,
                )
            with self._counts_lock:
                self._lat.append(lat)
            if _telemetry._ENABLED:
                _telemetry.observe("serving.request.latency", lat)
        _tracing.end_span(resolve_sp)
        _tracing.end_span(batch_sp)

    def _worker(self) -> None:
        inflight = None
        while True:
            if self._pause.is_set() and not self._stop.is_set():
                # elastic drain: fence the in-flight batch (its futures
                # RESOLVE — work already on the accelerator completes),
                # shed the backlog typed with the drain reason, confirm,
                # and park until resume() or stop()
                if inflight is not None:
                    self._resolve(inflight)
                    inflight = None
                n = self._fail_queued(self._pause_reason)
                if n:
                    with self._counts_lock:
                        self._counts["shed"] += n
                    if _telemetry._ENABLED:
                        _telemetry.inc("serving.drain.shed", n)
                self._drained.set()
                self._stop.wait(self._poll_s)  # parked; re-checks both events
                continue
            # stop(drain=False): collect nothing more — still-queued
            # requests fail typed below; the in-flight batch completes
            draining = not (
                self._stop.is_set() and not self._drain_on_stop
            )
            # non-blocking collect while a batch is in flight: the fence
            # must run as soon as there is nothing to stage, not after a
            # poll interval — every trailing batch's latency depends on it
            batch = self._collect(block=inflight is None) if draining else None
            staged = self._dispatch(batch) if batch else None
            if inflight is not None:
                self._resolve(inflight)
            inflight = staged
            if self._stop.is_set() and inflight is None and not batch:
                if self._drain_on_stop:
                    if self._carry or not self._q.empty():
                        continue  # keep serving until the backlog is gone
                else:
                    self._fail_queued("shutdown")
                break


# ---------------------------------------------------------------------- #
# endpoint builders                                                      #
# ---------------------------------------------------------------------- #
def program_endpoint(build, example_feature_shape, dtype, buckets: Sequence[int],
                     key: tuple, extra_args: tuple = (), place: Optional[Callable] = None,
                     input_sharding=None, donate: bool = False,
                     name: str = "program",
                     static_peak_bytes: Optional[int] = None) -> Endpoint:
    """An :class:`Endpoint` over an arbitrary program builder.

    ``build()`` returns the jitted program ``(batch, *extra_args) ->
    result``; each bucket's callable is resolved through the persistent
    AOT cache (:func:`heat_tpu.serving.aot_cache.ensure_program`) under
    ``key + (bucket,)`` — a warm process loads every bucket without
    tracing. ``donate=True`` donates the batch slab (argument 0).
    ``static_peak_bytes`` (optional, from ``ht.analysis.memcheck``)
    arms the dispatcher's HBM admission check."""
    feature_shape = tuple(int(s) for s in example_feature_shape)
    dtype = np.dtype(dtype)
    extra_sds = _aot._input_sds(extra_args)
    programs = {}
    for b in sorted(set(int(x) for x in buckets)):
        sds = jax.ShapeDtypeStruct((b,) + feature_shape, dtype, sharding=input_sharding)
        call, _status = _aot.ensure_program(
            tuple(key) + (("bucket", b),), build, (sds, *extra_sds),
            donate_argnums=(0,) if donate else (),
        )
        programs[b] = call
    return Endpoint(programs, feature_shape, dtype, extra_args=extra_args,
                    place=place, name=name, static_peak_bytes=static_peak_bytes)


def estimator_endpoint(estimator, buckets: Sequence[int] = (8, 32, 128),
                       donate: bool = False, name: Optional[str] = None) -> Endpoint:
    """An :class:`Endpoint` over a fitted estimator's serving program
    (``predict`` for the k-cluster family and KNeighborsClassifier —
    the estimator exposes it via ``serving_program()``). Batches are
    placed split-0 over the estimator's mesh; model state (centers /
    training set) rides as replicated ``extra_args``."""
    spec = estimator.serving_program()
    comm = spec.get("comm")
    place = None
    input_sharding = None
    if comm is not None and comm.is_distributed():
        ndim = 1 + len(spec["feature_shape"])
        input_sharding = comm.sharding(ndim, 0)

        def place(batch, _comm=comm):
            return _comm.shard(jnp.asarray(batch), 0)

    return program_endpoint(
        spec["build"], spec["feature_shape"], spec["dtype"], buckets,
        key=spec["key"], extra_args=spec["args"], place=place,
        input_sharding=input_sharding, donate=donate,
        name=name or spec.get("name", type(estimator).__name__.lower()),
    )


def transform_endpoint(transformer, buckets: Sequence[int] = (8, 32, 128),
                       donate: bool = False, name: Optional[str] = None) -> Endpoint:
    """An :class:`Endpoint` over a fitted transformer's serving program
    (one-hot / TF-IDF — ``preprocessing.sparse_encoders``). Same
    ``serving_program()`` contract as :func:`estimator_endpoint`; split
    out so warmup manifests and dashboards can tell ``transform``
    endpoints (feature pipelines) from ``predict`` endpoints (models),
    and so transformers without a distributed mesh stay replicated."""
    return estimator_endpoint(transformer, buckets=buckets, donate=donate,
                              name=name)
