"""Span tracing + the always-on flight recorder (ISSUE 15).

PR 1's counters/timers answer *how many* and *how long in aggregate*;
nothing in the stack can answer "which lap, which tier, which window"
— yet every modeled speedup in the TPU verdict backlog (overlap
1.54–1.60x, wire 0.251x, two-tier 7.5x, staging PCIe bounds) is a claim
about exactly that per-step structure. This module is the instrument:

- **spans** — ``with span("ht.call.hsvd.level0"): ...`` at the seams of
  the hot layers. A span is two things (PR 25):

  1. always a ``jax.profiler.TraceAnnotation``: under any profiler
     session (``ht.utils.monitor.trace(path)``,
     ``jax.profiler.start_trace``, ``benchmarks/run.py --trace 1``) it
     lands on the ``/host:CPU`` plane of the profiler's own trace, on the
     clock of the device ops, where Perfetto shows it beside them and
     ``benchmarks/spans.py`` reduces it. The session is the switch: no
     environment variable, no call. With no session it costs the
     constructor and one atomic read (about 1 us);
  2. when collection is enabled (``HEAT_TPU_TRACE``, below) also a
     structured, parented record in the ring of this module
     (``start_span``/``end_span``/``add_span`` write to the ring only),
     which :func:`export_trace` reads.

  What a reducer needs is in the span's NAME, a constant string at the
  call site; attrs are for a person reading the trace and are values
  already at hand, HOST-SIDE only (plan_id, step kind, tier, lap/window
  index, bucket, bytes, world epoch — never array values), so spans are
  trace-safe: inside a jitted program body one fires once per compile
  and is tagged ``traced=True`` (its duration is tracing time: it
  counts for the census only). The names of the ``ht.*`` spans
  and the metric that reads each are listed in ``docs/API.md``
  (observability) and root ``PERF.md`` section 3. Two of them say what
  the wait side of a call holds (PR 36): ``ht.sync.read`` wherever the
  library itself brings a device value to the host, ``ht.sync.wait``
  wherever it waits for a program; and the outermost span of a public
  call (:func:`call_span`) carries, under a profiler session only, the
  calling thread's and the process's CPU time as the event's arguments.
- **flight recorder** — a small ALWAYS-ON fixed-field ring, independent
  of the trace gate and of telemetry: one bool check + one bounded
  append per record. Its tail is attached to ``WorldChangedError``,
  dispatcher shed events, and chaos kills, so a post-mortem starts with
  the last N things the process actually did.
- **Chrome-trace export** — :func:`export_trace` emits
  trace-event-format JSON (per-thread tracks, ``plan_id``-correlated
  async spans) loadable in Perfetto/chrome://tracing and alignable with
  ``jax.profiler`` device traces via the ``redist_plan_<id>``
  named-scope stamps the executor already emits into HLO metadata.

Gate: ``HEAT_TPU_TRACE`` (declared in ``core/gates.py`` with
``affects_programs=False``) — ``0`` is the hard-off zero-overhead
escape hatch (every probe is one module-bool read), ``1`` forces
collection, ``auto`` (default) follows the telemetry switch
(``HEAT_TPU_TELEMETRY=1`` / ``ht.telemetry.enable()`` turn tracing on
too). The gate changes WHAT IS OBSERVED, never what runs: plans,
plan_ids, programs, and AOT envelope keys are byte-identical at every
value — pinned in tier-1 and diffed in the ci.sh parity leg.

Thread-safety: the span ring and the flight ring each sit behind one
module lock (bounded appends — recorders never block on readers for
long); the active-span stack and ambient-attribute context are
per-thread (``threading.local``), so concurrent recorders never see
each other's parents.

Stdlib-only at import on purpose (like ``core/gates``): importable
before jax loads, usable from the lightest CLI process; the first
``span`` imports ``jax.profiler``.
"""

from __future__ import annotations

import contextlib
import json
import threading
import time

from collections import deque
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

from ..core import gates as _gates

__all__ = [
    "TRACE_ENV",
    "Span",
    "add_span",
    "call_span",
    "capacity",
    "clear",
    "context",
    "current_span_id",
    "disable",
    "dropped",
    "enable",
    "enabled",
    "end_span",
    "export_trace",
    "flight_capacity",
    "flight_clear",
    "flight_dropped",
    "flight_record",
    "flight_tail",
    "span",
    "spans",
    "start_span",
    "trace_mode",
]

TRACE_ENV = "HEAT_TPU_TRACE"

#: span ring capacity — big enough for every lap/window/batch span
#: of a multi-GB plan execution, bounded
#: so instrumenting a serving hot loop cannot grow memory; overwrites
#: are counted in :func:`dropped` (never silently).
_SPAN_CAP = 16384

#: flight-recorder ring: deliberately small — the point is the LAST N
#: records at the moment something died, not history.
_FLIGHT_CAP = 256

# same epoch convention as events.py: timestamps relative to process
# start, perf_counter domain
_T0 = time.perf_counter()


def trace_mode() -> str:
    """Resolved ``HEAT_TPU_TRACE`` mode (``"0"``/``"1"``/``"auto"``).
    ``0`` = hard off (the zero-overhead escape hatch), ``1`` = force
    collection, ``auto`` (default) = follow the telemetry switch."""
    v = (_gates.get(TRACE_ENV) or "auto").strip().lower()
    if v in ("0", "off", "false", "no"):
        return "0"
    if v in ("1", "on", "true", "force", "yes"):
        return "1"
    return "auto"


def _initial_enabled() -> bool:
    mode = trace_mode()
    if mode == "1":
        return True
    if mode == "0":
        return False
    from . import telemetry as _telemetry

    return _telemetry._ENABLED


class Span:
    """One finished-or-active span. ``attrs`` are host-side values only
    (the trace-safety contract shared with telemetry/events)."""

    __slots__ = ("id", "parent", "name", "thread", "t0", "dur_s", "attrs")

    def __init__(self, id, parent, name, thread, t0, attrs):
        self.id = id
        self.parent = parent
        self.name = name
        self.thread = thread
        self.t0 = t0  # perf_counter domain
        self.dur_s = None  # set by end_span
        self.attrs = attrs

    def as_dict(self) -> Dict[str, Any]:
        return {
            "id": self.id,
            "parent": self.parent,
            "name": self.name,
            "thread": self.thread,
            "t0_s": round(self.t0 - _T0, 9),
            "dur_s": self.dur_s,
            "attrs": {k: v for k, v in self.attrs.items() if v is not None},
        }

    def __repr__(self) -> str:
        return f"Span({self.id}, {self.name!r}, dur={self.dur_s}, {self.attrs})"


# hooks read this attribute directly — the whole disabled-path cost
_ENABLED: bool = _initial_enabled()

_lock = threading.Lock()
_spans: deque = deque(maxlen=_SPAN_CAP)
_seq = 0
_dropped = 0
_tls = threading.local()

# thread ident -> name, for the export's thread tracks (plain dict:
# single-key writes are GIL-atomic, and a stale name is cosmetic)
_thread_names: Dict[int, str] = {}

_flight_lock = threading.Lock()
_flight: deque = deque(maxlen=_FLIGHT_CAP)
_flight_seq = 0
_flight_dropped = 0


def enable() -> None:
    """Turn span collection on (also via ``HEAT_TPU_TRACE=1``, or
    ``auto`` + the telemetry switch)."""
    global _ENABLED
    _ENABLED = True


def disable() -> None:
    """Turn span collection off. Collected spans are kept until
    :func:`clear`."""
    global _ENABLED
    _ENABLED = False


def enabled() -> bool:
    return _ENABLED


def _on_telemetry_switch(on: bool) -> None:
    """``telemetry.enable()``/``disable()`` notify here: under the
    default ``auto`` mode, tracing follows the telemetry switch; an
    explicit ``0``/``1`` pins it regardless."""
    global _ENABLED
    if trace_mode() == "auto":
        _ENABLED = bool(on)


def _stack() -> list:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def _ambient() -> list:
    amb = getattr(_tls, "ambient", None)
    if amb is None:
        amb = _tls.ambient = []
    return amb


def start_span(
    name: str, parent_id: Optional[int] = None, detached: bool = False, **attrs
) -> Optional[Span]:
    """Open a span; returns the token :func:`end_span` closes (``None``
    when tracing is disabled — ``end_span(None)`` is a no-op, so probes
    need no branch). ``parent_id`` overrides the ambient parent (the
    innermost active span on this thread); ``detached=True`` keeps the
    span OFF the thread's active stack — the shape for lifecycles that
    outlive the opening call frame (a dispatcher batch: opened at
    dispatch, closed at resolve, with other spans in between)."""
    global _seq
    if not _ENABLED:
        return None
    th = threading.current_thread()
    ident = th.ident or 0
    if ident not in _thread_names:
        _thread_names[ident] = th.name
    stack = _stack()
    if parent_id is None and stack:
        parent_id = stack[-1].id
    merged: Dict[str, Any] = {}
    for d in _ambient():
        merged.update(d)
    merged.update(attrs)
    with _lock:
        _seq += 1
        sid = _seq
    sp = Span(sid, parent_id, name, ident, time.perf_counter(), merged)
    if not detached:
        stack.append(sp)
    return sp


def end_span(sp: Optional[Span], **attrs) -> None:
    """Close a span opened by :func:`start_span` and commit it to the
    ring. Extra ``attrs`` (an outcome learned at the end — status,
    bytes, error) merge over the opening attrs. Out-of-order closes are
    legal: the span is removed from the thread stack wherever it sits."""
    global _dropped
    if sp is None:
        return
    sp.dur_s = round(time.perf_counter() - sp.t0, 9)
    if attrs:
        sp.attrs.update(attrs)
    stack = getattr(_tls, "stack", None)
    if stack:
        for i in range(len(stack) - 1, -1, -1):
            if stack[i] is sp:
                del stack[i]
                break
    with _lock:
        if len(_spans) == _SPAN_CAP:
            _dropped += 1
        _spans.append(sp)


_annotation = None  # jax.profiler.TraceAnnotation, bound by the first span


def _bind_annotation():
    """``jax.profiler.TraceAnnotation``, imported by the first span and not
    with this module, which stays importable before jax."""
    global _annotation
    from jax.profiler import TraceAnnotation

    _annotation = TraceAnnotation
    return TraceAnnotation


class span:
    """A span around the enclosed block: ``with span("ht.call.x"): ...``.

    Always a ``jax.profiler.TraceAnnotation``: under any profiler session
    (``ht.utils.monitor.trace``, ``jax.profiler.start_trace``) the span
    lands on the ``/host:CPU`` plane of the profiler's trace, on the clock
    of the device ops, with ``attrs`` as the event's arguments; with no
    session it costs the constructor and one atomic read. When collection
    is enabled (``HEAT_TPU_TRACE``) it is also recorded in the ring, with
    its parent, and ``__enter__`` returns the :class:`Span` (else
    ``None``). :func:`call_span` makes the one that also carries the
    calling thread's counters."""

    __slots__ = ("_name", "_parent_id", "_attrs", "_counted", "_ann", "_sp")

    def __init__(self, name: str, parent_id: Optional[int] = None, **attrs):
        self._name = name
        self._parent_id = parent_id
        self._attrs = attrs
        self._counted = False

    def __enter__(self) -> Optional[Span]:
        ann = self._ann = (_annotation or _bind_annotation())(self._name, **self._attrs)
        ann.__enter__()
        if self._counted and ann.is_enabled():
            # read inside the span, so their cost is the span's; the
            # annotation's arguments only: the ring's record keeps attrs
            ann.set_metadata(**_thread_counters())
        if _ENABLED:
            self._sp = sp = start_span(self._name, parent_id=self._parent_id, **self._attrs)
            return sp
        self._sp = None
        return None

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._sp is not None:
            end_span(self._sp)
        self._ann.__exit__(exc_type, exc, tb)


def call_span(name: str, **attrs) -> span:
    """The :class:`span` of an OUTERMOST public call (``ht.call.hsvd_rank``,
    ``ht.call.qr``, ``ht.call.kmeans.fit``, ...). Under a live profiler
    session, and only then (without one: the same ``is_enabled()`` read a
    plain span pays), it reads two CPU clocks at entry and passes them as
    the annotation's arguments, which the profiler keeps as the event's
    integer stats:

    - ``thread_cpu_ns`` (``time.thread_time_ns()``): CPU time of the
      calling thread;
    - ``process_cpu_ns`` (``time.process_time_ns()``): CPU time of all
      threads of the process.

    The thread's context switches are not among them: the chip machines'
    kernel (gVisor) counts none, and a zero from a source that cannot count
    rules nothing out (``PERF.md`` section 6, PR 36). It counts CPU time in
    ticks of 10 ms, so a reader needs a window of calls and says what that
    resolves. Nothing is computed here: a reader takes the difference
    between two consecutive calls (one entry to the next is one cycle: the
    call, the caller's wait, the caller's loop).
    ``benchmarks/hostside.py`` does, and ``docs/API.md`` (observability)
    says what each difference means. The counters go to the profiler alone,
    never to the ring."""
    sp = span(name, **attrs)
    sp._counted = True
    return sp


def _thread_counters() -> Dict[str, int]:
    """What :func:`call_span` reads at entry: two system calls (0.5 us on
    a Linux host, 12 us under the chip machine's gVisor). Observing must
    not fail the call observed: a platform that refuses one of the clocks
    gives what was read before it."""
    out: Dict[str, int] = {}
    try:
        out["thread_cpu_ns"] = time.thread_time_ns()
        out["process_cpu_ns"] = time.process_time_ns()
    except (AttributeError, OSError, ValueError):
        pass
    return out


def add_span(
    name: str, t0: float, t1: float, parent_id: Optional[int] = None, **attrs
) -> None:
    """Record a span retroactively from two ``time.perf_counter()``
    readings — the low-overhead form for lifecycles whose start was a
    plain timestamp (a request's submit time): no token to carry, one
    call at the point the duration becomes known."""
    global _seq, _dropped
    if not _ENABLED:
        return
    th = threading.current_thread()
    ident = th.ident or 0
    if ident not in _thread_names:
        _thread_names[ident] = th.name
    stack = getattr(_tls, "stack", None)
    if parent_id is None and stack:
        parent_id = stack[-1].id
    merged: Dict[str, Any] = {}
    for d in _ambient():
        merged.update(d)
    merged.update(attrs)
    with _lock:
        _seq += 1
        sp = Span(_seq, parent_id, name, ident, float(t0), merged)
        sp.dur_s = round(float(t1) - float(t0), 9)
        if len(_spans) == _SPAN_CAP:
            _dropped += 1
        _spans.append(sp)


@contextlib.contextmanager
def context(**attrs) -> Iterator[None]:
    """Push ambient attributes for the enclosed block: every span this
    THREAD starts inside inherits them (its own attrs win on conflict).
    The executor wraps a plan execution in ``context(plan_id=...)`` so
    the per-lap probes — three call layers down — carry the plan id
    without threading it through every signature."""
    if not _ENABLED:
        yield
        return
    amb = _ambient()
    amb.append(attrs)
    try:
        yield
    finally:
        amb.pop()


def current_span_id() -> Optional[int]:
    """Id of the innermost active span on this thread (``None`` when
    no span is open) — what ``events.emit`` stamps into its optional
    ``span`` correlation field."""
    stack = getattr(_tls, "stack", None)
    return stack[-1].id if stack else None


def spans() -> List[Dict[str, Any]]:
    """Snapshot of the committed spans, oldest first, as dicts."""
    with _lock:
        return [sp.as_dict() for sp in _spans]


def clear() -> None:
    """Drop every committed span (active stacks are untouched) and
    reset the overwrite counter."""
    global _dropped
    with _lock:
        _spans.clear()
        _dropped = 0


def dropped() -> int:
    """Spans overwritten by ring wrap since the last :func:`clear` —
    a non-zero value means the snapshot is a TAIL, not a history."""
    with _lock:
        return _dropped


def capacity() -> int:
    return _SPAN_CAP


# --------------------------------------------------------------------- #
# probe factories — the hot-seam wrappers                               #
# --------------------------------------------------------------------- #
def lap_probes(
    issue: Callable, consume: Callable, attrs: Optional[Dict[str, Any]] = None
) -> Tuple[Callable, Callable]:
    """Wrap a ``_run_laps`` ``(issue, consume)`` pair with one span per
    lap call — the executor's depth-2 loops stay byte-identical (the
    SL405-checked skeleton is untouched; only the callables it drives
    are decorated). The wrapped calls run at TRACE time inside a jitted
    program body, so the spans fire once per compile and are tagged
    ``traced=True``: census material, not wall time."""
    base = dict(attrs or {})

    def traced_issue(k):
        with span("redist.issue", lap=int(k), traced=True, **base):
            return issue(k)

    def traced_consume(state, result, k):
        with span("redist.consume", lap=int(k), traced=True, **base):
            return consume(state, result, k)

    return traced_issue, traced_consume


def window_probes(
    put: Callable, consume: Callable, plan_id: Optional[str] = None
) -> Tuple[Callable, Callable]:
    """Wrap ``staging.stream_windows``' ``(device_put, consume)`` pair:
    one ``staging.stage_in`` span per window transfer (REAL host wall
    time of the PCIe leg) and one ``staging.compute``
    span per window's consume call."""
    state = {"k": 0}

    def traced_put(host_block):
        w = state["k"]
        state["k"] += 1
        with span(
            "staging.stage_in",
            step="stage_in",
            tier="pcie",
            window=w,
            bytes=int(getattr(host_block, "nbytes", 0)),
            plan_id=plan_id,
        ):
            return put(host_block)

    def traced_consume(k, cur, win):
        with span(
            "staging.compute",
            step="compute",
            tier="hbm",
            window=int(k),
            plan_id=plan_id,
        ):
            return consume(k, cur, win)

    return traced_put, traced_consume


# --------------------------------------------------------------------- #
# the flight recorder                                                   #
# --------------------------------------------------------------------- #
# always-on by design (a post-mortem instrument that has to be switched
# on before the crash records nothing); tests may toggle
_FLIGHT_ENABLED = True


def flight_record(kind: str, what: str = "", value=None) -> None:
    """Append one FIXED-FIELD record to the flight ring: ``kind`` (the
    event class), ``what`` (a short string — a reason, a tag), ``value``
    (one number — a step, a count, an epoch). One bool check + one
    bounded append; never allocates beyond the record. Deliberately not
    a span and not an event: this ring survives with the process and is
    cheap enough to leave on everywhere."""
    global _flight_seq, _flight_dropped
    if not _FLIGHT_ENABLED:
        return
    with _flight_lock:
        if len(_flight) >= _FLIGHT_CAP:
            # the bounded deque is about to overwrite its oldest record
            _flight_dropped += 1
        _flight_seq += 1
        _flight.append(
            {
                "seq": _flight_seq,
                "t_s": round(time.perf_counter() - _T0, 6),
                "thread": threading.current_thread().name,
                "kind": kind,
                "what": what,
                "value": value,
            }
        )


def flight_tail(n: int = 64) -> List[Dict[str, Any]]:
    """The last ``n`` flight records, oldest first — what
    ``WorldChangedError``, dispatcher shed paths, and the chaos harness
    attach to their post-mortems."""
    n = int(n)
    if n <= 0:
        return []
    with _flight_lock:
        tail = list(_flight)[-n:]
    return [dict(r) for r in tail]


def flight_clear() -> None:
    with _flight_lock:
        _flight.clear()


def flight_capacity() -> int:
    return _FLIGHT_CAP


def flight_dropped() -> int:
    """How many flight records the bounded ring has overwritten since
    process start — the ring's health gauge (``prometheus_text``
    exports it as ``heat_tpu_flight_dropped_total``): a large number
    on a crashed process means the tail you are reading is recent,
    not complete."""
    with _flight_lock:
        return _flight_dropped


# --------------------------------------------------------------------- #
# Chrome-trace / Perfetto export                                        #
# --------------------------------------------------------------------- #
def export_trace(path: str, span_rows: Optional[List[Dict[str, Any]]] = None) -> int:
    """Write the span buffer as Chrome trace-event-format JSON
    (loadable in Perfetto / chrome://tracing); returns the event count.

    - every finished span becomes one complete (``"X"``) event on its
      thread's track, ``args`` = the span attrs;
    - spans carrying a ``plan_id`` attr additionally emit an async
      begin/end pair (``"b"``/``"e"``) under ``cat="plan"`` with
      ``id=plan_id``, so every lap/window/execute span of one plan
      lines up on one async track — and, on a device profile captured
      in the same session, aligns with the ``redist_plan_<id>``
      named-scope stamps ``jax.profiler`` records in the HLO metadata;
    - thread-name metadata events label the tracks.
    """
    rows = spans() if span_rows is None else list(span_rows)
    events: List[Dict[str, Any]] = []
    seen_threads: Dict[int, str] = {}
    for r in rows:
        tid = int(r.get("thread") or 0)
        if tid not in seen_threads:
            seen_threads[tid] = _thread_names.get(tid, f"thread-{tid}")
    for tid, tname in sorted(seen_threads.items()):
        events.append(
            {
                "ph": "M",
                "name": "thread_name",
                "pid": 0,
                "tid": tid,
                "args": {"name": tname},
            }
        )
    for r in rows:
        if r.get("dur_s") is None:
            continue  # never committed (crashed mid-span): skip
        ts_us = round(float(r["t0_s"]) * 1e6, 3)
        dur_us = round(float(r["dur_s"]) * 1e6, 3)
        args = dict(r.get("attrs") or {})
        args["span_id"] = r["id"]
        if r.get("parent") is not None:
            args["parent_id"] = r["parent"]
        tid = int(r.get("thread") or 0)
        events.append(
            {
                "ph": "X",
                "name": r["name"],
                "cat": r["name"].split(".", 1)[0],
                "pid": 0,
                "tid": tid,
                "ts": ts_us,
                "dur": dur_us,
                "args": args,
            }
        )
        plan_id = args.get("plan_id")
        if plan_id:
            common = {
                "cat": "plan",
                "id": str(plan_id),
                "pid": 0,
                "tid": tid,
                "name": r["name"],
            }
            events.append({"ph": "b", "ts": ts_us, **common})
            events.append({"ph": "e", "ts": round(ts_us + dur_us, 3), **common})
    doc = {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {
            "generator": "heat_tpu.observability.tracing",
            "spans": len(rows),
            "dropped": dropped(),
        },
    }
    with open(path, "w") as f:
        json.dump(doc, f)
    return len(events)
