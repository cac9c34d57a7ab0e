"""Structured runtime metrics core.

The reference instruments its continuous benchmarks EXTERNALLY (perun
``@monitor()`` decorators around benchmark scripts, HeAT paper 2007.13552);
the library itself cannot answer "how many collectives did this op launch,
how many bytes did that reshard move, did the program cache hit?" — even
though redistribution cost is exactly what dominates at scale (2112.01075).
This module is the first-party answer: a process-wide registry of

- **counters** (monotonic ints: cache hits/misses, reshard calls, bytes
  accounted via the ``*.bytes`` convention),
- **timers** (count / total / min / max plus a bounded sample reservoir
  for p50/p95),

fed by hook points in the hot layers (``core/_operations.py``,
``core/communication.py``, ``core/dndarray.py``, ``core/jit.py``) and by
the ``record()`` context manager for user-scoped blocks.

Design constraints, in order:

1. **Zero-cost when disabled.** Every hook gates on the module-level
   ``_ENABLED`` bool (one attribute read); no allocation, no lock, no
   string formatting happens on the disabled path. The default is
   disabled; ``HEAT_TPU_TELEMETRY=1`` in the environment enables at
   import, ``enable()``/``disable()`` switch at runtime.
2. **Trace-safe.** Hooks record only host-side Python values — shapes,
   splits, dtypes, wall times — never array *values*, so they are safe
   to hit inside a ``jax.jit``/``ht.jit`` trace (they then fire once per
   compile, not per execution; events carry a ``traced`` field where the
   distinction matters).
3. **Thread-safe AND contention-free under concurrent recorders.** The
   registry is SHARDED per recording thread (ISSUE 9: the serving
   dispatcher records request latencies from its worker while client
   threads bump submit counters): ``inc``/``observe`` touch only the
   calling thread's shard under that shard's own lock — uncontended in
   steady state, so recorders never serialize on one global lock — and
   readers (``snapshot``/``timer_table``) merge the shards. Counter and
   call totals are exact under any interleaving; the p50/p95 sample
   reservoir is bounded PER SHARD (``_SAMPLE_CAP`` each), and dead
   threads' shards fold into one retired accumulator when new threads
   register, so memory stays O(#metrics × #LIVE-recording-threads)
   even under request-handler thread churn.

Energy note (perun-parity deviation): this platform exposes no
in-container energy counter, so the registry records time/bytes/counts
only — see ``heat_tpu.utils.monitor`` for the TDP-envelope estimation
recipe.
"""

from __future__ import annotations

import collections
import contextlib
import json
import re
import threading
import time
import weakref

from typing import Any, Dict, Iterator, Optional

# stdlib-only sibling (the gate registry) — safe to import this early in
# process start, before jax or any heavy core module loads
from ..core import gates as _gates

__all__ = [
    "Registry",
    "disable",
    "enable",
    "enabled",
    "export_jsonl",
    "inc",
    "observe",
    "prometheus_text",
    "record",
    "report",
    "reset",
    "snapshot",
]

# reservoir size per timer: enough for stable p50/p95 on bench-scale call
# counts without unbounded growth on hot-loop instrumentation
_SAMPLE_CAP = 1024


def _env_truthy(value: Optional[str]) -> bool:
    return (value or "").strip().lower() in ("1", "true", "on", "yes")


def _percentile(sorted_samples, q: float) -> float:
    """Nearest-rank percentile on an already-sorted list."""
    if not sorted_samples:
        return 0.0
    idx = min(len(sorted_samples) - 1, max(0, int(round(q * (len(sorted_samples) - 1)))))
    return sorted_samples[idx]


class _Shard:
    """One recording thread's private accumulator. Only the owning thread
    mutates it (under ``lock``, uncontended unless a reader is merging),
    so concurrent recorders never touch each other's state. ``owner`` is
    a weakref to the recording thread: when the thread dies the registry
    folds the shard into its retired accumulator (exact totals survive,
    memory stays O(live threads), not threads-ever)."""

    __slots__ = ("lock", "counters", "timers", "owner")

    def __init__(self) -> None:
        self.lock = threading.Lock()
        self.counters: Dict[str, int] = {}
        self.timers: Dict[str, dict] = {}
        self.owner = weakref.ref(threading.current_thread())


class Registry:
    """Counter + timer store, sharded per recording thread. The
    module-level singleton backs the public API; ``heat_tpu.utils.monitor``
    holds its own always-on instance (the decorator is explicit opt-in,
    independent of the global switch)."""

    def __init__(self) -> None:
        # guards the shard LIST only; per-shard data is guarded by the
        # shard's own lock (the hot path never takes this one after its
        # thread's first record). `_retired` absorbs the shards of dead
        # threads so totals stay exact while memory stays bounded by the
        # LIVE thread count under churn.
        self._lock = threading.Lock()
        self._shards: list = []
        self._retired = _Shard()
        self._tls = threading.local()

    def _shard(self) -> _Shard:
        sh = getattr(self._tls, "shard", None)
        if sh is None:
            sh = _Shard()
            self._tls.shard = sh
            with self._lock:
                self._prune_locked()
                self._shards.append(sh)
        return sh

    def _prune_locked(self) -> None:
        """Fold shards whose recording thread has exited into the
        retired accumulator (called under ``self._lock`` whenever a new
        thread registers — the only moment the shard list grows)."""
        live = []
        for sh in self._shards:
            owner = sh.owner()
            if owner is not None and owner.is_alive():
                live.append(sh)
            else:
                self._fold_retired(sh)
        self._shards = live

    def _fold_retired(self, sh: _Shard) -> None:
        with sh.lock:
            counters, timers = sh.counters, sh.timers
            sh.counters, sh.timers = {}, {}
        with self._retired.lock:
            for name, value in counters.items():
                self._retired.counters[name] = self._retired.counters.get(name, 0) + value
            for name, ent in timers.items():
                agg = self._retired.timers.get(name)
                if agg is None:
                    self._retired.timers[name] = ent
                else:
                    agg["calls"] += ent["calls"]
                    agg["total_s"] += ent["total_s"]
                    agg["min_s"] = min(agg["min_s"], ent["min_s"])
                    agg["max_s"] = max(agg["max_s"], ent["max_s"])
                    agg["samples"].extend(ent["samples"])  # maxlen caps it

    def _all_shards(self) -> list:
        with self._lock:
            return list(self._shards) + [self._retired]

    def inc(self, name: str, n: int = 1) -> None:
        sh = self._shard()
        with sh.lock:
            sh.counters[name] = sh.counters.get(name, 0) + int(n)

    def observe(self, name: str, seconds: float) -> None:
        seconds = float(seconds)
        sh = self._shard()
        with sh.lock:
            ent = sh.timers.get(name)
            if ent is None:
                ent = {
                    "calls": 0,
                    "total_s": 0.0,
                    "min_s": float("inf"),
                    "max_s": 0.0,
                    "samples": collections.deque(maxlen=_SAMPLE_CAP),
                }
                sh.timers[name] = ent
            ent["calls"] += 1
            ent["total_s"] += seconds
            ent["min_s"] = min(ent["min_s"], seconds)
            ent["max_s"] = max(ent["max_s"], seconds)
            ent["samples"].append(seconds)

    def clear(self) -> None:
        for sh in self._all_shards():
            with sh.lock:
                sh.counters.clear()
                sh.timers.clear()

    def counters(self) -> Dict[str, int]:
        merged: Dict[str, int] = {}
        for sh in self._all_shards():
            with sh.lock:
                items = list(sh.counters.items())
            for name, value in items:
                merged[name] = merged.get(name, 0) + value
        return merged

    def timer_table(self) -> Dict[str, Dict[str, float]]:
        """{name: {calls, total_s, best_s, mean_s, max_s, p50_s, p95_s,
        p99_s}}.

        Merged across thread shards: calls/totals are exact sums,
        min/max exact aggregates, and the percentiles come from the
        union of the per-shard sample reservoirs (each bounded by
        ``_SAMPLE_CAP``)."""
        merged: Dict[str, dict] = {}
        for sh in self._all_shards():
            with sh.lock:
                items = [(k, dict(v), list(v["samples"])) for k, v in sh.timers.items()]
            for name, ent, samples in items:
                agg = merged.get(name)
                if agg is None:
                    agg = {
                        "calls": 0, "total_s": 0.0,
                        "min_s": float("inf"), "max_s": 0.0, "samples": [],
                    }
                    merged[name] = agg
                agg["calls"] += ent["calls"]
                agg["total_s"] += ent["total_s"]
                agg["min_s"] = min(agg["min_s"], ent["min_s"])
                agg["max_s"] = max(agg["max_s"], ent["max_s"])
                agg["samples"].extend(samples)
        table = {}
        for name, agg in merged.items():
            calls = agg["calls"]
            samples = sorted(agg["samples"])
            table[name] = {
                "calls": calls,
                "total_s": agg["total_s"],
                "best_s": agg["min_s"] if calls else 0.0,
                "mean_s": agg["total_s"] / calls if calls else 0.0,
                "max_s": agg["max_s"],
                "p50_s": _percentile(samples, 0.50),
                "p95_s": _percentile(samples, 0.95),
                "p99_s": _percentile(samples, 0.99),
            }
        return table

    def snapshot(self) -> Dict[str, Any]:
        return {"counters": self.counters(), "timers": self.timer_table()}


# ------------------------------------------------------------------ #
# module-level singleton + enable switch                             #
# ------------------------------------------------------------------ #
_REGISTRY = Registry()

# hooks read this attribute directly (one dict lookup + attribute read):
# the whole disabled-path cost of the instrumentation
_ENABLED: bool = _env_truthy(_gates.get("HEAT_TPU_TELEMETRY"))

# record() nesting is per thread: names join with '/'
_NESTING = threading.local()


def enable() -> None:
    """Turn telemetry collection on (also via ``HEAT_TPU_TELEMETRY=1``).
    Span tracing at its default ``HEAT_TPU_TRACE=auto`` follows this
    switch (an explicit ``0``/``1`` pins it independently)."""
    global _ENABLED
    _ENABLED = True
    from . import tracing as _tracing

    _tracing._on_telemetry_switch(True)


def disable() -> None:
    """Turn telemetry collection off. Collected data is kept until
    ``reset()``."""
    global _ENABLED
    _ENABLED = False
    from . import tracing as _tracing

    _tracing._on_telemetry_switch(False)


def enabled() -> bool:
    return _ENABLED


def inc(name: str, n: int = 1) -> None:
    """Increment counter ``name`` by ``n`` (no-op when disabled). Byte
    accounting uses the same mechanism under a ``<name>.bytes`` key."""
    if _ENABLED:
        _REGISTRY.inc(name, n)


def observe(name: str, seconds: float) -> None:
    """Record one duration sample for timer ``name`` (no-op when
    disabled)."""
    if _ENABLED:
        _REGISTRY.observe(name, seconds)


@contextlib.contextmanager
def record(name: str, **fields) -> Iterator[None]:
    """Time the enclosed block under ``name`` and emit a structured event.

    Nested ``record`` blocks compose their names with ``/``::

        with ht.telemetry.record("ingest"):
            with ht.telemetry.record("load"):   # timer key "ingest/load"
                ...

    ``fields`` become attributes of the emitted event (host-side values
    only — the block may run jax work, the fields must not hold tracers).
    A no-op (plain passthrough) when telemetry is disabled.
    """
    if not _ENABLED:
        yield
        return
    stack = getattr(_NESTING, "stack", None)
    if stack is None:
        stack = _NESTING.stack = []
    qualified = "/".join(stack + [name])
    stack.append(name)
    t0 = time.perf_counter()
    try:
        yield
    finally:
        dt = time.perf_counter() - t0
        stack.pop()
        _REGISTRY.observe(qualified, dt)
        from . import events as _events

        _events.emit("record", name=qualified, seconds=round(dt, 9), **fields)


def snapshot() -> Dict[str, Any]:
    """Point-in-time copy of all counters and timer statistics, plus
    the event ring's health metadata (``events.capacity/buffered/
    dropped`` — a non-zero ``dropped`` means the event buffer is a
    tail, not complete history)."""
    from . import events as _events

    snap = _REGISTRY.snapshot()
    snap["events"] = _events.meta()
    return snap


def report(as_json: bool = False) -> Any:
    """Snapshot of counters + timer stats (p50/p95 included); with
    ``as_json`` a JSON string."""
    snap = snapshot()
    return json.dumps(snap) if as_json else snap


def reset() -> None:
    """Clear all counters, timers and buffered events."""
    _REGISTRY.clear()
    from . import events as _events

    _events.clear()


_PROM_SANITIZE = re.compile(r"[^a-zA-Z0-9_]")


def _prom_name(name: str, suffix: str = "") -> str:
    return "heat_tpu_" + _PROM_SANITIZE.sub("_", name) + suffix


def _prom_num(v: float) -> str:
    # prometheus text format takes any Go-parseable float; plain repr of
    # a python int/float qualifies
    return repr(int(v)) if isinstance(v, bool) or v == int(v) else repr(float(v))


def prometheus_text() -> str:
    """Prometheus text-format exposition of the registry: every counter
    as a ``_total`` counter, every timer as a summary (``quantile``
    labels from the bounded reservoir plus ``_sum``/``_count``), the
    event ring's health, and — when the serving layer is loaded — one
    gauge set per live dispatcher (queue depth, request/batch/shed
    tallies, latency quantiles) labeled by dispatcher name. Pure text,
    no HTTP: mount it behind whatever exposition endpoint the
    deployment already runs (``scripts/metrics_dump.py`` is the CLI
    form)."""
    snap = _REGISTRY.snapshot()
    lines = []
    for name, value in sorted(snap["counters"].items()):
        m = _prom_name(name, "_total")
        lines.append(f"# TYPE {m} counter")
        lines.append(f"{m} {_prom_num(value)}")
    for name, st in sorted(snap["timers"].items()):
        m = _prom_name(name, "_seconds")
        lines.append(f"# TYPE {m} summary")
        for q, key in (("0.5", "p50_s"), ("0.95", "p95_s"), ("0.99", "p99_s")):
            lines.append(f'{m}{{quantile="{q}"}} {_prom_num(st[key])}')
        lines.append(f"{m}_sum {_prom_num(st['total_s'])}")
        lines.append(f"{m}_count {_prom_num(st['calls'])}")
    from . import events as _events

    emeta = _events.meta()
    lines.append("# TYPE heat_tpu_events_dropped_total counter")
    lines.append(f"heat_tpu_events_dropped_total {emeta['dropped']}")
    lines.append("# TYPE heat_tpu_events_buffered gauge")
    lines.append(f"heat_tpu_events_buffered {emeta['buffered']}")
    # flight-recorder health: spans already export
    # their drop count via events; the always-on flight ring gets the
    # same treatment so a scraped process shows when its post-mortem
    # tail stopped being complete
    from . import tracing as _tracing

    lines.append("# TYPE heat_tpu_flight_dropped_total counter")
    lines.append(f"heat_tpu_flight_dropped_total {_tracing.flight_dropped()}")
    # live dispatcher gauges — only when the serving layer is already
    # loaded (never import jax into a light metrics process)
    import sys

    disp_mod = sys.modules.get("heat_tpu.serving.dispatcher")
    if disp_mod is not None:
        rows = [
            (_PROM_SANITIZE.sub("_", d.name), d.stats())
            for d in disp_mod.live_dispatchers()
        ]
        if rows:
            # all samples of one metric grouped under its TYPE line
            for g in (
                "requests", "batches", "rejected", "shed", "rows",
                "padded_rows", "queue_depth_max",
            ):
                lines.append(f"# TYPE heat_tpu_serving_{g} gauge")
                for name, stats in rows:
                    lines.append(
                        'heat_tpu_serving_%s{dispatcher="%s"} %s'
                        % (g, name, _prom_num(stats[g]))
                    )
            lines.append("# TYPE heat_tpu_serving_latency_seconds summary")
            for name, stats in rows:
                for q, key in (("0.5", "p50_s"), ("0.95", "p95_s"), ("0.99", "p99_s")):
                    lines.append(
                        'heat_tpu_serving_latency_seconds{dispatcher="%s",quantile="%s"} %s'
                        % (name, q, _prom_num(stats[key]))
                    )
    return "\n".join(lines) + "\n"


def export_jsonl(path: str) -> int:
    """Write the registry + event buffer as JSON lines (one object per
    counter/timer/event) to ``path``; returns the number of lines."""
    snap = snapshot()
    from . import events as _events

    lines = []
    for name, value in sorted(snap["counters"].items()):
        lines.append({"kind": "counter", "name": name, "value": value})
    for name, stats in sorted(snap["timers"].items()):
        lines.append({"kind": "timer", "name": name, **stats})
    for ev in _events.snapshot():
        lines.append({"kind": "event", **ev})
    with open(path, "w") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")
    return len(lines)
