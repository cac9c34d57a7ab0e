#!/usr/bin/env python3
"""The wait side of a call, from inside the program: the ``ht.sync.*`` spans,
the calling thread's counters on the outermost ``ht.call.*`` span, and the
cycles of the traced window.

``spans.py`` splits the wall of ``bench.call`` by the program's spans. The
larger part of a call's host side lies after the launch has returned, in
``bench.wait``, and a clock around the call cannot say what happened there.
Three things inside the process can, and heat_tpu emits them since PR 36:

- ``ht.sync.read`` / ``ht.sync.wait``: the library itself brings a device
  value to the host (``n_iter_``, ``DNDarray.item()``, hSVD's spectrum) or
  waits for a program (the autotuners, the dispatcher's fence);
- the counters that ``observability.tracing.call_span`` reads at the entry of
  every outermost public call, kept by the profiler as the event's stats:
  ``thread_cpu_ns``, ``process_cpu_ns``. One entry to the next is one cycle
  (the call, the caller's wait, the caller's loop); a reader takes
  differences, nothing is computed in the program. The chip machines' kernel
  (gVisor) counts CPU time in ticks of 10 ms, so a window resolves a mean to
  one tick over its cycles and no finer; a window too short for half a
  millisecond a call reads ``None`` (``counter_per_call``; ``PERF.md``
  section 6, PR 36);
- the starts of those spans: a cycle far over the median is a late call, the
  time that moves ``input_gbps_chip`` while ``call_p50_ms`` stands.

``host_events`` reads the newest XPlane under ``benchmarks/.trace`` once a
process, as ``spans.program_spans`` does, and keeps of the ``/host:CPU`` plane
the ``ht.*`` events with their stats, every event on the line (thread) of
``bench.call`` and every event of other lines that overlaps a ``bench.wait``
(the runtime's own threads: the census below). A list that holds ``ht.*``
events already (hand-written: ``selftest_hostside.py``) is taken as it is.
Every reader returns ``None`` where the trace has no ``ht.*`` span (or, the
counter readers, no counter), and ``0.0`` where the spans are there and none
has the name. No duration crosses the host's and the device's clock.

As a script, on a trace of the benchmark that ``run.py`` did not remove
(``bench.call`` / ``bench.wait`` say where a call starts and its wait ends):

    python benchmarks/hostside.py <file.xplane.pb> [--census]

prints one line a call: the cycle's wall, the wall of ``bench.call`` and
``bench.wait``, the time under ``ht.sync.*`` and the two counter differences;
``late`` marks a cycle over 1.25 x the median. ``--census`` adds the
runtime's own host events inside ``ht.program.launch`` and inside
``bench.wait``, by name and thread, and after it the same for every late
call alone, with where the call's device ops lay in it.
"""

from __future__ import annotations

import bisect
import functools
import math
import os
import statistics
import sys
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

if __name__ == "__main__":
    # see run.py: `benchmarks.trace` must not shadow the standard library's `trace`
    sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from benchmarks import trace as T
from benchmarks.spans import PREFIX, TRACE_DIR

OUTERMOST = "ht.call."
SYNC = ("ht.sync.read", "ht.sync.wait")
LAUNCH = "ht.program.launch"
COUNTERS = ("thread_cpu_ns", "process_cpu_ns")
LATE = 1.25  # a cycle over this many medians is a late call
COARSE_NS = 0.5e6  # a window that resolves a counter's mean no finer than this a call gives no reading


class Event(NamedTuple):
    """``trace.Event`` with the annotation's arguments (the profiler's
    ``stats``): what ``trace.py``'s readers take, they take of this too."""

    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    stats: Dict[str, int] = {}

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def host(name: str, start: float, dur: float, line: str = "python", **stats: int) -> Event:
    """A hand-written host event (``selftest_hostside.py``)."""
    return Event(T.HOST_PLANE, line, name, float(start), float(dur), stats)


@functools.lru_cache(maxsize=2)
def _read(path: str, mtime: float) -> Tuple[Event, ...]:
    from jax.profiler import ProfileData

    lines: Dict[str, List[Event]] = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != T.HOST_PLANE:
            continue
        for line in plane.lines:
            kept = lines.setdefault(line.name, [])
            for ev in line.events:
                name = ev.name
                stats = {k: v for k, v in ev.stats if isinstance(v, int)} if name.startswith(OUTERMOST) else {}
                kept.append(Event(T.HOST_PLANE, line.name, name, float(ev.start_ns), float(ev.duration_ns), stats))
    callers = {e.line for evs in lines.values() for e in evs if e.name == T.CALL}
    waits = T.union((e.start_ns, e.end_ns) for evs in lines.values() for e in evs if e.name == T.WAIT)
    starts = [s for s, _ in waits]

    def in_a_wait(e: Event) -> bool:
        i = bisect.bisect_right(starts, e.end_ns) - 1
        return i >= 0 and waits[i][1] > e.start_ns and e.end_ns > waits[i][0]

    out: List[Event] = []
    for name, evs in lines.items():
        out.extend(e for e in evs if name in callers or e.name.startswith(PREFIX) or in_a_wait(e))
    return tuple(out)


def host_events(events: Sequence) -> List[Event]:
    """The host events to read: those of ``events`` if it holds an ``ht.*``
    event, else those of the newest XPlane under ``benchmarks/.trace``."""
    own = [e for e in events if e.plane == T.HOST_PLANE]
    if any(e.name.startswith(PREFIX) for e in own):
        return own
    path = T.newest_xplane(TRACE_DIR)
    return list(_read(path, os.path.getmtime(path))) if path else []


def _named(host_evs: Sequence, names) -> List:
    return sorted((e for e in host_evs if e.name in names), key=lambda e: e.start_ns)


def calls(host_evs: Sequence) -> List[Tuple[Event, Optional[Event]]]:
    """Every ``bench.call`` with the ``bench.wait`` that follows it on its
    line, before the next call (``None`` where the trace ends first)."""
    out = []
    bench = _named(host_evs, (T.CALL, T.WAIT))
    for i, e in enumerate(bench):
        if e.name == T.CALL:
            nxt = bench[i + 1] if i + 1 < len(bench) else None
            out.append((e, nxt if nxt is not None and nxt.name == T.WAIT and nxt.line == e.line else None))
    return out


def entries(host_evs: Sequence) -> List:
    """The outermost ``ht.call.*`` span of every call, in order: the first one
    inside each ``bench.call``."""
    public = [e for e in host_evs if e.name.startswith(OUTERMOST)]
    firsts = []
    for call, _ in calls(host_evs):
        inside = [e for e in public if e.line == call.line and call.start_ns <= e.start_ns and e.end_ns <= call.end_ns]
        if inside:
            firsts.append(min(inside, key=lambda e: (e.start_ns, -e.dur_ns)))
    return firsts


def sync_ns(host_evs: Sequence, line: str, window: T.Interval) -> float:
    """Time under ``ht.sync.*`` on ``line`` inside ``window`` (a union: a
    read inside a wait counts once)."""
    return T.length(T.clip(((e.start_ns, e.end_ns) for e in host_evs if e.name in SYNC and e.line == line), window))


def sync_ns_per_call(events: Sequence) -> Optional[float]:
    """Time under ``ht.sync.read`` / ``ht.sync.wait`` between a
    ``bench.call``'s start and the end of its ``bench.wait``, mean a call."""
    host_evs = host_events(events)
    per_call = calls(host_evs)
    if not per_call or not any(e.name.startswith(PREFIX) for e in host_evs):
        return None
    total = sum(sync_ns(host_evs, call.line, (call.start_ns, (wait or call).end_ns)) for call, wait in per_call)
    return total / len(per_call)


def counter_per_call(events: Sequence, counter: str) -> Optional[float]:
    """The mean difference of ``counter`` between consecutive calls' entries
    (one a cycle), with what the window resolves it to: the clocks' tick, as
    the entries' values show it (the greatest common divisor of all of them,
    both clocks: 10 ms under gVisor, a nanosecond or a microsecond on Linux),
    over the cycles. ``None`` where fewer than two entries carry the counter,
    and where that resolution is coarser than ``COARSE_NS`` a call: one tick
    more or less would then move the mean by as much as the levels it is
    read for lie apart (0.6-1.0 ms of work against 1.8 ms more). The rule
    asks what the window *can* show (tick and cycles, the same in every run
    of a cell) and not how many ticks it happened to hold (a sample: 1 to 9
    in twelve windows of one state), so a cell reports the metric in every
    run or in none."""
    stats = [getattr(e, "stats", {}) for e in entries(host_events(events))]
    values = [st[counter] for st in stats if counter in st]
    if len(values) < 2:
        return None
    cycles = len(values) - 1
    tick = math.gcd(*(v for st in stats for c, v in st.items() if c in COUNTERS))
    if tick / cycles > COARSE_NS:
        return None
    return (values[-1] - values[0]) / cycles


def cycle_ends(host_evs: Sequence) -> List[Tuple[Event, float]]:
    """Every call's entry with the instant its cycle ends: the next entry
    or, for the last call of the trace, the end of its ``bench.wait`` (a
    little short of a cycle: the caller's loop is missing. It is kept
    because a traced window ends *after* a long call more often than chance:
    the call that passes ``trace.max_seconds`` is the last)."""
    ents = entries(host_evs)
    out = [(a, b.start_ns) for a, b in zip(ents, ents[1:])]
    if ents:
        last = ents[-1]
        wait = next((w for c, w in calls(host_evs) if w is not None and c.line == last.line
                     and c.start_ns <= last.start_ns < c.end_ns), None)
        if wait is not None:
            out.append((last, wait.end_ns))
    return out


def cycles_ns(events: Sequence) -> Optional[List[float]]:
    """The cycles of the traced window (``cycle_ends``). ``None`` with fewer
    than two."""
    cycles = [end - a.start_ns for a, end in cycle_ends(host_events(events))]
    return cycles if len(cycles) > 1 else None


def late_ns_in_window(events: Sequence) -> Optional[float]:
    """Over the cycles of the traced window, the sum of what each takes over
    ``LATE`` x their median."""
    cycles = cycles_ns(events)
    if cycles is None:
        return None
    limit = LATE * statistics.median(cycles)
    return sum(c - limit for c in cycles if c > limit)


# --------------------------------------------------------------------- #
# the script: one line a call, and the census of the runtime's events    #
# --------------------------------------------------------------------- #
def lines_per_call(host_evs: Sequence) -> Tuple[List[str], List[Tuple[int, Event, Optional[Event]]]]:
    """One line a cycle: what the operator reads (``docs/API.md``); and the
    late ones as (index, ``bench.call``, ``bench.wait``)."""
    ends = cycle_ends(host_evs)
    per_call = calls(host_evs)
    ents = [a for a, _ in ends]
    limit = LATE * statistics.median(end - a.start_ns for a, end in ends) if ends else float("inf")
    head = ["call", "cycle_ms", "bench.call_ms", "bench.wait_ms", "sync_ms"] + ["d_" + c for c in COUNTERS] + [""]
    rows, late = ["\t".join(head)], []
    for i, (a, end) in enumerate(ends):
        b = ents[i + 1] if i + 1 < len(ents) else None
        call, wait = next(((c, w) for c, w in per_call if c.line == a.line and c.start_ns <= a.start_ns < c.end_ns), (None, None))
        span = (call.start_ns, (wait or call).end_ns) if call is not None else (a.start_ns, end)
        row = [str(i), f"{(end - a.start_ns) / 1e6:.3f}",
               "" if call is None else f"{call.dur_ns / 1e6:.3f}", "" if wait is None else f"{wait.dur_ns / 1e6:.3f}",
               f"{sync_ns(host_evs, a.line, span) / 1e6:.3f}"]
        for c in COUNTERS:
            if b is not None and c in a.stats and c in b.stats:
                d = b.stats[c] - a.stats[c]
                row.append(f"{d / 1e6:.3f}ms" if c.endswith("_ns") else str(d))
            else:
                row.append("")
        row.append("late" if end - a.start_ns > limit else "")
        if end - a.start_ns > limit and call is not None:
            late.append((i, call, wait))
        rows.append("\t".join(row))
    return rows, late


def census(host_evs: Sequence, device_done: Sequence[float] = (), top: int = 10,
           only: Optional[Event] = None) -> Dict[str, List[Tuple[str, str, int, float]]]:
    """The host events that are not the program's or the benchmark's, by
    name and thread, with their count and mean duration (ms): inside
    ``ht.program.launch`` (same thread), and overlapping a ``bench.wait``
    (any thread), there apart by the instant of ``device_done`` that falls in
    the wait (the end of the call's last device op, on the device's clock:
    the offset between the clocks, 0.3 ms, blurs the line between the two).
    The ``top`` by total time of each group. With ``only``, a ``bench.wait``,
    that wait alone (a late call's)."""
    foreign = [e for e in host_evs if not e.name.startswith(PREFIX) and e.name not in (T.CALL, T.WAIT)]
    launches = _named(host_evs, (LAUNCH,))
    waits = _named(host_evs, (T.WAIT,))
    caller = waits[0].line if waits else None
    if only is not None:
        launches, waits = [], [only]
    done = sorted(device_done)
    groups: Dict[str, Dict[Tuple[str, str], List[float]]] = {}

    def add(group: str, e, ns: float) -> None:
        thread = "caller" if e.line == caller else e.line.split("/")[0]
        groups.setdefault(group, {}).setdefault((e.name, thread), []).append(ns)

    for e in foreign:
        if any(l.line == e.line and l.start_ns <= e.start_ns and e.end_ns <= l.end_ns for l in launches):
            add("in launch", e, e.dur_ns)
        for w in waits:
            lo, hi = max(e.start_ns, w.start_ns), min(e.end_ns, w.end_ns)
            if hi < lo or (hi == lo and e.dur_ns):
                continue
            i = bisect.bisect_left(done, w.start_ns)
            cut = done[i] if i < len(done) and done[i] <= w.end_ns else None
            if cut is None:
                add("in wait", e, hi - lo)
                continue
            if lo < cut:
                add("in wait, device busy", e, min(hi, cut) - lo)
            if hi > cut or lo >= cut:
                add("in wait, device done", e, hi - max(lo, cut))
    out = {}
    for group, by_name in groups.items():
        ranked = sorted(by_name.items(), key=lambda kv: -sum(kv[1]))[:top]
        out[group] = [(name, thread, len(ns), sum(ns) / len(ns) / 1e6) for (name, thread), ns in ranked]
    return out


def report(path: str, with_census: bool = False) -> List[str]:
    """The script's lines for the XPlane at ``path``. With the census, every
    late call is followed by where its device ops lay in it (two instants
    across the clocks, good to their offset of 0.3 ms: a late call is late by
    tens of ms) and by the host events that overlap its ``bench.wait``."""
    host_evs = list(_read(path, os.path.getmtime(path)))
    rows, late = lines_per_call(host_evs)
    if not with_census:
        return rows
    loaded = T.load(path)
    ops = sorted(((e.start_ns, e.end_ns) for e in loaded if e.plane.startswith(T.DEVICE_PLANE_PREFIX) and e.line == T.OP_LINE))
    starts = [s_ for s_, _ in ops]

    def device_span(lo: float, hi: float) -> Optional[T.Interval]:
        inside = ops[bisect.bisect_left(starts, lo):bisect.bisect_right(starts, hi)]
        return (inside[0][0], max(e for _, e in inside)) if inside else None

    per_call = [(c, w) for c, w in calls(host_evs) if w is not None]
    done = [d[1] for d in (device_span(c.start_ns, w.end_ns) for c, w in per_call) if d is not None]

    def table(found: Dict[str, List[Tuple[str, str, int, float]]], n: int) -> None:
        for group, lines in sorted(found.items()):
            rows.append(f"# {group}: name\tthread\ta call\tmean_ms")
            rows.extend(f"{name[:80]}\t{thread}\t{count / n:.2f}\t{mean_ms:.4f}" for name, thread, count, mean_ms in lines)

    table(census(host_evs, done), max(len(per_call), 1))
    for i, call, wait in late:
        dev = device_span(call.start_ns, (wait or call).end_ns)
        where = "no device op in it" if dev is None else (
            f"first device op {(dev[0] - call.start_ns) / 1e6:.3f} ms after bench.call's start, "
            f"last one done {((wait or call).end_ns - dev[1]) / 1e6:.3f} ms before bench.wait's end")
        rows.append(f"## late call {i}: {where}")
        if wait is not None:
            table(census(host_evs, [dev[1]] if dev else [], only=wait), 1)
    return rows


def main(argv: Sequence[str]) -> int:
    paths = [a for a in argv if not a.startswith("--")]
    if len(paths) != 1:
        print(__doc__.split("As a script", 1)[1], file=sys.stderr)
        return 2
    print("\n".join(report(paths[0], with_census="--census" in argv)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
