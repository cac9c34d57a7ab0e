"""The program's own spans (``ht.*``) of a traced run, and where a call's
host time goes by them.

heat_tpu's ``observability.tracing.span`` enters a
``jax.profiler.TraceAnnotation``, so under ``run.py --trace 1`` its spans
(``ht.call.*``, ``ht.op.*``, ``ht.program.*``, ``ht.comm.*``) sit on the
``/host:CPU`` plane of the trace, on the thread and the clock of
``bench.call``. ``trace.load`` leaves them out, so ``program_spans`` reads
the XPlane that ``run.py`` has not removed yet a second time (once a
process); a list that holds ``ht.*`` events already (hand-written,
fixtures) is taken as it is. A program with no such span, as before PR 25,
gives ``[]`` and every reader here ``None``.

Every duration is a difference on one clock: host spans against host
spans, device ops against device ops. Nothing here needs JAX but that read.
"""

from __future__ import annotations

import functools
import os
from typing import Dict, List, Optional, Sequence, Tuple

from benchmarks import trace as T

TRACE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), ".trace")  # run.py's
PREFIX = "ht."
PROGRAM = "ht.program."
COMM = "ht.comm."
MISS = "ht.program.miss"
LAUNCHES = ("ht.program.launch", "ht.program.compile")
MODULE_LINE = "XLA Modules"
# the shares of a bench.call's wall, by the innermost span over each instant
WRAPPER, LAUNCH, COMMS, UNSPANNED = "wrapper", "launch", "comm", "unspanned"


@functools.lru_cache(maxsize=2)
def _read(path: str, mtime: float) -> Tuple[T.Event, ...]:
    from jax.profiler import ProfileData

    return tuple(
        T.Event(plane.name, line.name, ev.name, float(ev.start_ns), float(ev.duration_ns))
        for plane in ProfileData.from_file(path).planes
        if plane.name == T.HOST_PLANE
        for line in plane.lines
        for ev in line.events
        if ev.name.startswith(PREFIX)
    )


def program_spans(events: Sequence[T.Event]) -> List[T.Event]:
    """The ``ht.*`` host events: those of ``events`` if it holds any, else
    those of the newest XPlane under ``benchmarks/.trace``."""
    own = [e for e in events if e.plane == T.HOST_PLANE and e.name.startswith(PREFIX)]
    if own:
        return own
    path = T.newest_xplane(TRACE_DIR)
    return list(_read(path, os.path.getmtime(path))) if path else []


def calls(events: Sequence[T.Event]) -> List[Tuple[T.Event, List[T.Event]]]:
    """Every ``bench.call`` with the ``ht.*`` spans of its thread that lie
    inside it, outermost first. ``[]`` where the trace has no ``ht.*`` span."""
    own = program_spans(events)
    if not own:
        return []
    out = []
    for call in sorted((e for e in events if e.plane == T.HOST_PLANE and e.name == T.CALL), key=lambda e: e.start_ns):
        inside = [s for s in own if s.line == call.line and s.start_ns >= call.start_ns and s.end_ns <= call.end_ns]
        out.append((call, sorted(inside, key=lambda e: (e.start_ns, -e.dur_ns))))
    return out


def share_of(stack: Sequence[T.Event]) -> str:
    """Whose time an instant is, from the spans that cover it (outermost
    first): a program span anywhere makes it the launch's, else the
    innermost decides."""
    if not stack:
        return UNSPANNED
    if any(s.name.startswith(PROGRAM) for s in stack):
        return LAUNCH
    return COMMS if stack[-1].name.startswith(COMM) else WRAPPER


def shares_ns(call: T.Event, inside: Sequence[T.Event]) -> Dict[str, float]:
    """The wall of one ``bench.call`` split into the four shares: each
    instant goes to the innermost span over it (nesting on one thread), so
    the four add up to the wall."""
    total = {WRAPPER: 0.0, LAUNCH: 0.0, COMMS: 0.0, UNSPANNED: 0.0}
    stack: List[T.Event] = []
    at = call.start_ns

    def advance(to: float) -> None:
        nonlocal at
        while stack and stack[-1].end_ns <= to:
            end = stack[-1].end_ns
            total[share_of(stack)] += max(end - at, 0.0)
            at = max(at, end)
            stack.pop()
        total[share_of(stack)] += max(to - at, 0.0)
        at = max(at, to)

    for s in inside:
        advance(s.start_ns)
        stack.append(s)
    advance(call.end_ns)
    return total


def mean_shares_ns(events: Sequence[T.Event]) -> Optional[Dict[str, float]]:
    """The four shares and the wall (``"wall"``), mean a ``bench.call``."""
    per_call = calls(events)
    if not per_call:
        return None
    out = {WRAPPER: 0.0, LAUNCH: 0.0, COMMS: 0.0, UNSPANNED: 0.0, "wall": 0.0}
    for call, inside in per_call:
        for k, ns in shares_ns(call, inside).items():
            out[k] += ns / len(per_call)
        out["wall"] += call.dur_ns / len(per_call)
    return out


def launches_per_call(events: Sequence[T.Event]) -> Optional[float]:
    per_call = calls(events)
    if not per_call:
        return None
    return sum(1 for _, inside in per_call for s in inside if s.name in LAUNCHES) / len(per_call)


def prelaunch_ns_per_call(events: Sequence[T.Event]) -> Optional[float]:
    """``bench.call`` start to the end of the call's first launch (or
    compile), mean over the calls that launched."""
    waits = []
    for call, inside in calls(events):
        first = next((s for s in inside if s.name in LAUNCHES), None)
        if first is not None:
            waits.append(first.end_ns - call.start_ns)
    return sum(waits) / len(waits) if waits else None


def between_calls_ns(events: Sequence[T.Event]) -> float:
    """Mean host time from a ``bench.wait``'s end to the next
    ``bench.call``'s start (the benchmark's own loop)."""
    starts = [s for s, _ in T.spans(events, T.CALL)]
    ends = [e for _, e in T.spans(events, T.WAIT)]
    gaps = [s - e for e, s in zip(ends, starts[1:]) if s >= e]
    return sum(gaps) / len(gaps) if gaps else 0.0


def modules_per_call(events: Sequence[T.Event]) -> Optional[float]:
    """Programs run a call on the first device (``XLA Modules`` events in
    the traced window)."""
    by_device, n = T.device_ops(events, MODULE_LINE), T.n_calls(events)
    if not by_device or not n:
        return None
    return len(by_device[sorted(by_device)[0]]) / n
