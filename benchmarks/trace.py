"""From a profiler trace to a plain list of events, and interval arithmetic.

``load(path)`` turns an ``.xplane.pb`` (read with ``jax.profiler.ProfileData``,
nothing but JAX) into a list of :class:`Event`. Everything else here works on
that list and needs no JAX, so ``selftest.py`` feeds it hand-written events.
Every reducer in ``layers/`` gets the same list.

What a TPU trace looks like (read by hand from the first chip runs of PR 24;
``fixtures/`` keeps a trimmed one): one plane per chip named
``/device:TPU:<i>``. Its line ``XLA Ops`` holds one event per executed HLO
op, named by the instruction's whole text (``%fusion.75 = f32[512,25]{...}
fusion(...), kind=kOutput, ...``) and nested where an op has a body (a
``while`` spans the ops of its iterations). ``Async XLA Ops`` holds what
runs beside them from its ``-start`` to its ``-done`` (copies, collectives).
``XLA Modules`` holds one event per program run. The host's
``jax.profiler.TraceAnnotation`` spans (``bench.call``, ``bench.wait``) sit
on a thread line of the plane ``/host:CPU``, on the same clock.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

DEVICE_PLANE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OP_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
CALL, WAIT = "bench.call", "bench.wait"
BETWEEN = "between calls"

Interval = Tuple[float, float]


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def newest_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load(path: str) -> List[Event]:
    """Every event of the device planes and every annotation of ours on the
    host plane. Other host events (the runtime's own threads) are left out:
    no reducer reads them and they are most of the file."""
    from jax.profiler import ProfileData

    events: List[Event] = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        if not device and plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if device or ev.name in (CALL, WAIT):
                    events.append(Event(plane.name, line.name, ev.name, float(ev.start_ns), float(ev.duration_ns)))
    return events


# --------------------------------------------------------------------- #
# intervals                                                              #
# --------------------------------------------------------------------- #
def union(intervals: Iterable[Interval]) -> List[Interval]:
    """The same set of instants as disjoint, sorted intervals."""
    out: List[Interval] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def length(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in union(intervals))


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals if min(e, hi) > max(s, lo)]


def subtract(a: Iterable[Interval], b: Iterable[Interval]) -> List[Interval]:
    """The instants of ``a`` that are in no interval of ``b``."""
    out: List[Interval] = []
    cover = union(b)
    for s, e in union(a):
        at = s
        for cs, ce in cover:
            if ce <= at:
                continue
            if cs >= e:
                break
            if cs > at:
                out.append((at, cs))
            at = max(at, ce)
        if at < e:
            out.append((at, e))
    return out


def overlap(a: Iterable[Interval], b: Iterable[Interval]) -> float:
    a = union(a)
    return length(a) - length(subtract(a, b))


# --------------------------------------------------------------------- #
# what the reducers ask of a trace                                       #
# --------------------------------------------------------------------- #
def spans(events: Sequence[Event], name: str) -> List[Interval]:
    """The host annotation ``name``, in order of start."""
    return sorted((e.start_ns, e.end_ns) for e in events if e.plane == HOST_PLANE and e.name == name)


def window(events: Sequence[Event]) -> Optional[Interval]:
    """The traced window: first ``bench.call`` start to last ``bench.wait``
    end. Device events outside it (the warm-up's tail) are not the loop's."""
    calls, waits = spans(events, CALL), spans(events, WAIT)
    if not calls or not waits:
        return None
    return calls[0][0], max(e for _, e in waits)


def n_calls(events: Sequence[Event]) -> int:
    return len(spans(events, CALL))


def device_ops(events: Sequence[Event], line: str = OP_LINE) -> Dict[str, List[Event]]:
    """Per device plane, the events of ``line`` inside the traced window,
    clipped to it."""
    win = window(events)
    out: Dict[str, List[Event]] = {}
    if win is None:
        return out
    for e in events:
        if not e.plane.startswith(DEVICE_PLANE_PREFIX) or e.line != line:
            continue
        s, t = max(e.start_ns, win[0]), min(e.end_ns, win[1])
        if t > s:
            out.setdefault(e.plane, []).append(e._replace(start_ns=s, dur_ns=t - s))
    return out


def busy_ns(events: Sequence[Event]) -> Optional[float]:
    """Nanoseconds in which an op ran on the device, mean over the devices
    that ran any. ``None`` where no device op is in the window."""
    per_device = [length((e.start_ns, e.end_ns) for e in ops) for ops in device_ops(events).values()]
    return sum(per_device) / len(per_device) if per_device else None


def device_ns_per_call(events: Sequence[Event]) -> Optional[float]:
    """Device busy time over the calls in the traced window."""
    busy, calls = busy_ns(events), n_calls(events)
    return busy / calls if busy is not None and calls else None


CONTAINERS = ("while", "conditional", "call")


def leaves(ops: Sequence[Event]) -> List[Event]:
    """The ops that are work themselves: an HLO control-flow op (``while``,
    ``conditional``, ``call``) that spans the ops of its body is left out.
    Any other op that spans another stays: the two overlap."""
    ordered = sorted(ops, key=lambda e: (e.start_ns, -e.dur_ns))
    out = []
    for i, e in enumerate(ordered):
        nxt = ordered[i + 1] if i + 1 < len(ordered) else None
        holds_next = nxt is not None and nxt.start_ns < e.end_ns and nxt.end_ns <= e.end_ns
        if not (holds_next and e.name.lstrip("%").startswith(CONTAINERS)):
            out.append(e)
    return out


def self_times(ops: Sequence[Event]) -> Dict[str, float]:
    """Nanoseconds by op name, each instant given to the innermost op that
    covers it, so a parent is charged only what its children leave."""
    ordered = sorted(ops, key=lambda e: (e.start_ns, -e.dur_ns))
    total: Dict[str, float] = {}
    stack: List[list] = []  # [event, instant up to which it has been charged]

    def close(upto: float) -> None:
        while stack and stack[-1][0].end_ns <= upto:
            ev, at = stack.pop()
            total[ev.name] = total.get(ev.name, 0.0) + max(ev.end_ns - at, 0.0)
            if stack:
                stack[-1][1] = max(stack[-1][1], ev.end_ns)

    for e in ordered:
        close(e.start_ns)
        if stack:
            parent, at = stack[-1]
            total[parent.name] = total.get(parent.name, 0.0) + max(e.start_ns - at, 0.0)
            stack[-1][1] = max(at, e.start_ns)
        stack.append([e, e.start_ns])
    close(float("inf"))
    return total


_INSTRUCTION = re.compile(r"^(%[\w.\-]+) = (.*?) ([\w\-]+)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}|/\*.*?\*/")  # layouts and /*index=5*/ comments
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short(name: str, limit: int = 120) -> str:
    """An op's name, result shape and opcode from the instruction text the
    trace prints: ``%convert.3 bf16[131072,8192] convert``."""
    m = _INSTRUCTION.match(name)
    if not m:
        return name[:limit]
    target = _TARGET.search(name)
    text = f"{m.group(1)} {_LAYOUT.sub('', m.group(2))} {m.group(3)}" + (f" {target.group(1)}" if target else "")
    return text if len(text) <= limit else text[: limit - 3] + "..."


def breakdown(events: Sequence[Event], top_ops: int = 10, top_gaps: int = 5) -> Optional[dict]:
    """The device ops with most (self) time, summed over devices, and the
    longest idle gaps of the first device, each labelled by what the host
    was in for most of it."""
    by_device = device_ops(events)
    win = window(events)
    if not by_device or win is None:
        return None
    op_ns: Dict[str, float] = {}
    for ops in by_device.values():
        for name, ns in self_times(ops).items():
            op_ns[name] = op_ns.get(name, 0.0) + ns
    first = by_device[sorted(by_device)[0]]
    gaps = subtract([win], ((e.start_ns, e.end_ns) for e in first))
    host = {CALL: spans(events, CALL), WAIT: spans(events, WAIT)}

    def label(gap: Interval) -> str:
        inside = {k: overlap([gap], v) for k, v in host.items()}
        inside[BETWEEN] = (gap[1] - gap[0]) - length(clip(host[CALL] + host[WAIT], gap))
        return max(inside, key=inside.get)

    return {
        "device_ops": [[short(n), ns / 1e9] for n, ns in sorted(op_ns.items(), key=lambda kv: -kv[1])[:top_ops]],
        "idle_gaps": [[label(g), (g[1] - g[0]) / 1e9] for g in sorted(gaps, key=lambda g: g[0] - g[1])[:top_gaps]],
    }
