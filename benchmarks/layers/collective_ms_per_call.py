"""Device time under collective ops a call: per device, the union of the
intervals in which a collective is running or in flight, hidden under
compute or not, over the calls in the traced window; mean over devices,
in ms. What the merge's exchange costs the device, beside
``collective_exposed_pct`` (the part of it nothing covers).

A collective is an op named as one, on the op line or on the async line
(which spans it from ``-start`` to ``-done``). The chip's compiler also
overlaps an all-gather with compute as ``%async-collective-start`` ...
``%async-collective-done`` on the op line itself (read by hand from the
first four-chip trace of PR 27; ``fixtures/`` keeps one): such a pair
counts from the start of its ``-start`` to the end of its ``-done``.
Layer: communication."""

import re

from benchmarks import trace as T
from benchmarks.layers.collective_exposed_pct import COLLECTIVE_OPS

_OPCODE = re.compile(r"^%?([a-z\-]+?)(-start|-done)?(?:\.\d+)?(?: |$)")
_KINDS = COLLECTIVE_OPS + ("async-collective",)


def in_flight(ops):
    """The intervals of one device's collectives: a plain one is its own
    event, a ``-start`` runs to the end of the next ``-done`` of its kind."""
    out, open_since = [], {}
    for e in sorted(ops, key=lambda e: e.start_ns):
        m = _OPCODE.match(e.name)
        if not m or not m.group(1).startswith(_KINDS):
            continue
        kind, phase = m.group(1), m.group(2)
        if phase == "-start":
            open_since.setdefault(kind, e.start_ns)
        elif phase == "-done" and kind in open_since:
            out.append((open_since.pop(kind), e.end_ns))
        out.append((e.start_ns, e.end_ns))
    return out


def reduce(events, run):
    by_device, calls = T.device_ops(events), T.n_calls(events)
    if not by_device or not calls:
        return None
    beside = T.device_ops(events, T.ASYNC_LINE)
    per_device = [T.length(in_flight(T.leaves(ops) + beside.get(plane, []))) for plane, ops in by_device.items()]
    return sum(per_device) / len(per_device) / calls / 1e6
