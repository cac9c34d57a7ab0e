"""Host time from the start of ``bench.call`` to the end of the call's first
``ht.program.launch`` (or ``compile``), mean over the traced calls: the host
work an idle chip waits for in a closed loop. Layer: dispatch."""

from benchmarks import spans as S


def reduce(events, run):
    ns = S.prelaunch_ns_per_call(events)
    return None if ns is None else ns / 1e6
