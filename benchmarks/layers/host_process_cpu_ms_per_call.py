"""CPU time of all threads of the process in one cycle: the mean difference
of ``process_cpu_ns`` (read at the entry of the outermost ``ht.call.*`` span)
between consecutive calls of the traced window. A runtime that polls for the
device's completion shows the cycle's wall here, one that sleeps shows
little. ``None`` where the window resolves the mean no finer than half a
millisecond a call (``hostside.counter_per_call``: a clock that ticks in
10 ms over fewer than twenty cycles). Layer: device."""

from benchmarks import hostside as H


def reduce(events, run):
    ns = H.counter_per_call(events, "process_cpu_ns")
    return None if ns is None else ns / 1e6
