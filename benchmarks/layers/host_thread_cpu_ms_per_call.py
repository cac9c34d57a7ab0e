"""CPU time of the calling thread in one cycle (the call, the caller's wait,
the caller's loop): the mean difference of ``thread_cpu_ns``, which the
outermost ``ht.call.*`` span reads at entry, between consecutive calls of the
traced window. Against the cycle's wall less the device time it says whether
the thread computed or slept. ``None`` where the window resolves the mean no
finer than half a millisecond a call (``hostside.counter_per_call``: a clock
that ticks in 10 ms over fewer than twenty cycles). Layer: dispatch."""

from benchmarks import hostside as H


def reduce(events, run):
    ns = H.counter_per_call(events, "thread_cpu_ns")
    return None if ns is None else ns / 1e6
