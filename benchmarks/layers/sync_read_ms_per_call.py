"""Host time under the program's ``ht.sync.read`` / ``ht.sync.wait`` spans
(the library itself brings a device value to the host, or waits for a
program) between a ``bench.call``'s start and the end of its ``bench.wait``,
mean over the traced calls: the part of the wait side that is heat_tpu's own.
0.0 where the call reads nothing back. Layer: dispatch."""

from benchmarks import hostside as H


def reduce(events, run):
    ns = H.sync_ns_per_call(events)
    return None if ns is None else ns / 1e6
