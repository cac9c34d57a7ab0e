"""Over the cycles of the traced window (one start of an outermost
``ht.call.*`` span to the next; the last call's to the end of its
``bench.wait``), the sum of what each takes over 1.25 x their median: the time
of late calls, which moves ``input_gbps_chip`` and the traced means while
``call_p50_ms`` stands. Layer: dispatch."""

from benchmarks import hostside as H


def reduce(events, run):
    ns = H.late_ns_in_window(events)
    return None if ns is None else ns / 1e6
