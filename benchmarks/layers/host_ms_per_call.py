"""Wall time of one call on the host (``bench.call`` + ``bench.wait``, mean
over the traced calls) less the device time it took: what dispatch, the
Python wrappers and the wait's wake-up add. Layer: dispatch."""

from benchmarks import trace as T


def reduce(events, run):
    per_call = T.device_ns_per_call(events)
    if per_call is None:
        return None
    wall = sum(e - s for s, e in T.spans(events, T.CALL) + T.spans(events, T.WAIT))
    return (wall / T.n_calls(events) - per_call) / 1e6
