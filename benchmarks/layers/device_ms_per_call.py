"""Device busy time in the traced window over the calls in it. Layer: kernels."""

from benchmarks import trace as T


def reduce(events, run):
    per_call = T.device_ns_per_call(events)
    return None if per_call is None else per_call / 1e6
