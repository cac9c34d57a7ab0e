"""Device time of a ``ht.linalg.qr`` call's ``n x n`` chain, in ms a call,
mean over devices: the self time of every op that touches no array with an
extent over the configuration's ``cols`` (Cholesky factors, triangular
inverses, products of ``R`` factors, the tests that decide on the device
whether a repair step runs): latency-bound and sequential. The rule is
``qr_tall_ms_per_call``'s, which counts the rest. Layer: kernels."""

from benchmarks.layers.qr_tall_ms_per_call import split_ms


def reduce(events, run):
    parts = split_ms(events)
    return None if parts is None else parts[1]
