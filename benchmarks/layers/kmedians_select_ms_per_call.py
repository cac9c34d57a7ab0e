"""Device time of the median selection a ``KMedians.fit``: the ops named
``kmedians.select.pass`` (the counting passes of the radix selection and the
successor pass), in ms a call, mean over devices. Layer: kernels."""

from benchmarks.layers.kmedians_x_reads_per_call import busy_ms, per_call


def reduce(events, run):
    return per_call(events, busy_ms, "select")
