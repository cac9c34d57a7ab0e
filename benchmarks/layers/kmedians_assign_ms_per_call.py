"""Device time of the L1 assignment a ``KMedians.fit``: the ops named
``kmedians.assign.pass`` (one an iteration and one for the labels; k x d
``|x - c|`` terms a row on the VPU, no matmul form), in ms a call, mean over
devices. Layer: kernels."""

from benchmarks.layers.kmedians_x_reads_per_call import busy_ms, per_call


def reduce(events, run):
    return per_call(events, busy_ms, "assign")
