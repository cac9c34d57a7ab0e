"""Device time of the scalers' transform a call: the op named
``scaler.transform.pass`` (``(x - c) / s``: one read and one write of the
table), in ms a call, mean over devices. Layer: kernels."""

from benchmarks.layers.percentile_x_reads_per_call import busy_ms, per_call


def reduce(events, run):
    return per_call(events, busy_ms, "scaler.transform.pass")
