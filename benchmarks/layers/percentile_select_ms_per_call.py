"""Device time of the counting selection one ``ht.percentile`` call runs: the
ops named ``percentile.select.*`` (``.pass``: the whole reads of ``X``;
``.candidates``: the small kernels over the keys the gathering pass keeps), in
ms a call, mean over devices. The picks of a few values between them carry no
such name and are in ``device_ms_per_call`` alone. Layer: kernels."""

from benchmarks.layers.percentile_x_reads_per_call import busy_ms, per_call


def reduce(events, run):
    return per_call(events, busy_ms, "percentile.select.")
