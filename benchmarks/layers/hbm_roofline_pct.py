"""The least time the chip's memory could take for one call (the fewest
bytes the algorithm's schedule reads on one chip, from the op's
``least_bytes``, over the peak bytes/s of the ``device_kind`` in
``peaks.json``) as a share of the device time the call took. Bound by
memory bandwidth: both ops stream a tall array at a few flops a byte.
Layer: kernels."""

from benchmarks import trace as T


def reduce(events, run):
    per_call = T.device_ns_per_call(events)
    if per_call is None or not run.get("least_bytes_per_call"):
        return None
    least_s = run["least_bytes_per_call"] / run["peak"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (per_call / 1e9)
