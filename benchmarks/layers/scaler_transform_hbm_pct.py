"""The transform's share of its roofline: one read and one write of the
chip's rows of the table (two of the three reads' worth that the op's
``least_bytes`` counts) at the peak bytes/s of ``peaks.json``, over the device
time of the ops named ``scaler.transform.pass``, by the passes the window
holds. Layer: kernels."""

from benchmarks.layers.percentile_x_reads_per_call import busy_ms, one_read_s, per_call, whole


def reduce(events, run):
    name = "scaler.transform.pass"
    passes, ms, read_s = per_call(events, whole, name), per_call(events, busy_ms, name), one_read_s(run)
    if not passes or not ms or not read_s:
        return None
    return 100.0 * passes * 2 * read_s / (ms / 1e3)
