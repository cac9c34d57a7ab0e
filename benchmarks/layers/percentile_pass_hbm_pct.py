"""The selection's passes over ``X`` as a share of their roofline: over the
ops named ``percentile.select.pass``, reads x the time one read of the chip's
rows of ``X`` takes at the peak bytes/s of ``peaks.json``, over their summed
device time, as ``kmedians_pass_hbm_pct`` reckons it. Bound by memory
bandwidth by definition: a pass cannot take less than one read of ``X``; how
far the compares of a counting pass and the folding of the gathering pass hold
it above that is what this share says. Layer: kernels."""

from benchmarks.layers.percentile_x_reads_per_call import busy_ms, one_read_s, per_call, whole


def reduce(events, run):
    name = "percentile.select.pass"
    reads, ms, read_s = per_call(events, whole, name), per_call(events, busy_ms, name), one_read_s(run)
    if not reads or not ms or not read_s:
        return None
    return 100.0 * reads * read_s / (ms / 1e3)
