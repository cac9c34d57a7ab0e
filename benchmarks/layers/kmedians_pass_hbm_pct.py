"""The new kernels' share of their roofline: over the ops that read ``X``
(``kmedians.assign.pass``, ``kmedians.select.pass``), reads x the bytes of one
read / the peak bytes/s of ``peaks.json``, over their summed device time.
Bound by memory bandwidth by definition: a pass cannot take less than one read
of ``X``; how far the VPU's and the MXU's work holds a pass above that is what
this share says. ``run`` carries no configuration, so the bytes of one read are
``least_bytes_per_call`` over the assignment passes a call (one an iteration
and one for the labels, the op's own count). Layer: kernels."""

from benchmarks.layers.kmedians_x_reads_per_call import busy_ms, per_call, whole


def reduce(events, run):
    reads, assigns, ms = per_call(events, whole), per_call(events, whole, "assign"), per_call(events, busy_ms)
    if not reads or not assigns or not ms or not run.get("least_bytes_per_call"):
        return None
    one_read_s = run["least_bytes_per_call"] / assigns / run["peak"]["hbm_bytes_per_s"]
    return 100.0 * reads * one_read_s / (ms / 1e3)
