"""Reads of ``X`` one ``ht.percentile`` along the sample axis makes where it
counts: the device ops named ``percentile.select.pass`` (the first digit's
pass, the counting passes, the gathering pass, the successor pass where the
selection ends on ``X``: one op is one whole read), mean a call and a device.
1 is the floor; what a counting selection adds is what a later ``perf_opt``
drives down. The program names the ops (``heat_tpu/core/_pallas_select.py``);
where it has none, as before PR 38 or where the call sorts, there is nothing
to read. This file also holds what the four readers beside it share. Layer:
kernels."""

from benchmarks import trace as T
from benchmarks.layers.kmedians_x_reads_per_call import busy_ms, whole  # noqa: F401 (the readers beside this one)


def named(events, name):
    """Per device plane, the ops inside the traced window whose own name
    (the text before `` = ``: what follows names the operands) holds
    ``name``."""
    return {plane: [e for e in ops if name in e.name.split(" = ", 1)[0]] for plane, ops in T.device_ops(events).items()}


def per_call(events, value, name):
    """Mean over the devices of ``value(ops named name)``, over the calls;
    ``None`` where no device ran such an op."""
    by_device, calls = named(events, name), T.n_calls(events)
    if not calls or not any(by_device.values()):
        return None
    return sum(value(ops) for ops in by_device.values()) / len(by_device) / calls


def one_read_s(run):
    """Seconds one read of a chip's rows of ``X`` takes at the peak:
    ``least_bytes`` of the op is three of them (``ops/robust_scale.py``)."""
    if not run.get("least_bytes_per_call") or not run.get("peak"):
        return None
    return run["least_bytes_per_call"] / 3 / run["peak"]["hbm_bytes_per_s"]


def reduce(events, run):
    return per_call(events, whole, "percentile.select.pass")
