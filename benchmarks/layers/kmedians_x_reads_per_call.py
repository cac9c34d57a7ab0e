"""Reads of ``X`` one ``KMedians.fit`` makes: the device ops named for a pass
over ``X`` (``kmedians.assign.pass``, ``kmedians.select.pass``: one op is one
read; an op that carries both names counts once), mean a call and a device.
Six is the floor at five iterations (one an iteration, one for the labels);
what a counting selection adds is what a later ``perf_opt`` drives down.
The program names the ops (``heat_tpu/cluster/_pallas_l1.py``); where it has
none, as before PR 32, there is nothing to read. Layer: kernels."""

import re

from benchmarks import trace as T

_PASS = re.compile(r"kmedians\.(assign|select)\.pass")


def passes(events, phase=None):
    """Per device plane, the pass ops inside the traced window; with a
    ``phase`` (``"assign"`` / ``"select"``) those that carry its name. An
    op is named by the text before `` = ``: what follows names its operands,
    and the op that adds a pass's counts up is no pass."""
    want = f"kmedians.{phase}.pass" if phase else None
    out = {}
    for plane, ops in T.device_ops(events).items():
        own = [(e, e.name.split(" = ", 1)[0]) for e in ops]
        out[plane] = [e for e, name in own if (want in name if want else _PASS.search(name))]
    return out


def whole(ops):
    """The passes the window holds, less the slivers at its ends: the
    device's clock runs some tens of microseconds ahead of the host's, so
    the window cuts the first pass of its first call short and keeps the
    start of the first pass after its last (``fixtures/``). A read is an op
    of which at least half the median pass is left."""
    if not ops:
        return 0
    lengths = sorted(e.dur_ns for e in ops)
    return sum(1 for e in ops if e.dur_ns >= lengths[len(lengths) // 2] / 2)


def busy_ms(ops):
    return T.length((e.start_ns, e.end_ns) for e in ops) / 1e6


def per_call(events, value, phase=None):
    """Mean over the devices of ``value(ops)``, over the calls; ``None``
    where no device ran a pass."""
    by_device, calls = passes(events, phase), T.n_calls(events)
    if not calls or not any(by_device.values()):
        return None
    return sum(value(ops) for ops in by_device.values()) / len(by_device) / calls


def reduce(events, run):
    return per_call(events, whole)
