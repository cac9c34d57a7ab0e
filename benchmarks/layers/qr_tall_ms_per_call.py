"""Device time of a ``ht.linalg.qr`` call's work over the tall operand, in ms
a call, mean over devices: the self time (``trace.self_times``: each instant
to the innermost op over it) of the ops that touch an array with more rows
than the configuration has columns. The rule reads the instruction text the
trace prints: an op is tall if any shape in it (result or operand, ``f32[8192,
1024]``) has an extent over ``cols``: the matrix products over row blocks of
``A`` and ``Q``, their slices and updates, the loops that carry them. Every
other op is the ``n x n`` chain (``qr_small_ms_per_call``): Cholesky factors,
triangular inverses, the products of ``R`` factors. The two add up to
``device_ms_per_call`` where no two ops overlap. The program also names the
phases (``jax.named_scope``: ``qr.tall.*`` / ``qr.small.*``), which a trace
viewer shows; the text alone tells them apart. Where a program before PR 34
runs the call (one eager XLA QR) the rule still holds: its panel updates are
tall. Layer: kernels."""

import json
import re

from benchmarks import trace as T
from benchmarks.layers.qr_mxu_roofline_pct import CONFIG

_SHAPE = re.compile(r"\[([0-9][0-9,]*)\]")


def is_tall(text: str, cols: int) -> bool:
    return any(int(x) > cols for dims in _SHAPE.findall(text) for x in dims.split(",") if x)


def split_ms(events):
    """(tall, small) device self time in ms a call, mean over devices; None
    where no device op is in the window."""
    by_device, calls = T.device_ops(events), T.n_calls(events)
    if not by_device or not calls:
        return None
    with open(CONFIG) as f:
        cols = json.load(f)["cols"]
    tall = small = 0.0
    for ops in by_device.values():
        for name, ns in T.self_times(ops).items():
            if is_tall(name, cols):
                tall += ns
            else:
                small += ns
    scale = 1e6 * len(by_device) * calls
    return tall / scale, small / scale


def reduce(events, run):
    parts = split_ms(events)
    return None if parts is None else parts[0]
