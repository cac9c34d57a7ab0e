"""The least time the chip's MXU could take for one call (``least_flops`` of
the configuration: the Householder count of a thin QR with ``Q``, ``4 m n^2 -
4/3 n^3``, whatever implements it, over the peak bf16 flops/s of the
``device_kind`` in ``peaks.json``) as a share of the device time the call
took. The configuration states f32: the MXU runs an f32 product as one, three
or six bf16 passes, so a program whose products all take six cannot read over
16.7 %, one of three 33.3 %, and a Cholesky-QR with a second pass does twice
the Householder count besides. The number shows the passes paid for, by
design. ``run`` carries no shape, so ``m`` and ``n`` are read from the
configuration's file (the chip's rows: ``rows_per_chip``). Layer: kernels."""

import json
import os

from benchmarks import trace as T

CONFIG = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs", "qr-northstar.json")


def least_flops(cfg: dict) -> float:
    """Flops one chip needs for a thin QR of its ``rows_per_chip x cols``
    rows with ``Q`` formed, by the Householder count."""
    m, n = cfg["rows_per_chip"], cfg["cols"]
    return 4.0 * m * n * n - 4.0 / 3.0 * n ** 3


def reduce(events, run):
    per_call = T.device_ns_per_call(events)
    if per_call is None or not (run.get("peak") or {}).get("bf16_flops_per_s"):
        return None
    with open(CONFIG) as f:
        least_s = least_flops(json.load(f)) / run["peak"]["bf16_flops_per_s"]
    return 100.0 * least_s / (per_call / 1e9)
