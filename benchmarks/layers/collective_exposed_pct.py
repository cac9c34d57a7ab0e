"""Share of the traced window in which a collective op runs on a device and
no other op does, mean over devices. A collective is an event of the op
line or of the async line (which spans it from ``-start`` to ``-done``)
named as one; the other ops are those of the op line, less the control-flow
ops that only span their bodies. Layer: communication."""

from benchmarks import trace as T

# observability.hlo.COLLECTIVE_OPS, copied: the yardstick does not follow
# later changes there
COLLECTIVE_OPS = ("all-gather", "all-reduce", "all-to-all", "collective-permute", "reduce-scatter")


def is_collective(name: str) -> bool:
    return name.lstrip("%").startswith(COLLECTIVE_OPS)


def reduce(events, run):
    by_device, win = T.device_ops(events), T.window(events)
    if not by_device or win is None:
        return None
    shares = []
    beside = T.device_ops(events, T.ASYNC_LINE)
    for plane, ops in by_device.items():
        work = T.leaves(ops)
        coll = [(e.start_ns, e.end_ns) for e in work + beside.get(plane, []) if is_collective(e.name)]
        rest = [(e.start_ns, e.end_ns) for e in work if not is_collective(e.name)]
        shares.append(T.length(T.subtract(coll, rest)) / (win[1] - win[0]))
    return 100.0 * sum(shares) / len(shares)
