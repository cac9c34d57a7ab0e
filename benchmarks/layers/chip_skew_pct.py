"""How unevenly the cell's devices were busy over the traced window: the
longest busy time less the shortest, over the mean, in percent. The call
ends when the last device does, so this is what a straggler costs. Nothing
to read on one device. Layer: device."""

from benchmarks import trace as T


def reduce(events, run):
    busy = [T.length((e.start_ns, e.end_ns) for e in ops) for ops in T.device_ops(events).values()]
    if len(busy) < 2 or not sum(busy):
        return None
    return 100.0 * (max(busy) - min(busy)) / (sum(busy) / len(busy))
