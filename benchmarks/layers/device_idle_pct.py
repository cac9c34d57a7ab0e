"""Share of the traced window in which no op ran on the device, mean over
the cell's devices. Layer: device."""

from benchmarks import trace as T


def reduce(events, run):
    busy, win = T.busy_ns(events), T.window(events)
    if busy is None or win is None:
        return None
    return 100.0 * (1.0 - busy / (win[1] - win[0]))
