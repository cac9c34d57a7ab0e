"""Device idle a call (the traced window less the device's busy time,
over the calls) less ``host_prelaunch_ms_per_call`` less the mean host time
from a ``bench.wait``'s end to the next ``bench.call``'s start: the idle
that follows the first launch. On a one-program call that is the wake-up
(device done to ``block_until_ready`` returning, and ``finish``); on a
many-program call also the gaps between its programs. Each term is a
difference on one clock. Layer: device."""

from benchmarks import spans as S
from benchmarks import trace as T


def reduce(events, run):
    busy, win, calls = T.busy_ns(events), T.window(events), T.n_calls(events)
    prelaunch = S.prelaunch_ns_per_call(events)
    if busy is None or win is None or not calls or prelaunch is None:
        return None
    idle = ((win[1] - win[0]) - busy) / calls
    return (idle - prelaunch - S.between_calls_ns(events)) / 1e6
