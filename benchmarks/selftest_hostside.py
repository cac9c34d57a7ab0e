#!/usr/bin/env python3
"""Check the readers of the wait side (``hostside.py`` and its four metrics),
on the CPU, with no chip: plain ``python benchmarks/selftest_hostside.py``
(``selftest.py`` and ``selftest_spans.py`` check the rest of the yardstick).

A hand-written window of five calls with known answers goes through the four
reducers: one cycle is late; the second call holds an ``ht.sync.read`` inside
its ``bench.wait`` and one lies between two calls, on another thread and
outside any call, which must not count; the counters on the outermost
``ht.call.*`` spans step by known amounts, and an inner span's do not count.
The same window with its counters taken away must read ``None`` for the two
counter metrics and the same for the other two; a counter that ticks in 10 ms
must read ``None`` over six cycles (1.7 ms a call is all they resolve) and a
number over forty; spans without ``ht.sync.*`` must read ``0.0``; a window
with no ``ht.*`` span ``None`` everywhere. Then,
unless ``--no-rehearse`` is given, ``run.py --rehearse --trace 1`` runs for
``kmeans-northstar.fit10`` (its ``finish`` reads ``n_iter_``) and
``hsvd-northstar.1chip`` (reads nothing back): both lines must hold the four,
``sync_read_ms_per_call`` over zero in the first and exactly 0.0 in the
second (about 15 s each).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if __name__ == "__main__":
    sys.path[0] = ROOT  # see run.py: `benchmarks.trace` must not shadow the stdlib's `trace`

from benchmarks import hostside as H
from benchmarks import selftest as base
from benchmarks import trace as T

expect, layer, host = base.expect, base.layer, H.host

METRICS = ("sync_read_ms_per_call", "host_thread_cpu_ms_per_call", "host_process_cpu_ms_per_call",
           "late_call_ms_in_window")
COUNTER_METRICS = METRICS[1:3]


def five_calls() -> list:
    """Calls start every 1000 ns but the fourth, which starts 3000 after the
    third (a late one): the outermost spans start at 10, 1010, 2010, 5010,
    6010, so the cycles are 1000, 1000, 3000, 1000 and, the last call's to
    the end of its ``bench.wait``, 890: the median 1000 and the late time
    3000 - 1250 = 1750. Each call is ``bench.call`` [t, t + 300)
    and ``bench.wait`` [t + 300, t + 900). Call 2 reads for 200 ns inside its
    wait, call 3 waits 100 ns inside its ``bench.call`` and reads 50 ns in a
    span that runs 30 ns past its ``bench.wait`` (20 count): 320 over five
    calls. Between calls 3 and 4 a read of 500 ns under no call, and in call 1
    one on another thread: neither counts. ``thread_cpu_ns`` steps 400, 400,
    1200, 400 (mean 600), ``process_cpu_ns`` 1000 x 4; the inner span's
    counter would read otherwise."""
    ev = []
    starts = (0, 1000, 2000, 5000, 6000)
    cpu = (100, 500, 900, 2100, 2500)
    for i, t in enumerate(starts):
        ev += [
            host(T.CALL, t, 300), host(T.WAIT, t + 300, 600),
            host("ht.call.kmeans.fit", t + 10, 280, thread_cpu_ns=cpu[i], process_cpu_ns=1000 * i),
            host("ht.call.kmeans.program", t + 20, 200, thread_cpu_ns=999999),
            host("ht.program.launch", t + 30, 150),
        ]
    ev += [
        host("ht.sync.read", 1400, 200),                      # call 2, inside its bench.wait
        host("ht.sync.wait", 2100, 100),                      # call 3, inside its bench.call
        host("ht.sync.read", 2880, 50),                       # call 3: 20 ns before its bench.wait ends
        host("ht.sync.read", 3500, 500),                      # between the calls: not a call's
        host("ht.sync.read", 400, 300, line="worker"),        # another thread: not the caller's
        host("PjRtBuffer::Await", 350, 500), host("TfrtEvent", 5400, 100, line="runtime/17"),
    ]
    return ev


def known_answers() -> None:
    ev, run = five_calls(), {}
    expect("calls, each with its wait", [(c.start_ns, w.end_ns) for c, w in H.calls(ev)],
           [(float(t), float(t + 900)) for t in (0, 1000, 2000, 5000, 6000)])
    expect("entries: the outermost span of each call", [e.start_ns for e in H.entries(ev)],
           [10.0, 1010.0, 2010.0, 5010.0, 6010.0])
    expect("sync_read_ms_per_call", layer("sync_read_ms_per_call")(ev, run), (200 + 100 + 20) / 5 * 1e-6)
    expect("host_thread_cpu_ms_per_call", layer("host_thread_cpu_ms_per_call")(ev, run), 600e-6)
    expect("host_process_cpu_ms_per_call", layer("host_process_cpu_ms_per_call")(ev, run), 1000e-6)
    expect("late_call_ms_in_window: 3000 less 1.25 x 1000", layer("late_call_ms_in_window")(ev, run), 1750e-6)
    rows, late = H.lines_per_call(ev)
    expect("the script: a head and one line a cycle, the last call's too", len(rows), 6)
    expect("the script: the late cycle is marked", [r.split("\t")[-1] for r in rows[1:]], ["", "", "late", "", ""])
    expect("the script: the late call with its wait", [(i, c.start_ns, w.end_ns) for i, c, w in late], [(2, 2000.0, 2900.0)])
    expect("census of that wait alone: nothing of the runtime's in it", H.census(ev, only=late[0][2]), {})
    long_last = [e._replace(dur_ns=2600.0) if e.name == T.WAIT and e.start_ns == 6300 else e for e in ev]
    expect("late_call_ms_in_window: a long last call counts (2890 and 3000 over 1250)",
           layer("late_call_ms_in_window")(long_last, run), (1640 + 1750) * 1e-6)
    expect("the script: call 2's line", rows[2].split("\t")[:6], ["1", "0.001", "0.000", "0.001", "0.000", "0.000ms"])
    found = H.census(ev, device_done=[700.0])
    expect("census: the runtime's event in call 1's wait, apart at the device's end",
           {g: [(n, t, c) for n, t, c, _ in rows_] for g, rows_ in found.items()},
           {"in wait, device busy": [("PjRtBuffer::Await", "caller", 1)],
            "in wait, device done": [("PjRtBuffer::Await", "caller", 1)],
            "in wait": [("TfrtEvent", "runtime", 1)]})


def nothing_to_read() -> None:
    ev = five_calls()
    bare = [e._replace(stats={}) for e in ev]                 # the parent of PR 36: spans, no counters
    for name in COUNTER_METRICS:
        expect(f"{name}: None with no counter argument", layer(name)(bare, {}), None)
    expect("sync_read_ms_per_call: the same without counters", layer("sync_read_ms_per_call")(bare, {}), 64e-6)
    expect("late_call_ms_in_window: the same without counters", layer("late_call_ms_in_window")(bare, {}), 1750e-6)
    no_sync = [e for e in ev if e.name not in H.SYNC]
    expect("sync_read_ms_per_call: 0.0 with spans and no ht.sync.*", layer("sync_read_ms_per_call")(no_sync, {}), 0.0)
    one_call = [e for e in ev if e.start_ns < 1000 and e.line == "python"]
    expect("late_call_ms_in_window: None with one call", layer("late_call_ms_in_window")(one_call, {}), None)
    two_calls = [e for e in ev if e.start_ns < 2000 and e.line == "python"]
    expect("late_call_ms_in_window: 0.0 with two calls", layer("late_call_ms_in_window")(two_calls, {}), 0.0)
    expect("host_thread_cpu_ms_per_call: None with one call", layer("host_thread_cpu_ms_per_call")(one_call, {}), None)
    steady = [e for e in ev if e.start_ns < 3000]
    expect("late_call_ms_in_window: 0.0 in a steady window", layer("late_call_ms_in_window")(steady, {}), 0.0)
    # no ht.* span at all (a program before PR 25) and nothing under benchmarks/.trace
    none = [e for e in ev if not e.name.startswith(H.PREFIX)]
    if T.newest_xplane(H.TRACE_DIR) is None:
        for name in METRICS:
            expect(f"{name}: None with no ht.* span", layer(name)(none, {}), None)


def ticking(calls: int, ticks) -> list:
    """``calls`` calls a millisecond apart whose two counters stand on
    multiples of 10 ms, as the chip machines' kernel counts it: the counter
    steps one tick before each call whose index is in ``ticks``."""
    ev, cpu = [], 230_000_000
    for i in range(calls):
        cpu += 10_000_000 * (i in ticks)
        t = 1_000_000 * i
        ev += [host(T.CALL, t, 300_000), host(T.WAIT, t + 300_000, 600_000),
               host("ht.call.qr", t + 10, 280_000, thread_cpu_ns=cpu, process_cpu_ns=cpu + 70_000_000)]
    return ev


def coarse_counters() -> None:
    """What a window resolves: its tick over its cycles. Six cycles of a
    10 ms tick resolve 1.67 ms a call, as large as what is read: ``None``,
    whether the window held a tick or not. Forty resolve 0.25 ms: a number."""
    for name in COUNTER_METRICS:
        expect(f"{name}: None where a tick is 1.67 ms a call", layer(name)(ticking(7, (2, 5)), {}), None)
        expect(f"{name}: None there with no tick in the window too", layer(name)(ticking(7, ()), {}), None)
        expect(f"{name}: five ticks over forty cycles", layer(name)(ticking(41, (3, 9, 17, 30, 36)), {}), 50.0 / 40)
        expect(f"{name}: 0.0 with no tick over forty cycles", layer(name)(ticking(41, ()), {}), 0.0)


def rehearse() -> None:
    env = {k: v for k, v in os.environ.items() if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    for cell, reads in (("kmeans-northstar.fit10", True), ("hsvd-northstar.1chip", False)):
        cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload", cell, "--seed", "2147483693",
               "--seconds", "1", "--trace", "1", "--rehearse"]
        done = subprocess.run(cmd, capture_output=True, text=True, env=env, timeout=600)
        what = f"rehearse {cell} --trace 1"
        expect(f"{what}: exit code", done.returncode, 0)
        if done.returncode:
            print(done.stderr[-2000:])
            continue
        got = {k: v["value"] for k, v in json.loads(done.stdout.strip().splitlines()[-1])["metrics"].items()}
        expect(f"{what}: the four metrics of the wait side", sorted(set(METRICS) - set(got)), [])
        sync = got.get("sync_read_ms_per_call")
        if reads:
            expect(f"{what}: finish reads n_iter_ under ht.sync.read", sync is not None and sync > 0, True)
        else:
            expect(f"{what}: the call reads nothing back", sync, 0.0)


def main() -> int:
    known_answers()
    nothing_to_read()
    coarse_counters()
    if "--no-rehearse" not in sys.argv[1:]:
        rehearse()
    print(f"selftest_hostside: {'FAILED: ' + ', '.join(base.FAILED) if base.FAILED else 'all passed'}")
    return 1 if base.FAILED else 0


if __name__ == "__main__":
    sys.exit(main())
