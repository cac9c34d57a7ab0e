#!/usr/bin/env python3
"""Check the readers of the program's own spans, on the CPU, with no chip:
plain ``python benchmarks/selftest_spans.py`` (``selftest.py`` checks the
rest of the yardstick).

A hand-written event list with ``ht.*`` spans and known answers (two calls,
nested spans, one miss, a call with two launches, a span on another thread,
stray modules on the device) goes through ``spans.py`` and every reducer in
``layers/`` that reads it; the four host shares must add up to the
``bench.call`` wall; a list with no ``ht.*`` span must give ``None``. Then
``run.py --rehearse --trace 1`` runs once for every cell: the CPU trace has a
``/host:CPU`` plane, so its line must hold the host-side metrics, with no
miss in the window and, on a one-chip cell, one launch a call.
``--no-rehearse`` skips those runs (about 15 s each).
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

from benchmarks import selftest as base
from benchmarks import spans as S
from benchmarks import trace as T

expect, layer, dev, host = base.expect, base.layer, base.dev, base.host

HOST_SIDE = ("host_prelaunch_ms_per_call", "host_wrapper_ms_per_call", "host_launch_ms_per_call",
             "host_comm_ms_per_call", "host_unspanned_pct", "launches_per_call", "program_cache_misses")
DEVICE_SIDE = ("idle_after_launch_ms_per_call", "stray_programs_per_call")


def two_calls() -> list:
    """Call 1 is [0, 1000) and misses: wrapper self time 80 + 100 + 40 + 60,
    program spans 100 + 360 (the place inside the compile is the
    compile's), comm 60 + 40 + 60, unspanned 100. Call 2 is [3100, 3900)
    and launches twice: wrapper 110 + 68 + 200, program 2 + 200 + 180,
    unspanned 40. The device runs one module in call 1 and three in call 2,
    busy 2200 + 1200 of a window of 5000."""
    return [
        host(T.CALL, 0, 1000), host(T.WAIT, 1000, 2000),
        host("ht.call.hsvd_rank", 50, 900),
        host("ht.call.hsvd.prepare", 60, 100),
        host("ht.call.hsvd.level0", 200, 500),
        host("ht.program.miss", 210, 100),
        host("ht.program.compile", 320, 360), host("ht.comm.place", 400, 50),
        host("ht.call.hsvd.wrap", 720, 220),
        host("ht.comm.place", 740, 60),
        host("ht.comm.shard", 820, 100), host("ht.comm.place", 840, 60),
        T.Event(T.HOST_PLANE, "worker", "ht.comm.place", 100.0, 100.0),   # another thread: not the call's
        host("ht.program.miss", -100, 50),                                  # before the window
        host("ht.op.unary", 3000, 50),                                      # between the calls
        host(T.CALL, 3100, 800), host(T.WAIT, 3900, 1100),
        host("ht.call.hsvd_rank", 3120, 760),
        host("ht.call.hsvd.level0", 3150, 450),
        host("ht.program.hit", 3160, 2),
        host("ht.program.launch", 3170, 200), host("ht.program.launch", 3400, 180),
        host("ht.op.matmul", 3620, 200),
        dev(0, "module.a", 300, 2200, line=S.MODULE_LINE), dev(0, "op.a", 300, 2200),
        dev(0, "module.a", 3300, 200, line=S.MODULE_LINE), dev(0, "module.b", 3550, 950, line=S.MODULE_LINE),
        dev(0, "module.stray", 3700, 50, line=S.MODULE_LINE), dev(0, "op.b", 3300, 1200),
    ]


def known_answers() -> None:
    ev = two_calls()
    per_call = S.calls(ev)
    expect("calls, and the spans inside each", [len(inside) for _, inside in per_call], [10, 6])
    first, second = (S.shares_ns(call, inside) for call, inside in per_call)
    expect("call 1 shares", first, {S.WRAPPER: 280.0, S.LAUNCH: 460.0, S.COMMS: 160.0, S.UNSPANNED: 100.0})
    expect("call 2 shares", second, {S.WRAPPER: 378.0, S.LAUNCH: 382.0, S.COMMS: 0.0, S.UNSPANNED: 40.0})
    for (call, _), shares in zip(per_call, (first, second)):
        expect(f"the four shares add up to the wall of the call at {call.start_ns:.0f}", sum(shares.values()), call.dur_ns)
    run = {}
    expect("host_wrapper_ms_per_call", layer("host_wrapper_ms_per_call")(ev, run), 329e-6)
    expect("host_launch_ms_per_call", layer("host_launch_ms_per_call")(ev, run), 421e-6)
    expect("host_comm_ms_per_call", layer("host_comm_ms_per_call")(ev, run), 80e-6)
    expect("host_unspanned_pct", layer("host_unspanned_pct")(ev, run), 100.0 * 70 / 900)
    expect("host_prelaunch_ms_per_call: to the end of the first launch or compile",
           layer("host_prelaunch_ms_per_call")(ev, run), (680 + 270) / 2 * 1e-6)
    expect("launches_per_call: the compile and the two launches", layer("launches_per_call")(ev, run), 1.5)
    expect("program_cache_misses: the one in the window", layer("program_cache_misses")(ev, run), 1)
    expect("stray_programs_per_call: four modules, three launches", layer("stray_programs_per_call")(ev, run), 0.5)
    # idle (5000 - 3400) / 2 = 800 a call, less prelaunch 475, less the loop's own 100
    expect("idle_after_launch_ms_per_call", layer("idle_after_launch_ms_per_call")(ev, run), 225e-6)


def nothing_to_read() -> None:
    """A program with no span of its own (the parent of PR 25), and a trace
    with no call: every reader returns None."""
    bare = [e for e in two_calls() if not e.name.startswith(S.PREFIX)]
    no_call = [e for e in two_calls() if e.name not in (T.CALL, T.WAIT)]
    for name in HOST_SIDE + DEVICE_SIDE:
        expect(f"{name}: None with no ht.* span", layer(name)(bare, {}), None)
        expect(f"{name}: None with no bench.call", layer(name)(no_call, {}), None)
    host_only = [e for e in two_calls() if e.plane == T.HOST_PLANE]
    for name in DEVICE_SIDE:
        expect(f"{name}: None with no device plane", layer(name)(host_only, {}), None)


def rehearse() -> None:
    bench = base.harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    env = {k: v for k, v in os.environ.items() if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    for cell in bench["workloads"]:
        cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload", cell["name"], "--seed", "2147483693",
               "--seconds", "1", "--trace", "1", "--rehearse"]
        done = subprocess.run(cmd, capture_output=True, text=True, env=env, timeout=600)
        what = f"rehearse {cell['name']} --trace 1"
        expect(f"{what}: exit code", done.returncode, 0)
        if done.returncode:
            print(done.stderr[-2000:])
            continue
        got = {k: v["value"] for k, v in json.loads(done.stdout.strip().splitlines()[-1])["metrics"].items()}
        expect(f"{what}: every host-side metric of the spans", sorted(set(HOST_SIDE) - set(got)), [])
        expect(f"{what}: no miss in the window", got.get("program_cache_misses"), 0)
        launches = got.get("launches_per_call")
        if cell["chips"] == 1:
            expect(f"{what}: one launch a call", launches, 1.0)
        else:
            expect(f"{what}: a whole number of launches a call, over one",
                   launches is not None and launches > 1 and launches == int(launches), True)


def main() -> int:
    known_answers()
    nothing_to_read()
    if "--no-rehearse" not in sys.argv[1:]:
        rehearse()
    print(f"selftest_spans: {'FAILED: ' + ', '.join(base.FAILED) if base.FAILED else 'all passed'}")
    return 1 if base.FAILED else 0


if __name__ == "__main__":
    sys.exit(main())
