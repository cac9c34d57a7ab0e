#!/usr/bin/env python3
"""Check the yardstick itself, on the CPU, with no chip: plain ``python
benchmarks/selftest.py``.

Hand-written event lists with known answers go through every reducer in
``layers/`` and through ``trace.py``'s interval arithmetic; the percentile
rule, the end-to-end arithmetic and both ops' ``work_bytes`` /
``least_bytes`` are checked against numbers worked out by hand; a trimmed
list of events from a real chip trace (``fixtures/``) must reduce to what
that run reported; ``BENCHMARK.json`` must agree with the files it names;
and ``run.py --rehearse`` runs once for every cell (toy twin, virtual CPU
devices) and its last line must hold the keys the driver reads.
``--no-rehearse`` skips those runs (about 15 s each).
"""

from __future__ import annotations

import json
import math
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if __name__ == "__main__":
    sys.path[0] = ROOT  # see run.py: `benchmarks.trace` must not shadow the stdlib's `trace`

from benchmarks import run as harness
from benchmarks import trace as T

FAILED = []


def expect(what: str, got, want, tol: float = 1e-9) -> None:
    same = math.isclose(got, want, rel_tol=tol, abs_tol=tol) if isinstance(want, float) and got is not None else got == want
    print(("ok   " if same else "FAIL ") + f"{what}: {got!r}" + ("" if same else f", expected {want!r}"))
    if not same:
        FAILED.append(what)


def layer(name: str):
    return harness.load_module("layers", name).reduce


def dev(i, name, start, dur, line=T.OP_LINE):
    return T.Event(f"{T.DEVICE_PLANE_PREFIX}{i}", line, name, float(start), float(dur))


def host(name, start, dur):
    return T.Event(T.HOST_PLANE, "python", name, float(start), float(dur))


def intervals() -> None:
    expect("union merges overlap and touch", T.union([(5, 7), (0, 2), (1, 3), (3, 4)]), [(0, 4), (5, 7)])
    expect("length of a union", T.length([(0, 2), (1, 3), (5, 7)]), 5)
    expect("subtract", T.subtract([(0, 10)], [(2, 3), (5, 12)]), [(0, 2), (3, 5)])
    expect("overlap", T.overlap([(0, 10)], [(2, 3), (5, 12)]), 6)
    expect("clip", T.clip([(0, 4), (6, 9)], (2, 7)), [(2, 4), (6, 7)])


def one_device() -> None:
    """Two calls in a 1000 ns window. Device ops (ns): fusion.1 [100, 300),
    a while [350, 650) holding body ops [360, 460) and [500, 600), then in
    call two kernel [700, 900) overlapped by copy [850, 950). Busy = 200 +
    300 + 250 = 750; idle 25 %. A module-line event and an op before the
    window must not count."""
    ev = [
        host(T.CALL, 0, 50), host(T.WAIT, 50, 600),            # call 1: [0, 650)
        host(T.CALL, 660, 40), host(T.WAIT, 700, 300),         # call 2: [660, 1000)
        dev(0, "warmup-tail", -500, 200),
        dev(0, "module", 0, 1000, line="XLA Modules"),
        dev(0, "fusion.1", 100, 200),
        dev(0, "while.2", 350, 300), dev(0, "body.3", 360, 100), dev(0, "body.3", 500, 100),
        dev(0, "kernel.4", 700, 200), dev(0, "copy.5", 850, 100),
    ]
    run = {"least_bytes_per_call": 300, "peak": {"hbm_bytes_per_s": 1e9}, "compiles_in_window": 0}
    expect("window", T.window(ev), (0.0, 1000.0))
    expect("calls", T.n_calls(ev), 2)
    expect("busy ns", T.busy_ns(ev), 750.0)
    expect("device_idle_pct", layer("device_idle_pct")(ev, run), 25.0)
    expect("device_ms_per_call", layer("device_ms_per_call")(ev, run), 375e-6)
    # least 300 B / 1e9 B/s = 300 ns of 375 ns a call
    expect("hbm_roofline_pct", layer("hbm_roofline_pct")(ev, run), 80.0)
    # host wall 650 + 340 = 990 ns over 2 calls, less 375 ns of device
    expect("host_ms_per_call", layer("host_ms_per_call")(ev, run), (990 / 2 - 375) * 1e-6)
    expect("compiles_in_window", layer("compiles_in_window")(ev, run), 0)
    expect("collective_exposed_pct without collectives", layer("collective_exposed_pct")(ev, run), 0.0)
    expect("leaves leave the while out", sorted(e.name for e in T.leaves(T.device_ops(ev)["/device:TPU:0"])),
           ["body.3", "body.3", "copy.5", "fusion.1", "kernel.4"])
    st = T.self_times(T.device_ops(ev)["/device:TPU:0"])
    # the while keeps the 100 ns its body leaves; the copy, innermost from
    # 850, takes the overlap from the kernel
    expect("self times", st, {"fusion.1": 200.0, "while.2": 100.0, "body.3": 200.0, "kernel.4": 150.0, "copy.5": 100.0})
    bd = T.breakdown(ev)
    expect("breakdown: top op", bd["device_ops"][0], ["fusion.1", 200e-9])
    # gaps: [0,100) in call 1 (50 ns in bench.call, 50 in bench.wait: the
    # first of equals), [300,350) and [650,700) (10 between, 40 in call 2's bench.call), [950,1000)
    expect("breakdown: gaps", bd["idle_gaps"],
           [[T.CALL, 100e-9], [T.WAIT, 50e-9], [T.CALL, 50e-9], [T.WAIT, 50e-9]])
    expect("no device events: reducers return nothing",
           [layer(n)(ev[:4], run) for n in ("device_idle_pct", "device_ms_per_call", "hbm_roofline_pct",
                                            "host_ms_per_call", "collective_exposed_pct")], [None] * 5)
    expect("no annotations: no window", T.window(ev[4:]), None)


def two_devices() -> None:
    """One call in a 1000 ns window on two devices. Device 0: compute
    [0, 400), an all-reduce [300, 600) (hidden under compute until 400:
    200 ns exposed), a collective-permute-start [700, 800) alone (100 ns
    exposed). Device 1: compute [0, 900) with an all-gather [100, 200)
    wholly hidden, and on the async line an all-to-all from its start at
    850 to its done at 1000: 100 ns exposed after the compute ends; an
    async copy must not count. Exposed: 30 % and 10 %, mean 20 %; busy 700
    and 900, mean 800."""
    ev = [
        host(T.CALL, 0, 100), host(T.WAIT, 100, 900),
        dev(0, "fusion.1", 0, 400), dev(0, "all-reduce.2", 300, 300), dev(0, "%collective-permute-start.3", 700, 100),
        dev(1, "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 0, 900), dev(1, "all-gather.7", 100, 100),
        dev(1, "%all-to-all-start.9 = (f32[8]{0}) all-to-all-start(f32[8]{0} %x)", 850, 150, line=T.ASYNC_LINE),
        dev(1, "%copy-start.4 = (f32[8]{0}) copy-start(f32[8]{0} %y)", 900, 100, line=T.ASYNC_LINE),
    ]
    run = {"least_bytes_per_call": 400, "peak": {"hbm_bytes_per_s": 1e9}}
    expect("two devices: busy is the mean", T.busy_ns(ev), 800.0)
    expect("two devices: device_idle_pct", layer("device_idle_pct")(ev, run), 20.0)
    expect("two devices: collective_exposed_pct", layer("collective_exposed_pct")(ev, run), 20.0)
    expect("two devices: hbm_roofline_pct", layer("hbm_roofline_pct")(ev, run), 50.0)
    expect("short name of an instruction",
           T.short('%run.1 = (f32[32,8192]{1,0:T(8,128)S(1)}, f32[8,128]{1,0}) custom-call(f32[32,131072]{1,0} %pad.0), '
                   'custom_call_target="tpu_custom_call", operand_layout_constraints={f32[32,131072]{1,0}}'),
           "%run.1 (f32[32,8192], f32[8,128]) custom-call tpu_custom_call")
    expect("short name of a plain name", T.short("fusion.1"), "fusion.1")


def end_to_end() -> None:
    e2e = lambda name: harness.load_module("end_to_end", name)
    p95 = e2e("call_p95_ms")
    expect("tail rank, 1000 samples", p95.tail_rank(1000), 949)
    expect("tail rank, 200 samples: ten beyond", p95.tail_rank(200), 189)
    expect("tail rank, 50 samples: ten beyond", p95.tail_rank(50), 39)
    expect("tail rank, 11 samples", p95.tail_rank(11), 0)
    expect("p95 of 1..1000", p95.compute({"samples_ms": list(range(1000, 0, -1))}), 950)
    expect("p95 of 1..50 falls back", p95.compute({"samples_ms": list(range(1, 51))}), 40)
    expect("p95 of 5 samples is the median", p95.compute({"samples_ms": [5, 1, 4, 2, 3]}), 3)
    expect("p95 of 19 samples is the median, not the 9th", p95.compute({"samples_ms": list(range(1, 20))}), 10)
    expect("p95 of 21 samples: ten beyond", p95.compute({"samples_ms": list(range(1, 22))}), 11)
    expect("p50", e2e("call_p50_ms").compute({"samples_ms": [4.0, 1.0, 3.0, 2.0]}), 2.5)
    expect("input_gbps_chip", e2e("input_gbps_chip").compute({"work_bytes": 8e9, "window_s": 2.0, "chips": 4}), 1.0)
    expect("setup_s", e2e("setup_s").compute({"setup_s": 12.5}), 12.5)


def op_bytes() -> None:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    hsvd = harness.load_module("ops", "hsvd_rank")
    a_bytes = 4 * 65536 * 8192 * 4
    state = {"cfg": {}, "chips": 4, "bytes": a_bytes}
    expect("hsvd work_bytes: all of A", hsvd.work_bytes(state, None), a_bytes)
    expect("hsvd least_bytes: two passes over the chip's rows", hsvd.least_bytes(state, None), 2 * 65536 * 8192 * 4)
    expect("hsvd least_bytes, one-view: one pass",
           hsvd.least_bytes({**state, "cfg": {"single_pass": True}}, None), 65536 * 8192 * 4)
    km = harness.load_module("ops", "kmeans_fit")
    x_bytes = 15_625_000 * 64 * 4
    state = {"chips": 1, "bytes": x_bytes}
    expect("kmeans work_bytes: X once an iteration", km.work_bytes(state, {"n_iter": 10}), 10 * x_bytes)
    expect("kmeans least_bytes: and once for the labels", km.least_bytes(state, {"n_iter": 10}), 11 * x_bytes)


def fixture() -> None:
    """A real chip trace, trimmed: must reduce to what that run printed."""
    for name in sorted(os.listdir(os.path.join(HERE, "fixtures"))):
        if not name.endswith(".json"):
            continue
        fx = harness.load_json(os.path.join(HERE, "fixtures", name))
        ev = [T.Event(*e) for e in fx["events"]]
        for metric, want in fx["expected"].items():
            expect(f"fixture {name}: {metric}", layer(metric)(ev, fx["run"]), float(want), tol=1e-6)


def files() -> None:
    """BENCHMARK.json against the files it names."""
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    e2e = {m["name"] for m in bench["end_to_end"]}
    for cfg in bench["configs"]:
        doc = harness.load_json(os.path.join(ROOT, cfg["file"]))
        expect(f"config {cfg['name']}: file names it, its source and what was reduced",
               (doc["name"], doc["source"], sorted(doc["reduced"])), (cfg["name"], cfg["source"], sorted(cfg["reduced"])))
        expect(f"config {cfg['name']}: op file", os.path.isfile(os.path.join(HERE, "ops", doc["op"] + ".py")), True)
    for cell in bench["workloads"]:
        doc = harness.load_json(os.path.join(HERE, "workloads", cell["name"] + ".json"))
        expect(f"cell {cell['name']}: workload file agrees",
               (doc["name"], doc["config"], doc["chips"]), (cell["name"], cell["config"], cell["chips"]))
    for kind, key in (("end_to_end", "end_to_end"), ("layers", "per_layer")):
        for m in bench[key]:
            expect(f"{key} {m['name']}: reader file", os.path.isfile(os.path.join(HERE, kind, m["name"] + ".py")), True)
    for m in bench["per_layer"]:
        expect(f"per_layer {m['name']}: moves an end-to-end metric", m["moves"] in e2e, True)
    expect("peaks.json: v5e", harness.load_json(os.path.join(HERE, "peaks.json"))["TPU v5 lite"]["hbm_bytes_per_s"], 819e9)


def rehearse() -> None:
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    env = {k: v for k, v in os.environ.items() if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    for cell in bench["workloads"]:
        for trace in (0, 1):
            cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload", cell["name"], "--seed", "2147483659",
                   "--seconds", "1", "--trace", str(trace), "--rehearse"]
            done = subprocess.run(cmd, capture_output=True, text=True, env=env, timeout=600)
            what = f"rehearse {cell['name']} --trace {trace}"
            expect(f"{what}: exit code", done.returncode, 0)
            if done.returncode:
                print(done.stderr[-2000:])
                continue
            line = json.loads(done.stdout.strip().splitlines()[-1])
            expect(f"{what}: keys", sorted(set(line) - {"breakdown"}), ["attempted", "correct", "device", "failed", "metrics"])
            expect(f"{what}: correct, none failed, never a chip result",
                   (line["correct"], line["failed"], line["device"]["platform"], line["device"]["count"]),
                   (True, 0, "cpu", cell["chips"]))
            if trace == 0:
                want = sorted(m["name"] for m in harness.metrics_of(bench["end_to_end"], cell["name"]))
                expect(f"{what}: every end-to-end metric, none 0",
                       sorted(k for k, v in line["metrics"].items() if v["value"] > 0), want)
            else:
                expect(f"{what}: no compile in the window", line["metrics"]["compiles_in_window"]["value"], 0)
    # without --rehearse there is no TPU here: an error and no result
    cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload", bench["workloads"][0]["name"], "--seconds", "1"]
    done = subprocess.run(cmd, capture_output=True, text=True, env={**env, "JAX_PLATFORMS": "cpu"}, timeout=600)
    expect("no TPU: non-zero exit and no result", (done.returncode != 0, done.stdout.strip()), (True, ""))


def main() -> int:
    for part in (intervals, one_device, two_devices, end_to_end, op_bytes, fixture, files):
        part()
    if "--no-rehearse" not in sys.argv[1:]:
        rehearse()
    print(f"selftest: {'FAILED: ' + ', '.join(FAILED) if FAILED else 'all passed'}")
    return 1 if FAILED else 0


if __name__ == "__main__":
    sys.exit(main())
