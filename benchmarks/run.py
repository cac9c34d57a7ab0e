#!/usr/bin/env python3
"""heat_tpu's benchmark: one cell, one run, one JSON line.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is one entry of ``BENCHMARK.json``'s ``workloads``. Everything that
belongs to one cell, configuration, op or metric sits in a file of its own,
found by name (``README.md`` beside this file says how to add one):

    workloads/<cell>.json      the traffic: who calls, how, what a traced run traces
    configs/<config>.json      the deployment: sizes, data, guarantees, and its ``op``
    ops/<op>.py                make / call / reference / check / work_bytes / least_bytes
    end_to_end/<metric>.py     compute(run) -> value            (read in a --trace 0 run)
    layers/<metric>.py         reduce(events, run) -> value     (read in a --trace 1 run)

This file knows no cell, op or metric by name. It sets the process up,
makes the data from ``--seed`` on the device, takes the plain reference,
warms the call up and checks its result (all of that, less the TPU
runtime's own start inside the first ``jax.devices()``, is ``setup_s``), then
drives the traffic for ``--seconds``: a closed loop, the next call when the
last result is ready. The last call's result is checked too. A ``--trace 1``
run traces the first calls of its window with ``jax.profiler`` and reduces
the trace before it exits.

It fails, and prints no result, where JAX's first device is not a TPU or
the device count is not the cell's ``chips``. ``--rehearse`` (the config's
toy twin on virtual CPU devices) is for building the harness; its line
says ``"platform": "cpu"`` and is never a chip result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # as near to the start of the process as Python gets

import argparse
import gc
import gzip
import importlib.util
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_DIR = os.path.join(HERE, ".trace")  # listed in .gitignore; emptied by every traced run
MAX_FAILED = 3  # calls that raise before the loop gives the window up


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``<kind>/<name>.py`` beside this file, by path: a name may hold dots
    and dashes."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        sys.exit(f"benchmarks/run.py: no {kind}/{name}.py")
    spec = importlib.util.spec_from_file_location(f"benchmarks.{kind}.{name.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def named(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    sys.exit(f"benchmarks/run.py: BENCHMARK.json has no {what} named {name!r}")


def metrics_of(entries: list, cell: str) -> list:
    """The metrics this cell reports: those with no ``workloads`` key, or
    with the cell in it."""
    return [m for m in entries if cell in m.get("workloads", [cell])]


def ready(out):
    """``out`` (arrays, DNDarrays, containers of them) once the device is done."""
    import jax

    leaves = jax.tree.leaves(out, is_leaf=lambda x: hasattr(x, "_phys"))
    jax.block_until_ready([a for a in (getattr(x, "_phys", x) for x in leaves) if isinstance(a, jax.Array)])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None, help="default: BENCHMARK.json's run_seconds")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="the config's toy twin on virtual CPU devices; never a chip result")
    ap.add_argument("--dump-events", default=None, metavar="FILE",
                    help="with --trace 1: also write the trace's event list there as gzipped JSON")
    args = ap.parse_args(argv)

    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = named(bench["workloads"], args.workload, "workload")
    traffic = load_json(os.path.join(HERE, "workloads", cell["name"] + ".json"))
    cfg = load_json(os.path.join(ROOT, named(bench["configs"], cell["config"], "configuration")["file"]))
    chips = cell["chips"]
    if (traffic["config"], traffic["chips"]) != (cell["config"], chips):
        sys.exit(f"benchmarks/run.py: workloads/{cell['name']}.json and BENCHMARK.json disagree on config or chips")
    if (traffic["loop"], traffic["callers"]) != ("closed", 1):
        sys.exit("benchmarks/run.py: the generator drives a closed loop of one caller; "
                 f"{cell['name']} asks for {traffic['loop']!r} with {traffic['callers']} callers")
    seconds = float(bench["run_seconds"] if args.seconds is None else args.seconds)

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + f" --xla_force_host_platform_device_count={chips}"
        ).strip()
        cfg = {**cfg, **cfg["toy"]}

    import jax

    t0 = time.perf_counter()
    devices = jax.devices()  # starts the TPU runtime
    runtime_start_s = time.perf_counter() - t0
    platform = devices[0].platform
    if platform != "tpu" and not args.rehearse:
        sys.exit(f"benchmarks/run.py: jax.devices()[0].platform is {platform!r}, not 'tpu'")
    if len(devices) != chips:
        sys.exit(f"benchmarks/run.py: {cell['name']} needs exactly {chips} devices, JAX found {len(devices)}")
    kind = devices[0].device_kind
    peaks = load_json(os.path.join(HERE, "peaks.json"))
    if platform == "tpu" and kind not in peaks:
        sys.exit(f"benchmarks/run.py: device_kind {kind!r} is not in benchmarks/peaks.json")

    import jax.monitoring
    from jax._src.dispatch import BACKEND_COMPILE_EVENT

    import heat_tpu as ht
    from benchmarks import trace as T

    cache_dir = ht.utils.place_compile_cache()
    # keep every program, however fast it compiled: a run after the first
    # of a checkout has to find all of them in the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(secs) if event == BACKEND_COMPILE_EVENT else None
    )

    op = load_module("ops", cfg["op"])
    finish = getattr(op, "finish", lambda state, out: None)

    def one_call():
        with jax.profiler.TraceAnnotation(T.CALL):
            out = op.call(state)
        with jax.profiler.TraceAnnotation(T.WAIT):
            ready(out)
            finish(state, out)
        return out

    # ---- set-up: data, reference, warm-up, first check ------------------
    marks = [("import_and_devices", time.perf_counter())]
    state = ready(op.make(cfg, chips, jax.random.key(args.seed % 2**32)))
    marks.append(("make", time.perf_counter()))
    ref = op.reference(state)
    marks.append(("reference", time.perf_counter()))
    out = one_call()
    marks.append(("first_call", time.perf_counter()))
    checks = {"warm_up": op.check(state, out, ref)}
    marks.append(("check", time.perf_counter()))
    tracing = bool(args.trace)
    if tracing:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # our annotations are TraceMes; Python frames only slow the host
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    gc.collect()
    compiles_before = len(compiles)

    # ---- the window: a closed loop of one caller ------------------------
    samples_ms, work_bytes, attempted, failed = [], 0, 0, 0
    traced_calls = 0
    limit = traffic["trace"]
    t_window = time.perf_counter()
    # the TPU runtime's own start is not the program's or the benchmark's to
    # shorten, and on the chip it read about 8 or 11 s in phases of minutes (PERF.md,
    # PR 24): with it in, two sets of the same code differ by more than the bound
    setup_s = t_window - T_START - runtime_start_s
    t_end = t_window
    while t_end - t_window < seconds and failed < MAX_FAILED:
        attempted += 1
        t0 = time.perf_counter()
        try:
            out = one_call()
        except Exception as e:  # the loop's boundary: a failed call is counted, not fatal
            failed += 1
            print(f"benchmarks/run.py: call {attempted} raised {type(e).__name__}: {e}", file=sys.stderr)
            t_end = time.perf_counter()
            continue
        t_end = time.perf_counter()
        samples_ms.append((t_end - t0) * 1e3)
        work_bytes += op.work_bytes(state, out)
        if tracing:
            traced_calls += 1
            if traced_calls >= limit["max_calls"] or t_end - t_window >= limit["max_seconds"]:
                jax.profiler.stop_trace()
                tracing = False
    window_s = t_end - t_window
    if tracing:
        jax.profiler.stop_trace()
    compiles_in_window = len(compiles) - compiles_before
    if not samples_ms:
        sys.exit(f"benchmarks/run.py: no call of {attempted} completed")

    # ---- after the window: last check, metrics, the line ----------------
    checks["last_call"] = op.check(state, out, ref)
    misses = [f"{when}: {m}" for when, c in checks.items() for m in c["misses"]]
    for m in misses:
        print(f"benchmarks/run.py: MISS {m}", file=sys.stderr)
    run = {
        "chips": chips, "setup_s": setup_s, "window_s": window_s,
        "samples_ms": samples_ms, "work_bytes": work_bytes,
        "least_bytes_per_call": op.least_bytes(state, out),
        "compiles_in_window": compiles_in_window, "peak": peaks.get(kind),
    }
    stats = [d.memory_stats() or {} for d in devices]
    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": max(s.get("peak_bytes_in_use", 0) for s in stats)}
    result = {"correct": not misses and not failed, "attempted": attempted, "failed": failed}
    print(json.dumps({
        "samples": len(samples_ms), "window_s": window_s, "runtime_start_s": runtime_start_s,
        "setup_parts_s": {k: t - prev for (k, t), prev in zip(marks, [T_START] + [t for _, t in marks])},
        "compiles_in_set_up": compiles_before, "compile_s_in_set_up": sum(compiles[:compiles_before]),
        "compiles_in_window": compiles_in_window,
        "compile_cache": cache_dir, "checks": {k: c["measured"] for k, c in checks.items()},
    }), flush=True)

    if args.trace:
        path = T.newest_xplane(TRACE_DIR)
        events = T.load(path) if path else []
        if args.dump_events:  # how fixtures/ are recorded; gzip keeps four chips' ops small
            with gzip.open(args.dump_events, "wt") as f:
                json.dump([list(e) for e in events], f)
        values = {m["name"]: load_module("layers", m["name"]).reduce(events, run)
                  for m in metrics_of(bench["per_layer"], cell["name"])}
        busy, win = T.busy_ns(events), T.window(events)
        device["busy_s"] = (busy or 0.0) / 1e9
        device["window_s"] = (win[1] - win[0]) / 1e9 if win else 0.0
        device["traced_calls"] = T.n_calls(events)
        breakdown = T.breakdown(events)
        if breakdown:
            result["breakdown"] = breakdown
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    else:
        values = {m["name"]: load_module("end_to_end", m["name"]).compute(run)
                  for m in metrics_of(bench["end_to_end"], cell["name"])}
    units = {m["name"]: m["unit"] for m in bench["per_layer" if args.trace else "end_to_end"]}
    # a reader that found nothing to read returns None: the metric is left out
    result["metrics"] = {k: {"value": v, "unit": units[k]} for k, v in values.items() if v is not None}
    result["device"] = device
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    # the script's own directory leads sys.path: put the checkout there
    # instead, so that `heat_tpu` and `benchmarks.*` are this checkout's and
    # benchmarks/trace.py cannot shadow the standard library's `trace`
    sys.path[0] = ROOT
    sys.exit(main())
