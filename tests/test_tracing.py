"""Span tracing & flight recorder (ISSUE 15).

The contract pinned here, four ways:

1. **Census == plan structure** — with tracing on and the program cache
   cleared, one chunked-a2a, one ring, and one staged execution each
   record exactly the span census their Schedule describes (issue/
   consume pairs == collective laps; stage_in/compute windows == the
   staging annotation's ``n_windows``), and a dispatcher run records
   one ``serving.batch`` span per batch it reports in ``stats()``.
2. **Byte identity at every gate value** — ``HEAT_TPU_TRACE`` is
   registered ``affects_programs=False``: plan canonical serializations,
   plan_ids, the AOT gate fingerprint, and the envelope gate roster are
   identical under ``0``/``1``/unset (the golden-dump sha pin in
   test_effectcheck plus the ci.sh parity leg diff the full dumps).
3. **Zero overhead at ``=0``** — the hard-off escape hatch keeps every
   probe a single module-bool read: no span is recorded, the context
   manager yields ``None``, and ``telemetry.enable()`` does NOT drag
   tracing on (an explicit ``0`` beats ``auto``-follow).
4. **Thread safety** — concurrent recorders commit every span exactly
   once with unique ids and per-thread parentage, and the module passes
   the racecheck/gatecheck analyzer clean (SL402–SL406).

Satellites ride along: Chrome-trace export validity + structural
determinism, the flight recorder's bound/tail/always-on contract,
``events.dropped`` overwrite accounting + span correlation,
``timer_table`` p99, and the Prometheus text exposition.
"""

import importlib
import json
import os
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import heat_tpu as ht

from heat_tpu.core import gates
from heat_tpu.observability import events, telemetry, tracing
from heat_tpu.redistribution import RedistSpec, executor, planner, staging

from test_suites.basic_test import TestCase, env_pin

P = len(jax.devices())
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TracingCase(TestCase):
    """Every test runs with a clean span buffer and restores the
    ambient off state (the suite's telemetry convention)."""

    def setUp(self):
        tracing.enable()
        tracing.clear()

    def tearDown(self):
        tracing.disable()
        tracing.clear()


# --------------------------------------------------------------------- #
# 1. span primitives                                                    #
# --------------------------------------------------------------------- #
class TestSpanPrimitives(TracingCase):
    def test_span_nesting_and_attrs(self):
        with tracing.span("outer", a=1) as so:
            with tracing.span("inner", b=2) as si:
                self.assertEqual(si.parent, so.id)
                self.assertEqual(tracing.current_span_id(), si.id)
        rows = tracing.spans()
        self.assertEqual([r["name"] for r in rows], ["inner", "outer"])
        inner, outer = rows
        self.assertEqual(inner["attrs"], {"b": 2})
        self.assertEqual(outer["attrs"], {"a": 1})
        self.assertEqual(inner["parent"], outer["id"])
        self.assertIsNotNone(outer["dur_s"])
        self.assertIsNone(tracing.current_span_id())

    def test_ambient_context_inherited(self):
        with tracing.context(plan_id="p1", tier="ici"):
            with tracing.span("work", tier="dcn"):
                pass
        (row,) = tracing.spans()
        # ambient attrs merge under the span's own (span wins)
        self.assertEqual(row["attrs"], {"plan_id": "p1", "tier": "dcn"})

    def test_detached_span_stays_off_the_stack(self):
        sp = tracing.start_span("batch", detached=True)
        self.assertIsNone(tracing.current_span_id())
        with tracing.span("phase", parent_id=sp.id):
            pass
        tracing.end_span(sp, status="ok")
        rows = {r["name"]: r for r in tracing.spans()}
        self.assertEqual(rows["phase"]["parent"], sp.id)
        self.assertEqual(rows["batch"]["attrs"]["status"], "ok")

    def test_add_span_retroactive(self):
        import time

        t0 = time.perf_counter()
        t1 = t0 + 0.25
        tracing.add_span("lifecycle", t0, t1, rows=3)
        (row,) = tracing.spans()
        self.assertAlmostEqual(row["dur_s"], 0.25, places=6)
        self.assertEqual(row["attrs"]["rows"], 3)

    def test_ring_bound_and_dropped(self):
        cap = tracing.capacity()
        self.assertEqual(tracing.dropped(), 0)
        for i in range(cap + 7):
            tracing.add_span("s", 0.0, 1e-9, i=i)
        self.assertEqual(len(tracing.spans()), cap)
        self.assertEqual(tracing.dropped(), 7)
        tracing.clear()
        self.assertEqual(tracing.dropped(), 0)


# --------------------------------------------------------------------- #
# 2. census == plan structure (the acceptance pins)                     #
# --------------------------------------------------------------------- #
def _lap_census(sched):
    """issue/consume span counts recorded for one traced execution of
    ``sched``, keyed by span name (plan_id-filtered)."""
    counts = {}
    for r in tracing.spans():
        attrs = r["attrs"]
        if attrs.get("plan_id") == sched.plan_id and attrs.get("traced"):
            counts[r["name"]] = counts.get(r["name"], 0) + 1
    return counts


@pytest.mark.skipif(P < 2, reason="needs a real mesh")
class TestCensusMatchesPlan(TracingCase):
    def _execute_traced(self, spec, budget):
        sched = planner.plan(spec, budget)
        oracle = np.arange(spec.size, dtype=spec.dtype).reshape(spec.gshape)
        x = ht.array(oracle, split=spec.src_split)
        executor.clear_program_cache()  # fresh trace: lap probes re-fire
        tracing.clear()
        executor.execute(self.comm, x._phys, spec, sched)
        return sched

    def test_chunked_and_ring_census(self):
        """For every multi-lap plan the tiny-budget sweep produces, the
        issue/consume span pairs recorded at trace time equal the plan's
        own collective count — the census IS the plan structure. The
        sweep covers chunked-all-to-all and (at the 8-dev mesh) the
        ppermute ring."""
        spec = RedistSpec.normalize((64, 48), "float32", 0, 1, P)
        strategies = set()
        for budget in (384, 1024, 2048):
            sched = self._execute_traced(spec, budget)
            strategies.add(sched.strategy)
            laps = sum(sched.collective_counts().values())
            census = _lap_census(sched)
            self.assertEqual(census.get("redist.issue", 0), laps, sched.strategy)
            self.assertEqual(census.get("redist.consume", 0), laps, sched.strategy)
            # the execute wrapper span carries the plan id + strategy
            execs = [
                r for r in tracing.spans()
                if r["name"] == "redist.execute"
                and r["attrs"].get("plan_id") == sched.plan_id
            ]
            self.assertEqual(len(execs), 1)
            self.assertEqual(execs[0]["attrs"]["strategy"], sched.strategy)
        if P == 8:  # the sweep is 8-dev-shaped: both gated forms appear
            self.assertIn("ring", strategies)
            self.assertIn("chunked-all-to-all", strategies)

    def test_census_cached_program_records_once(self):
        """Lap spans fire at TRACE time: re-executing a cached program
        adds an execute span but no new lap spans — the census counts
        compiles, not runs."""
        spec = RedistSpec.normalize((64, 48), "float32", 0, 1, P)
        sched = self._execute_traced(spec, 1024)
        first = _lap_census(sched)
        self.assertGreater(first.get("redist.issue", 0), 0)
        oracle = np.arange(spec.size, dtype=np.float32).reshape(spec.gshape)
        x = ht.array(oracle, split=0)
        executor.execute(self.comm, x._phys, spec, sched)
        self.assertEqual(_lap_census(sched), first)

    def test_staged_window_census(self):
        """One staged stream records exactly one stage_in + one compute
        span per window, plan_id-tagged, with real (non-traced) wall
        time and bytes on the pcie leg."""
        data = np.arange(4096 * 64, dtype=np.float32).reshape(4096, 64)
        host = staging.HostArray(data)
        slab = 256 << 10
        sched = staging.plan_staged_passes(
            host.shape, host.dtype, [{"tag": "sketch", "axis": 0}], slab=slab
        )
        wins = staging.window_extents(host.shape, host.dtype.itemsize, 0, slab)
        tracing.clear()
        seen = []
        staging.stream_windows(
            host, 0, wins, lambda k, arr, w: seen.append(int(k)),
            plan_id=sched.plan_id,
        )
        n = sched.staging["passes"][0]["n_windows"]
        self.assertEqual(len(wins), n)
        by_name = {}
        for r in tracing.spans():
            if r["attrs"].get("plan_id") == sched.plan_id:
                by_name[r["name"]] = by_name.get(r["name"], 0) + 1
        self.assertEqual(by_name.get("staging.stage_in", 0), n)
        self.assertEqual(by_name.get("staging.compute", 0), n)
        stage_in = [
            r for r in tracing.spans() if r["name"] == "staging.stage_in"
        ]
        self.assertTrue(all(r["attrs"]["tier"] == "pcie" for r in stage_in))
        self.assertTrue(all(not r["attrs"].get("traced") for r in stage_in))
        self.assertTrue(all(r["attrs"]["bytes"] > 0 for r in stage_in))

    def test_dispatcher_batch_census(self):
        """serving.batch spans == the dispatcher's own batch tally, with
        the full submit→queue→dispatch→fence→resolve lifecycle around
        them and one serving.request span per request."""
        from heat_tpu import serving as srv

        ep = srv.Endpoint(
            {8: jax.jit(lambda b: b * 2.0)}, (4,), np.float32, name="census"
        )
        with srv.Dispatcher(ep, max_queue=32, poll_s=0.001) as disp:
            futs = [disp.submit(np.ones((2, 4), np.float32)) for _ in range(6)]
            for f in futs:
                f.result(timeout=60)
            stats = disp.stats()
        by_name = {}
        for r in tracing.spans():
            by_name[r["name"]] = by_name.get(r["name"], 0) + 1
        self.assertEqual(by_name.get("serving.batch", 0), stats["batches"])
        self.assertEqual(by_name.get("serving.submit", 0), stats["requests"])
        self.assertEqual(by_name.get("serving.request", 0), stats["requests"])
        self.assertEqual(by_name.get("serving.queue", 0), stats["requests"])
        for phase in ("serving.dispatch", "serving.fence", "serving.resolve"):
            self.assertEqual(by_name.get(phase, 0), stats["batches"], phase)
        # phase spans parent to their batch span
        batches = {
            r["id"] for r in tracing.spans() if r["name"] == "serving.batch"
        }
        for r in tracing.spans():
            if r["name"] in ("serving.dispatch", "serving.fence", "serving.resolve"):
                self.assertIn(r["parent"], batches)


# --------------------------------------------------------------------- #
# 3. byte identity + zero overhead at =0 (the escape hatch)             #
# --------------------------------------------------------------------- #
class TestGateByteIdentity(TestCase):
    def test_gate_registered_not_program_affecting(self):
        spec = gates.GATES["HEAT_TPU_TRACE"]
        self.assertFalse(spec.affects_programs)
        self.assertNotIn(
            "HEAT_TPU_TRACE", gates.program_gate_roster().split(",")
        )

    def test_plans_and_aot_stamps_identical_both_ways(self):
        """plan canonical bytes, plan_id, the AOT gate fingerprint, and
        the envelope gate roster must not move at any gate value (the
        ci.sh parity leg diffs the full golden dumps on top)."""
        from heat_tpu.serving import aot_cache

        spec = RedistSpec.normalize((1000, 250000), "float32", 0, 1, 8)
        got = {}
        for mode in ("0", "1", None):
            with env_pin(tracing.TRACE_ENV, mode):
                sched = planner.plan(spec, 256 << 20, topology="flat")
                got[mode] = (
                    sched.plan_id,
                    sched.canonical_json(),
                    gates.aot_fingerprint(),
                    aot_cache._envelope_stamps()["gate_roster"],
                )
        self.assertEqual(got["0"], got["1"])
        self.assertEqual(got["0"], got[None])

    def test_zero_records_nothing_and_beats_telemetry_follow(self):
        was_tel = telemetry.enabled()
        tracing.clear()
        try:
            with env_pin(tracing.TRACE_ENV, "0"):
                tracing.disable()
                # auto-follow must NOT engage under an explicit 0
                telemetry.enable()
                self.assertFalse(tracing.enabled())
                self.assertIsNone(tracing.start_span("x"))
                tracing.end_span(None)  # no-op by contract
                with tracing.span("y") as sp:
                    self.assertIsNone(sp)
                tracing.add_span("z", 0.0, 1.0)
                self.assertEqual(tracing.spans(), [])
        finally:
            telemetry.disable() if not was_tel else telemetry.enable()
            tracing.disable()
            tracing.clear()

    def test_auto_follows_telemetry_switch(self):
        was_tel = telemetry.enabled()
        try:
            with env_pin(tracing.TRACE_ENV, None):
                tracing.disable()
                telemetry.enable()
                self.assertTrue(tracing.enabled())
                telemetry.disable()
                self.assertFalse(tracing.enabled())
        finally:
            telemetry.disable() if not was_tel else telemetry.enable()
            tracing.disable()
            tracing.clear()

    @pytest.mark.skipif(P < 2, reason="needs a real mesh")
    def test_execution_off_records_nothing(self):
        tracing.disable()
        tracing.clear()
        spec = RedistSpec.normalize((64, 48), "float32", 0, 1, P)
        sched = planner.plan(spec, 1024)
        oracle = np.arange(64 * 48, dtype=np.float32).reshape(64, 48)
        x = ht.array(oracle, split=0)
        executor.clear_program_cache()
        executor.execute(self.comm, x._phys, spec, sched)
        self.assertEqual(tracing.spans(), [])


# --------------------------------------------------------------------- #
# 4. thread safety + analyzer cleanliness                               #
# --------------------------------------------------------------------- #
class TestThreadedRecorders(TracingCase):
    def test_concurrent_recorders_commit_every_span_once(self):
        N, M = 8, 200  # well under capacity: nothing may drop
        errs = []

        def worker(t):
            try:
                for i in range(M):
                    with tracing.span(f"w{t}", i=i):
                        with tracing.span(f"w{t}.inner"):
                            pass
            except Exception as e:  # pragma: no cover
                errs.append(e)

        threads = [threading.Thread(target=worker, args=(t,)) for t in range(N)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        self.assertEqual(errs, [])
        rows = tracing.spans()
        self.assertEqual(len(rows), N * M * 2)
        self.assertEqual(tracing.dropped(), 0)
        ids = [r["id"] for r in rows]
        self.assertEqual(len(ids), len(set(ids)))
        # per-thread parentage: every inner span's parent is a span of
        # the SAME logical worker (stacks are thread-local)
        by_id = {r["id"]: r for r in rows}
        for r in rows:
            if r["name"].endswith(".inner"):
                parent = by_id[r["parent"]]
                self.assertEqual(parent["name"] + ".inner", r["name"])
                self.assertEqual(parent["thread"], r["thread"])

    def test_tracing_module_is_analyzer_clean(self):
        """SL402–SL406 over the tracer: the lock/ring/TLS discipline
        documented in the module must hold up to the racecheck pass,
        not just the docstring."""
        from heat_tpu.analysis import effectcheck

        rel = "heat_tpu/observability/tracing.py"
        with open(os.path.join(ROOT, rel), encoding="utf-8") as f:
            src = f.read()
        found = effectcheck.lint_source(src, rel)
        self.assertEqual([repr(f) for f in found], [], rel)


# --------------------------------------------------------------------- #
# 5. Chrome-trace export                                                #
# --------------------------------------------------------------------- #
class TestExportTrace(TracingCase):
    def _rows(self):
        with tracing.context(plan_id="pX"):
            with tracing.span("redist.execute", step="execute"):
                with tracing.span("staging.stage_in", tier="pcie", window=0):
                    pass
        with tracing.span("serving.batch", endpoint="e"):
            pass
        return tracing.spans()

    def test_export_valid_and_structurally_deterministic(self):
        rows = self._rows()
        import tempfile

        with tempfile.TemporaryDirectory() as d:
            p1, p2 = os.path.join(d, "a.json"), os.path.join(d, "b.json")
            n1 = ht.observability.export_trace(p1, span_rows=rows)
            n2 = ht.observability.export_trace(p2, span_rows=rows)
            with open(p1, "rb") as f:
                b1 = f.read()
            with open(p2, "rb") as f:
                b2 = f.read()
            self.assertEqual(b1, b2)  # same rows -> byte-identical docs
            doc = json.loads(b1)
        self.assertEqual(n1, n2)
        evs = doc["traceEvents"]
        self.assertEqual(len(evs), n1)
        phases = {e["ph"] for e in evs}
        self.assertEqual(phases, {"M", "X", "b", "e"})
        # every complete event is well-formed
        for e in evs:
            if e["ph"] == "X":
                self.assertIn("ts", e)
                self.assertIn("dur", e)
                self.assertGreaterEqual(e["dur"], 0)
                self.assertEqual(e["cat"], e["name"].split(".", 1)[0])
                self.assertIn("span_id", e["args"])
        # plan-correlated spans emit balanced async begin/end pairs
        # under one id per plan
        begins = [e for e in evs if e["ph"] == "b"]
        ends = [e for e in evs if e["ph"] == "e"]
        self.assertEqual(len(begins), 2)  # execute + stage_in carry pX
        self.assertEqual(len(ends), len(begins))
        self.assertEqual({e["id"] for e in begins}, {"pX"})
        self.assertTrue(all(e["cat"] == "plan" for e in begins + ends))
        # thread tracks are labeled
        metas = [e for e in evs if e["ph"] == "M"]
        self.assertTrue(all(e["name"] == "thread_name" for e in metas))
        self.assertEqual(doc["otherData"]["spans"], len(rows))

    def test_unfinished_spans_are_skipped(self):
        sp = tracing.start_span("never.closed", detached=True)
        self.assertIsNotNone(sp)
        rows = tracing.spans() + [
            {"id": 999, "parent": None, "name": "open", "thread": 1,
             "t0_s": 0.0, "dur_s": None, "attrs": {}}
        ]
        import tempfile

        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "t.json")
            ht.observability.export_trace(path, span_rows=rows)
            with open(path) as f:
                doc = json.load(f)
        self.assertEqual(
            [e for e in doc["traceEvents"] if e["ph"] == "X"], []
        )


# --------------------------------------------------------------------- #
# 6. flight recorder                                                    #
# --------------------------------------------------------------------- #
class TestFlightRecorder(TestCase):
    def setUp(self):
        tracing.flight_clear()

    def tearDown(self):
        tracing.flight_clear()

    def test_always_on_and_bounded(self):
        # independent of the trace gate: records land with tracing OFF
        tracing.disable()
        cap = tracing.flight_capacity()
        for i in range(cap + 10):
            tracing.flight_record("test.kind", "w", i)
        tail = tracing.flight_tail(cap + 100)
        self.assertEqual(len(tail), cap)
        self.assertEqual(tail[-1]["value"], cap + 9)
        # oldest-first, monotonic seq, fixed fields
        seqs = [r["seq"] for r in tail]
        self.assertEqual(seqs, sorted(seqs))
        self.assertEqual(
            set(tail[0]), {"seq", "t_s", "thread", "kind", "what", "value"}
        )
        self.assertEqual(len(tracing.flight_tail(8)), 8)

    def test_world_changed_error_carries_tail(self):
        from heat_tpu.resilience import elastic

        tracing.flight_record("test.before", "breadcrumb", 42)
        err = elastic.WorldChangedError("test-reason", old_size=8, new_size=4)
        kinds = [r["kind"] for r in err.flight_tail]
        self.assertIn("test.before", kinds)
        self.assertIn("world.changed", kinds)  # the error records itself
        self.assertEqual(err.flight_tail[-1]["what"], "test-reason")

    def test_dispatcher_shed_carries_tail(self):
        from heat_tpu import serving as srv
        from heat_tpu.serving.admission import ServingOverloaded

        from concurrent.futures import Future

        ep = srv.Endpoint(
            {8: jax.jit(lambda b: b)}, (4,), np.float32, name="shedtail"
        )
        disp = srv.Dispatcher(ep, max_queue=8, poll_s=0.001)
        tracing.flight_record("test.breadcrumb", "before-shed", 7)
        # a queued request swept by the shed path (never started: the
        # queue is drained directly, the worker is not involved)
        req = type("R", (), {"future": Future(), "rows": 1})()
        disp._q.put_nowait(req)
        shed = disp._fail_queued("failover")
        self.assertEqual(shed, 1)
        exc = req.future.exception()
        self.assertIsInstance(exc, ServingOverloaded)
        # the typed error carries the tail, breadcrumb included
        kinds = [r["kind"] for r in exc.flight_tail]
        self.assertIn("serving.shed", kinds)
        self.assertIn("test.breadcrumb", kinds)


# --------------------------------------------------------------------- #
# 7. events: overwrite accounting + span correlation                    #
# --------------------------------------------------------------------- #
class TestEventsRingAccounting(TestCase):
    def setUp(self):
        events.clear()

    def tearDown(self):
        events.clear()
        tracing.disable()
        tracing.clear()

    def test_overwrites_counted_and_surfaced(self):
        cap = events.capacity()
        self.assertEqual(events.dropped(), 0)
        for i in range(cap + 12):
            events.emit("test.flood", i=i)
        self.assertEqual(events.dropped(), 12)
        self.assertEqual(len(events.snapshot()), cap)
        meta = events.meta()
        self.assertEqual(meta, {"capacity": cap, "buffered": cap, "dropped": 12})
        # the ring health rides every telemetry snapshot
        self.assertEqual(telemetry.snapshot()["events"], meta)
        events.clear()
        self.assertEqual(events.dropped(), 0)

    def test_events_correlate_to_active_span(self):
        tracing.enable()
        tracing.clear()
        with tracing.span("correlated") as sp:
            events.emit("test.inside")
        events.emit("test.outside")
        inside, outside = events.snapshot()[-2:]
        self.assertEqual(inside["span"], sp.id)
        self.assertNotIn("span", outside)


# --------------------------------------------------------------------- #
# 8. p99 + Prometheus exposition                                        #
# --------------------------------------------------------------------- #
class TestTelemetryExposition(TestCase):
    def setUp(self):
        telemetry.reset()
        telemetry.enable()

    def tearDown(self):
        telemetry.disable()
        telemetry.reset()
        tracing.disable()
        tracing.clear()

    def test_timer_table_p99(self):
        for v in range(1, 101):
            telemetry.observe("test.lat", v / 1000.0)
        table = telemetry.report()["timers"]["test.lat"]
        self.assertEqual(table["calls"], 100)
        self.assertIn("p99_s", table)
        self.assertGreaterEqual(table["p99_s"], table["p95_s"])
        self.assertGreaterEqual(table["p95_s"], table["p50_s"])
        self.assertAlmostEqual(table["p99_s"], 0.099, places=3)

    def test_dispatcher_stats_p99(self):
        from heat_tpu import serving as srv

        ep = srv.Endpoint(
            {8: jax.jit(lambda b: b)}, (4,), np.float32, name="p99"
        )
        with srv.Dispatcher(ep, max_queue=8, poll_s=0.001) as disp:
            disp.call(np.ones((2, 4), np.float32), timeout=60)
            stats = disp.stats()
        for k in ("p50_s", "p95_s", "p99_s"):
            self.assertIn(k, stats)
            self.assertGreater(stats[k], 0.0)

    def test_prometheus_text_format(self):
        telemetry.inc("test.prom.count", 3)
        for v in (0.01, 0.02, 0.03):
            telemetry.observe("test.prom.lat", v)
        text = ht.observability.prometheus_text()
        lines = text.splitlines()
        self.assertIn("# TYPE heat_tpu_test_prom_count_total counter", lines)
        self.assertIn("heat_tpu_test_prom_count_total 3", lines)
        self.assertIn("# TYPE heat_tpu_test_prom_lat_seconds summary", lines)
        for q in ("0.5", "0.95", "0.99"):
            self.assertTrue(
                any(
                    l.startswith(f'heat_tpu_test_prom_lat_seconds{{quantile="{q}"}} ')
                    for l in lines
                ),
                q,
            )
        self.assertTrue(any(l.startswith("heat_tpu_test_prom_lat_seconds_sum ") for l in lines))
        self.assertIn("heat_tpu_test_prom_lat_seconds_count 3", lines)
        self.assertIn("# TYPE heat_tpu_events_dropped_total counter", lines)
        # exposition-format shape: every non-comment line is
        # "name{labels} value" with a parseable float value
        for l in lines:
            if not l or l.startswith("#"):
                continue
            name_part, _, value = l.rpartition(" ")
            self.assertTrue(name_part)
            float(value)  # must parse

    def test_prometheus_live_dispatcher_gauges(self):
        from heat_tpu import serving as srv

        ep = srv.Endpoint(
            {8: jax.jit(lambda b: b)}, (4,), np.float32, name="promgauge"
        )
        with srv.Dispatcher(ep, max_queue=8, poll_s=0.001, name="promgauge") as disp:
            disp.call(np.ones((1, 4), np.float32), timeout=60)
            text = ht.observability.prometheus_text()
            self.assertIn(
                'heat_tpu_serving_requests{dispatcher="promgauge"} 1', text
            )
            self.assertIn(
                'heat_tpu_serving_latency_seconds{dispatcher="promgauge",quantile="0.99"}',
                text,
            )
        # stopped dispatchers drop off the exposition
        text = ht.observability.prometheus_text()
        self.assertNotIn('dispatcher="promgauge"', text)

    def test_flight_dropped_counter_exported(self):
        before = tracing.flight_dropped()
        for i in range(tracing.flight_capacity() + 5):
            tracing.flight_record("test.fill", "x", i)
        self.assertGreaterEqual(tracing.flight_dropped(), before + 5)
        text = telemetry.prometheus_text()
        self.assertIn("heat_tpu_flight_dropped_total", text)


if __name__ == "__main__":
    import unittest

    unittest.main()


# --------------------------------------------------------------------- #
# 8. spans on the profiler's clock (PR 25)                              #
# --------------------------------------------------------------------- #
# Every span is a jax.profiler.TraceAnnotation too: under a profiler
# session the program's ht.call.* / ht.op.* / ht.program.* / ht.comm.*
# spans sit on the /host:CPU plane of the trace, with no gate to set.
import subprocess
import sys
import timeit


def _profiled(tmp_path, fn, stats=False):
    """Run ``fn`` under a ``jax.profiler`` session; the ``ht.*`` events of
    the host plane as (name, thread line, start_ns, end_ns), in order of
    start, outermost first; with ``stats`` also each event's arguments as
    the profiler kept them (a dict)."""
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    found = sorted(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    assert found, "the profiler wrote no XPlane"
    rows = []
    for plane in ProfileData.from_file(str(found[-1])).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("ht."):
                    row = (ev.name, line.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    rows.append(row + (dict(ev.stats),) if stats else row)
    return sorted(rows, key=lambda r: (r[2], -r[3]))


def _assert_properly_nested(rows):
    """On one thread two spans are disjoint or one holds the other."""
    for thread in {r[1] for r in rows}:
        stack = []
        for name, _, start, end in (r for r in rows if r[1] == thread):
            while stack and stack[-1][1] <= start:
                stack.pop()
            if stack:
                assert end <= stack[-1][1], f"{name} straddles the end of {stack[-1][0]}"
            stack.append((name, end))


def _inside(rows, inner, outer):
    outers = [r for r in rows if r[0] == outer]
    return all(any(o[2] <= r[2] and r[3] <= o[3] for o in outers) for r in rows if r[0] == inner)


def _hsvd_one_device():
    from heat_tpu.core.linalg import svdtools

    svdtools._sketched_single_rank_fn.cache_clear()
    a = ht.random.randn(331, 96, split=None)
    return (
        lambda: ht.linalg.hsvd_rank(a, 2, compute_sv=True),
        {"ht.call.hsvd_rank", "ht.call.hsvd.prepare", "ht.call.hsvd.level0", "ht.call.hsvd.wrap", "ht.comm.place"},
        "ht.call.hsvd_rank",
    )


def _hsvd_split():
    """The rank-budget call on a split array: one observed program."""
    from heat_tpu.core.linalg import svdtools

    svdtools._dist_rank_fn.cache_clear()
    a = ht.random.randn(8 * 331, 96, split=0)
    return (
        lambda: ht.linalg.hsvd_rank(a, 2, compute_sv=True),
        {"ht.call.hsvd_rank", "ht.call.hsvd.prepare", "ht.call.hsvd.level0", "ht.call.hsvd.wrap", "ht.comm.place"},
        "ht.call.hsvd_rank",
    )


def _hsvd_split_staged():
    """Tolerance mode on a split array keeps the staged path: level-0
    program, resplit and TSQR merge, the other factor by a matmul over A."""
    from heat_tpu.core.linalg import svdtools

    svdtools._local_svd_fn.cache_clear()
    importlib.import_module("heat_tpu.core.linalg.qr")._tsqr_fn.cache_clear()
    a = ht.random.randn(8 * 331, 96, split=0)
    return (
        lambda: ht.linalg.hsvd_rtol(a, 0.5, compute_sv=True, maxrank=2),
        {"ht.call.hsvd_rtol", "ht.call.hsvd.prepare", "ht.call.hsvd.level0", "ht.call.hsvd.merge",
         "ht.call.hsvd.wrap", "ht.call.hsvd.postprocess", "ht.op.matmul", "ht.comm.reshard", "ht.comm.shard",
         "ht.comm.place"},
        "ht.call.hsvd_rtol",
    )


def _qr_one_device():
    """``ht.linalg.qr`` on a replicated array: one observed program (``qr.local``)."""
    importlib.import_module("heat_tpu.core.linalg.qr")._local_qr_fn.cache_clear()
    a = ht.random.randn(331, 24, split=None)
    return (
        lambda: ht.linalg.qr(a),
        {"ht.call.qr", "ht.call.qr.prepare", "ht.call.qr.wrap"},
        "ht.call.qr",
    )


def _qr_split():
    """On a split array: TSQR (``qr.tsqr``), whose level 0 is the same local factorization."""
    importlib.import_module("heat_tpu.core.linalg.qr")._tsqr_fn.cache_clear()
    a = ht.random.randn(8 * 331, 24, split=0)
    return (
        lambda: ht.linalg.qr(a),
        {"ht.call.qr", "ht.call.qr.prepare", "ht.comm.place"},
        "ht.call.qr",
    )


def _kmeans_fit():
    from heat_tpu.cluster import _kcluster, kmeans

    kmeans._lloyd_step.cache_clear()
    _kcluster._fused_fit_program.cache_clear()
    x = ht.random.randn(8 * 53, 7, split=0)
    init = ht.array(np.asarray(x.numpy()[:3]))
    return (
        lambda: ht.cluster.KMeans(3, init=init, max_iter=3, tol=0.0).fit(x),
        {"ht.call.kmeans.fit", "ht.call.kmeans.init", "ht.call.kmeans.program", "ht.call.kmeans.wrap",
         "ht.comm.place", "ht.comm.shard"},
        "ht.call.kmeans.fit",
    )


def _l1_fit(est):
    def case():
        from heat_tpu.cluster import _kcluster

        _kcluster._l1_step.cache_clear()
        _kcluster._fused_fit_program.cache_clear()
        x = ht.random.randn(8 * 53, 7, split=0)
        init = ht.array(np.asarray(x.numpy()[:3]))
        root = f"ht.call.{est.lower()}.fit"
        return (
            lambda: getattr(ht.cluster, est)(3, init=init, max_iter=2).fit(x),
            {root, "ht.call.kmeans.init", "ht.call.kmeans.program", "ht.call.kmeans.wrap",
             "ht.comm.place", "ht.comm.shard"},
            root,
        )

    case.__name__ = f"_{est.lower()}_fit"
    return case


@pytest.mark.skipif(P < 2, reason="the split path needs a real mesh")
@pytest.mark.parametrize("case", [_hsvd_one_device, _hsvd_split, _hsvd_split_staged, _kmeans_fit,
                                  _l1_fit("KMedians"), _l1_fit("KMedoids"), _qr_one_device, _qr_split],
                         ids=lambda f: f.__name__.strip("_"))
def test_profiler_trace_holds_the_spans_of_the_call(case, tmp_path):
    """One call that misses and one that hits, under a profiler session and
    nothing else: every span the path runs is on the host plane, nested,
    with miss + compile in the first call and hit + launch in the second."""
    assert not tracing.enabled()
    call, names, root = case()
    rows = _profiled(tmp_path, lambda: (call(), call()))
    assert names <= {r[0] for r in rows}, sorted(names - {r[0] for r in rows})
    _assert_properly_nested(rows)
    first, second = [r for r in rows if r[0] == root]
    for name in names - {root}:
        assert _inside(rows, name, root), f"{name} outside {root}"
    in_first = [r[0] for r in rows if first[2] <= r[2] and r[3] <= first[3]]
    in_second = [r[0] for r in rows if second[2] <= r[2] and r[3] <= second[3]]
    assert "ht.program.miss" in in_first and "ht.program.compile" in in_first
    assert "ht.program.miss" not in in_second and "ht.program.compile" not in in_second
    assert "ht.program.hit" in in_second and "ht.program.launch" in in_second
    if root in ("ht.call.kmedians.fit", "ht.call.kmedoids.fit", "ht.call.qr"):
        assert in_second.count("ht.program.launch") == 1  # the whole L1 fit, and the whole QR, is one program
    if case is _qr_one_device:
        assert not [n for n in in_second if n.startswith("ht.op.")]
    if case is _hsvd_split:
        # the whole split call is ONE launch, with no op, shard or reshard beside it
        assert in_second.count("ht.program.launch") == 1
        assert not [n for n in in_second if n.startswith("ht.op.") or n in ("ht.comm.shard", "ht.comm.reshard")]
    assert tracing.spans() == []  # a profiler session does not turn the ring on


@pytest.mark.parametrize("est", ["KMedians", "KMedoids"])
def test_l1_fit_counts_its_form_and_scopes_its_phases(est):
    """Once a fit, ``<name>.step.select.xla`` (here: no TPU) or ``.pallas``
    says which form of the passes the factory chose; the device ops of the
    two phases lie under the scopes ``<name>.assign`` and ``<name>.select``,
    and the Pallas passes carry the names the benchmark's readers count."""
    from heat_tpu.cluster import _kcluster, _pallas_l1

    name = est.lower()
    x = ht.random.randn(8 * 31, 8, split=0)
    init = ht.array(np.asarray(x.numpy()[:3]))
    was = ht.telemetry.enabled()
    ht.telemetry.enable()
    try:
        before = ht.telemetry.snapshot()["counters"]
        for _ in range(2):  # the second fit is a cache hit and counts all the same
            getattr(ht.cluster, est)(3, init=init, max_iter=2).fit(x)
        after = ht.telemetry.snapshot()["counters"]
    finally:
        if not was:
            ht.telemetry.disable()
            ht.telemetry.reset()  # later tests of this file read the builders' counters from zero
    assert after.get(f"{name}.step.select.xla", 0) - before.get(f"{name}.step.select.xla", 0) == 2
    assert after.get(f"{name}.step.select.pallas", 0) == before.get(f"{name}.step.select.pallas", 0)
    assert after.get(f"{name}.step.select.gather", 0) == before.get(f"{name}.step.select.gather", 0)  # the kernels' alone
    step = _kcluster._l1_step(name, 3, (248, 8), "float32", 0, ht.MPI_WORLD.mesh, ht.MPI_WORLD.axis_name,
                              est == "KMedoids")
    a, c = jax.ShapeDtypeStruct((248, 8), jnp.float32), jax.ShapeDtypeStruct((3, 8), jnp.float32)
    text = jax.jit(step).lower(a, c).as_text(debug_info=True)
    assert f"{name}.assign" in text and f"{name}.select" in text
    passes = _pallas_l1.l1_passes(3, (256, 8), interpret=False)
    lowered = jax.jit(lambda arr, cen: _kcluster._cluster_medians(
        arr, passes.assign(arr, cen)[0], 3, cen, passes=passes)).trace(
            jax.ShapeDtypeStruct((256, 8), jnp.float32), c).jaxpr
    assert "kmedians.assign.pass" in str(lowered) and "kmedians.select.pass" in str(lowered)


def test_qr_counts_its_form_scopes_its_phases_and_is_in_the_contract():
    """``qr.local.householder`` (here: no TPU) or ``.gram`` once a call on a tall
    real array, with the builder's own counters; the Gram form's device ops
    lie under ``qr.tall.*`` / ``qr.small.*``; every name is in ``docs/API.md``."""
    qr = importlib.import_module("heat_tpu.core.linalg.qr")
    qr._local_qr_fn.cache_clear()
    a = ht.random.randn(331, 24, split=None)
    was = ht.telemetry.enabled()
    ht.telemetry.enable()
    try:
        before = ht.telemetry.snapshot()["counters"]
        for _ in range(2):
            ht.linalg.qr(a)
        ht.linalg.qr(ht.random.randn(8 * 40, 24, split=0), calc_q=False)  # level 0 of TSQR counts too
        after = ht.telemetry.snapshot()["counters"]
    finally:
        if not was:
            ht.telemetry.disable()
            ht.telemetry.reset()
    grew = {k: after.get(k, 0) - before.get(k, 0) for k in
            ("qr.local.householder", "qr.local.gram", "qr.local.miss", "qr.local.hit")}
    assert grew == {"qr.local.householder": 3, "qr.local.gram": 0, "qr.local.miss": 1, "qr.local.hit": 1}
    text = jax.jit(qr._gram_qr).lower(jax.ShapeDtypeStruct((512, 24), jnp.float32)).as_text(debug_info=True)
    scopes = ("qr.tall.gram", "qr.tall.apply", "qr.tall.finish", "qr.tall.repair", "qr.small.factor", "qr.small.repair")
    assert all(scope in text for scope in scopes)
    with open(os.path.join(ROOT, "docs", "API.md")) as f:
        contract = f.read()
    for name in ("`ht.call.qr`", "`ht.call.qr.prepare`", "`qr.local`", "`qr.local.gram`", "`qr.local.householder`",
                 *(f"`{scope}`" for scope in scopes)):
        assert name in contract, name


def test_profiler_trace_holds_predict_and_op_spans(tmp_path):
    x = ht.random.randn(8 * 53, 7, split=0)
    km = ht.cluster.KMeans(3, init=ht.array(np.asarray(x.numpy()[:3])), max_iter=2, tol=0.0).fit(x)

    def work():
        km.predict(x)
        y = ht.exp(x) + 1.0
        ht.cumsum(y, 0)
        ht.sum(y, axis=0)
        ht.transpose(y)
        ht.jit(lambda v: v * 2.0)(x)

    rows = _profiled(tmp_path, work)
    names = {r[0] for r in rows}
    assert {"ht.call.kmeans.predict", "ht.op.unary", "ht.op.binary", "ht.op.cum", "ht.op.reduce",
            "ht.op.transpose", "ht.program.miss", "ht.program.compile"} <= names, sorted(names)
    _assert_properly_nested(rows)
    for name in ("ht.program.hit", "ht.program.launch"):
        assert _inside(rows, name, "ht.call.kmeans.predict") or any(
            _inside([r for r in rows if r[0] in (name, op)], name, op)
            for op in ("ht.op.unary", "ht.op.binary", "ht.op.cum", "ht.op.reduce")
        )


def test_span_costs_little_with_no_session_and_leaves_the_ring_empty():
    """No profiler session, ``HEAT_TPU_TRACE`` unset: a span is a small
    object and a TraceMe that reads one atomic. 2 us is generous: it guards
    against the generator's return, not the chip."""
    assert not tracing.enabled()
    tracing.clear()
    span = tracing.span

    def block():
        with span("x"):
            pass

    n = 100_000
    per_use = min(timeit.repeat(block, number=n, repeat=3)) / n
    assert per_use < 2e-6, f"{per_use * 1e6:.2f} us a span"
    assert tracing.spans() == []
    with span("y") as sp:
        assert sp is None


def test_ring_still_gets_parented_spans_of_the_call():
    """``HEAT_TPU_TRACE=1`` (here ``enable()``): the spans of a public call
    land in the ring with their parents, as executor/serving spans do."""
    a = ht.random.randn(331, 96, split=None)
    ht.linalg.hsvd_rank(a, 2, compute_sv=True)  # the next call launches, it does not compile
    tracing.clear()
    tracing.enable()
    try:
        ht.linalg.hsvd_rank(a, 2, compute_sv=True)
        rows = tracing.spans()
    finally:
        tracing.disable()
        tracing.clear()
    by_name = {r["name"]: r for r in rows}
    root = by_name["ht.call.hsvd_rank"]
    assert root["parent"] is None
    assert by_name["ht.call.hsvd.level0"]["parent"] == root["id"]
    assert by_name["ht.program.launch"]["parent"] == by_name["ht.call.hsvd.level0"]["id"]
    assert by_name["ht.program.launch"]["attrs"] == {"cache": "hsvd.sketched_rank"}


def _observed_builders():
    from heat_tpu.cluster import _kcluster, kmeans
    from heat_tpu.core import _operations as ops, statistics
    from heat_tpu.core.linalg import svdtools
    from heat_tpu.preprocessing import preprocessing

    qr = importlib.import_module("heat_tpu.core.linalg.qr")  # the package exports the function under that name
    return {
        "op.binary": ops._binary_callable, "op.unary": ops._unary_callable,
        "op.reduce": ops._reduce_callable, "op.cum": ops._cum_callable,
        "hsvd.sketched_rank": svdtools._sketched_single_rank_fn,
        "hsvd.one_view_rank": svdtools._one_view_single_rank_fn,
        "hsvd.sketched": svdtools._sketched_single_fn,
        "hsvd.local_svd": svdtools._local_svd_fn,
        "hsvd.staged_rank_tail": svdtools._staged_rank_tail_fn,
        "hsvd.staged_oneview_tail": svdtools._staged_oneview_tail_fn,
        "qr.tsqr": qr._tsqr_fn,
        "qr.local": qr._local_qr_fn,
        "kmeans.lloyd_step": kmeans._lloyd_step,
        "kmeans.partial_fit_step": kmeans._partial_fit_step,
        "kcluster.fused_fit": _kcluster._fused_fit_program,
        "kcluster.predict": _kcluster._predict_program,
        "percentile.select": statistics._percentile_select_program,
        "scaler.transform": preprocessing._affine_program,
        "scaler.robust_fit_transform": preprocessing._robust_fit_transform_program,
    }


@pytest.mark.parametrize("name", [
    "op.binary", "op.unary", "op.reduce", "op.cum", "hsvd.sketched_rank", "hsvd.one_view_rank", "hsvd.sketched",
    "hsvd.local_svd", "hsvd.staged_rank_tail", "hsvd.staged_oneview_tail", "qr.tsqr", "qr.local",
    "kmeans.lloyd_step", "kmeans.partial_fit_step", "kcluster.fused_fit", "kcluster.predict",
    "percentile.select", "scaler.transform", "scaler.robust_fit_transform",
])
def test_observed_builder_keeps_the_lru_cache_surface(name):
    builder = _observed_builders()[name]
    info = builder.cache_info()
    assert {"hits", "misses", "maxsize", "currsize"} <= set(info._fields)
    assert info.maxsize >= 64
    assert callable(builder.cache_clear) and callable(builder.__wrapped__)


def test_hit_hands_back_the_proxy_made_at_the_miss_and_mesh_caches_clear():
    from heat_tpu.core import communication
    from heat_tpu.core.linalg import svdtools

    builder = svdtools._sketched_single_rank_fn
    builder.cache_clear()
    first = builder(7, 17, 2, "both")
    assert builder(7, 17, 2, "both") is first  # nothing allocated on a hit
    assert builder.cache_info().currsize == 1 and builder.cache_info().hits == 1
    assert hasattr(first, "lower")  # the jitted program's own surface passes through
    builder.cache_clear()
    assert builder.cache_info().currsize == 0
    # register_mesh_cache holds the wrapped object and still clears it
    assert svdtools._local_svd_fn in communication._MESH_KEYED_CACHES
    a = ht.random.randn(8 * 64, 40, split=0)
    ht.linalg.hsvd_rank(a, 2)
    if P > 1:
        assert svdtools._local_svd_fn.cache_info().currsize >= 1
    communication._clear_mesh_caches()
    assert svdtools._local_svd_fn.cache_info().currsize == 0


def test_telemetry_counts_the_cells_builders():
    """The hit/miss/build/compile counters keep their names and now count
    on the hSVD and KMeans paths too."""
    from heat_tpu.cluster import _kcluster
    from heat_tpu.core.linalg import svdtools

    svdtools._sketched_single_rank_fn.cache_clear()
    _kcluster._fused_fit_program.cache_clear()
    a = ht.random.randn(331, 96, split=None)
    x = ht.random.randn(8 * 53, 7, split=0)
    init = ht.array(np.asarray(x.numpy()[:3]))
    was = telemetry.enabled()
    telemetry.enable()
    try:
        for _ in range(2):
            ht.linalg.hsvd_rank(a, 2, compute_sv=True)
            ht.cluster.KMeans(3, init=init, max_iter=2, tol=0.0).fit(x)
        snap = telemetry.snapshot()
    finally:
        telemetry.enable() if was else telemetry.disable()
        tracing.disable()
        tracing.clear()
    for name in ("hsvd.sketched_rank", "kcluster.fused_fit"):
        assert snap["counters"].get(f"{name}.miss") == 1, name
        assert snap["counters"].get(f"{name}.hit", 0) >= 1, name
        assert f"{name}.build" in snap["timers"] and f"{name}.compile" in snap["timers"], name


def test_programs_plans_and_aot_keys_identical_with_and_without_a_session(tmp_path):
    """A profiler session changes what is observed, never what runs: the
    lowered program of a builder, a plan's id and bytes, and the AOT
    stamps are the same inside a session and outside one."""
    from heat_tpu.core.linalg import svdtools
    from heat_tpu.serving import aot_cache

    spec = RedistSpec.normalize((1000, 250000), "float32", 0, 1, 8)
    shape = jax.ShapeDtypeStruct((331, 96), np.float32)

    def stamps():
        svdtools._sketched_single_rank_fn.cache_clear()
        sched = planner.plan(spec, 256 << 20, topology="flat")
        return (
            svdtools._sketched_single_rank_fn(7, 17, 2, "both").lower(shape).as_text(),
            sched.plan_id,
            sched.canonical_json(),
            gates.aot_fingerprint(),
            aot_cache._envelope_stamps()["gate_roster"],
        )

    outside = stamps()
    inside = []
    _profiled(tmp_path, lambda: (ht.sum(ht.ones((8,), split=0)), inside.append(stamps())))
    assert inside[0] == outside


def test_selftest_of_the_span_readers():
    """``benchmarks/selftest_spans.py``: hand-written events with known
    answers through every reducer that reads the spans (no rehearsal here:
    about 15 s a cell)."""
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "selftest_spans.py"), "--no-rehearse"],
        capture_output=True, text=True, timeout=300,
    )
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-2000:]
    assert "selftest_spans: all passed" in done.stdout


# The wait side (PR 36): ht.sync.read where the library itself brings a
# device value to the host, and the calling thread's counters on the
# outermost span of a public call, under a profiler session only.
COUNTERS = {"thread_cpu_ns", "process_cpu_ns"}


def _ring_names(fn):
    """The names of the spans ``fn`` enters, by the ring (with their attrs)."""
    tracing.clear()
    tracing.enable()
    try:
        fn()
        return [(r["name"], r["attrs"]) for r in tracing.spans()]
    finally:
        tracing.disable()
        tracing.clear()


@pytest.mark.parametrize("attr", ["n_iter_", "inertia_"])
@pytest.mark.parametrize("est", ["KMeans", "KMedians"])
def test_sync_read_is_entered_by_the_first_read_of_a_fitted_estimator_and_not_by_the_second(est, attr):
    x = ht.random.randn(8 * 53, 7, split=0)
    init = ht.array(np.asarray(x.numpy()[:3]))
    fitted = getattr(ht.cluster, est)(3, init=init, max_iter=2).fit(x)
    first = _ring_names(lambda: getattr(fitted, attr))
    assert first == [("ht.sync.read", {"what": attr})]
    assert _ring_names(lambda: getattr(fitted, attr)) == []  # the cached value: no read, no span
    assert isinstance(getattr(fitted, attr), int if attr == "n_iter_" else float)


@pytest.mark.parametrize("read", [
    lambda a: a.item(), float, int, bool, complex, lambda a: a.numpy(), np.asarray, lambda a: a.tolist(),
], ids=["item", "float", "int", "bool", "complex", "numpy", "__array__", "tolist"])
def test_sync_read_is_entered_once_by_a_host_read_of_a_dndarray(read):
    a = ht.sum(ht.ones((8,), split=0))
    jax.block_until_ready(a._phys)
    assert [n for n, _ in _ring_names(lambda: read(a))].count("ht.sync.read") == 1


def _counted_calls():
    x = ht.random.randn(8 * 53, 7, split=0)
    init = ht.array(np.asarray(x.numpy()[:3]))
    a = ht.random.randn(331, 24, split=None)
    return {
        "hsvd_rank": (lambda: ht.linalg.hsvd_rank(a, 2, compute_sv=True), "ht.call.hsvd_rank", "ht.call.hsvd.prepare"),
        "qr": (lambda: ht.linalg.qr(a), "ht.call.qr", "ht.call.qr.prepare"),
        "kmeans_fit": (lambda: ht.cluster.KMeans(3, init=init, max_iter=2, tol=0.0).fit(x), "ht.call.kmeans.fit",
                       "ht.call.kmeans.program"),
    }


@pytest.mark.parametrize("which", ["hsvd_rank", "qr", "kmeans_fit"])
def test_outermost_call_span_carries_the_threads_counters_under_a_session(which, tmp_path):
    """Under a CPU profiler session the outermost ``ht.call.*`` event holds
    the two counters as integer stats, read at entry (so they do not fall
    from one call to the next), and an inner span holds none."""
    call, root, inner = _counted_calls()[which]
    rows = _profiled(tmp_path, lambda: (call(), call()), stats=True)
    first, second = [r[4] for r in rows if r[0] == root]
    for got in (first, second):
        assert COUNTERS <= set(got), sorted(COUNTERS - set(got))
        assert all(isinstance(got[c], int) for c in COUNTERS), got
    assert all(second[c] >= first[c] for c in COUNTERS)
    assert second["thread_cpu_ns"] > first["thread_cpu_ns"]  # the first call ran on this thread
    inners = [r[4] for r in rows if r[0] == inner]
    assert inners and not any(COUNTERS & set(got) for got in inners), inners
    assert tracing.spans() == []  # the counters go to the profiler, never to the ring


def test_no_session_reads_no_counter_and_the_ring_never_gets_one(monkeypatch):
    """With no profiler session a public call reads neither CPU clock at
    all; with the ring on and no session the ring's record of the outermost
    span holds no counter."""
    import time

    reads = []
    for clock in ("thread_time_ns", "process_time_ns"):
        real = getattr(time, clock)
        monkeypatch.setattr(time, clock, lambda real=real, clock=clock: (reads.append(clock), real())[1])
    assert not jax.profiler.TraceAnnotation.is_enabled()
    calls = _counted_calls()
    for call, _, _ in calls.values():
        call()
    assert reads == []
    assert dict(_ring_names(calls["qr"][0]))["ht.call.qr"] == {}
    assert reads == []
    # the helper itself reads both, and a platform that refuses a clock gives what was read before it
    assert set(tracing._thread_counters()) == COUNTERS and reads == ["thread_time_ns", "process_time_ns"]
    monkeypatch.setattr(time, "process_time_ns", lambda: (_ for _ in ()).throw(OSError("no such clock")))
    assert set(tracing._thread_counters()) == {"thread_cpu_ns"}


def test_programs_are_the_same_with_the_counters_read(tmp_path):
    """The counters are read on the host, at the entry of the call: the
    program a public call builds under a session (counters read) has the
    text of the one it builds without (none read), and the counters go to
    the profiler alone."""
    qr = importlib.import_module("heat_tpu.core.linalg.qr")
    shape = jax.ShapeDtypeStruct((331, 24), np.float32)
    a = ht.random.randn(331, 24, split=None)

    def texts():
        qr._local_qr_fn.cache_clear()
        ht.linalg.qr(a)  # through call_span: under a session the counters are read
        return qr._local_qr_fn(331, 24, "float32", True).lower(shape).as_text()

    outside = texts()
    inside, ring = [], []
    rows = _profiled(tmp_path, lambda: (inside.append(texts()), ring.extend(_ring_names(lambda: ht.linalg.qr(a)))),
                     stats=True)
    assert all(COUNTERS <= set(r[4]) for r in rows if r[0] == "ht.call.qr")
    assert inside[0] == outside
    assert dict(ring)["ht.call.qr"] == {}  # a session and the ring at once: the ring's record holds no counter


def test_selftest_of_the_wait_side_readers():
    """``benchmarks/selftest_hostside.py``: hand-written events with known
    answers through ``hostside.py`` and the four reducers that read it (no
    rehearsal here: about 15 s a cell)."""
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "selftest_hostside.py"), "--no-rehearse"],
        capture_output=True, text=True, timeout=300,
    )
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-2000:]
    assert "selftest_hostside: all passed" in done.stdout
