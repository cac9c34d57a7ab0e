#!/usr/bin/env python
"""Chaos CI drill (ISSUE 13): kill a simulated slice mid-``fit`` and
prove recovery end to end.

One seeded run, five asserted facts:

1. **Detection** — the declared slice loss fires as a typed
   ``WorldChangedError`` mid-stream (never a hang), and the world
   re-resolves onto the survivors (8 -> 4 devices at the 2x4 topology;
   5 -> 3 on the odd mesh).
2. **Serving failover** — the live dispatcher's queued requests resolve
   as ``ServingOverloaded(reason="resize")`` (the fail-over contract;
   the in-flight batch COMPLETES), submits during the drain are
   rejected with the same reason, and the endpoint re-warms against the
   new world and serves again.
3. **Cache rekey** — the epoch bumps and the plan/program/jit caches
   are swept.
4. **Bit-reproducible resume** — the checkpoint-resumed ``fit`` (which
   also survives a chaos-truncated newest envelope by falling back to
   the committed predecessor) produces centers bit-identical to an
   uninterrupted same-seed run on the ORIGINAL world.
5. **Flight-recorder post-mortem** (ISSUE 15) — the always-on flight
   ring recorded the injected kill at its declared step, the
   ``WorldChangedError`` carries that tail (``e.flight_tail``), and the
   chaos truncation landed in the ring too — the post-mortem is inside
   the exception, not in scrollback.

Run under both CI meshes::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \\
        HEAT_TPU_RESILIENCE=1 python scripts/chaos_drill.py
    XLA_FLAGS=--xla_force_host_platform_device_count=5 JAX_PLATFORMS=cpu \\
        HEAT_TPU_RESILIENCE=1 python scripts/chaos_drill.py
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import threading

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("HEAT_TPU_OOC_SLAB_MB", "1")  # multi-window stream

import numpy as np  # noqa: E402

import jax  # noqa: E402

import heat_tpu as ht  # noqa: E402
from heat_tpu.redistribution import staging  # noqa: E402
from heat_tpu.resilience import chaos, checkpoint as ck, elastic  # noqa: E402
from heat_tpu.serving.admission import ServingOverloaded  # noqa: E402
from heat_tpu.serving.dispatcher import Dispatcher, Endpoint  # noqa: E402

KILL_STEP = 2
SEED = 11


def main() -> int:
    ht.utils.place_compile_cache()
    n_dev = len(jax.devices())
    topology = "2x4" if n_dev == 8 else None  # odd meshes: flat, kill half
    rng = np.random.default_rng(0)
    pts = rng.standard_normal((40960, 16)).astype(np.float32)
    host = staging.HostArray(pts)
    wins = staging.window_extents(host.shape, 4, 0, staging.slab_bytes())
    assert len(wins) >= 4, f"drill needs a multi-window stream, got {len(wins)}"

    # --- uninterrupted reference on the full world ------------------- #
    km_ref = ht.cluster.KMeans(n_clusters=4, init="random", random_state=SEED)
    km_ref.fit(host)
    ref_bits = np.asarray(km_ref.cluster_centers_.numpy()).view(np.uint32)

    # --- the chaos run ------------------------------------------------ #
    report = {"devices": n_dev, "windows": len(wins), "topology": topology or "flat"}
    with tempfile.TemporaryDirectory(prefix="ht-chaos-") as d:
        cfg = ck.CheckpointConfig(directory=d, tag="drill", every=1)
        monkey = (
            chaos.ChaosMonkey(seed=3)
            .kill_slice(step=KILL_STEP)
            .truncate_checkpoint(step=KILL_STEP + 1)
        )
        watcher = monkey.watcher(topology=topology)

        # a live serving dispatcher with a parked worker so requests are
        # provably QUEUED when the drain fires (the place hook blocks
        # the worker inside the batch it already collected)
        gate = threading.Event()
        entered = threading.Event()

        def blocking_place(batch):
            entered.set()
            gate.wait(30)
            import jax.numpy as jnp

            return jnp.asarray(batch)

        ep = Endpoint(
            {8: jax.jit(lambda b: b * 2.0)}, (16,), np.float32, place=blocking_place
        )
        disp = Dispatcher(ep, max_queue=32, poll_s=0.005).start()
        inflight = disp.submit(np.ones((2, 16), np.float32))
        assert entered.wait(10), "worker never started the in-flight batch"
        # enqueued only once the worker is provably INSIDE the blocked
        # batch — these can only be served by a later batch or shed
        queued = [disp.submit(np.ones((1, 16), np.float32)) for _ in range(6)]

        km = ht.cluster.KMeans(n_clusters=4, init="random", random_state=SEED)
        epoch_before = elastic.world_epoch()
        try:
            km.fit(host, ckpt=cfg, _watcher=watcher, _chaos=monkey)
            raise AssertionError("declared slice kill never fired")
        except elastic.WorldChangedError as e:
            report["detected"] = str(e)
            # ISSUE 15: the error is its own post-mortem — the flight
            # tail it carries must contain the injected kill at its
            # declared step
            tail = getattr(e, "flight_tail", None)
            assert tail, "WorldChangedError carries no flight-recorder tail"
            kills = [r for r in tail
                     if r["kind"] == "chaos.slice-lost" and r["value"] == KILL_STEP]
            assert kills, (
                f"flight tail is missing the injected kill at step {KILL_STEP}: "
                f"{[(r['kind'], r['value']) for r in tail]}"
            )
            report["flight_tail_kill"] = kills[-1]

        # serving side: fence + shed typed, reject during drain. The
        # drain is ARMED while the worker is still inside the blocked
        # in-flight batch (so the 6 queued requests are provably still
        # queued), then the batch is released: the worker fences it —
        # its future RESOLVES — and sheds the backlog typed.
        drained = []
        drain_t = threading.Thread(
            target=lambda: drained.append(disp.drain(reason="resize", timeout=30))
        )
        drain_t.start()
        gate.set()  # release the in-flight batch so the fence can pass
        drain_t.join(35)
        assert drained and drained[0], "drain timed out"
        np.testing.assert_allclose(np.asarray(inflight.result(1)), 2.0)
        shed = 0
        for f in queued:
            try:
                f.result(1)
            except ServingOverloaded as exc:
                assert exc.reason == "resize", exc.reason
                shed += 1
        assert shed >= 1, "no queued request was shed typed"
        try:
            disp.submit(np.ones((1, 16), np.float32))
            raise AssertionError("submit during drain must be rejected")
        except ServingOverloaded as exc:
            assert exc.reason == "resize", exc.reason
        report["shed_typed"] = shed

        # rekey: re-resolve onto the survivors, bump + sweep
        new_comm = elastic.resolve_world(watcher.devices())
        counts = elastic.invalidate_caches("resize")
        assert elastic.world_epoch() == epoch_before + 1
        report["survivors"] = new_comm.size
        report["evicted"] = counts
        assert new_comm.size < n_dev

        # re-warm the endpoint against the new world and serve again
        ep2 = Endpoint({8: jax.jit(lambda b: b * 2.0)}, (16,), np.float32)
        disp.resume(endpoint=ep2)
        np.testing.assert_allclose(
            np.asarray(disp.call(np.ones((2, 16), np.float32), timeout=30)), 2.0
        )
        disp.stop()

        # resume: the truncated newest envelope must fall back, and the
        # resumed run must reproduce the uninterrupted bits exactly
        steps_before = ck.list_steps(d, "drill")
        km.fit(host, ckpt=cfg)
        got_bits = np.asarray(km.cluster_centers_.numpy()).view(np.uint32)
        assert np.array_equal(ref_bits, got_bits), (
            "resumed centers differ from the uninterrupted run"
        )
        report["resumed_from_steps"] = steps_before
        report["chaos_log"] = monkey.log
        report["bit_identical"] = True
        truncated = [e for e in monkey.log if e["kind"] == "truncate-ckpt"]
        assert truncated, "the declared checkpoint truncation never fired"
        # the truncation must be in the flight ring too (fire-time
        # breadcrumb next to the kill, for post-mortems with no error)
        from heat_tpu.observability import tracing as _tracing

        flight = _tracing.flight_tail(_tracing.flight_capacity())
        assert any(r["kind"] == "chaos.truncate" for r in flight), (
            "flight ring is missing the chaos truncation record"
        )
        report["flight_records"] = sorted({r["kind"] for r in flight})

    print(json.dumps({"chaos_drill": "ok", **report}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
