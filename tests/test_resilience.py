"""ISSUE 13: the elastic, fault-tolerant runtime (``heat_tpu.resilience``).

Contracts pinned here:

- **Checkpoint envelope** — slab-streamed save/load round-trips numpy /
  jax (replicated AND split-0-sharded) / DNDarray / scalar / RNG-tuple
  state bit-exactly; per-entry sha256 catches truncation as
  ``CheckpointCorrupt`` and ``restore_latest`` falls back to the
  committed predecessor; ``.tmp-*`` write orphans are invisible; host
  memory stays O(slab), ASSERTED off the envelope's recorded
  ``max_slab_bytes``; the meta stamps the PR 12 gate roster + topology.
- **Resume contract** — ``KMeans.fit(HostArray, ckpt=)`` commits the
  window cursor and resumes bit-identically to an uninterrupted
  same-seed run: same world, a crashed-and-restarted process, or a
  RESIZED world (the restored arrays re-shard onto the survivors).
- **RNG satellite** — seed/stream state is explicit model state: two
  same-seed models draw IDENTICAL inits, the ctor never touches the
  global stream, and checkpoint-restored twins draw identically (the
  PR 11 footgun closed).
- **World re-resolution** — epoch bump + eviction sweep over the
  plan/program/jit caches; a stamped stale-epoch communicator entering
  the redistribution executor raises the typed ``WorldChangedError``.
- **Serving failover** — ``Dispatcher.drain(reason="resize")`` fences
  the in-flight batch (its futures RESOLVE), sheds the queue typed,
  rejects submits during the drain, and ``resume``/``drain_and_rewarm``
  serve again with a rebuilt endpoint.
- **Chaos harness** — same seed + same declarations = byte-identical
  injection schedules; poison recovery is bit-identical.
- **SL406** — the swallowed-worker-exception rule fires on the golden
  fixture, passes every surfacing idiom, and the shipped dispatcher /
  partial-dataset workers are pinned clean (with a seeded-bug mutation
  proof on the dispatcher's own handler).
- **Escape hatch** — ``HEAT_TPU_RESILIENCE=0``: no checkpoints, no
  fences, plain fit paths.
"""

import os
import shutil
import tempfile
import threading

import numpy as np

import jax
import jax.numpy as jnp

import heat_tpu as ht

import analysis_fixtures as fx  # noqa: F401  (fixture import parity with test_effectcheck)

from heat_tpu.analysis import effectcheck, findings
from heat_tpu.core import communication as comm_mod, gates, tiers
from heat_tpu.core import random as ht_random
from heat_tpu.redistribution import planner, staging
from heat_tpu.resilience import chaos, checkpoint as ck, elastic
from heat_tpu.serving.admission import ServingOverloaded
from heat_tpu.serving.dispatcher import Dispatcher, Endpoint

from test_suites.basic_test import TestCase, env_pin

P = len(jax.devices())
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _bits(dnd) -> np.ndarray:
    return np.asarray(dnd.numpy()).view(np.uint32)


def _host(n=40960, d=16, seed=0) -> staging.HostArray:
    rng = np.random.default_rng(seed)
    pts = rng.standard_normal((n, d)).astype(np.float32)
    pts[: n // 4] += 4.0
    return staging.HostArray(pts)


def _restore_full_world():
    comm_mod.use_comm(comm_mod.MPI_WORLD)
    elastic._clear_stamps()


# ------------------------------------------------------------------ #
# gates + lattice edge                                               #
# ------------------------------------------------------------------ #
class TestResilienceGates(TestCase):
    def test_gates_declared(self):
        spec = gates.GATES["HEAT_TPU_RESILIENCE"]
        self.assertEqual(spec.values, ("0", "1", "auto"))
        self.assertTrue(spec.affects_programs)
        self.assertIn("aot", spec.scopes)
        dir_spec = gates.GATES["HEAT_TPU_CKPT_DIR"]
        self.assertFalse(dir_spec.affects_programs)
        self.assertEqual(dir_spec.kind, "path")
        roster = gates.program_gate_roster()
        self.assertIn("HEAT_TPU_RESILIENCE", roster)
        self.assertNotIn("HEAT_TPU_CKPT_DIR", roster)

    def test_mode_resolution(self):
        with env_pin(ck.RESILIENCE_ENV, None):
            self.assertEqual(ck.resilience_mode(), "auto")
            self.assertFalse(ck.resilience_enabled())
            self.assertTrue(ck.resilience_enabled(explicit=True))
        for raw in ("0", "off", "no"):
            with env_pin(ck.RESILIENCE_ENV, raw):
                self.assertEqual(ck.resilience_mode(), "0")
                self.assertFalse(ck.resilience_enabled(explicit=True))
        for raw in ("1", "force", "on"):
            with env_pin(ck.RESILIENCE_ENV, raw):
                self.assertEqual(ck.resilience_mode(), "1")
                self.assertTrue(ck.resilience_enabled())

    def test_ckpt_dir_resolution(self):
        with env_pin(ck.CKPT_DIR_ENV, "/tmp/ht-ckpt-test"):
            self.assertEqual(ck.ckpt_dir(), "/tmp/ht-ckpt-test")
        self.assertEqual(ck.ckpt_dir("/explicit"), "/explicit")

    def test_disk_edge_priced(self):
        self.assertEqual(tiers.bandwidth("disk"), tiers.DISK_BPS)
        self.assertEqual(tiers.edge_between("host", "disk"), "disk")
        self.assertGreaterEqual(tiers.penalty("disk"), 1)
        self.assertIn("disk", tiers.describe())
        # the durable-commit price sits BELOW the pcie staging edge —
        # a checkpoint is never modeled faster than the host hop
        self.assertLess(tiers.DISK_BPS, tiers.PCIE_BPS)


# ------------------------------------------------------------------ #
# checkpoint envelope                                                #
# ------------------------------------------------------------------ #
class TestCheckpointEnvelope(TestCase):
    def test_round_trip_all_kinds(self):
        with tempfile.TemporaryDirectory() as d:
            x = ht.ones((64, 8), split=0 if P > 1 else None) * 3.5
            carry = comm_mod.get_comm().shard(
                jnp.arange(P * 6, dtype=jnp.float32).reshape(P, 6), 0
            )
            state = {
                "dnd": x,
                "np": np.arange(24, dtype=np.float64).reshape(4, 6),
                "jax_repl": jnp.full((3, 3), 2.25, jnp.float32),
                "jax_sharded": carry,
                "rng": ("Threefry", 7, 13, 0, 0.0),
                "cursor": 5,
                "note": "resume",
            }
            ck.save(state, tag="rt", step=3, directory=d)
            step, got, meta = ck.restore_latest(d, tag="rt")
            self.assertEqual(step, 3)
            np.testing.assert_array_equal(got["dnd"].numpy(), x.numpy())
            self.assertEqual(got["dnd"].split, x.split)
            np.testing.assert_array_equal(got["np"], state["np"])
            np.testing.assert_array_equal(
                np.asarray(got["jax_repl"]), np.asarray(state["jax_repl"])
            )
            np.testing.assert_array_equal(
                np.asarray(jax.device_get(got["jax_sharded"])),
                np.asarray(jax.device_get(carry)),
            )
            if P > 1:
                self.assertFalse(got["jax_sharded"].sharding.is_fully_replicated)
            self.assertEqual(got["rng"], state["rng"])
            self.assertEqual(got["cursor"], 5)
            self.assertEqual(got["note"], "resume")

    def test_stamps(self):
        with tempfile.TemporaryDirectory() as d:
            ck.save({"a": np.zeros(4, np.float32)}, tag="s", step=1, directory=d)
            _, _, meta = ck.restore_latest(d, tag="s")
            stamps = meta["stamps"]
            self.assertEqual(stamps["gate_roster"], gates.program_gate_roster())
            self.assertEqual(stamps["world_size"], comm_mod.get_comm().size)
            self.assertEqual(stamps["topology"], str(comm_mod.get_comm().topology))
            self.assertEqual(meta["format"], ck.FORMAT)

    def test_truncation_detected_and_fallback(self):
        with tempfile.TemporaryDirectory() as d:
            state = {"a": np.arange(4096, dtype=np.float32)}
            ck.save(state, tag="t", step=1, directory=d)
            ck.save(state, tag="t", step=2, directory=d)
            path2 = ck.step_path(d, "t", 2)
            with open(os.path.join(path2, "a.bin"), "r+b") as f:
                f.truncate(100)
            with self.assertRaises(ck.CheckpointCorrupt):
                ck.load(path2)
            step, _, _ = ck.restore_latest(d, tag="t")
            self.assertEqual(step, 1)  # corruption costs recency, not correctness

    def test_bitflip_detected(self):
        with tempfile.TemporaryDirectory() as d:
            ck.save({"a": np.zeros(1024, np.float32)}, tag="b", step=1, directory=d)
            fp = os.path.join(ck.step_path(d, "b", 1), "a.bin")
            with open(fp, "r+b") as f:
                f.seek(512)
                f.write(b"\x01")
            with self.assertRaises(ck.CheckpointCorrupt):
                ck.load(ck.step_path(d, "b", 1))

    def test_tmp_orphans_invisible(self):
        with tempfile.TemporaryDirectory() as d:
            ck.save({"a": np.zeros(4, np.float32)}, tag="o", step=1, directory=d)
            orphan = ck.step_path(d, "o", 2) + ".tmp-999"
            os.makedirs(orphan)
            with open(os.path.join(orphan, "meta.json"), "w") as f:
                f.write("{}")  # a torn write that never committed
            self.assertEqual(ck.list_steps(d, "o"), [1])
            self.assertEqual(ck.latest_step(d, "o"), 1)

    def test_meta_tamper_detected(self):
        """Review regression: the meta carries the resume-critical
        cursor — a parseable-but-flipped meta.json (window_index digit
        flip) must fail verification, not resume from a wrong cursor."""
        with tempfile.TemporaryDirectory() as d:
            ck.save(
                {"a": np.zeros(8, np.float32), "window_index": 3},
                tag="m", step=1, directory=d,
            )
            mp = os.path.join(ck.step_path(d, "m", 1), "meta.json")
            with open(mp) as f:
                tampered = f.read().replace('"window_index": 3', '"window_index": 7')
            with open(mp, "w") as f:
                f.write(tampered)
            with self.assertRaises(ck.CheckpointCorrupt):
                ck.load(ck.step_path(d, "m", 1))
            self.assertIsNone(ck.restore_latest(d, tag="m"))

    def test_prune_keeps_newest(self):
        with tempfile.TemporaryDirectory() as d:
            for s in (1, 2, 3, 4):
                ck.save({"a": np.zeros(4, np.float32)}, tag="p", step=s, directory=d)
            dropped = ck.prune(d, "p", keep=2)
            self.assertEqual(dropped, [1, 2])
            self.assertEqual(ck.list_steps(d, "p"), [3, 4])

    def test_host_memory_o_slab_asserted(self):
        """The acceptance pin: host staging during save is bounded at
        O(slab), read off the envelope's RECORDED high-water mark — an
        unsharded 256 MiB entry stages at most SLAB_BYTES at once, and
        a split-0 DNDarray at most one device block."""
        with tempfile.TemporaryDirectory() as d:
            big = np.zeros((256 << 20) // 4, dtype=np.float32)  # 256 MiB
            path = ck.save({"big": big}, tag="slab", step=1, directory=d)
            meta = ck._read_meta(path)
            self.assertEqual(meta["total_bytes"], big.nbytes)
            self.assertLessEqual(meta["max_slab_bytes"], ck.SLAB_BYTES)
            self.assertLess(meta["max_slab_bytes"], big.nbytes // 2)
        with tempfile.TemporaryDirectory() as d:
            rows = 512 * max(P, 1)
            x = ht.ones((rows, 64), split=0 if P > 1 else None)
            path = ck.save({"x": x}, tag="slab", step=1, directory=d)
            meta = ck._read_meta(path)
            block = (x._phys.shape[0] // max(P, 1)) * 64 * 4 if P > 1 else x.numpy().nbytes
            self.assertLessEqual(meta["max_slab_bytes"], max(block, ck.SLAB_BYTES))

    def test_write_floor_vs_disk_edge(self):
        """Supporting evidence for the bench floor (``ckpt_write_2gb``
        pins >= 0.5x at 2.1 GB): the pipelined writer is disk-bound, not
        hash-bound — a 64 MiB durable commit must reach a LOOSE 0.2x of
        what THIS disk gives a plain write + fsync of the same bytes,
        measured beside it. (Against the lattice's constant disk edge the
        test measured the sandbox: 0.09-0.10 GB/s here, under any code.)"""
        import time

        with tempfile.TemporaryDirectory() as d:
            data = np.random.default_rng(0).standard_normal((32 << 20) // 4)
            data = np.tile(data.astype(np.float32), 2)  # 64 MiB
            t0 = time.perf_counter()
            with open(os.path.join(d, "raw.bin"), "wb") as f:
                f.write(data.tobytes())
                f.flush()
                os.fsync(f.fileno())
            raw = time.perf_counter() - t0
            t0 = time.perf_counter()
            ck.save({"data": data}, tag="bw", step=1, directory=d)
            dt = time.perf_counter() - t0
            self.assertLessEqual(
                dt, raw / 0.2,
                f"durable commit took {dt:.2f} s, a plain write+fsync {raw:.2f} s",
            )

    def test_failed_save_leaks_no_writer_threads(self):
        """Review regression: a mid-entry save failure aborts the
        writer — no parked hasher, no 20 Hz flusher, no open fd left
        behind per retry."""
        if P == 1:
            self.skipTest("split-1 needs a multi-device mesh")
        import threading as _threading
        import time as _time

        with tempfile.TemporaryDirectory() as d:
            before = _threading.active_count()
            for _ in range(3):
                with self.assertRaises(NotImplementedError):
                    ck.save(
                        {"ok": np.zeros(8, np.float32), "x": ht.ones((32, 32), split=1)},
                        tag="leak", step=1, directory=d,
                    )
            _time.sleep(0.1)
            self.assertLessEqual(_threading.active_count(), before)
            self.assertEqual(ck.list_steps(d, "leak"), [])  # nothing committed

    def test_flush_error_fails_the_commit(self):
        """Review regression: a writeback error observed by the early
        flusher must fail the commit — close() re-raises it instead of
        letting its own (error-cleared) fsync falsely succeed."""
        with tempfile.TemporaryDirectory() as d:
            w = ck._SlabWriter(os.path.join(d, "e.bin"))
            w.write(np.zeros(16, np.float32))
            w._flush_error = OSError("injected EIO")
            with self.assertRaises(OSError):
                w.close()

    def test_replicated_jax_staging_recorded_honestly(self):
        """Review regression: a replicated jax entry stages WHOLE on
        the host — max_slab_bytes must record that true footprint, not
        just the 64 MiB write chunks."""
        with tempfile.TemporaryDirectory() as d:
            big = jnp.zeros((1 << 20,), jnp.float32)  # 4 MiB replicated
            path = ck.save({"p": big}, tag="honest", step=1, directory=d)
            meta = ck._read_meta(path)
            self.assertGreaterEqual(meta["max_slab_bytes"], big.nbytes)

    def test_split1_dnd_rejected(self):
        if P == 1:
            self.skipTest("split-1 needs a multi-device mesh")
        with tempfile.TemporaryDirectory() as d:
            x = ht.ones((32, 32), split=1)
            with self.assertRaises(NotImplementedError):
                ck.save({"x": x}, tag="s1", step=1, directory=d)


# ------------------------------------------------------------------ #
# the RNG satellite                                                  #
# ------------------------------------------------------------------ #
class TestExplicitRngState(TestCase):
    def _data(self):
        rng = np.random.default_rng(5)
        return ht.array(rng.standard_normal((256, 8)).astype(np.float32), split=None)

    def test_same_seed_models_draw_identical_inits(self):
        """The PR 11 footgun closed: two same-seed models created then
        fitted IN SEQUENCE draw identical inits (each owns a private
        (seed, 0) stream; the old global-stream contract made the
        second model draw from wherever the first left the counter)."""
        data = self._data()
        for init in ("random", "kmeans++"):
            a = ht.cluster.KMeans(n_clusters=4, init=init, max_iter=5, random_state=9)
            b = ht.cluster.KMeans(n_clusters=4, init=init, max_iter=5, random_state=9)
            a.fit(data)
            b.fit(data)
            np.testing.assert_array_equal(
                _bits(a.cluster_centers_), _bits(b.cluster_centers_), init
            )

    def test_ctor_and_fit_leave_global_stream_untouched(self):
        before = ht_random.get_state()
        km = ht.cluster.KMeans(n_clusters=3, init="random", max_iter=3, random_state=4)
        km.fit(self._data())
        self.assertEqual(ht_random.get_state(), before)
        self.assertEqual(km.rng_state[1], 4)  # seed
        self.assertGreater(km.rng_state[2], 0)  # init ADVANCED the model stream

    def test_unseeded_model_keeps_legacy_global_stream(self):
        ht_random.seed(123)
        before = ht_random.get_state()
        km = ht.cluster.KMeans(n_clusters=3, init="random", max_iter=3)
        self.assertIsNone(km.rng_state)
        km.fit(self._data())
        self.assertNotEqual(ht_random.get_state(), before)

    def test_restored_twins_draw_identical(self):
        """The satellite's acceptance sentence: two models restored
        from the SAME checkpoint carry the same stream state and draw
        identical subsequent inits."""
        data = self._data()
        km = ht.cluster.KMeans(n_clusters=4, init="random", max_iter=5, random_state=9)
        km.fit(data)
        with tempfile.TemporaryDirectory() as d:
            ck.save(
                {"rng_state": km.rng_state, "centers": km.cluster_centers_},
                tag="twins", step=1, directory=d,
            )
            _, state, _ = ck.restore_latest(d, tag="twins")
            twins = []
            for _ in range(2):
                m = ht.cluster.KMeans(n_clusters=4, init="random", max_iter=5)
                m.rng_state = state["rng_state"]
                m.fit(data)  # draws its init from the restored stream
                twins.append(_bits(m.cluster_centers_))
            np.testing.assert_array_equal(twins[0], twins[1])
            self.assertEqual(state["rng_state"], km.rng_state)


# ------------------------------------------------------------------ #
# streaming resume                                                   #
# ------------------------------------------------------------------ #
class TestStreamingResume(TestCase):
    def _ref(self, host, seed=11):
        km = ht.cluster.KMeans(n_clusters=4, init="random", random_state=seed)
        km.fit(host)
        return _bits(km.cluster_centers_)

    def test_checkpointed_fit_bit_identical_to_plain(self):
        # explicit gate anchor: these tests REQUIRE the runtime engaged,
        # so the HEAT_TPU_RESILIENCE=0 escape-hatch CI leg still passes
        with env_pin(staging.SLAB_ENV, "1"), env_pin(ck.RESILIENCE_ENV, "auto"):
            host = _host()
            ref = self._ref(host)
            with tempfile.TemporaryDirectory() as d:
                cfg = ck.CheckpointConfig(directory=d, tag="km", every=2)
                km = ht.cluster.KMeans(n_clusters=4, init="random", random_state=11)
                km.fit(host, ckpt=cfg)
                np.testing.assert_array_equal(ref, _bits(km.cluster_centers_))
                self.assertTrue(ck.list_steps(d, "km"))

    def test_crash_resume_bit_identical(self):
        """Kill the run after an early checkpoint (simulated: drop the
        later envelopes), resume in a FRESH model, and reproduce the
        uninterrupted bits — including the streaming counts."""
        with env_pin(staging.SLAB_ENV, "1"), env_pin(ck.RESILIENCE_ENV, "auto"):
            host = _host()
            ref = self._ref(host)
            with tempfile.TemporaryDirectory() as d:
                cfg = ck.CheckpointConfig(directory=d, tag="crash", every=1, keep=99)
                km = ht.cluster.KMeans(n_clusters=4, init="random", random_state=11)
                km.fit(host, ckpt=cfg)
                full_counts = np.asarray(jax.device_get(km._partial_counts))
                steps = ck.list_steps(d, "crash")
                self.assertGreaterEqual(len(steps), 3)
                for s in steps[1:]:
                    shutil.rmtree(ck.step_path(d, "crash", s))
                fresh = ht.cluster.KMeans(n_clusters=4, init="random", random_state=11)
                fresh.fit(host, ckpt=cfg)
                np.testing.assert_array_equal(ref, _bits(fresh.cluster_centers_))
                np.testing.assert_array_equal(
                    full_counts, np.asarray(jax.device_get(fresh._partial_counts))
                )

    def test_resume_on_resized_world_bit_identical(self):
        """The elastic acceptance at this mesh: restore re-shards onto
        a SHRUNK world and the resumed windows reproduce the original
        world's bits exactly."""
        if P < 2:
            self.skipTest("needs a multi-device mesh to shrink")
        with env_pin(staging.SLAB_ENV, "1"), env_pin(ck.RESILIENCE_ENV, "auto"):
            host = _host()
            ref = self._ref(host)
            try:
                with tempfile.TemporaryDirectory() as d:
                    cfg = ck.CheckpointConfig(directory=d, tag="rs", every=1, keep=99)
                    km = ht.cluster.KMeans(
                        n_clusters=4, init="random", random_state=11
                    )
                    km.fit(host, ckpt=cfg)
                    steps = ck.list_steps(d, "rs")
                    for s in steps[2:]:
                        shutil.rmtree(ck.step_path(d, "rs", s))
                    elastic.resolve_world(comm_mod.MPI_WORLD.devices[: P // 2 + 1])
                    elastic.invalidate_caches("test-resize")
                    fresh = ht.cluster.KMeans(
                        n_clusters=4, init="random", random_state=11
                    )
                    fresh.fit(host, ckpt=cfg)
                    self.assertEqual(
                        fresh.cluster_centers_.comm.size, P // 2 + 1
                    )
                    np.testing.assert_array_equal(ref, _bits(fresh.cluster_centers_))
            finally:
                _restore_full_world()

    def test_fit_ckpt_rejects_unstreamable_inputs(self):
        cfg = ck.CheckpointConfig(directory=tempfile.gettempdir(), tag="x")
        with env_pin(ck.RESILIENCE_ENV, "auto"):
            with self.assertRaises(ValueError):
                ht.cluster.KMeans(n_clusters=2).fit(
                    ht.ones((32, 4), split=None), ckpt=cfg
                )
            with env_pin(staging.OOC_ENV, "0"):
                with self.assertRaises(ValueError):
                    ht.cluster.KMeans(n_clusters=2).fit(
                        staging.HostArray(np.ones((64, 4), np.float32)), ckpt=cfg
                    )
        # ... but under the =0 escape hatch ckpt= is inert EVERYWHERE
        # (review regression): both shapes run the plain pre-resilience
        # fit instead of raising
        with env_pin(ck.RESILIENCE_ENV, "0"):
            km = ht.cluster.KMeans(n_clusters=2, random_state=1).fit(
                ht.ones((32, 4), split=None), ckpt=cfg
            )
            self.assertIsNotNone(km.cluster_centers_)
            with env_pin(staging.OOC_ENV, "0"):
                km = ht.cluster.KMeans(n_clusters=2, random_state=1).fit(
                    staging.HostArray(np.ones((64, 4), np.float32)), ckpt=cfg
                )
                self.assertIsNotNone(km.cluster_centers_)

    def test_escape_hatch_ignores_ckpt(self):
        """HEAT_TPU_RESILIENCE=0: the exact pre-resilience stream — no
        checkpoint is ever written, and elastic_fit is plain fit."""
        with env_pin(ck.RESILIENCE_ENV, "0"), env_pin(staging.SLAB_ENV, "1"):
            host = _host(n=8192)
            with tempfile.TemporaryDirectory() as d:
                cfg = ck.CheckpointConfig(directory=d, tag="off", every=1)
                km = ht.cluster.KMeans(n_clusters=4, init="random", random_state=3)
                elastic.elastic_fit(km, host, ckpt=cfg)
                self.assertEqual(ck.list_steps(d, "off"), [])
                plain = ht.cluster.KMeans(n_clusters=4, init="random", random_state=3)
                plain.fit(host)
                np.testing.assert_array_equal(
                    _bits(km.cluster_centers_), _bits(plain.cluster_centers_)
                )


    def test_escape_hatch_leaves_hooks_inert(self):
        """Review regression: under HEAT_TPU_RESILIENCE=0 the watcher/
        chaos hooks are inert too — a declared slice kill neither fires
        nor costs the per-window validation sync."""
        with env_pin(ck.RESILIENCE_ENV, "0"), env_pin(staging.SLAB_ENV, "1"):
            host = _host(n=8192)
            watcher = elastic.SimulatedWorldWatcher(
                topology="2x4" if P == 8 else None
            ).kill_slice_at(1, 0)
            km = ht.cluster.KMeans(n_clusters=4, init="random", random_state=3)
            km.fit(host, _watcher=watcher)  # must NOT raise
            self.assertEqual(watcher.events, [])
            self.assertEqual(comm_mod.get_comm().size, P)

    def test_failure_before_first_commit_still_bit_reproducible(self):
        """Review regression: a poison at window 0 (BEFORE any commit)
        rewinds the model's private RNG stream, so the retry re-inits
        identically and the recovered fit still matches the
        uninterrupted run bit-for-bit."""
        with env_pin(staging.SLAB_ENV, "1"), env_pin(ck.RESILIENCE_ENV, "auto"):
            host = _host()
            ref = self._ref(host)
            with tempfile.TemporaryDirectory() as d:
                cfg = ck.CheckpointConfig(directory=d, tag="early", every=3)
                monkey = chaos.ChaosMonkey(seed=2).poison_collective(step=0)
                km = ht.cluster.KMeans(n_clusters=4, init="random", random_state=11)
                elastic.elastic_fit(km, host, ckpt=cfg, chaos=monkey)
                np.testing.assert_array_equal(ref, _bits(km.cluster_centers_))

    def test_resume_refuses_foreign_operand(self):
        """Review regression: a same-tag resume against a DIFFERENT
        dataset fails typed instead of adopting the old cursor."""
        with env_pin(staging.SLAB_ENV, "1"), env_pin(ck.RESILIENCE_ENV, "auto"):
            with tempfile.TemporaryDirectory() as d:
                cfg = ck.CheckpointConfig(directory=d, tag="op", every=1)
                km = ht.cluster.KMeans(n_clusters=4, init="random", random_state=1)
                km.fit(_host(), ckpt=cfg)
                other = staging.HostArray(
                    np.ones((8192, 16), np.float32)
                )
                km2 = ht.cluster.KMeans(n_clusters=4, init="random", random_state=1)
                with self.assertRaises(ValueError) as cm:
                    km2.fit(other, ckpt=cfg)
                self.assertIn("fresh tag", str(cm.exception))


# ------------------------------------------------------------------ #
# world re-resolution                                                #
# ------------------------------------------------------------------ #
class TestElasticWorld(TestCase):
    def test_world_changed_error_typed(self):
        e = elastic.WorldChangedError("slice-lost", old_size=8, new_size=4, epoch=2)
        self.assertEqual(e.reason, "slice-lost")
        self.assertEqual((e.old_size, e.new_size, e.epoch), (8, 4, 2))
        self.assertIn("8 -> 4", str(e))

    def test_simulated_watcher_slice_major(self):
        if P != 8:
            self.skipTest("slice-major layout pinned at the 8-device mesh")
        w = elastic.SimulatedWorldWatcher(topology="2x4")
        w.kill_slice_at(3, slice_index=0)
        self.assertIsNone(w.poll(2))
        evt = w.poll(3)
        self.assertEqual(evt.kind, "slice-lost")
        # slice 0 owns mesh positions [0, 4): the SURVIVORS are 4..7
        all_devs = comm_mod.MPI_WORLD.devices
        self.assertEqual(evt.devices, all_devs[4:])
        self.assertEqual(w.devices(), all_devs[4:])
        self.assertEqual(evt.detail["old_size"], 8)
        # successive events report the PREVIOUS world's size, not the
        # original one (review regression)
        w.resize_at(5, 2)
        evt2 = w.poll(5)
        self.assertEqual(evt2.detail["old_size"], 4)
        self.assertIsNone(w.poll(3))  # fires once

    def test_invalidate_bumps_epoch_and_sweeps(self):
        spec_name, spec = next(iter(planner.golden_specs()))
        planner.plan(spec)

        @ht.jit
        def prog(a):
            return a + 1.0

        prog(ht.ones((8,)))
        before = elastic.world_epoch()
        counts = elastic.invalidate_caches("test")
        self.assertEqual(elastic.world_epoch(), before + 1)
        self.assertGreaterEqual(counts["plans"], 1)
        self.assertGreaterEqual(counts["jit_entries"], 1)
        self.assertEqual(len(prog._ht_jit_cache), 0)

    def test_stale_epoch_comm_raises_in_executor(self):
        if P < 2:
            self.skipTest("needs a distributed resplit")
        stale = comm_mod.MeshCommunication(comm_mod.MPI_WORLD.devices)
        try:
            with env_pin(ck.RESILIENCE_ENV, "auto"):
                elastic.stamp(stale)
                elastic.invalidate_caches("test-stale")
                x = ht.ones((64, 4), split=0, comm=stale)
                with self.assertRaises(elastic.WorldChangedError):
                    x.resplit(1)
        finally:
            elastic._clear_stamps()
        # fence disarmed: the same movement executes normally again
        y = ht.ones((64, 4), split=0).resplit(1)
        self.assertEqual(y.split, 1)

    def test_check_world_is_noop_by_default_and_under_escape_hatch(self):
        elastic._clear_stamps()
        elastic.check_world(comm_mod.get_comm())  # fence disarmed: no-op
        # a STALE comm object (not the installed default) trips the fence
        stale = comm_mod.MeshCommunication(comm_mod.MPI_WORLD.devices)
        try:
            elastic.stamp(stale)
            elastic.invalidate_caches("test-hatch")
            with env_pin(ck.RESILIENCE_ENV, "0"):
                elastic.check_world(stale)  # escape hatch: never raises
            with env_pin(ck.RESILIENCE_ENV, "auto"):
                with self.assertRaises(elastic.WorldChangedError):
                    elastic.check_world(stale)
        finally:
            elastic._clear_stamps()

    def test_elastic_fit_recovers_from_slice_kill(self):
        if P < 2:
            self.skipTest("needs a multi-device mesh to shrink")
        with env_pin(staging.SLAB_ENV, "1"), env_pin(ck.RESILIENCE_ENV, "auto"):
            host = _host()
            km_ref = ht.cluster.KMeans(n_clusters=4, init="random", random_state=11)
            km_ref.fit(host)
            ref = _bits(km_ref.cluster_centers_)
            try:
                with tempfile.TemporaryDirectory() as d:
                    cfg = ck.CheckpointConfig(directory=d, tag="el", every=1)
                    monkey = chaos.ChaosMonkey(seed=3).kill_slice(step=2)
                    watcher = monkey.watcher(
                        topology="2x4" if P == 8 else None
                    )
                    km = ht.cluster.KMeans(
                        n_clusters=4, init="random", random_state=11
                    )
                    elastic.elastic_fit(
                        km, host, ckpt=cfg, watcher=watcher, chaos=monkey
                    )
                    self.assertLess(comm_mod.get_comm().size, P)
                    np.testing.assert_array_equal(ref, _bits(km.cluster_centers_))
            finally:
                _restore_full_world()


    def test_recovery_order_leaves_current_world_live(self):
        """Review regression: resolve_world() THEN invalidate_caches()
        (the shipped recovery order) must leave the installed world
        UN-fenced — the current communicator rides the epoch bump
        forward; only dead worlds' comms trip the fence."""
        if P < 2:
            self.skipTest("needs a distributed resplit")
        try:
            with env_pin(ck.RESILIENCE_ENV, "auto"):
                comm = elastic.resolve_world(comm_mod.MPI_WORLD.devices)
                elastic.invalidate_caches("test-order")
                elastic.check_world(comm)  # must NOT raise
                x = ht.ones((64, 4), split=0).resplit(1)  # executor entry
                self.assertEqual(x.split, 1)
                # and the inverse order too
                elastic.invalidate_caches("test-order-2")
                comm2 = elastic.resolve_world(comm_mod.MPI_WORLD.devices)
                elastic.check_world(comm2)
        finally:
            _restore_full_world()


# ------------------------------------------------------------------ #
# serving failover                                                   #
# ------------------------------------------------------------------ #
class TestDispatcherDrain(TestCase):
    def _blocked_dispatcher(self):
        gate, entered = threading.Event(), threading.Event()

        def blocking_place(batch):
            entered.set()
            gate.wait(30)
            return jnp.asarray(batch)

        ep = Endpoint(
            {8: jax.jit(lambda b: b * 2.0)}, (4,), np.float32, place=blocking_place
        )
        d = Dispatcher(ep, max_queue=32, poll_s=0.005).start()
        return d, gate, entered

    def test_drain_fences_inflight_and_sheds_typed(self):
        d, gate, entered = self._blocked_dispatcher()
        try:
            inflight = d.submit(np.ones((2, 4), np.float32))
            self.assertTrue(entered.wait(10))
            queued = [d.submit(np.ones((1, 4), np.float32)) for _ in range(6)]
            out = []
            t = threading.Thread(
                target=lambda: out.append(d.drain(reason="resize", timeout=30))
            )
            t.start()
            gate.set()
            t.join(35)
            self.assertEqual(out, [True])
            # the in-flight batch COMPLETED — its future resolves
            np.testing.assert_allclose(np.asarray(inflight.result(1)), 2.0)
            reasons = set()
            for f in queued:
                with self.assertRaises(ServingOverloaded) as cm:
                    f.result(1)
                reasons.add(cm.exception.reason)
            self.assertEqual(reasons, {"resize"})
            self.assertGreaterEqual(d.stats()["shed"], 6)
            # submits during the drain fail fast with the drain reason
            with self.assertRaises(ServingOverloaded) as cm:
                d.submit(np.ones((1, 4), np.float32))
            self.assertEqual(cm.exception.reason, "resize")
        finally:
            gate.set()
            d.stop()

    def test_resume_serves_with_new_endpoint(self):
        d, gate, entered = self._blocked_dispatcher()
        try:
            gate.set()
            self.assertTrue(d.drain(reason="resize", timeout=10))
            ep2 = Endpoint({8: jax.jit(lambda b: b * 5.0)}, (4,), np.float32)
            d.resume(endpoint=ep2)
            out = d.call(np.ones((2, 4), np.float32), timeout=10)
            np.testing.assert_allclose(np.asarray(out), 5.0)
        finally:
            d.stop()

    def test_drain_and_rewarm_helper(self):
        d, gate, entered = self._blocked_dispatcher()
        try:
            gate.set()
            ep2 = elastic.drain_and_rewarm(
                d,
                lambda: Endpoint({8: jax.jit(lambda b: b * 7.0)}, (4,), np.float32),
                reason="resize",
            )
            self.assertIs(d.endpoint, ep2)
            out = d.call(np.ones((1, 4), np.float32), timeout=10)
            np.testing.assert_allclose(np.asarray(out), 7.0)
        finally:
            d.stop()

    def test_drain_and_rewarm_timeout_raises(self):
        """Review regression: a drain that cannot confirm must raise —
        swapping the endpoint under a live worker is never safe."""
        d, gate, entered = self._blocked_dispatcher()
        try:
            d.submit(np.ones((1, 4), np.float32))
            self.assertTrue(entered.wait(10))  # worker wedged in the batch
            with self.assertRaises(TimeoutError):
                elastic.drain_and_rewarm(
                    d, lambda: None, reason="resize", timeout=0.2
                )
        finally:
            gate.set()
            d.stop()

    def test_drain_not_running_sweeps(self):
        ep = Endpoint({4: jax.jit(lambda b: b)}, (2,), np.float32)
        d = Dispatcher(ep, max_queue=4)
        self.assertTrue(d.drain(reason="resize", timeout=1))

    def test_stop_reason_stays_shutdown(self):
        d, gate, entered = self._blocked_dispatcher()
        gate.set()
        d.call(np.ones((1, 4), np.float32), timeout=10)
        d.stop()
        with self.assertRaises(RuntimeError):
            d.submit(np.ones((1, 4), np.float32))


# ------------------------------------------------------------------ #
# chaos determinism                                                  #
# ------------------------------------------------------------------ #
class TestChaosMonkey(TestCase):
    def test_same_seed_same_schedule(self):
        def build():
            m = (
                chaos.ChaosMonkey(seed=42)
                .kill_slice(step=5)
                .poison_collective(step=9)
                .truncate_checkpoint(step=12)
            )
            m.watcher(topology="2x4" if P == 8 else None)  # resolves the slice draw
            return m

        a, b = build(), build()
        self.assertEqual(a.schedule(), b.schedule())
        self.assertEqual(a.log, b.log)

    def test_poison_recovery_bit_identical(self):
        with env_pin(staging.SLAB_ENV, "1"), env_pin(ck.RESILIENCE_ENV, "auto"):
            host = _host()
            km_ref = ht.cluster.KMeans(n_clusters=4, init="random", random_state=11)
            km_ref.fit(host)
            ref = _bits(km_ref.cluster_centers_)
            with tempfile.TemporaryDirectory() as d:
                cfg = ck.CheckpointConfig(directory=d, tag="po", every=2)
                monkey = chaos.ChaosMonkey(seed=5).poison_collective(step=3)
                km = ht.cluster.KMeans(n_clusters=4, init="random", random_state=11)
                elastic.elastic_fit(km, host, ckpt=cfg, chaos=monkey)
                np.testing.assert_array_equal(ref, _bits(km.cluster_centers_))
                self.assertEqual(
                    [e["kind"] for e in monkey.log], ["poison-collective"]
                )

    def test_truncation_mutilates_largest_entry(self):
        with tempfile.TemporaryDirectory() as d:
            path = ck.save(
                {"big": np.zeros(4096, np.float32), "small": np.zeros(2, np.float32)},
                tag="tr", step=7, directory=d,
            )
            monkey = chaos.ChaosMonkey(seed=1).truncate_checkpoint(step=7)
            monkey.after_checkpoint(path, 7)
            self.assertEqual(monkey.log[0]["entry"], "big.bin")
            with self.assertRaises(ck.CheckpointCorrupt):
                ck.load(path)


# ------------------------------------------------------------------ #
# SL406 — the swallowed-worker-exception rule                        #
# ------------------------------------------------------------------ #
class TestSL406(TestCase):
    def test_fixture_trips_and_twins_pass(self):
        found = effectcheck.lint_source(fx.SWALLOWED_WORKER_EXC_SRC, "heat_tpu/x.py")
        self.assertEqual({f.rule for f in found}, {"SL406"})
        self.assertEqual(len(found), 2)
        self.assertTrue(all(f.severity == "error" for f in found))
        blob = " ".join(f.message for f in found)
        self.assertIn("SwallowingWorker", blob)
        # log-and-continue is the FLAGSHIP swallow: passing the caught
        # object to a logger is formatting, not delivery
        self.assertIn("LoggingSwallowWorker", blob)

    def test_suppression_pragma(self):
        patched = fx.SWALLOWED_WORKER_EXC_SRC.replace(
            "            except Exception:\n"
            "                continue",
            "            except Exception:  # shardlint: ignore[SL406] -- test\n"
            "                continue",
        ).replace(
            "            except Exception as e:",
            "            except Exception as e:  # shardlint: ignore[SL406] -- test",
        )
        self.assertNotEqual(patched, fx.SWALLOWED_WORKER_EXC_SRC)
        self.assertEqual(effectcheck.lint_source(patched, "heat_tpu/x.py"), [])

    def test_shipped_workers_clean(self):
        for rel in (
            "heat_tpu/serving/dispatcher.py",
            "heat_tpu/utils/data/partial_dataset.py",
            "heat_tpu/resilience/checkpoint.py",
        ):
            with open(os.path.join(ROOT, rel), encoding="utf-8") as f:
                src = f.read()
            found = [f for f in effectcheck.lint_source(src, rel) if f.rule == "SL406"]
            self.assertEqual(found, [], (rel, [repr(f) for f in found]))

    def test_mutation_swallowing_dispatch_handler_trips(self):
        """Seeded-bug proof: neuter the dispatcher's batch-failure
        handler (the drain path's contract — every owned future fails
        typed) and SL406 must trip at error."""
        with open(os.path.join(ROOT, "heat_tpu/serving/dispatcher.py"), encoding="utf-8") as f:
            src = f.read()
        anchor = (
            "        except Exception as e:  # program build/placement failure: fail the batch, not the loop\n"
            "            for r in reqs:\n"
            "                if not r.future.done():\n"
            "                    r.future.set_exception(e)\n"
            "            _tracing.end_span(batch_sp, status=\"error\")\n"
            "            return None\n"
        )
        self.assertIn(anchor, src)
        mutated = src.replace(
            anchor,
            "        except Exception:\n            return None\n",
        )
        found = [
            f
            for f in effectcheck.lint_source(mutated, "heat_tpu/serving/dispatcher.py")
            if f.rule == "SL406"
        ]
        self.assertTrue(found, "neutered handler not caught")
        self.assertTrue(all(f.severity == "error" for f in found))

    def test_rule_catalogued(self):
        self.assertIn("SL406", findings.RULES)


# ------------------------------------------------------------------ #
# DataParallelOptimizer checkpoint                                   #
# ------------------------------------------------------------------ #
class TestOptimizerCheckpoint(TestCase):
    def _toy(self, n=256, d=16, classes=4, seed=0):
        rng = np.random.default_rng(seed)
        w = rng.standard_normal((d, classes)).astype(np.float32)
        x = rng.standard_normal((n, d)).astype(np.float32)
        y = np.argmax(x @ w, axis=1).astype(np.int32)
        return ht.array(x, split=0), ht.array(y, split=0)

    def _mlp(self):
        import heat_tpu.nn as htnn

        return htnn.Sequential(htnn.Linear(16, 32), htnn.ReLU(), htnn.Linear(32, 4))

    def _fresh(self, wire_quant=None):
        import heat_tpu.nn as htnn
        import heat_tpu.optim as htoptim

        dp = htnn.DataParallel(self._mlp(), key=2)
        opt = htoptim.DataParallelOptimizer(
            htoptim.Adam(lr=0.01), dp, wire_quant=wire_quant
        )
        return dp, opt

    def test_resume_bit_identical(self):
        X, Y = self._toy()
        dp_ref, opt_ref = self._fresh()
        for _ in range(6):
            opt_ref.step(X, Y)
        with tempfile.TemporaryDirectory() as d:
            dp_a, opt_a = self._fresh()
            for i in range(3):
                opt_a.step(X, Y)
            ck.save(opt_a.checkpoint_state(), tag="dpo", step=3, directory=d)
            dp_b, opt_b = self._fresh()
            step, state, _ = ck.restore_latest(d, tag="dpo")
            opt_b.load_checkpoint_state(state)
            self.assertEqual(opt_b._iter, 3)
            for _ in range(step, 6):
                opt_b.step(X, Y)
            for a, b in zip(jax.tree.leaves(dp_ref.params), jax.tree.leaves(dp_b.params)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_ef_carry_round_trip_and_codec_guard(self):
        if P < 2:
            self.skipTest("quantized DP needs a distributed mesh")
        X, Y = self._toy()
        dp, opt = self._fresh(wire_quant="int8")
        for _ in range(2):
            opt.step(X, Y)
        with tempfile.TemporaryDirectory() as d:
            ck.save(opt.checkpoint_state(), tag="q", step=2, directory=d)
            _, state, _ = ck.restore_latest(d, tag="q")
            dp2, opt2 = self._fresh(wire_quant="int8")
            opt2.load_checkpoint_state(state)
            np.testing.assert_array_equal(
                np.asarray(jax.device_get(opt._ef_carry)),
                np.asarray(jax.device_get(opt2._ef_carry)),
            )
            # codec mismatch is refused — the carry is codec-specific —
            # and the refusal leaves the optimizer UNMUTATED (review
            # regression: validation precedes mutation)
            dp3, opt3 = self._fresh(wire_quant=None)
            before = [np.asarray(l) for l in jax.tree.leaves(dp3.params)]
            before_iter = opt3._iter
            with self.assertRaises(ValueError):
                opt3.load_checkpoint_state(state)
            self.assertEqual(opt3._iter, before_iter)
            for a, b in zip(before, jax.tree.leaves(dp3.params)):
                np.testing.assert_array_equal(a, np.asarray(b))

    def test_ef_carry_fold_preserves_total_residual(self):
        """World-resize restore folds carry rows as r -> r % p_new with
        the TOTAL outstanding residual (what error feedback re-injects)
        preserved exactly."""
        if P < 2:
            self.skipTest("needs a multi-device mesh")
        X, Y = self._toy()
        dp, opt = self._fresh(wire_quant="int8")
        for _ in range(2):
            opt.step(X, Y)
        carry = np.asarray(jax.device_get(opt._ef_carry))
        with tempfile.TemporaryDirectory() as d:
            ck.save(opt.checkpoint_state(), tag="fold", step=1, directory=d)
            sub = comm_mod.MeshCommunication(comm_mod.MPI_WORLD.devices[: P // 2 + 1])
            try:
                comm_mod.use_comm(sub)
                _, state, _ = ck.restore_latest(d, tag="fold")
                dp2, opt2 = self._fresh(wire_quant="int8")
                opt2.load_checkpoint_state(state)
                folded = np.asarray(jax.device_get(opt2._ef_carry))
                self.assertEqual(folded.shape[0], sub.size)
                # fold-then-sum reassociates the f32 additions vs the
                # direct 8-row sum: bit equality is not the contract
                # here (same-size restores ARE bit-pinned above), the
                # preserved TOTAL is
                np.testing.assert_allclose(
                    folded.sum(axis=0), carry.sum(axis=0), rtol=1e-5, atol=1e-7
                )
            finally:
                _restore_full_world()


if __name__ == "__main__":
    import unittest

    unittest.main()
