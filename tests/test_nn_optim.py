"""Deep-learning layer tests: DataParallel + DataParallelOptimizer + DASO.

The analog of the reference's examples/nn/mnist.py training loop (BASELINE
config #5) exercised on the virtual 8-device mesh: a synthetic separable
classification task must train to high accuracy, the DP step's loss must
match a hand-rolled single-device replica step, and DASO must converge with
staggered global syncs.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import heat_tpu as ht
from heat_tpu import nn as htnn
from heat_tpu import optim as htoptim


def _toy_problem(n=512, d=16, classes=4, seed=0):
    rng = np.random.default_rng(seed)
    w = rng.standard_normal((d, classes)).astype(np.float32)
    x = rng.standard_normal((n, d)).astype(np.float32)
    y = np.argmax(x @ w + 0.1 * rng.standard_normal((n, classes)).astype(np.float32), axis=1)
    return x, y.astype(np.int32)


def _mlp(d=16, classes=4):
    return htnn.Sequential(
        htnn.Linear(d, 32),
        htnn.ReLU(),
        htnn.Linear(32, classes),
    )


class TestDataParallel:
    def test_forward_shapes_and_split(self):
        model = htnn.Sequential(htnn.Linear(8, 3), htnn.Tanh())
        dp = htnn.DataParallel(model, key=0)
        x = ht.random.randn(40, 8, split=0)
        out = dp(x)
        assert out.shape == (40, 3)
        assert out.split == 0
        # forward matches the functional apply on the logical array
        ref = model.apply(dp.params, x.larray)
        np.testing.assert_allclose(out.numpy(), np.asarray(ref), rtol=1e-5, atol=1e-5)

    def test_training_converges(self):
        x_np, y_np = _toy_problem()
        x = ht.array(x_np, split=0)
        y = ht.array(y_np, split=0)
        dp = htnn.DataParallel(_mlp(), key=1)
        opt = htoptim.DataParallelOptimizer(htoptim.Adam(lr=0.01), dp)
        losses = [float(opt.step(x, y)) for _ in range(60)]
        assert losses[-1] < 0.25 * losses[0], losses[::10]
        preds = np.argmax(dp(x).numpy(), axis=1)
        assert (preds == y_np).mean() > 0.9

    def test_dp_matches_single_device_replica(self):
        """Grad-allreduce semantics: the sharded-batch step must produce the
        same parameters as an unsharded replica computing the global-mean
        loss (the invariant the reference's Allreduce hooks maintain,
        data_parallel.py:219-237)."""
        x_np, y_np = _toy_problem(n=64, seed=3)
        model = _mlp()
        dp = htnn.DataParallel(model, key=5)
        # deep-copy: the fused step donates the live param buffers
        params0 = jax.tree.map(lambda a: jnp.array(a, copy=True), dp.params)
        opt = htoptim.DataParallelOptimizer(htoptim.SGD(lr=0.1), dp)
        loss_dist = float(opt.step(ht.array(x_np, split=0), ht.array(y_np, split=0)))

        # oracle: same init, plain single-array step
        import optax
        tx = optax.sgd(0.1)
        st = tx.init(params0)
        ce = htnn.CrossEntropyLoss()

        def lf(p):
            return ce.raw(model.apply(p, jnp.asarray(x_np)), jnp.asarray(y_np))

        loss_ref, g = jax.value_and_grad(lf)(params0)
        upd, _ = tx.update(g, st, params0)
        ref_params = optax.apply_updates(params0, upd)

        assert abs(loss_dist - float(loss_ref)) < 1e-5
        for a, b in zip(jax.tree.leaves(dp.params), jax.tree.leaves(ref_params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)

    def test_uneven_batch_masked(self):
        """Padded batch rows must not contribute to loss or gradients."""
        x_np, y_np = _toy_problem(n=100, seed=4)  # 100 over 8 devices: pad to 104
        dp = htnn.DataParallel(_mlp(), key=2)
        opt = htoptim.DataParallelOptimizer(htoptim.SGD(lr=0.05), dp)
        loss = float(opt.step(ht.array(x_np, split=0), ht.array(y_np, split=0)))

        ce = htnn.CrossEntropyLoss()
        dp2 = htnn.DataParallel(_mlp(), key=2)
        ref = float(ce.raw(dp2.module.apply(dp2.params, jnp.asarray(x_np)), jnp.asarray(y_np)))
        assert abs(loss - ref) < 1e-5

    def test_loss_callable_on_dndarrays(self):
        x_np, y_np = _toy_problem(n=32, seed=6)
        dp = htnn.DataParallel(_mlp(), key=0)
        out = dp(ht.array(x_np, split=0))
        loss = htnn.CrossEntropyLoss()(out, ht.array(y_np, split=0))
        ref = htnn.CrossEntropyLoss().raw(dp(jnp.asarray(x_np)), jnp.asarray(y_np))
        assert abs(float(loss) - float(ref)) < 1e-5


class TestDASO:
    @pytest.fixture(autouse=True)
    def _needs_even_mesh(self):
        # DASO's two-level ("node", "local") mesh factorization requires
        # divisibility — same constraint as the reference's node groups
        if ht.get_comm().size % 2 != 0:
            pytest.skip("DASO n_nodes=2 needs an even mesh")

    def test_daso_converges_and_syncs(self):
        x_np, y_np = _toy_problem(n=512, seed=7)
        x = ht.array(x_np, split=0)
        y = ht.array(y_np, split=0)
        dp = htnn.DataParallel(_mlp(), key=1)
        daso = htoptim.DASO(htoptim.Adam(lr=0.01), dp, n_nodes=2, global_skip=4)
        losses = [float(daso.step(x, y)) for _ in range(60)]
        assert losses[-1] < 0.3 * losses[0], losses[::10]
        # eval through the wrapped model must see trained weights WITHOUT an
        # explicit sync (the reference mutates the torch model in place)
        preds = np.argmax(dp(x).numpy(), axis=1)
        assert (preds == y_np).mean() > 0.85
        # node copies agree right after a forced sync
        daso.sync_params()
        stacked = jax.tree.leaves(daso.params)[0]
        np.testing.assert_allclose(np.asarray(stacked[0]), np.asarray(stacked[1]), rtol=1e-6)

    def test_daso_global_sync_equalizes_nodes(self):
        x_np, y_np = _toy_problem(n=256, seed=8)
        x = ht.array(x_np, split=0)
        y = ht.array(y_np, split=0)
        dp = htnn.DataParallel(_mlp(), key=3)
        daso = htoptim.DASO(htoptim.SGD(lr=0.05), dp, n_nodes=2, global_skip=3, compression=False)
        for i in range(1, 7):
            daso.step(x, y)
            leaf = np.asarray(jax.tree.leaves(daso.params)[0])
            same = np.allclose(leaf[0], leaf[1], rtol=1e-6, atol=1e-7)
            assert same == (i % 3 == 0), f"iter {i}: node agreement {same}"

    def test_daso_state_dict_and_load(self):
        """Checkpoints during DASO training must capture trained weights,
        and loading must redirect subsequent forwards."""
        x_np, y_np = _toy_problem(n=256, seed=12)
        x, y = ht.array(x_np, split=0), ht.array(y_np, split=0)
        dp = htnn.DataParallel(_mlp(), key=9)
        init_leaf = np.asarray(jax.tree.leaves(dp.params)[0]).copy()
        daso = htoptim.DASO(htoptim.SGD(lr=0.1), dp, n_nodes=2, global_skip=2)
        for _ in range(5):
            daso.step(x, y)
        ckpt = dp.state_dict()
        trained_leaf = np.asarray(jax.tree.leaves(ckpt)[0])
        assert not np.allclose(trained_leaf, init_leaf), "state_dict returned init weights"
        out_before = dp(x).numpy()
        for _ in range(5):
            daso.step(x, y)
        dp.load_state_dict(ckpt)
        np.testing.assert_allclose(dp(x).numpy(), out_before, rtol=1e-5, atol=1e-6)

    def test_daso_custom_loss_raw_contract(self):
        """A loss implementing only the documented raw() API must work."""
        class L2Loss:
            def raw(self, output, target, weight=None):
                per = jnp.sum((output - jax.nn.one_hot(target, output.shape[-1])) ** 2, axis=-1)
                if weight is not None:
                    return jnp.sum(per * weight) / jnp.maximum(jnp.sum(weight), 1.0)
                return jnp.mean(per)

        x_np, y_np = _toy_problem(n=128, seed=13)
        dp = htnn.DataParallel(_mlp(), key=4)
        daso = htoptim.DASO(htoptim.SGD(lr=0.05), dp, n_nodes=2, loss=L2Loss())
        l0 = float(daso.step(ht.array(x_np, split=0), ht.array(y_np, split=0)))
        l1 = float(daso.step(ht.array(x_np, split=0), ht.array(y_np, split=0)))
        assert np.isfinite(l0) and np.isfinite(l1)

    def test_daso_lr_scheduler(self):
        dp = htnn.DataParallel(_mlp(), key=0)
        daso = htoptim.DASO(htoptim.SGD(lr=0.2), dp, n_nodes=2)
        sched = htoptim.lr_scheduler.ExponentialLR(daso, gamma=0.5)
        assert abs(daso.lr - 0.2) < 1e-8
        sched.step()
        assert abs(daso.lr - 0.1) < 1e-8

    def test_epoch_loss_logic_matches_reference_policy(self):
        """The schedule must take the reference's decisions verbatim on a
        scripted loss sequence (reference dp_optimizer.py:354-470):
        warmup zeros → post-warmup (4,1,1) → plateaus collapse the skips
        by the reduction factor → bottoming out at gs=1 widens back to
        max_gs → cooldown zeros."""
        dp = htnn.DataParallel(_mlp(), key=0)
        daso = htoptim.DASO(
            htoptim.SGD(lr=0.01), dp, n_nodes=2,
            total_epochs=20, warmup_epochs=2, cooldown_epochs=2,
            stability_level=0.05, max_global_skips=8,
        )
        # hand-simulated reference trace: (loss, gs, ls, btw) AFTER the call
        flat = 0.8  # < 5% change → counts as a bad epoch
        trace = [
            (1.0, 0, 0, 0),   # warmup epoch 0
            (0.9, 0, 0, 0),   # warmup epoch 1
            (flat, 4, 1, 1),  # end of warmup: (4,1,1); best=0.8, improving
            (flat, 4, 1, 1),  # bad 1
            (flat, 4, 1, 1),  # bad 2 (patience)
            (flat, 2, 1, 1),  # bad 3 > patience → plateau: gs 4→2, clamps
            (flat, 2, 1, 1),  # counter reset after detection: bad 1
            (flat, 2, 1, 1),  # bad 2
            (flat, 1, 1, 1),  # plateau → gs 2→1
            (flat, 1, 1, 1),
            (flat, 1, 1, 1),
            (flat, 8, 2, 2),  # plateau at gs=1 → widen to max_gs
            (0.2, 8, 2, 2),   # real improvement: counter resets, no change
            (flat, 8, 2, 2),  # bad 1 (vs best 0.2)
            (flat, 8, 2, 2),  # bad 2
            (flat, 4, 1, 1),  # plateau → gs 8→4, ls 2→1, btw 2→1
            (flat, 4, 1, 1),
            (flat, 4, 1, 1),
            (flat, 0, 0, 0),  # epoch 18 ≥ total-cooldown → cooldown zeros
            (flat, 0, 0, 0),  # epoch 19
        ]
        for i, (loss, gs, ls, btw) in enumerate(trace):
            daso.epoch_loss_logic(loss)
            assert (daso.global_skip, daso.local_skip, daso.batches_to_wait) == (
                gs, ls, btw
            ), f"epoch {i}: got {(daso.global_skip, daso.local_skip, daso.batches_to_wait)}"

    def test_daso_converges_through_schedule(self):
        """End-to-end: training drives the schedule through warmup and
        adaptation while the loss still decreases."""
        x_np, y_np = _toy_problem(n=256, seed=11)
        x = ht.array(x_np, split=0)
        y = ht.array(y_np, split=0)
        dp = htnn.DataParallel(_mlp(), key=3)
        daso = htoptim.DASO(htoptim.SGD(lr=0.05), dp, n_nodes=2,
                            total_epochs=8, warmup_epochs=1, cooldown_epochs=1)
        epoch_losses = []
        for _ in range(8):
            losses = [float(daso.step(x, y)) for _ in range(4)]
            epoch_losses.append(losses[-1])
            daso.epoch_loss_logic(epoch_losses[-1])
        assert daso.epoch == 8
        assert epoch_losses[-1] < epoch_losses[0], epoch_losses
        # cooldown reached: full sync restored
        assert daso.global_skip == 0


class TestSchedulersAndUtils:
    def test_step_lr(self):
        dp = htnn.DataParallel(_mlp(), key=0)
        opt = htoptim.DataParallelOptimizer(htoptim.SGD(lr=0.1), dp)
        sched = htoptim.lr_scheduler.StepLR(opt, step_size=2, gamma=0.1)
        sched.step()
        assert abs(opt.lr - 0.1) < 1e-8
        sched.step()
        assert abs(opt.lr - 0.01) < 1e-8
        # the updated lr actually drives the next step
        x_np, y_np = _toy_problem(n=32, seed=1)
        before = [np.asarray(l).copy() for l in jax.tree.leaves(dp.params)]
        opt.step(ht.array(x_np, split=0), ht.array(y_np, split=0))
        after = jax.tree.leaves(dp.params)
        deltas = [np.abs(np.asarray(a) - b).max() for a, b in zip(after, before)]
        assert max(deltas) < 0.05  # tiny lr → tiny update

    def test_plateau_detector(self):
        det = htoptim.DetectMetricPlateau(patience=2)
        assert not det.test_if_improving(1.0)
        assert not det.test_if_improving(0.5)
        assert not det.test_if_improving(0.5)
        assert not det.test_if_improving(0.5)
        assert det.test_if_improving(0.5)  # patience exceeded
        state = det.get_state()
        det2 = htoptim.DetectMetricPlateau()
        det2.set_state(state)
        assert det2.best == det.best

    def test_nn_flax_fallback(self):
        import flax.linen as linen
        assert htnn.Conv is linen.Conv

    def test_optim_optax_fallback(self):
        import optax
        assert htoptim.cosine_decay_schedule is optax.cosine_decay_schedule


class TestRingAttention:
    """Sequence-parallel exact attention (nn.attention) — the TPU-native
    long-context primitive (no reference analog; SURVEY §5 names the ring
    mechanism of distance.py:262-359 as its building block)."""

    @staticmethod
    def _dense(q, k, v, causal, scale):
        s = np.einsum("...qd,...kd->...qk", q, k) * scale
        if causal:
            S1, S2 = s.shape[-2:]
            s = np.where(np.tril(np.ones((S1, S2), bool)), s, -1e30)
        p = np.exp(s - s.max(-1, keepdims=True))
        return np.einsum("...qk,...kd->...qd", p / p.sum(-1, keepdims=True), v)

    @pytest.mark.parametrize("S", [64, 61, 11])
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense(self, S, causal):
        rng = np.random.default_rng(S)
        qn, kn, vn = (rng.standard_normal((S, 8)).astype(np.float32) for _ in range(3))
        q, k, v = (ht.array(x, split=0) for x in (qn, kn, vn))
        out = ht.nn.ring_attention(q, k, v, causal=causal)
        assert out.split == 0
        ref = self._dense(qn, kn, vn, causal, 1 / np.sqrt(8))
        np.testing.assert_allclose(out.numpy(), ref, rtol=2e-4, atol=2e-5)
        phys = np.asarray(jax.device_get(out._phys))
        assert np.all(phys[S:] == 0)

    def test_batched_heads(self):
        rng = np.random.default_rng(0)
        B, H, S, D = 2, 3, 33, 8
        qn, kn, vn = (rng.standard_normal((B, H, S, D)).astype(np.float32) for _ in range(3))
        q, k, v = (ht.array(x, split=2) for x in (qn, kn, vn))
        out = ht.nn.ring_attention(q, k, v, causal=True)
        ref = self._dense(qn, kn, vn, True, 1 / np.sqrt(D))
        np.testing.assert_allclose(out.numpy(), ref, rtol=2e-4, atol=2e-5)

    def test_replicated_and_self(self):
        rng = np.random.default_rng(1)
        xn = rng.standard_normal((17, 8)).astype(np.float32)
        x = ht.array(xn)
        out = ht.nn.ring_self_attention(x)
        ref = self._dense(xn, xn, xn, False, 1 / np.sqrt(8))
        np.testing.assert_allclose(out.numpy(), ref, rtol=2e-4, atol=2e-5)

    def test_differentiable(self):
        import jax.numpy as jnp
        from heat_tpu.nn.attention import _ring_attention_program

        comm = ht.get_comm()
        prog = _ring_attention_program(
            comm.mesh, comm.axis_name, 2, 0, 64, 64, False, float(1 / np.sqrt(8)), "float32"
        )
        qj = comm.shard(jnp.asarray(np.random.default_rng(2).standard_normal((64, 8)).astype(np.float32)), 0)
        g = jax.grad(lambda a: prog(a, a, a).sum())(qj)
        assert np.isfinite(np.asarray(jax.device_get(g))).all()

    @pytest.mark.parametrize("S,chunk", [(8 * 8, 3), (8 * 8 - 5, 4), (8 * 8, 16)])
    def test_inner_chunking_matches_unchunked(self, S, chunk):
        # the per-step K/V tiling (bounded live memory at scale) must be
        # numerically invisible, incl. non-dividing chunks and uneven
        # global sequence lengths, and stay differentiable
        import jax.numpy as jnp
        from heat_tpu.nn.attention import _ring_attention_program

        comm = ht.get_comm()
        D = 8
        scale = float(1 / np.sqrt(D))
        rng = np.random.default_rng(S + chunk)
        qn, kn, vn = (rng.standard_normal((S, D)).astype(np.float32) for _ in range(3))
        args = tuple(comm.shard(jnp.asarray(a), 0) for a in (qn, kn, vn))
        S_pad = args[0].shape[0]
        prog_c = _ring_attention_program(
            comm.mesh, comm.axis_name, 2, 0, S, S, True, scale, "float32", chunk
        )
        prog_full = _ring_attention_program(
            comm.mesh, comm.axis_name, 2, 0, S, S, True, scale, "float32", S_pad
        )
        out_c = np.asarray(jax.device_get(prog_c(*args)))[:S]
        out_f = np.asarray(jax.device_get(prog_full(*args)))[:S]
        np.testing.assert_allclose(out_c, out_f, rtol=1e-5, atol=1e-6)
        # the backward through the inner scan + dynamic_slice transpose
        # must MATCH the unchunked gradients (not merely be finite)
        def loss(prog):
            return lambda q, k, v: (prog(q, k, v) ** 2).sum()
        g_c = jax.grad(loss(prog_c), argnums=(0, 1, 2))(*args)
        g_f = jax.grad(loss(prog_full), argnums=(0, 1, 2))(*args)
        for gc, gf, name in zip(g_c, g_f, "qkv"):
            np.testing.assert_allclose(
                np.asarray(jax.device_get(gc)), np.asarray(jax.device_get(gf)),
                rtol=1e-4, atol=1e-5, err_msg=f"d{name} mismatch",
            )

    def test_gradient_matches_dense_oracle(self):
        # the ring program's grad (through scan + ppermute transpose
        # rules) must equal the dense attention gradient, not merely be
        # finite — this pins training-through-ring-attention numerics
        import jax.numpy as jnp
        from heat_tpu.nn.attention import _ring_attention_program

        comm = ht.get_comm()
        S, D = 8 * comm.size, 8
        scale = float(1 / np.sqrt(D))
        rng = np.random.default_rng(7)
        qn, kn, vn = (rng.standard_normal((S, D)).astype(np.float32) for _ in range(3))
        prog = _ring_attention_program(
            comm.mesh, comm.axis_name, 2, 0, S, S, True, scale, "float32"
        )

        def dense(q, k, v):
            s = (q @ k.T) * scale
            s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, jnp.finfo(jnp.float32).min)
            p = jax.nn.softmax(s, axis=-1)
            return p @ v

        tgt = jnp.asarray(rng.standard_normal((S, D)).astype(np.float32))
        args = tuple(comm.shard(jnp.asarray(a), 0) for a in (qn, kn, vn))
        g_ring = jax.grad(lambda q, k, v: jnp.sum((prog(q, k, v) - tgt) ** 2), argnums=(0, 1, 2))(*args)
        g_dense = jax.grad(
            lambda q, k, v: jnp.sum((dense(q, k, v) - tgt) ** 2), argnums=(0, 1, 2)
        )(jnp.asarray(qn), jnp.asarray(kn), jnp.asarray(vn))
        for gr, gd, name in zip(g_ring, g_dense, "qkv"):
            np.testing.assert_allclose(
                np.asarray(jax.device_get(gr)), np.asarray(gd),
                rtol=2e-3, atol=2e-4, err_msg=f"d{name} mismatch",
            )

    def test_wrong_split_raises(self):
        x = ht.array(np.zeros((4, 8), dtype=np.float32), split=1)
        with pytest.raises(ValueError):
            ht.nn.ring_attention(x, x, x)

    def test_value_head_dim_differs(self):
        # Dv != Dq is legal attention; must work on the DISTRIBUTED ring
        rng = np.random.default_rng(3)
        S = 33
        qn = rng.standard_normal((S, 4)).astype(np.float32)
        kn = rng.standard_normal((S, 4)).astype(np.float32)
        vn = rng.standard_normal((S, 6)).astype(np.float32)
        out = ht.nn.ring_attention(*(ht.array(x, split=0) for x in (qn, kn, vn)))
        assert out.shape == (S, 6)
        ref = self._dense(qn, kn, vn, False, 1 / np.sqrt(4))
        np.testing.assert_allclose(out.numpy(), ref, rtol=2e-4, atol=2e-5)


class TestRingKernelAttention:
    """Kernel-backed ring attention (VERDICT r4 #1): each ring step runs
    the splash/flash Pallas kernel in save-residuals form and the per-step
    (out, lse) combine must be EXACT against the blocked-XLA ring oracle.
    CPU meshes run the kernels in Mosaic interpret mode."""

    B, H, S, D = 1, 2, 1024, 64

    def _mk(self, dtype=np.float32, seed=0):
        rng = np.random.default_rng(seed)
        return tuple(
            rng.standard_normal((self.B, self.H, self.S, self.D)).astype(dtype)
            for _ in range(3)
        )

    @pytest.mark.slow
    @pytest.mark.parametrize("causal", [False, True])
    def test_kernel_ring_matches_blocked_oracle_p8(self, causal):
        import heat_tpu.nn.attention as att

        comm = ht.get_comm()
        scale = float(1 / np.sqrt(self.D))
        qn, kn, vn = self._mk()
        q, k, v = (ht.array(x, split=2) for x in (qn, kn, vn))
        kprog = att._ring_attention_kernel_program(
            comm.mesh, comm.axis_name, self.S, self.S, self.B, self.H,
            self.D, causal, scale, "float32", True,
        )
        assert kprog is not None
        out_k = np.asarray(jax.device_get(kprog(q._phys, k._phys, v._phys)))
        prog = att._ring_attention_program(
            comm.mesh, comm.axis_name, 4, 2, self.S, self.S, causal,
            scale, "float32",
        )
        out_b = np.asarray(jax.device_get(prog(q._phys, k._phys, v._phys)))
        np.testing.assert_allclose(out_k, out_b, rtol=2e-5, atol=2e-6)

    @pytest.mark.slow
    def test_public_dispatch_routes_to_kernel_and_matches_dense(self, monkeypatch):
        import heat_tpu.nn.attention as att

        monkeypatch.setattr(att, "_RING_KERNEL_INTERPRET", True)
        calls = []
        orig = att._ring_attention_kernel_program

        def spy(*a, **kw):
            r = orig(*a, **kw)
            calls.append(r is not None)
            return r

        monkeypatch.setattr(att, "_ring_attention_kernel_program", spy)
        qn, kn, vn = self._mk(seed=1)
        q, k, v = (ht.array(x, split=2) for x in (qn, kn, vn))
        out = ht.nn.ring_attention(q, k, v, causal=True)
        assert calls == [True], "kernel ring program was not dispatched"
        assert out.split == 2
        ref = TestRingAttention._dense(qn, kn, vn, True, 1 / np.sqrt(self.D))
        np.testing.assert_allclose(out.numpy(), ref, rtol=2e-4, atol=2e-5)

    @pytest.mark.slow
    def test_kernel_ring_p1_wrapper_is_exact(self):
        """Size-1 ring: the wrapper (scan of one step + switch) around the
        kernel must be numerically invisible — the real-chip bench pins
        its cost; this pins its numerics."""
        import heat_tpu.nn.attention as att
        from jax.sharding import Mesh

        mesh1 = Mesh(np.asarray(jax.devices()[:1]), ("d",))
        scale = float(1 / np.sqrt(self.D))
        qn, kn, vn = self._mk(seed=2)
        kprog = att._ring_attention_kernel_program(
            mesh1, "d", self.S, self.S, self.B, self.H, self.D, True,
            scale, "float32", True,
        )
        assert kprog is not None
        out_k = np.asarray(jax.device_get(kprog(*map(jnp.asarray, (qn, kn, vn)))))
        ref = TestRingAttention._dense(qn, kn, vn, True, scale)
        np.testing.assert_allclose(out_k, ref, rtol=2e-4, atol=2e-5)

    @pytest.mark.slow
    def test_kernel_ring_bf16(self):
        import heat_tpu.nn.attention as att

        comm = ht.get_comm()
        scale = float(1 / np.sqrt(self.D))
        qn, kn, vn = self._mk(seed=3)
        args = tuple(
            ht.array(x, split=2).astype(ht.bfloat16)._phys for x in (qn, kn, vn)
        )
        kprog = att._ring_attention_kernel_program(
            comm.mesh, comm.axis_name, self.S, self.S, self.B, self.H,
            self.D, True, scale, "bfloat16", True,
        )
        assert kprog is not None
        out_k = np.asarray(jax.device_get(kprog(*args))).astype(np.float32)
        ref = TestRingAttention._dense(qn, kn, vn, True, scale)
        # bf16 storage + bf16 kernel matmuls: ~8-bit mantissa tolerance
        np.testing.assert_allclose(out_k, ref, rtol=0.06, atol=0.06)

    def test_kernel_ring_hlo_ppermute_structure(self):
        """The kernel ring is UNROLLED over the static ring length:
        exactly 2(p-1) collective-permutes — K and V per hop, and the
        final wasted rotation elided — never an all-gather. Same total
        ICI bytes as the blocked ring's 2-permute scan, minus one hop.
        S is derived from the mesh size so the odd-mesh CI leg exercises
        it too."""
        import heat_tpu.nn.attention as att

        comm = ht.get_comm()
        S = 128 * comm.size  # 128-row shards: smallest splash block
        scale = float(1 / np.sqrt(self.D))
        kprog = att._ring_attention_kernel_program(
            comm.mesh, comm.axis_name, S, S, self.B, self.H,
            self.D, True, scale, "float32", True,
        )
        assert kprog is not None
        txt = kprog.as_text()
        n_pp = txt.count(" collective-permute(") + txt.count("collective-permute-start(")
        want = 2 * (comm.size - 1)
        assert n_pp == want, f"kernel ring ppermute count {n_pp} != {want}"
        assert " all-gather(" not in txt and "all-gather-start(" not in txt

    @pytest.mark.slow
    @pytest.mark.parametrize("causal", [False, True])
    def test_scan_body_matches_blocked_oracle_p8(self, causal, monkeypatch):
        """The scan-with-carry ring body — the composition real-TPU f32
        (flash) dispatch runs, which the unrolled-by-default CPU suite
        would otherwise never compile — must match the blocked oracle
        too (code-review r5)."""
        import heat_tpu.nn.attention as att

        monkeypatch.setattr(att, "_RING_KERNEL_FORCE_SCAN", True)
        att._ring_attention_kernel_callable.cache_clear()
        att._ring_attention_kernel_program.cache_clear()
        try:
            comm = ht.get_comm()
            scale = float(1 / np.sqrt(self.D))
            qn, kn, vn = self._mk(seed=4)
            q, k, v = (ht.array(x, split=2) for x in (qn, kn, vn))
            kprog = att._ring_attention_kernel_program(
                comm.mesh, comm.axis_name, self.S, self.S, self.B, self.H,
                self.D, causal, scale, "float32", True,
            )
            assert kprog is not None
            out_k = np.asarray(jax.device_get(kprog(q._phys, k._phys, v._phys)))
            prog = att._ring_attention_program(
                comm.mesh, comm.axis_name, 4, 2, self.S, self.S, causal,
                scale, "float32",
            )
            out_b = np.asarray(jax.device_get(prog(q._phys, k._phys, v._phys)))
            np.testing.assert_allclose(out_k, out_b, rtol=2e-5, atol=2e-6)
        finally:
            att._ring_attention_kernel_callable.cache_clear()
            att._ring_attention_kernel_program.cache_clear()

    def test_kernel_build_failure_raises(self, monkeypatch):
        """Only shape gates may answer None: a kernel that fails to BUILD
        is an error the caller sees, never a cached silent fallback to
        the blocked ring."""
        import heat_tpu.nn.attention as att

        def broken(*a, **kw):
            raise RuntimeError("Mosaic says no")

        monkeypatch.setattr(att, "_build_splash_mha", broken)
        att._ring_step_kernels.cache_clear()
        try:
            with pytest.raises(RuntimeError, match="Mosaic says no"):
                att._ring_step_kernels(1, 2, 128, 128, 64, 0.125, "bfloat16", True)
            # the shape gate still answers None without building anything
            assert att._ring_step_kernels(1, 2, 100, 100, 64, 0.125, "bfloat16", True) is None
        finally:
            att._ring_step_kernels.cache_clear()

    def test_ineligible_signatures_fall_back(self):
        import heat_tpu.nn.attention as att

        comm = ht.get_comm()
        # non-divisible global sequence → pad rows the kernels cannot mask
        assert (
            att._ring_attention_kernel_program(
                comm.mesh, comm.axis_name, 1001, 1001, 1, 2, 64, False,
                0.125, "float32", True,
            )
            is None
        )
        # causal with mismatched q/kv lengths has no diagonal kernel
        assert (
            att._ring_attention_kernel_program(
                comm.mesh, comm.axis_name, 1024, 2048, 1, 2, 64, True,
                0.125, "float32", True,
            )
            is None
        )
        # tracers (user jit/grad) must never take the kernel path, even
        # when the platform gate is open
        import unittest.mock as mock

        hit = []

        def probe(x):
            with mock.patch.object(att, "_RING_KERNEL_INTERPRET", True):
                hit.append(att._ring_kernel_refusal(x, x, x, 4, 2, jnp.float32))
            return x

        jax.make_jaxpr(probe)(jnp.zeros((1, 2, 64, 64), jnp.float32))
        assert len(hit) == 1 and "traced" in hit[0]


class TestPallasAttentionGating:
    """The Mosaic flash kernel is a TPU-only fast path: on any other
    backend the gate must return None (blocked program serves) and say
    why in ``last_decisions``."""

    def test_gate_off_on_non_tpu_backend(self):
        import jax
        import jax.numpy as jnp
        from heat_tpu.nn import attention as att

        if jax.default_backend() == "tpu":
            pytest.skip("gate is open on a real TPU backend")
        x = jnp.zeros((1, 1, 512, 64), jnp.float32)
        assert att._pallas_attention(x, x, x, False, 0.125) is None
        dec = att.last_decisions()[("single", x.shape, x.shape, "float32", False)]
        assert dec == {"path": "blocked", "why": "backend is not tpu"}

    def test_shape_gate_backend_independent(self):
        import jax.numpy as jnp
        from heat_tpu.nn.attention import _pallas_attention_fits

        good = (1, 1, 512, 64)
        assert _pallas_attention_fits(good, good, good, jnp.float32)
        assert _pallas_attention_fits(good, good, good, jnp.bfloat16)
        # 3-D input, odd seq, odd head dim, f64, cross-attention lengths,
        # mismatched value head dim: all rejected before any compile
        assert not _pallas_attention_fits((8, 512, 64), (8, 512, 64), (8, 512, 64), jnp.float32)
        assert not _pallas_attention_fits((1, 1, 500, 64), (1, 1, 500, 64), (1, 1, 500, 64), jnp.float32)
        assert not _pallas_attention_fits((1, 1, 512, 60), (1, 1, 512, 60), (1, 1, 512, 60), jnp.float32)
        assert not _pallas_attention_fits(good, good, good, jnp.float64)
        assert not _pallas_attention_fits(good, (1, 1, 1024, 64), (1, 1, 1024, 64), jnp.float32)
        assert not _pallas_attention_fits(good, good, (1, 1, 512, 128), jnp.float32)


class TestSDPAAlias:
    """torch-parity F.scaled_dot_product_attention over ring/blocked
    attention (reference functional is a torch passthrough)."""

    def test_matches_oracle_both_routes(self):
        from heat_tpu.nn import functional as F

        rng = np.random.default_rng(0)
        S, D = 33, 8
        qn, kn, vn = (rng.standard_normal((S, D)).astype(np.float32) for _ in range(3))
        s_ = qn @ kn.T / np.sqrt(D)
        s_ = np.where(np.tril(np.ones((S, S), bool)), s_, -1e30)
        p_ = np.exp(s_ - s_.max(-1, keepdims=True)); p_ /= p_.sum(-1, keepdims=True)
        ref = p_ @ vn
        out = F.scaled_dot_product_attention(
            ht.array(qn, split=0), ht.array(kn, split=0), ht.array(vn, split=0),
            is_causal=True,
        )
        np.testing.assert_allclose(out.numpy(), ref, rtol=2e-4, atol=2e-5)
        out2 = F.scaled_dot_product_attention(
            jnp.asarray(qn), jnp.asarray(kn), jnp.asarray(vn), is_causal=True
        )
        np.testing.assert_allclose(np.asarray(out2), ref, rtol=2e-4, atol=2e-5)
        with pytest.raises(NotImplementedError):
            F.scaled_dot_product_attention(jnp.asarray(qn), jnp.asarray(kn), jnp.asarray(vn), attn_mask=1)


class TestConvLayers:
    """CNN layer parity vs torch-CPU oracles — the reference's flagship
    example is a Conv2d/Dropout2d/max_pool2d net (examples/nn/mnist.py:26)
    served there by the torch passthrough."""

    def _torch(self):
        torch = pytest.importorskip("torch")
        return torch

    def test_conv2d_matches_torch(self):
        torch = self._torch()
        import jax
        import jax.numpy as jnp

        rng = np.random.default_rng(0)
        x = rng.standard_normal((2, 3, 12, 12)).astype(np.float32)
        for stride, padding in [(1, 0), (2, 1), (1, (2, 1))]:
            m = htnn.Conv2d(3, 5, 3, stride=stride, padding=padding)
            params = m.init(jax.random.PRNGKey(0))
            tconv = torch.nn.Conv2d(3, 5, 3, stride=stride, padding=padding)
            with torch.no_grad():
                tconv.weight.copy_(torch.from_numpy(np.asarray(params["weight"])))
                tconv.bias.copy_(torch.from_numpy(np.asarray(params["bias"])))
                ref = tconv(torch.from_numpy(x)).numpy()
            got = np.asarray(m.apply(params, jnp.asarray(x)))
            np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)

    def test_conv2d_same_padding_matches_torch(self):
        torch = self._torch()
        import torch.nn.functional as tF
        import jax.numpy as jnp

        rng = np.random.default_rng(2)
        x = rng.standard_normal((1, 1, 4, 4)).astype(np.float32)
        # even kernels: torch pads the odd element on the HIGH side
        for k in [2, 3, (2, 3)]:
            w_shape = (1, 1) + (k if isinstance(k, tuple) else (k, k))
            w = rng.standard_normal(w_shape).astype(np.float32)
            ref = tF.conv2d(torch.from_numpy(x), torch.from_numpy(w), padding="same").numpy()
            m = htnn.Conv2d(1, 1, k, padding="same", bias=False)
            got = np.asarray(m.apply({"weight": jnp.asarray(w)}, jnp.asarray(x)))
            np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)
        # torch parity: strided 'same' is rejected
        with pytest.raises(ValueError):
            htnn.Conv2d(1, 1, 3, stride=2, padding="same")

    def test_maxpool_integer_dtype(self):
        import jax.numpy as jnp

        x = jnp.arange(16, dtype=jnp.int32).reshape(1, 1, 4, 4)
        out = np.asarray(htnn.MaxPool2d(2).apply({}, x))
        np.testing.assert_array_equal(out[0, 0], [[5, 7], [13, 15]])

    def test_pools_match_torch(self):
        torch = self._torch()
        import jax.numpy as jnp

        rng = np.random.default_rng(1)
        x = rng.standard_normal((2, 4, 10, 10)).astype(np.float32)
        for k, s in [(2, None), (3, 2), ((2, 3), (1, 2))]:
            got = np.asarray(htnn.MaxPool2d(k, s).apply({}, jnp.asarray(x)))
            ref = torch.nn.functional.max_pool2d(
                torch.from_numpy(x), k, stride=s
            ).numpy()
            np.testing.assert_allclose(got, ref)
            got = np.asarray(htnn.AvgPool2d(k, s).apply({}, jnp.asarray(x)))
            ref = torch.nn.functional.avg_pool2d(
                torch.from_numpy(x), k, stride=s
            ).numpy()
            # atol: reduce_window may sum the window in a different order
            # than torch — near-zero outputs can differ by an ULP or two
            np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-7)

    def test_dropout2d_channelwise(self):
        import jax
        import jax.numpy as jnp

        x = jnp.ones((4, 6, 5, 5), jnp.float32)
        out = np.asarray(
            htnn.Dropout2d(0.5).apply({}, x, train=True, key=jax.random.PRNGKey(3))
        )
        # each (sample, channel) map is either all-zero or all-scaled
        per_map = out.reshape(4, 6, -1)
        for m in per_map.reshape(24, -1):
            assert np.all(m == 0.0) or np.all(m == 2.0)
        # eval mode: identity
        np.testing.assert_array_equal(
            np.asarray(htnn.Dropout2d(0.5).apply({}, x, train=False)), np.asarray(x)
        )

    def test_cnn_trains_under_data_parallel(self):
        """The reference CNN shape (conv-conv-pool-fc) must train through
        DataParallel + DataParallelOptimizer on the mesh."""
        import jax

        rng = np.random.default_rng(4)
        n = 64
        y_np = rng.integers(0, 2, size=n).astype(np.int32)
        # class-dependent mean patch makes the task learnable
        x_np = (
            rng.standard_normal((n, 1, 8, 8)) + y_np[:, None, None, None] * 2.0
        ).astype(np.float32)
        net = htnn.Sequential(
            htnn.Conv2d(1, 4, 3),
            htnn.ReLU(),
            htnn.MaxPool2d(2),
            htnn.Flatten(),
            htnn.Linear(4 * 3 * 3, 2),
        )
        dp = htnn.DataParallel(net, key=5)
        opt = htoptim.DataParallelOptimizer(htoptim.Adam(lr=0.01), dp)
        x = ht.array(x_np, split=0)
        y = ht.array(y_np, split=0)
        losses = [float(opt.step(x, y)) for _ in range(30)]
        assert losses[-1] < 0.5 * losses[0], losses[::10]
        preds = np.argmax(np.asarray(dp(x).numpy()), axis=1)
        assert (preds == y_np).mean() > 0.9


class TestNormAndEmbedding:
    def test_layernorm_matches_torch(self):
        torch = pytest.importorskip("torch")
        rng = np.random.default_rng(0)
        x = rng.standard_normal((3, 5, 8)).astype(np.float32)
        m = htnn.LayerNorm(8)
        params = m.init(jax.random.PRNGKey(0))
        tln = torch.nn.LayerNorm(8)
        ref = tln(torch.from_numpy(x)).detach().numpy()
        got = np.asarray(m.apply(params, jnp.asarray(x)))
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)
        # multi-dim normalized_shape, no affine
        m2 = htnn.LayerNorm((5, 8), elementwise_affine=False)
        tln2 = torch.nn.LayerNorm((5, 8), elementwise_affine=False)
        np.testing.assert_allclose(
            np.asarray(m2.apply({}, jnp.asarray(x))),
            tln2(torch.from_numpy(x)).detach().numpy(),
            rtol=1e-5, atol=1e-6,
        )

    def test_layernorm_shape_mismatch_raises(self):
        m = htnn.LayerNorm(8)
        params = m.init(jax.random.PRNGKey(0))
        with pytest.raises(ValueError):
            m.apply(params, jnp.zeros((3, 5, 1), jnp.float32))
        with pytest.raises(ValueError):
            htnn.LayerNorm((5, 8), elementwise_affine=False).apply({}, jnp.zeros((3, 4, 8)))

    def test_embedding_lookup(self):
        m = htnn.Embedding(10, 4)
        params = m.init(jax.random.PRNGKey(1))
        idx = jnp.asarray([0, 3, 9, 3])
        out = np.asarray(m.apply(params, idx))
        np.testing.assert_array_equal(out[1], out[3])
        np.testing.assert_array_equal(out, np.asarray(params["weight"])[np.asarray(idx)])

    def test_tiny_transformer_block_with_ring_attention(self):
        """Embedding + LayerNorm + ring attention + Linear — the
        long-context building blocks compose on the mesh."""
        S, D = 64, 8
        rng = np.random.default_rng(2)
        tokens = rng.integers(0, 16, size=S).astype(np.int32)
        emb = htnn.Embedding(16, D)
        ln = htnn.LayerNorm(D)
        proj = htnn.Linear(D, D)
        key = jax.random.PRNGKey(3)
        k1, k2, k3 = jax.random.split(key, 3)
        pe, pl, pp = emb.init(k1), ln.init(k2), proj.init(k3)
        h = ln.apply(pl, emb.apply(pe, jnp.asarray(tokens)))
        hd = ht.array(np.asarray(h), split=0)
        att = ht.nn.ring_attention(hd, hd, hd, causal=True)
        out = proj.apply(pp, att.larray)
        assert out.shape == (S, D)
        assert np.isfinite(np.asarray(out)).all()


class TestTorchParityEdges:
    def test_embedding_raises_out_of_range(self):
        m = htnn.Embedding(4, 2)
        params = m.init(jax.random.PRNGKey(0))
        with pytest.raises(IndexError):
            m.apply(params, jnp.asarray([3, 7]))
        with pytest.raises(IndexError):
            m.apply(params, jnp.asarray([-1]))
        # traced calls keep gather-clamp semantics (documented)
        out = jax.jit(lambda i: m.apply(params, i))(jnp.asarray([0, 3]))
        assert out.shape == (2, 2)

    def test_dropout_p1_zeroes(self):
        x = jnp.ones((3, 3), jnp.float32)
        out = htnn.Dropout(1.0).apply({}, x, train=True, key=jax.random.PRNGKey(0))
        np.testing.assert_array_equal(np.asarray(out), 0.0)
        # eval mode: identity even at p=1 (torch parity)
        np.testing.assert_array_equal(
            np.asarray(htnn.Dropout(1.0).apply({}, x, train=False)), np.asarray(x)
        )


class TestMultiheadAttention:
    def test_torch_oracle_self_attention(self):
        torch = pytest.importorskip("torch")

        torch.manual_seed(0)
        B, S, E, H = 2, 12, 16, 4
        x = np.random.default_rng(0).standard_normal((B, S, E)).astype(np.float32)

        t_mha = torch.nn.MultiheadAttention(E, H, bias=True, batch_first=True)
        with torch.no_grad():
            ref, _ = t_mha(torch.tensor(x), torch.tensor(x), torch.tensor(x),
                           need_weights=False)

        mha = ht.nn.MultiheadAttention(E, H, bias=True)
        params = {
            "in_proj": jnp.asarray(t_mha.in_proj_weight.detach().numpy().T),
            "in_bias": jnp.asarray(t_mha.in_proj_bias.detach().numpy()),
            "out_proj": jnp.asarray(t_mha.out_proj.weight.detach().numpy().T),
            "out_bias": jnp.asarray(t_mha.out_proj.bias.detach().numpy()),
        }
        out = mha.apply(params, jnp.asarray(x))
        np.testing.assert_allclose(np.asarray(out), ref.numpy(), rtol=2e-4, atol=2e-5)

    def test_causal_and_unbatched(self):
        torch = pytest.importorskip("torch")

        torch.manual_seed(1)
        S, E, H = 9, 8, 2
        x = np.random.default_rng(1).standard_normal((S, E)).astype(np.float32)
        t_mha = torch.nn.MultiheadAttention(E, H, bias=True, batch_first=True)
        mask = torch.triu(torch.ones(S, S, dtype=torch.bool), diagonal=1)
        with torch.no_grad():
            ref, _ = t_mha(torch.tensor(x[None]), torch.tensor(x[None]),
                           torch.tensor(x[None]), attn_mask=mask, need_weights=False)
        mha = ht.nn.MultiheadAttention(E, H, bias=True, causal=True)
        params = {
            "in_proj": jnp.asarray(t_mha.in_proj_weight.detach().numpy().T),
            "in_bias": jnp.asarray(t_mha.in_proj_bias.detach().numpy()),
            "out_proj": jnp.asarray(t_mha.out_proj.weight.detach().numpy().T),
            "out_bias": jnp.asarray(t_mha.out_proj.bias.detach().numpy()),
        }
        out = mha.apply(params, jnp.asarray(x))  # unbatched (S, E)
        assert out.shape == (S, E)
        np.testing.assert_allclose(np.asarray(out), ref.numpy()[0], rtol=2e-4, atol=2e-5)

    def test_trains_in_sequential(self):
        # end-to-end: a tiny transformer-ish stack learns under DataParallel
        rng = np.random.default_rng(2)
        n, s, e = 256, 8, 16
        x = ht.array(rng.standard_normal((n, s * e)).astype(np.float32), split=0)
        y = (ht.sum(x, axis=1) > 0).astype(ht.int32)

        class Reshape(ht.nn.Module):
            def apply(self, params, a, *, train=False, key=None):
                return a.reshape(a.shape[0], s, e)

        class Pool(ht.nn.Module):
            def apply(self, params, a, *, train=False, key=None):
                return a.mean(axis=1)

        model = ht.nn.Sequential(
            Reshape(), ht.nn.MultiheadAttention(e, 4, causal=True), Pool(),
            ht.nn.Linear(e, 2),
        )
        dp = ht.nn.DataParallel(model)
        opt = ht.optim.DataParallelOptimizer(ht.optim.SGD(lr=0.1), dp)
        first = last = None
        for _ in range(15):
            loss = float(opt.step(x, y))
            first = loss if first is None else first
            last = loss
        assert np.isfinite(last) and last < first

    def test_validation(self):
        with pytest.raises(ValueError):
            ht.nn.MultiheadAttention(10, 3)

    def test_grad_finite(self):
        mha = ht.nn.MultiheadAttention(8, 2, causal=True)
        params = mha.init(jax.random.key(0))
        x = jnp.asarray(np.random.default_rng(3).standard_normal((2, 6, 8)).astype(np.float32))
        g = jax.grad(lambda p: jnp.sum(mha.apply(p, x) ** 2))(params)
        for leaf in jax.tree.leaves(g):
            assert np.isfinite(np.asarray(leaf)).all()
