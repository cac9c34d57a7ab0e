"""Data-parallel optimizers.

Replaces /root/reference/heat/optim/dp_optimizer.py:

- ``DataParallelOptimizer`` (reference :851-894): wraps a local optimizer
  for synchronous data parallelism. The reference defers ``step()`` under
  its non-blocking hook scheme; here one jitted train step fuses forward,
  backward, gradient all-reduce (inserted by GSPMD: the batch is sharded
  along axis 0, parameters are replicated, so the gradient of a global-mean
  loss lowers to one fused all-reduce over the mesh) and the optimizer
  update. Blocking vs non-blocking is moot — XLA overlaps the collective
  with compute.
- Quantized-gradient DP (ISSUE 7, opt-in ``wire_quant="int8"/"bf16"``):
  the gradient all-reduce decomposes into the block-quantized wire form
  of ``heat_tpu.kernels.quant`` — quantize the local contribution (plus
  the error-feedback carry), ship int8 blocks through ONE all-to-all
  (the reduce-scatter leg: each device decodes and sums the p partials
  of its block full-width) and ONE all-gather of the re-encoded reduced
  blocks, then dequantize. Wire bytes drop to ``wire_ratio`` (~0.25
  int8 / 0.5 bf16) of the psum's, which on the analytic v5e-64 model
  converts ≥1.5× of step time on ICI-bound layers
  (``kernels.quant.dp_step_model``); the per-device error-feedback
  carry re-injects the compression error next step, so the long-run
  gradient is unbiased (EQuARX, arXiv:2506.17615).
- ``DASO`` (reference :64-850): hierarchical/asynchronous DP. The
  reference runs node-local torch-DDP every batch and staggers global MPI
  syncs across "skip batches" with bf16-compressed buffers and custom MPI
  ops for half types (:21-62). Here the hierarchy is a two-level
  ``Mesh(("node", "local"))``: parameters carry a leading node axis sharded
  over ``"node"`` (each node owns a divergent copy — the single-controller
  representation of per-node model replicas), every step psums gradients
  over ``"local"`` only, and every ``global_skip``-th step additionally
  psum-averages the PARAMETERS over ``"node"``, optionally cast to
  bfloat16 for the wire (the reference's compression, :21-62). The skip
  schedule adapts via ``epoch_loss_logic`` (reference :354).
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from typing import Optional

from ..core.dndarray import DNDarray
from ..nn.modules import CrossEntropyLoss, scalar_dndarray

__all__ = ["SGD", "Adam", "AdamW", "DataParallelOptimizer", "DASO"]


# --------------------------------------------------------------------- #
# local optimizers (optax-backed; lr lives in state via inject_hyperparams
# so lr_scheduler can mutate it)                                        #
# --------------------------------------------------------------------- #
class LocalOptimizer:
    """A local (per-replica) gradient transformation — the role torch
    optimizers play in the reference (any torch.optim.Optimizer instance,
    dp_optimizer.py:868)."""

    def __init__(self, tx, defaults: dict):
        self.tx = tx
        self.defaults = dict(defaults)


class SGD(LocalOptimizer):
    def __init__(self, lr: float = 0.01, momentum: float = 0.0, weight_decay: float = 0.0,
                 nesterov: bool = False):
        import optax

        # momentum/weight_decay structure is decided statically so plain SGD
        # carries no dead trace accumulator or no-op decay stage
        mom = momentum if momentum else None

        def sgd_part(learning_rate):
            return optax.sgd(learning_rate, momentum=mom, nesterov=nesterov)

        if weight_decay:
            def make(learning_rate, weight_decay):
                return optax.chain(optax.add_decayed_weights(weight_decay),
                                   sgd_part(learning_rate))

            tx = optax.inject_hyperparams(make)(learning_rate=lr, weight_decay=weight_decay)
        else:
            tx = optax.inject_hyperparams(sgd_part)(learning_rate=lr)
        super().__init__(tx, dict(lr=lr, momentum=momentum, weight_decay=weight_decay))


class Adam(LocalOptimizer):
    def __init__(self, lr: float = 1e-3, betas=(0.9, 0.999), eps: float = 1e-8,
                 weight_decay: float = 0.0):
        import optax

        b1, b2 = betas

        def adam_part(learning_rate):
            return optax.adam(learning_rate, b1=b1, b2=b2, eps=eps)

        if weight_decay:
            def make(learning_rate, weight_decay):
                return optax.chain(optax.add_decayed_weights(weight_decay),
                                   adam_part(learning_rate))

            tx = optax.inject_hyperparams(make)(learning_rate=lr, weight_decay=weight_decay)
        else:
            tx = optax.inject_hyperparams(adam_part)(learning_rate=lr)
        super().__init__(tx, dict(lr=lr, betas=betas, eps=eps, weight_decay=weight_decay))


class AdamW(LocalOptimizer):
    def __init__(self, lr: float = 1e-3, betas=(0.9, 0.999), eps: float = 1e-8,
                 weight_decay: float = 1e-2):
        import optax

        b1, b2 = betas
        tx = optax.inject_hyperparams(
            lambda learning_rate, weight_decay: optax.adamw(
                learning_rate, b1=b1, b2=b2, eps=eps, weight_decay=weight_decay
            )
        )(learning_rate=lr, weight_decay=weight_decay)
        super().__init__(tx, dict(lr=lr, betas=betas, eps=eps, weight_decay=weight_decay))


_loss_scalar = scalar_dndarray


def _aligned_labels(x: DNDarray, y: DNDarray) -> jax.Array:
    """Physical labels row-aligned with x's physical batch. A replicated
    y against a SHARDED x differs in physical extent whenever the batch
    pads (surfaced by the odd-mesh CI leg: 512 rows over 5 devices pad to
    515 on the sharded side only) — resplitting y to x.split pads it
    identically; the pad rows are masked by the step's validity weight.
    Gated on the EXTENTS, not the splits: when they already match (the
    common evenly-divisible case) the raw buffer passes through free and
    jit reshards it inside the step."""
    if y._phys.shape[0] != x._phys.shape[0]:
        y = y.resplit(x.split)
    return y._phys


class DataParallelOptimizer:
    """Synchronous data-parallel optimizer (reference dp_optimizer.py:851).

    Parameters
    ----------
    local_optimizer : LocalOptimizer
        SGD/Adam/AdamW (or any optax GradientTransformation wrapped in
        LocalOptimizer).
    model : heat_tpu.nn.DataParallel
        The wrapped model whose parameters this optimizer advances.
    loss : loss object with ``raw(output, target, weight)``, optional
        Defaults to CrossEntropyLoss.
    blocking : bool
        Reference API parity; both values run the same fused step (the
        blocking/non-blocking distinction is the reference's hook
        choreography, data_parallel.py:219-295, which XLA makes obsolete).
    wire_quant : {"int8", "bf16"}, optional
        Opt-in quantized-gradient mode: the gradient all-reduce ships
        block-quantized payloads (``heat_tpu.kernels.quant``, scale per
        1024-element tile) with a per-device error-feedback carry. The
        default ``None`` keeps the exact full-width psum — this mode is
        a constructor decision, never an ambient env flip, because it
        changes training numerics (within the codec's pinned tolerance
        per step; EF makes the long-run gradient unbiased).
    """

    def __init__(self, local_optimizer, model, loss=None, blocking: bool = True,
                 wire_quant: Optional[str] = None):
        if not isinstance(local_optimizer, LocalOptimizer):
            raise TypeError(
                f"local_optimizer must be a heat_tpu.optim optimizer, got {type(local_optimizer)}"
            )
        if wire_quant is not None:
            from ..kernels.quant import MODES

            if wire_quant not in MODES:
                raise ValueError(
                    f"wire_quant must be one of {MODES} or None, got {wire_quant!r}"
                )
        self.model = model
        self.tx = local_optimizer.tx
        self.loss = loss if loss is not None else CrossEntropyLoss()
        self.blocking = bool(blocking)
        self.wire_quant = wire_quant
        repl = model.comm.sharding(0, None)
        self.opt_state = jax.device_put(self.tx.init(model.params), repl)
        self._iter = 0
        self._base_key = jax.random.PRNGKey(0)
        self._step_cache = {}
        # per-device error-feedback carry (quantized mode only), built
        # lazily once the flat gradient size is known
        self._ef_carry = None

    # -------------------------------------------------------------- #
    def zero_grad(self) -> None:
        """No-op: gradients are locals of the fused step (reference
        dp_optimizer.py:897 zeroes torch .grad buffers)."""

    @property
    def lr(self) -> float:
        return float(self.opt_state.hyperparams["learning_rate"])

    def set_lr(self, lr: float) -> None:
        self.opt_state.hyperparams["learning_rate"] = jnp.asarray(
            lr, dtype=self.opt_state.hyperparams["learning_rate"].dtype
        )

    # -------------------------------------------------------------- #
    def _get_step(self, xshape, xdtype, yshape, ydtype, n_valid: int):
        key = (xshape, xdtype, yshape, ydtype, n_valid)
        fn = self._step_cache.get(key)
        if fn is not None:
            return fn
        module, loss, tx = self.model.module, self.loss, self.tx
        import optax

        padded = xshape[0] != n_valid

        def step(params, opt_state, xb, yb, dropkey):
            weight = None
            if padded:
                weight = (jnp.arange(xb.shape[0]) < n_valid).astype(xb.dtype)

            def lf(p):
                out = module.apply(p, xb, train=True, key=dropkey)
                return loss.raw(out, yb, weight=weight)

            loss_val, grads = jax.value_and_grad(lf)(params)
            updates, new_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), new_state, loss_val

        fn = jax.jit(step, donate_argnums=(0, 1))
        self._step_cache[key] = fn
        return fn

    # -------------------------------------------------------------- #
    # quantized-gradient mode (ISSUE 7)                               #
    # -------------------------------------------------------------- #
    def _flat_param_count(self) -> int:
        from jax.flatten_util import ravel_pytree

        return int(ravel_pytree(self.model.params)[0].size)

    def _init_ef_carry(self):
        """Zero per-device error-feedback residuals: one flat gradient
        vector per device, leading axis sharded over the mesh."""
        comm = self.model.comm
        n = self._flat_param_count()
        self._ef_carry = jax.device_put(
            jnp.zeros((comm.size, n), jnp.float32), comm.sharding(2, 0)
        )

    def _get_quant_step(self, xshape, xdtype, yshape, ydtype, n_valid: int):
        comm = self.model.comm
        # two-tier wire (ISSUE 8): at a tiered topology the quantized
        # all-reduce runs hierarchically — intra-slice reduce-scatter,
        # inter-slice exchange of the reduced+encoded shard, intra-slice
        # all-gather — so only ~1/C of the encoded gradient crosses DCN
        topo_t = comm.topology
        topo = (
            (topo_t.n_slices, topo_t.chips_per_slice)
            if topo_t.tiered and topo_t.chips_per_slice > 1
            else None
        )
        key = (xshape, xdtype, yshape, ydtype, n_valid, self.wire_quant, topo)
        fn = self._step_cache.get(key)
        if fn is not None:
            return fn
        module, loss, tx = self.model.module, self.loss, self.tx
        p, axis = comm.size, comm.axis_name
        mode = self.wire_quant
        import optax

        from jax.flatten_util import ravel_pytree
        from ..kernels import quant as _quant

        blk_rows = xshape[0] // p

        def blk(params, opt_state, carry_blk, xb, yb, dropkey):
            dev = jax.lax.axis_index(axis)
            rows = dev * blk_rows + jnp.arange(blk_rows)
            w = (rows < n_valid).astype(xb.dtype)

            def local_sums(pp):
                out = module.apply(
                    pp, xb, train=True, key=jax.random.fold_in(dropkey, dev)
                )
                # loss contract (see DASO): raw() is the weighted MEAN;
                # x Σw recovers the weighted sum this wire reduces over
                return loss.raw(out, yb, weight=w) * jnp.sum(w)

            sum_loss, g = jax.value_and_grad(local_sums)(params)
            g_flat, unravel = ravel_pytree(g)
            # error feedback: re-inject last step's compression residual,
            # ship the compensated gradient through the quantized wire
            h = g_flat.astype(jnp.float32) + carry_blk[0]
            if topo is not None:
                red, resid = _quant.hierarchical_allreduce_sum(
                    h, axis, topo[0], topo[1], mode
                )
            else:
                red, resid = _quant.quantized_allreduce_sum(h, axis, p, mode)
            wsum = jax.lax.psum(jnp.sum(w), axis)
            gbar = unravel((red / jnp.maximum(wsum, 1.0)).astype(g_flat.dtype))
            updates, o2 = tx.update(gbar, opt_state, params)
            p2 = optax.apply_updates(params, updates)
            gl = jax.lax.psum(sum_loss, axis) / jnp.maximum(wsum, 1.0)
            return p2, o2, resid[None], gl

        mapped = shard_map(
            blk,
            mesh=comm.mesh,
            in_specs=(P(), P(), P(axis), P(axis), P(axis), P()),
            out_specs=(P(), P(), P(axis), P()),
            check_vma=False,
        )
        fn = jax.jit(mapped, donate_argnums=(0, 1, 2))
        self._step_cache[key] = fn
        return fn

    # -------------------------------------------------------------- #
    # checkpointed resume (ISSUE 13)                                  #
    # -------------------------------------------------------------- #
    def checkpoint_state(self) -> dict:
        """Everything a bit-reproducible mid-training resume needs, as
        a flat ``heat_tpu.resilience.checkpoint.save``-able dict:
        parameters and optimizer-state leaves (replicated), the
        per-device error-feedback carry (sharded — streamed as
        split-blocks), the step counter the dropout key folds, and the
        base PRNG key. The pytree STRUCTURES are not serialized — a
        restore adopts the leaves into the structures of the receiving
        optimizer, which must wrap the same architecture."""
        import jax

        p_leaves = jax.tree.leaves(self.model.params)
        o_leaves = jax.tree.leaves(self.opt_state)
        state = {f"param_{i:04d}": l for i, l in enumerate(p_leaves)}
        state.update({f"opt_{i:04d}": l for i, l in enumerate(o_leaves)})
        state["base_key"] = np.asarray(jax.device_get(self._base_key))
        state["iter"] = int(self._iter)
        state["n_params"] = len(p_leaves)
        state["n_opt"] = len(o_leaves)
        state["wire_quant"] = self.wire_quant or ""
        if self._ef_carry is not None:
            state["ef_carry"] = self._ef_carry
        return state

    def load_checkpoint_state(self, state: dict) -> None:
        """Adopt a restored checkpoint ONTO THE CURRENT WORLD: params/
        optimizer leaves re-place replicated over this optimizer's
        mesh, and the error-feedback carry re-shards split-0. On a
        RESIZED world the carry's per-device rows fold as ``row r ->
        r % p_new`` (summed) — the total outstanding residual, which is
        what error feedback re-injects, is preserved exactly; on the
        same-size world the carry restores bit-identically."""
        import jax

        comm = self.model.comm
        repl = comm.sharding(0, None)
        n_p, n_o = int(state["n_params"]), int(state["n_opt"])
        p_leaves = [state[f"param_{i:04d}"] for i in range(n_p)]
        o_leaves = [state[f"opt_{i:04d}"] for i in range(n_o)]
        p_def = jax.tree.structure(self.model.params)
        o_def = jax.tree.structure(self.opt_state)
        if p_def.num_leaves != n_p or o_def.num_leaves != n_o:
            raise ValueError(
                f"checkpoint carries {n_p} param / {n_o} optimizer leaves "
                f"but this optimizer has {p_def.num_leaves} / "
                f"{o_def.num_leaves} — architectures differ"
            )
        # ALL validation precedes mutation: a refused restore must
        # leave the optimizer exactly as it was
        saved_wire = state.get("wire_quant") or None
        if saved_wire != self.wire_quant:
            raise ValueError(
                f"checkpoint was written with wire_quant={saved_wire!r} but "
                f"this optimizer runs {self.wire_quant!r} — the EF carry is "
                "only meaningful under the same codec"
            )
        def _cast(l, c):
            # non-array leaves (plain counters some transforms keep)
            # round-trip as scalars and adopt as-is
            dt = getattr(c, "dtype", None)
            return jnp.asarray(l, dtype=dt) if dt is not None else l

        cur_p = jax.tree.leaves(self.model.params)
        cur_o = jax.tree.leaves(self.opt_state)
        p_leaves = [_cast(l, c) for l, c in zip(p_leaves, cur_p)]
        o_leaves = [_cast(l, c) for l, c in zip(o_leaves, cur_o)]
        self.model.params = jax.device_put(jax.tree.unflatten(p_def, p_leaves), repl)
        self.opt_state = jax.device_put(jax.tree.unflatten(o_def, o_leaves), repl)
        self._iter = int(state["iter"])
        self._base_key = jnp.asarray(state["base_key"])
        carry = state.get("ef_carry")
        if carry is None or self.wire_quant is None:
            self._ef_carry = None
            return
        host = np.asarray(jax.device_get(carry), dtype=np.float32)
        p_new = comm.size
        if host.shape[0] != p_new:
            folded = np.zeros((p_new,) + host.shape[1:], dtype=host.dtype)
            for r in range(host.shape[0]):
                folded[r % p_new] += host[r]
            host = folded
        self._ef_carry = jax.device_put(jnp.asarray(host), comm.sharding(2, 0))

    def step(self, x: DNDarray, y: DNDarray) -> DNDarray:
        """One fused train step on a global batch; returns the global-mean
        loss as a 0-d replicated DNDarray (no host sync)."""
        xb, yb = x._phys, _aligned_labels(x, y)
        self._iter += 1
        dropkey = jax.random.fold_in(self._base_key, self._iter)
        if self.wire_quant is not None and self.model.comm.size > 1:
            if self._ef_carry is None:
                self._init_ef_carry()
            fn = self._get_quant_step(
                tuple(xb.shape), str(xb.dtype), tuple(yb.shape), str(yb.dtype),
                x.shape[0],
            )
            params, self.opt_state, self._ef_carry, loss_val = fn(
                self.model.params, self.opt_state, self._ef_carry, xb, yb, dropkey
            )
            self.model.params = params
            return _loss_scalar(loss_val, self.model.comm, x.device)
        fn = self._get_step(
            tuple(xb.shape), str(xb.dtype), tuple(yb.shape), str(yb.dtype), x.shape[0]
        )
        params, self.opt_state, loss_val = fn(self.model.params, self.opt_state, xb, yb, dropkey)
        self.model.params = params
        return _loss_scalar(loss_val, self.model.comm, x.device)


class DASO:
    """Distributed Asynchronous and Selective Optimization (reference
    dp_optimizer.py:64): hierarchical data parallelism on a two-level mesh.

    Parameters (reference-aligned where the concept survives)
    ----------
    local_optimizer : LocalOptimizer
    model : heat_tpu.nn.DataParallel
    n_nodes : int, optional
        Number of node groups (reference: inferred from MPI topology /
        GPUs per node, dp_optimizer.py:137-160). Default: 2 when the mesh
        size is even, else 1.
    global_skip : int
        Batches between global parameter syncs (reference
        ``max_global_skips``-controlled schedule, :202).
    compression : bool
        Cast parameters to bfloat16 for the global sync wire (reference
        mpi_sum_bfloat custom op, :21-62).
    loss : loss object, optional
    """

    def __init__(self, local_optimizer, model, n_nodes: Optional[int] = None,
                 global_skip: int = 4, compression: bool = True, loss=None,
                 total_epochs: Optional[int] = None, warmup_epochs: int = 4,
                 cooldown_epochs: int = 4, stability_level: float = 0.05,
                 max_global_skips: int = 8, skip_reduction_factor: int = 2,
                 local_skip_factor: int = 4):
        if not isinstance(local_optimizer, LocalOptimizer):
            raise TypeError(
                f"local_optimizer must be a heat_tpu.optim optimizer, got {type(local_optimizer)}"
            )
        self.model = model
        self.comm = model.comm
        self.tx = local_optimizer.tx
        self.loss = loss if loss is not None else CrossEntropyLoss()
        size = self.comm.size
        if n_nodes is None:
            n_nodes = 2 if size % 2 == 0 and size > 1 else 1
        if size % n_nodes != 0:
            raise ValueError(f"mesh size {size} not divisible by n_nodes {n_nodes}")
        self.n_nodes = int(n_nodes)
        self.local_size = size // self.n_nodes
        self.global_skip = int(global_skip)
        self.compression = bool(compression)
        devs = np.array(self.comm.devices).reshape(self.n_nodes, self.local_size)
        self.mesh = Mesh(devs, ("node", "local"))

        # node-stacked parameters: leading axis = node, sharded over "node";
        # the single-controller form of per-node divergent replicas
        node_sharded = NamedSharding(self.mesh, P("node"))
        self.params = jax.tree.map(
            lambda p: jax.device_put(
                jnp.broadcast_to(p[None], (self.n_nodes,) + p.shape), node_sharded
            ),
            model.params,
        )
        self.opt_state = jax.device_put(jax.vmap(self.tx.init)(self.params), node_sharded)
        self._iter = 0
        self._base_key = jax.random.PRNGKey(0)
        self._step_cache = {}
        # epoch_loss_logic state (reference :354-470): the widening/
        # collapsing skip schedule with its stability detector
        from .utils import DetectMetricPlateau

        self.total_epochs = total_epochs
        self.warmup_epochs = int(warmup_epochs)
        self.cooldown_epochs = int(cooldown_epochs)
        self.max_gs = int(max_global_skips)
        self.skip_reduction_factor = int(skip_reduction_factor)
        self.local_skip_factor = int(local_skip_factor)
        self.stability = DetectMetricPlateau(patience=2, threshold=float(stability_level))
        self.epoch = 0
        # local_skip / batches_to_wait are schedule STATE kept for policy
        # parity: the two-level mesh averages within a node in-program
        # every batch (a fused psum over ICI — effectively free, unlike
        # the reference's NCCL hop, so skipping it buys nothing), and a
        # synchronous collective has no recv-delay to wait batches for.
        self.local_skip = 1
        self.batches_to_wait = 1
        # keep the wrapped model's eval path current: forwards read the
        # node-averaged parameters lazily (the reference mutates the torch
        # model in place every step, so eval there is always current)
        self._eval_cache = (-1, None)
        model._param_override = self._eval_params
        model._owner = self

    def _eval_params(self):
        it, cached = self._eval_cache
        if it != self._iter:
            cached = jax.tree.map(lambda a: jnp.mean(a, axis=0), self.params)
            self._eval_cache = (self._iter, cached)
        return cached

    @property
    def lr(self) -> float:
        return float(self.opt_state.hyperparams["learning_rate"][0])

    def set_lr(self, lr: float) -> None:
        cur = self.opt_state.hyperparams["learning_rate"]
        self.opt_state.hyperparams["learning_rate"] = jnp.full_like(cur, lr)

    # -------------------------------------------------------------- #
    def _get_step(self, xshape, xdtype, yshape, ydtype, n_valid: int, global_sync: bool):
        key = (xshape, xdtype, yshape, ydtype, n_valid, global_sync)
        fn = self._step_cache.get(key)
        if fn is not None:
            return fn
        module, loss, tx = self.model.module, self.loss, self.tx
        n_nodes, local_size = self.n_nodes, self.local_size
        compression = self.compression
        import optax

        blk_rows = xshape[0] // (n_nodes * local_size)

        def blk(params_blk, opt_blk, xb, yb, dropkey):
            p = jax.tree.map(lambda a: a[0], params_blk)
            o = jax.tree.map(lambda a: a[0], opt_blk)
            dev = jax.lax.axis_index("node") * local_size + jax.lax.axis_index("local")
            rows = dev * blk_rows + jnp.arange(blk_rows)
            w = (rows < n_valid).astype(xb.dtype)

            def local_sums(pp):
                out = module.apply(pp, xb, train=True, key=jax.random.fold_in(dropkey, dev))
                # documented loss contract: raw(output, target, weight) is the
                # weighted MEAN; × Σw recovers the weighted sum this
                # hierarchy reduces over
                return loss.raw(out, yb, weight=w) * jnp.sum(w)

            sum_loss, g = jax.value_and_grad(local_sums)(p)
            wsum = jnp.sum(w)
            node_w = jax.lax.psum(wsum, "local")
            g = jax.tree.map(
                lambda a: jax.lax.psum(a, "local") / jnp.maximum(node_w, 1.0).astype(a.dtype), g
            )
            updates, o2 = tx.update(g, o, p)
            p2 = optax.apply_updates(p, updates)
            if global_sync and n_nodes > 1:
                def gsync(a):
                    wire = a.astype(jnp.bfloat16) if compression else a
                    return (jax.lax.psum(wire, "node") / n_nodes).astype(a.dtype)
                p2 = jax.tree.map(gsync, p2)
            gl = jax.lax.psum(sum_loss, ("node", "local")) / jnp.maximum(
                jax.lax.psum(wsum, ("node", "local")), 1.0
            )
            return (
                jax.tree.map(lambda a: a[None], p2),
                jax.tree.map(lambda a: a[None], o2),
                gl,
            )

        mapped = shard_map(
            blk,
            mesh=self.mesh,
            in_specs=(P("node"), P("node"), P(("node", "local")), P(("node", "local")), P()),
            out_specs=(P("node"), P("node"), P()),
            check_vma=False,
        )
        fn = jax.jit(mapped, donate_argnums=(0, 1))
        self._step_cache[key] = fn
        return fn

    def step(self, x: DNDarray, y: DNDarray) -> DNDarray:
        """One DASO step: node-local sync always, global parameter
        averaging every ``global_skip`` batches (reference :202-350)."""
        xb, yb = x._phys, _aligned_labels(x, y)
        if xb.shape[0] % (self.n_nodes * self.local_size) != 0:
            raise ValueError(
                f"DASO requires the physical batch ({xb.shape[0]}) divisible by the "
                f"mesh ({self.n_nodes}x{self.local_size})"
            )
        self._iter += 1
        global_sync = self.global_skip <= 1 or (self._iter % self.global_skip == 0)
        dropkey = jax.random.fold_in(self._base_key, self._iter)
        fn = self._get_step(
            tuple(xb.shape), str(xb.dtype), tuple(yb.shape), str(yb.dtype),
            x.shape[0], bool(global_sync),
        )
        self.params, self.opt_state, loss_val = fn(self.params, self.opt_state, xb, yb, dropkey)
        return _loss_scalar(loss_val, self.comm, x.device)

    def zero_grad(self) -> None:
        """No-op (see DataParallelOptimizer.zero_grad)."""

    def load_params(self, params) -> None:
        """Adopt externally loaded weights (checkpoint restore): restack
        them per node and reinitialize the optimizer state (momentum is not
        part of the reference's checkpoint either, optim/utils.py:72)."""
        node_sharded = NamedSharding(self.mesh, P("node"))
        self.params = jax.tree.map(
            lambda p: jax.device_put(
                jnp.broadcast_to(jnp.asarray(p)[None], (self.n_nodes,) + jnp.asarray(p).shape),
                node_sharded,
            ),
            params,
        )
        self.opt_state = jax.device_put(jax.vmap(self.tx.init)(self.params), node_sharded)
        self._eval_cache = (-1, None)

    def sync_params(self) -> None:
        """Force a global parameter average and push the result into the
        wrapped model (reference: the end-of-epoch full sync, :700-780)."""
        mean = jax.tree.map(lambda a: jnp.mean(a, axis=0), self.params)
        repl = self.comm.sharding(0, None)
        self.model.params = jax.tree.map(lambda p: jax.device_put(p, repl), mean)
        node_sharded = NamedSharding(self.mesh, P("node"))
        self.params = jax.tree.map(
            lambda p: jax.device_put(jnp.broadcast_to(p[None], (self.n_nodes,) + p.shape),
                                     node_sharded),
            self.model.params,
        )

    def epoch_loss_logic(self, loss, loss_globally_averaged: bool = True) -> None:
        """Adapt the sync schedule from the end-of-epoch loss — the
        reference's policy verbatim (dp_optimizer.py:354-470):

        * warmup epochs: every skip forced to 0 (full sync each batch);
        * end of warmup: ``global_skip=4, local_skip=1, batches_to_wait=1``;
        * cooldown (last ``cooldown_epochs`` of ``total_epochs``): skips 0;
        * plateau detected (``DetectMetricPlateau``, patience 2) while
          ``global_skip > 1``: divide skips by ``skip_reduction_factor``
          and decrement ``batches_to_wait`` (sync more often to escape),
          clamping live skips to ≥ 1;
        * plateau detected at ``global_skip == 1``: widen back to
          ``max_global_skips`` (and ``max_gs // local_skip_factor`` local
          skips / wait batches).

        Call once per epoch with the training loss; the epoch counter
        advances here (the reference advances it on the last batch of its
        DataLoader, which this framework does not see). The loss under a
        single controller is already the global average (``step`` psums
        it), so ``loss_globally_averaged`` defaults True; pass False for a
        per-host value (e.g. a locally computed eval loss) and it is
        averaged across processes first — every host must then make the
        SAME schedule decision or their compiled sync programs diverge
        (the reference's Allreduce at :372 exists for the same reason).
        """
        avg_loss = float(loss)
        if not loss_globally_averaged and jax.process_count() > 1:
            from jax.experimental import multihost_utils

            all_losses = multihost_utils.process_allgather(
                jnp.asarray(avg_loss, dtype=jnp.float32)
            )
            avg_loss = float(jnp.mean(all_losses))
        self.epoch += 1
        epoch = self.epoch - 1  # the epoch this loss belongs to, 0-based

        if epoch < self.warmup_epochs:
            self.global_skip = 0
            self.local_skip = 0
            self.batches_to_wait = 0
            return
        if epoch == self.warmup_epochs:
            self.global_skip = 4
            self.local_skip = 1
            self.batches_to_wait = 1
        if (
            self.total_epochs is not None
            and epoch >= self.total_epochs - self.cooldown_epochs
        ):
            self.global_skip = 0
            self.local_skip = 0
            self.batches_to_wait = 0
            return

        stable = self.stability.test_if_improving(avg_loss)
        if stable and self.global_skip > 1:
            # collapse: sync more often while the loss is on a plateau
            self.global_skip //= self.skip_reduction_factor
            self.local_skip //= self.skip_reduction_factor
            self.batches_to_wait -= 1
            if self.global_skip > 0:
                if self.batches_to_wait == 0:
                    self.batches_to_wait = 1
                if self.local_skip == 0:
                    self.local_skip = 1
        elif stable and self.global_skip == 1:
            # bottomed out: widen back to the maximum
            self.global_skip = self.max_gs
            self.local_skip = self.max_gs // self.local_skip_factor
            self.batches_to_wait = self.max_gs // self.local_skip_factor
