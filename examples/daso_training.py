"""Hierarchical data-parallel training with DASO — the analog of the
reference's examples/nn/imagenet-DASO.py pattern (node-local sync every
batch, staggered global syncs, bf16-compressed wire) on a two-level
("node", "local") device mesh.

Trains the same MLP classification task as examples/mnist.py, but through
``heat_tpu.optim.DASO``: each node group holds its own parameter replica
(sharded over the "node" mesh axis), local batches update it every step,
and every ``--global-skip`` steps the replicas average over the slow axis
— the reference's skip-batch schedule with bf16 compression on the wire.

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/daso_training.py [--steps 80] [--global-skip 4]
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import heat_tpu as ht
from heat_tpu import nn, optim


def synthetic_task(n: int = 2048, d: int = 32, classes: int = 4, seed: int = 0):
    """Linearly-separable-ish blobs (offline stand-in for MNIST)."""
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((classes, d)).astype(np.float32) * 3.0
    y = rng.integers(0, classes, size=n)
    x = centers[y] + rng.standard_normal((n, d)).astype(np.float32)
    return x.astype(np.float32), y.astype(np.int32)


def main() -> None:
    ht.utils.place_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=80)
    ap.add_argument("--global-skip", type=int, default=4)
    ap.add_argument("--lr", type=float, default=0.01)
    args = ap.parse_args()

    comm = ht.get_comm()
    if comm.size % 2:
        print(f"mesh size {comm.size} is odd - DASO needs an even device count; "
              f"run under the 8-device CPU mesh (see module docstring)")
        return

    x_np, y_np = synthetic_task()
    x = ht.array(x_np, split=0)
    y = ht.array(y_np, split=0)

    model = nn.DataParallelMultiGPU(
        nn.Sequential(nn.Linear(32, 64), nn.ReLU(), nn.Linear(64, 4)), key=1
    )
    daso = optim.DASO(
        optim.Adam(lr=args.lr), model,
        n_nodes=2, global_skip=args.global_skip, compression=True,
    )
    print(f"mesh: {comm.size} devices as (node={daso.n_nodes}, local={daso.local_size}); "
          f"global sync every {args.global_skip} steps, bf16 wire")

    for step in range(1, args.steps + 1):
        loss = float(daso.step(x, y))
        if step % 10 == 0 or step == 1:
            preds = np.argmax(np.asarray(model(x).numpy()), axis=1)
            acc = float((preds == y_np).mean())
            print(f"step {step:3d}: loss={loss:.4f} acc={acc:.3f}")

    daso.sync_params()
    preds = np.argmax(np.asarray(model(x).numpy()), axis=1)
    acc = float((preds == y_np).mean())
    print(f"final (synced): acc={acc:.3f}")
    assert acc > 0.8, "DASO training should fit the synthetic task"


if __name__ == "__main__":
    main()
