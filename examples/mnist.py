"""Data-parallel MLP training — the analog of the reference's
examples/nn/mnist.py (BASELINE config #5), written against heat_tpu's
nn/optim/data layers.

Runs on real MNIST when IDX files are available (point --data-root at a
directory containing MNIST/raw/...); otherwise trains on a synthetic
separable classification task so the example is runnable offline.

    python examples/mnist.py [--epochs 3] [--data-root PATH]
"""

from __future__ import annotations

import argparse
import os
import sys

# allow running straight from a checkout: examples/.. is the repo root
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import heat_tpu as ht
from heat_tpu import nn, optim


def load_data(data_root, cnn=False):
    if data_root:
        from heat_tpu.utils.data.mnist import MNISTDataset

        ds = MNISTDataset(data_root, train=True)
        # MNISTDataset already scales pixels to [0, 1]
        x = np.asarray(ds.data).astype(np.float32)
        x = x.reshape(len(x), 1, 28, 28) if cnn else x.reshape(len(x), -1)
        y = ds.targets.astype(np.int32)
        return ht.array(x[:8192], split=0), ht.array(y[:8192], split=0), 784, 10
    # offline fallback: separable blobs, one per class (as 8x8 "images"
    # in cnn mode)
    rng = np.random.default_rng(0)
    n, d, k = 4096, 64 if cnn else 16, 4
    centers = rng.standard_normal((k, d)).astype(np.float32) * 4
    y = rng.integers(0, k, n).astype(np.int32)
    x = (centers[y] + rng.standard_normal((n, d))).astype(np.float32)
    if cnn:
        x = x.reshape(n, 1, 8, 8)
    return ht.array(x, split=0), ht.array(y, split=0), d, k


def cnn_net(n_cls, side):
    """The reference example's CNN (examples/nn/mnist.py:23-31: two 3x3
    convs, max-pool, dropout, two fc layers) built from heat_tpu layers."""
    flat = 64 * ((side - 4) // 2) ** 2
    return nn.Sequential(
        nn.Conv2d(1, 32, 3),
        nn.ReLU(),
        nn.Conv2d(32, 64, 3),
        nn.ReLU(),
        nn.MaxPool2d(2),
        nn.Dropout2d(0.25),
        nn.Flatten(),
        nn.Linear(flat, 128),
        nn.ReLU(),
        nn.Dropout(0.5),
        nn.Linear(128, n_cls),
    )


def main() -> None:
    ht.utils.place_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--epochs", type=int, default=3)
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--data-root", default=None)
    p.add_argument("--cnn", action="store_true",
                   help="train the reference example's Conv2d net instead of the MLP")
    args = p.parse_args()

    x, y, d_in, n_cls = load_data(args.data_root, cnn=args.cnn)
    if args.cnn:
        model = cnn_net(n_cls, x.shape[-1])
    else:
        model = nn.Sequential(nn.Linear(d_in, 128), nn.ReLU(), nn.Linear(128, n_cls))
    dp = nn.DataParallel(model)                      # grad-psum over the mesh
    opt = optim.DataParallelOptimizer(optim.SGD(lr=args.lr), dp)

    steps_per_epoch = 20
    for epoch in range(args.epochs):
        loss = None
        for _ in range(steps_per_epoch):
            loss = opt.step(x, y)
        preds = ht.argmax(dp(x), axis=1)
        acc = float(ht.mean((preds == y).astype(ht.float32)))
        ht.print0(f"epoch {epoch}: loss={float(loss):.4f} acc={acc:.3f}")


if __name__ == "__main__":
    main()
