"""Distributed K-nearest-neighbour classification on the iris dataset —
the analog of the reference's examples/classification/demo_knn.py
(reference behavior: load iris.h5 split=0, 5-fold-style verification with
a held-out slice, report accuracy).

    python examples/knn.py [--neighbours 5]
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 python examples/knn.py
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import heat_tpu as ht
from heat_tpu import datasets
from heat_tpu.classification import KNeighborsClassifier


def main() -> None:
    ht.utils.place_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--neighbours", type=int, default=5)
    args = ap.parse_args()

    x = ht.load_hdf5(datasets.path("iris.h5"), dataset="data", split=0)
    # iris ships sorted by class: 50 setosa, 50 versicolor, 50 virginica
    y = ht.array(np.repeat(np.arange(3), 50), split=0)

    # hold out every 5th sample (deterministic analog of the reference's
    # random fold) — the mask routes through distributed boolean indexing
    idx = np.arange(x.shape[0])
    test_mask = idx % 5 == 0
    train_x, train_y = x[ht.array(~test_mask)], y[ht.array(~test_mask)]
    test_x, test_y = x[ht.array(test_mask)], y[ht.array(test_mask)]

    clf = KNeighborsClassifier(n_neighbors=args.neighbours)
    clf.fit(train_x, train_y)
    pred = clf.predict(test_x)

    acc = float(ht.mean((pred.astype(ht.int32) == test_y.astype(ht.int32)).astype(ht.float32)))
    print(f"kNN(k={args.neighbours}) on iris: {train_x.shape[0]} train / {test_x.shape[0]} test")
    print(f"accuracy: {acc:.3f}")
    assert acc > 0.9, "iris kNN should be >90% accurate"


if __name__ == "__main__":
    main()
