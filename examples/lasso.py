"""Distributed Lasso regression on the diabetes dataset — the analog of
the reference's examples/lasso/demo.py (load diabetes.h5 split=0,
feature-normalize, fit coordinate-descent Lasso, report coefficients
and training error; the reference additionally plots, which has no
terminal analog).

    python examples/lasso.py [--lam 0.1] [--max-iter 100]
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 python examples/lasso.py
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import heat_tpu as ht
from heat_tpu import datasets
from heat_tpu.regression import Lasso


def main() -> None:
    ht.utils.place_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--lam", type=float, default=0.1)
    ap.add_argument("--max-iter", type=int, default=100)
    args = ap.parse_args()

    x = ht.load_hdf5(datasets.path("diabetes.h5"), dataset="x", split=0)
    y = ht.load_hdf5(datasets.path("diabetes.h5"), dataset="y", split=0)

    # feature normalization, as the reference demo does before fitting
    x = x / ht.sqrt(ht.mean(x ** 2, axis=0))

    estimator = Lasso(lam=args.lam, max_iter=args.max_iter)
    estimator.fit(x, y)

    pred = estimator.predict(x)
    mse = float(ht.mean((pred - y) ** 2))
    coef = np.asarray(estimator.coef_.numpy()).ravel()
    nz = int(np.sum(np.abs(coef) > 1e-8))
    print(f"lasso(lam={args.lam}) on diabetes {x.shape}: mse={mse:.1f}")
    print(f"nonzero coefficients: {nz}/{coef.size}")
    print("coef:", np.round(coef, 2))
    assert np.isfinite(mse)


if __name__ == "__main__":
    main()
