"""Fusing whole pipelines with ``ht.jit`` — no reference analog (the
reference is torch-eager; a chain of heat calls cannot be fused there).

Demonstrates the round-4 fused-program surface on a small end-to-end
feature pipeline: standardize → gram → spectral row-norms, plus a fitted
estimator's ``predict`` traced into one program.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/jit_pipeline.py
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import heat_tpu as ht


@ht.jit
def feature_pipeline(x):
    """Five public ops — ONE compiled XLA program, one dispatch."""
    x = (x - ht.mean(x, axis=0)) / (ht.std(x, axis=0) + 1e-6)
    g = ht.matmul(ht.transpose(x), x)          # (d, d) across the sharded axis
    return ht.sqrt(ht.sum(g * g, axis=1))      # spectral row-norms


def main() -> None:
    ht.utils.place_compile_cache()
    ht.random.seed(0)
    x = ht.random.randn(200_000, 64, split=0)

    t0 = time.perf_counter()
    norms = feature_pipeline(x)                # compiles on first call
    norms.numpy()
    t_compile = time.perf_counter() - t0

    t0 = time.perf_counter()
    norms = feature_pipeline(x + 0.0)          # cached program, one dispatch
    norms.numpy()
    t_cached = time.perf_counter() - t0

    # eager comparison: the same chain, one program PER op. Warm the
    # per-op programs first — timing the cold pass would charge one-time
    # compiles to the eager side (warm, THEN time)
    def eager_chain(a):
        ae = (a - ht.mean(a, axis=0)) / (ht.std(a, axis=0) + 1e-6)
        g = ht.matmul(ht.transpose(ae), ae)
        return ht.sqrt(ht.sum(g * g, axis=1))

    eager_chain(x).numpy()  # warmup/compile
    t0 = time.perf_counter()
    ref = eager_chain(x + 0.0)
    ref.numpy()
    t_eager = time.perf_counter() - t0

    np.testing.assert_allclose(norms.numpy(), ref.numpy(), rtol=1e-3, atol=1e-3)
    ht.print0(
        f"pipeline: compile {t_compile:.3f}s, fused {t_cached*1e3:.1f}ms, "
        f"eager chain {t_eager*1e3:.1f}ms (same results)"
    )

    # estimators compose: a fitted model's predict as one program
    km = ht.cluster.KMeans(n_clusters=4, init="kmeans++", random_state=0).fit(
        x[:20_000]
    )
    fused_predict = ht.jit(km.predict)
    labels = fused_predict(x[:20_000])
    ht.print0(f"fused predict: {labels.shape} labels, split={labels.split}")


if __name__ == "__main__":
    main()
