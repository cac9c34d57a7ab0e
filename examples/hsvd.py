"""Hierarchical SVD of a distributed matrix — the north-star operation
(reference blog: hSVD of a 200 GB dataset; BASELINE.json target).

    python examples/hsvd.py [--rows 16384] [--cols 2048] [--rank 10]
"""

from __future__ import annotations

import argparse
import os
import sys

# allow running straight from a checkout: examples/.. is the repo root
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import time

import heat_tpu as ht


def main() -> None:
    ht.utils.place_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--rows", type=int, default=16384)
    p.add_argument("--cols", type=int, default=2048)
    p.add_argument("--rank", type=int, default=10)
    args = p.parse_args()

    ht.random.seed(0)
    a = ht.random.randn(args.rows, args.cols, split=0)
    ht.print0(f"A: {a.shape} split={a.split} over {a.comm.size} device(s)")

    # first call compiles (~seconds); measure the warm path
    u, sigma, v, err = ht.linalg.hsvd_rank(a, args.rank, compute_sv=True)
    _ = u.numpy()
    t0 = time.perf_counter()
    u, sigma, v, err = ht.linalg.hsvd_rank(a, args.rank, compute_sv=True)
    _ = u.numpy()  # materialize before stopping the clock
    dt = time.perf_counter() - t0

    gb = args.rows * args.cols * 4 / 1e9
    per_chip = gb / dt / a.comm.size
    ht.print0(
        f"hsvd_rank(r={args.rank}): {dt*1000:.1f} ms  "
        f"({gb/dt:.1f} GB/s aggregate, {per_chip:.1f} GB/s/chip)  "
        f"rel-err estimate {float(err):.3f}"
    )
    ht.print0(f"sigma: {sigma.numpy().round(2)}")

    # one-view variant (r5): reads A exactly ONCE — ~1.7x on TPU for
    # near-low-rank data (docs/PERF.md documents the quality trade).
    # NOTE this demo's random matrix is flat-spectrum — OUT of one-view's
    # domain, so expect a large (and honest) error estimate; the row
    # demonstrates the throughput, not the approximation.
    u1, err1 = ht.linalg.hsvd_rank(a, args.rank, single_pass=True)
    _ = u1.numpy()
    t0 = time.perf_counter()
    u1, err1 = ht.linalg.hsvd_rank(a, args.rank, single_pass=True)
    _ = u1.numpy()
    dt1 = time.perf_counter() - t0
    ht.print0(
        f"hsvd_rank(single_pass=True): {dt1*1000:.1f} ms  "
        f"({gb/dt1:.1f} GB/s aggregate)  rel-err estimate {float(err1):.3f}"
    )


if __name__ == "__main__":
    main()
