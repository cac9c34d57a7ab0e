"""Sequence-parallel attention over a long context — sequence length
shards across the mesh; K/V blocks ride the ICI ring (heat_tpu.nn
ring_attention). Run under a virtual mesh to see the sharding:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/ring_attention_longctx.py --seq 8192
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
    p.add_argument("--seq", type=int, default=4096)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--dim", type=int, default=64)
    args = p.parse_args()

    ht.random.seed(0)
    shape = (1, args.heads, args.seq, args.dim)
    q = ht.random.randn(*shape, split=2)   # sequence axis sharded
    k = ht.random.randn(*shape, split=2)
    v = ht.random.randn(*shape, split=2)
    ht.print0(f"q/k/v: {q.shape} seq-split over {q.comm.size} device(s)")

    t0 = time.perf_counter()
    out = ht.nn.ring_attention(q, k, v, causal=True)
    _ = out.numpy()
    dt = time.perf_counter() - t0
    flops = args.heads * 2 * 2 * args.seq**2 * args.dim * 0.5
    ht.print0(f"causal attention S={args.seq}: {dt*1000:.1f} ms ({flops/dt/1e12:.2f} TFLOP/s)")


if __name__ == "__main__":
    main()
