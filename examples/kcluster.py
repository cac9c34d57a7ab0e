"""K-clustering family demo on synthetic spherical data — the analog of
the reference's examples/cluster/demo_kClustering.py (fit KMeans,
KMedians and KMedoids on a 4-cluster spherical dataset and report the
recovered centroids).

    python examples/kcluster.py [--samples 5000]
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 python examples/kcluster.py
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import heat_tpu as ht
from heat_tpu.utils.data.spherical import create_spherical_dataset


def main() -> None:
    ht.utils.place_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--samples", type=int, default=5000, help="samples per cluster")
    args = ap.parse_args()

    data = create_spherical_dataset(
        num_samples_cluster=args.samples, radius=1.0, offset=4.0,
        dtype=ht.float32, random_state=1,
    )
    print(f"data: {data.shape} split={data.split} over {data.comm.size} device(s)")

    for name, algo in [
        ("KMeans", ht.cluster.KMeans(n_clusters=4, init="kmeans++", random_state=1)),
        ("KMedians", ht.cluster.KMedians(n_clusters=4, init="kmedians++", random_state=1)),
        ("KMedoids", ht.cluster.KMedoids(n_clusters=4, init="kmedoids++", random_state=1)),
    ]:
        algo.fit(data)
        centers = np.sort(np.asarray(algo.cluster_centers_.numpy()).round(1), axis=0)
        print(f"{name:9s} n_iter={getattr(algo, 'n_iter_', '?'):>3} centers (sorted):")
        print(centers)
        # the spherical generator plants clusters at diag(-8), diag(-4),
        # diag(4), diag(8); sorted recovered centers must sit near them
        planted = np.array([[-8.0] * 3, [-4.0] * 3, [4.0] * 3, [8.0] * 3])
        assert centers.shape == (4, 3)
        assert np.abs(centers - planted).max() < 1.0, centers


if __name__ == "__main__":
    main()
