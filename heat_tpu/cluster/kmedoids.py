"""K-Medoids clustering.

API parity with /root/reference/heat/cluster/kmedoids.py: Lloyd-style
iterations where each new center snaps to the closest actual data point
of the cluster (reference kmedoids.py:116 performs the snap with extra
comm). Here an iteration is KMedians' (an L1 assignment and one counting
selection of all ``k x d`` medians, ``_kcluster._cluster_medians``), then
the snap: for one cluster at a time, the argmin of the L1 distance to its
median over its own rows.
"""

from __future__ import annotations

from typing import Optional, Union

from ..core.dndarray import DNDarray
from ..observability.tracing import call_span as _call_span
from ._kcluster import _KCluster, l1_step_for

__all__ = ["KMedoids"]


class KMedoids(_KCluster):
    """K-Medoids: centers are actual data points, the member nearest (L1) to its cluster's median.

    Manhattan metric throughout (reference: kmedoids.py:48). An iteration
    is KMedians' (an L1 assignment, then all ``k x d`` exact medians by one
    counting selection, ``_kcluster._cluster_medians``) followed by the
    snap, one cluster at a time: the row of the cluster with the least L1
    distance to its median."""

    _assignment_metric = "manhattan"

    def __init__(
        self,
        n_clusters: int = 8,
        init: Union[str, DNDarray] = "random",
        max_iter: int = 300,
        random_state: Optional[int] = None,
    ):
        if isinstance(init, str) and init == "kmedoids++":
            init = "probability_based"
        super().__init__(
            metric=lambda x, y: None,
            n_clusters=n_clusters,
            init=init,
            max_iter=max_iter,
            tol=0.0,
            random_state=random_state,
        )

    def fit(self, x: DNDarray) -> "KMedoids":
        """Seeding + convergence loop + assignment as ONE compiled program
        (see ``_kcluster._fused_fit_program``); ``inertia_`` is the sum of
        the L1 distances to the final medoids, from the label pass."""
        with _call_span("ht.call.kmedoids.fit"):
            return self._fit_fused(x, l1_step_for(x, "kmedoids", snap=True), returns_inertia=False)
