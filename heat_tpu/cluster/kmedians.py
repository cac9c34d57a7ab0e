"""K-Medians clustering.

API parity with /root/reference/heat/cluster/kmedians.py: Lloyd-style
iterations where the centroid update is the per-cluster coordinate-wise
median (reference computes distributed medians with extra comm per
cluster). Here an iteration is an L1 assignment that never holds
``n x k x d`` and one selection of all ``k x d`` medians at once
(``_kcluster._cluster_medians``): a radix selection that counts keys under
thresholds, two bits a pass over ``X``, exact, with no copy of ``X`` and a
number of passes that does not grow with ``k``. The selection is
``core/_selection.py``'s, by label (``ht.percentile`` runs it for all rows).
On a TPU, for tall narrow f32 data, the passes are Pallas kernels (the
assignment in ``_pallas_l1``, the selection's in ``core/_pallas_select``); on a
split array the counts of the shards are summed before a bracket narrows.
"""

from __future__ import annotations

from typing import Optional, Union

from ..core.dndarray import DNDarray
from ..observability.tracing import call_span as _call_span
from ._kcluster import _KCluster, l1_step_for

__all__ = ["KMedians"]


class KMedians(_KCluster):
    """K-Medians: centers are the exact coordinate-wise medians, found by counting, not sorting.

    Assignment and functional value use the Manhattan metric (reference:
    kmedians.py:49 passes ht.spatial.distance.manhattan). The update is
    ``_kcluster._cluster_medians``: all ``k x d`` medians of one iteration
    at once, by a radix selection over the order-preserving integer image
    of the values (two bits a pass over ``X``, then the upper middle value
    of the even counts): the value ``numpy.median`` of each cluster's rows
    gives, with no copy of ``X`` and a number of passes that does not grow
    with ``k``. ``inertia_`` is the sum of the L1 distances to the final
    centres."""

    _assignment_metric = "manhattan"

    def __init__(
        self,
        n_clusters: int = 8,
        init: Union[str, DNDarray] = "random",
        max_iter: int = 300,
        tol: float = 1e-4,
        random_state: Optional[int] = None,
    ):
        if isinstance(init, str) and init == "kmedians++":
            init = "probability_based"
        super().__init__(
            metric=lambda x, y: None,
            n_clusters=n_clusters,
            init=init,
            max_iter=max_iter,
            tol=tol,
            random_state=random_state,
        )

    def fit(self, x: DNDarray) -> "KMedians":
        """Seeding + convergence loop + assignment as ONE compiled program
        (see ``_kcluster._fused_fit_program``); ``inertia_`` is the sum of
        the L1 distances to the final centres, from the label pass."""
        with _call_span("ht.call.kmedians.fit"):
            return self._fit_fused(x, l1_step_for(x, "kmedians"), returns_inertia=False)
