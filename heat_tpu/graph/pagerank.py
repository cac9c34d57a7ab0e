"""PageRank as a streamed SpMV fixpoint on the sparse engine.

The power iteration over the column-stochastic transition operator

    r  <-  alpha * (M @ r  +  dangling_mass / n)  +  (1 - alpha) / n

with ``M = A^T D_out^{-1}`` — every iteration is ONE distributed SpMV
on the DBCSR brick engine (kernels/spmm.py), so the damping/teleport
arithmetic rides for free on the host between multiplies and the whole
fixpoint inherits the engine's 0-collective local census and the
``HEAT_TPU_SPMM_KERNEL`` gate.

Two forms:

* :func:`pagerank` — the transition matrix lives on the mesh as a
  ``DBCSR_matrix``; right for graphs whose edge structure fits HBM.
* :func:`pagerank_stream` — the edge list never materializes on
  device: a :class:`~heat_tpu.redistribution.staging.HostArray` of
  (src, dst) pairs streams through the PR 11 staging windows
  (depth-2 double-buffered ``stream_windows``, plan-stamped by
  ``plan_staged_passes``) and each window's contribution lands via a
  segment-sum — PageRank on graphs larger than HBM, the ROADMAP's
  "larger-than-HBM" scenario applied to edges instead of samples.

Both forms converge to the same fixpoint (same operator, different
storage tier); ``tests/test_graph.py`` pins them against a dense numpy
oracle on seeded random graphs.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Union

import numpy as np

import jax
import jax.numpy as jnp

from ..core import factories, types
from ..core.dndarray import DNDarray
from ..core.devices import Device
from ..core.communication import Communication
from ..sparse.dbcsr_matrix import DBCSR_matrix, sparse_dbcsr_matrix
from ..sparse.dcsr_matrix import DCSR_matrix
from ..sparse.factories import _to_scipy_csr
from ..redistribution import staging as _staging

__all__ = ["PageRankResult", "pagerank", "pagerank_stream"]


class PageRankResult(NamedTuple):
    """Outcome of a PageRank fixpoint run."""

    ranks: DNDarray          # (n,) — sums to 1
    iterations: int          # SpMV sweeps taken
    converged: bool          # l1 delta fell under tol before max_iter
    delta: float             # final l1 step size


def _adjacency_to_scipy(A) -> "np.ndarray":
    """Adjacency (A[i, j] != 0 is an edge i -> j) to host scipy CSR."""
    import scipy.sparse as sp

    if isinstance(A, DBCSR_matrix):
        return A._to_scipy_bsr().tocsr()[: A.shape[0], : A.shape[1]]
    if isinstance(A, DCSR_matrix):
        indptr = np.asarray(jax.device_get(A.indptr))
        indices = np.asarray(jax.device_get(A.indices))
        data = np.asarray(jax.device_get(A.data))
        return sp.csr_matrix((data, indices, indptr), shape=A.shape)
    if isinstance(A, DNDarray):
        return sp.csr_matrix(np.asarray(A.numpy()))
    return _to_scipy_csr(A, None)


def _transition(csr, dtype_np):
    """Column-stochastic M = A^T D_out^{-1} plus the dangling mask.

    Rows of A with no out-edges (dangling nodes) have no column in M;
    their rank mass teleports uniformly — handled in the iteration, not
    the matrix, so M keeps the graph's sparsity exactly."""
    import scipy.sparse as sp

    n = csr.shape[0]
    outdeg = np.asarray(csr.sum(axis=1)).ravel()
    dangling = outdeg == 0
    inv = np.where(dangling, 0.0, 1.0 / np.where(dangling, 1.0, outdeg))
    M = (sp.diags(inv) @ csr).T.tocsr().astype(dtype_np)
    return M, dangling.astype(dtype_np), n


def pagerank(
    A: Union[DBCSR_matrix, DCSR_matrix, DNDarray, "object"],
    alpha: float = 0.85,
    tol: float = 1e-8,
    max_iter: int = 200,
    split: Optional[int] = 0,
    device: Optional[Device] = None,
    comm: Optional[Communication] = None,
) -> PageRankResult:
    """PageRank of a directed graph given its adjacency structure.

    ``A[i, j] != 0`` is an edge ``i -> j`` (weights count as edge
    multiplicity). The transition matrix is built once host-side, lands
    on the mesh as a row-distributed ``DBCSR_matrix``, and the fixpoint
    runs one brick-engine SpMV per iteration. ``alpha`` is the damping
    factor, ``tol`` the l1 convergence threshold on the rank delta.
    """
    if not 0.0 < alpha < 1.0:
        raise ValueError(f"alpha must be in (0, 1), got {alpha}")
    csr = _adjacency_to_scipy(A)
    if csr.shape[0] != csr.shape[1]:
        raise ValueError(f"adjacency must be square, got {csr.shape}")
    M_host, dangling, n = _transition(csr, np.float32)
    M = sparse_dbcsr_matrix(M_host, dtype=types.float32, split=split,
                            device=device, comm=comm)
    r = np.full(n, 1.0 / n, np.float32)
    delta = np.inf
    it = 0
    for it in range(1, max_iter + 1):
        mass = float(dangling @ r)  # dangling rank teleports uniformly
        y = M @ jnp.asarray(r)
        r_new = np.asarray(y.numpy()) * alpha + np.float32(
            (alpha * mass + (1.0 - alpha)) / n
        )
        delta = float(np.abs(r_new - r).sum())
        r = r_new
        if delta < tol:
            break
    ranks = factories.array(r / r.sum(), dtype=types.float32, split=split,
                            device=device, comm=comm)
    return PageRankResult(ranks, it, delta < tol, delta)


def pagerank_stream(
    edges: Union[_staging.HostArray, np.ndarray],
    n: int,
    alpha: float = 0.85,
    tol: float = 1e-8,
    max_iter: int = 200,
    slab: Optional[int] = None,
) -> PageRankResult:
    """PageRank from a host-resident edge list that never fully lands
    on device.

    ``edges`` is an (E, 2) int32 ``HostArray`` (or ndarray, wrapped) of
    ``(src, dst)`` pairs — duplicates count as multiplicity, matching
    the weighted adjacency of :func:`pagerank`. One streamed pass
    computes the out-degrees; each fixpoint iteration then re-streams
    the edges through the PR 11 depth-2 windows, accumulating
    ``segment_sum(r[src] / outdeg[src], dst)`` per window. The staged
    plan is stamped (``plan_staged_passes`` + ``prove_fits``), so the
    stream's spans carry its ``plan_id`` like every other staged workload.
    """
    if not 0.0 < alpha < 1.0:
        raise ValueError(f"alpha must be in (0, 1), got {alpha}")
    if not isinstance(edges, _staging.HostArray):
        edges = _staging.HostArray(np.ascontiguousarray(edges, np.int32))
    if edges.shape[1] != 2:
        raise ValueError(f"edges must be (E, 2) (src, dst), got {edges.shape}")
    n = int(n)
    sched = _staging.plan_staged_passes(
        edges.shape,
        edges.dtype,
        [{"tag": "outdeg", "axis": 0}, {"tag": "power", "axis": 0}],
        out_bytes=3 * n * 4 + (1 << 20),  # r, outdeg, accumulator in HBM
        slab=slab,
    )
    _staging.prove_fits(sched)
    slab_b = int(sched.staging["slab_bytes"])
    wins = _staging.window_extents(edges.shape, edges.dtype.itemsize, 0, slab_b)

    @jax.jit
    def _deg_window(acc, slab_arr):
        return acc + jax.ops.segment_sum(
            jnp.ones(slab_arr.shape[0], jnp.float32), slab_arr[:, 0],
            num_segments=n,
        )

    @jax.jit
    def _power_window(acc, slab_arr, w):
        return acc + jax.ops.segment_sum(
            w[slab_arr[:, 0]], slab_arr[:, 1], num_segments=n
        )

    # pass 1: out-degrees (windowed bincount of the src column)
    outdeg = jnp.zeros(n, jnp.float32)

    def _consume_deg(k, slab_arr, win):
        nonlocal outdeg
        outdeg = _deg_window(outdeg, slab_arr)

    _staging.stream_windows(edges, 0, wins, _consume_deg, plan_id=sched.plan_id)
    dangling = np.asarray(jax.device_get(outdeg)) == 0
    inv = jnp.asarray(np.where(dangling, 0.0, 1.0 / np.maximum(
        np.asarray(jax.device_get(outdeg)), 1e-30)).astype(np.float32))

    r = np.full(n, 1.0 / n, np.float32)
    delta = np.inf
    it = 0
    for it in range(1, max_iter + 1):
        w = jnp.asarray(r) * inv
        acc = jnp.zeros(n, jnp.float32)

        def _consume_pow(k, slab_arr, win):
            nonlocal acc
            acc = _power_window(acc, slab_arr, w)

        _staging.stream_windows(edges, 0, wins, _consume_pow,
                                plan_id=sched.plan_id)
        mass = float(r[dangling].sum())
        r_new = np.asarray(jax.device_get(acc)) * alpha + np.float32(
            (alpha * mass + (1.0 - alpha)) / n
        )
        delta = float(np.abs(r_new - r).sum())
        r = r_new
        if delta < tol:
            break
    ranks = factories.array(r / r.sum(), dtype=types.float32, split=None)
    return PageRankResult(ranks, it, delta < tol, delta)
