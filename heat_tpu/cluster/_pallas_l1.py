"""Pallas TPU kernel of the L1 family's assignment (KMedians, KMedoids), and
the passes one of their iterations is made of: the assignment here, the median
selection's from ``core/_pallas_select.py`` (the exact counting selection that
``ht.percentile`` runs too; by label here: a row counts for its own cluster's
medians). Nothing is of ``X``'s size besides ``X`` and the label vector.

``X`` is tiled as KMeans' pass tiles it (``_pallas``): the chip keeps a tall
``f32[n, d]`` with ``d < 128`` feature-major, ``x.T`` is a bitcast, a grid
step takes a block ``(d, tn)`` with the rows on the lanes.

    assign   lab = first argmin_c sum_j |x_j - c_cj|      VPU, k x d a row: no matmul form
             counts (int32), sum of the least distances   lane partials
    count, next, gather, and the two kernels over the kept keys: ``core/_pallas_select.py``

Every kernel that reads all of ``X`` is named for its phase,
``kmedians.assign.pass`` or ``kmedians.select.pass`` (``count``, ``next`` and
``gather``): one op of such a name is one whole read of ``X``, and the
benchmark's readers count reads of ``X`` by these names (``docs/API.md``,
observability). The two kernels over the kept keys are named
``kmedians.select.candidates``: they read no ``X``.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import shard_map as _shard_map
from jax.sharding import PartitionSpec as P

from jax.experimental import pallas as pl

from ..core import _pallas_select as _ps
from ..core._pallas_select import _MOST_CLUSTERS, _call, _const, _grid_kernel, _lane_partials, _round_up, _table, _vpu_tn, _x_block
from ._pallas import lloyd_pass_serves

__all__ = ["L1Passes", "l1_passes", "l1_passes_serve"]

_SELECT = "kmedians.select"  # the selection's kernels: ``.pass`` over X, ``.candidates`` over the kept keys


class L1Passes(NamedTuple):
    """The passes an L1 iteration is made of, on whole (global) arrays.
    ``_kcluster`` holds the ``jax.numpy`` form of the first three; the last
    three are ``None`` where the selection stays on ``X`` to its end."""

    assign: Callable      # (arr, centers) -> labels int32 (n,), counts int32 (k,), fun f32 ()
    count_below: Callable  # (arr, labels, thr0 (k, d), step ()) -> int32 (T, k, d)
    next_above: Callable  # (arr, labels, at (k, d)) -> (k, d) keys, the type's max where none
    gather: Optional[Callable] = None      # (arr, labels, base (k, d), bits (k, d), skip bool ()) -> kept int32 (d, m), spilled ()
    kept_below: Optional[Callable] = None  # (kept, thr (q, d)) -> int32 (q, d): kept[j] < thr[i, j]
    kept_above: Optional[Callable] = None  # (kept, at (q, d)) -> int32 (q, d): least kept[j] > at[i, j]


def l1_passes_serve(backend: str, dtype, shape, k: int, split, devices: int = 1) -> bool:
    """The gate, a pure function of what the code sees in its input: where
    KMeans' pass serves (a TPU, f32, ``d`` a multiple of 8 under 128, one
    device or equal split-0 shards: ``_pallas.lloyd_pass_serves``) and
    ``2 <= k <= 32`` (the assignment and the selects are unrolled over
    ``k``; at ``k = 1`` the chip's compiler aborts on the assignment, whose
    least distance is then the bare sublane reduction)."""
    return 2 <= k <= _MOST_CLUSTERS and lloyd_pass_serves(backend, dtype, shape, k, split, devices)


@functools.lru_cache(maxsize=64)
def _assign_program(n: int, d: int, k: int, interpret: bool):
    k8, tn = _round_up(k, 8), _vpu_tn(n, d, _round_up(k, 8))

    def tile(refs, valid):
        xt_ref, ct_ref, lab_ref, cnt_ref, fun_ref = refs
        x = xt_ref[...]  # (d, tn) f32
        to = lambda c: jnp.sum(jnp.abs(x - ct_ref[:, c:c + 1]), axis=0, keepdims=True)  # (1, tn)
        best, lab = to(0), jnp.zeros((1, tn), jnp.int32)
        for c in range(1, k):
            dist = to(c)
            lab = jnp.where(dist < best, c, lab)  # strictly less: the first of equals, as argmin has it
            best = jnp.minimum(dist, best)
        onehot = jax.lax.broadcasted_iota(jnp.int32, (k8, tn), 0) == lab
        if valid is not None:
            onehot = onehot & valid
            best = jnp.where(valid, best, 0.0)
        cnt_ref[...] += _lane_partials(jnp.where(onehot, 1, 0), tn)
        fun_ref[...] += _lane_partials(best, tn)
        lab_ref[...] = lab.reshape(tn)

    call = _call(
        _grid_kernel(tile, n, tn, 2, 0), "kmedians.assign.pass", n, tn,
        [_x_block(d, tn), _const((d, k8))],
        [jax.ShapeDtypeStruct((n,), jnp.int32), jax.ShapeDtypeStruct((k8, 128), jnp.int32),
         jax.ShapeDtypeStruct((1, 128), jnp.float32)],
        [pl.BlockSpec((tn,), lambda i: (i,), memory_space=_ps._VMEM), _const((k8, 128)), _const((1, 128))],
        interpret,
    )

    def run(x, centers):
        labels, cnt, fun = call(x.T, _table(centers, k8, jnp.float32))  # x.T: a bitcast of the feature-major array
        return labels, jnp.sum(cnt[:k], axis=1), jnp.sum(fun)

    return run


@functools.lru_cache(maxsize=64)
def l1_passes(k: int, shape, mesh=None, axis_name=None, interpret: bool = False) -> L1Passes:
    """The passes for ``arr`` of ``shape``: the assignment, and the
    selection's by label (``_pallas_select.select_passes``; those over the
    kept keys where ``gather_pays`` for one device's rows). On one device
    they are called bare. With a ``mesh`` they run under ``shard_map``, as
    KMeans' pass does: with an ``axis_name``, ``arr`` and the labels are
    split 0 over it in equal shards, each device passes over its rows (and
    keeps their keys), and the counts are ``psum``med (the successors:
    ``pmin``, the spill flag: ``pmax``) before any bracket narrows; without
    one ``arr`` is replicated and every device runs the whole pass."""
    n, d = int(shape[0]), int(shape[1])
    p = mesh.devices.size if axis_name is not None else 1
    assign = _assign_program(n // p, d, k, interpret)
    select = _ps.select_passes((n, d), k, True, _SELECT, mesh, axis_name, interpret)[:5]
    if mesh is None:
        return L1Passes(assign, *select)
    if axis_name is not None:
        local_assign = assign

        def assign(arr, centers):
            labels, cnt, fun = local_assign(arr, centers)
            return labels, jax.lax.psum(cnt, axis_name), jax.lax.psum(fun, axis_name)

    assign = _shard_map(assign, mesh=mesh, check_vma=False, in_specs=(P(axis_name, None), P()),
                        out_specs=(P(axis_name), P(), P()))
    return L1Passes(assign, *select)
