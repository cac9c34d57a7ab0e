"""Brick SpMM / SDDMM kernels for the DBCSR format.

The compute unit is the DBCSR brick — one (8, 128) f32 VREG tile
(sparse/dbcsr_matrix.py) — and both contraction families reduce to a
stream of dense (8,128)x(128,k) brick matmuls plus one masked
segment-sum over brick rows:

* **SpMM** ``y = A @ x``: per stored brick ``t``, ``contrib[t] =
  bdata[t] @ xb[bcol[t]]`` where ``xb`` is the dense operand viewed as
  (nb, 128, k) brick slabs; contributions land on the brick's 8 output
  rows via ``segment_sum``. Straddle/pad bricks route their non-owned
  rows to a dropped segment through the precomputed ``bmask``.
* **SDDMM** ``C = S \\circ (U @ V^T)``: per stored brick, ``out[t] =
  sdata[t] * (ub[brow[t]] @ vb[bcol[t]]^T)`` — the sampled dense-dense
  product that only ever computes the stored tiles.

Two implementations per family, dispatched by ``HEAT_TPU_SPMM_KERNEL``
(core/gates.py):

* ``xla`` (the oracle/floor, gate ``0``): brick-level ``take`` of the
  dense operand — a coarse-grained (128*k)-element contiguous gather
  per brick, NOT a per-element gather — followed by one batched matmul
  and the segment-sum. Pure XLA, runs anywhere, and is the
  bit-identity reference.
* ``pallas`` (gate ``1``): a scalar-prefetch brick kernel — the brick
  column map rides ``PrefetchScalarGridSpec`` so each grid step DMAs
  exactly the X (or U/V) brick it needs straight into VMEM and issues
  one MXU matmul. Gather-free by construction: the index never touches
  the vector units. On CPU the same kernel runs under
  ``interpret=True`` (the ci.sh forced leg), so the path is testable
  off-TPU; the accumulation stays in the SAME XLA segment-sum as the
  oracle, which is what makes kernel-on == kernel-off bit-identical.

``auto`` resolves to the oracle off-TPU and to a per-signature
autotune on TPU (eager, timed to ``block_until_ready``, cached per
(family, B, k, dtype) signature). Telemetry:
``sparse.kernel.hit`` counts brick-kernel dispatches,
``sparse.kernel.fallback`` oracle dispatches.

Distribution: the per-device slab layout makes every device's bricks
sufficient for its canonical output rows, so the distributed programs
are ``shard_map`` LOCAL programs — 0 collectives, pinned by
tests/test_spmm.py's census. A split dense operand is resharded to
replicated BEFORE the local program through ``comm.reshard_phys`` (the
redistribution planner: plan-stamped, shardlint info-downgraded).

Accumulation dtype: low-precision brick data (bf16/f16) is widened to
f32 for the brick matmuls and the segment-sum, cast back at the end —
SL601-clean by construction.
"""

from __future__ import annotations

import functools
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from ..core import gates as _gates
from ..core import _padding
from ..observability.tracing import span as _span

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "spmm_kernel_mode",
    "decide",
    "last_decisions",
    "spmm_bcsr_program",
    "sddmm_bcsr_program",
]

BR, BC = 8, 128  # brick sublanes x lanes (sparse.dbcsr_matrix.BRICK_SHAPE)


# --------------------------------------------------------------------- #
# gate / dispatch                                                       #
# --------------------------------------------------------------------- #
def _mode() -> str:
    v = _gates.get("HEAT_TPU_SPMM_KERNEL", "auto").strip().lower()
    if v in ("0", "off", "false"):
        return "0"
    if v in ("1", "on", "true", "force"):
        return "1"
    return "auto"


def spmm_kernel_mode() -> str:
    """The resolved ``HEAT_TPU_SPMM_KERNEL`` mode (``"0"``/``"1"``/
    ``"auto"``) — introspection for tests and bench records. Cache
    staleness on env flips is handled by keying the compiled programs
    on the DECIDED path string this mode feeds (see :func:`decide`)."""
    return _mode()


def _inc(name: str) -> None:
    from ..observability import telemetry

    telemetry.inc(name)


#: last dispatch decision per signature — bench/test introspection
_DECISIONS: dict = {}

#: autotune winners per signature (TPU only; only autotuned entries
#: may answer ``auto`` mode)
_AUTOTUNE: dict = {}


def last_decisions() -> dict:
    return dict(_DECISIONS)


def _acc_dtype(jt: jnp.dtype) -> jnp.dtype:
    """f32 accumulation for sub-f32 brick data (SL601 by construction)."""
    if jnp.dtype(jt) in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float16)):
        return jnp.dtype(jnp.float32)
    return jnp.dtype(jt)


def decide(family: str, B: int, k: int, jdtype: str) -> str:
    """Resolve the implementation path (``"xla"``/``"pallas"``) for one
    (family, bricks, dense-cols, dtype) signature under the gate."""
    mode = _mode()
    sig = (family, int(B), int(k), str(jdtype))
    if mode == "0":
        d = {"path": "xla", "why": "gate=0"}
    elif mode == "1":
        d = {"path": "pallas", "why": "gate=1"}
    elif jax.default_backend() != "tpu":
        # auto off-TPU: the interpreted kernel is a debugging vehicle,
        # never a performance one — oracle wins without measurement
        d = {"path": "xla", "why": "auto:cpu-oracle"}
    else:
        d = _AUTOTUNE.get(sig)
        if d is None:
            d = _autotune(sig)
    _DECISIONS[sig] = d
    _inc("sparse.kernel.hit" if d["path"] == "pallas" else "sparse.kernel.fallback")
    return d["path"]


def _autotune(sig) -> dict:
    """Time both paths on synthetic operands of this signature (TPU
    only, eager — never under a trace) and cache the winner: median of
    3. A kernel the backend refuses is recorded with the compiler's
    message in ``why`` (visible in :func:`last_decisions`); the oracle
    then serves, and a forced gate (``=1``) raises instead."""
    family, B, k, jdtype = sig
    jt = jnp.dtype(jdtype)
    nb = max(2, min(B, 64))
    key = jax.random.key(7)
    bdata = jax.random.normal(key, (B, BR, BC), dtype=jnp.float32).astype(jt)
    bcol = (jnp.arange(B, dtype=jnp.int32) * 7) % nb
    if family == "spmm":
        xb = jax.random.normal(key, (nb, BC, k), dtype=jnp.float32).astype(jt)

        def run_xla():
            return _contrib_xla(bdata, xb, bcol, jt)

        def run_pallas():
            return _brick_spmm_call(B, nb, k, jt.name, False)(bcol, bdata, xb)
    else:
        mb = max(2, min(B, 64))
        brow = (jnp.arange(B, dtype=jnp.int32) * 3) % mb
        ub = jax.random.normal(key, (mb, BR, k), dtype=jnp.float32).astype(jt)
        vb = jax.random.normal(key, (nb, BC, k), dtype=jnp.float32).astype(jt)

        def run_xla():
            return _sddmm_xla(bdata, ub, vb, brow, bcol, jt)

        def run_pallas():
            return _brick_sddmm_call(B, mb, nb, k, jt.name, False)(
                brow, bcol, bdata, ub, vb
            )

    def _time(fn) -> float:
        with _span("ht.sync.wait", what="spmm.autotune"):
            jax.block_until_ready(fn())  # compile + warm
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            with _span("ht.sync.wait", what="spmm.autotune"):
                jax.block_until_ready(fn())
            ts.append(time.perf_counter() - t0)
        ts.sort()
        return ts[1]

    t_o = _time(run_xla)
    try:
        t_k = _time(run_pallas)
    except Exception as e:  # the backend refused the brick kernel
        d = {"path": "xla", "why": f"autotune: pallas refused: {type(e).__name__}: {e}"}
    else:
        d = {
            "path": "pallas" if t_k < t_o else "xla",
            "why": f"autotune:{t_k * 1e6:.0f}us-vs-{t_o * 1e6:.0f}us",
            "autotuned": True,
        }
    _AUTOTUNE[sig] = d
    return d


# --------------------------------------------------------------------- #
# brick contraction implementations                                     #
# --------------------------------------------------------------------- #
def _contrib_xla(bdata, xb, bcol, jt):
    """Oracle SpMM contributions: brick-level take + batched matmul.
    The take moves contiguous (128, k) slabs — XLA's coarse dynamic
    gather, nothing per-element."""
    xg = jnp.take(xb, bcol, axis=0)
    return jax.vmap(lambda a, b: jnp.dot(a, b, preferred_element_type=jt))(
        bdata, xg
    )


def _sddmm_xla(sdata, ub, vb, brow, bcol, jt):
    """Oracle SDDMM bricks: take the U/V bricks, one batched matmul,
    scale by the stored values (the Hadamard/sampled form)."""
    ug = jnp.take(ub, brow, axis=0)
    vg = jnp.take(vb, bcol, axis=0)
    prod = jax.vmap(lambda a, b: jnp.dot(a, b.T, preferred_element_type=jt))(
        ug, vg
    )
    return sdata.astype(jt) * prod


@functools.lru_cache(maxsize=128)
def _brick_spmm_call(B: int, nb: int, k: int, jdtype: str, interpret: bool):
    """The scalar-prefetch SpMM brick kernel: grid over the B slab
    bricks; the prefetched ``bcol`` drives the X-brick index map, so the
    needed (128, k) brick is DMA'd per step — no gather instruction."""
    jt = jnp.dtype(jdtype)

    def kernel(bcol_ref, bdata_ref, xb_ref, out_ref):
        out_ref[0] = jnp.dot(bdata_ref[0], xb_ref[0], preferred_element_type=jt)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, BR, BC), lambda i, bcol: (i, 0, 0)),
            pl.BlockSpec((1, BC, k), lambda i, bcol: (bcol[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, BR, k), lambda i, bcol: (i, 0, 0)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, BR, k), jt),
        interpret=interpret,
    )


@functools.lru_cache(maxsize=128)
def _brick_sddmm_call(
    B: int, mb: int, nb: int, d: int, jdtype: str, interpret: bool
):
    """The scalar-prefetch SDDMM brick kernel: ``brow``/``bcol`` drive
    the U-/V-brick index maps; each step computes one stored tile."""
    jt = jnp.dtype(jdtype)

    def kernel(brow_ref, bcol_ref, sdata_ref, ub_ref, vb_ref, out_ref):
        prod = jnp.dot(ub_ref[0], vb_ref[0].T, preferred_element_type=jt)
        out_ref[0] = sdata_ref[0].astype(jt) * prod

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, BR, BC), lambda i, brow, bcol: (i, 0, 0)),
            pl.BlockSpec((1, BR, d), lambda i, brow, bcol: (brow[i], 0, 0)),
            pl.BlockSpec((1, BC, d), lambda i, brow, bcol: (bcol[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, BR, BC), lambda i, brow, bcol: (i, 0, 0)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, BR, BC), jt),
        interpret=interpret,
    )


# --------------------------------------------------------------------- #
# distributed programs                                                  #
# --------------------------------------------------------------------- #
def _local_spmm(bdata, bcol, brow, bmask, x, r, *, nb, B, c, jt, acc, path):
    """One device's SpMM: brick contractions + masked segment-sum into
    the device's canonical c output rows. Collective-free."""
    k = x.shape[1]
    # k == 1 hits XLA:CPU's matvec special case, whose reduction order
    # differs between the batched (oracle) and per-brick (interpret
    # kernel) contractions — zero-pad to k=2 so both take the bitwise-
    # identical matmul path (the pad column contributes exact zeros)
    kk = max(k, 2)
    if kk != k:
        x = jnp.pad(x, ((0, 0), (0, kk - k)))
    xp = jnp.pad(x.astype(acc), ((0, nb * BC - x.shape[0]), (0, 0)))
    xb = xp.reshape(nb, BC, kk)
    bd = bdata.astype(acc)
    if path == "pallas":
        interpret = jax.default_backend() != "tpu"
        contrib = _brick_spmm_call(B, nb, kk, acc.name, interpret)(bcol, bd, xb)
    else:
        contrib = _contrib_xla(bd, xb, bcol, acc)
    if kk != k:
        contrib = contrib[..., :k]
    rows = (
        brow[:, None].astype(jnp.int32) * BR
        + jnp.arange(BR, dtype=jnp.int32)[None, :]
        - r * c
    )
    rows = jnp.where(bmask, rows, c)  # non-owned / pad rows -> dropped
    y = jax.ops.segment_sum(
        contrib.reshape(-1, k), rows.reshape(-1), num_segments=c + 1
    )[:c]
    return y.astype(jt)


@functools.lru_cache(maxsize=256)
def spmm_bcsr_program(comm, m: int, nb: int, B: int, split, out_ndim: int,
                      jdtype: str, path: str):
    """(bdata, bcol, brow, bmask, x2d) -> y physical. ``split == 0`` on
    a real mesh runs as a shard_map LOCAL program — each device computes
    exactly its canonical output rows from its own brick slab and the
    replicated dense operand: 0 collectives (the pinned census)."""
    jt = jnp.dtype(jdtype)
    acc = _acc_dtype(jt)
    p = comm.size if split == 0 else 1
    c = _padding.pad_extent(m, p) // p if (split == 0 and p > 1) else max(m, 1)
    kw = dict(nb=nb, B=B, c=c, jt=jt, acc=acc, path=path)

    if split == 0 and p > 1:
        ax = comm.axis_name

        def local(bdata, bcol, brow, bmask, x):
            r = lax.axis_index(ax)
            return _local_spmm(bdata, bcol, brow, bmask, x, r, **kw)

        fn = shard_map(
            local,
            mesh=comm.mesh,
            in_specs=(P(ax, None, None), P(ax), P(ax), P(ax, None), P(None, None)),
            out_specs=P(ax, None),
            check_vma=False,  # pallas_call outputs carry no vma annotation
        )

        def run(bdata, bcol, brow, bmask, x):
            y = fn(bdata, bcol, brow, bmask, x)
            return y if out_ndim == 2 else y[:, 0]

        return jax.jit(run)  # shardlint: ignore[SL202] -- lru-cached brick program keyed on the gate-decided path; operands are reused across calls so donation is unwanted, and the sharded path routes through comm.jit_sharded

    def run(bdata, bcol, brow, bmask, x):
        y = _local_spmm(bdata, bcol, brow, bmask, x, 0, **kw)[:m]
        return y if out_ndim == 2 else y[:, 0]

    return comm.jit_sharded(run, out_ndim, split)


def _local_sddmm(sdata, bcol, brow, u, v, *, mb, nb, B, jt, acc, path):
    """One device's SDDMM bricks. Collective-free: U/V arrive
    replicated, the takes are brick-level and local."""
    d = u.shape[1]
    # same k==1 matvec-codepath hazard as _local_spmm: zero-pad the
    # contraction dim to 2 (pad terms are exact zeros)
    dd = max(d, 2)
    if dd != d:
        u = jnp.pad(u, ((0, 0), (0, dd - d)))
        v = jnp.pad(v, ((0, 0), (0, dd - d)))
    up = jnp.pad(u.astype(acc), ((0, mb * BR - u.shape[0]), (0, 0)))
    vp = jnp.pad(v.astype(acc), ((0, nb * BC - v.shape[0]), (0, 0)))
    ub = up.reshape(mb, BR, dd)
    vb = vp.reshape(nb, BC, dd)
    sd = sdata.astype(acc)
    if path == "pallas":
        interpret = jax.default_backend() != "tpu"
        out = _brick_sddmm_call(B, mb, nb, dd, acc.name, interpret)(
            brow, bcol, sd, ub, vb
        )
    else:
        out = _sddmm_xla(sd, ub, vb, brow, bcol, acc)
    return out.astype(jt)


@functools.lru_cache(maxsize=256)
def sddmm_bcsr_program(comm, mb: int, nb: int, B: int, split, jdtype: str,
                       path: str):
    """(sdata, bcol, brow, u, v) -> new brick data physical, same slab
    layout as the pattern operand. shard_map local on a real mesh —
    0 collectives, same census pin as SpMM."""
    jt = jnp.dtype(jdtype)
    acc = _acc_dtype(jt)
    p = comm.size if split == 0 else 1
    kw = dict(mb=mb, nb=nb, B=B, jt=jt, acc=acc, path=path)

    if split == 0 and p > 1:
        ax = comm.axis_name

        def local(sdata, bcol, brow, u, v):
            return _local_sddmm(sdata, bcol, brow, u, v, **kw)

        fn = shard_map(
            local,
            mesh=comm.mesh,
            in_specs=(P(ax, None, None), P(ax), P(ax), P(None, None), P(None, None)),
            out_specs=P(ax, None, None),
            check_vma=False,  # pallas_call outputs carry no vma annotation
        )
        return jax.jit(fn)  # shardlint: ignore[SL202] -- lru-cached brick program (see spmm_bcsr_program); sharded path routes through comm.jit_sharded

    def run(sdata, bcol, brow, u, v):
        return _local_sddmm(sdata, bcol, brow, u, v, **kw)

    return comm.jit_sharded(run, 3, split)


from ..core.communication import register_mesh_cache

# program entries bake mesh geometry: cleared when the world rebuilds
register_mesh_cache(spmm_bcsr_program)
register_mesh_cache(sddmm_bcsr_program)
