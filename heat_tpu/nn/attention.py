"""Ring attention: sequence-parallel exact attention for long contexts.

The reference has NO attention stack; SURVEY §5 notes its long-context
mechanisms are exactly the ring-circulation pattern of
``spatial/distance._dist`` (distance.py:262-359). This module is the
TPU-native realization of that pattern for attention (Liu et al., Ring
Attention; the flash-attention online-softmax rescaling makes each ring
step exact): the SEQUENCE axis is sharded over the mesh, each device
keeps its Q block stationary, and K/V blocks circulate with
``lax.ppermute`` over ICI — per step the rotating block is consumed in
(Bq × chunk) attention tiles on the MXU while the next K/V block is in
flight. Memory per device is O(S·d / p + Bq·chunk) with chunk ≤ 1024
(``_RING_INNER_CHUNK``): no device ever holds the full S×S score matrix,
the full K/V, or even a full (Bq × Bk) block product, so sequence length
scales with the mesh without the score buffer growing as (S/p)².

Differentiable (scan + ppermute have transpose rules), causal-maskable,
and pad-safe: logical sequence lengths propagate through the masks so
uneven shards never contribute.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.lax import pcast
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from typing import Optional

from ..core.dndarray import DNDarray
from ..core.communication import register_mesh_cache
from ..core import types

__all__ = ["last_decisions", "ring_attention", "ring_self_attention"]


def _online_softmax_update(q, k_c, v_c, o, m, l, valid, scale, neg):
    """One flash-attention accumulation step, shared by the ring program
    (distributed) and the blocked program (single device) so the two paths
    cannot numerically diverge: masked scores → running-max rescale →
    (o, m, l) update."""
    s = jnp.einsum("...qd,...kd->...qk", q, k_c) * jnp.asarray(scale, q.dtype)
    s = jnp.where(valid, s, neg)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    pexp = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m - m_new)
    l = l * corr + jnp.sum(pexp, axis=-1, keepdims=True)
    o = o * corr + jnp.einsum("...qk,...kd->...qd", pexp, v_c)
    return o, m_new, l


# upper bound on the K/V sub-chunk each inner attention tile works on:
# the per-ring-step score buffer is (..., bq, min(bk, CHUNK)) instead of
# (..., bq, bk) — the einsum materializes scores over ALL leading
# batch/head dims at once, so at the 1M-token/64-chip north star
# (B=1, H=8, bk=16384, bf16) the naive block product is a 4 GB live
# buffer per step (16 GB in f32); chunked it is 256 MB.
_RING_INNER_CHUNK = 1024


def _ring_attention_program(
    mesh: Mesh,
    axis_name: str,
    ndim: int,
    seq_axis: int,
    n_q: int,
    n_kv: int,
    causal: bool,
    scale: float,
    jdtype: str,
    inner_chunk: Optional[int] = None,
):
    """Normalizing entry point for the cached blocked-ring builder: the
    lru_cache keys on the positional signature, so a defaulted call and
    an explicit-same-value call would otherwise compile the identical
    program twice (ADVICE r4). All callers go through here."""
    return _ring_attention_program_cached(
        mesh, axis_name, int(ndim), int(seq_axis), int(n_q), int(n_kv),
        bool(causal), float(scale), str(jdtype),
        _RING_INNER_CHUNK if inner_chunk is None else int(inner_chunk),
    )


@functools.lru_cache(maxsize=64)
def _ring_attention_program_cached(
    mesh: Mesh,
    axis_name: str,
    ndim: int,
    seq_axis: int,
    n_q: int,
    n_kv: int,
    causal: bool,
    scale: float,
    jdtype: str,
    inner_chunk: int,
):
    """One jitted shard_map program: stationary Q block, K/V rotating the
    ring, online-softmax (m, l, o) accumulation per step; within a step
    the rotating block is consumed in ``inner_chunk``-sized tiles (same
    blocked schedule as the single-device program) so live memory is
    O(bq·chunk), independent of the per-device block size."""
    p = mesh.devices.size
    spec = P(*(axis_name if i == seq_axis else None for i in range(ndim)))
    neg = jnp.finfo(jnp.dtype(jdtype)).min

    def body(q, k, v):
        r = lax.axis_index(axis_name)
        bq = q.shape[seq_axis]
        bk = k.shape[seq_axis]
        chunk = max(1, min(int(inner_chunk), bk))
        n_inner = -(-bk // chunk)
        pad_inner = n_inner * chunk - bk
        if pad_inner:
            # pad ONCE before the ring; rotations carry the padded block
            # (bounded overhead: < chunk/bk extra ICI bytes) and the
            # lidx < bk mask below keeps pad rows out of the softmax
            widths = [(0, 0)] * ndim
            widths[-2] = (0, pad_inner)
            k = jnp.pad(k, widths)
            v = jnp.pad(v, widths)
        # canonical layout (..., B, D): seq axis at -2 already by caller
        q_pos = (r * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)).astype(jnp.int32)

        # constant-initialized carry entries must be marked device-varying:
        # they mix with the rotating (varying) K/V blocks inside the scan.
        # o accumulates into V's head dim (which may differ from q's)
        o0 = jnp.zeros(q.shape[:-1] + (v.shape[-1],), dtype=q.dtype)
        m0 = jnp.full(q.shape[:-1] + (1,), neg, dtype=q.dtype)
        l0 = jnp.zeros(q.shape[:-1] + (1,), dtype=q.dtype)
        if p > 1:
            o0 = pcast(o0, axis_name, to="varying")
            m0 = pcast(m0, axis_name, to="varying")
            l0 = pcast(l0, axis_name, to="varying")
        k0, v0 = k, v

        def step(carry, t):
            k_cur, v_cur, o, m, l = carry
            src = (r + t) % p

            def tile(c2, j):
                o, m, l = c2
                k_c = lax.dynamic_slice_in_dim(k_cur, j * chunk, chunk, axis=-2)
                v_c = lax.dynamic_slice_in_dim(v_cur, j * chunk, chunk, axis=-2)
                lidx = j * chunk + jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
                k_pos = (src * bk + lidx).astype(jnp.int32)
                # lidx < bk masks the inner-chunk pad; k_pos < n_kv the
                # global sequence pad
                valid = (lidx < bk) & (k_pos < n_kv)
                if causal:
                    valid = valid & (k_pos <= q_pos)
                o, m, l = _online_softmax_update(q, k_c, v_c, o, m, l, valid, scale, neg)
                return (o, m, l), None

            if n_inner == 1:
                (o, m, l), _ = tile((o, m, l), 0)
            else:
                (o, m, l), _ = lax.scan(tile, (o, m, l), jnp.arange(n_inner))
            perm = [((i + 1) % p, i) for i in range(p)]
            k_nxt = lax.ppermute(k_cur, axis_name, perm) if p > 1 else k_cur
            v_nxt = lax.ppermute(v_cur, axis_name, perm) if p > 1 else v_cur
            return (k_nxt, v_nxt, o, m, l), None

        (_, _, o, m, l), _ = lax.scan(step, (k0, v0, o0, m0, l0), jnp.arange(p))
        # normalize; zero q pad rows explicitly (they attend to valid keys
        # and would otherwise carry garbage into the pad region)
        keep = (q_pos < n_q) & (l > 0)  # (..., bq, 1): broadcasts over D
        o = jnp.where(keep, o / jnp.where(l > 0, l, 1.0), 0.0)
        return o

    fn = shard_map(body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return jax.jit(fn)


@functools.lru_cache(maxsize=64)
def _blocked_attention_program(
    q_shape, k_shape, v_shape, causal: bool, scale: float, jdtype: str
):
    """Single-device flash-style attention: ``lax.scan`` over K/V chunks
    with the same online-softmax accumulation the ring uses — one
    (S, chunk) tile live at a time instead of the full (S, S) scores."""
    S_kv = k_shape[-2]
    chunk = max(1, min(1024, S_kv))
    n_chunks = max(1, -(-S_kv // chunk))
    pad = n_chunks * chunk - S_kv
    neg = jnp.finfo(jnp.dtype(jdtype)).min

    def run(q, k, v):
        if pad:
            widths_k = [(0, 0)] * (k.ndim - 2) + [(0, pad), (0, 0)]
            k = jnp.pad(k, widths_k)
            v = jnp.pad(v, widths_k)
        S_q = q.shape[-2]
        q_pos = jax.lax.broadcasted_iota(jnp.int32, (S_q, 1), 0)
        # (chunks, ..., chunk, d) leading scan axis
        ks = jnp.moveaxis(
            k.reshape(k.shape[:-2] + (n_chunks, chunk, k.shape[-1])), -3, 0
        )
        vs = jnp.moveaxis(
            v.reshape(v.shape[:-2] + (n_chunks, chunk, v.shape[-1])), -3, 0
        )

        o0 = jnp.zeros(q.shape[:-1] + (v.shape[-1],), dtype=q.dtype)
        m0 = jnp.full(q.shape[:-1] + (1,), neg, dtype=q.dtype)
        l0 = jnp.zeros(q.shape[:-1] + (1,), dtype=q.dtype)

        def step(carry, blk):
            o, m, l, idx = carry
            k_c, v_c = blk
            k_pos = idx * chunk + jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
            valid = k_pos < S_kv
            if causal:
                valid = valid & (k_pos <= q_pos)
            o, m, l = _online_softmax_update(q, k_c, v_c, o, m, l, valid, scale, neg)
            return (o, m, l, idx + 1), None

        (o, _, l, _), _ = lax.scan(step, (o0, m0, l0, jnp.int32(0)), (ks, vs))
        return jnp.where(l > 0, o / jnp.where(l > 0, l, 1.0), 0.0)

    return jax.jit(run)


# which program served each dispatched signature, and why a fused kernel
# did not where it did not: {signature: {"path": ..., "why": ...}}. Only
# gates (backend, x64, tracers, shapes) route to the blocked XLA
# programs; a kernel that fails to BUILD or COMPILE on the TPU backend
# raises to the caller — it is never cached as a silent fallback.
_DECISIONS: dict = {}


def last_decisions() -> dict:
    """Copy of the attention dispatcher's decisions: the ``path``
    (``splash``/``flash``/``blocked`` on one device, ``ring-splash``/
    ``ring-flash``/``blocked-ring`` over a mesh) that served each
    signature, with the gate's reason where a kernel was not used."""
    return {k: dict(v) for k, v in _DECISIONS.items()}


# tests force Mosaic interpret mode so the kernel ring path runs (slowly)
# on CPU meshes; production leaves this False and the path is TPU-gated
_RING_KERNEL_INTERPRET = False

# tests force the scan-with-carry ring body (the f32/flash hardware
# composition) on CPU meshes, where the unrolled body would otherwise be
# the only one CI ever compiles; build-time flag — clear the builder
# caches after flipping it
_RING_KERNEL_FORCE_SCAN = False


def _pick_block(n: int, candidates) -> Optional[int]:
    """Largest candidate block size that divides n, else None."""
    return next((c for c in candidates if n % c == 0), None)


@functools.lru_cache(maxsize=64)
def _ring_step_kernels(
    b: int, h: int, bq: int, bk: int, d: int,
    scale: float, jdtype: str, interpret: bool,
):
    """Per-ring-step Pallas kernel pair ``(full_fn, diag_fn)`` for one
    block signature, or None when no kernel serves it.

    Each fn maps raw (B, H, bq|bk, D) blocks to ``(out, lse)`` where
    ``out`` is the NORMALIZED attention output of q against that K/V
    block alone and ``lse`` is its float32 logsumexp — the save-residuals
    form that lets the ring combine per-step results exactly
    (o = Σ_i exp(lse_i − LSE)·out_i). ``diag_fn`` applies the causal mask
    for the block on the ring diagonal (src == r, requires bq == bk);
    ``full_fn`` is unmasked for blocks strictly behind the query block.

    bf16 → splash kernel (which computes in bf16 anyway); f32 → the
    flash kernel via its residual form (keeps f32 exactness, no
    interpret mode). Only the shape gates return None; a build failure
    raises.
    """
    jt = jnp.dtype(jdtype)
    if jt == jnp.bfloat16 or (interpret and jt == jnp.float32):
        bq_blk = _pick_block(bq, (1024, 512, 256, 128))
        bkv_blk = _pick_block(bk, (2048, 1024, 512, 256, 128))
        if bq_blk is None or bkv_blk is None or d % 64 != 0:
            return None
        full_fn = _build_splash_mha(
            h, bq, bk, False, scale, bq_blk, bkv_blk, True, interpret
        )
        diag_fn = (
            _build_splash_mha(
                h, bq, bq, True, scale, bq_blk, bq_blk, True, interpret
            )
            if bq == bk
            else None
        )
        return (full_fn, diag_fn)

    if jt == jnp.float32 and not interpret:
        import jax.experimental.pallas.ops.tpu.flash_attention as _fa

        bq_blk = _pick_block(bq, (1024, 512, 256, 128))
        # the residual form's (1024, 2048, 1024) working set grows with
        # the K/V block and passes the 16 MB scoped-VMEM limit from
        # bk = 16384 on (16.41 MB there, refused by the v5e compiler):
        # long blocks take a 1024 k-major block
        bkm = _pick_block(
            bk, (1024, 512, 256, 128) if bk >= 16384 else (2048, 1024, 512, 256, 128)
        )
        bk_blk = _pick_block(bk, (1024, 512, 256, 128))
        if None in (bq_blk, bkm, bk_blk) or d % 64 != 0:
            return None

        def build(causal_blk: bool):
            def run(qa, ka, va):
                # keyword-bind everything after the arrays: the impl is
                # underscore-private, and a signature drift must fail
                # loudly (TypeError) rather than bind positionally and
                # compute wrong residuals
                o, l, m = _fa._flash_attention_impl(
                    qa, ka, va, None, None,
                    save_residuals=True, causal=causal_blk,
                    sm_scale=float(scale), block_b=1, block_q=bq_blk,
                    block_k_major=bkm, block_k=bk_blk, debug=False,
                )
                # full/diag blocks always have ≥1 valid key per row, l > 0
                return o, (m + jnp.log(l)).astype(jnp.float32)

            return run

        return (build(False), build(True) if bq == bk else None)

    return None


@functools.lru_cache(maxsize=64)
def _ring_attention_kernel_callable(
    mesh: Mesh,
    axis_name: str,
    n_q: int,
    n_kv: int,
    b: int,
    h: int,
    d: int,
    causal: bool,
    scale: float,
    jdtype: str,
    interpret: bool,
):
    """TRACEABLE shard_map form of the kernel-backed ring attention: the
    same stationary-Q / rotating-K,V ppermute schedule as
    ``_ring_attention_program``, but each ring step runs a fused Pallas
    kernel (splash for bf16, flash for f32) instead of the blocked XLA
    online-softmax — so sharded-sequence attention keeps kernel-level
    MFU. The per-step results combine exactly via their logsumexp
    residuals (f32 accumulator); for causal masks a 3-way ``lax.switch``
    schedules each step as skip (block strictly ahead of the queries),
    diagonal (causal-masked kernel), or full (unmasked).

    Returns None when the signature has no serving kernel (odd blocks,
    non-divisible shards). Dispatch goes
    through the AOT ``_ring_attention_kernel_program``.
    """
    p = mesh.devices.size
    if n_q % p or n_kv % p:
        return None  # physical pad rows would need masks the kernels lack
    bq, bk = n_q // p, n_kv // p
    if causal and bq != bk:
        return None
    kernels = _ring_step_kernels(b, h, bq, bk, d, float(scale), jdtype, interpret)
    if kernels is None:
        return None
    full_fn, diag_fn = kernels
    if causal and diag_fn is None:
        return None
    spec = P(None, None, axis_name, None)
    jt = jnp.dtype(jdtype)
    neg_inf = jnp.float32(-jnp.inf)
    # Composition is gated by kernel family (empirical Mosaic constraint
    # on this toolchain): the splash kernel compiles under shard_map in
    # ANY composition, so bf16 takes the faster UNROLLED body; the flash
    # kernel under shard_map only compiles inside a scan-with-carry
    # region (direct call, 2/3-branch switch without scan, and scan
    # without array carry all crash the TPU compile helper), so f32
    # keeps the scan+switch body.
    unrolled = (
        jt == jnp.bfloat16 or (interpret and jt == jnp.float32)
    ) and not _RING_KERNEL_FORCE_SCAN

    def body_unrolled(q, k, v):
        # UNROLLED over the (static) ring length: t=0 ASSIGNS the first
        # kernel result instead of combining against a -inf carry (one
        # whole output pass saved — measured ~0.4 ms at 16k/p=1, the
        # bulk of the wrapper overhead vs the bare kernel), the causal
        # diagonal kernel is chosen statically at t=0 (src == r exactly
        # when t == 0), and the final wasted K/V rotation is skipped
        # (p-1 hops, not p). XLA can also pipeline hop t+1 against
        # kernel t — the overlap the ring schedule exists for.
        r = lax.axis_index(axis_name)
        perm = [((i + 1) % p, i) for i in range(p)]
        k_cur, v_cur = k, v
        o = lse = None
        for t in range(p):
            if t == 0:
                out_i, lse_i = (diag_fn if causal else full_fn)(q, k_cur, v_cur)
                o, lse = out_i.astype(jnp.float32), lse_i
            else:
                if causal:
                    # src = (r+t) % p != r here: only full (src strictly
                    # behind the queries) or skip (strictly ahead)
                    def run_skip(qa, ka, va):
                        return (
                            jnp.zeros((b, h, bq, d), dtype=jt),
                            jnp.full((b, h, bq), neg_inf, dtype=jnp.float32),
                        )

                    src = (r + t) % p
                    out_i, lse_i = lax.switch(
                        jnp.where(src < r, 1, 0).astype(jnp.int32),
                        (run_skip, lambda qa, ka, va: full_fn(qa, ka, va)),
                        q, k_cur, v_cur,
                    )
                else:
                    out_i, lse_i = full_fn(q, k_cur, v_cur)
                lse_new = jnp.logaddexp(lse, lse_i)
                # skip steps carry lse_i = -inf; lse is finite from t=0
                # (causal t=0 is the diagonal), so lse_new stays finite
                # and exp(lse_i - lse_new) cleanly gives beta = 0
                alpha = jnp.exp(lse - lse_new)
                beta = jnp.exp(lse_i - lse_new)
                o = o * alpha[..., None] + out_i.astype(jnp.float32) * beta[..., None]
                lse = lse_new
            if t < p - 1:
                k_cur = lax.ppermute(k_cur, axis_name, perm)
                v_cur = lax.ppermute(v_cur, axis_name, perm)
        return o.astype(jt)

    def body_scan(q, k, v):
        r = lax.axis_index(axis_name)
        o0 = jnp.zeros((b, h, bq, d), dtype=jnp.float32)
        lse0 = jnp.full((b, h, bq), neg_inf, dtype=jnp.float32)

        def step(carry, t):
            k_cur, v_cur, o, lse = carry
            src = (r + t) % p
            if causal:
                def run_skip(qa, ka, va):
                    return (
                        jnp.zeros((b, h, bq, d), dtype=jt),
                        jnp.full((b, h, bq), neg_inf, dtype=jnp.float32),
                    )

                idx = jnp.where(src == r, 1, jnp.where(src < r, 2, 0))
                out_i, lse_i = lax.switch(
                    idx, (run_skip, diag_fn, full_fn), q, k_cur, v_cur
                )
            else:
                out_i, lse_i = full_fn(q, k_cur, v_cur)
            lse_new = jnp.logaddexp(lse, lse_i)
            # both-(-inf) cannot happen causally (t=0 is the diagonal),
            # but keep the combine total: exp(-inf − -inf) would be NaN
            dead = jnp.isneginf(lse_new)
            alpha = jnp.where(dead, 0.0, jnp.exp(lse - lse_new))
            beta = jnp.where(dead, 0.0, jnp.exp(lse_i - lse_new))
            o = o * alpha[..., None] + out_i.astype(jnp.float32) * beta[..., None]
            k_nxt = lax.ppermute(k_cur, axis_name, perm_all) if p > 1 else k_cur
            v_nxt = lax.ppermute(v_cur, axis_name, perm_all) if p > 1 else v_cur
            return (k_nxt, v_nxt, o, lse_new), None

        perm_all = [((i + 1) % p, i) for i in range(p)]
        (_, _, o, _), _ = lax.scan(step, (k, v, o0, lse0), jnp.arange(p))
        return o.astype(jt)

    body = body_unrolled if unrolled else body_scan

    # check_vma=False: pallas_call outputs carry no varying-mesh-axes
    # annotation, which the vma checker rejects inside shard_map
    return shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )


@functools.lru_cache(maxsize=64)
def _ring_attention_kernel_program(
    mesh: Mesh,
    axis_name: str,
    n_q: int,
    n_kv: int,
    b: int,
    h: int,
    d: int,
    causal: bool,
    scale: float,
    jdtype: str,
    interpret: bool,
):
    """AOT-compiled executable of ``_ring_attention_kernel_callable``,
    lowered against the exact shardings dispatch guarantees (the DNDarray
    physical layout). None when a shape gate of the callable refuses
    the signature; a Mosaic compile failure raises here, once, to the
    caller."""
    fn = _ring_attention_kernel_callable(
        mesh, axis_name, n_q, n_kv, b, h, d, causal, scale, jdtype, interpret
    )
    if fn is None:
        return None
    seq_axis = 2
    spec = P(*(axis_name if i == seq_axis else None for i in range(4)))
    jt = jnp.dtype(jdtype)
    sh = NamedSharding(mesh, spec)
    return jax.jit(fn).lower(
        jax.ShapeDtypeStruct((b, h, n_q, d), jt, sharding=sh),
        jax.ShapeDtypeStruct((b, h, n_kv, d), jt, sharding=sh),
        jax.ShapeDtypeStruct((b, h, n_kv, d), jt, sharding=sh),
    ).compile()


def _ring_kernel_refusal(qp, kp, vp, ndim: int, seq_axis: int, jt) -> Optional[str]:
    """Dispatch gate for the kernel ring — why it cannot serve these
    operands, or None when it can: concrete 4-D (B, H, S, D)
    self-attention-shaped operands on the TPU backend (or interpret mode
    for tests), matching head dims, x64 off. Shape/divisibility gates
    live in the program builder, which returns None per signature."""
    if not (_RING_KERNEL_INTERPRET or jax.default_backend() == "tpu"):
        return "backend is not tpu"
    if jax.config.jax_enable_x64 and not _RING_KERNEL_INTERPRET:
        # hardware kernels mis-trace under forced x64 (same gate as
        # _pallas_attention); interpret mode traces cleanly regardless
        return "x64 is forced on"
    if any(isinstance(t, jax.core.Tracer) for t in (qp, kp, vp)):
        # user jit/grad trace: only the blocked ring is guaranteed
        # differentiable (the save-residuals combine is forward-only)
        return "traced operands: the kernel ring is forward-only"
    if ndim != 4 or seq_axis != 2 or qp.shape[-1] != vp.shape[-1]:
        return "shape gate: needs (B, H, S, D) operands with equal q/v head dims"
    if jnp.dtype(jt) not in (jnp.bfloat16, jnp.float32):
        return f"dtype gate: {jnp.dtype(jt).name} is neither bf16 nor f32"
    return None


def _build_splash_mha(
    h: int, sq: int, skv: int, causal: bool, scale: float,
    block_q: int, block_kv: int, save_residuals: bool, interpret: bool,
):
    """Shared splash-kernel assembly (mask, BlockSizes, pre-scaled-q vmap
    wrapper) behind both the single-device callable and the ring step
    kernels — the splash configuration lives in exactly one place.
    Splash takes a PRE-SCALED q (no sm_scale parameter). Raises on a
    shape the kernel refuses; the shape gates live with the callers."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as _sk,
        splash_attention_mask as _sm,
    )

    kv_comp = min(1024, block_kv)
    bs = _sk.BlockSizes(
        block_q=block_q, block_kv=block_kv, block_kv_compute=kv_comp,
        block_q_dkv=block_q, block_kv_dkv=block_kv,
        block_kv_dkv_compute=kv_comp,
        block_q_dq=block_q, block_kv_dq=block_kv,
    )
    mask = _sm.MultiHeadMask(
        [
            _sm.CausalMask((sq, skv)) if causal else _sm.FullMask((sq, skv))
            for _ in range(h)
        ]
    )
    kern = _sk.make_splash_mha_single_device(
        mask=mask, block_sizes=bs, save_residuals=save_residuals,
        interpret=interpret,
    )

    def run(qa, ka, va):
        qs = (qa * qa.dtype.type(scale)).astype(qa.dtype)
        out = jax.vmap(kern)(qs, ka, va)
        if not save_residuals:
            return out
        o, res = out
        lse = res[0] if isinstance(res, tuple) else res
        return o, lse.astype(jnp.float32)

    return run


@functools.lru_cache(maxsize=64)
def _splash_callable(q_shape, kv_shape, causal: bool, scale: float, jdtype: str):
    """TRACEABLE splash-attention callable (the newer production TPU
    kernel family), or None when it cannot serve the signature. Measured
    on v5e at S=16k/D=128/causal bf16: ~0.68-0.70 MFU vs the flash
    kernel's ~0.60-0.67 across a block sweep (docs/PERF.md records the
    sweep) — splash is preferred, flash is the fallback, the blocked XLA
    program stays the oracle. Splash takes a PRE-SCALED q (no sm_scale
    parameter), applied inside the compiled program. Dispatch uses the
    AOT ``_splash_attention_program``."""
    if jnp.dtype(jdtype) != jnp.bfloat16:
        # splash runs its matmuls in bf16 regardless of input dtype
        # (measured f32 rel-err ~3e-3 vs the blocked oracle, where the
        # flash kernel keeps ~2e-7): f32 callers get flash's exactness
        return None
    b, h, sq, d = q_shape
    skv = kv_shape[-2]
    if sq % 1024 != 0:
        return None  # v5e-tuned 1024 q-blocks; other shapes use flash
    bkv = 2048 if skv % 2048 == 0 else 1024
    if skv % bkv != 0:
        return None
    return _build_splash_mha(h, sq, skv, causal, scale, 1024, bkv, False, False)


@functools.lru_cache(maxsize=64)
def _splash_attention_program(q_shape, kv_shape, causal: bool, scale: float, jdtype: str):
    """AOT-compiled executable of ``_splash_callable``, or None when its
    shape gates refuse the signature. A Mosaic compile failure raises."""
    run = _splash_callable(q_shape, kv_shape, causal, scale, jdtype)
    if run is None:
        return None
    jt = jnp.dtype(jdtype)
    return jax.jit(run).lower(
        jax.ShapeDtypeStruct(q_shape, jt),
        jax.ShapeDtypeStruct(kv_shape, jt),
        jax.ShapeDtypeStruct(kv_shape, jt),
    ).compile()


@functools.lru_cache(maxsize=64)
def _pallas_attention_program(q_shape, kv_shape, causal: bool, scale: float, jdtype: str):
    """AOT-compiled Mosaic (Pallas) flash-attention executable for one
    signature that ``_pallas_attention_fits`` admits. A Mosaic compile
    failure (VMEM overflow etc.) raises to the caller."""
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        BlockSizes,
        flash_attention,
    )

    sq, skv = q_shape[-2], kv_shape[-2]
    # v5e-tuned tiles (interleaved sweep: ~1.4x over the blocked XLA
    # program at S=4096); clamp to divisors of the sequence length
    bq = 1024 if sq % 1024 == 0 else 512
    bkm = 2048 if skv % 2048 == 0 else (1024 if skv % 1024 == 0 else 512)
    bk = 1024 if skv % 2048 == 0 else 512
    bs = BlockSizes(
        block_q=bq, block_k_major=bkm, block_k=bk, block_b=1,
        block_q_major_dkv=bq, block_k_major_dkv=bkm, block_k_dkv=bk, block_q_dkv=bq,
        block_k_major_dq=bkm, block_k_dq=bk, block_q_dq=bq,
    )

    def run(qa, ka, va):
        # x64 is off on TPU by platform policy (devices._apply_x64_policy),
        # so the kernel's int32 block-index maps trace cleanly; the
        # forced-x64 configuration is gated out in _pallas_attention
        return flash_attention(
            qa, ka, va, causal=causal, sm_scale=float(scale), block_sizes=bs
        )

    jt = jnp.dtype(jdtype)
    # the AOT Compiled executable is what gets called — compiling once
    # and dispatching through jit would compile the kernel a second
    # time (AOT lowering does not populate jit's dispatch cache)
    return jax.jit(run).lower(
        jax.ShapeDtypeStruct(q_shape, jt),
        jax.ShapeDtypeStruct(kv_shape, jt),
        jax.ShapeDtypeStruct(kv_shape, jt),
    ).compile()


def _pallas_attention_fits(q_shape, k_shape, v_shape, dtype) -> bool:
    """Backend-independent tiling gate for the flash kernel: 4-D f32/bf16
    self-attention with 512-multiple sequence length and lane-aligned
    (64-multiple) head dim, q/k/v agreeing on batch/head/seq dims."""
    if len(q_shape) != 4 or jnp.dtype(dtype) not in (jnp.float32, jnp.bfloat16):
        return False
    b, h, sq, d = q_shape
    skv = k_shape[-2]
    return (
        tuple(k_shape) == (b, h, skv, d)
        and tuple(v_shape) == (b, h, skv, d)
        and sq == skv
        and sq % 512 == 0
        and d % 64 == 0
    )


def _kernel_refusal(qa, ka, va) -> Optional[str]:
    """Why the fused single-device kernels cannot serve these operands,
    or None when they can."""
    if jax.default_backend() != "tpu":
        return "backend is not tpu"
    if jax.config.jax_enable_x64:
        # explicitly-forced x64 on TPU: the kernel's block-index maps mix
        # int32 iotas with Python ints and mis-trace in x64 mode — the
        # blocked XLA program serves this configuration
        return "x64 is forced on"
    if any(isinstance(t, jax.core.Tracer) for t in (qa, ka, va)):
        # inside a user jit/grad trace: only the blocked program is
        # guaranteed differentiable and compilable — the flash kernel's
        # dkv/dq backward kernels are never AOT-probed here
        return "traced operands: the kernels are forward-only here"
    if not _pallas_attention_fits(qa.shape, ka.shape, va.shape, qa.dtype):
        return (
            "shape gate: needs 4-D f32/bf16 self-attention with S % 512 == 0 "
            "and D % 64 == 0"
        )
    # the Compiled executable is lowered for default-device placement;
    # operands living elsewhere (explicit device_put, multi-chip sharding)
    # take the jitted blocked program, which places freely
    if {d for t in (qa, ka, va) for d in t.devices()} != {jax.devices()[0]}:
        return "operands are not on the default device"
    return None


def _pallas_attention(qa, ka, va, causal: bool, scale: float):
    """Mosaic (Pallas) fused attention kernel for the single-device path
    — the native-kernel realization of the same online-softmax algorithm
    (one (Bq, Bk) tile in VMEM at a time): splash where its shape gates
    admit the signature (bf16, 1024-multiple S), else the flash kernel.
    Returns None when a gate refuses the operands (recorded with its
    reason in ``last_decisions``); the blocked XLA program then serves
    and is the numerical oracle."""
    sig = (
        "single", tuple(qa.shape), tuple(ka.shape), np.dtype(qa.dtype).name,
        bool(causal),
    )
    why = _kernel_refusal(qa, ka, va)
    if why is not None:
        _DECISIONS[sig] = {"path": "blocked", "why": why}
        return None
    args = (
        tuple(qa.shape), tuple(ka.shape), bool(causal), float(scale),
        np.dtype(qa.dtype).name,
    )
    prog, path = _splash_attention_program(*args), "splash"
    if prog is None:
        prog, path = _pallas_attention_program(*args), "flash"
    _DECISIONS[sig] = {"path": path, "why": "compiled kernel"}
    return prog(qa, ka, va)


def _single_device_attention(qa, ka, va, causal: bool, scale):
    """Shared single-device flash attention on raw jax arrays: non-inexact
    dtypes promote to float32, the default scale is 1/sqrt(d), and the
    blocked program runs — the ONE code path behind both ring_attention's
    single-device branch and functional.scaled_dot_product_attention's
    raw-array route (divergence here would mean same inputs, different
    numerics depending on the array wrapper)."""
    jt = qa.dtype if jnp.issubdtype(qa.dtype, jnp.inexact) else jnp.dtype(jnp.float32)
    qa, ka, va = (t.astype(jt) for t in (qa, ka, va))
    if scale is None:
        scale = 1.0 / float(np.sqrt(qa.shape[-1]))
    out = _pallas_attention(qa, ka, va, bool(causal), float(scale))
    if out is not None:
        return out
    prog = _blocked_attention_program(
        tuple(qa.shape), tuple(ka.shape), tuple(va.shape),
        bool(causal), float(scale), np.dtype(jt).name,
    )
    return prog(qa, ka, va)


def ring_attention(
    q: DNDarray,
    k: DNDarray,
    v: DNDarray,
    causal: bool = False,
    scale: Optional[float] = None,
) -> DNDarray:
    """Exact scaled-dot-product attention with the sequence axis sharded
    over the mesh (sequence parallelism for long contexts).

    ``q``/``k``/``v``: (..., S, D) DNDarrays split along the S axis
    (axis -2). Output matches q's shape and sharding. Unsplit inputs run
    the same program on a size-1 ring (plain flash-style attention).
    """
    for name, t in (("q", q), ("k", k), ("v", v)):
        if not isinstance(t, DNDarray):
            raise TypeError(f"{name} must be a DNDarray, got {type(t)}")
        if t.ndim < 2:
            raise ValueError(f"{name} needs at least (S, D) dims, got {t.ndim}")
    seq_axis = q.ndim - 2
    if q.split not in (None, seq_axis) or k.split not in (None, seq_axis) or v.split not in (None, seq_axis):
        raise ValueError(
            f"ring_attention shards the sequence axis ({seq_axis}); got splits "
            f"{q.split}/{k.split}/{v.split} — resplit the operands first"
        )
    if k.shape[:-1] != v.shape[:-1]:
        raise ValueError(
            f"k and v must agree on batch/sequence dims, got {k.shape} vs {v.shape}"
        )
    if q.shape[-1] != k.shape[-1]:
        raise ValueError(f"q and k head dims must agree, got {q.shape[-1]} vs {k.shape[-1]}")
    if q.gshape[:-2] != k.gshape[:-2]:
        raise ValueError(
            f"q and k batch dims must agree, got {q.gshape[:-2]} vs {k.gshape[:-2]}"
        )
    out_gshape = q.gshape[:-1] + (v.gshape[-1],)
    dtype = q.dtype if types.heat_type_is_inexact(q.dtype) else types.float32
    jt = dtype.jax_type()
    if scale is None:
        scale = 1.0 / float(np.sqrt(q.shape[-1]))

    comm = q.comm
    if comm.size == 1 or q.split is None:
        # single device / replicated q: blocked flash-style attention —
        # the dense formulation would materialize the (B, H, S, S) score
        # tensor (2 GB at S=4k), the blocked scan keeps it one tile
        # raw logical arrays: the helper owns promotion (same rule that
        # produced jt), so its policy is authoritative for BOTH routes
        out = _single_device_attention(q.larray, k.larray, v.larray, causal, scale)
        return DNDarray(
            comm.shard(out, q.split), out_gshape, dtype, q.split, q.device, comm
        )

    qp = q._phys.astype(jt) if q.split == seq_axis else comm.shard(q.larray.astype(jt), seq_axis)
    kp = k._phys.astype(jt) if k.split == seq_axis else comm.shard(k.larray.astype(jt), seq_axis)
    vp = v._phys.astype(jt) if v.split == seq_axis else comm.shard(v.larray.astype(jt), seq_axis)
    sig = (
        "ring", comm.size, tuple(q.shape), tuple(k.shape), np.dtype(jt).name,
        bool(causal),
    )
    why = _ring_kernel_refusal(qp, kp, vp, q.ndim, seq_axis, jt)
    if why is None:
        kprog = _ring_attention_kernel_program(
            comm.mesh, comm.axis_name, q.shape[seq_axis], k.shape[seq_axis],
            q.shape[0], q.shape[1], q.shape[-1], bool(causal), float(scale),
            np.dtype(jt).name, _RING_KERNEL_INTERPRET,
        )
        if kprog is not None:
            splash = jnp.dtype(jt) == jnp.bfloat16 or _RING_KERNEL_INTERPRET
            _DECISIONS[sig] = {
                "path": "ring-splash" if splash else "ring-flash",
                "why": "interpret-mode kernel" if _RING_KERNEL_INTERPRET else "compiled kernel",
            }
            return DNDarray(kprog(qp, kp, vp), out_gshape, dtype, seq_axis, q.device, comm)
        why = "shape gate: shards not divisible into kernel blocks, or D % 64 != 0"
    _DECISIONS[sig] = {"path": "blocked-ring", "why": why}
    prog = _ring_attention_program(
        comm.mesh, comm.axis_name, q.ndim, seq_axis,
        q.shape[seq_axis], k.shape[seq_axis], bool(causal), float(scale),
        np.dtype(jt).name,
    )
    out_phys = prog(qp, kp, vp)
    return DNDarray(out_phys, out_gshape, dtype, seq_axis, q.device, comm)


def ring_self_attention(x: DNDarray, causal: bool = False, scale: Optional[float] = None) -> DNDarray:
    """Self-attention convenience: q = k = v = x."""
    return ring_attention(x, x, x, causal=causal, scale=scale)


# programs bake the mesh: clear on init_distributed world rebuilds
register_mesh_cache(_ring_attention_program_cached)
register_mesh_cache(_ring_attention_kernel_callable)
register_mesh_cache(_ring_attention_kernel_program)
