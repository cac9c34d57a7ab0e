"""Hierarchical SVD — the north-star operation.

API parity with /root/reference/heat/core/linalg/svdtools.py (``hsvd_rank``
:31, ``hsvd_rtol`` :124, ``hsvd`` :259, ``compute_local_truncated_svd``
:477; algorithm after Iwen/Ong 2016 and Himpe/Leibner/Rave 2018). The
reference runs: transpose if split=0 (:314-318) → per-rank truncated local
SVD → a greedy Send/Recv **merge tree** over shrinking rank sets
(:346-445) → Bcast of the final U.

TPU-native redesign (same math, different schedule):

1. **Level 0** — one ``shard_map``: every device computes the truncated
   SVD of its local column block and scales ``U_loc·Σ_loc``; discarded
   energy is accumulated for the a-posteriori error bound. Output is the
   global matrix ``B = [U_1Σ_1 ∥ … ∥ U_pΣ_p]`` (m × p·r), sharded along
   columns — no host round-trip.
2. **Merge** — instead of a log-depth Send/Recv tree whose node count
   shrinks dynamically (hostile to XLA's static shapes), the merge is ONE
   TSQR of ``B`` (see ``qr.py``) followed by an SVD of the tiny
   (p·r × p·r) R factor: ``B = Q·R``, ``R = U_R Σ V^T`` ⇒ left singular
   vectors ``Q·U_R`` — one all-gather of R factors on ICI plus local MXU
   matmuls. Mathematically this *is* a single-level merge with exact
   arithmetic on the concatenated factors; the truncation error analysis
   of the reference applies unchanged. Under the
   ``HEAT_TPU_REDIST_OVERLAP`` gate the TSQR runs its collective-matmul
   form (ISSUE 6): the R-factor all-gather decomposed into a ppermute
   ring whose blocks are stacked as they land
   (``kernels.cmatmul.ring_all_gather``) — byte-equivalent movement,
   bit-identical factors, so everything below is form-agnostic.
3. rank-budget (``hsvd_rank``) truncates statically; tolerance mode
   (``hsvd_rtol``) picks the final rank from the merged spectrum on host
   (a scalar-sized transfer), keeping all array shapes static under jit.

4. Since PR 27 the rank-budget call on a split array (the north star's
   form) is ONE program, ``_dist_rank_fn``: level 0 on each device's
   block as it lies (no ``Aᵀ``), the merge by an in-program gather of
   ``B`` (TSQR where p·r is wide), and the split-side factor from the
   devices' own level-0 factors (``U_i = u_i Z_i``) instead of a third
   pass over ``A``. Steps 1-3 describe the staged path the other modes
   keep.

``maxmergedim``/``no_of_merges`` tuned the reference's tree arity against
MPI message sizes; the TSQR merge has no such knob — they are accepted and
validated for API parity.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec
from typing import Optional, Tuple, Union

from .. import types
from .. import _padding
from jax import shard_map as _shard_map
from ..communication import MeshCommunication
from ..dndarray import DNDarray
from ..sanitation import sanitize_in
from ...observability import telemetry as _telemetry
from ...observability.instrument import observed_program_cache
from ...observability.tracing import call_span as _call_span, span as _span
from ._lapack import safe_svd, svd_x32_scope

__all__ = ["hsvd", "hsvd_rank", "hsvd_rtol"]


_SKETCH_OVERSAMPLE = 10

#: fixed tile grain of the streaming sketch passes (ISSUE 11): pass 1
#: walks 512-column tiles, pass 2 512-row tiles, the one-view stream
#: 512-column tiles — ALWAYS, in-HBM and staged alike. XLA's gemm
#: kernel choice is shape-dependent (a narrow tail gemm reassociates
#: differently than the same columns inside a wide gemm — measured),
#: so the fixed grain is what makes the out-of-core staged windows
#: (``redistribution.staging``, window extents = grain multiples)
#: replay EXACTLY the in-HBM tile sequence: same-shaped dots on the
#: same data, bit-identical factors by construction. Must equal
#: ``staging.GRAIN``; arrays smaller than one tile keep the single-gemm
#: form (bit-identical to the pre-ISSUE-11 programs).
_PASS_TILE = 512


def _pass1_tiles(g, a):
    """Pass 1 of the 2-pass sketch — ``w = g @ a`` — streamed in fixed
    ``_PASS_TILE``-column tiles. Each output tile is an independent
    same-shaped dot (the contraction axis is untouched), so the result
    is identical whether the loop runs inside one in-HBM program or
    across staged host windows."""
    m, n = a.shape
    T = _PASS_TILE
    nfull = n // T
    if nfull == 0:
        return g @ a
    w0 = jnp.zeros((g.shape[0], nfull * T), dtype=a.dtype)

    def body(k, w):
        blk = jax.lax.dynamic_slice(a, (0, k * T), (m, T))
        return jax.lax.dynamic_update_slice(w, g @ blk, (0, k * T))

    w = jax.lax.fori_loop(0, nfull, body, w0)
    if n % T:
        w = jnp.concatenate([w, g @ a[:, nfull * T :]], axis=1)
    return w


def _pass2_tiles(a, qw, norm_in):
    """Pass 2 — ``z = a @ qw`` in fixed ``_PASS_TILE``-row tiles, with
    the Frobenius accumulation folded into the SAME stream on top of
    ``norm_in`` (the running carry): the XLA fallback reads A exactly
    twice, like the TPU schedule. The carry is an explicit argument so
    staged windows thread it through in tile order — the scalar addition
    sequence is identical to the in-HBM fori_loop and the error estimate
    stays bit-identical too."""
    m, n = a.shape
    T = _PASS_TILE
    nfull = m // T
    if nfull == 0:
        return a @ qw, norm_in + jnp.sum(jnp.real(a * jnp.conj(a)))
    z0 = jnp.zeros((nfull * T, qw.shape[1]), dtype=a.dtype)

    def body(k, carry):
        z, acc = carry
        blk = jax.lax.dynamic_slice(a, (k * T, 0), (T, n))
        z = jax.lax.dynamic_update_slice(z, blk @ qw, (k * T, 0))
        return z, acc + jnp.sum(jnp.real(blk * jnp.conj(blk)))

    z, acc = jax.lax.fori_loop(0, nfull, body, (z0, norm_in))
    if m % T:
        tail = a[nfull * T :]
        z = jnp.concatenate([z, tail @ qw], axis=0)
        acc = acc + jnp.sum(jnp.real(tail * jnp.conj(tail)))
    return z, acc


def _oneview_tiles(g, omega, a, y_in, norm_in):
    """The one-view stream — ``w = g @ a``, ``y += a @ omega``,
    ``norm += |a|²`` from ONE read of ``a`` — in fixed
    ``_PASS_TILE``-column tiles with explicit (y, norm) carries (the
    XLA fallback used to pay three reads; now one, mirroring the fused
    TPU dual-sketch kernel's schedule). Staged host windows call this
    per window, threading the carries — same tile order, bit-identical
    sketches."""
    m, n = a.shape
    T = _PASS_TILE
    nfull = n // T
    if nfull == 0:
        w = g @ a
        y = y_in + a @ omega
        return w, y, norm_in + jnp.sum(jnp.real(a * jnp.conj(a)))
    w0 = jnp.zeros((g.shape[0], nfull * T), dtype=a.dtype)

    def body(k, carry):
        w, y, acc = carry
        blk = jax.lax.dynamic_slice(a, (0, k * T), (m, T))
        om = jax.lax.dynamic_slice(omega, (k * T, 0), (T, omega.shape[1]))
        w = jax.lax.dynamic_update_slice(w, g @ blk, (0, k * T))
        return w, y + blk @ om, acc + jnp.sum(jnp.real(blk * jnp.conj(blk)))

    w, y, acc = jax.lax.fori_loop(0, nfull, body, (w0, y_in, norm_in))
    if n % T:
        tail = a[:, nfull * T :]
        w = jnp.concatenate([w, g @ tail], axis=1)
        y = y + tail @ omega[nfull * T :]
        acc = acc + jnp.sum(jnp.real(tail * jnp.conj(tail)))
    return w, y, acc


def _needs_exact_spectrum(rtol: Optional[float]) -> bool:
    """Tight-rtol rank selection needs singular values below the sketch's
    capture floor: the σ¹-weighted range finder (``_sketched_uds_both``)
    loses directions whose σ sits near √ε·σ_max in f32 (measured: a
    1e-4·σ_max value comes back as ~1e-7), and no SVD of the projected
    factor can recover energy the basis never captured. Below rtol=1e-3
    the full-SVD path is the only spectrum the selection rule can trust
    (ADVICE r3; the reference's compute_local_truncated_svd is always a
    full SVD)."""
    return rtol is not None and float(rtol) < 1e-3


def _warn_merge_knobs(maxmergedim, no_of_merges) -> None:
    """The reference's merge-tree arity knobs tuned MPI message sizes
    (svdtools.py:346-445); the TSQR merge has no such knob. A silent
    no-op would surprise callers porting tuned reference code, so
    non-default values warn once per call site (VERDICT r2 #10)."""
    if maxmergedim is not None or (no_of_merges is not None and no_of_merges != 2):
        import warnings

        warnings.warn(
            "maxmergedim/no_of_merges are accepted for reference-API parity "
            "but have no effect: the TSQR merge (flat, or the two-level tree at composite p>=16) replaces the "
            "reference's Send/Recv merge tree",
            UserWarning,
            stacklevel=3,
        )


def _gram_orthonormalize(z):
    """Orthonormalize the columns of a tall-skinny ``z`` via two rounds of
    Gram eigen-orthonormalization (z ← z·V·Λ^{-1/2}). Unlike Cholesky-QR
    this cannot fail on (near-)rank-deficient sketches — eigh of a PSD
    Gram always succeeds and clamped near-zero directions are simply
    rotated noise columns, which the second round re-orthonormalizes.
    Cost: two reads of the SMALL z (m×l) instead of a latency-bound
    Householder sweep."""
    for _ in range(2):
        # conjugated Gram (z^H z): hermitian PSD for native complex
        # inputs too (CPU/GPU worlds); conj is the identity on reals
        gram = jnp.matmul(jnp.conj(z).T, z, precision="highest")  # (l, l) PSD
        lam, v = jnp.linalg.eigh(gram)                  # ascending
        # relative floor for rank deficiency PLUS an absolute one: an
        # all-zero block (max λ = 0) must yield rsqrt(tiny) — finite — so
        # zeros propagate as zeros instead of 0·inf = NaN
        lam = jnp.maximum(
            jnp.maximum(lam, jnp.finfo(z.dtype).eps * jnp.max(lam) * z.shape[0]),
            jnp.finfo(z.dtype).tiny,
        )
        z = jnp.matmul(z, v, precision="highest") * jax.lax.rsqrt(lam)
    return z


def _cholqr2_refine(v):
    """Re-orthonormalize a NEAR-orthonormal ``v`` by two rounds of
    Cholesky-QR: vᵀv ≈ I is perfectly conditioned, so two rounds reach
    f32 machine orthogonality, and the triangular correction R ≈ I mixes
    columns only negligibly — preserving the column↔σ_i pairing the
    U·Σ·Vᵀ contract needs (a Gram-eigh pass would rotate arbitrarily
    within the σ-clusters). The tiny ridge keeps exact-zero columns
    (σ_i = 0 truncation noise) at zero instead of NaN."""
    eye = jnp.eye(v.shape[1], dtype=v.dtype)
    for _ in range(2):
        # the MXU's default bf16 passes cap orthogonality at ~1e-3; these
        # (l×l)-contraction matmuls are free at full f32 precision.
        # Conjugated forms (v^H v = r r^H, v ← v r^{-H}) so the refine is
        # the complex Cholesky-QR on native complex inputs — an
        # unconjugated complex Gram is not hermitian and its Cholesky
        # NaNs (the pre-PR-5 hsvd split=0 complex failure mode)
        g = jnp.matmul(jnp.conj(v).T, v, precision="highest") + jnp.finfo(v.dtype).eps * eye
        r = jnp.linalg.cholesky(g)  # lower: g = r r^H
        v = jnp.conj(jax.scipy.linalg.solve_triangular(r, jnp.conj(v).T, lower=True)).T
    return v


def _sketched_uds(a_blk, keep: int, sketch_l: int, want_left: bool = True):
    """Randomized truncated SVD in TWO streaming passes over ``a_blk`` —
    the factors of the best rank-``keep`` approximation in O(m·n·l)
    instead of the O(m·n²) full SVD the reference's
    ``compute_local_truncated_svd`` (svdtools.py:477) pays for a small
    rank budget. Passes, not FLOPs, are the budget at the north-star
    size (~2.6 ms per streaming pass over the 2.1 GB shard at HBM
    speed); see ``_sketched_uds_both`` for the schedule, the Gram-eigh
    rationale, and the σ¹-vs-σ³ subspace-quality trade.

    The SVD of the projected z is taken via its (l, l) Gram matrix: XLA's
    bidiagonalization of a tall matrix is a latency-bound column loop,
    while the Gram route is one MXU matmul plus a tiny eigh — and its
    eigenvalues λ_i = σ_i² are EXACTLY the energies the truncation bound
    consumes, so the error estimate loses nothing. Only σ_i below
    ~√ε·σ_max (f32: ~3e-4·σ_max) lose relative accuracy —
    truncation-noise columns in a rank-``keep`` budget (tight-rtol rank
    selection therefore bypasses the sketch, ``_needs_exact_spectrum``).

    ``want_left`` returns U (m, keep); otherwise V (n, keep). BOTH sides
    come from the same two passes, which is how the split=0 (transposed)
    orientation serves either factor without materializing Aᵀ or paying
    the reference's ``U = A·V·Σ⁻¹`` postprocessing pass (svdtools.py:456-467).

    Returns (u (m|n, keep) orthonormal, s (keep,), err_sq (), norm_sq ())."""
    u, v, s, err_sq, norm_sq = _sketched_uds_both(
        a_blk, keep, sketch_l, "left" if want_left else "right"
    )
    return (u if want_left else v), s, err_sq, norm_sq


def _sketched_uds_both(a_blk, keep: int, sketch_l: int, want: str = "left"):
    """Core of ``_sketched_uds`` returning whichever factors ``want``
    ("left" | "right" | "both") asks for — both sides cost the same TWO
    passes; only the tiny (m|n, keep) assembly matmuls differ.

    Round-4 schedule (r3 used three passes — sketch, σ²-filtered column
    image ``z = A(gA)ᵀ``, projection ``b = qzᵀA``): the power pass is
    dropped. ``Q = orth(wᵀ)`` spans the ROW-space sketch, pass 2 projects
    ``z = A·Q``, and the Gram-eigh of z yields both factor sides:
    A ≈ (z·u_z·Σ⁻¹)·Σ·(Q·u_z)ᵀ. This is the classic HMT range finder at
    σ¹ weighting instead of the power iteration's σ³ — the documented
    quality trade (VERDICT r3 #5): exact for matrices of rank ≤ l, the
    standard (1+√(r/oversample))·σ_{r+1}-class bound otherwise, and the
    a-posteriori error estimate below stays EXACT for the returned
    factorization either way (orthonormal Q ⇒ ‖A − AQQᵀ‖² = ‖A‖² − ‖z‖²).

    Passes over A: 2, and nothing else streams it. On TPU the fused
    Pallas sketch+norm kernel folds the Frobenius pass into pass 1 and
    pass 2 is ONE dot, whose bf16 cast of A the compiler fuses into the
    dot's operand read. Everywhere else (and where the kernel's gates do
    not hold) both passes run the fixed-grain tiled XLA streams
    ``_pass1_tiles``/``_pass2_tiles``, the norm folded into pass 2's
    stream (ISSUE 11), so the out-of-core staged windows of
    ``redistribution.staging`` replay the exact same tile sequence and
    staged factors are bit-identical to in-HBM ones there. Bound
    819/2 ≈ 410 GB/s either way.

    Returns (u|None, v|None, s, err_sq, norm_sq)."""
    m, n = a_blk.shape
    key = jax.random.key(0x5BD)  # deterministic, like the reference's SVD
    g = jax.random.normal(key, (sketch_l, m), dtype=a_blk.dtype)
    # pass 1 (+norm fused): the Pallas kernel streams each A tile through
    # VMEM once and feeds BOTH the sketch matmul and the Frobenius
    # accumulation — the tiled XLA form is the fallback and the oracle.
    norm_sq = None
    from ._pallas_sketch import sketch_with_norm

    fused = sketch_with_norm(g, a_blk)
    if fused is not None:
        w, norm_sq = fused               # pass 1 + norm in one stream
    else:
        w = _pass1_tiles(g, a_blk)       # pass 1: (l, n)
    # the range basis must span rows of w CONJUGATED (A ≈ A·Q·Q^H needs
    # Q from the row space of A, i.e. columns of A^H = conj(wᵀ) sketches)
    qw = _gram_orthonormalize(jnp.conj(w).T)  # (n, l) — small O(n·l²), no pass
    if norm_sq is None:
        # pass 2 with the Frobenius accumulation folded into the stream
        zero = jnp.zeros((), dtype=jnp.real(jnp.zeros((), a_blk.dtype)).dtype)
        z, norm_sq = _pass2_tiles(a_blk, qw, zero)
        _telemetry.inc("hsvd.pass2.tiled")
    else:
        # pass 2: (m, l) projection, on the chip (pass 1 was the kernel).
        # ONE dot, not the tiled loop: out of the loop the chip's compiler
        # hoists the loop-invariant bf16 cast of A, a third stream that
        # writes a copy of A (9.7 of 19.4 ms at the north-star shard); of
        # one dot it fuses the cast into the operand read and streams f32
        # A once, at pass 1's speed. Same arithmetic, to the bit: bf16 MXU
        # inputs, f32 accumulation
        z = a_blk @ qw
        _telemetry.inc("hsvd.pass2.one_dot")
    return _projection_tail(z, qw, norm_sq, keep, want)


def _projection_tail(z, qw, norm_sq, keep: int, want: str):
    """Everything after the streaming passes of ``_sketched_uds_both``
    — Gram-eigh of the projection, factor assembly, the exact
    a-posteriori error identity. Factored out so the staged executor
    runs the IDENTICAL tail on its assembled (z, qw, norm)."""
    gram = jnp.matmul(jnp.conj(z).T, z, precision="highest")  # (l, l): λ accuracy
                                         # sets σ² quality; full f32 is free here
    lam, u_z = jnp.linalg.eigh(gram)     # ascending
    lam = jnp.maximum(lam[::-1], 0.0)    # descending energies σ²
    u_z = u_z[:, ::-1]
    lam = lam[:keep]
    s = jnp.sqrt(lam)
    u = v = None
    if want in ("left", "both"):
        inv_s = jnp.where(s > 0, 1.0 / s, 0.0)
        u = jnp.matmul(z, u_z[:, :keep], precision="highest") * inv_s  # (m, keep)
        # the Gram-eigh route loses orthogonality within σ-clusters
        # (measured up to ~5e-1 on flat spectra in f32); Cholesky-QR2
        # restores the isometry contract without rotating columns.
        # σ=0 columns stay exactly zero (truncation noise, documented).
        u = _cholqr2_refine(u)
    if want in ("right", "both"):
        # orthonormal·orthogonal — full precision keeps it at machine eps
        v = jnp.matmul(qw, u_z[:, :keep], precision="highest")  # (n, keep)
    err_sq = jnp.maximum(norm_sq - jnp.sum(lam), 0.0)
    return u, v, s, err_sq, norm_sq


_ONEVIEW_GAP = 9   # k̂ = keep + GAP column-sketch oversample (Tropp one-view)
_ONEVIEW_ERRQ = 10  # extra Ψ rows reserved for the unbiased error estimator


def _one_view_params(keep: int, cap: int, m: Optional[int] = None, n: Optional[int] = None):
    """(k̂, ℓ) for the one-view sketch, or None when it should not run:
    matrix too small for the sketch (the 4·l ≤ cap gate the 2-pass route
    mirrors), or — ON TPU, when (m, n) are given — a signature the fused
    dual kernel cannot serve (k̂/ℓ caps, tile divisibility, VMEM
    footprint): the XLA fallback streams A THREE times, strictly worse
    than the 2-pass default the caller opted out of, so single_pass
    silently reverts to 2-pass instead (code-review r5). k̂ = keep +
    oversample, ℓ = 2k̂ + 1 (Tropp's co-range width); ℓ counts only the
    B-fitting rows, the _ONEVIEW_ERRQ estimator rows ride on top."""
    k_hat = keep + _ONEVIEW_GAP
    l_row = 2 * k_hat + 1
    if 4 * (l_row + _ONEVIEW_ERRQ) > cap:
        return None
    if m is not None and n is not None and jax.default_backend() == "tpu":
        from ._pallas_sketch import dual_sketch_serviceable

        if not dual_sketch_serviceable(l_row + _ONEVIEW_ERRQ, k_hat, m, n):
            return None
    return k_hat, l_row


def _one_view_uds_both(a_blk, keep: int, k_hat: int, sketch_l: int, want: str = "left"):
    """ONE-VIEW (single-pass) randomized truncated SVD (Tropp et al.,
    'Practical sketching algorithms for low-rank matrix approximation'):
    the column sketch ``Y = AΩ`` and the row sketch ``W = ΨA`` both come
    from the SAME streaming read of A — on TPU literally one pass via the
    fused ``dual_sketch_with_norm`` Pallas kernel (w, y, and ‖A‖² from
    each tile in VMEM), so the HBM bound is 819 GB/s where the 2-pass
    schedule of ``_sketched_uds_both`` caps at 410.

    Reconstruction: Q = orth(Y); B = (ΨQ)⁺W via QR + triangular solve;
    A ≈ Q·B; Gram-eigh of B gives both factor sides (same rationale as
    the 2-pass route). Quality trade (documented, opt-in via
    ``hsvd_rank(..., single_pass=True)``): exact for rank ≤ k̂ matrices;
    on decaying spectra the constant is modestly larger than the HMT
    2-pass bound (measured 1.32× vs 1.11× optimal on i^-1.5); on
    HEAVY-TAILED / flat spectra the σ estimates absorb folded residual
    energy (up to ~10× inflation on iid Gaussian inputs) — the intended
    domain is near-low-rank data, and the default 2-pass route is the
    right tool elsewhere.

    The a-posteriori error is an UNBIASED sketched estimator, not the
    2-pass route's exact identity: _ONEVIEW_ERRQ extra Ψ rows ride the
    SAME fused pass (never used to fit B, so no selection bias) and
    E‖Ψ₂(A − QB)‖²_F = q·‖A − QB‖²_F gives the residual directly —
    this stays honest on the heavy-tailed inputs where a norm-minus-
    captured-energy estimate would clamp to a misleading zero.

    ℓ = sketch_l rows fit B; k̂ columns for Ω; ℓ ≥ 2k̂ recommended.
    Returns (u|None, v|None, s, err_sq, norm_sq)."""
    m, n = a_blk.shape
    kg, ko = jax.random.split(jax.random.key(0x5BD1))
    q_err = _ONEVIEW_ERRQ
    g = jax.random.normal(kg, (sketch_l + q_err, m), dtype=a_blk.dtype)
    omega = jax.random.normal(ko, (n, k_hat), dtype=a_blk.dtype)
    from ._pallas_sketch import dual_sketch_with_norm

    fused = dual_sketch_with_norm(g, omega, a_blk)
    if fused is not None:
        w_full, y, norm_sq = fused       # ONE stream over A
    else:
        # XLA fallback/oracle: the same one-read schedule as the fused
        # kernel, as the fixed-grain tiled stream (ISSUE 11 — it used
        # to pay three reads); the staged windows replay it carry for
        # carry, bit-identical
        zero = jnp.zeros((), dtype=jnp.real(jnp.zeros((), a_blk.dtype)).dtype)
        w_full, y, norm_sq = _oneview_tiles(
            g, omega, a_blk, jnp.zeros((m, k_hat), dtype=a_blk.dtype), zero
        )
    return _one_view_tail(w_full, y, norm_sq, g, keep, sketch_l, want)


def _one_view_tail(w_full, y, norm_sq, g, keep: int, sketch_l: int, want: str):
    """Everything after the one-view stream — Q from the column sketch,
    the (ΨQ)⁺W solve, Gram-eigh, factor assembly, the unbiased sketched
    error estimator. Factored out so the staged executor runs the
    IDENTICAL tail on its assembled (w, y, norm)."""
    q_err = _ONEVIEW_ERRQ
    w, w_err = w_full[:sketch_l], w_full[sketch_l:]
    g_err = g[sketch_l:]
    q = _gram_orthonormalize(y)          # (m, k̂) — O(m·k̂²), no pass
    psi_q = jnp.matmul(g[:sketch_l], q, precision="highest")  # (ℓ, k̂)
    qq, rr = jnp.linalg.qr(psi_q)
    # B = (ΨQ)⁺ W solved through the QR factors (Tropp's stable form);
    # conjugated adjoints keep the pseudo-inverse and Gram hermitian on
    # native complex inputs (identity on reals)
    b = jax.scipy.linalg.solve_triangular(
        rr, jnp.matmul(jnp.conj(qq).T, w, precision="highest"), lower=False
    )                                    # (k̂, n)
    gram = jnp.matmul(b, jnp.conj(b).T, precision="highest")
    lam, u_b = jnp.linalg.eigh(gram)
    lam = jnp.maximum(lam[::-1], 0.0)
    u_b = u_b[:, ::-1]
    lam = lam[:keep]
    s = jnp.sqrt(lam)
    u = v = None
    if want in ("left", "both"):
        u = jnp.matmul(q, u_b[:, :keep], precision="highest")
        # Q itself degrades when Y is rank-deficient (exact-rank inputs:
        # the Gram orthonormalization has a null space) — the same
        # CholeskyQR2 refine the 2-pass route applies restores the
        # isometry contract; σ=0 truncation-noise columns stay zero
        u = _cholqr2_refine(u)
    if want in ("right", "both"):
        inv_s = jnp.where(s > 0, 1.0 / s, 0.0)
        v = jnp.matmul(jnp.conj(b).T, u_b[:, :keep], precision="highest") * inv_s
        v = _cholqr2_refine(v)
    # unbiased residual estimate from the held-out sketch rows:
    # Ψ₂A − (Ψ₂Q)B, with the KEPT-rank reconstruction (drop tail modes)
    b_keep = jnp.matmul(
        jnp.conj(u_b[:, :keep]).T, b, precision="highest"
    )                                    # (keep, n) rank-truncated B
    pred = jnp.matmul(
        jnp.matmul(g_err, q, precision="highest") @ u_b[:, :keep],
        b_keep, precision="highest",
    )
    resid = w_err - pred
    err_sq = jnp.sum(jnp.real(resid * jnp.conj(resid))) / q_err
    return u, v, s, err_sq, norm_sq


def _truncate_with_err(res, r_final: int):
    """Shared rank-budget tail: truncate the sketch factors to
    ``r_final`` and fold the a-posteriori relative error — the ONE
    definition every jitted rank program (2-pass, one-view, and their
    staged forms) composes, so the arithmetic cannot drift apart."""
    u, v, s, err_sq, norm_sq = res
    err = jnp.sqrt(err_sq + jnp.sum(s[r_final:] ** 2)) / jnp.maximum(
        jnp.sqrt(norm_sq), 1e-30
    )
    return (
        u[:, :r_final] if u is not None else None,
        v[:, :r_final] if v is not None else None,
        s[:r_final],
        err,
    )


@observed_program_cache("hsvd.one_view_rank")
def _one_view_single_rank_fn(keep: int, k_hat: int, sketch_l: int, r_final: int, want: str = "left"):
    """Jitted one-view rank-budget program (the single_pass analog of
    ``_sketched_single_rank_fn``): truncation + approximate error fold
    into one compiled program, one dispatch."""

    def run(arr):
        return _truncate_with_err(
            _one_view_uds_both(arr, keep, k_hat, sketch_l, want), r_final
        )

    return jax.jit(run)


@observed_program_cache("hsvd.sketched")
def _sketched_single_fn(keep: int, sketch_l: int, want: str = "left"):
    """Jitted single-device randomized truncated SVD returning the
    ``want``ed factor side(s) — both sides come from the same two
    passes, so the transposed (split=0) orientation never materializes
    Aᵀ (an eager or even traced ``arr.T`` at the north-star size is a
    full strided read+write over A, ~5 ms profiled round 3) and never
    pays the reference's ``U = A·V·Σ⁻¹`` postprocessing pass."""

    def run(arr):
        return _sketched_uds_both(arr, keep, sketch_l, want)

    return jax.jit(run)


@observed_program_cache("hsvd.sketched_rank")
def _sketched_single_rank_fn(keep: int, sketch_l: int, r_final: int, want: str = "left"):
    """Rank-budget variant: truncation and the a-posteriori error fold
    into the SAME compiled program, so one call is ONE dispatch and no
    blocking host read."""

    def run(arr):
        return _truncate_with_err(_sketched_uds_both(arr, keep, sketch_l, want), r_final)

    return jax.jit(run)


# --------------------------------------------------------------------- #
# out-of-core staging (ISSUE 11): the host-resident rank-budget sketch  #
# --------------------------------------------------------------------- #
@functools.lru_cache(maxsize=1)
def _staged_stream_fns():
    """Per-window jitted forms of the tiled streams — jax.jit caches per
    window shape, and every window's tile sequence is the in-HBM one."""
    return (
        jax.jit(_pass1_tiles),
        jax.jit(_pass2_tiles),
        jax.jit(_oneview_tiles),
        jax.jit(lambda w: _gram_orthonormalize(jnp.conj(w).T)),
    )


@observed_program_cache("hsvd.staged_rank_tail")
def _staged_rank_tail_fn(keep: int, r_final: int, want: str):
    """Jitted tail of the staged 2-pass rank-budget sketch: the exact
    ``_projection_tail`` + truncation + error arithmetic of
    ``_sketched_single_rank_fn``, on the staged (z, qw, norm)."""

    def run(z, qw, norm_sq):
        return _truncate_with_err(_projection_tail(z, qw, norm_sq, keep, want), r_final)

    return jax.jit(run)


@observed_program_cache("hsvd.staged_oneview_tail")
def _staged_oneview_tail_fn(keep: int, sketch_l: int, r_final: int, want: str):
    """Jitted tail of the staged ONE-pass sketch: ``_one_view_tail`` +
    truncation + error, on the staged (w, y, norm)."""

    def run(w_full, y, norm_sq, g):
        return _truncate_with_err(
            _one_view_tail(w_full, y, norm_sq, g, keep, sketch_l, want), r_final
        )

    return jax.jit(run)


def _staged_sketch_rank(host, keep: int, sketch_l: int, r_final: int, want: str,
                        one_view, jt):
    """Rank-budget sketch over a HOST-RESIDENT operand, window by
    window (``redistribution.staging`` — arXiv:2112.09017's host-staged
    schedule): the operand never materializes on device; (8,128)-tile-
    aligned windows stream through the depth-2 double-buffered HBM slab
    (``jax.device_put`` of window k+1 issued under window k's compute),
    the window schedule is planned as a ``host-staging`` Schedule priced
    by the memory-tier lattice and PROVEN to fit ``capacity("hbm")``
    before the first byte moves, and — because the windows replay the
    in-HBM streams' fixed tile grain with explicit carries — the
    returned factors are BIT-IDENTICAL to the in-HBM path on a fitting
    twin (pinned).

    2-pass form: column windows feed ``_pass1_tiles`` (w assembled on
    device), row windows feed ``_pass2_tiles`` (z + the Frobenius carry);
    1-pass (``one_view=(k̂, ℓ)``): column windows feed ``_oneview_tiles``
    with the (y, norm) carries — ONE stream over the host operand.

    Returns device arrays ``(u|None, v|None, s, err)``."""
    from ...redistribution import staging as _staging

    m, n = host.shape
    item = np.dtype(jt).itemsize
    passes = (
        [{"tag": "dual-sketch", "axis": 1}]
        if one_view is not None
        else [{"tag": "sketch", "axis": 1}, {"tag": "project", "axis": 0}]
    )
    # HBM-resident working set held across the window loops: the sketch
    # factors and the assembled projection (w/qw/z or w/y), plus the
    # small tail outputs
    l_rows = (one_view[1] + _ONEVIEW_ERRQ) if one_view is not None else sketch_l
    width = one_view[0] if one_view is not None else sketch_l
    out_bytes = item * (l_rows * n + l_rows * m + 2 * n * width + 2 * m * width)
    sched = _staging.plan_staged_passes((m, n), np.dtype(jt), passes, out_bytes=out_bytes)
    _staging.prove_fits(sched)
    slab = int(sched.staging["slab_bytes"])
    _jit_pass1, _jit_pass2, _jit_oneview, _jit_orth_rows = _staged_stream_fns()

    def _cast(arr):
        return arr.astype(jt) if arr.dtype != np.dtype(jt) else arr

    if one_view is not None:
        k_hat, l_row = one_view
        kg, ko = jax.random.split(jax.random.key(0x5BD1))
        g = jax.random.normal(kg, (l_row + _ONEVIEW_ERRQ, m), dtype=jt)
        omega = jax.random.normal(ko, (n, k_hat), dtype=jt)
        wins = _staging.window_extents((m, n), item, 1, slab)
        chunks = []
        carry = {
            "y": jnp.zeros((m, k_hat), dtype=jt),
            "norm": jnp.zeros((), dtype=jnp.real(jnp.zeros((), jt)).dtype),
        }

        def consume(k, slab_arr, win):
            w_k, carry["y"], carry["norm"] = _jit_oneview(
                g, omega[win[0] : win[1]], _cast(slab_arr), carry["y"], carry["norm"]
            )
            chunks.append(w_k)

        _staging.stream_windows(host, 1, wins, consume, plan_id=sched.plan_id)
        w_full = chunks[0] if len(chunks) == 1 else jnp.concatenate(chunks, axis=1)
        return _staged_oneview_tail_fn(keep, l_row, r_final, want)(
            w_full, carry["y"], carry["norm"], g
        )

    key = jax.random.key(0x5BD)  # the in-HBM sketch's key — same g, same w
    g = jax.random.normal(key, (sketch_l, m), dtype=jt)
    wins1 = _staging.window_extents((m, n), item, 1, slab)
    chunks = []

    def consume1(k, slab_arr, win):
        chunks.append(_jit_pass1(g, _cast(slab_arr)))

    _staging.stream_windows(host, 1, wins1, consume1, plan_id=sched.plan_id)
    w = chunks[0] if len(chunks) == 1 else jnp.concatenate(chunks, axis=1)
    qw = _jit_orth_rows(w)

    wins2 = _staging.window_extents((m, n), item, 0, slab)
    zc = []
    carry2 = {"norm": jnp.zeros((), dtype=jnp.real(jnp.zeros((), jt)).dtype)}

    def consume2(k, slab_arr, win):
        z_k, carry2["norm"] = _jit_pass2(_cast(slab_arr), qw, carry2["norm"])
        zc.append(z_k)

    _staging.stream_windows(host, 0, wins2, consume2, plan_id=sched.plan_id)
    z = zc[0] if len(zc) == 1 else jnp.concatenate(zc, axis=0)
    return _staged_rank_tail_fn(keep, r_final, want)(z, qw, carry2["norm"])


def _hsvd_rank_host(host, maxrank: int, compute_sv: bool, safetyshift: int,
                    single_pass: bool):
    """``hsvd_rank`` over a host-tier operand (``staging.HostArray``).

    Staged when the gate allows and the rank-budget sketch is
    admissible; with ``HEAT_TPU_OOC=0`` (or a sketch-inadmissible
    budget — tiny matrices need the full SVD) the operand is
    materialized whole IF it fits ``tiers.capacity("hbm")`` and takes
    the ordinary in-HBM path, else a MemoryError names the numbers."""
    from ...redistribution import staging as _staging
    from ..communication import get_comm
    from ..devices import sanitize_device

    m, n = host.shape
    heat_dt = types.canonical_heat_type(host.dtype)
    if types.heat_type_is_exact(heat_dt):
        heat_dt = types.float32
    jt = heat_dt.jax_type()
    full_rank_cap = min(m, n)
    budget = maxrank + safetyshift
    l = min(budget + _SKETCH_OVERSAMPLE, full_rank_cap)
    admissible = 4 * l <= full_rank_cap

    if not _staging.ooc_engaged(host.nbytes, host_resident=True) or not admissible:
        # escape hatch (HEAT_TPU_OOC=0) or a budget only the full SVD
        # serves (staging streams the sketch passes only): materialize
        # the operand IF the chip can hold it — the shared helper names
        # the numbers otherwise
        what = (
            "hsvd_rank"
            if admissible
            else "hsvd_rank (sketch-inadmissible rank budget needs the full SVD)"
        )
        arr = _staging.materialize(host, what=what).astype(heat_dt)
        return hsvd_rank(
            arr, maxrank, compute_sv=compute_sv, safetyshift=safetyshift,
            single_pass=single_pass,
        )

    comm = get_comm()
    device = sanitize_device(None)
    keep = min(budget, full_rank_cap)
    r_final = max(1, min(maxrank, keep))
    want = "both" if compute_sv else "left"
    ov = _one_view_params(keep, full_rank_cap, m, n) if single_pass else None
    with _span("ht.call.hsvd.level0"), svd_x32_scope(jt):
        u_t, v_t, s_t, err_dev = _staged_sketch_rank(
            host, keep, sketch_l=l, r_final=r_final, want=want, one_view=ov, jt=jt
        )
    with _span("ht.call.hsvd.wrap"):
        err = _err_scalar(err_dev, comm=comm, device=device)
        U = DNDarray(u_t, (m, r_final), heat_dt, None, device, comm)
        sigma = DNDarray(
            _place(jnp.asarray(s_t), comm.sharding(1, None)),
            (int(s_t.shape[0]),),
            heat_dt,
            None,
            device,
            comm,
        )
        if not compute_sv:
            return U, err
        V = DNDarray(v_t, (n, r_final), heat_dt, None, device, comm)
        return U, sigma, V, err


@observed_program_cache("hsvd.local_svd")
def _local_svd_fn(
    mesh, axis_name: str, lrows: int, lcols: int, rloc: int, jdtype: str,
    sketch_l: Optional[int] = None, one_view: Optional[tuple] = None,
):
    """Compiled level-0 kernel of the STAGED distributed path (tolerance
    mode, full local SVDs, the one-view sketch; the rank-budget two-pass
    call is ``_dist_rank_fn``): per-shard truncated SVD → U·Σ block plus
    discarded-energy scalar (the analog of reference
    ``compute_local_truncated_svd``, svdtools.py:477). With ``sketch_l``
    the block SVD is the randomized range-finder variant; ``one_view``
    = (k̂, ℓ) selects the single-pass sketch per shard (r5). It takes the
    reference's orientation, column blocks (``P(None, axis)``): a split-0
    caller hands it ``phys.T``, a copy of ``A``."""

    def kernel(a_blk):
        # a_blk: (lrows, lcols) local column block of A (split=1 layout)
        if one_view is not None or sketch_l is not None:
            keep = min(rloc, min(a_blk.shape))
            if one_view is not None:
                k_hat, l_row = one_view
                u, _, s, err_sq, norm_sq = _one_view_uds_both(
                    a_blk, keep, k_hat, l_row, "left"
                )
            else:
                u, s, err_sq, norm_sq = _sketched_uds(a_blk, keep, sketch_l)
            u_scaled = u * s
            if keep < rloc:
                u_scaled = jnp.pad(u_scaled, ((0, 0), (0, rloc - keep)))
            return u_scaled, err_sq[None], norm_sq[None]
        u, s, _ = jnp.linalg.svd(a_blk, full_matrices=False)
        k = s.shape[0]
        keep = min(rloc, k)
        u_scaled = u[:, :keep] * s[:keep]
        if keep < rloc:
            u_scaled = jnp.pad(u_scaled, ((0, 0), (0, rloc - keep)))
        err_sq = jnp.sum(s[keep:] ** 2)
        # Frobenius partial fused into the same data read (the a-posteriori
        # bound needs ‖A‖_F; a separate eager pass would re-stream A)
        norm_sq = jnp.sum(s * s)
        return u_scaled, err_sq[None], norm_sq[None]

    return jax.jit(
        _shard_map(
            kernel,
            mesh=mesh,
            in_specs=PartitionSpec(None, axis_name),
            out_specs=(
                PartitionSpec(None, axis_name),
                PartitionSpec(axis_name),
                PartitionSpec(axis_name),
            ),
            check_vma=False,
        )
    )


#: widest stacked factor ``B`` (p·rloc columns) that the distributed
#: rank-budget program merges by gathering it whole onto every device:
#: up to one 128-lane tile the Gram of ``B`` and its eigh are one tile's
#: work, replicated. Wider stacks (the north star's 64 x 15 = 960) keep
#: the row-split TSQR merge, whose work and bytes divide by p.
_MERGE_GATHER_MAX_COLS = 128


@observed_program_cache("hsvd.dist_rank")
def _dist_rank_fn(
    mesh, axis_name: str, split: int, blk_shape: tuple, jdtype: str,
    rloc: int, sketch_l: int, r_final: int, tsqr: bool, ring: bool = False, topo=None,
):
    """The whole distributed rank-budget call as ONE ``shard_map`` program
    over a split-``split`` 2-D array whose device block is ``blk_shape``
    (level 0, merge, both factors, the error estimate; no host read).

    **Level 0** (``jax.named_scope("hsvd.level0")``): every device runs
    ``_sketched_uds_both`` on its block AS IT LIES, ``A_i ≈ u_i s_i v_iᴴ``:
    the two streams of the one-device call (the Pallas sketch+norm pass
    and one dot, at the MXU's default precision), no transposed or cast
    copy of the block. Of the two local factors one lives on the split
    axis (``own``: ``u_i`` for split 0, ``v_i`` for split 1), the other
    spans the axis every device shares.

    **Merge** (``"hsvd.merge"``): the stacked ``B = [shared_1 s_1 ∥ … ∥
    shared_p s_p] = W S Zᴴ``. Up to ``_MERGE_GATHER_MAX_COLS`` columns
    ``B`` is all-gathered (8192 x 60 f32 = 2 MB on four chips) and every
    device takes the eigh of its Gram; wider, ``B`` goes row-split by one
    all-to-all and through TSQR (``qr._tsqr_kernel``) and the SVD of R.
    ``W`` (rows of it, out split 0) is the shared-side factor. The
    own-side factor needs NO third pass ``A W Σ⁻¹``: device i's rows of it
    are ``own_i Z_i`` (``Z_i``: the device's ``rloc`` rows of ``Z``), a
    (rows x rloc)(rloc x r) product, orthonormal by construction
    (``Σ Z_iᴴ Z_i = I``). Every product here is skinny and runs at
    ``precision="highest"``.

    Returns ``(own (split 0), sigma, shared rows (split 0), err)``."""
    from .qr import _tsqr_kernel

    p = mesh.devices.size
    shared_n = blk_shape[1 - split]
    chunk = -(-shared_n // p)  # rows of the shared-side factor a device returns
    merge_rows = _tsqr_kernel(p, axis_name, True, ring, topo) if tsqr else None

    def kernel(a_blk):
        i = jax.lax.axis_index(axis_name)
        with jax.named_scope("hsvd.level0"):
            # rloc <= both extents of the block (the caller's min), so it is the rank kept
            u, v, s, err_sq, norm_sq = _sketched_uds_both(a_blk, rloc, sketch_l, "both")
            own, shared = (u, v) if split == 0 else (v, u)
            b = shared * s
        with jax.named_scope("hsvd.merge"):
            b = jnp.pad(b, ((0, chunk * p - shared_n), (0, 0)))
            if tsqr:
                rows = jax.lax.all_to_all(b, axis_name, 0, 1, tiled=True)  # (chunk, p·rloc)
                q_rows, r = merge_rows(rows)
                u_r, s_all, zh = safe_svd(r, full_matrices=False)
                w_rows = jnp.matmul(q_rows, u_r[:, :r_final], precision="highest")
                z = jnp.conj(zh).T[:, :r_final]
                # Q of an all-zero pad row is not zero by construction
                live = (i * chunk + jnp.arange(chunk) < shared_n)[:, None]
                w_rows = jnp.where(live, w_rows, 0)
            else:
                bs = jax.lax.all_gather(b, axis_name, axis=1, tiled=True)  # (·, p·rloc)
                gram = jnp.matmul(jnp.conj(bs).T, bs, precision="highest")
                lam, z = jnp.linalg.eigh(gram)  # ascending
                s_all = jnp.sqrt(jnp.maximum(lam[::-1], 0.0))
                z = z[:, ::-1][:, :r_final]
                inv_s = jnp.where(s_all[:r_final] > 0, 1.0 / s_all[:r_final], 0.0)
                w = _cholqr2_refine(jnp.matmul(bs, z, precision="highest") * inv_s)
                w_rows = jax.lax.dynamic_slice_in_dim(w, i * chunk, chunk)
            z_i = jax.lax.dynamic_slice_in_dim(z, i * rloc, rloc)
            own_out = jnp.matmul(own, z_i, precision="highest")
            err = jnp.sqrt(
                jax.lax.psum(err_sq, axis_name) + jnp.sum(s_all[r_final:] ** 2)
            ) / jnp.maximum(jnp.sqrt(jax.lax.psum(norm_sq, axis_name)), 1e-30)
        return own_out, s_all[:r_final], w_rows, err

    in_spec = [None, None]
    in_spec[split] = axis_name
    rows_spec = PartitionSpec(axis_name, None)
    return jax.jit(
        _shard_map(
            kernel,
            mesh=mesh,
            in_specs=PartitionSpec(*in_spec),
            out_specs=(rows_spec, PartitionSpec(), rows_spec, PartitionSpec()),
            check_vma=False,
        )
    )


def _err_scalar(val, A=None, comm=None, device=None) -> DNDarray:
    """Wrap the relative-error estimate as a 0-d replicated DNDarray — the
    reference returns a DNDarray too (svdtools.py:449), and keeping it lazy
    avoids a host read-back per call.
    ``A`` supplies comm/device; host-staged callers (no DNDarray operand)
    pass them explicitly."""
    comm = A.comm if A is not None else comm
    device = A.device if A is not None else device
    arr = jnp.asarray(val)
    if types.heat_type_is_exact(types.canonical_heat_type(arr.dtype)):
        arr = arr.astype(jnp.float32)
    return DNDarray(
        _place(arr, comm.sharding(0, None)),
        (),
        types.canonical_heat_type(arr.dtype),
        None,
        device,
        comm,
    )


def _merge_svd(B: DNDarray, calc_u: bool = True):
    """SVD of the stacked factor matrix via TSQR + small-R SVD.

    B (m × K) with K = p·r small: resplit to rows, TSQR, then SVD of the
    K×K R on-device (replicated — it is tiny). The merge of the staged
    distributed path (``hsvd_rtol``, full local SVDs, the one-view
    sketch); the rank-budget two-pass call merges inside its one program
    (``_dist_rank_fn``). ``Q·U_R`` is K wide and runs at
    ``precision="highest"`` (at the MXU's default it cost U three digits
    of orthonormality).
    Returns (U as DNDarray split=0 | None, s, total extra err 0.0).
    """
    from .qr import qr as _qr

    m, K = B.shape
    _telemetry.inc("hsvd.dist.merge.tsqr" if m >= K else "hsvd.dist.merge.gather")
    if m >= K:
        Brow = B.resplit(0)
        q, r = _qr(Brow, calc_q=calc_u)
        u_r, s, _ = safe_svd(r.larray, full_matrices=False)
        if not calc_u:
            return None, s
        U = DNDarray(
            _padding.mask_phys(
                jnp.matmul(q._phys, u_r, precision="highest"), (m, int(u_r.shape[1])), 0
            ),
            (m, int(u_r.shape[1])),
            q.dtype,
            0,
            B.device,
            B.comm,
        )
        return U, s
    # short-fat stacked matrix: gather (it is small by construction)
    u, s, _ = safe_svd(B.larray, full_matrices=False)
    U = DNDarray(
        B.comm.shard(u, 0), (int(u.shape[0]), int(u.shape[1])), B.dtype, 0, B.device, B.comm
    )
    return U, s


def hsvd_rank(
    A: DNDarray,
    maxrank: int,
    compute_sv: bool = False,
    maxmergedim: Optional[int] = None,
    safetyshift: int = 5,
    silent: bool = True,
    single_pass: bool = False,
):
    """Truncated hierarchical SVD with a fixed rank budget (reference:
    svdtools.py:31). Returns ``(U, sigma, V, rel_error_estimate)`` when
    ``compute_sv=True`` else ``(U, rel_error_estimate)``.

    ``single_pass=True`` (r5, no reference analog) selects the ONE-VIEW
    sketch (``_one_view_uds_both``): column and row sketches from a
    single streaming read of A — on TPU one literal HBM pass via the
    fused dual-sketch kernel, doubling the throughput ceiling of the
    default 2-pass schedule. Opt-in because the approximation constant
    is larger than the 2-pass HMT bound and the returned error estimate
    is approximate; exact for matrices of rank ≤ maxrank+safetyshift.

    OUT-OF-CORE (ISSUE 11): ``A`` may be a
    ``ht.redistribution.staging.HostArray`` — a host-RAM- or
    HDF5-resident operand LARGER than HBM. The rank-budget sketch then
    streams (8,128)-aligned windows through a depth-2 double-buffered
    HBM slab (2-pass, or 1-pass with ``single_pass=True``), priced by
    the memory-tier lattice and proven to fit ``capacity("hbm")``
    before running; factors are bit-identical to the in-HBM path on a
    fitting twin. ``HEAT_TPU_OOC=0`` is the escape hatch (HostArray
    operands materialize whole when they fit), ``=1`` forces the
    staged pipeline for device operands too (the CI leg).
    """
    from ...redistribution import staging as _staging

    with _call_span("ht.call.hsvd_rank"):
        if isinstance(A, _staging.HostArray):
            if not isinstance(maxrank, (int, np.integer)) or maxrank < 1:
                raise ValueError(f"maxrank must be a positive integer, got {maxrank}")
            _warn_merge_knobs(maxmergedim, None)
            return _hsvd_rank_host(
                A, int(maxrank), compute_sv, int(safetyshift), bool(single_pass)
            )
        with _span("ht.call.hsvd.prepare"):
            sanitize_in(A)
            if A.ndim != 2:
                raise ValueError(f"hsvd requires a 2-dimensional array, got {A.ndim}")
            if not isinstance(maxrank, (int, np.integer)) or maxrank < 1:
                raise ValueError(f"maxrank must be a positive integer, got {maxrank}")
            if maxmergedim is not None and maxmergedim < 2 * (maxrank + safetyshift) + 1:
                raise ValueError(
                    "maxmergedim too small for maxrank+safetyshift (reference constraint, svdtools.py)"
                )
            _warn_merge_knobs(maxmergedim, None)
        return _hsvd_impl(
            A,
            maxrank=int(maxrank),
            rtol=None,
            safetyshift=int(safetyshift),
            compute_sv=compute_sv,
            silent=silent,
            single_pass=bool(single_pass),
        )


def hsvd_rtol(
    A: DNDarray,
    rtol: float,
    compute_sv: bool = False,
    maxrank: Optional[int] = None,
    maxmergedim: Optional[int] = None,
    no_of_merges: Optional[int] = None,
    silent: bool = True,
    safetyshift: int = 5,
):
    """Hierarchical SVD truncated to a relative error tolerance (reference:
    svdtools.py:124): the returned factorization satisfies
    ‖A − UΣVᵀ‖_F ≤ rtol·‖A‖_F (upper-bound estimate).
    """
    with _call_span("ht.call.hsvd_rtol"):
        with _span("ht.call.hsvd.prepare"):
            sanitize_in(A)
            if A.ndim != 2:
                raise ValueError(f"hsvd requires a 2-dimensional array, got {A.ndim}")
            if rtol <= 0:
                raise ValueError(f"rtol must be positive, got {rtol}")
            _warn_merge_knobs(maxmergedim, no_of_merges)
        return _hsvd_impl(
            A,
            maxrank=int(maxrank) if maxrank is not None else None,
            rtol=float(rtol),
            safetyshift=int(safetyshift),
            compute_sv=compute_sv,
            silent=silent,
        )


def hsvd(
    A: DNDarray,
    maxrank: Optional[int] = None,
    maxmergedim: Optional[int] = None,
    rtol: Optional[float] = None,
    safetyshift: int = 0,
    no_of_merges: Optional[int] = 2,
    compute_sv: bool = False,
    silent: bool = True,
    warnings_off: bool = False,
):
    """General hierarchical SVD entry point (reference: svdtools.py:259)."""
    with _call_span("ht.call.hsvd"):
        with _span("ht.call.hsvd.prepare"):
            sanitize_in(A)
            if maxrank is None and rtol is None:
                raise ValueError("at least one of maxrank and rtol must be given")
            _warn_merge_knobs(maxmergedim, no_of_merges)
        return _hsvd_impl(
            A,
            maxrank=int(maxrank) if maxrank is not None else None,
            rtol=rtol,
            safetyshift=int(safetyshift),
            compute_sv=compute_sv,
            silent=silent,
        )


def _hsvd_impl(
    A: DNDarray,
    maxrank: Optional[int],
    rtol: Optional[float],
    safetyshift: int,
    compute_sv: bool,
    silent: bool,
    single_pass: bool = False,
):
    """The three public calls' shared body. Not distributed (or
    ``split=None``): one jitted sketch program, or a full SVD. Distributed:

    - rank budget, two-pass sketch (``rtol is None``, the sketch gates
      holding, real dtype): ``_hsvd_dist_rank``: ONE observed program on
      the physical array as it lies. No transpose of ``A`` (each device
      asks ``_sketched_uds_both`` for both sides of its block), the merged
      factor from a gather (or TSQR, where p·r is wide) inside the program,
      the split-side factor from the devices' own level-0 factors times
      their rows of the merge's ``Z`` (no third pass over ``A``), every
      product but the two streams over ``A`` at ``precision="highest"``,
      the estimate a lazy 0-d array.
    - everything else (tolerance mode, which reads the merged spectrum on
      the host; full local SVDs; the one-view sketch; complex): the staged
      path: ``_local_svd_fn`` on the reference's column orientation (an
      eager ``phys.T`` for split 0), ``_merge_svd`` (resplit + TSQR), the
      complementary factor by ``_postprocess_v``'s pass over ``A``."""
    from ...redistribution import staging as _staging

    comm: MeshCommunication = A.comm
    dtype = A.dtype
    if types.heat_type_is_exact(dtype):
        dtype = types.float32
    jt = dtype.jax_type()

    # orient split=1 (columns distributed) — reference svdtools.py:314-318.
    # A split=0 array is NOT resharded: its physical row shards ARE the
    # column shards of Aᵀ (P('d',None) → transpose → P(None,'d')), so the
    # orientation is a device-local relabel with no collective and no
    # unpad/repad round trip.
    transposed = A.split == 0
    m, n = (A.shape[1], A.shape[0]) if transposed else A.shape
    full_rank_cap = min(m, n)

    # u_direct/v_direct: factors of the INPUT orientation computed
    # directly by the single-device path — both sides come from the same
    # passes, so neither the reference's transpose (svdtools.py:314-318)
    # nor its ``U = A·V·Σ⁻¹`` postprocessing pass (:456-467) is needed,
    # and the returned factors are orthonormal by construction (the
    # postprocessed product with SKETCHED (σ, v) pairs is not).
    u_direct = None
    v_direct = None
    if A.split is None or not comm.is_distributed():
        with _span("ht.call.hsvd.prepare"):
            arr = A.larray.astype(jt)
            budget = (maxrank + safetyshift) if maxrank is not None else None
            sketch_l = None
            if budget is not None and not _needs_exact_spectrum(rtol):
                l = min(budget + _SKETCH_OVERSAMPLE, full_rank_cap)
                if 4 * l <= full_rank_cap:
                    sketch_l = l
            if sketch_l is not None:
                # small rank budget: randomized range finder, O(mnl) not O(mn²)
                keep = min(budget, full_rank_cap)
                want = "both" if compute_sv else "left"
                if rtol is None:
                    r_final = max(1, min(maxrank, keep))
                    ov = (
                        _one_view_params(keep, full_rank_cap, A.shape[0], A.shape[1])
                        if single_pass
                        else None
                    )
        if sketch_l is not None and rtol is None:
            # rank-budget mode needs no spectrum on host (rank is static),
            # so truncation + error fold into the jitted program (one
            # dispatch) and err stays a lazy 0-d DNDarray
            with _span("ht.call.hsvd.level0"), svd_x32_scope(jt):
                if _staging.ooc_mode() == "1":
                    # HEAT_TPU_OOC=1 (the forced CI leg): route the
                    # in-HBM operand through the staged window
                    # pipeline — the fixed-grain tile streams make
                    # the result bit-identical by construction,
                    # and the pinned sweep proves it
                    with _span("ht.sync.read", what="hsvd.ooc_operand"):
                        host = _staging.HostArray(np.asarray(arr))
                    u_t, v_t, s_t, err_dev = _staged_sketch_rank(
                        host, keep, sketch_l=sketch_l, r_final=r_final,
                        want=want, one_view=ov, jt=jt,
                    )
                elif ov is not None:
                    k_hat, l_row = ov
                    u_t, v_t, s_t, err_dev = _one_view_single_rank_fn(
                        keep, k_hat, l_row, r_final, want
                    )(arr)
                else:
                    u_t, v_t, s_t, err_dev = _sketched_single_rank_fn(
                        keep, sketch_l, r_final, want
                    )(arr)
            with _span("ht.call.hsvd.wrap"):
                err = _err_scalar(err_dev, A)
                u_direct = DNDarray(u_t, (A.shape[0], r_final), dtype, None, A.device, comm)
                if v_t is not None:
                    v_direct = DNDarray(v_t, (A.shape[1], r_final), dtype, None, A.device, comm)
                s_np = s_t
        elif sketch_l is not None:
            with _span("ht.call.hsvd.level0"), svd_x32_scope(jt):
                u_f, v_f, s_dev, err0_sq_dev, norm_sq_dev = _sketched_single_fn(
                    keep, sketch_l, want
                )(arr)
            with _span("ht.call.hsvd.merge"):
                with _span("ht.sync.read", what="hsvd.spectrum"):
                    s_host, err0_sq, norm_sq = jax.device_get((s_dev, err0_sq_dev, norm_sq_dev))
                a_norm = float(np.sqrt(max(float(norm_sq), 0.0)))
                r_final = _choose_rank(
                    np.asarray(s_host), maxrank, rtol, a_norm, float(err0_sq), full_rank_cap
                )
                err_val = (
                    float(np.sqrt(float(err0_sq) + np.sum(np.asarray(s_host)[r_final:] ** 2)))
                    / max(a_norm, 1e-30)
                )
            with _span("ht.call.hsvd.wrap"):
                err = _err_scalar(err_val, A)
                u_direct = DNDarray(u_f[:, :r_final], (A.shape[0], r_final), dtype, None, A.device, comm)
                if v_f is not None:
                    v_direct = DNDarray(v_f[:, :r_final], (A.shape[1], r_final), dtype, None, A.device, comm)
                s_np = s_dev[:r_final]
        else:
            # full SVD dominates; BOTH sides fall out of the one call, so
            # no orientation transpose and no postprocessing pass
            with _span("ht.call.hsvd.level0"):
                u, s, vt = safe_svd(arr, full_matrices=False)
            with _span("ht.call.hsvd.merge"):
                # one combined transfer for norm + spectrum
                with _span("ht.sync.read", what="hsvd.spectrum"):
                    s_host = np.asarray(jax.device_get(s))
                a_norm = float(np.sqrt(np.sum(s_host.astype(np.float64) ** 2)))
                err_sq = 0.0
                r_final = _choose_rank(s_host, maxrank, rtol, a_norm, err_sq, full_rank_cap)
            with _span("ht.call.hsvd.wrap"):
                u_direct = DNDarray(u[:, :r_final], (A.shape[0], r_final), dtype, None, A.device, comm)
                v_direct = DNDarray(vt[:r_final].T, (A.shape[1], r_final), dtype, None, A.device, comm)
                s_np = s[:r_final]
                err = _err_scalar(
                    float(np.sqrt(np.sum(s_host[r_final:] ** 2))) / max(a_norm, 1e-30), A
                )
    else:
        with _span("ht.call.hsvd.prepare"):
            p = comm.size
            rloc = min(m, -(-n // p))
            if maxrank is not None:
                rloc = min(rloc, maxrank + safetyshift)
            # a device's block as it lies; its transpose is level 0's
            # (m, lcols) column block of the reference orientation
            blk = list(A._phys.shape)
            blk[A.split] //= p
            lcols = blk[A.split]
            sketch_l = None
            if maxrank is not None and not _needs_exact_spectrum(rtol):
                lmin = min(blk)
                l = min(rloc + _SKETCH_OVERSAMPLE, lmin)
                if 4 * l <= lmin:
                    sketch_l = l
            one_view = None
            if single_pass and sketch_l is not None:
                one_view = _one_view_params(min(rloc, lcols), min(m, lcols), m, lcols)
        if (
            sketch_l is not None
            and rtol is None
            and one_view is None
            and not types.heat_type_is_complexfloating(dtype)
        ):
            # rank budget, two-pass sketch: the whole call is one program
            return _hsvd_dist_rank(A, dtype, tuple(blk), rloc, sketch_l, maxrank, compute_sv)
        with _span("ht.call.hsvd.prepare"):
            phys = A._phys.astype(jt)
            if transposed:
                # pad rows become zero pad columns: Frobenius/SVD-neutral
                phys = phys.T
        with _span("ht.call.hsvd.level0"):
            fn = _local_svd_fn(
                comm.mesh, comm.axis_name, phys.shape[0], lcols, rloc, np.dtype(jt).name,
                sketch_l, one_view,
            )
            with svd_x32_scope(jt):
                b_phys, err_blocks, normsq_blocks = fn(phys)
            B = DNDarray(
                b_phys, (m, int(b_phys.shape[1])), dtype, 1, A.device, comm
            )
        with _span("ht.call.hsvd.merge"):
            U_merged, s_all = _merge_svd(B, calc_u=True)
            if rtol is None:
                # static rank: err computed on device, ONE scalar read-back
                r_final = max(1, min(maxrank, min(int(s_all.shape[0]), full_rank_cap)))
                err_val = jnp.sqrt(
                    jnp.sum(err_blocks) + jnp.sum(s_all[r_final:] ** 2)
                ) / jnp.maximum(jnp.sqrt(jnp.sum(normsq_blocks)), 1e-30)
            else:
                with _span("ht.sync.read", what="hsvd.spectrum"):
                    s_np_all, lvl_sq, nrm_sq = jax.device_get(
                        (s_all, jnp.sum(err_blocks), jnp.sum(normsq_blocks))
                    )
                s_np_all = np.asarray(s_np_all)
                a_norm = float(np.sqrt(max(float(nrm_sq), 0.0)))
                level_err_sq = float(lvl_sq)
                r_final = _choose_rank(s_np_all, maxrank, rtol, a_norm, level_err_sq, full_rank_cap)
                merge_err_sq = float(np.sum(s_np_all[r_final:] ** 2))
                err_val = float(np.sqrt(level_err_sq + merge_err_sq)) / max(a_norm, 1e-30)
        with _span("ht.call.hsvd.wrap"):
            err = _err_scalar(err_val, A)
            # truncate U to the final rank
            u_trunc = U_merged.larray[:, :r_final]
            U_arr = DNDarray(comm.shard(u_trunc, 0), (m, r_final), dtype, 0, A.device, comm)
            s_np = s_all[:r_final]

    with _span("ht.call.hsvd.wrap"):
        sigma_arr = jnp.asarray(s_np)
        sigma = DNDarray(
            _place(sigma_arr, comm.sharding(1, None)),
            (int(sigma_arr.shape[0]),),
            dtype,
            None,
            A.device,
            comm,
        )

    if u_direct is not None or v_direct is not None:
        # single-device path: factors already in the input orientation
        U_of_A, V_of_A = u_direct, v_direct
    elif transposed:
        # A = U Σ V^H for the original orientation: the left factors of
        # Aᵀ are conj(V) (Aᵀ = conj(V) Σ Uᵀ), so native complex inputs
        # conjugate on the relabel; real inputs swap factors unchanged
        U_of_A = None
        if types.heat_type_is_complexfloating(dtype):
            from .. import complex_math as _cmath

            V_of_A = _cmath.conj(U_arr)
        else:
            V_of_A = U_arr
    else:
        U_of_A = U_arr
        V_of_A = None

    if not compute_sv:
        # reference returns (U, relerr) where U are the left singular
        # vectors of the *input orientation*
        primary = U_of_A if U_of_A is not None else _postprocess_v(A, V_of_A, sigma, left=True)
        return primary, err

    # compute any missing factor via the reference's postprocessing
    # (svdtools.py:456-467): V = Aᵀ U Σ⁻¹ (or U = A V Σ⁻¹) — only the
    # distributed path still needs this; single-device has both sides
    if U_of_A is not None and V_of_A is not None:
        return U_of_A, sigma, V_of_A, err
    if U_of_A is not None:
        V = _postprocess_v(A, U_of_A, sigma, left=False)
        return U_of_A, sigma, V, err
    U = _postprocess_v(A, V_of_A, sigma, left=True)
    return U, sigma, V_of_A, err


def _hsvd_dist_rank(
    A: DNDarray, dtype, blk: tuple, rloc: int, sketch_l: int, maxrank: int, compute_sv: bool
):
    """``hsvd_rank`` on a distributed split-0 or split-1 array whose
    device block is ``blk``: ONE launch of ``_dist_rank_fn`` on the
    physical array as it lies, then the ``DNDarray`` wrappers. Both factors come back split 0, sigma
    replicated, the error estimate a lazy 0-d array, as the staged
    distributed path returns them."""
    from .qr import _tsqr_ring_active

    comm: MeshCommunication = A.comm
    jt = dtype.jax_type()
    r_final = max(1, min(maxrank, comm.size * rloc, min(A.shape)))
    tsqr = comm.size * rloc > _MERGE_GATHER_MAX_COLS
    _telemetry.inc("hsvd.dist.merge.tsqr" if tsqr else "hsvd.dist.merge.gather")
    _telemetry.inc("hsvd.dist.u.local")
    topo_t = comm.topology
    with _span("ht.call.hsvd.level0"), svd_x32_scope(jt):
        fn = _dist_rank_fn(
            comm.mesh, comm.axis_name, A.split, blk, np.dtype(jt).name,
            rloc, sketch_l, r_final, tsqr,
            ring=tsqr and _tsqr_ring_active(),
            topo=(topo_t.n_slices, topo_t.chips_per_slice) if tsqr and topo_t.tiered else None,
        )
        own, s_dev, shared, err_dev = fn(A._phys.astype(jt))
    with _span("ht.call.hsvd.wrap"):
        err = _err_scalar(err_dev, A)
        own = DNDarray(own, (A.shape[A.split], r_final), dtype, 0, A.device, comm)
        shared = DNDarray(shared, (A.shape[1 - A.split], r_final), dtype, 0, A.device, comm)
        U, V = (own, shared) if A.split == 0 else (shared, own)
        if not compute_sv:
            return U, err
        sigma = DNDarray(
            _place(s_dev, comm.sharding(1, None)), (r_final,), dtype, None, A.device, comm
        )
        return U, sigma, V, err


def _postprocess_v(A: DNDarray, factor: DNDarray, sigma: DNDarray, left: bool) -> DNDarray:
    """Compute the complementary singular factor: V = Aᵀ U / σ or
    U = A V / σ (reference: svdtools.py:456-467)."""
    from . import basics

    _telemetry.inc("hsvd.dist.u.postprocess")
    with _span("ht.call.hsvd.postprocess"):
        if left:
            prod = basics.matmul(A, factor)  # (m, r)
        else:
            # V = A^H U / σ: the adjoint, not the transpose — native complex
            # inputs conjugate (conj is the identity on reals)
            At = basics.transpose(A, None)
            if types.heat_type_is_complexfloating(A.dtype):
                from .. import complex_math as _cmath

                At = _cmath.conj(At)
            prod = basics.matmul(At, factor)  # (n, r)
        inv_sigma = jnp.where(sigma.larray > 0, 1.0 / sigma.larray, 0.0)
        scaled = prod.larray * inv_sigma
        # A·V·Σ⁻¹ with TRUNCATED (σ, v) pairs is only approximately an
        # isometry (deviation ~ discarded-energy/σ_r — ~1e-1 on flat spectra;
        # the reference ships that deviation, svdtools.py:456-467). Two
        # Cholesky-QR rounds on the skinny (·, r) result restore machine
        # orthogonality without rotating columns; on a sharded operand the
        # (r, r) Gram is XLA's psum, ~2 cheap passes.
        scaled = _cholqr2_refine(scaled)
        return DNDarray(
            prod.comm.shard(scaled, prod.split) if prod.split is not None else scaled,
            prod.shape,
            prod.dtype,
            prod.split,
            prod.device,
            prod.comm,
        )


def _choose_rank(
    s: np.ndarray,
    maxrank: Optional[int],
    rtol: Optional[float],
    a_norm: float,
    prior_err_sq: float,
    cap: int,
) -> int:
    """Final truncation rank: static budget and/or smallest rank whose
    discarded energy keeps the total error below rtol·‖A‖ (reference
    truncation logic in compute_local_truncated_svd / hsvd)."""
    s = np.asarray(s, dtype=np.float64)
    k = min(len(s), cap)
    if rtol is None:
        return max(1, min(maxrank, k))
    budget_sq = (rtol * a_norm) ** 2 - prior_err_sq
    # discarded tail energy for every candidate rank
    tail = np.cumsum((s[::-1] ** 2))[::-1]  # tail[i] = sum_{j>=i} s_j^2
    r = k
    for i in range(k, 0, -1):
        discard = tail[i] if i < len(s) else 0.0
        if discard <= max(budget_sq, 0.0):
            r = i
        else:
            break
    if maxrank is not None:
        r = min(r, maxrank)
    return max(1, r)

from ..communication import register_mesh_cache
from ..communication import place as _place

# entries bake mesh geometry: cleared when init_distributed rebuilds the world
register_mesh_cache(_local_svd_fn)
