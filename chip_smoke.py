#!/usr/bin/env python3
"""Bring-up smoke: the array -> linalg -> estimator -> nn path on the chip.

Drives heat_tpu's main path once, through ``import heat_tpu as ht`` only,
at the sizes the repo calls its per-chip north-star shard, and checks
every phase against a plain ``jax.numpy`` reference written here. Data is
made on the device from ``--seed``; nothing of size crosses the host.

    python chip_smoke.py              # one chip (what the driver runs)
    python chip_smoke.py --chips 4    # the split-array path on a 4-chip mesh
    python chip_smoke.py --rehearse [--chips 4]
                                      # toy sizes on virtual CPU devices

Standard output carries one JSON line per phase and, only when every
phase passed on the accelerator it was asked to use, a last line

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The process exits non-zero, with the phase and the error on standard
error, when JAX finds no TPU, when a phase raises or misses its
tolerance, or when a kernel a phase names did not run compiled.
``--rehearse`` relaxes the platform check and lets kernels run in
interpret mode; its last line reports the platform JAX really used
(``"cpu"``), so it can never be read as a chip pass.

Seconds printed here are facts of this run (host clock around
``block_until_ready``), not rates and not shares of any peak.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
import traceback

# --------------------------------------------------------------------- #
# sizes: the per-chip north-star shards and their toy twins              #
# --------------------------------------------------------------------- #
REAL = dict(
    chain_n=67_108_864,            # 256 MB f32 per elementwise pass
    mm_n=8192,                     # MXU-saturating square matmul
    hsvd=(65536, 8192), rank=10,   # 2.1 GB: the headline per-chip shard
    qr=(524288, 1024),             # 2.1 GB: tall-skinny, one size under the benchmark's cell
    km=(15_625_000, 64), km_k=8,   # 4 GB: 1B x 64 over a v5e-64
    sort_n=1 << 24,
    mlp_batch=8192, mlp_steps=5,
    attn=(1, 8, 16384, 128),       # long-context attention, bf16, causal
    cplx_n=1024,
)
TOY = dict(
    chain_n=1 << 14,
    mm_n=256,
    hsvd=(2048, 256), rank=10,
    qr=(4096, 64),
    km=(20_000, 64), km_k=8,
    sort_n=1 << 14,
    mlp_batch=256, mlp_steps=5,
    attn=(1, 2, 512, 64),
    cplx_n=64,
)
MESH_SORT_N = 1 << 21              # --chips 4: below the one-device autotune threshold,
                                   # which would add two ~1 min sort compiles at 4x the price
MLP = (784, 128, 10)               # examples/mnist.py at MNIST's widths
SORT_BLOCK = 512                   # kernels/sort.py's Pallas block


def setup(argv=None) -> None:
    """Parse the command line, choose the backend and only then import
    jax and heat_tpu (a rehearsal has to set the backend first). Fills
    the module globals the phases read; exits where no TPU is found."""
    global ARGS, SZ, jax, jnp, ht, DEVICES, PLATFORM, ON_CHIP, HI, CACHE_DIR
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the split-array path and what it is compared with, and nothing else")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes on virtual CPU devices; never a chip pass")
    ARGS = ap.parse_args(argv)
    if ARGS.rehearse:
        # the rehearsal of on-chip-measurement guide 2.1-2.2: CPU backend,
        # one virtual device per chip asked for
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={ARGS.chips}"
        ).strip()

    import jax
    import jax.numpy as jnp

    DEVICES = jax.devices()
    PLATFORM = DEVICES[0].platform
    if PLATFORM != "tpu" and not ARGS.rehearse:
        sys.exit(f"chip_smoke: platform: jax.devices()[0].platform is {PLATFORM!r}, not 'tpu'")
    if len(DEVICES) != ARGS.chips:
        sys.exit(f"chip_smoke: platform: --chips {ARGS.chips} needs exactly that many "
                 f"devices, JAX found {len(DEVICES)}")

    import heat_tpu as ht

    CACHE_DIR = ht.utils.place_compile_cache()
    SZ = TOY if ARGS.rehearse else REAL
    ON_CHIP = PLATFORM == "tpu"
    HI = jax.lax.Precision.HIGHEST


# --------------------------------------------------------------------- #
# harness                                                                #
# --------------------------------------------------------------------- #
class Miss(Exception):
    """A phase ran but its result is not right."""


def ready(out):
    """``out`` (arrays, DNDarrays, tuples of them) once the device is done."""
    leaves = jax.tree.leaves(out, is_leaf=lambda x: hasattr(x, "_phys"))
    jax.block_until_ready([getattr(x, "_phys", x) for x in leaves])
    return out


def timed(fn):
    """``fn`` twice: (result, first-call seconds, warm seconds). The first
    call traces and compiles (or loads from the cache) and runs."""
    t0 = time.perf_counter()
    ready(fn())
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = ready(fn())
    return out, first, time.perf_counter() - t0


def check(name: str, err: float, tol: float) -> None:
    if not err <= tol:  # also catches NaN
        raise Miss(f"{name}: error {err:.3e} exceeds tolerance {tol:.1e}")


def need(cond, what: str) -> None:
    if not cond:
        raise Miss(what)


def rel(a, b) -> float:
    """max |a - b| over max |b|, in f32 on the device."""
    a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    return float(jnp.max(jnp.abs(a - b)) / jnp.maximum(jnp.max(jnp.abs(b)), 1e-30))


def times(first: float, warm: float) -> dict:
    return {
        "compile_s": round(max(first - warm, 0.0), 3),
        "first_s": round(first, 3),
        "warm_s": round(warm, 4),
    }


FAILED = []


def phase(name: str, fn) -> None:
    """Run one phase and print its JSON line. ``fn(rec)`` fills ``rec``
    as results come, so a failure still prints what was measured before
    it. A failure is printed, counted and ends in a non-zero exit; later
    phases still run so one chip call shows every fault."""
    t0 = time.perf_counter()
    rec = {"phase": name, "ok": True}
    try:
        fn(rec)
    except Exception as e:
        traceback.print_exc()
        print(f"chip_smoke: phase {name} FAILED: {type(e).__name__}: {e}", file=sys.stderr)
        FAILED.append(name)
        rec.update(ok=False, error=f"{type(e).__name__}: {e}"[:2000])
    gc.collect()
    stats = DEVICES[0].memory_stats() or {}
    rec["phase_s"] = round(time.perf_counter() - t0, 2)
    rec["peak_bytes"] = stats.get("peak_bytes_in_use")
    rec["bytes_in_use_after"] = stats.get("bytes_in_use")
    print(json.dumps(rec), flush=True)


def key(i: int):
    return jax.random.fold_in(jax.random.key(ARGS.seed), i)


def has_kernel(fn, *args) -> bool:
    """Whether the program ``fn(*args)`` compiles to holds a Mosaic
    kernel (compile only, nothing runs). Interpret mode lowers to plain HLO, so ``tpu_custom_call``
    means the kernel was compiled for the chip."""
    return "tpu_custom_call" in compiled_text(fn, *args)


def compiled_text(fn, *args) -> str:
    return ht.observability.collective_counts(fn, *args).hlo_text


# --------------------------------------------------------------------- #
# one chip                                                               #
# --------------------------------------------------------------------- #
def dispatch(rec: dict) -> None:
    dev = ht.get_device()
    rec.update(path="eager ht ops; ht.jit(chain+sum) as one program", device=str(dev),
               x64=ht.use_x64(), complex_mode=ht.complex_mode(), elements=SZ["chain_n"])
    need(dev.device_type == PLATFORM, f"ht.get_device() is {dev}, JAX runs on {PLATFORM}")
    need(ht.use_x64() == (not ON_CHIP), f"x64 policy resolved to {ht.use_x64()} on {PLATFORM}")
    xj = jax.random.normal(key(1), (SZ["chain_n"],), jnp.float32)
    x = ht.array(xj, split=0)

    def chain(v):  # sin, mul, add, abs, add, sqrt
        return ht.sqrt(ht.abs(ht.sin(v) * 2.0 + v) + 1.0)

    ref = jnp.sqrt(jnp.abs(jnp.sin(xj) * 2.0 + xj) + 1.0)
    ref_sum = float(jnp.sum(ref))
    eager, first_e, warm_e = timed(lambda: chain(x))
    rec.update(times(first_e, warm_e))
    errs = {"eager chain": rel(eager.larray, ref),
            "ht.sum": abs(float(ht.sum(eager)) - ref_sum) / abs(ref_sum)}
    del eager
    fused, first_j, warm_j = timed(lambda f=ht.jit(lambda v: ht.sum(chain(v))): f(x))
    errs["ht.jit chain+sum"] = abs(float(fused) - ref_sum) / abs(ref_sum)
    rec.update(jit=times(first_j, warm_j), max_err=max(errs.values()), tol=1e-4, errs=errs)
    for name, err in errs.items():
        check(name, err, 1e-5 if name == "eager chain" else 1e-4)


def matmul(rec: dict) -> None:
    n = SZ["mm_n"]
    # bf16 MXU inputs (2^-9 each) summed over n random-sign terms, against
    # the largest entry: 2e-2 bounds it with room
    rec.update(path="ht.matmul -> XLA dot (default precision)", n=n, max_err=0.0, tol=2e-2)
    for i, dt in enumerate((jnp.bfloat16, jnp.float32)):
        aj = jax.random.normal(key(10 + i), (n, n), jnp.float32).astype(dt)
        bj = jax.random.normal(key(20 + i), (n, n), jnp.float32).astype(dt)
        a, b = ht.array(aj), ht.array(bj)
        c, first, warm = timed(lambda: ht.matmul(a, b))
        need(c.larray.dtype == dt, f"ht.matmul returned {c.larray.dtype} for {dt.__name__} operands")
        ref = jnp.matmul(aj.astype(jnp.float32), bj.astype(jnp.float32), precision=HI)
        err = rel(c.larray, ref)
        rec[dt.__name__] = {**times(first, warm), "max_err": err}
        rec["max_err"] = max(rec["max_err"], err)
        check(f"matmul {dt.__name__}", err, 2e-2)
        del a, b, c, ref, aj, bj


def low_rank(m: int, n: int, rank: int, k, sigma_max: float = 100.0):
    """Rank-``rank`` signal with the spectrum sigma_max * 0.8^i plus noise
    whose largest singular value (~3.5e-4 (sqrt m + sqrt n)) stays far below."""
    ku, kv, kn = jax.random.split(k, 3)
    s = sigma_max * 0.8 ** jnp.arange(rank, dtype=jnp.float32)

    @jax.jit
    def make():
        u, _ = jnp.linalg.qr(jax.random.normal(ku, (m, rank), jnp.float32))
        v, _ = jnp.linalg.qr(jax.random.normal(kv, (n, rank), jnp.float32))
        return jnp.matmul(u * s, v.T, precision=HI) + 1e-3 * jax.random.normal(kn, (m, n), jnp.float32)

    return make()


def top_eigs(aj, rank: int):
    """Largest ``rank`` eigenvalues of A^T A and ||A||_F^2 in plain jnp at
    precision highest: Rayleigh-Ritz on a block power iteration (the gap
    between signal and noise makes three steps exact to f32)."""

    @jax.jit
    def run(a):
        q = jax.random.normal(jax.random.key(7), (a.shape[1], 2 * rank), jnp.float32)
        for _ in range(3):
            q, _ = jnp.linalg.qr(jnp.matmul(a.T, jnp.matmul(a, q, precision=HI), precision=HI))
        y = jnp.matmul(a, q, precision=HI)
        lam = jnp.linalg.eigvalsh(jnp.matmul(y.T, y, precision=HI))[::-1]
        return lam[:rank], jnp.sum(jnp.square(a))

    return run(aj)


def residual_sq(aj, uj, sj, vj):
    """||A - U S V^T||_F^2 in plain jnp at precision highest."""
    return jax.jit(lambda a: jnp.sum(jnp.square(a - jnp.matmul(uj * sj, vj.T, precision=HI))))(aj)


def svd_errors(aj, u, sig, v, err, lam, norm_sq, rank) -> dict:
    """What the factors measure against the reference: orthonormality,
    singular values against sqrt(eig(A^T A)) (relative to the largest),
    and the residual ||A - U S V^T||_F beside the returned estimate and
    the optimum no rank-``rank`` factorization can beat."""
    eye = jnp.eye(rank, dtype=jnp.float32)
    uj, vj, sj = u.larray, v.larray, sig.larray
    need(uj.shape[1] == rank and vj.shape[1] == rank and sj.shape == (rank,),
         f"factor shapes {uj.shape} {sj.shape} {vj.shape}")
    return {
        "orth_err": max(
            float(jnp.max(jnp.abs(jnp.matmul(uj.T, uj, precision=HI) - eye))),
            float(jnp.max(jnp.abs(jnp.matmul(vj.T, vj, precision=HI) - eye))),
        ),
        "sigma_err": rel(sj, jnp.sqrt(lam)),
        "rel_err_estimate": float(err),
        "rel_err_measured": float(jnp.sqrt(residual_sq(aj, uj, sj, vj) / norm_sq)),
        "rel_err_optimal": float(jnp.sqrt(jnp.maximum(norm_sq - jnp.sum(lam), 0.0) / norm_sq)),
    }


# Tolerances of the two schedules. The sketch passes run at default MXU
# precision: bf16 inputs, 2^-8 = 3.9e-3 relative. two_pass: sigma within
# 2.5 of those steps; its estimate is an exact identity for the returned
# factors; its residual within the HMT range-finder constant of the
# optimum. single_pass (one-view): a coarser approximation by design (its
# docstring states the larger constant). Its sigma bound was 1e-2 too,
# until the chip measured 2.8e-2 on this matrix — whose noise energy is
# 3x sigma_10^2 — at default and at highest precision alike; 4e-2 was set
# after that reading (PERF.md, Findings of PR 22). Its estimate is an
# unbiased estimator from 10 sketch rows (relative spread ~ sqrt(2/10)).
SVD_TOL = {
    "two_pass": dict(sigma=1e-2, estimate=0.05, slack=2.0),
    "single_pass": dict(sigma=4e-2, estimate=1.0, slack=3.0),
}


def check_svd(name: str, e: dict, tol: dict) -> None:
    # factors are re-orthonormalised at full precision
    check(f"{name} U^T U, V^T V", e["orth_err"], 1e-3)
    check(f"{name} sigma", e["sigma_err"], tol["sigma"])
    need(0.99 * e["rel_err_optimal"] <= e["rel_err_measured"] <= tol["slack"] * e["rel_err_optimal"],
         f"{name}: residual {e['rel_err_measured']:.4e} against the optimal error {e['rel_err_optimal']:.4e}")
    need(abs(e["rel_err_estimate"] - e["rel_err_measured"]) <= tol["estimate"] * e["rel_err_measured"],
         f"{name}: error estimate {e['rel_err_estimate']:.4e} vs measured residual "
         f"{e['rel_err_measured']:.4e} (tol {tol['estimate']:.0%})")


def hsvd(rec: dict) -> None:
    (m, n), rank = SZ["hsvd"], SZ["rank"]
    rec.update(shape=[m, n], rank=rank, tol=max(t["sigma"] for t in SVD_TOL.values()), tols=SVD_TOL)
    aj = low_rank(m, n, rank, key(30))
    lam, norm_sq = ready(top_eigs(aj, rank))
    a = ht.array(aj, split=0)
    for label, kw in (("two_pass", {}), ("single_pass", {"single_pass": True})):
        def call(a_):
            return ht.linalg.hsvd_rank(a_, rank, compute_sv=True, **kw)

        (u, sig, v, err), first, warm = timed(lambda: call(a))
        rec[label] = {**times(first, warm), **svd_errors(aj, u, sig, v, err, lam, norm_sq, rank)}
        hlo = compiled_text(call, a)
        rec[label]["sketch_kernel_in_hlo"] = "tpu_custom_call" in hlo
        # the two passes read f32 A; a bf16 copy of it is a third stream
        rec[label]["copy_of_a_in_hlo"] = f"bf16[{m},{n}]" in hlo
        del u, sig, v, err
    # how much of the one-view's sigma error is MXU input rounding: the
    # same call with every XLA matmul at full precision (diagnostic)
    with jax.default_matmul_precision("highest"):
        u, sig, v, err = ht.linalg.hsvd_rank(a, rank, compute_sv=True, single_pass=True)
        rec["single_pass"]["sigma_err_at_highest_precision"] = rel(sig.larray, jnp.sqrt(lam))
    kern = all(rec[k]["sketch_kernel_in_hlo"] for k in SVD_TOL)
    rec.update(
        path="pallas sketch_with_norm / dual_sketch_with_norm (compiled)" if kern
        else "XLA tiled sketch (no TPU backend)",
        max_err=max(rec[k]["sigma_err"] for k in SVD_TOL),
        **{k: rec["two_pass"][k] for k in ("compile_s", "first_s", "warm_s")},
    )
    for label, tol in SVD_TOL.items():
        check_svd(label, rec[label], tol)
        need(rec[label]["sketch_kernel_in_hlo"] or not ON_CHIP,
             f"{label}: no tpu_custom_call in the compiled hsvd_rank program — the Pallas sketch kernel did not run")
        need(not rec[label]["copy_of_a_in_hlo"],
             f"{label}: the compiled hsvd_rank program casts all of A to bf16[{m},{n}] — a pass more than the schedule has")


# QR: max |Q^T Q - I| and ||A - Q R||_F / ||A||_F, R against a plain fold relative
# to its largest entry. The limits are the benchmark configuration's (PERF.md, PR 34).
QR_TOL = {"orthonormal": 5e-5, "residual": 3e-5, "r": 2e-4}
QR_BLOCK = 8192


def qr(rec: dict) -> None:
    """``ht.linalg.qr`` of a tall-skinny uniform matrix (BASELINE.json's config
    3 with ``hsvd``), against plain ``jax.numpy``: the factors' own invariants
    a row block at a time, and ``R`` from a fold of Householder QRs of ``[R;
    block]`` with its diagonal made positive."""
    m, n = SZ["qr"]
    blk = min(QR_BLOCK, m)
    need(m % blk == 0, f"qr: {m} rows are no multiple of the check's block {blk}")
    aj = jax.random.uniform(key(35), (m, n), jnp.float32)
    a = ht.array(aj, split=0)
    (q, r), first, warm = timed(lambda: ht.linalg.qr(a))
    hlo = compiled_text(lambda a_: ht.linalg.qr(a_), a)

    def positive(x):
        return x * jnp.where(jnp.diagonal(x) < 0, -1.0, 1.0)[:, None]

    @jax.jit
    def errors(aj, qj, rj):
        def fold(i, c):
            r_ref, gram, resid_sq, norm_sq = c
            ab, qb = jax.lax.dynamic_slice_in_dim(aj, i * blk, blk), jax.lax.dynamic_slice_in_dim(qj, i * blk, blk)
            with jax.default_matmul_precision("highest"):
                r_ref = jnp.linalg.qr(jnp.concatenate([r_ref, ab]), mode="r")
            gram = gram + jnp.matmul(qb.T, qb, precision=HI)
            resid_sq = resid_sq + jnp.sum(jnp.square(ab - jnp.matmul(qb, rj, precision=HI)))
            return r_ref, gram, resid_sq, norm_sq + jnp.sum(jnp.square(ab))

        zero = jnp.zeros((n, n), jnp.float32)
        r_ref, gram, resid_sq, norm_sq = jax.lax.fori_loop(0, m // blk, fold, (zero, zero, 0.0, 0.0))
        r_ref = positive(r_ref)
        return {"orthonormal": jnp.max(jnp.abs(gram - jnp.eye(n))), "residual": jnp.sqrt(resid_sq / norm_sq),
                "r": jnp.max(jnp.abs(positive(rj) - r_ref)) / jnp.max(jnp.abs(r_ref)),
                "below_diagonal": jnp.max(jnp.abs(jnp.tril(rj, -1)))}

    e = {k: float(v) for k, v in errors(aj, q.larray, r.larray).items()}
    gram_form = "cholesky" in hlo.lower()
    rec.update(shape=[m, n], **times(first, warm), **e, tol=QR_TOL["orthonormal"], tols=QR_TOL,
               max_err=e["orthonormal"],
               path="qr.local: Cholesky-QR with a second pass (MXU products over row blocks)" if gram_form
               else "qr.local: XLA's Householder QR (no TPU backend)")
    need(gram_form or not ON_CHIP, "qr: the compiled program has no Cholesky: the Gram form did not run on the chip")
    need(e["below_diagonal"] == 0.0, f"qr: R has {e['below_diagonal']:.3e} below its diagonal")
    for name, tol in QR_TOL.items():
        check(f"qr {name}", e[name], tol)


def blobs(n: int, d: int, k: int, kk):
    """k separable Gaussian blobs, filled chunk by chunk into one buffer
    so that the generator's temporaries stay a fraction of the data."""
    kc, kd = jax.random.split(kk)
    centers = 4.0 * jax.random.normal(kc, (k, d), jnp.float32)
    chunks = next(c for c in (125, 100, 50, 20, 10, 5, 4, 2, 1) if n % c == 0)
    rows = n // chunks

    @jax.jit
    def make(cen):
        def body(i, buf):
            ky, kn = jax.random.split(jax.random.fold_in(kd, i))
            y = jax.random.randint(ky, (rows,), 0, k)
            blk = cen[y] + jax.random.normal(kn, (rows, d), jnp.float32)
            return jax.lax.dynamic_update_slice(buf, blk, (i * rows, 0))

        return jax.lax.fori_loop(0, chunks, body, jnp.zeros((n, d), jnp.float32))

    return make(centers), centers


def kmeans(rec: dict) -> None:
    (n, d), k, iters = SZ["km"], SZ["km_k"], 5
    xj, centers = blobs(n, d, k, key(40))
    init = centers + 0.5 * jax.random.normal(key(41), (k, d), jnp.float32)
    x = ht.array(xj, split=0)
    from heat_tpu.cluster._pallas import lloyd_pass_serves

    fused = lloyd_pass_serves(jax.default_backend(), "float32", (n, d), k, x.split, x.comm.size)
    rec.update(path="ht.cluster.KMeans.fit -> one program (Lloyd while_loop + label pass), the step "
               + ("the Pallas pass over f32 X" if fused else "XLA's two streams"),
               rows=n, rows_north_star=REAL["km"][0], features=d)

    def fit():
        km = ht.cluster.KMeans(n_clusters=k, init=ht.array(init), max_iter=iters, tol=0.0).fit(x)
        return km.cluster_centers_.larray, km.n_iter_

    (got, n_iter), first, warm = timed(fit)
    rec.update(times(first, warm), n_iter=int(n_iter))
    # the fit stops early only at an exact fixed point (tol=0), where the
    # remaining reference steps change nothing either
    need(1 <= rec["n_iter"] <= iters, f"KMeans ran {rec['n_iter']} iterations, max_iter {iters}")

    @jax.jit
    def lloyd(xs, c):
        for _ in range(iters):
            d2 = (
                jnp.sum(xs * xs, axis=1, keepdims=True)
                - 2.0 * jnp.matmul(xs, c.T, precision=HI)
                + jnp.sum(c * c, axis=1)[None, :]
            )
            onehot = jax.nn.one_hot(jnp.argmin(d2, axis=1), k, dtype=jnp.float32)
            counts = jnp.sum(onehot, axis=0)[:, None]
            c = jnp.where(counts > 0, jnp.matmul(onehot.T, xs, precision=HI) / jnp.maximum(counts, 1.0), c)
        return c

    ref = lloyd(xj, init)
    # the fit's one-hot matmul feeds the MXU bf16 inputs: 2^-8 relative on
    # every element, which a cluster mean need not average out
    scale = float(jnp.max(jnp.abs(ref)))
    rec.update(max_err=float(jnp.max(jnp.abs(got - ref))), tol=scale * 2.0 ** -8, max_abs_center=scale)
    check("KMeans centers", rec["max_err"], rec["tol"])


def _jsonable(decisions: dict) -> dict:
    return {" ".join(map(str, k)): v for k, v in decisions.items()}


def sort(rec: dict) -> None:
    n = SZ["sort_n"]
    rec.update(n=n, max_err=0.0, tol=0.0)
    xj = jax.random.normal(key(50), (n,), jnp.float32)
    x = ht.array(xj, split=0)
    (vals, idx), first, warm = timed(lambda: ht.sort(x))
    decisions = _jsonable(ht.kernels.sort.last_decisions())
    path = next((d["path"] for d in decisions.values() if d.get("autotuned")), "lax (auto off-TPU)")
    rec.update(times(first, warm), decisions=decisions, path=f"{path} at n={n}")
    # one reference program (each XLA sort of this size compiles for ~1
    # min): the stable two-operand lax.sort, which is jnp.sort and
    # jnp.argsort in one
    ref_v, ref_i = jax.lax.sort((xj, jnp.arange(n, dtype=jnp.int32)), num_keys=1, is_stable=True)
    need(bool(jnp.all(ref_v[1:] >= ref_v[:-1])), "reference sort is not sorted")
    need(bool(jnp.array_equal(vals.larray, ref_v)), "ht.sort values differ from the reference sort")
    need(bool(jnp.array_equal(idx.larray, ref_i)), "ht.sort indices differ from the reference stable argsort")
    if ON_CHIP:
        need(any(d.get("autotuned") for d in decisions.values()),
             f"no autotuned sort decision recorded on the chip: {decisions}")
    # the Pallas radix block kernel serves <= 512-element blocks under the
    # forced gate only: run it compiled, against the same reference
    os.environ["HEAT_TPU_SORT_KERNEL"] = "1"
    try:
        blk = ht.array(xj[:SORT_BLOCK])
        bv, bi = ht.sort(blk)
        rec["block_kernel_in_hlo"] = has_kernel(lambda t: ht.sort(t), blk)
    finally:
        del os.environ["HEAT_TPU_SORT_KERNEL"]
    ran = "compiled" if rec["block_kernel_in_hlo"] else "interpreted"
    rec["path"] += f"; pallas radix block kernel {ran} at n={SORT_BLOCK} (forced gate)"
    rec["block_kernel_wrong_values"] = int(jnp.sum(bv.larray != jnp.sort(xj[:SORT_BLOCK])))
    rec["block_kernel_wrong_indices"] = int(jnp.sum(bi.larray != jnp.argsort(xj[:SORT_BLOCK], stable=True)))
    need(rec["block_kernel_wrong_values"] == 0 and rec["block_kernel_wrong_indices"] == 0,
         f"block kernel: {rec['block_kernel_wrong_values']} values and "
         f"{rec['block_kernel_wrong_indices']} indices of {SORT_BLOCK} differ from jnp.sort/argsort")
    need(rec["block_kernel_in_hlo"] or not ON_CHIP,
         "forced sort block kernel has no tpu_custom_call: it ran interpreted")


def train_data(batch: int, kk):
    kc, ky, kn = jax.random.split(kk, 3)
    cen = jax.random.normal(kc, (MLP[2], MLP[0]), jnp.float32)
    y = jax.random.randint(ky, (batch,), 0, MLP[2]).astype(jnp.int32)
    return cen[y] + 0.5 * jax.random.normal(kn, (batch, MLP[0]), jnp.float32), y


def plain_sgd(params, xj, yj, steps: int, lr: float):
    """The same steps in plain jax: softmax cross entropy, SGD."""

    def loss_fn(p):
        h = jnp.maximum(xj @ p[0]["weight"] + p[0]["bias"], 0.0)
        logits = h @ p[2]["weight"] + p[2]["bias"]
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, yj[:, None], axis=1))

    @jax.jit
    def step(p):
        loss, g = jax.value_and_grad(loss_fn)(p)
        return jax.tree.map(lambda w, gw: w - lr * gw, p, g), loss

    losses = []
    for _ in range(steps):
        params, loss = step(params)
        losses.append(float(loss))
    return params, losses


def mlp(comm=None):
    model = ht.nn.Sequential(ht.nn.Linear(MLP[0], MLP[1]), ht.nn.ReLU(), ht.nn.Linear(MLP[1], MLP[2]))
    dp = ht.nn.DataParallel(model, comm=comm)
    return dp, ht.optim.DataParallelOptimizer(ht.optim.SGD(lr=0.05), dp)


def train(rec: dict) -> None:
    batch, steps = SZ["mlp_batch"], SZ["mlp_steps"]
    rec.update(path="ht.nn.Sequential + DataParallelOptimizer(SGD): one fused XLA step",
               batch=batch, widths=list(MLP), tol=1e-3)
    xj, yj = train_data(batch, key(60))
    x, y = ht.array(xj, split=0), ht.array(yj, split=0)
    dp, opt = mlp()
    params0 = jax.tree.map(jnp.copy, dp.state_dict())  # the step donates its params
    t0 = time.perf_counter()
    losses = [float(opt.step(x, y))]
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    losses += [float(opt.step(x, y)) for _ in range(steps - 1)]
    warm = (time.perf_counter() - t0) / (steps - 1)
    _, ref = plain_sgd(params0, xj, yj, steps, 0.05)
    rec.update(times(first, warm), losses=[round(l, 5) for l in losses],
               max_err=max(abs(a - b) / abs(b) for a, b in zip(losses, ref)))
    need(all(l == l and abs(l) < 1e6 for l in losses), f"losses not finite: {losses}")
    need(losses[-1] < losses[0], f"loss did not fall: {losses}")
    check("loss vs plain jax", rec["max_err"], rec["tol"])


def attention(rec: dict) -> None:
    b, h, s, d = SZ["attn"]
    # bf16 storage and bf16 kernel matmuls on outputs of magnitude ~1
    rec.update(shape=[b, h, s, d], dtype="bfloat16", causal=True, tol=5e-2)
    qj, kj, vj = (jax.random.normal(key(70 + i), (b, h, s, d), jnp.float32).astype(jnp.bfloat16) for i in range(3))
    q, k, v = ht.array(qj), ht.array(kj), ht.array(vj)
    out, first, warm = timed(lambda: ht.nn.ring_attention(q, k, v, causal=True))
    dec = ht.nn.attention.last_decisions()[("single", (b, h, s, d), (b, h, s, d), "bfloat16", True)]
    rec.update(times(first, warm), path=f"{dec['path']} ({dec['why']})")
    need(out.larray.shape == (b, h, s, d) and out.larray.dtype == jnp.bfloat16,
         f"attention output {out.larray.shape} {out.larray.dtype}")
    need(dec["path"] in ("splash", "flash") or not ON_CHIP,
         f"attention was served by {dec['path']!r} ({dec['why']}), not a compiled kernel")

    @jax.jit
    def one_head(qh, kh, vh):  # plain softmax attention in f32: (s, s) scores
        sc = jnp.matmul(qh.astype(jnp.float32), kh.astype(jnp.float32).T, precision=HI) / jnp.sqrt(jnp.float32(d))
        sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
        return jnp.matmul(jax.nn.softmax(sc, axis=-1), vh.astype(jnp.float32), precision=HI)

    rec["max_err"] = 0.0
    for bi in range(b):
        for hi in range(h):
            ref = one_head(qj[bi, hi], kj[bi, hi], vj[bi, hi])
            err = float(jnp.max(jnp.abs(out.larray[bi, hi].astype(jnp.float32) - ref)))
            rec["max_err"] = max(rec["max_err"], err)
    check("attention vs f32 softmax", rec["max_err"], rec["tol"])


def native_complex(rec: dict) -> None:
    """Does the installed runtime execute native complex64? Reported, not
    asserted, and run last: heat_tpu's default on TPU stays planar."""
    n = SZ["cplx_n"]
    rec["path"] = "jnp.matmul on complex64 (plain jax, not through ht)"
    try:
        z = jax.random.normal(key(80), (n, n, 2), jnp.float32)
        c = jax.lax.complex(z[..., 0], z[..., 1])
        got = jnp.matmul(c, c, precision=HI)
        re = jnp.matmul(z[..., 0], z[..., 0], precision=HI) - jnp.matmul(z[..., 1], z[..., 1], precision=HI)
        rec.update(native_complex64_ran=True,
                   detail=f"complex64 {n}x{n} matmul ran; real part vs planes: {rel(jnp.real(got), re):.2e}")
    except Exception as e:  # a refusal is the finding, not a failure
        rec.update(native_complex64_ran=False, detail=f"{type(e).__name__}: {e}"[:500])


# --------------------------------------------------------------------- #
# four chips: the split-array path against the one-device communicator   #
# --------------------------------------------------------------------- #
def census(fn, *args) -> dict:
    rep = ht.observability.collective_counts(fn, *args)
    return {k: v for k, v in rep.counts.items() if v}


def placement(a) -> dict:
    """The shards sit on distinct devices with the ``comm.chunk`` geometry."""
    comm = a.comm
    shards = a._phys.addressable_shards
    devs = {s.device for s in shards}
    need(len(devs) == comm.size == ARGS.chips, f"{len(shards)} shards on {len(devs)} devices, comm.size {comm.size}")
    order = comm.mesh.devices.ravel().tolist()
    rows = []
    for s in shards:
        offset, lshape, _ = comm.chunk(a.gshape, a.split, rank=order.index(s.device))
        need((s.index[a.split].start or 0) == offset and s.data.shape[a.split] >= lshape[a.split] > 0,
             f"device {s.device} holds {s.index} {s.data.shape}, comm.chunk says offset {offset} {lshape}")
        rows.append({"device": str(s.device), "shard": list(s.data.shape), "chunk_offset": offset,
                     "chunk": list(lshape)})
    return {"distinct_devices": len(devs), "shards": rows}


def onto(x1, x4):
    """A one-device result moved, device to device, next to its mesh twin."""
    return jax.device_put(x1, x4.sharding)


def four_chips() -> None:
    world, one = ht.MPI_WORLD, ht.MPI_SELF
    need(world.size == ARGS.chips and one.size == 1, f"world {world.size}, self {one.size}")
    (m, n), rank = SZ["hsvd"], SZ["rank"]

    def mesh_hsvd(rec: dict) -> None:
        # weak scaling: with p times the rows the per-entry signal stays what
        # one chip's block has alone only if sigma_max grows with sqrt(p)
        aj = low_rank(m, n, rank, key(30), sigma_max=100.0 * ARGS.chips ** 0.5)
        a4, a1 = ht.array(aj, split=0), ht.array(aj, split=0, comm=one)
        del aj
        rec.update(tol=1e-2, placement=placement(a4))

        def call(a_):
            return ht.linalg.hsvd_rank(a_, rank, compute_sv=True)

        (u4, s4, v4, e4), first, warm = timed(lambda: call(a4))
        u1, s1, v1, e1 = call(a1)
        # same left subspace: the singular values of U4^T U1 are all 1
        u4j = u4.larray
        cos = jnp.linalg.svd(jnp.matmul(u4j.T, onto(u1.larray, u4j), precision=HI), compute_uv=False)
        # the hierarchical estimate bounds the error of ITS factorization
        # (not the one-device one's): measure that residual on the mesh
        a4j = a4.larray
        resid = residual_sq(a4j, u4j, s4.larray, v4.larray)
        kern = has_kernel(call, a4)
        eye = jnp.eye(rank, dtype=jnp.float32)
        v4j = v4.larray
        rec.update(
            times(first, warm), collectives=census(call, a4),
            path="one shard_map program: level-0 sketch " + ("pallas (compiled)" if kern else "XLA tiles")
            + " on each chip's rows, gathered merge, U from the local factors",
            orth_err=max(float(jnp.max(jnp.abs(jnp.matmul(u4j.T, u4j, precision=HI) - eye))),
                         float(jnp.max(jnp.abs(jnp.matmul(v4j.T, v4j, precision=HI) - eye)))),
            sigma_err=rel(s4.larray, onto(s1.larray, s4.larray)), subspace_err=float(1.0 - jnp.min(cos)),
            rel_err_estimate=float(e4), rel_err_measured=float(jnp.sqrt(resid / jnp.sum(jnp.square(a4j)))),
            rel_err_estimate_one_device=float(e1),
        )
        rec["max_err"] = max(rec["sigma_err"], rec["subspace_err"])
        # check_svd's limit: agreeing with one chip in sigma and subspace says
        # nothing of the factors' own orthonormality (3e-3 passed PR 22 so)
        check("4 chips U^T U, V^T V", rec["orth_err"], 1e-3)
        check("sigma 4 chips vs 1", rec["sigma_err"], 1e-2)
        check("subspace 4 chips vs 1", rec["subspace_err"], 1e-3)
        need(0.95 * rec["rel_err_measured"] <= rec["rel_err_estimate"] <= 2.0 * rec["rel_err_measured"],
             f"error estimate {rec['rel_err_estimate']:.4e} does not bound the measured residual "
             f"{rec['rel_err_measured']:.4e}")
        need(rec["rel_err_measured"] <= 2.0 * rec["rel_err_estimate_one_device"],
             f"4-chip residual {rec['rel_err_measured']:.4e} against the one-device estimate "
             f"{rec['rel_err_estimate_one_device']:.4e}")
        need(kern or not ON_CHIP, "no tpu_custom_call in the 4-chip hsvd_rank program")

    def mesh_resplit(rec: dict) -> None:
        aj = jax.random.normal(key(31), (m, n), jnp.float32)
        a4 = ht.array(aj, split=0)
        scale = float(jnp.max(jnp.abs(aj)))

        def call(a_):
            return a_.resplit(1).resplit(0)

        # as a user calls it: on the TPU backend the planner's default
        # (HEAT_TPU_WIRE_QUANT=auto) ships large f32 exchanges through
        # the int8 wire codec, at the tolerance its plan states per
        # crossing; elsewhere, and on one device, the wire is exact
        plan = ht.redistribution.explain(a4, 1)
        b4, first, warm = timed(lambda: call(a4))
        mid = a4.resplit(1)
        crossing = plan.quant["tol"] * scale if plan.quant else 0.0
        rec.update(times(first, warm), path=f"redistribution planner: resplit 0->1->0, {plan.strategy}",
                   wire_codec=plan.quant, collectives=census(call, a4), placement_split1=placement(mid),
                   err_split1=float(jnp.max(jnp.abs(mid.larray - aj))),
                   max_err=float(jnp.max(jnp.abs(b4.larray - aj))), tol=2 * crossing)
        need(mid.split == 1 and b4.split == 0, f"splits {mid.split} {b4.split}")
        # and with the codec off: the bit-exact identity, everywhere
        os.environ["HEAT_TPU_WIRE_QUANT"] = "0"
        try:
            exact_mid = a4.resplit(1)
            exact = exact_mid.resplit(0)
        finally:
            del os.environ["HEAT_TPU_WIRE_QUANT"]
        rec["exact_wire_identity"] = bool(jnp.array_equal(exact_mid.larray, aj) and jnp.array_equal(exact.larray, aj))
        check("resplit(1) under the default gate", rec["err_split1"], crossing)
        check("resplit 0->1->0 under the default gate", rec["max_err"], rec["tol"])
        need(rec["exact_wire_identity"], "HEAT_TPU_WIRE_QUANT=0: resplit 0->1->0 is not the bit-exact identity")

    def mesh_sort(rec: dict) -> None:
        n_sort = min(SZ["sort_n"], MESH_SORT_N)
        xj = jax.random.normal(key(50), (n_sort,), jnp.float32)
        x4, x1 = ht.array(xj, split=0), ht.array(xj, split=0, comm=one)
        (v4, i4), first, warm = timed(lambda: ht.sort(x4))
        v1, i1 = ht.sort(x1)
        rec.update(times(first, warm), path="distributed sort network over the mesh", n=n_sort,
                   collectives=census(lambda t: ht.sort(t), x4),
                   decisions=_jsonable(ht.kernels.sort.last_decisions()), max_err=0.0, tol=0.0)
        need(v4.split == 0, f"sorted values split {v4.split}")
        need(bool(jnp.array_equal(v4.larray, onto(v1.larray, v4.larray))),
             "distributed sort values differ from one device")
        need(bool(jnp.array_equal(i4.larray, onto(i1.larray, i4.larray))),
             "distributed sort indices differ from one device")

    def mesh_matmul(rec: dict) -> None:
        k = SZ["mm_n"]
        aj = jax.random.normal(key(10), (k, k), jnp.float32)
        bj = jax.random.normal(key(20), (k, k), jnp.float32)
        c4, first, warm = timed(lambda: ht.matmul(ht.array(aj, split=0), ht.array(bj, split=1)))
        c1 = ht.matmul(ht.array(aj, split=0, comm=one), ht.array(bj, split=1, comm=one))
        rec.update(times(first, warm), path="split=0 @ split=1", n=k, result_split=c4.split,
                   collectives=census(ht.matmul, ht.array(aj, split=0), ht.array(bj, split=1)),
                   max_err=rel(c4.larray, onto(c1.larray, c4.larray)), tol=1e-3)
        check("matmul 4 chips vs 1", rec["max_err"], rec["tol"])

    def mesh_train(rec: dict) -> None:
        xj, yj = train_data(SZ["mlp_batch"], key(60))
        x4, y4 = ht.array(xj, split=0), ht.array(yj, split=0)
        x1, y1 = ht.array(xj, split=0, comm=one), ht.array(yj, split=0, comm=one)
        (dp4, opt4), (dp1, opt1) = mlp(world), mlp(one)
        t0 = time.perf_counter()
        l4 = float(opt4.step(x4, y4))
        first = time.perf_counter() - t0
        l1 = float(opt1.step(x1, y1))
        _, probe = mlp(world)  # a throwaway optimizer: tracing a step leaves tracers in it
        rec.update(path="DataParallelOptimizer step, batch split over the mesh", loss=l4, loss_one_device=l1,
                   first_s=round(first, 3), collectives=census(lambda a, b: probe.step(a, b), x4, y4),
                   max_err=abs(l4 - l1) / abs(l1), tol=1e-4)
        for w4, w1 in zip(jax.tree.leaves(dp4.state_dict()), jax.tree.leaves(dp1.state_dict())):
            need(len(w4.sharding.device_set) == ARGS.chips, "parameters are not replicated over the mesh")
            rec["max_err"] = max(rec["max_err"], rel(w4, onto(w1, w4)))
        check("loss and updated parameters, 4 chips vs 1", rec["max_err"], rec["tol"])

    for name, fn in (("mesh.hsvd", mesh_hsvd), ("mesh.resplit", mesh_resplit), ("mesh.sort", mesh_sort),
                     ("mesh.matmul", mesh_matmul), ("mesh.train_step", mesh_train)):
        phase(name, fn)


def main(argv=None) -> int:
    setup(argv)
    print(json.dumps({
        "phase": "start", "rehearse": ARGS.rehearse, "chips": ARGS.chips, "seed": ARGS.seed,
        "jax": jax.__version__, "platform": PLATFORM, "kind": DEVICES[0].device_kind,
        "compile_cache": CACHE_DIR,
    }), flush=True)
    if ARGS.chips == 4:
        four_chips()
    else:
        for name, fn in (("dispatch", dispatch), ("matmul", matmul), ("hsvd", hsvd), ("qr", qr), ("kmeans", kmeans),
                         ("sort", sort), ("train_step", train), ("attention", attention),
                         ("dispatch.native_complex64", native_complex)):
            phase(name, fn)
    if FAILED:
        print(f"chip_smoke: FAILED phases: {', '.join(FAILED)}", file=sys.stderr)
        return 1
    print(json.dumps({
        "ok": True,
        "device": {"platform": PLATFORM, "kind": DEVICES[0].device_kind, "count": len(DEVICES)},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
