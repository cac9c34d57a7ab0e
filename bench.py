"""Benchmark harness — the TPU analog of the reference's continuous
benchmarks (/root/reference/benchmarks/cb/{linalg,cluster,manipulations}.py).

Runs the cb workload set on the default JAX platform (the real TPU chip
under the driver) and prints ONE JSON line::

    {"metric": "...", "value": N, "unit": "...", "vs_baseline": N, ...}

Headline metric: ``hsvd_rank`` GB/s/chip (BASELINE.json north star).

Two kinds of rows in ``detail``:

* **cb-parity rows** (matmul n=3000, qr n=2000, …) replicate the
  reference's continuous-benchmark configurations and carry
  ``speedup_vs_torch_cpu`` against the reference's compute engine:
  single-process reference Heat short-circuits all MPI paths and runs
  plain torch CPU kernels (torch.linalg.svd IS
  ``compute_local_truncated_svd``, reference svdtools.py:477); mpi4py is
  absent in this image so torch-CPU is the closest faithful stand-in.
  The container exposes ONE CPU core (`nproc` = 1), so the torch
  baseline is single-threaded — that is the container's honest
  capability, not a handicap, but it means these ratios measure
  chip-vs-one-core and cannot carry a "matching-or-beating" claim alone.

* **chip rows** (``*_8k``, ``*_16k``, ``*_1gb``, ``hsvd_2gb``) are sized
  to saturate the v5e and carry absolute-utilization fields instead:
  ``mfu`` (fraction of the 197 TFLOP/s bf16 MXU peak) for compute-bound
  rows and ``hbm_frac`` (fraction of the 819 GB/s HBM stream peak) for
  memory-bound rows. These carry the performance argument.

Measurement methodology. Times are host-clock readings around work that
ends in ``jax.block_until_ready``; a single reading carries dispatch and
host-scheduling noise of the order of the shorter rows' device time, so
every measurement below either chains a data dependency through all
iterations or loops INSIDE one compiled program, and reports a slope.
(A floor constant measured at startup and subtracted fabricates per-op
times — round-3 incident: a 6 ms matmul "measured" past the chip's
roofline at 154% MFU. Independent repeated calls whose outputs are
never read measured NEGATIVE marginal cost per op.)

Methods:

* ``loop-program``: the op body runs k iterations inside one jitted
  ``lax.fori_loop`` with a loop-carried dependency — one dispatch, k
  serial device executions. Per-op time is the slope between a short and
  a long loop, cancelling sync latency, dispatch cost, and cache-lookup
  constants. Purest device rate; used for the chip rows AND (via the
  ht.jit tracing machinery, ``_traced_loop_factory``) for every row
  whose device time sits below the host-clock noise — the
  composite fits, lanczos, the scalers, and the 128 MB hsvd row. Loop
  bodies digest ALL outputs (a single-element digest lets XLA
  dead-code-eliminate the rest), and chip rows re-measure when a slope
  lands above the row's physical roofline (``_measure_bounded``).
* ``chained-slope``: public API calls with each call consuming the
  previous call's output (dispatch cost included — that is what a user
  pays), timed as the same two-point slope, median over reps. Used for
  the cb rows big enough to carry it; the op_chain rows carry the
  dispatch-cost story centrally.
"""

from __future__ import annotations

import functools
import json
import os
import statistics
import sys
import time

BASELINE_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "BENCH_BASELINE.json")

# --------------------------------------------------------------------- #
# v5e single-chip peaks (per-chip accounting for mfu / hbm_frac)        #
# --------------------------------------------------------------------- #
V5E_BF16_FLOPS = 197e12   # MXU peak, bf16 multiply / f32 accumulate
# ceiling for f32 matmul at DEFAULT precision (bf16 MXU passes + the f32
# accumulate overhead): consistently measured ~0.78-0.81 of the bf16
# peak; 165 TF/s is safely above every plausible f32 rate, so a sample
# past it is noise, not the chip
V5E_F32_DEFAULT_FLOPS = 165e12
V5E_HBM_BPS = 819e9       # HBM stream peak

# cb-parity workload sizes (reference cb configurations)
N_MATMUL = 3000          # benchmarks/cb/linalg.py:45
N_QR = 2000              # benchmarks/cb/linalg.py:55
HSVD_M, HSVD_N, HSVD_R = 16384, 2048, 10   # torch-comparable baseline workload
KM_N, KM_D, KM_K = 1_048_576, 64, 8        # KMeans iter/s at scale
RESHAPE_SHAPE = (1000, 250_000)            # cb uses 1000x10M..40M on a cluster
# lane-friendly reshape companion (ISSUE 5): 1.07 GB with minor dims
# >= 128 END TO END (512-/256-lane shards over p=8), so no pivot stage
# pays lane amplification — the row that shows what the repartition
# machinery does when layout is not the bottleneck
LANE_SHAPE = (65536, 4096)
LANE_OUT = (131072, 2048)
CONCAT_SIZES = (10_000, 20_000, 40_000)    # benchmarks/cb/manipulations.py:20
SUM_N = 100_000_000
SORT_N = 16_777_216                        # distributed sort (values+indices)
RA_B, RA_H, RA_S, RA_D = 4, 8, 4096, 64    # cb-scale ring attention workload

# chip-saturating workload sizes
MM_8K = 8192                                   # bf16 matmul at MXU-saturating size
HSVD_BIG_M, HSVD_BIG_N = 65536, 8192           # 2.1 GB — the north-star per-chip
                                               # shard (200 GB over v5e-64 ~ 3 GB)
RAB_B, RAB_H, RAB_S, RAB_D = 1, 8, 16384, 128  # long-context attention, 16k tokens
SUM_BIG_N = 268_435_456                        # 1.07 GB reduction
SORT_BIG_N = 134_217_728                       # 0.54 GB sort (values + argsort)
CHAIN_N = 67_108_865                           # 256 MB/pass; odd length exercises
                                               # the pad-inside-jit path
KM_BIG_N = 15_625_000                          # KMeans north-star per-chip shard:
                                               # 1B x 64 over v5e-64 = 15.625M rows
                                               # (~4 GB f32) per chip (BASELINE #4)
SPMM_N = 16384                                 # spmm_1gb: 1 GB dense-EQUIVALENT
                                               # operand (16384^2 f32); the brick
                                               # engine stores/streams 67 MB of it
SPMM_OCC = 0.0625                              # brick-grid fill: 16384 full (8,128)
                                               # bricks -> 16.7M nnz
SPMM_K = 4                                     # slim dense operand (embedding-ish)
PR_N, PR_DEG = 8192, 256                       # pagerank_2m: ~2M edges after
                                               # self-loop drop + dedup
PR_TOL = 1e-8


def _best_of(fn, reps: int = 3) -> float:
    fn()  # warmup / compile
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _chained_slope_group(members, sync, k1, k2, reps=5):
    """Two-point slope timing for a GROUP of directly-compared chained
    workloads, interleaved within the same rep loop so every member sees
    the same host-clock noise.

    ``members``: {name: (init_state, step)} where ``step(state) -> state``
    must consume its input (the data dependency defeats
    dead-compute elimination and forces serial execution). Per-op time is
    ``(T(k2) - T(k1)) / (k2 - k1)`` — the sync read-back, dispatch-queue
    constants and anything else independent of iteration count cancels.
    Median over reps rejects outliers.
    """
    for name, (init, step) in members.items():
        sync(step(init))  # warmup / compile
    ests = {k: [] for k in members}
    for _ in range(reps):
        for name, (init, step) in members.items():
            y = init
            t0 = time.perf_counter()
            for _ in range(k1):
                y = step(y)
            sync(y)
            t1 = time.perf_counter()
            y = init
            for _ in range(k2):
                y = step(y)
            sync(y)
            t2 = time.perf_counter()
            ests[name].append(((t2 - t1) - (t1 - t0)) / (k2 - k1))
    return {k: max(statistics.median(v), 1e-9) for k, v in ests.items()}


def _chained_slope(init, step, sync, k1, k2, reps=5) -> float:
    return _chained_slope_group({"x": (init, step)}, sync, k1, k2, reps)["x"]


def _loop_program_time(make_looped, args, sync, k1, k2, reps=7) -> float:
    """Per-iteration device time of a loop-carried body compiled as ONE
    program per loop length: slope between the k1- and k2-iteration
    executables. ``make_looped(k) -> jitted fn(*args)``."""
    f1, f2 = make_looped(k1), make_looped(k2)
    sync(f1(*args))
    sync(f2(*args))
    est = []
    for _ in range(reps):
        t0 = time.perf_counter()
        sync(f1(*args))
        t1 = time.perf_counter()
        sync(f2(*args))
        t2 = time.perf_counter()
        est.append(((t2 - t1) - (t1 - t0)) / (k2 - k1))
    return max(statistics.median(est), 1e-9)


def _loop_program_group(members, sync, k1, k2, reps=7):
    """``_loop_program_time`` for a GROUP of directly-compared
    loop-carried bodies, interleaved within the same rep loop so every
    member sees the same host-clock noise (ISSUE 5: ``vs_splash_row``
    must be computed from same-run samples — two independently-measured
    rows can drift ±20% apart on noise alone and fabricate a ratio).

    ``members``: {name: (make_looped, args)} with ``make_looped(k) ->
    jitted fn(*args)`` exactly as for ``_loop_program_time``."""
    fns = {name: (make(k1), make(k2)) for name, (make, _args) in members.items()}
    for name, (_make, args) in members.items():
        f1, f2 = fns[name]
        sync(f1(*args))  # compile + warm both loop lengths
        sync(f2(*args))
    ests = {name: [] for name in members}
    for _ in range(reps):
        for name, (_make, args) in members.items():
            f1, f2 = fns[name]
            t0 = time.perf_counter()
            sync(f1(*args))
            t1 = time.perf_counter()
            sync(f2(*args))
            t2 = time.perf_counter()
            ests[name].append(((t2 - t1) - (t1 - t0)) / (k2 - k1))
    return {k: max(statistics.median(v), 1e-9) for k, v in ests.items()}


def _measure_bounded(thunk, floor_seconds, retries=2):
    """Run a loop-program measurement with a PHYSICAL floor: a slope
    below ``floor_seconds`` (the roofline time — bytes/peak or
    flops/peak) is an under-measurement fabricated by host-clock noise
    (observed: an "1.8x of HBM peak" hsvd sample), never the chip.
    Re-measure up to ``retries`` times and keep the slowest estimate —
    over-measurement only under-reports, which is the safe direction."""
    t = thunk()
    for _ in range(retries):
        if t >= floor_seconds:
            break
        t = max(t, thunk())
    return t


def _measure_bounded_group(thunk, floors, retries=2):
    """The floor/retry machinery of ``_measure_bounded`` for a GROUP
    measurement (``thunk() -> {name: seconds}``, e.g. a
    ``_chained_slope_group``): while any member sits under its physical
    floor in ``floors``, re-measure the whole group (members must stay
    interleaved to see the same host-clock noise) and keep each member's
    slowest estimate — the safe, under-reporting direction."""
    out = thunk()
    for _ in range(retries):
        if all(out[k] >= f for k, f in floors.items()):
            break
        nxt = thunk()
        out = {k: max(v, nxt[k]) for k, v in out.items()}
    return out


def _progress(name, seconds):
    print(f"[bench] {name}: {seconds*1e3:.3f} ms", file=sys.stderr, flush=True)


def _attribution_summary(att: dict) -> dict:
    """Compact per-row form of an attribution report: the modeled wall,
    the trace census, the per-leg joins, and the mean |model_error| over
    every priced leg — ``mean_abs_model_error`` is the regression-gated
    figure (scripts/bench_compare.py, lower-is-better): a planner or
    lattice change that degrades the cost model's fidelity is caught
    here before the TPU round. When a lattice profile was in reach the
    calibrated column's mean rides along (``mean_abs_calibrated_error``,
    same gate) — the ci.sh calibration leg proves it lands at or below
    the constants figure."""
    f = {
        "model_wall_s": att["model"]["wall_s"],
        "census": att["census"],
        "legs": att["legs"],
    }
    errs = [abs(l["model_error"]) for l in att["legs"] if "model_error" in l]
    if errs:
        f["mean_abs_model_error"] = round(sum(errs) / len(errs), 4)
    cal = [abs(l["calibrated_error"]) for l in att["legs"] if "calibrated_error" in l]
    if cal:
        f["mean_abs_calibrated_error"] = round(sum(cal) / len(cal), 4)
    return f


def _attach_attribution(row: dict, att: dict) -> None:
    """Hang an attribution detail on a bench row. The mean-error
    figures are hoisted to the row's top level because bench_compare
    only gates top-level numeric fields."""
    if not att:
        return
    row["attribution"] = att
    for k in ("mean_abs_model_error", "mean_abs_calibrated_error"):
        if k in att:
            row[k] = att[k]


def _eager_wallclock(fn, reps: int = 2) -> float:
    """One warmed EAGER wall-clock sample of a public call: dispatch,
    sync, and wrapper overhead included — what a user pays calling
    fit()/transform() once, next to the traced device-rate rows (ADVICE
    r4: the loop-program speedups are device-time numbers; this field
    keeps the single-call story honest in the same record)."""
    fn()  # warm: compile is a one-time cost, not part of either story
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


# --------------------------------------------------------------------- #
# torch-CPU baseline (reference compute engine, single process)         #
# --------------------------------------------------------------------- #
def measure_baseline() -> dict:
    import torch

    torch.manual_seed(0)
    out = {}

    a = torch.randn(N_MATMUL, N_MATMUL)
    b = torch.randn(N_MATMUL, N_MATMUL)
    out["matmul"] = _best_of(lambda: a @ b)
    del a, b

    c = torch.randn(N_QR, N_QR)
    out["qr"] = _best_of(lambda: torch.linalg.qr(c), reps=2)
    del c

    d = torch.randn(HSVD_M, HSVD_N)
    def _hsvd_ref():
        u, s, vt = torch.linalg.svd(d, full_matrices=False)
        return u[:, :HSVD_R], s[:HSVD_R]
    out["hsvd"] = _best_of(_hsvd_ref, reps=1)

    # the strongest torch counterpart for the same task: its own
    # randomized truncated SVD (the reference's hsvd_rank code path uses
    # the FULL torch.linalg.svd, svdtools.py:477 — both ratios reported)
    def _hsvd_lowrank():
        return torch.svd_lowrank(d, q=HSVD_R + 15, niter=1)
    out["hsvd_lowrank"] = _best_of(_hsvd_lowrank, reps=3)
    del d

    x = torch.randn(KM_N, KM_D)
    cent = x[:KM_K].clone()
    def _km_iter():
        d2 = torch.cdist(x, cent)
        labels = d2.argmin(dim=1)
        oh = torch.nn.functional.one_hot(labels, KM_K).to(x.dtype)
        sums = oh.T @ x
        counts = oh.sum(dim=0).clamp(min=1)
        return sums / counts[:, None]
    out["kmeans_iter"] = _best_of(_km_iter, reps=1)
    del x, cent

    r = torch.zeros(RESHAPE_SHAPE)
    out["reshape"] = _best_of(lambda: r.reshape(10_000_000, -1).contiguous(), reps=2)
    del r

    arrs = [torch.zeros(1000, s) for s in CONCAT_SIZES]
    out["concatenate"] = _best_of(lambda: torch.cat(arrs, dim=1), reps=2)
    del arrs

    s_in = torch.arange(SUM_N, dtype=torch.float32)
    out["sum"] = _best_of(lambda: s_in.sum())
    del s_in

    srt = torch.randn(SORT_N)
    out["sort"] = _best_of(lambda: torch.sort(srt), reps=2)
    del srt

    # ---- lanczos (reference cb: linalg.py:38-40 — n=50, f64, m=n) ---- #
    g = torch.Generator().manual_seed(7)
    A50 = torch.randn(50, 50, dtype=torch.float64, generator=g)
    B50 = A50 @ A50.T

    def _lanczos_ref():
        # the reference single-process path: m torch matvecs with full
        # Gram-Schmidt reorthogonalization (reference solver.py:245-255)
        n = B50.shape[0]
        m = n
        V = torch.zeros((n, m), dtype=B50.dtype)
        v = torch.randn(n, dtype=B50.dtype, generator=g)
        v = v / v.norm()
        V[:, 0] = v
        w = B50 @ v
        a = w @ v
        w = w - a * v
        alpha, beta = [a], [torch.zeros((), dtype=B50.dtype)]
        for i in range(1, m):
            b = w.norm()
            vi = w / b
            vi = vi - V[:, :i] @ (V[:, :i].T @ vi)
            vi = vi / vi.norm()
            V[:, i] = vi
            w = B50 @ vi
            a = w @ vi
            w = w - a * vi - b * V[:, i - 1]
            alpha.append(a)
            beta.append(b)
        T = torch.diag(torch.stack(alpha))
        off = torch.stack(beta[1:])
        return V, T + torch.diag(off, 1) + torch.diag(off, -1)

    out["lanczos_cb"] = _best_of(_lanczos_ref, reps=3)
    del A50, B50

    # ---- cluster fits (reference cb: cluster.py — 4x5000 spherical) ---- #
    def _spherical_torch(n=5000):
        gs = torch.Generator().manual_seed(1)
        parts = []
        for sign in (-2.0, -1.0, 1.0, 2.0):
            d = torch.randn(n, 3, generator=gs)
            d = d / d.norm(dim=1, keepdim=True).clamp_min(1e-30)
            u = torch.rand(n, 1, generator=gs)
            parts.append(d * u.pow(1.0 / 3.0) + sign * 4.0)
        return torch.cat(parts)

    sph = _spherical_torch()
    k_cl = 4

    def _kpp_seed(x, k, gen):
        n = x.shape[0]
        centers = [x[torch.randint(n, (1,), generator=gen)[0]]]
        d2 = ((x - centers[0]) ** 2).sum(1)
        for _ in range(k - 1):
            idx = torch.multinomial(d2 / d2.sum(), 1, generator=gen)[0]
            centers.append(x[idx])
            d2 = torch.minimum(d2, ((x - centers[-1]) ** 2).sum(1))
        return torch.stack(centers)

    def _kmeans_fit_ref():
        gen = torch.Generator().manual_seed(1)
        c = _kpp_seed(sph, k_cl, gen)
        for _ in range(300):
            lab = torch.cdist(sph, c).argmin(1)
            new = torch.stack(
                [sph[lab == i].mean(0) if (lab == i).any() else c[i] for i in range(k_cl)]
            )
            shift = ((new - c) ** 2).sum()
            c = new
            if shift <= 1e-4:
                break
        return c

    out["kmeans_fit_cb"] = _best_of(_kmeans_fit_ref, reps=3)

    def _kmedians_fit_ref():
        gen = torch.Generator().manual_seed(1)
        c = _kpp_seed(sph, k_cl, gen)
        for _ in range(300):
            lab = torch.cdist(sph, c, p=1).argmin(1)
            new = torch.stack(
                [sph[lab == i].median(0).values if (lab == i).any() else c[i] for i in range(k_cl)]
            )
            shift = ((new - c) ** 2).sum()
            c = new
            if shift <= 1e-4:
                break
        return c

    out["kmedians_fit_cb"] = _best_of(_kmedians_fit_ref, reps=3)

    def _kmedoids_fit_ref():
        gen = torch.Generator().manual_seed(1)
        c = _kpp_seed(sph, k_cl, gen)
        for _ in range(300):
            lab = torch.cdist(sph, c, p=1).argmin(1)
            new = []
            for i in range(k_cl):
                members = sph[lab == i]
                if members.shape[0] == 0:
                    new.append(c[i])
                    continue
                med = members.median(0).values
                new.append(members[(members - med).abs().sum(1).argmin()])
            new = torch.stack(new)
            if (new == c).all():
                break
            c = new
        return c

    out["kmedoids_fit_cb"] = _best_of(_kmedoids_fit_ref, reps=3)
    del sph

    # ---- preprocessing scalers (reference cb: preprocessing.py — 5000x50,
    # fit + transform + inverse, in place) ---- #
    Xp = torch.randn(5000, 50, generator=g)

    def _std_scaler():
        m, s = Xp.mean(0), Xp.var(0).sqrt()
        s = torch.where(s > 0, s, torch.ones_like(s))
        y = (Xp - m) / s
        return y * s + m

    def _minmax_scaler():
        lo, hi = Xp.min(0).values, Xp.max(0).values
        rng = torch.where(hi - lo > 0, hi - lo, torch.ones_like(hi))
        scale = 1.0 / rng
        y = (Xp - lo) * scale
        return y / scale + lo

    def _maxabs_scaler():
        s = Xp.abs().max(0).values
        s = torch.where(s > 0, s, torch.ones_like(s))
        y = Xp / s
        return y * s

    def _robust_scaler():
        med = Xp.median(0).values
        q1 = torch.quantile(Xp, 0.25, dim=0)
        q3 = torch.quantile(Xp, 0.75, dim=0)
        iqr = torch.where(q3 - q1 > 0, q3 - q1, torch.ones_like(q3))
        y = (Xp - med) / iqr
        return y * iqr + med

    def _normalizer():
        n = Xp.norm(dim=1, keepdim=True).clamp_min(1e-30)
        return Xp / n

    out["scaler_standard"] = _best_of(_std_scaler, reps=3)
    out["scaler_minmax"] = _best_of(_minmax_scaler, reps=3)
    out["scaler_maxabs"] = _best_of(_maxabs_scaler, reps=3)
    out["scaler_robust"] = _best_of(_robust_scaler, reps=3)
    out["normalizer_l2"] = _best_of(_normalizer, reps=3)
    del Xp

    out["_meta"] = {
        "engine": "torch-cpu",
        "torch": torch.__version__,
        "threads": torch.get_num_threads(),
        "cpus_visible": os.cpu_count(),
        "note": "reference Heat single-process == local torch kernels (mpi4py absent); "
        "the container exposes one CPU core, so this engine is honestly single-threaded",
    }
    return out


# --------------------------------------------------------------------- #
# heat_tpu measurements                                                 #
# --------------------------------------------------------------------- #
def measure_heat_tpu() -> dict:
    import jax
    import jax.numpy as jnp
    from jax import lax
    import numpy as np
    import heat_tpu as ht

    def sync(x):
        # the completion fence of every timed region
        if isinstance(x, tuple):
            x = x[0]
        jax.block_until_ready(x._phys if hasattr(x, "_phys") else x)

    out = {"_meta": {"platform": jax.devices()[0].platform,
                     "device": str(jax.devices()[0]),
                     "n_devices": len(jax.devices())}}
    method = {}
    eager = {}  # name -> single warmed eager wall-clock sample (s)

    ht.random.seed(0)

    probe = ht.zeros((4,))
    sync(probe)
    out["_meta"]["sync_floor_s"] = round(_best_of(lambda: sync(probe), reps=5), 6)

    # ------------------------------------------------------------------ #
    # cb-parity rows: chained public API calls (dispatch cost included)  #
    # ------------------------------------------------------------------ #
    # NOTE: f32 matmul uses JAX's DEFAULT precision on TPU = bf16 MXU
    # passes with f32 accumulation (the same trade as torch-CUDA's tf32
    # default), so f32≈bf16 seconds at this size is expected, not an
    # anomaly; ht.matmul(precision="highest") buys exact f32 at ~3x.
    # Chained matmuls overflow to inf after ~20 steps — TPU arithmetic on
    # inf/nan runs at identical speed (fixed-function MXU), so timing is
    # unaffected.
    a = ht.random.random((N_MATMUL, N_MATMUL), split=0)
    b = ht.random.random((N_MATMUL, N_MATMUL), split=0)
    b1 = b.resplit(1)
    abf = a.astype(ht.bfloat16); bbf = b.astype(ht.bfloat16)
    mm = _chained_slope_group(
        {
            "f32": (a, lambda y: ht.matmul(y, b)),
            "split1": (a.resplit(1), lambda y: ht.matmul(y, b1)),
            "bf16": (abf, lambda y: ht.matmul(y, bbf)),
        },
        sync, k1=8, k2=72, reps=5,
    )
    out["matmul"] = mm["f32"]
    _progress("matmul", out["matmul"])
    out["matmul_split1"] = mm["split1"]
    _progress("matmul_split1", out["matmul_split1"])
    out["matmul_bf16"] = mm["bf16"]
    _progress("matmul_bf16", out["matmul_bf16"])
    method["matmul"] = method["matmul_split1"] = method["matmul_bf16"] = "chained-slope"
    del a, b, b1, abf, bbf

    # QR of an orthonormal factor costs the same Householder sweep (the
    # algorithm is data-oblivious); chaining y <- q keeps the dependency
    c0 = ht.random.random((N_QR, N_QR), split=0)
    out["qr"] = _chained_slope(c0, lambda y: ht.linalg.qr(y)[0], sync, k1=4, k2=28)
    _progress("qr", out["qr"])
    method["qr"] = "chained-slope"
    del c0

    from heat_tpu.core.dndarray import DNDarray

    def _traced_loop_factory(step_of_dnd, meta):
        """make_looped(k) for _loop_program_time: iterate a traced
        public-API body (DNDarray in → derived scalar corner-write) k
        times inside one program. The body must DIGEST every output it
        cares about (jnp.sum over all result arrays) — a single-element
        digest lets XLA dead-code-eliminate the rest of the program."""
        @functools.lru_cache(maxsize=None)
        def make(k):
            def body(i, y):
                d = DNDarray(y, *meta)
                res = step_of_dnd(d)
                return y.at[(0,) * y.ndim].set(res * 1e-30)
            return jax.jit(lambda y: lax.fori_loop(0, k, body, y))
        return make

    # hsvd cb row feeds the headline vs_baseline: measured as a traced
    # loop-program (public hsvd_rank, full-output digest) — the chained
    # form of this 128 MB workload swung 0.013-0.072 s with host-clock
    # noise, swinging the headline ratio with it
    d = ht.random.random((HSVD_M, HSVD_N), split=0)

    def _hsvd_cb_res(dd):
        u, err = ht.linalg.hsvd_rank(dd, HSVD_R)
        return jnp.sum(u.larray) + err.larray

    # factory hoisted OUT of the retry thunk: a floor-violation retry must
    # reuse the lru-cached loop executables, not recompile them
    hsvd_looped = _traced_loop_factory(
        _hsvd_cb_res, (d.shape, d.dtype, d.split, d.device, d.comm)
    )
    out["hsvd"] = _measure_bounded(
        lambda: _loop_program_time(hsvd_looped, (d._phys,), sync, k1=4, k2=204),
        # bytes-based floor for the traced row (ADVICE r4): 2 passes over
        # the 128 MB operand at HBM peak is the physical minimum
        2 * HSVD_M * HSVD_N * 4 / V5E_HBM_BPS,
    )
    _progress("hsvd", out["hsvd"])
    method["hsvd"] = "loop-program (public hsvd_rank traced)"
    eager["hsvd"] = _eager_wallclock(lambda: sync(ht.linalg.hsvd_rank(d, HSVD_R)[0]))
    del d

    from heat_tpu.cluster.kmeans import _lloyd_step
    x = ht.random.randn(KM_N, KM_D, split=0)
    cent0 = x.larray[:KM_K]
    step = _lloyd_step(KM_K, tuple(x.larray.shape), np.dtype(x.larray.dtype).name)
    # Lloyd's iteration is naturally chained: centroids feed back
    out["kmeans_iter"] = _chained_slope(
        cent0, lambda c: step(x.larray, c)[0], sync, k1=8, k2=40
    )
    method["kmeans_iter"] = "chained-slope"
    del x, cent0

    # cb cluster config: FULL fits (++-seeding + convergence loop + label
    # assignment) on 4x5000 spherical samples. These workloads are
    # sub-MB: per-call dispatch and sync artifacts swamp the ~2 ms of
    # actual work, so the honest
    # number is a loop-program — the REAL public fit traced (the same
    # machinery as ht.jit: wrapper metadata runs at trace time, the math
    # stays on device) and iterated k times inside one compiled
    # fori_loop, chained through a corner write. Dispatch cost is
    # reported separately and centrally by the op_chain rows.
    from heat_tpu.utils.data.spherical import create_spherical_dataset
    data = create_spherical_dataset(num_samples_cluster=5000, radius=1.0, offset=4.0,
                                    dtype=ht.float32, random_state=1)
    fit_meta = (data.shape, data.dtype, data.split, data.device, data.comm)

    def _fit_res(cls, init):
        def run(d):
            km = cls(n_clusters=4, init=init, random_state=1)
            km.fit(d)
            # digest EVERYTHING the fit produces — consuming a single
            # element would let XLA dead-code-eliminate the rest of the
            # program (observed: a "0 us" fit row)
            return (
                jnp.sum(km._cluster_centers.larray)
                + jnp.sum(km._labels.larray).astype(jnp.float32)
                + jnp.asarray(km._inertia, jnp.float32)
            )
        return run

    def _fit_eager(cls, init):
        def run():
            km = cls(n_clusters=4, init=init, random_state=1)
            km.fit(data)
            sync(km._cluster_centers)
        return run

    fit_floor = 20_000 * 3 * 4 / V5E_HBM_BPS  # one pass over the samples
    for name, cls, init, kk2 in (
        # loop counts sized per row so the slope signal (k2*device_time)
        # clears the sync-floor noise: kmeans converges
        # in ~50 us/fit, the L1 fits in ~1.5 ms/fit
        ("kmeans_fit_cb", ht.cluster.KMeans, "kmeans++", 2008),
        ("kmedians_fit_cb", ht.cluster.KMedians, "kmedians++", 208),
        ("kmedoids_fit_cb", ht.cluster.KMedoids, "kmedoids++", 208),
    ):
        looped = _traced_loop_factory(_fit_res(cls, init), fit_meta)
        out[name] = _measure_bounded(
            lambda looped=looped, kk2=kk2: _loop_program_time(
                looped, (data._phys,), sync, k1=8, k2=kk2
            ),
            fit_floor,
        )
        _progress(name, out[name])
        method[name] = "loop-program (public fit traced: ++seeding + while_loop + labels)"
        eager[name] = _eager_wallclock(_fit_eager(cls, init))
    del data

    # lanczos (cb config: n=50, f64 — degrades to f32 on TPU per the
    # platform-conditional x64 policy; the baseline runs true f64).
    # Public path traced (v0 draw + m=50 scan + on-device T assembly).
    lz = ht.random.random((50, 50), dtype=ht.float64, split=0)
    lzb = ht.matmul(lz, ht.transpose(lz))
    fit_meta = (lzb.shape, lzb.dtype, lzb.split, lzb.device, lzb.comm)

    def _lanczos_res(d):
        V, T = ht.linalg.lanczos(d, 50)
        return (jnp.sum(V.larray) + jnp.sum(T.larray)).astype(d.larray.dtype)

    lanczos_looped = _traced_loop_factory(_lanczos_res, fit_meta)
    out["lanczos_cb"] = _measure_bounded(
        lambda: _loop_program_time(lanczos_looped, (lzb._phys,), sync, k1=8, k2=308),
        50 * 50 * 50 * 4 / V5E_HBM_BPS,  # m=50 matvec passes over B
    )
    _progress("lanczos_cb", out["lanczos_cb"])
    method["lanczos_cb"] = "loop-program (public lanczos traced; f64→f32 on TPU)"
    eager["lanczos_cb"] = _eager_wallclock(lambda: sync(ht.linalg.lanczos(lzb, 50)[0]))
    del lz, lzb

    # preprocessing scalers (cb config: 5000x50, fit+transform+inverse),
    # public classes traced the same way
    Xp = ht.random.randn(5000, 50, split=0)
    fit_meta = (Xp.shape, Xp.dtype, Xp.split, Xp.device, Xp.comm)

    def _scaler_res(make, inverse=True):
        def run(d):
            sc = make()
            y = sc.fit_transform(d)
            if inverse:
                y = sc.inverse_transform(y)
            return jnp.sum(y.larray)  # full-output digest (see _fit_res)
        return run

    # k2 per row: the microsecond-class scalers need ~65k in-program
    # iterations for the slope to clear the sync-floor noise;
    # the robust scaler (distributed percentiles, ~300 us/iter) would
    # burn minutes at that count and clears noise by ~2k
    def _scaler_eager(maker, inv):
        def run():
            sc = maker()
            y = sc.fit_transform(Xp)
            if inv:
                y = sc.inverse_transform(y)
            sync(y)
        return run

    scaler_floor = 5000 * 50 * 4 / V5E_HBM_BPS  # one pass over X (~1.2 us)
    for name, maker, inv, kk2 in (
        ("scaler_standard", lambda: ht.preprocessing.StandardScaler(copy=False), True, 65552),
        ("scaler_minmax", lambda: ht.preprocessing.MinMaxScaler(copy=False), True, 65552),
        ("scaler_maxabs", lambda: ht.preprocessing.MaxAbsScaler(copy=False), True, 65552),
        ("scaler_robust", lambda: ht.preprocessing.RobustScaler(copy=False), True, 2016),
        ("normalizer_l2", lambda: ht.preprocessing.Normalizer(copy=False), False, 65552),
    ):
        looped = _traced_loop_factory(_scaler_res(maker, inv), fit_meta)
        out[name] = _measure_bounded(
            lambda looped=looped, kk2=kk2: _loop_program_time(
                looped, (Xp._phys,), sync, k1=16, k2=kk2, reps=3
            ),
            scaler_floor,
        )
        _progress(name, out[name])
        method[name] = (
            "loop-program (public fit+transform+inverse traced)" if inv
            else "loop-program (public fit+transform traced)"
        )
        eager[name] = _eager_wallclock(_scaler_eager(maker, inv))
    del Xp

    # ------------------------------------------------------------------ #
    # redistribution-planner rows (ROADMAP `reshape` + ISSUE 6 overlap): #
    # the 1 GB planner-routed relayouts, measured as there-and-back      #
    # pairs (halved) with the bytes-based floor/retry machinery — a      #
    # slope under one read + one write of the per-chip shard at HBM peak #
    # is host-clock noise. Each row runs as ONE interleaved group with its #
    # sequential twin (HEAT_TPU_REDIST_OVERLAP=0 vs 1): the same-run     #
    # samples the PR-5 attention fix demands, so `vs_sequential` is a    #
    # real ratio, not two noisy draws. The headline row is the         #
    # overlap (shipped-default-on-TPU) member.                           #
    # ------------------------------------------------------------------ #
    redist_bytes = RESHAPE_SHAPE[0] * RESHAPE_SHAPE[1] * 4  # 1 GB operand
    redist_floor = 2 * redist_bytes / max(len(jax.devices()), 1) / V5E_HBM_BPS

    def _gated_step(step, mode):
        # execute() re-reads HEAT_TPU_REDIST_OVERLAP per call, and the
        # executor keys its programs on the resolved pipelined flag, so
        # per-step toggling dispatches the right cached program
        def run(y):
            os.environ["HEAT_TPU_REDIST_OVERLAP"] = mode
            return step(y)
        return run

    def _overlap_pair(row, init, step, floor):
        old = os.environ.get("HEAT_TPU_REDIST_OVERLAP")
        ratios = []  # seq/overlap per GROUP RUN: same-run samples only

        def thunk():
            res = {
                k: v / 2
                for k, v in _chained_slope_group(
                    {
                        row: (init, _gated_step(step, "1")),
                        f"{row}_seq": (init, _gated_step(step, "0")),
                    },
                    sync, k1=2, k2=10,
                ).items()
            }
            if res[row] > 1e-9:
                ratios.append(res[f"{row}_seq"] / res[row])
            return res

        try:
            pair = _measure_bounded_group(thunk, {row: floor, f"{row}_seq": floor})
        finally:
            if old is None:
                os.environ.pop("HEAT_TPU_REDIST_OVERLAP", None)
            else:
                os.environ["HEAT_TPU_REDIST_OVERLAP"] = old
        out.update(pair)
        # the ratio must come from ONE run's pair, not the per-member
        # maxes a floor retry may have taken from different runs (that
        # would be exactly the cross-run artifact the interleaved group
        # exists to kill); median over runs rejects noise
        if ratios:
            out[f"_{row}_vs_seq"] = statistics.median(ratios)
        _progress(row, pair[row])
        _progress(f"{row}_seq", pair[f"{row}_seq"])

    def _plan_fields(plan):
        f = {"strategy": plan.strategy, "plan_id": plan.plan_id,
             "overlap": plan.overlap_depth}
        if plan.overlap:
            # the acceptance field: modeled sequential/critical-path
            # ratio of the pipelined stage groups (max-vs-sum arithmetic)
            f["critical_path_model"] = plan.overlap["model_speedup"]
        # wire-codec accounting (ISSUE 7): raw vs actually-shipped bytes
        # of the executing plan (quantized under HEAT_TPU_WIRE_QUANT —
        # auto engages int8 on TPU; wire_ratio 1.0 = full-width wire).
        # The acceptance gate is wire_ratio <= 0.5 on the int8 rows.
        raw, sent = plan.wire_bytes_raw, plan.wire_bytes_sent
        f["wire_bytes_raw"] = raw
        f["wire_bytes_sent"] = sent
        f["wire_ratio"] = round(sent / raw, 4) if raw else 1.0
        if plan.quant:
            f["quant"] = plan.quant["mode"]
        return f

    def _attribution_fields(step, x, plan):
        """ISSUE 15: one traced execution -> the model-vs-measured join.
        Clears the executor program cache first so the per-lap trace
        probes re-fire (census == plan structure), brackets the run in
        a ``fenced`` span (the execute leg attribution judges against
        the plan's modeled wall), and returns the compact diagnosis —
        census + per-leg measured_s/model_error — that rides the row."""
        import importlib

        # the package attr `attribution` is the FUNCTION (the documented
        # call shape); the module must come via importlib
        _att = importlib.import_module("heat_tpu.observability.attribution")
        from heat_tpu.observability import tracing as _tr
        from heat_tpu.redistribution import executor as _rexec

        was = _tr.enabled()
        try:
            _tr.enable()
            _tr.clear()
            _rexec.clear_program_cache()  # fresh trace: lap census fires
            t0 = time.perf_counter()
            sync(step(x))
            t1 = time.perf_counter()
            _tr.add_span(
                "bench.execute", t0, t1,
                plan_id=plan.plan_id, step="execute", fenced=True,
            )
            att = _att.attribution(plan)
            return _attribution_summary(att)
        except Exception:  # pragma: no cover — diagnosis must never take bench down
            return {}
        finally:
            if not was:
                _tr.disable()
            _tr.clear()

    def _mem_fields(fn, *xs):
        # static memory bounds (ISSUE 10): the memcheck liveness peak
        # per device plus the compiler's own buffer-assignment numbers,
        # compile-only. `static_peak_bytes` is GATED lower-is-better by
        # scripts/bench_compare.py — a planner change that inflates the
        # live set is caught pre-TPU; the xla_* fields are the
        # cross-check context (tier-1 pins static/xla within 2x).
        try:
            ctx = ht.analysis.memcheck(fn, *xs).context
            out = {"static_peak_bytes": int(ctx["static_peak_bytes"])}
            for k in ("xla_temp_bytes", "xla_output_bytes"):
                if ctx.get(k) is not None:
                    out[k] = int(ctx[k])
            return out
        except Exception:
            return {}

    # reshape there-and-back per step = 2 ops; slope halved. The legacy
    # `reshape` row is FOLDED into the planner-named `reshape_split1_1gb`
    # row (they were one measurement since PR 3, and the legacy name was
    # still carrying the pre-planner 0.084 hbm_frac in old artifacts —
    # scripts/bench_compare.py maps baseline `reshape` onto this row).
    # The row self-identifies as planner-routed via strategy/plan_id.
    r = ht.zeros(RESHAPE_SHAPE, split=1)
    _overlap_pair(
        "reshape_split1_1gb", r,
        lambda y: ht.reshape(ht.reshape(y, (10_000_000, -1), new_split=1),
                             RESHAPE_SHAPE, new_split=1),
        redist_floor,
    )
    method["reshape_split1_1gb"] = (
        "chained-slope (pair, halved; planner-routed; folds the legacy `reshape` row; "
        "interleaved with the HEAT_TPU_REDIST_OVERLAP=0 sequential twin)"
    )
    try:
        plan = ht.redistribution.explain(r, reshape=(10_000_000, 25), new_split=1)
        out["_reshape_plan"] = _plan_fields(plan)
        out["_reshape_plan"].update(
            _mem_fields(lambda y: ht.reshape(y, (10_000_000, -1), new_split=1), r)
        )
        _attach_attribution(out["_reshape_plan"], _attribution_fields(
            lambda y: ht.reshape(y, (10_000_000, -1), new_split=1), r, plan
        ))
    except Exception:
        out["_reshape_plan"] = {}
    del r

    # reshape_lane_1gb: the lane-friendly companion — same planner-routed
    # pivot machinery, minor dims >= 128 on every stage, so its hbm_frac
    # reads the machinery's own ceiling rather than the lane cap
    rl = ht.zeros(LANE_SHAPE, split=1)
    lane_bytes = LANE_SHAPE[0] * LANE_SHAPE[1] * 4
    lane_floor = 2 * lane_bytes / max(len(jax.devices()), 1) / V5E_HBM_BPS
    _overlap_pair(
        "reshape_lane_1gb", rl,
        lambda y: ht.reshape(ht.reshape(y, LANE_OUT, new_split=1),
                             LANE_SHAPE, new_split=1),
        lane_floor,
    )
    method["reshape_lane_1gb"] = (
        "chained-slope (pair, halved; planner-routed lane-friendly companion; "
        "interleaved with the sequential twin)"
    )
    try:
        plan = ht.redistribution.explain(rl, reshape=LANE_OUT, new_split=1)
        out["_reshape_lane_plan"] = _plan_fields(plan)
        out["_reshape_lane_plan"].update(
            _mem_fields(lambda y: ht.reshape(y, LANE_OUT, new_split=1), rl)
        )
        _attach_attribution(out["_reshape_lane_plan"], _attribution_fields(
            lambda y: ht.reshape(y, LANE_OUT, new_split=1), rl, plan
        ))
    except Exception:
        out["_reshape_lane_plan"] = {}
    del rl

    # resplit_1gb: split 0 -> 1 -> 0, one planned (chunked, pipelinable)
    # exchange per direction
    rsp = ht.zeros(RESHAPE_SHAPE, split=0)
    _overlap_pair(
        "resplit_1gb", rsp, lambda y: y.resplit(1).resplit(0), redist_floor
    )
    method["resplit_1gb"] = "chained-slope (pair, halved; interleaved with the sequential twin)"
    try:
        _rsp_plan = ht.redistribution.explain(rsp, 1)
        out["_resplit_plan"] = _plan_fields(_rsp_plan)
        out["_resplit_plan"].update(_mem_fields(lambda y: y.resplit(1), rsp))
        _attach_attribution(out["_resplit_plan"], _attribution_fields(
            lambda y: y.resplit(1), rsp, _rsp_plan
        ))
    except Exception:
        out["_resplit_plan"] = {}
    del rsp

    # concatenate + a dependency slice per step = concat op + cheap slice
    arrs = [ht.zeros((1000, s), split=(None if i == 1 else 1)) for i, s in enumerate(CONCAT_SIZES)]
    def _concat_step(y):
        c = ht.concatenate([y, arrs[1], arrs[2]], axis=1)
        return c[:, : CONCAT_SIZES[0]]
    out["concatenate"] = _chained_slope(arrs[0], _concat_step, sync, k1=4, k2=24)
    _progress("concatenate", out["concatenate"])
    method["concatenate"] = "chained-slope (includes one dependency slice)"
    del arrs

    # reductions cannot chain at the API level (scalar out): loop-program
    # with the accumulator folded into the (single) read pass
    s_in = ht.arange(SUM_N, dtype=ht.float32, split=0)
    @functools.lru_cache(maxsize=None)
    def _sum_loop(k):
        def run(v):
            # acc feeds back into the summand: not loop-invariant, still
            # exactly one stream over v per iteration (add fuses into the
            # reduction read)
            return lax.fori_loop(
                0, k, lambda i, acc: jnp.sum(v + acc * 1e-30), jnp.float32(0)
            )
        return jax.jit(run)
    out["sum"] = _loop_program_time(_sum_loop, (s_in._phys,), sync, k1=4, k2=68)
    _progress("sum", out["sum"])
    method["sum"] = "loop-program"
    del s_in

    # public ht.sort: values AND argsort indices (the reference returns
    # both); sorting its own sorted output costs the same network (every
    # dispatched path — lax.sort, blocked columnsort, radix — is
    # data-oblivious). The raw values-only jnp.sort companion runs
    # INTERLEAVED in the same rep loop (same host-clock noise) — it is the
    # denominator of the `vs_jnp_sort` acceptance ratio (ISSUE 4).
    srt = ht.random.randn(SORT_N, split=0)
    n_dev = max(len(jax.devices()), 1)  # sort work is sharded like redist
    sort_floor = {
        "ht": 2 * SORT_N * 8 / n_dev / V5E_HBM_BPS,
        "jnp": 2 * SORT_N * 4 / n_dev / V5E_HBM_BPS,
    }
    grp = _measure_bounded_group(
        lambda: _chained_slope_group(
            {
                "ht": (srt, lambda y: ht.sort(y)[0]),
                "jnp": (srt._phys, lambda y: jnp.sort(y)),
            },
            sync, k1=2, k2=8, reps=4,
        ),
        sort_floor,
    )
    out["sort"], out["jnp_sort"] = grp["ht"], grp["jnp"]
    _progress("sort", out["sort"])
    _progress("jnp_sort", out["jnp_sort"])
    method["sort"] = method["jnp_sort"] = "chained-slope (interleaved pair)"
    del srt

    # ring attention: output feeds back as the next query. Same
    # floor/retry machinery as the matmul rows (the r5 attention-MFU
    # regression went unflagged): a slope under the causal-FLOPs bf16
    # roofline is host-clock noise, re-measure and keep the slowest.
    qkv = [ht.random.randn(RA_B, RA_H, RA_S, RA_D, split=2) for _ in range(3)]
    qkv_bf = [t.astype(ht.bfloat16) for t in qkv]
    ra_cb_floor = RA_B * RA_H * 2 * 2 * RA_S * RA_S * RA_D * 0.5 / V5E_BF16_FLOPS
    ra = _measure_bounded_group(
        lambda: _chained_slope_group(
            {
                "f32": (qkv[0], lambda y: ht.nn.ring_attention(y, qkv[1], qkv[2], causal=True)),
                "bf16": (qkv_bf[0], lambda y: ht.nn.ring_attention(y, qkv_bf[1], qkv_bf[2], causal=True)),
            },
            sync, k1=8, k2=40, reps=4,
        ),
        # f32 cannot beat the bf16 MXU peak either — one bound serves both
        {"f32": ra_cb_floor, "bf16": ra_cb_floor},
    )
    out["ring_attention"] = ra["f32"]
    _progress("ring_attention", out["ring_attention"])
    out["ring_attention_bf16"] = ra["bf16"]
    _progress("ring_attention_bf16", out["ring_attention_bf16"])
    method["ring_attention"] = method["ring_attention_bf16"] = "chained-slope"
    del qkv, qkv_bf

    # ------------------------------------------------------------------ #
    # chip rows: loop programs (pure device rate) unless noted           #
    # ------------------------------------------------------------------ #
    @functools.lru_cache(maxsize=None)
    def _mm_loop(k):
        # y <- (y * 1e-4) @ r : loop-carried, scale fuses into the matmul
        return jax.jit(lambda y, r: lax.fori_loop(0, k, lambda i, y: (y * 1e-4) @ r, y))

    am = ht.random.randn(MM_8K, MM_8K, split=0).astype(ht.bfloat16)
    af = ht.random.randn(MM_8K, MM_8K, split=0)
    mm_floor = 2 * MM_8K**3 / V5E_BF16_FLOPS
    out["matmul_bf16_8k"] = _measure_bounded(
        lambda: _loop_program_time(_mm_loop, (am._phys, am._phys), sync, k1=4, k2=36),
        mm_floor,
    )
    _progress("matmul_bf16_8k", out["matmul_bf16_8k"])
    out["matmul_f32_8k"] = _measure_bounded(
        lambda: _loop_program_time(_mm_loop, (af._phys, af._phys), sync, k1=4, k2=36),
        2 * MM_8K**3 / V5E_F32_DEFAULT_FLOPS,
    )
    _progress("matmul_f32_8k", out["matmul_f32_8k"])
    method["matmul_bf16_8k"] = method["matmul_f32_8k"] = "loop-program"
    del am, af

    # long-context attention: the MFU row loops the preferred kernel
    # callable (splash; see nn/attention._splash_callable) inside one
    # program — the chained public path swung ±0.2 MFU with host-clock
    # noise (r4 runs: 0.60/0.80/1.10 for identical code). Dispatch
    # cost of the public wrapper is carried by the cb-scale
    # ring_attention rows above.
    qkv_big = [
        ht.random.randn(RAB_B, RAB_H, RAB_S, RAB_D, split=2).astype(ht.bfloat16)
        for _ in range(3)
    ]
    from heat_tpu.nn.attention import _splash_callable
    ra_shape = (RAB_B, RAB_H, RAB_S, RAB_D)
    ra_scale = RAB_D ** -0.5
    kern_run = _splash_callable(ra_shape, ra_shape, True, ra_scale, "bfloat16")
    ra_floor = RAB_B * RAB_H * 2 * 2 * RAB_S * RAB_S * RAB_D * 0.5 / V5E_BF16_FLOPS

    def _attn_make(fn3):
        """make_looped factory for an attention callable fn3(q, k, v) —
        shared by the bare-splash row and the kernel-ring row so their
        digest/loop logic cannot diverge."""
        kb, vb = qkv_big[1]._phys, qkv_big[2]._phys

        @functools.lru_cache(maxsize=None)
        def make(k):
            def body(i, y):
                return fn3(y, kb, vb).astype(y.dtype)
            return jax.jit(lambda y: lax.fori_loop(0, k, body, y))

        return make

    def _attn_loop_row(fn3):
        return _measure_bounded(
            lambda: _loop_program_time(_attn_make(fn3), (qkv_big[0]._phys,), sync, k1=4, k2=44),
            ra_floor,
        )

    # VERDICT r4 #1 done-criterion: the KERNEL RING program on a 1-chip
    # mesh must sit within ~10% of the bare splash row — proving the ring
    # wrapper (shard_map + scan + causal switch + lse combine) costs
    # nothing, so the multi-chip path keeps kernel-level MFU per step.
    # ISSUE 5: both rows are measured as ONE interleaved group with the
    # matmul-grade floor/retry machinery, so `vs_splash_row` is computed
    # from same-run samples — two independently-measured rows drift ±20%
    # on host-clock noise alone, which is how a ring "faster than its
    # inner splash kernel" used to pass by luck.
    measured = False
    if kern_run is not None:
        from heat_tpu.nn.attention import _ring_attention_kernel_callable
        from jax.sharding import Mesh as _Mesh1

        mesh1 = _Mesh1(np.asarray(jax.devices()[:1]), ("d",))
        ring1 = _ring_attention_kernel_callable(
            mesh1, "d", RAB_S, RAB_S, RAB_B, RAB_H, RAB_D, True, ra_scale,
            "bfloat16", False,
        )
        if ring1 is not None:
            try:
                grp = _measure_bounded_group(
                    lambda: _loop_program_group(
                        {
                            "splash": (_attn_make(kern_run), (qkv_big[0]._phys,)),
                            "ring": (_attn_make(ring1), (qkv_big[0]._phys,)),
                        },
                        sync, k1=4, k2=44,
                    ),
                    {"splash": ra_floor, "ring": ra_floor},
                )
                out["ring_attention_16k_bf16"] = grp["splash"]
                out["ring_kernel_p1_16k"] = grp["ring"]
                method["ring_attention_16k_bf16"] = "loop-program (splash kernel; interleaved group)"
                method["ring_kernel_p1_16k"] = "loop-program (kernel ring, 1-chip mesh; interleaved group)"
                _progress("ring_kernel_p1_16k", out["ring_kernel_p1_16k"])
                measured = True
            except Exception:
                pass
        if not measured:
            try:  # ring wrapper unavailable: bare splash row alone
                out["ring_attention_16k_bf16"] = _attn_loop_row(kern_run)
                method["ring_attention_16k_bf16"] = "loop-program (splash kernel)"
                measured = True
            except Exception:
                pass
    if not measured:  # non-TPU or kernel unavailable: public chained path
        out["ring_attention_16k_bf16"] = _chained_slope(
            qkv_big[0],
            lambda y: ht.nn.ring_attention(y, qkv_big[1], qkv_big[2], causal=True),
            sync, k1=4, k2=28, reps=5,
        )
        method["ring_attention_16k_bf16"] = "chained-slope (public path)"
    _progress("ring_attention_16k_bf16", out["ring_attention_16k_bf16"])
    del qkv_big

    # headline: hsvd_rank at the north-star per-chip shard (2.1 GB), the
    # jitted 4-pass sketch core in a loop program; the public wrapper
    # adds one cached-jit dispatch (~0.1 ms of ~14 ms)
    from heat_tpu.core.linalg.svdtools import _sketched_uds
    dbig = ht.random.randn(HSVD_BIG_M, HSVD_BIG_N, split=0)
    @functools.lru_cache(maxsize=None)
    def _hsvd_loop(k):
        def body(i, y):
            # want_left=True mirrors the public split=0 rank path, which
            # returns U of the input orientation directly from the sketch
            u, s, err_sq, norm_sq = _sketched_uds(y, HSVD_R + 5, HSVD_R + 15, want_left=True)
            # result-derived single-element write keeps the dependency;
            # in-place on the loop carry
            return y.at[0, 0].set(y[0, 0] + err_sq * 1e-30)
        return jax.jit(lambda y: lax.fori_loop(0, k, body, y))
    out["hsvd_2gb"] = _measure_bounded(
        lambda: _loop_program_time(_hsvd_loop, (dbig._phys,), sync, k1=2, k2=22),
        2 * HSVD_BIG_M * HSVD_BIG_N * 4 / V5E_HBM_BPS,  # 2-pass HBM floor
    )
    _progress("hsvd_2gb", out["hsvd_2gb"])
    method["hsvd_2gb"] = "loop-program"

    # r5: ONE-VIEW (single-pass) hSVD at the same shard — column + row
    # sketches from one fused streaming read (dual-sketch Pallas kernel),
    # so the bound is the FULL 819 GB/s stream where the 2-pass schedule
    # caps at 410. Opt-in quality trade (docs/PERF.md); this row carries
    # the throughput side of that trade.
    from heat_tpu.core.linalg.svdtools import _one_view_uds_both, _one_view_params

    ov = _one_view_params(HSVD_R + 5, min(HSVD_BIG_M, HSVD_BIG_N), HSVD_BIG_M, HSVD_BIG_N)
    if ov is not None:
        ov_k, ov_l = ov

        @functools.lru_cache(maxsize=None)
        def _hsvd1_loop(k):
            def body(i, y):
                u, _, s, err_sq, norm_sq = _one_view_uds_both(
                    y, HSVD_R + 5, ov_k, ov_l, "left"
                )
                digest = err_sq + jnp.sum(s) + u[0, 0] * 1e-30
                return y.at[0, 0].set(y[0, 0] + digest * 1e-30)
            return jax.jit(lambda y: lax.fori_loop(0, k, body, y))

        out["hsvd_1pass_2gb"] = _measure_bounded(
            lambda: _loop_program_time(_hsvd1_loop, (dbig._phys,), sync, k1=2, k2=22),
            HSVD_BIG_M * HSVD_BIG_N * 4 / V5E_HBM_BPS,  # ONE-pass floor
        )
        _progress("hsvd_1pass_2gb", out["hsvd_1pass_2gb"])
        method["hsvd_1pass_2gb"] = "loop-program (one-view dual-sketch kernel)"
    del dbig

    sb = ht.arange(SUM_BIG_N, dtype=ht.float32, split=0)
    out["sum_1gb"] = _measure_bounded(
        lambda: _loop_program_time(_sum_loop, (sb._phys,), sync, k1=4, k2=68),
        SUM_BIG_N * 4 / V5E_HBM_BPS,
    )
    _progress("sum_1gb", out["sum_1gb"])
    method["sum_1gb"] = "loop-program"
    del sb

    # KMeans at the NORTH-STAR per-chip shard (VERDICT r4 #4 / BASELINE
    # config #4: "KMeans iter/s at 1B x 64 — measure & report"): 1B x 64
    # over v5e-64 is 15.625M x 64 (~4 GB f32) per chip. Lloyd's step is
    # HBM-bound (one stream over X per iteration, the (K,D) centroid
    # cross-chip psum is noise), so the per-chip row carries an
    # hbm_frac bound and projects directly to the 64-chip config.
    xb_big = ht.random.randn(KM_BIG_N, KM_D, split=0)
    cb_big = xb_big.larray[:KM_K]
    step_big = _lloyd_step(KM_K, tuple(xb_big.larray.shape), np.dtype(xb_big.larray.dtype).name)

    @functools.lru_cache(maxsize=None)
    def _km_big_loop(k):
        # the 4 GB operand is an ARGUMENT, not a closure capture — a
        # captured concrete array would bake into both loop executables
        # as a program constant and stay pinned in HBM past the `del`
        def run(c, xv):
            return lax.fori_loop(0, k, lambda i, c: step_big(xv, c)[0], c)
        return jax.jit(run)

    out["kmeans_iter_4gb"] = _measure_bounded(
        lambda: _loop_program_time(_km_big_loop, (cb_big, xb_big._phys), sync, k1=2, k2=18),
        KM_BIG_N * KM_D * 4 / V5E_HBM_BPS,
    )
    _progress("kmeans_iter_4gb", out["kmeans_iter_4gb"])
    method["kmeans_iter_4gb"] = "loop-program"
    del xb_big, cb_big

    # sort_1gb + its raw jnp.sort companion, interleaved (ISSUE 4: the
    # vs_jnp_sort ratio and the sort_frac bound both live on this row).
    # On a 1-chip mesh the ht path autotunes its local-sort engine on
    # first call (cached) and the chosen path/pass-model is recorded
    # next to the measurement; multi-device runs take the distributed
    # network and say so instead of misattributing the model.
    srtb = ht.random.randn(SORT_BIG_N, split=0)
    sortb_floor = {
        "ht": 2 * SORT_BIG_N * 8 / n_dev / V5E_HBM_BPS,
        "jnp": 2 * SORT_BIG_N * 4 / n_dev / V5E_HBM_BPS,
    }
    grp = _measure_bounded_group(
        lambda: _chained_slope_group(
            {
                "ht": (srtb, lambda y: ht.sort(y)[0]),
                "jnp": (srtb._phys, lambda y: jnp.sort(y)),
            },
            sync, k1=1, k2=3, reps=3,
        ),
        sortb_floor,
    )
    out["sort_1gb"], out["jnp_sort_1gb"] = grp["ht"], grp["jnp"]
    _progress("sort_1gb", out["sort_1gb"])
    _progress("jnp_sort_1gb", out["jnp_sort_1gb"])
    method["sort_1gb"] = method["jnp_sort_1gb"] = "chained-slope (interleaved pair)"
    # the pass-count model and autotune decisions describe the
    # SINGLE-CHIP local sort — on a >1-device mesh ht.sort takes the
    # distributed network instead, so the model would misattribute
    from heat_tpu.kernels import sort as _ksort
    if n_dev == 1:
        out["_sort_plans"] = {
            "sort": _ksort.sort_plan(SORT_N, "float32", with_indices=True),
            "sort_1gb": _ksort.sort_plan(SORT_BIG_N, "float32", with_indices=True),
            "decisions": {
                f"n={k[0]}": v for k, v in _ksort.last_decisions().items()
            },
        }
    else:
        out["_sort_plans"] = {
            "note": f"{n_dev}-device mesh: sort rows ran the distributed "
                    "network; single-chip pass models not applicable"
        }
    del srtb

    # spmm_1gb (ISSUE 18): brick-CSR SpMM over a 1 GB dense-EQUIVALENT
    # operand (16384^2 f32) at 6.25% brick-grid fill — every stored
    # brick is a full (8,128) VREG tile, so the engine streams 67 MB
    # where the dense matmul twin streams the whole gigabyte. The twin
    # runs interleaved in the same rep loop so `vs_dense_matmul` is a
    # same-run ratio (the vs_jnp_sort discipline). The floor is the
    # lattice's nnz-weighted wire mass (tiers.sparse_transfer_time:
    # value + int32 column index per stored element, once per pass).
    import scipy.sparse as _scipy_sp
    from heat_tpu.core import tiers as _tiers
    from heat_tpu.kernels import spmm as _kspmm
    from heat_tpu.observability import calibration as _calibration
    from heat_tpu.sparse.dbcsr_matrix import BRICK_SHAPE as _BRICK

    _br, _bc = _BRICK
    _smb, _snb = SPMM_N // _br, SPMM_N // _bc
    _srng = np.random.default_rng(0x18)
    _lin = np.sort(_srng.choice(_smb * _snb, int(_smb * _snb * SPMM_OCC), replace=False))
    _sbrow = (_lin // _snb).astype(np.int32)
    _sbindptr = np.zeros(_smb + 1, np.int64)
    np.add.at(_sbindptr, _sbrow + 1, 1)
    _sbsr = _scipy_sp.bsr_matrix(
        (
            _srng.standard_normal((_lin.size, _br, _bc)).astype(np.float32),
            (_lin % _snb).astype(np.int32),
            np.cumsum(_sbindptr),
        ),
        shape=(SPMM_N, SPMM_N),
    )
    Ssp = ht.sparse.sparse_dbcsr_matrix(_sbsr, split=0)
    Dsp = jnp.asarray(_sbsr.toarray())  # the dense twin's 1 GB operand
    del _sbsr
    xsp = ht.random.randn(SPMM_N, SPMM_K, split=None)._phys

    # this deployment's stream price: the lattice hbm edge on TPU or
    # under an active calibration profile; otherwise the live PR 16
    # copy probe — on the CPU container the 819 GB/s constant would
    # price a fiction and fabricate a ~0.005 nnz_bw_frac
    stream_source = "lattice"
    stream_bps = _tiers.bandwidth("hbm")
    if jax.default_backend() != "tpu" and _tiers.profile_id() is None:
        _hbm_probe = _calibration.probe_hbm()
        if _hbm_probe and not _hbm_probe.get("measurement_suspect"):
            stream_bps, stream_source = _hbm_probe["bps"], "copy-probe"

    _sB = Ssp.slab_bricks
    _spath = _kspmm.decide("spmm", _sB, SPMM_K, "float32")
    _sprog = _kspmm.spmm_bcsr_program(
        Ssp.comm, SPMM_N, Ssp.nb, _sB, Ssp.split, 2, "float32", _spath
    )

    # both loops feed y (n, k) back as the next operand — the data
    # dependency defeats dead-compute elimination, and SPMM_N square
    # makes the shapes close
    @functools.lru_cache(maxsize=None)
    def _spmm_loop(k):
        def run(bdata, bcol, brow, bmask, xv):
            return lax.fori_loop(
                0, k, lambda i, y: _sprog(bdata, bcol, brow, bmask, y), xv
            )
        return jax.jit(run)

    @functools.lru_cache(maxsize=None)
    def _spmm_dense_loop(k):
        def run(d, xv):
            return lax.fori_loop(0, k, lambda i, y: d @ y, xv)
        return jax.jit(run)

    spmm_wire = Ssp.nnz * (4 + 4)  # the sparse_transfer_time mass
    spmm_floors = {
        "sp": spmm_wire / n_dev / stream_bps,
        "dn": (SPMM_N * SPMM_N + 2 * SPMM_N * SPMM_K) * 4 / stream_bps,
    }
    sgrp = _measure_bounded_group(
        lambda: _loop_program_group(
            {
                "sp": (_spmm_loop, (*Ssp._phys_components, xsp)),
                "dn": (_spmm_dense_loop, (Dsp, xsp)),
            },
            sync, k1=2, k2=10,
        ),
        spmm_floors,
    )
    out["spmm_1gb"], out["dense_matmul_1gb"] = sgrp["sp"], sgrp["dn"]
    _progress("spmm_1gb", out["spmm_1gb"])
    _progress("dense_matmul_1gb", out["dense_matmul_1gb"])
    method["spmm_1gb"] = method["dense_matmul_1gb"] = "loop-program (interleaved pair)"
    out["_spmm_meta"] = {
        "nnz": int(Ssp.nnz),
        "occupancy": round(Ssp.occupancy, 4),
        "bricks": int(Ssp.nbricks),
        "wire_bytes": int(spmm_wire),
        "path": _spath,
        "kernel_mode": _kspmm.spmm_kernel_mode(),
        "stream_gbps": round(stream_bps / 1e9, 2),
        "stream_source": stream_source,
        "gbps": round(spmm_wire / out["spmm_1gb"] / 1e9, 2),
        # achieved fraction of the nnz-bandwidth floor — the ISSUE 18
        # acceptance pin (>= 0.5 on the CPU container)
        "nnz_bw_frac": round(
            spmm_wire / n_dev / stream_bps / out["spmm_1gb"], 3
        ),
        "vs_dense_matmul": round(out["dense_matmul_1gb"] / out["spmm_1gb"], 3),
    }
    del Ssp, Dsp, xsp

    # pagerank_2m (ISSUE 18): the end-to-end graph scenario — PageRank
    # on a seeded ~2M-edge random digraph through the public API, so the
    # wall-clock includes the host-side transition build, the DBCSR
    # landing, and one brick-engine SpMV per fixpoint iteration.
    # iterations-to-tol is deterministic for the seeded graph; edges/s
    # counts every edge of every sweep.
    from heat_tpu.graph import pagerank as _pagerank

    _prng = np.random.default_rng(0x18)
    _psrc = _prng.integers(0, PR_N, PR_N * PR_DEG)
    _pdst = _prng.integers(0, PR_N, PR_N * PR_DEG)
    _pkeep = _psrc != _pdst
    _prA = _scipy_sp.csr_matrix(
        (
            np.ones(int(_pkeep.sum()), np.float32),
            (_psrc[_pkeep], _pdst[_pkeep]),
        ),
        shape=(PR_N, PR_N),
    )
    _prA.sum_duplicates()
    _pres = _pagerank(_prA, tol=PR_TOL)  # warm: autotune + program cache
    out["pagerank_2m"] = _best_of(lambda: _pagerank(_prA, tol=PR_TOL), reps=2)
    _progress("pagerank_2m", out["pagerank_2m"])
    method["pagerank_2m"] = "eager wall-clock best-of (full fixpoint, conversion included)"
    out["_pagerank_meta"] = {
        "edges": int(_prA.nnz),
        "iterations": int(_pres.iterations),
        "converged": bool(_pres.converged),
        "tol": PR_TOL,
        "edges_per_s": int(_prA.nnz * _pres.iterations / out["pagerank_2m"]),
    }
    del _prA

    # op-dispatch overhead: a chained elementwise expression through the
    # ht.* wrappers vs the same 3 eager jnp dispatches vs ONE hand-jitted
    # fused program — all three feed their output back in (values run to
    # inf/nan; TPU element rate is value-independent). 64M elements so
    # device time (≈2 ms/pass) dominates dispatch cost.
    e = ht.random.randn(CHAIN_N, split=0)
    fused = jax.jit(lambda v: jnp.exp(jnp.sin(v) * 2.0 + v))
    ht_fused = ht.jit(lambda y: ht.exp(ht.sin(y) * 2.0 + y))
    chain = _chained_slope_group(
        {
            "ht": (e, lambda y: ht.exp(ht.sin(y) * 2.0 + y)),
            # the same public-op chain under ht.jit: ONE program, one dispatch
            "ht_jit": (e, ht_fused),
            # raw unfused jnp (same 3 dispatches): isolates the WRAPPER overhead
            "raw": (e._phys, lambda y: jnp.exp(jnp.sin(y) * 2.0 + y)),
            # single fused program: the fusion gap any 3-call chain pays
            "fused": (e._phys, fused),
        },
        # k2=96: the ~2 ms fused pass needs ~200 ms of loop signal for the
        # slope to clear the sync-floor noise — at k2=40
        # the ht_jit/fused ratio swung 0.57-1.46 across recorded runs
        sync, k1=8, k2=96, reps=5,
    )
    out["op_chain"] = chain["ht"]
    _progress("op_chain", out["op_chain"])
    out["ht_jit_chain"] = chain["ht_jit"]
    _progress("ht_jit_chain", out["ht_jit_chain"])
    out["op_chain_raw_jnp"] = chain["raw"]
    _progress("op_chain_raw_jnp", out["op_chain_raw_jnp"])
    out["op_chain_fused_jnp"] = chain["fused"]
    _progress("op_chain_fused_jnp", out["op_chain_fused_jnp"])
    method["op_chain"] = method["ht_jit_chain"] = method["op_chain_raw_jnp"] = method["op_chain_fused_jnp"] = "chained-slope"
    del e

    out["_method"] = method
    out["_eager"] = eager
    return out


def _staging_rows() -> dict:
    """Out-of-core staging rows (ISSUE 11): the `*_hostram` operands
    live on the HOST tier and stream (8,128)-aligned windows through
    the depth-2 double-buffered HBM slab (``redistribution.staging``).

    - ``hsvd_20gb_hostram``: ANALYTIC lattice row (no 20 GB slab on
      this box — the MULTICHIP methodology): the 2-pass staged plan for
      the 65536x81920 f32 operand (21.5 GB — larger than a v5e chip's
      16 GiB HBM), priced by ``tiers.transfer_time``; PCIe-bound by
      construction, ``stage_bw_frac`` ~1.0 is the TPU round's floor.
    - ``hsvd_2gb_hostram``: MEASURED CPU twin at the north-star shard:
      staged ``hsvd_rank`` over a host-resident 2.1 GB operand vs the
      depth-2 bound ``max(raw window streaming, in-HBM compute)`` —
      ``stage_bw_frac`` >= 0.5 means staging costs at most the
      un-overlappable transfer (this container's host->device copy
      shares the compute cores; a real PCIe DMA overlaps toward 1.0).
    - ``kmeans_stream_2gb``: MEASURED streaming ``KMeans.partial_fit``
      epoch over a 2.1 GB host operand (the compute is light, so this
      row is the pure staging-pipeline efficiency).
    """
    import time

    import numpy as np

    import jax
    import heat_tpu as ht
    from heat_tpu.redistribution import staging

    rows: dict = {}
    hsvd2 = [{"tag": "sketch", "axis": 1}, {"tag": "project", "axis": 0}]
    sched20 = staging.plan_staged_passes(
        (65536, 81920), "float32", hsvd2,
        slab=staging.DEFAULT_SLAB_MB << 20, out_bytes=128 << 20,
    )
    m20 = sched20.staging["model"]
    rows["hsvd_20gb_hostram"] = {
        "modeled": True,
        "path": "host-staging",
        "plan_id": sched20.plan_id,
        "host_bytes": sched20.staging["host_bytes"],
        "window_bytes": sched20.staging["window_bytes"],
        "n_windows": sched20.staging["n_windows"],
        "pcie_s": m20["pcie_s"],
        "critical_path_s": m20["critical_path_s"],
        "stage_model_gbps": m20["bound_gbps"],
        "stage_bw_frac": round(m20["pcie_s"] / m20["critical_path_s"], 3),
        "method": (
            "analytic lattice model (tiers.transfer_time over the staged "
            "plan; operand larger than HBM — no in-core baseline exists)"
        ),
    }

    # measured 2.1 GB twin — same shard the hsvd_2gb row measures in-HBM
    rng = np.random.default_rng(0)
    host_np = rng.standard_normal((HSVD_BIG_M, HSVD_BIG_N), dtype=np.float32)
    host = staging.HostArray(host_np)
    nbytes = host.nbytes
    slab = staging.slab_bytes()
    wins1 = staging.window_extents(host.shape, 4, 1, slab)
    wins0 = staging.window_extents(host.shape, 4, 0, slab)

    def raw_stage_s() -> float:
        t0 = time.perf_counter()
        for axis, wins in ((1, wins1), (0, wins0)):
            for a, b in wins:
                jax.device_put(host.window(axis, a, b)).block_until_ready()
        return time.perf_counter() - t0

    def inhbm_s() -> float:
        arr = ht.array(host_np, split=None)
        u, _ = ht.linalg.hsvd_rank(arr, HSVD_R)
        u.larray.block_until_ready()  # warm compile
        t0 = time.perf_counter()
        u, _ = ht.linalg.hsvd_rank(arr, HSVD_R)
        u.larray.block_until_ready()
        return time.perf_counter() - t0

    def staged_s() -> float:
        t0 = time.perf_counter()
        u, _ = ht.linalg.hsvd_rank(host, HSVD_R)
        u.larray.block_until_ready()
        return time.perf_counter() - t0

    def _staged_attribution(run) -> dict:
        """ISSUE 16: one extra TRACED staged execution -> the
        model-vs-measured join for the staged plan it streams. The
        timed row runs stay untraced (their seconds are the product
        figure); this re-run pays the probe cost on its own clock. The
        plan_id rides in on the ``stage_in`` spans the window stream
        emits — the staged plan registered itself on construction."""
        import importlib

        _att = importlib.import_module("heat_tpu.observability.attribution")
        from heat_tpu.observability import tracing as _tr

        was = _tr.enabled()
        try:
            _tr.enable()
            _tr.clear()
            t0 = time.perf_counter()
            run()
            t1 = time.perf_counter()
            pids = [(r.get("attrs") or {}).get("plan_id") for r in _tr.spans()]
            pids = [p for p in pids if p]
            if not pids:
                return {}
            _tr.add_span("bench.execute", t0, t1,
                         plan_id=pids[-1], step="execute", fenced=True)
            return _attribution_summary(_att.attribution(pids[-1]))
        except Exception:  # diagnosis must never take bench down
            return {}
        finally:
            if not was:
                _tr.disable()
            _tr.clear()

    stage_raw = raw_stage_s()
    compute = inhbm_s()
    staged_s()  # warm the per-window programs
    staged = staged_s()
    bound = max(stage_raw, compute)
    rows["hsvd_2gb_hostram"] = {
        "seconds": round(staged, 6),
        "path": "host-staging",
        "window_bytes": slab // 2,
        "n_windows": len(wins1) + len(wins0),
        "gbps": round(2 * nbytes / staged / 1e9, 2),
        "stage_raw_s": round(stage_raw, 6),
        "inhbm_s": round(compute, 6),
        "stage_bw_frac": round(bound / staged, 3),
        "method": (
            "measured staged hsvd_rank over a host-resident twin vs the "
            "depth-2 bound max(raw window stream, in-HBM compute)"
        ),
    }
    if rows["hsvd_2gb_hostram"]["stage_bw_frac"] > 1.0:
        rows["hsvd_2gb_hostram"]["measurement_suspect"] = True
    _attach_attribution(rows["hsvd_2gb_hostram"], _staged_attribution(staged_s))
    del host_np, host

    # streaming KMeans epoch over a 2.1 GB host operand
    km_np = rng.standard_normal((8_388_608, KM_D), dtype=np.float32)
    km_host = staging.HostArray(km_np)
    kwins = staging.window_extents(km_host.shape, 4, 0, slab)

    def km_stage_s() -> float:
        t0 = time.perf_counter()
        for a, b in kwins:
            jax.device_put(km_host.window(0, a, b)).block_until_ready()
        return time.perf_counter() - t0

    def km_staged_s() -> float:
        km = ht.cluster.KMeans(n_clusters=KM_K, init="random", random_state=0)
        t0 = time.perf_counter()
        km.fit(km_host)
        km.cluster_centers_.larray.block_until_ready()
        return time.perf_counter() - t0

    km_raw = km_stage_s()
    km_staged_s()  # warm the window programs
    km_staged = km_staged_s()
    rows["kmeans_stream_2gb"] = {
        "seconds": round(km_staged, 6),
        "path": "host-staging",
        "window_bytes": slab // 2,
        "n_windows": len(kwins),
        "gbps": round(km_host.nbytes / km_staged / 1e9, 2),
        "rows_per_s": round(km_host.shape[0] / km_staged, 1),
        "stage_raw_s": round(km_raw, 6),
        "stage_bw_frac": round(km_raw / km_staged, 3),
        "method": (
            "measured streaming partial_fit epoch (fit on a HostArray) vs "
            "the raw window-stream bound"
        ),
    }
    if rows["kmeans_stream_2gb"]["stage_bw_frac"] > 1.0:
        rows["kmeans_stream_2gb"]["measurement_suspect"] = True
    _attach_attribution(rows["kmeans_stream_2gb"], _staged_attribution(km_staged_s))
    return rows


def _resilience_rows() -> dict:
    """Resilience rows (ISSUE 13):

    - ``ckpt_write_2gb``: MEASURED durable slab-streamed checkpoint
      commit of a 2.1 GB state — write, per-entry sha256, fsync, atomic
      rename — vs the lattice's host->disk durable-commit edge
      (``tiers.bandwidth("disk")``, the fsync-inclusive 0.8 GB/s figure).
      ``bound_frac`` >= 0.5 is the pinned floor; ``max_slab_bytes`` is
      the RECORDED host high-water mark (the O(slab) proof rides in the
      envelope, asserted in tier-1).
    - ``recovery_resume``: MEASURED detect→drain→rekey→resume
      wall-clock on the simulated 2x4 mesh: a declared slice kill
      mid-stream-``fit``, the serving dispatcher drained typed
      (``reason="resize"``), the world re-resolved onto the survivors,
      plan/program/jit caches swept, and the newest committed
      checkpoint restored; the resumed fit's bits are checked against
      an uninterrupted same-seed run (``bit_identical`` — a False
      flags the row suspect).
    """
    import shutil
    import tempfile
    import time

    import numpy as np

    import jax
    import jax.numpy as jnp

    import heat_tpu as ht
    from heat_tpu.core import tiers
    from heat_tpu.redistribution import staging
    from heat_tpu.resilience import chaos as _chaos, checkpoint as ck, elastic
    from heat_tpu.serving.dispatcher import Dispatcher, Endpoint

    rows: dict = {}

    # ---- ckpt_write_2gb: durable slab-streamed commit ---------------- #
    rng = np.random.default_rng(0)
    data = rng.standard_normal((8_388_608, 64)).astype(np.float32)  # 2.1 GB
    tmp = tempfile.mkdtemp(prefix="ht-ckpt-bench-")
    try:
        t0 = time.perf_counter()
        path = ck.save({"data": data}, tag="bench", step=1, directory=tmp)
        dt = time.perf_counter() - t0
        meta = ck._read_meta(path)
        bound_gbps = tiers.bandwidth("disk") / 1e9
        write_gbps = meta["total_bytes"] / dt / 1e9
        rows["ckpt_write_2gb"] = {
            "seconds": round(dt, 6),
            "write_gbps": round(write_gbps, 3),
            "disk_bound_gbps": round(bound_gbps, 3),
            "bound_frac": round(write_gbps / bound_gbps, 3),
            "total_bytes": meta["total_bytes"],
            "max_slab_bytes": meta["max_slab_bytes"],
            "method": (
                "measured durable checkpoint commit (slab writes + sha256 + "
                "fsync + atomic rename) of a 2.1 GB host state vs the "
                "lattice disk edge (fsync-inclusive durable-commit price)"
            ),
        }
        if rows["ckpt_write_2gb"]["bound_frac"] < 0.5:
            rows["ckpt_write_2gb"]["measurement_suspect"] = True
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    del data

    # ---- recovery_resume: detect -> drain -> rekey -> resume --------- #
    import os as _os

    saved_slab = _os.environ.get("HEAT_TPU_OOC_SLAB_MB")
    _os.environ["HEAT_TPU_OOC_SLAB_MB"] = "1"  # multi-window stream
    tmp = tempfile.mkdtemp(prefix="ht-recovery-bench-")
    disp = None
    try:
        pts = rng.standard_normal((40960, 16)).astype(np.float32)
        host = staging.HostArray(pts)
        km_ref = ht.cluster.KMeans(n_clusters=4, init="random", random_state=11)
        km_ref.fit(host)
        ref_bits = np.asarray(km_ref.cluster_centers_.numpy()).view(np.uint32)

        cfg = ck.CheckpointConfig(directory=tmp, tag="recovery", every=1)
        monkey = _chaos.ChaosMonkey(seed=3).kill_slice(step=2)
        watcher = monkey.watcher(topology="2x4")
        km = ht.cluster.KMeans(n_clusters=4, init="random", random_state=11)
        ep = Endpoint({8: jax.jit(lambda b: b * 2.0)}, (16,), np.float32)
        disp = Dispatcher(ep, max_queue=32, poll_s=0.005).start()
        disp.call(np.ones((2, 16), np.float32))
        t_detect = None
        try:
            km.fit(host, ckpt=cfg, _watcher=watcher, _chaos=monkey)
        except elastic.WorldChangedError:
            t_detect = time.perf_counter()
        if t_detect is None:
            raise RuntimeError("declared slice kill never fired")
        disp.drain(reason="resize", timeout=10)
        t_drain = time.perf_counter()
        elastic.resolve_world(watcher.devices())
        counts = elastic.invalidate_caches("resize")
        t_rekey = time.perf_counter()
        restored = ck.restore_latest(tmp, tag="recovery")
        t_restore = time.perf_counter()
        disp.resume(endpoint=Endpoint({8: jax.jit(lambda b: b * 2.0)}, (16,), np.float32))
        km.fit(host, ckpt=cfg)  # restore + replay the remaining windows
        t_done = time.perf_counter()
        disp.stop()
        got_bits = np.asarray(km.cluster_centers_.numpy()).view(np.uint32)
        identical = bool(np.array_equal(ref_bits, got_bits))
        rows["recovery_resume"] = {
            "recovery_s": round(t_restore - t_detect, 6),
            "drain_s": round(t_drain - t_detect, 6),
            "rekey_s": round(t_rekey - t_drain, 6),
            "restore_s": round(t_restore - t_rekey, 6),
            "resume_s": round(t_done - t_restore, 6),
            "evicted_plans": counts["plans"],
            "evicted_programs": counts["programs"],
            "restored_step": restored[0] if restored else None,
            "bit_identical": identical,
            "method": (
                "declared 2x4 slice kill mid-stream: dispatcher drain "
                "(typed resize shed) + world re-resolution + cache sweep + "
                "checkpoint restore; recovery_s = detect->restore-complete, "
                "resumed bits checked against the uninterrupted run"
            ),
        }
        if not identical:
            rows["recovery_resume"]["measurement_suspect"] = True
    finally:
        # UNCONDITIONAL restoration: a failure anywhere mid-row must
        # not leave later bench rows measuring a shrunk world behind a
        # parked dispatcher (the guard in main() swallows exceptions)
        if disp is not None:
            try:
                disp.stop(timeout=5)
            except Exception:
                pass
        try:
            elastic.resolve_world(ht.core.communication.MPI_WORLD.devices)
            elastic.invalidate_caches("bench-restore")
            elastic._clear_stamps()
        except Exception:
            pass
        if saved_slab is None:
            _os.environ.pop("HEAT_TPU_OOC_SLAB_MB", None)
        else:
            _os.environ["HEAT_TPU_OOC_SLAB_MB"] = saved_slab
        shutil.rmtree(tmp, ignore_errors=True)
    return rows


def _factorization_rows(pol_mn=(524288, 1024), eig_n=2048, chol_n=23170,
                        reps=3, on_tpu=False) -> dict:
    """Dense-factorization rows (ISSUE 19): the matmul-native solver
    suite measured against the SAME-RUN reference GEMM, plus the
    analytic 200 GB v5e-64 rows priced by the calibrated tier lattice.

    - ``polar_2gb``: Newton–Schulz polar over a 524288x1024 f32 split-0
      operand (2.1 GB) at a FIXED 2-iteration sweep (``tol=0`` pins the
      while-loop trip count, so the flop count is exact: ``iters·4mn²``
      gram+update rings plus the final ``2mn²`` H ring).
      ``frac_of_matmul`` is the acceptance figure: the polar flop rate
      over the same-run reference GEMM at the iteration's own update
      shape — both measured interleaved in ONE chained-slope group so
      they see the same host-clock noise (>= 0.5 pinned in PERF.md; the
      bare GEMM is the ceiling by construction).
    - ``eig_2gb``: spectral divide-and-conquer ``eigh`` measured at the
      REDUCED n=2048 — the recursion's host-driven rank splits make the
      full 23170-square row impractical per bench run, so the honest
      ``n`` field rides the row and the 200 GB claim is the analytic
      twin below. ``mfu`` counts the canonical ``9n³`` dense-eig flops.
    - ``cholesky_2gb``: ring-lookahead blocked Cholesky at n=23170
      (2.1 GB). ``vs_matmul_count`` is the acceptance figure: measured
      seconds over the matmul-count time model (``n³/3`` flops at the
      same-run reference GEMM rate) — <= 2.0 pinned in PERF.md.
    - ``*_200gb_v5e64``: ANALYTIC lattice rows (no v5e-64 mesh on this
      box — the MULTICHIP methodology): the same solvers priced at the
      paper-scale 223600-square f32 operand (200 GB) on 64 chips —
      compute at the f32 matmul peak, wire from the factorization
      plan's own ring schedule at the lattice's (calibrated, when a
      profile is active) ICI price.

    All three measured rows re-run once TRACED to attach the
    model-vs-measured ``attribution`` join against the solver's
    registered plan (``eig_2gb`` joins its first-split polar plan — the
    recursion's dominant collective mass).
    """
    import math
    import time

    import numpy as np

    import heat_tpu as ht
    from heat_tpu.core.linalg import factorizations as _fac
    from heat_tpu.redistribution import planner as _planner
    from heat_tpu.core import tiers as _tiers

    rows: dict = {}
    m, n = pol_mn

    def sync(x):
        x.larray.block_until_ready()
        return x

    rng = np.random.default_rng(0)
    a = ht.random.randn(m, n, split=0)
    # reference GEMM twin of the Newton–Schulz update: (m,n) split-0
    # against a replicated (n,n), spectral norm ~1 so the chain neither
    # explodes nor vanishes over the slope iterations
    g = ht.array(
        (rng.standard_normal((n, n)) * (0.5 / math.sqrt(n))).astype(np.float32),
        split=None,
    )
    hn = rng.standard_normal((eig_n, eig_n)).astype(np.float32)
    h0 = ht.array((hn @ hn.T / eig_n + 2.0 * np.eye(eig_n, dtype=np.float32)),
                  split=0)
    # diagonally-dominant s.p.d. operand: cheap to build at 2.1 GB (no
    # setup-side n³ matmul); cholesky reads the lower triangle
    spd = ht.random.randn(chol_n, chol_n, split=0) * 0.01 + ht.eye(
        (chol_n, chol_n), split=0
    ) * 4.0

    pol_iters = 2
    pol_flops = (pol_iters * 4 + 2) * m * n * n
    mm_flops = 2 * m * n * n
    eig_flops = 9 * eig_n**3
    chol_flops = chol_n**3 / 3

    # the 1e-30 feedback keeps the chained data dependency (no
    # dead-compute elimination) while leaving the f32 operand values —
    # and therefore the solvers' data-dependent control flow — identical
    # on every step
    members = {
        "ref": (a, lambda y: ht.matmul(y, g)),
        "polar": (a, lambda y: _fac.polar(y, maxiter=pol_iters, tol=0.0).U),
        "eig": (h0, lambda y: _fac.eigh(h0 + y * 1e-30).eigenvectors),
        "chol": (spd, lambda y: _fac.cholesky(spd + y * 1e-30)),
    }
    floors = {
        "ref": mm_flops / V5E_BF16_FLOPS,
        "polar": pol_flops / V5E_BF16_FLOPS,
        "eig": eig_flops / V5E_BF16_FLOPS,
        "chol": chol_flops / V5E_BF16_FLOPS,
    }
    t = _measure_bounded_group(
        lambda: _chained_slope_group(members, sync, k1=1, k2=3, reps=reps),
        floors,
    )
    mm_rate = mm_flops / t["ref"]

    def mem_fields(fn, *xs):
        try:
            ctx = ht.analysis.memcheck(fn, *xs).context
            out = {"static_peak_bytes": int(ctx["static_peak_bytes"])}
            for k in ("xla_temp_bytes", "xla_output_bytes"):
                if ctx.get(k) is not None:
                    out[k] = int(ctx[k])
            return out
        except Exception:
            return {}

    def fac_attribution(sched, run) -> dict:
        """One extra TRACED fenced run -> the model-vs-measured join
        against the solver's registered plan (the timed rows above stay
        untraced; this re-run pays the probe cost on its own clock)."""
        import importlib

        _att = importlib.import_module("heat_tpu.observability.attribution")
        from heat_tpu.observability import tracing as _tr

        was = _tr.enabled()
        try:
            _tr.enable()
            _tr.clear()
            t0 = time.perf_counter()
            run()
            t1 = time.perf_counter()
            _tr.add_span("bench.execute", t0, t1,
                         plan_id=sched.plan_id, step="execute", fenced=True)
            return _attribution_summary(_att.attribution(sched))
        except Exception:  # diagnosis must never take bench down
            return {}
        finally:
            if not was:
                _tr.disable()
            _tr.clear()

    jt = np.float32
    pol_sched = _fac._runtime_plan("polar", (m, n), jt, a.comm)
    eig_sched = _fac._runtime_plan("polar", (eig_n, eig_n), jt, h0.comm)
    chol_sched = _fac._runtime_plan("cholesky", (chol_n, chol_n), jt, spd.comm)

    rows["polar_2gb"] = {
        "seconds": round(t["polar"], 6),
        "m": m, "n": n, "iters": pol_iters,
        "tflops": round(pol_flops / t["polar"] / 1e12, 2),
        "frac_of_matmul": round((pol_flops / t["polar"]) / mm_rate, 3),
        "ref_gemm_tflops": round(mm_rate / 1e12, 2),
        "plan_id": pol_sched.plan_id,
        "method": (
            "chained-slope (interleaved with the same-shape reference GEMM); "
            "fixed 2-iteration Newton–Schulz sweep (tol=0), flops = 10mn²"
        ),
    }
    rows["eig_2gb"] = {
        "seconds": round(t["eig"], 6),
        "n": eig_n,
        "tflops": round(eig_flops / t["eig"] / 1e12, 2),
        "frac_of_matmul": round((eig_flops / t["eig"]) / mm_rate, 3),
        "plan_id": eig_sched.plan_id,
        "method": (
            "chained-slope (interleaved group); spectral divide-and-conquer "
            "at the reduced n=2048 (honest-n row — the 200 GB claim is the "
            "analytic twin); mfu counts the canonical 9n³ dense-eig flops"
        ),
    }
    chol_model_s = chol_flops / mm_rate
    rows["cholesky_2gb"] = {
        "seconds": round(t["chol"], 6),
        "n": chol_n,
        "tflops": round(chol_flops / t["chol"] / 1e12, 2),
        "vs_matmul_count": round(t["chol"] / chol_model_s, 3),
        "matmul_count_s": round(chol_model_s, 6),
        "plan_id": chol_sched.plan_id,
        "method": (
            "chained-slope (interleaved group); vs_matmul_count = measured "
            "over the n³/3-flop model at the same-run reference GEMM rate "
            "(<= 2.0 is the acceptance bound)"
        ),
    }
    if on_tpu:
        rows["polar_2gb"]["mfu"] = round(pol_flops / t["polar"] / V5E_BF16_FLOPS, 3)
        rows["eig_2gb"]["mfu"] = round(eig_flops / t["eig"] / V5E_BF16_FLOPS, 3)
        rows["cholesky_2gb"]["mfu"] = round(chol_flops / t["chol"] / V5E_BF16_FLOPS, 3)
    # a solver cannot beat the bare GEMM it is made of; cholesky under
    # ~0.9x of its own flop model is the same impossibility — noise
    if rows["polar_2gb"]["frac_of_matmul"] > 1.0:
        rows["polar_2gb"]["measurement_suspect"] = True
    if rows["cholesky_2gb"]["vs_matmul_count"] < 0.9:
        rows["cholesky_2gb"]["measurement_suspect"] = True

    _attach_attribution(
        rows["polar_2gb"],
        fac_attribution(pol_sched,
                        lambda: sync(_fac.polar(a, maxiter=pol_iters, tol=0.0).U)),
    )
    _attach_attribution(
        rows["eig_2gb"],
        fac_attribution(eig_sched, lambda: sync(_fac.eigh(h0).eigenvectors)),
    )
    _attach_attribution(
        rows["cholesky_2gb"],
        fac_attribution(chol_sched, lambda: sync(_fac.cholesky(spd))),
    )
    rows["polar_2gb"].update(
        mem_fields(lambda x: _fac.polar(x, maxiter=pol_iters, tol=0.0), a))
    rows["cholesky_2gb"].update(mem_fields(_fac.cholesky, spd))
    del a, g, h0, spd

    # ---- analytic 200 GB v5e-64 rows (the paper-scale claim) ---------
    # No v5e-64 mesh is attached, so — like dp_step_quant and the
    # MULTICHIP pins — the rows ARE the checkable model: compute at the
    # 64-chip f32 matmul peak, wire from the factorization plan's own
    # ring schedule at the lattice ICI price (calibrated when a profile
    # is active). Budget pinned to the default so the plan_ids match
    # the golden dump, not the ambient HEAT_TPU_REDIST_BUDGET_MB.
    p64 = 64
    n200 = 223600  # n²·4 B ≈ 200 GB f32 — larger than any single chip's HBM
    b64 = _planner.DEFAULT_BUDGET_MB << 20
    chip_flops = p64 * V5E_F32_DEFAULT_FLOPS

    def analytic_row(kind, flops, method):
        sched = _fac._factorization_plan(kind, (n200, n200), "float32", p64,
                                         budget=b64)
        tm = _planner.tier_time_model(sched)
        compute_s = flops / chip_flops
        wire_s = float(tm["total_s"])
        wall = max(compute_s, wire_s)
        return {
            "modeled": True,
            "n": n200, "p": p64,
            "plan_id": sched.plan_id,
            "strategy": sched.strategy,
            "model_compute_s": round(compute_s, 6),
            "model_wire_s": round(wire_s, 6),
            "model_wall_s": round(wall, 6),
            "model_mfu": round(flops / wall / (p64 * V5E_BF16_FLOPS), 3),
            "model_bound": "compute" if compute_s >= wire_s else "wire",
            "method": method,
        }

    rows["polar_200gb_v5e64"] = analytic_row(
        "polar", (pol_iters * 4 + 2) * n200**3,
        "analytic lattice model: the measured polar_2gb workload's fixed "
        "2-iteration sweep at the 200 GB square operand on v5e-64 — "
        "compute at the f32 matmul peak, wire = the plan's static rings "
        "at the lattice ICI price (tiers/tier_time_model)",
    )
    rows["eig_200gb_v5e64"] = analytic_row(
        "polar", 9 * n200**3,
        "analytic lattice model (LOWER bound): canonical 9n³ dense-eig "
        "flops at the f32 matmul peak vs the first-split polar plan's "
        "wire — the recursion's sub-operand rings ride under compute",
    )
    rows["cholesky_200gb_v5e64"] = analytic_row(
        "cholesky", n200**3 / 3,
        "analytic lattice model: n³/3 flops at the f32 matmul peak vs "
        "the p(p-1) panel gather rings at the lattice ICI price — the "
        "trailing updates run under the hops (ring lookahead)",
    )
    return rows


def _serving_qps_row() -> dict:
    """serving_qps (ISSUE 9): sustained micro-batched QPS + per-request
    p95 at a fixed bucket shape — concurrent clients against one
    dispatcher, measured in-process (the dispatcher worker and the
    clients are real threads; the accelerator sees only bucket-shaped
    programs). floor/retry: while the drain finishes under the
    physical floor (the batches' HBM traffic), re-measure and keep the
    SLOWEST drain — over-measurement only under-reports QPS."""
    import threading

    import numpy as np

    import jax.numpy as jnp

    import heat_tpu.serving as srv
    from heat_tpu.cluster import _kcluster

    d, k, bucket = 64, 16, 256
    req_rows, n_clients, reqs_per_client = 32, 4, 24
    total = n_clients * reqs_per_client
    rng = np.random.default_rng(0)
    centers = jnp.asarray(rng.normal(size=(k, d)).astype(np.float32))
    spec = _kcluster.serving_spec("euclidean", centers)
    prog = spec["build"]()
    payloads = rng.normal(size=(n_clients, req_rows, d)).astype(np.float32)

    def run_once():
        ep = srv.Endpoint({bucket: prog}, (d,), np.float32,
                          extra_args=(centers,), name="bench")
        disp = srv.Dispatcher(ep, max_queue=total + 8, poll_s=0.001)
        disp.start()
        try:
            disp.call(payloads[0], timeout=120)  # warm: compile outside the clock
            barrier = threading.Barrier(n_clients + 1)

            client_errors = []

            def client(i):
                try:
                    barrier.wait()
                    futs = [disp.submit(payloads[i]) for _ in range(reqs_per_client)]
                    for f in futs:
                        f.result(timeout=120)
                except Exception as e:  # a dead client = a bogus row, flagged below
                    client_errors.append(e)

            threads = [threading.Thread(target=client, args=(i,)) for i in range(n_clients)]
            for t in threads:
                t.start()
            barrier.wait()
            t0 = time.perf_counter()
            for t in threads:
                t.join(300)
            elapsed = time.perf_counter() - t0
            ok = not client_errors and not any(t.is_alive() for t in threads)
            return elapsed, disp.stats(), ok
        finally:
            disp.stop()

    # physical floor: every batch reads its bucket slab once
    n_batches_min = -(-total * req_rows // bucket)
    floor = n_batches_min * bucket * d * 4 / V5E_HBM_BPS
    elapsed, stats, ok = run_once()
    for _ in range(2):
        if elapsed >= floor:
            break
        e2, s2, ok2 = run_once()
        if e2 > elapsed:
            elapsed, stats, ok = e2, s2, ok2
    row = {
        "qps": round(total / elapsed, 1),
        "p50_s": round(stats["p50_s"], 6),
        "p95_s": round(stats["p95_s"], 6),
        "bucket": bucket,
        "req_rows": req_rows,
        "clients": n_clients,
        "requests": total,
        "batches": stats["batches"],
        "padded_frac": round(
            stats["padded_rows"] / max(stats["rows"] + stats["padded_rows"], 1), 3
        ),
        "queue_depth_max": stats["queue_depth_max"],
        "method": (
            "in-process dispatcher drain: 4 client threads x 24 requests of "
            "32 rows, kcluster predict program at bucket 256 (floor/retry, "
            "slowest drain kept)"
        ),
    }
    # total + 1: the out-of-clock warmup call rides the same counters;
    # a client that died (timeout/exception) makes elapsed meaningless
    if (not ok or stats["requests"] != total + 1
            or stats["rejected"] or stats["shed"]):
        row["measurement_suspect"] = True
    # ISSUE 15 attribution detail: a short TRACED drain after the
    # measured one (tracing off during the clocked runs), reduced to
    # the per-phase lifecycle breakdown — where a request's time went
    # (queue vs dispatch vs fence vs resolve), p50/p95/p99 each
    try:
        import importlib

        # the package attr `attribution` is the FUNCTION (the documented
        # call shape); the module must come via importlib
        _att = importlib.import_module("heat_tpu.observability.attribution")
        from heat_tpu.observability import tracing as _tr

        was = _tr.enabled()
        try:
            _tr.enable()
            _tr.clear()
            ep = srv.Endpoint({bucket: prog}, (d,), np.float32,
                              extra_args=(centers,), name="bench-traced")
            with srv.Dispatcher(ep, max_queue=32, poll_s=0.001) as disp:
                futs = [disp.submit(payloads[0]) for _ in range(8)]
                for f in futs:
                    f.result(timeout=120)
            row["attribution"] = _att.serving_breakdown()
        finally:
            if not was:
                _tr.disable()
            _tr.clear()
    except Exception:  # pragma: no cover — diagnosis must never take bench down
        pass
    return row


def main() -> None:
    import heat_tpu as ht

    ht.utils.place_compile_cache()
    if "--measure-baseline" in sys.argv:
        base = measure_baseline()
        with open(BASELINE_FILE, "w") as f:
            json.dump(base, f, indent=2)
        print(json.dumps({"written": BASELINE_FILE, **{k: v for k, v in base.items() if k != "_meta"}}))
        return

    ours = measure_heat_tpu()
    base = {}
    if os.path.exists(BASELINE_FILE):
        with open(BASELINE_FILE) as f:
            base = json.load(f)

    on_tpu = ours["_meta"]["platform"] == "tpu"
    method = ours.get("_method", {})

    hsvd_bytes = HSVD_M * HSVD_N * 4
    hsvd_gbps = hsvd_bytes / ours["hsvd"] / 1e9
    hsvd_base_gbps = hsvd_bytes / base["hsvd"] / 1e9 if base.get("hsvd") else None
    hsvd_big_gbps = HSVD_BIG_M * HSVD_BIG_N * 4 / ours["hsvd_2gb"] / 1e9

    detail = {}
    for k, t_ours in ours.items():
        if k.startswith("_"):
            continue
        entry = {"seconds": round(t_ours, 6)}
        if t_ours < 1e-5:
            # microsecond-class rows lose their value to 6-decimal
            # rounding (ADVICE r4): keep the unrounded sample too
            entry["seconds_unrounded"] = t_ours
        bkey = "matmul" if k == "matmul_split1" else k
        if k in ("matmul_bf16", "ring_attention_bf16"):
            bkey = None  # no comparable torch-cpu bf16 engine
        # (the torch `reshape` baseline is implicitly excluded: the
        # planner row's name never matches it, and new_split=1 does real
        # repartition work while torch's reshape is a free view)
        if bkey and base.get(bkey):
            entry["speedup_vs_torch_cpu"] = round(base[bkey] / t_ours, 3)
        if k in method:
            entry["method"] = method[k]
        detail[k] = entry

    # eager wall-clock companions for the traced device-rate rows
    # (ADVICE r4 medium): what ONE public call costs —
    # dispatch + sync included. The traced 'seconds' is device time; the
    # speedup_vs_torch_cpu fields compare device-time against eager torch
    # and are therefore device-rate claims, not single-call claims.
    for k, t_eager in ours.get("_eager", {}).items():
        if k in detail:
            detail[k]["eager_wallclock_s"] = round(t_eager, 6)

    def mfu(key, flops):
        detail[key]["tflops"] = round(flops / ours[key] / 1e12, 2)
        if on_tpu:
            detail[key]["mfu"] = round(flops / ours[key] / V5E_BF16_FLOPS, 3)

    def hbm(key, nbytes):
        detail[key]["gbps"] = round(nbytes / ours[key] / 1e9, 2)
        if on_tpu:
            detail[key]["hbm_frac"] = round(nbytes / ours[key] / V5E_HBM_BPS, 3)

    # cb-parity derived throughputs
    mfu("matmul", 2 * N_MATMUL**3)
    mfu("matmul_bf16", 2 * N_MATMUL**3)
    detail["kmeans_iter"]["iter_per_s"] = round(1.0 / ours["kmeans_iter"], 2)
    detail["sort"]["melem_per_s"] = round(SORT_N / ours["sort"] / 1e6, 1)
    ra_flops = RA_B * RA_H * 2 * 2 * RA_S * RA_S * RA_D * 0.5  # causal ~ half
    mfu("ring_attention", ra_flops)
    mfu("ring_attention_bf16", ra_flops)
    hbm("sum", SUM_N * 4)
    detail["hsvd"]["gbps"] = round(hsvd_gbps, 2)
    if base.get("hsvd_lowrank"):
        # vs torch's own randomized truncated SVD — the fairer algorithmic
        # peer (the reference's code path is the full SVD above)
        detail["hsvd"]["speedup_vs_torch_svd_lowrank"] = round(
            base["hsvd_lowrank"] / ours["hsvd"], 3
        )

    # redistribution-planner rows (VERDICT r4 #5 / ROADMAP reshape): the
    # new_split repartition reads and writes the full 1 GB operand, so
    # the single-chip bound is the HBM stream; the achieved fraction is
    # the comparison (the torch baseline's reshape is a free view on one
    # process — not comparable, hence no speedup field). The legacy
    # `reshape` row is folded into `reshape_split1_1gb`, which carries
    # the planner's strategy/plan_id so the number is attributable.
    rs_bytes = 2 * RESHAPE_SHAPE[0] * RESHAPE_SHAPE[1] * 4
    for k in ("resplit_1gb", "reshape_split1_1gb"):
        if k in detail:
            detail[k]["bytes_moved"] = rs_bytes
            hbm(k, rs_bytes)
    # lane-friendly companion (ISSUE 5): minor dims >= 128 end to end —
    # its hbm_frac is the repartition machinery's own ceiling, next to
    # the lane-capped row it contextualizes
    if "reshape_lane_1gb" in detail:
        lane_pair_bytes = 2 * LANE_SHAPE[0] * LANE_SHAPE[1] * 4
        detail["reshape_lane_1gb"]["bytes_moved"] = lane_pair_bytes
        hbm("reshape_lane_1gb", lane_pair_bytes)
    plan_keys = {
        "resplit_1gb": "_resplit_plan",
        "reshape_split1_1gb": "_reshape_plan",
        "reshape_lane_1gb": "_reshape_lane_plan",
    }
    for row, pkey in plan_keys.items():
        if row not in detail:
            continue
        detail[row].update(ours.get(pkey, {}))
        if "strategy" in detail[row]:
            # `path` mirrors the sort rows' field: the dispatched route
            # the number is attributable to (packed-pivot = the
            # lane-packing relayout engine, heat_tpu.kernels.relayout)
            detail[row]["path"] = detail[row]["strategy"]
        # ISSUE 6 acceptance fields: `overlap` (pipeline depth from the
        # plan annotation), `critical_path_model` (the modeled
        # max-vs-sum speedup, set when the plan pipelines), and the
        # MEASURED overlap-vs-sequential ratio — median of the
        # interleaved group's per-run seq/overlap pairs (same-run
        # samples by construction)
        ratio = ours.get(f"_{row}_vs_seq")
        if ratio is not None:
            detail[row]["vs_sequential"] = round(ratio, 3)

    # sparse-engine rows (ISSUE 18): fold the measured-alongside
    # metadata into the gated rows — the nnz-bandwidth fraction and
    # dense-twin ratio for spmm_1gb, the fixpoint census for
    # pagerank_2m. A fraction past 1.0 means the sample beat its own
    # wire mass (noise); an unconverged fixpoint means the seconds
    # measured a truncated run, not the scenario.
    if "spmm_1gb" in detail:
        detail["spmm_1gb"].update(ours.get("_spmm_meta", {}))
        if detail["spmm_1gb"].get("nnz_bw_frac", 0) > 1.0:
            detail["spmm_1gb"]["measurement_suspect"] = True
    if "pagerank_2m" in detail:
        detail["pagerank_2m"].update(ours.get("_pagerank_meta", {}))
        if not detail["pagerank_2m"].get("converged", True):
            detail["pagerank_2m"]["measurement_suspect"] = True

    # dp_step_quant (ISSUE 7): the analytic v5e-64 quantized-gradient
    # row — no DP mesh is attached, so the row IS the checkable model
    # (the MULTICHIP_*.json convention): a 100M-param f32 ICI-bound
    # layer (1 ms compute vs ~3.94 ms psum wire at 200 GB/s/chip) under
    # the int8 codec. `dp_model_speedup` and `wire_ratio` are gated by
    # scripts/bench_compare.py; tests pin >= 1.5x.
    try:
        from heat_tpu.kernels import quant as _wire_quant

        _dpm = _wire_quant.dp_step_model(
            400_000_000, compute_s=1e-3, p=64, mode="int8"
        )
        detail["dp_step_quant"] = {
            "modeled": True,
            "param_bytes": _dpm["param_bytes"],
            "compute_ms": 1.0,
            "wire_ms_raw": round(_dpm["wire_s_raw"] * 1e3, 3),
            "wire_ms_quant": round(_dpm["wire_s_quant"] * 1e3, 3),
            "dp_model_speedup": _dpm["model_speedup"],
            "wire_ratio": _dpm["wire_ratio"],
            "method": (
                "analytic v5e-64 model (kernels.quant.dp_step_model; "
                "no DP mesh attached)"
            ),
        }
    except Exception:  # pragma: no cover — the model must never take bench down
        pass

    # two-tier analytic rows (ISSUE 8): no DCN hardware is attached, so
    # — like dp_step_quant and the MULTICHIP pins — the rows ARE the
    # checkable model, derived from the planner's tiered plans at a
    # simulated 2x8 v5e mesh (the 16-chip two-slice production target).
    try:
        from heat_tpu.core import communication as _topo_comm
        from heat_tpu.kernels import quant as _wire_quant
        from heat_tpu.redistribution import planner as _redist_planner
        from heat_tpu.redistribution.spec import RedistSpec as _RSpec

        _b28 = _redist_planner.DEFAULT_BUDGET_MB << 20
        _spec16 = _RSpec.normalize((1000, 250000), "float32", 0, 1, 16)
        _flat16 = _redist_planner.plan(_spec16, _b28, quant="0", topology="flat")
        _hier16 = _redist_planner.plan(_spec16, _b28, quant="int8", topology="2x8")
        # flat baseline: a topology-blind plan's replica groups span
        # slices, so its whole crossing payload completes at DCN speed
        _t_flat = _flat16.bytes_moved / _topo_comm.DCN_BPS
        _tm = _redist_planner.tier_time_model(_hier16)
        detail["resplit_1gb_2x8_dcn"] = {
            "modeled": True,
            "strategy": _hier16.strategy,
            "plan_id": _hier16.plan_id,
            "ici_bytes": _tm["ici_bytes"],
            "dcn_bytes": _tm["dcn_bytes"],
            "wire_ratio": (
                round(_hier16.wire_bytes_sent / _hier16.wire_bytes_raw, 4)
                if _hier16.wire_bytes_raw
                else 1.0
            ),
            "tier_model": {
                "flat_dcn_ms": round(_t_flat * 1e3, 3),
                "hier_ici_ms": round(_tm["ici_s"] * 1e3, 3),
                "hier_dcn_ms": round(_tm["dcn_s"] * 1e3, 3),
                "hier_total_ms": round(_tm["total_s"] * 1e3, 3),
            },
            "tier_model_speedup": round(_t_flat / _tm["total_s"], 3),
            "method": (
                "analytic two-tier model: planner plans at topology=2x8 "
                "(hierarchical-a2a + int8 DCN hop) vs the topology-blind "
                "flat plan priced at DCN_BPS (no DCN hardware attached)"
            ),
        }
        _dpm2 = _wire_quant.dp_step_model_2tier(
            400_000_000, compute_s=1e-3, n_slices=2, chips_per_slice=8
        )
        detail["dp_step_quant_2x8"] = {
            "modeled": True,
            "param_bytes": _dpm2["param_bytes"],
            "compute_ms": 1.0,
            "ici_bytes": _dpm2["ici_bytes"],
            "dcn_bytes": _dpm2["dcn_bytes"],
            "tier_model": {
                "flat_f32_ms": round(_dpm2["wire_s_flat"] * 1e3, 3),
                "hier_int8_ms": round(_dpm2["wire_s_hier"] * 1e3, 3),
            },
            "dp_model_speedup": _dpm2["model_speedup"],
            "method": (
                "analytic 2x8 two-tier model (kernels.quant."
                "dp_step_model_2tier): hierarchical+int8 gradient wire vs "
                "flat+f32 all-reduce at DCN speed"
            ),
        }
    except Exception:  # pragma: no cover — the model must never take bench down
        pass

    # serving row (ISSUE 9): measured, not modeled — the dispatcher
    # drain (QPS + p95 at a fixed bucket). Guarded: serving must never
    # take the bench down with it. (The fresh-process AOT-load-vs-compile
    # row left with PR 22: its children need the chip this process
    # holds. The benchmark issue decides how to measure a cold start.)
    try:
        detail["serving_qps"] = _serving_qps_row()
        _progress("serving_qps", 1.0 / max(detail["serving_qps"]["qps"], 1e-9))
    except Exception as e:  # pragma: no cover — diagnostics only
        print(f"[bench] serving_qps skipped: {e}", file=sys.stderr, flush=True)

    # out-of-core staging rows (ISSUE 11): the analytic 20 GB lattice
    # row + the measured 2.1 GB host-resident twins. Guarded: staging
    # must never take the bench down with it.
    try:
        detail.update(_staging_rows())
        _progress("hsvd_2gb_hostram", detail["hsvd_2gb_hostram"]["seconds"])
    except Exception as e:  # pragma: no cover — diagnostics only
        print(f"[bench] staging rows skipped: {e}", file=sys.stderr, flush=True)

    # resilience rows (ISSUE 13): the durable slab-streamed checkpoint
    # commit vs the lattice disk edge, and the detect→drain→rekey→resume
    # recovery wall-clock on the simulated 2x4 mesh. Guarded: the chaos
    # machinery must never take the bench down with it.
    try:
        detail.update(_resilience_rows())
        _progress("ckpt_write_2gb", detail["ckpt_write_2gb"]["seconds"])
    except Exception as e:  # pragma: no cover — diagnostics only
        print(f"[bench] resilience rows skipped: {e}", file=sys.stderr, flush=True)

    # dense-factorization rows (ISSUE 19): the matmul-native solver
    # suite vs the same-run reference GEMM (polar/eig/cholesky measured,
    # attribution-joined) plus the analytic 200 GB v5e-64 twins priced
    # by the calibrated tier lattice. Guarded: the solver suite must
    # never take the bench down with it.
    try:
        detail.update(_factorization_rows(on_tpu=on_tpu))
        _progress("polar_2gb", detail["polar_2gb"]["seconds"])
        _progress("cholesky_2gb", detail["cholesky_2gb"]["seconds"])
    except Exception as e:  # pragma: no cover — diagnostics only
        print(f"[bench] factorization rows skipped: {e}", file=sys.stderr, flush=True)

    # chip rows
    mfu("matmul_bf16_8k", 2 * MM_8K**3)
    mfu("matmul_f32_8k", 2 * MM_8K**3)
    mfu("ring_attention_16k_bf16", RAB_B * RAB_H * 2 * 2 * RAB_S * RAB_S * RAB_D * 0.5)
    if "ring_kernel_p1_16k" in detail:
        mfu("ring_kernel_p1_16k", RAB_B * RAB_H * 2 * 2 * RAB_S * RAB_S * RAB_D * 0.5)
        # the done-criterion ratio: kernel-ring wrapper vs bare splash
        detail["ring_kernel_p1_16k"]["vs_splash_row"] = round(
            ours["ring_kernel_p1_16k"] / ours["ring_attention_16k_bf16"], 3
        )
    if "kmeans_iter_4gb" in detail:
        hbm("kmeans_iter_4gb", KM_BIG_N * KM_D * 4)
        detail["kmeans_iter_4gb"]["iter_per_s"] = round(1.0 / ours["kmeans_iter_4gb"], 2)
        # 1B x 64 over v5e-64 runs this exact per-chip shard + one (K,D)
        # psum (~2 us on ICI): the projected global iter/s IS this row
        detail["kmeans_iter_4gb"]["projected_iter_per_s_1Bx64_v5e64"] = round(
            1.0 / ours["kmeans_iter_4gb"], 2
        )
    detail["hsvd_2gb"]["gbps"] = round(hsvd_big_gbps, 2)
    if "hsvd_1pass_2gb" in detail:
        h1 = HSVD_BIG_M * HSVD_BIG_N * 4 / ours["hsvd_1pass_2gb"] / 1e9
        detail["hsvd_1pass_2gb"]["gbps"] = round(h1, 2)
        detail["hsvd_1pass_2gb"]["passes_over_A"] = 1
        if on_tpu:
            detail["hsvd_1pass_2gb"]["hbm_frac_algorithmic"] = round(
                HSVD_BIG_M * HSVD_BIG_N * 4 / ours["hsvd_1pass_2gb"] / V5E_HBM_BPS, 3
            )
    # algorithmic stream utilization: r4's two-pass schedule (row-space
    # sketch + projection, no power pass — svdtools._sketched_uds_both);
    # the Pallas kernel fuses the Frobenius norm into pass 1 on TPU and
    # the tiled XLA fallback folds it into pass 2 (ISSUE 11), so BOTH
    # schedules stream A exactly twice now
    passes = 2
    detail["hsvd_2gb"]["passes_over_A"] = passes
    if on_tpu:
        detail["hsvd_2gb"]["hbm_frac_algorithmic"] = round(
            passes * HSVD_BIG_M * HSVD_BIG_N * 4 / ours["hsvd_2gb"] / V5E_HBM_BPS, 3
        )
    hbm("sum_1gb", SUM_BIG_N * 4)
    # sort rows: element rate is the honest headline unit (multi-pass
    # kernels), plus the ISSUE-4 acceptance fields — `vs_jnp_sort`
    # (public values+argsort ht.sort against the VALUES-ONLY raw
    # jnp.sort, same shape: ≥ 1 means the fused path gives away nothing
    # for carrying indices) and `sort_frac` (achieved bytes/s over the
    # dispatched path's pass-count model, as a fraction of HBM peak —
    # heat_tpu.kernels.sort.sort_plan; arithmetic in docs/PERF.md).
    detail["sort_1gb"]["melem_per_s"] = round(SORT_BIG_N / ours["sort_1gb"] / 1e6, 1)
    for row, nelem in (("sort", SORT_N), ("sort_1gb", SORT_BIG_N)):
        jnp_row = "jnp_sort" if row == "sort" else "jnp_sort_1gb"
        if jnp_row in detail:
            detail[jnp_row]["melem_per_s"] = round(nelem / ours[jnp_row] / 1e6, 1)
            detail[row]["vs_jnp_sort"] = round(ours[jnp_row] / ours[row], 3)
        plan = ours.get("_sort_plans", {}).get(row)
        if plan:
            detail[row]["path"] = plan.get("path")
            detail[row]["passes_model"] = plan.get("passes")
            if on_tpu:
                detail[row]["sort_frac"] = round(
                    plan["hbm_bytes"] / ours[row] / V5E_HBM_BPS, 3
                )

    if min(ours["op_chain_raw_jnp"], ours["op_chain_fused_jnp"]) > 1e-8:
        detail["op_chain"]["overhead_vs_raw_jnp"] = round(
            ours["op_chain"] / ours["op_chain_raw_jnp"], 3
        )
        detail["op_chain"]["overhead_vs_fused_jnp"] = round(
            ours["op_chain"] / ours["op_chain_fused_jnp"], 3
        )
    else:  # clamped denominator: noise ate the signal, don't fabricate
        detail["op_chain"]["overhead_vs_raw_jnp"] = None
        detail["op_chain"]["overhead_vs_fused_jnp"] = None
        detail["op_chain"]["measurement_suspect"] = True
    # the answer to the eager-dispatch gap: the same chain under ht.jit
    # must track the hand-fused jnp program (≤1.2x). A clamped slope on
    # either side means noise ate the signal — report null, not a
    # fabricated 0.0x
    if min(ours["ht_jit_chain"], ours["op_chain_fused_jnp"]) > 1e-8:
        detail["ht_jit_chain"]["overhead_vs_fused_jnp"] = round(
            ours["ht_jit_chain"] / ours["op_chain_fused_jnp"], 3
        )
    else:
        detail["ht_jit_chain"]["overhead_vs_fused_jnp"] = None
        detail["ht_jit_chain"]["measurement_suspect"] = True
    # sanity: one fused program must not lose to a 3-dispatch chain (a
    # violation means the measurement was dispatch-bound, not a
    # device-time result — flagged instead of silently reported)
    detail["op_chain"]["ordering_ok"] = bool(
        ours["op_chain_fused_jnp"] <= min(ours["op_chain"], ours["op_chain_raw_jnp"]) * 1.1
    )
    # roofline credibility: a row above the chip's physical peak means the
    # measurement (not the chip) is wrong — flag it rather than report it
    for row in detail.values():
        if (
            row.get("mfu", 0) > 1.0
            or row.get("hbm_frac", 0) > 1.0
            or row.get("hbm_frac_algorithmic", 0) > 1.0
        ):
            row["measurement_suspect"] = True
        # a clamped/zero slope means the row's signal drowned in host-clock
        # noise — flag it instead of reporting an absurd speedup
        if row.get("seconds", 1.0) <= 1e-8:
            row["measurement_suspect"] = True
    # f32 matmul cannot beat bf16 (f32 = bf16 MXU passes + extra
    # accumulate work): if a run says otherwise, the f32 sample is noise
    if detail["matmul_f32_8k"].get("mfu", 0) > detail["matmul_bf16_8k"].get("mfu", 1):
        detail["matmul_f32_8k"]["measurement_suspect"] = True
    # same cross-check for the attention rows (the r5 unflagged-regression
    # fix): f32 ring attention beating bf16 is the f32 sample's noise
    if detail["ring_attention"].get("mfu", 0) > detail["ring_attention_bf16"].get("mfu", 1):
        detail["ring_attention"]["measurement_suspect"] = True
    # the kernel-ring program IS splash + wrapper work: measuring it >10%
    # FASTER than the bare splash row means one of the two samples is
    # noise — flag both, the ratio carries the done-criterion claim
    if "ring_kernel_p1_16k" in detail:
        ratio = detail["ring_kernel_p1_16k"].get("vs_splash_row")
        if ratio is not None and ratio < 0.9:
            detail["ring_kernel_p1_16k"]["measurement_suspect"] = True
            detail["ring_attention_16k_bf16"]["measurement_suspect"] = True

    result = {
        "metric": (
            f"hsvd_rank(r={HSVD_R}) GB/s/chip on {HSVD_BIG_M}x{HSVD_BIG_N} f32 split=0 "
            f"(2.1 GB, the north-star per-chip shard; vs_baseline from the "
            f"{HSVD_M}x{HSVD_N} torch-comparable workload)"
        ),
        "value": round(hsvd_big_gbps, 3),
        "unit": "GB/s",
        "vs_baseline": round(hsvd_gbps / hsvd_base_gbps, 3) if hsvd_base_gbps else None,
        "baseline": "reference engine (torch-CPU single-process Heat path), BENCH_BASELINE.json",
        "platform": ours["_meta"],
        "peaks": {"bf16_tflops": V5E_BF16_FLOPS / 1e12, "hbm_gbps": V5E_HBM_BPS / 1e9},
        "detail": detail,
    }

    # Full record to a file; stdout gets ONE compact line. The driver's
    # tail capture is bounded (~2000 chars — BENCH_r03 was truncated
    # mid-JSON and recorded parsed:null), so the parseable line must stay
    # small: headline + the key chip rows only, everything else in
    # BENCH_DETAIL.json.
    detail_file = os.path.join(os.path.dirname(os.path.abspath(__file__)), "BENCH_DETAIL.json")
    with open(detail_file, "w") as f:
        json.dump(result, f, indent=2)

    # regression gate: diff this run against the latest driver round
    # artifact (>10% unflagged moves -> BENCH_COMPARE.json + one stderr
    # line). Guarded: the gate must never take the bench down with it,
    # and stdout stays the single compact line below.
    try:
        sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "scripts"))
        import bench_compare

        gate = bench_compare.run(current_path=detail_file)
        if gate["verdict"] == "skipped":
            print(
                f"[bench] regression gate skipped: {gate.get('reason')}",
                file=sys.stderr, flush=True,
            )
        else:
            print(
                f"[bench] regression gate: {gate['verdict']} "
                f"({len([r for r in gate.get('regressions', []) if 'waived' not in r])} unflagged, "
                f"details in BENCH_COMPARE.json)",
                file=sys.stderr, flush=True,
            )
    except Exception as e:  # pragma: no cover - diagnostics only
        print(f"[bench] regression gate skipped: {e}", file=sys.stderr, flush=True)

    def pick(row, *fields):
        return {f: detail[row][f] for f in fields if f in detail[row]}

    compact = {
        "metric": f"hsvd_rank(r={HSVD_R}) GB/s/chip, {HSVD_BIG_M}x{HSVD_BIG_N} f32 (2.1GB north-star shard)",
        "value": result["value"],
        "unit": "GB/s",
        # vs_baseline compares the reference's OWN hsvd_rank code path (a
        # full torch SVD, reference svdtools.py:477); the sketch-vs-sketch
        # ratio against torch.svd_lowrank sits next to it for fairness
        "vs_baseline": result["vs_baseline"],
        "vs_torch_svd_lowrank": detail["hsvd"].get("speedup_vs_torch_svd_lowrank"),
        "platform": ours["_meta"]["platform"],
        "key_rows": {
            "matmul_bf16_8k": pick("matmul_bf16_8k", "mfu", "measurement_suspect"),
            "matmul_f32_8k": pick("matmul_f32_8k", "mfu", "measurement_suspect"),
            "ring_attention_16k_bf16": pick("ring_attention_16k_bf16", "mfu", "measurement_suspect"),
            "ring_kernel_p1_16k": (
                pick("ring_kernel_p1_16k", "mfu", "vs_splash_row", "measurement_suspect")
                if "ring_kernel_p1_16k" in detail else {}
            ),
            "hsvd_2gb": pick("hsvd_2gb", "gbps", "passes_over_A", "hbm_frac_algorithmic", "measurement_suspect"),
            "hsvd_1pass_2gb": (
                pick("hsvd_1pass_2gb", "gbps", "hbm_frac_algorithmic", "measurement_suspect")
                if "hsvd_1pass_2gb" in detail else {}
            ),
            "sum_1gb": pick("sum_1gb", "hbm_frac", "measurement_suspect"),
            "kmeans_iter_4gb": (
                pick("kmeans_iter_4gb", "iter_per_s", "hbm_frac", "measurement_suspect")
                if "kmeans_iter_4gb" in detail else {}
            ),
            "sort_1gb": pick("sort_1gb", "melem_per_s", "vs_jnp_sort", "sort_frac", "path"),
            # ISSUE 18 sparse-engine rows: the nnz-bandwidth fraction
            # (acceptance floor >= 0.5 on the CPU container), the
            # same-run dense-twin ratio + dispatched path, and the
            # PageRank scenario's iterations-to-tol and edge rate —
            # gated by scripts/bench_compare.py
            "spmm_1gb": pick(
                "spmm_1gb", "gbps", "nnz_bw_frac", "vs_dense_matmul",
                "path", "measurement_suspect",
            ),
            "pagerank_2m": pick(
                "pagerank_2m", "iterations", "edges_per_s",
                "measurement_suspect",
            ),
            # ISSUE 19 dense-factorization rows: polar/eig mfu and the
            # same-run GEMM fraction (acceptance floor >= 0.5 for
            # polar), cholesky's matmul-count ratio (<= 2.0), and the
            # deterministic analytic 200 GB v5e-64 model fields (exact-
            # equality gated via --unchanged-fields like the other
            # `model` fields) — gated by scripts/bench_compare.py
            "polar_2gb": (
                pick("polar_2gb", "mfu", "frac_of_matmul", "measurement_suspect")
                if "polar_2gb" in detail else {}
            ),
            "eig_2gb": (
                pick("eig_2gb", "mfu", "frac_of_matmul", "measurement_suspect")
                if "eig_2gb" in detail else {}
            ),
            "cholesky_2gb": (
                pick("cholesky_2gb", "mfu", "vs_matmul_count", "measurement_suspect")
                if "cholesky_2gb" in detail else {}
            ),
            "polar_200gb_v5e64": (
                pick("polar_200gb_v5e64", "model_mfu", "model_wall_s")
                if "polar_200gb_v5e64" in detail else {}
            ),
            "cholesky_200gb_v5e64": (
                pick("cholesky_200gb_v5e64", "model_mfu", "model_wall_s")
                if "cholesky_200gb_v5e64" in detail else {}
            ),
            # the ROADMAP reshape acceptance fields (ISSUE 5) + the
            # ISSUE 6 overlap fields (`critical_path_model` = modeled
            # max-vs-sum speedup, `vs_sequential` = measured same-run
            # ratio) + the ISSUE 7 `wire_ratio` (encoded/raw wire bytes
            # of the executing plan — the <= 0.5 acceptance gate) + the
            # ISSUE 10 `static_peak_bytes` (memcheck's per-device
            # liveness peak, gated lower-is-better so a planner change
            # that inflates the live set is caught pre-TPU): in the
            # driver artifact so future rounds gate on them
            "reshape_split1_1gb": pick(
                "reshape_split1_1gb", "hbm_frac", "path", "critical_path_model",
                "vs_sequential", "wire_ratio", "static_peak_bytes",
                "measurement_suspect",
            ),
            "reshape_lane_1gb": (
                pick("reshape_lane_1gb", "hbm_frac", "path", "critical_path_model",
                     "vs_sequential", "wire_ratio", "static_peak_bytes",
                     "measurement_suspect")
                if "reshape_lane_1gb" in detail else {}
            ),
            "resplit_1gb": pick(
                "resplit_1gb", "hbm_frac", "path", "critical_path_model",
                "vs_sequential", "wire_ratio", "static_peak_bytes",
                "measurement_suspect",
            ),
            # ISSUE 7 analytic DP row (modeled, gated)
            "dp_step_quant": (
                pick("dp_step_quant", "dp_model_speedup", "wire_ratio")
                if "dp_step_quant" in detail else {}
            ),
            # ISSUE 8 two-tier analytic rows (modeled, gated): the
            # hierarchical-vs-flat speedups and the per-tier byte split
            # at the simulated 2x8 mesh
            "resplit_1gb_2x8_dcn": (
                pick("resplit_1gb_2x8_dcn", "tier_model_speedup", "wire_ratio",
                     "dcn_bytes", "ici_bytes")
                if "resplit_1gb_2x8_dcn" in detail else {}
            ),
            "dp_step_quant_2x8": (
                pick("dp_step_quant_2x8", "dp_model_speedup", "dcn_bytes")
                if "dp_step_quant_2x8" in detail else {}
            ),
            # ISSUE 9 serving row: sustained micro-batched QPS + p95 —
            # gated by scripts/bench_compare.py
            "serving_qps": (
                pick("serving_qps", "qps", "p95_s", "measurement_suspect")
                if "serving_qps" in detail else {}
            ),
            # ISSUE 11 out-of-core staging rows: the analytic 20 GB
            # lattice model + the measured host-resident twins
            # (stage_bw_frac >= 0.5 is the pinned pipeline-efficiency
            # floor) — gated by scripts/bench_compare.py
            "hsvd_20gb_hostram": (
                pick("hsvd_20gb_hostram", "stage_model_gbps", "stage_bw_frac")
                if "hsvd_20gb_hostram" in detail else {}
            ),
            "hsvd_2gb_hostram": (
                pick("hsvd_2gb_hostram", "gbps", "stage_bw_frac", "measurement_suspect")
                if "hsvd_2gb_hostram" in detail else {}
            ),
            "kmeans_stream_2gb": (
                pick("kmeans_stream_2gb", "gbps", "stage_bw_frac", "measurement_suspect")
                if "kmeans_stream_2gb" in detail else {}
            ),
            # ISSUE 13 resilience rows: durable checkpoint commit GB/s vs
            # the lattice disk edge (floor bound_frac >= 0.5) and the
            # detect→drain→rekey→resume recovery wall-clock on the
            # simulated 2x4 mesh — gated by scripts/bench_compare.py
            # (write_gbps higher-is-better, recovery_s lower)
            "ckpt_write_2gb": (
                pick("ckpt_write_2gb", "write_gbps", "bound_frac",
                     "measurement_suspect")
                if "ckpt_write_2gb" in detail else {}
            ),
            "recovery_resume": (
                pick("recovery_resume", "recovery_s", "resume_s",
                     "measurement_suspect")
                if "recovery_resume" in detail else {}
            ),
            "op_chain": pick("op_chain", "overhead_vs_raw_jnp", "overhead_vs_fused_jnp"),
            "ht_jit_chain": pick("ht_jit_chain", "overhead_vs_fused_jnp") if "ht_jit_chain" in detail else {},
            "kmeans_fit_cb": pick("kmeans_fit_cb", "seconds", "speedup_vs_torch_cpu"),
            "lanczos_cb": pick("lanczos_cb", "speedup_vs_torch_cpu") if "lanczos_cb" in detail else {},
        },
        "detail_file": "BENCH_DETAIL.json",
    }
    line = json.dumps(compact)
    # 1700: headroom under the driver's ~2000-char tail capture once the
    # ISSUE 18 sparse rows joined the key set (BENCH_r03 proved what a
    # mid-JSON truncation costs — parsed:null for the whole round)
    assert len(line) < 1700, f"compact bench line too long ({len(line)} chars)"
    print(line)


if __name__ == "__main__":
    main()
