"""Op ``hsvd_rank``: ``ht.linalg.hsvd_rank(a, rank, compute_sv=True)`` on a
tall split-0 matrix that lives on the chips.

The generator, the plain reference and the error arithmetic are copies of
``chip_smoke.py``'s (``low_rank``, ``top_eigs``, ``residual_sq``,
``svd_errors``), which PR 22 proved on the chip; the copy is the yardstick
and does not follow later changes there. One difference: ``A`` is made with
``out_shardings`` on the communicator's mesh, so that across chips no chip
ever holds more than its rows. The reference runs on that same sharded
array under plain ``jax.jit``, never through ``ht``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

import heat_tpu as ht

HI = jax.lax.Precision.HIGHEST


def low_rank(m: int, n: int, rank: int, data: dict, key, sharding):
    """Rank-``rank`` signal with the spectrum ``sigma_max * decay^i`` plus
    Gaussian noise far below it, rows laid out as ``sharding`` says. The key
    is an argument of both programs, so every seed runs the same two."""
    s = data["sigma_max"] * data["decay"] ** jnp.arange(rank, dtype=jnp.float32)

    @jax.jit
    def factors(ku, kv):
        u, _ = jnp.linalg.qr(jax.random.normal(ku, (m, rank), jnp.float32))
        v, _ = jnp.linalg.qr(jax.random.normal(kv, (n, rank), jnp.float32))
        return u * s, v

    def fill(us, v, kn):
        return jnp.matmul(us, v.T, precision=HI) + data["noise"] * jax.random.normal(kn, (m, n), jnp.float32)

    ku, kv, kn = jax.random.split(key, 3)
    return jax.jit(fill, out_shardings=sharding)(*factors(ku, kv), kn)


def make(cfg: dict, chips: int, key) -> dict:
    comm = ht.MPI_WORLD
    if comm.size != chips:
        raise RuntimeError(f"ht.MPI_WORLD spans {comm.size} devices, the cell asks for {chips}")
    m, n = cfg["rows_per_chip"] * chips, cfg["cols"]
    aj = low_rank(m, n, cfg["rank"], cfg["data"], key, comm.sharding(2, 0))
    a = ht.array(aj, split=0)
    del aj
    kwargs = {"compute_sv": True}
    if cfg.get("single_pass"):
        kwargs["single_pass"] = True
    return {"cfg": cfg, "chips": chips, "a": a, "rank": cfg["rank"], "kwargs": kwargs,
            "bytes": m * n * 4}


def call(state: dict):
    return ht.linalg.hsvd_rank(state["a"], state["rank"], **state["kwargs"])


def work_bytes(state: dict, out) -> int:
    """Bytes of input one completed call turned into a result: all of ``A``."""
    return state["bytes"]


def least_bytes(state: dict, out) -> int:
    """The fewest bytes one chip must read for one call: each pass of the
    schedule streams the chip's rows of ``A`` once (two passes, or one with
    the one-view schedule); the sketches and factors are noise beside it."""
    passes = 1 if state["cfg"].get("single_pass") else 2
    return passes * state["bytes"] // state["chips"]


def reference(state: dict):
    """Largest ``rank`` eigenvalues of AᵀA and ‖A‖_F² in plain jnp at
    precision highest: Rayleigh-Ritz on a block power iteration (the gap
    between signal and noise makes three steps exact to f32)."""
    rank = state["rank"]

    @jax.jit
    def run(a):
        q = jax.random.normal(jax.random.key(7), (a.shape[1], 2 * rank), jnp.float32)
        for _ in range(3):
            q, _ = jnp.linalg.qr(jnp.matmul(a.T, jnp.matmul(a, q, precision=HI), precision=HI))
        y = jnp.matmul(a, q, precision=HI)
        lam = jnp.linalg.eigvalsh(jnp.matmul(y.T, y, precision=HI))[::-1]
        return lam[:rank], jnp.sum(jnp.square(a))

    return jax.block_until_ready(run(state["a"].larray))


@jax.jit
def _errors(a, u, s, v, lam, norm_sq):
    """chip_smoke.svd_errors as one program: orthonormality, sigma against
    sqrt(eig(AᵀA)) relative to the largest, and ‖A − U S Vᵀ‖_F beside the
    optimum no factorization of this rank can beat, all at precision highest."""
    eye = jnp.eye(s.shape[0], dtype=jnp.float32)
    top = jnp.sqrt(lam)
    resid_sq = jnp.sum(jnp.square(a - jnp.matmul(u * s, v.T, precision=HI)))
    return {
        "orth_err": jnp.maximum(
            jnp.max(jnp.abs(jnp.matmul(u.T, u, precision=HI) - eye)),
            jnp.max(jnp.abs(jnp.matmul(v.T, v, precision=HI) - eye)),
        ),
        "sigma_err": jnp.max(jnp.abs(s - top)) / jnp.max(top),
        "rel_err_measured": jnp.sqrt(resid_sq / norm_sq),
        "rel_err_optimal": jnp.sqrt(jnp.maximum(norm_sq - jnp.sum(lam), 0.0) / norm_sq),
    }


def measure(state: dict, out, ref) -> dict:
    """What the factors measure against the reference."""
    u, sig, v, err = out
    rank = state["rank"]
    uj, sj, vj = u.larray, sig.larray, v.larray
    if not (uj.shape[1] == rank and vj.shape[1] == rank and sj.shape == (rank,)):
        raise ValueError(f"factor shapes {uj.shape} {sj.shape} {vj.shape}")
    e = {k: float(x) for k, x in _errors(state["a"].larray, uj, sj, vj, *ref).items()}
    e["rel_err_estimate"] = float(err)
    return e


def check(state: dict, out, ref) -> dict:
    """Hold the result to the configuration's guarantees. A miss is a line
    of text; none means the result is right."""
    g = state["cfg"]["guarantees"]
    e = measure(state, out, ref)
    misses = []
    if not e["orth_err"] <= g["orthonormal"]:
        misses.append(f"UᵀU, VᵀV off the identity by {e['orth_err']:.3e} > {g['orthonormal']:.1e}")
    if not e["sigma_err"] <= g["sigma"]:
        misses.append(f"sigma off by {e['sigma_err']:.3e} of σ_max > {g['sigma']:.1e}")
    lo, hi = g["residual_over_optimal"]
    if not lo * e["rel_err_optimal"] <= e["rel_err_measured"] <= hi * e["rel_err_optimal"]:
        misses.append(f"residual {e['rel_err_measured']:.4e} outside [{lo}, {hi}] x optimal {e['rel_err_optimal']:.4e}")
    lo, hi = g["estimate_over_residual"]["one_chip" if state["chips"] == 1 else "across_chips"]
    if not lo * e["rel_err_measured"] <= e["rel_err_estimate"] <= hi * e["rel_err_measured"]:
        misses.append(f"estimate {e['rel_err_estimate']:.4e} outside [{lo}, {hi}] x residual {e['rel_err_measured']:.4e}")
    return {"measured": e, "misses": misses}
