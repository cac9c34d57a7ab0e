"""Op ``qr``: ``ht.linalg.qr(a)`` (``Q`` and ``R``) on a tall-skinny split-0
matrix that lives on the chips: upstream's ``qr_split_0`` at the north star's
shape.

``A`` is uniform on [0, 1) (upstream's ``ht.random.random``), made on the
device from the seed's key, which is an argument of the generator. The plain
reference never goes through ``ht``: ``R`` by a sequential fold of
``jnp.linalg.qr(mode="r")`` over row blocks (``[R; block] -> R``, XLA's
Householder QR) at precision highest, signs fixed so that ``diag(R) >= 0``.
Reference and check work a row block at a time, so that nothing of ``A``'s
size is allocated beside ``A`` and ``Q``.

``work_bytes`` is ``A`` once; ``least_bytes`` one read of ``A`` and one write
of ``Q``, what any schedule must move; ``least_flops`` the Householder count
of a thin QR with ``Q``, whatever implements it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import heat_tpu as ht
from benchmarks.layers.qr_mxu_roofline_pct import least_flops  # noqa: F401 (the op's interface; jax-free there)

HI = jax.lax.Precision.HIGHEST
REF_BLOCK_ROWS = 7168  # rows folded into R at a time: with R's 1024 rows one Householder QR of 8192 x 1024
CHECK_BLOCK_ROWS = 8192  # rows of A and Q a step of the check: 2 x 32 MB


def make(cfg: dict, chips: int, key) -> dict:
    comm = ht.MPI_WORLD
    if comm.size != chips:
        raise RuntimeError(f"ht.MPI_WORLD spans {comm.size} devices, the cell asks for {chips}")
    m, n = cfg["rows_per_chip"] * chips, cfg["cols"]
    fill = jax.jit(lambda k: jax.random.uniform(k, (m, n), jnp.float32), out_shardings=comm.sharding(2, 0))
    a = ht.array(fill(key), split=0)
    return {"cfg": cfg, "chips": chips, "a": a, "bytes": m * n * 4}


def call(state: dict):
    return ht.linalg.qr(state["a"])


def work_bytes(state: dict, out) -> int:
    """Bytes of input one completed call turned into a result: all of ``A``."""
    return state["bytes"]


def least_bytes(state: dict, out) -> int:
    """The fewest bytes one chip must move for one call: its rows of ``A``
    read once and its rows of ``Q`` written once."""
    return 2 * state["bytes"] // state["chips"]


def _blocks(m: int, want: int) -> int:
    """The largest block of at most ``want`` rows that divides ``m``."""
    b = min(want, m)
    while m % b:
        b -= 1
    return b


def positive_diagonal(r):
    """``R`` with each row's sign turned so that its diagonal is >= 0: the
    thin QR of a full-rank matrix is unique up to these signs."""
    return r * jnp.where(jnp.diagonal(r) < 0, -1.0, 1.0)[:, None]


@jax.jit
def _fold(a):
    """``R`` of ``A`` by Householder QRs of ``[R; block]``, block by block."""
    m, n = a.shape
    b = _blocks(m, REF_BLOCK_ROWS)
    with jax.default_matmul_precision("highest"):
        r = jax.lax.fori_loop(
            0, m // b,
            lambda i, r: jnp.linalg.qr(jnp.concatenate([r, jax.lax.dynamic_slice_in_dim(a, i * b, b)]), mode="r"),
            jnp.zeros((n, n), a.dtype),
        )
    return positive_diagonal(r)


def reference(state: dict):
    return jax.block_until_ready(_fold(state["a"].larray))


@jax.jit
def _errors(a, q, r, r_ref):
    """What the factors measure, a row block of ``A`` and ``Q`` at a time, at
    precision highest: ``max |Q^T Q - I|``, ``||A - Q R||_F / ||A||_F``,
    whether ``R`` is exactly zero below its diagonal, ``R`` against the
    reference's relative to its largest entry (both with a diagonal >= 0),
    whether all is finite."""
    m, n = a.shape
    b = _blocks(m, CHECK_BLOCK_ROWS)

    def step(i, carry):
        gram, resid_sq, norm_sq, finite = carry
        ab, qb = jax.lax.dynamic_slice_in_dim(a, i * b, b), jax.lax.dynamic_slice_in_dim(q, i * b, b)
        gram = gram + jax.lax.dot_general(qb, qb, (((0,), (0,)), ((), ())), precision=HI)
        resid_sq = resid_sq + jnp.sum(jnp.square(ab - jnp.matmul(qb, r, precision=HI)))
        return gram, resid_sq, norm_sq + jnp.sum(jnp.square(ab)), finite & jnp.all(jnp.isfinite(qb))

    gram, resid_sq, norm_sq, finite = jax.lax.fori_loop(
        0, m // b, step, (jnp.zeros((n, n), jnp.float32), jnp.float32(0), jnp.float32(0), jnp.bool_(True)))
    return {
        "orth_err": jnp.max(jnp.abs(gram - jnp.eye(n, dtype=jnp.float32))),
        "residual": jnp.sqrt(resid_sq / norm_sq),
        "below_diagonal_zero": jnp.all(jnp.tril(r, -1) == 0),
        "r_err": jnp.max(jnp.abs(positive_diagonal(r) - r_ref)) / jnp.max(jnp.abs(r_ref)),
        "finite": finite & jnp.all(jnp.isfinite(r)),
    }


def measure(a, q, r, r_ref) -> dict:
    """``_errors`` on plain arrays, as Python numbers (the control, which
    runs the program with other precisions, goes through here too)."""
    e = _errors(a, q, r, r_ref)
    return {"orth_err": float(e["orth_err"]), "residual": float(e["residual"]), "r_err": float(e["r_err"]),
            "below_diagonal_zero": bool(e["below_diagonal_zero"]), "finite": bool(e["finite"])}


def misses_of(e: dict, g: dict) -> list:
    misses = []
    if not e["finite"]:
        misses.append("Q or R is not finite")
    if not e["below_diagonal_zero"]:
        misses.append("R is not exactly zero below its diagonal")
    if not e["orth_err"] <= g["orthonormal"]:
        misses.append(f"Q^T Q off the identity by {e['orth_err']:.3e} > {g['orthonormal']:.1e}")
    if not e["residual"] <= g["residual"]:
        misses.append(f"||A - Q R||_F / ||A||_F {e['residual']:.3e} > {g['residual']:.1e}")
    if not e["r_err"] <= g["r"]:
        misses.append(f"R off the reference's by {e['r_err']:.3e} of max|R| > {g['r']:.1e}")
    return misses


def check(state: dict, out, ref) -> dict:
    """Hold the result to the configuration's guarantees. A miss is a line
    of text; none means the result is right."""
    q, r = out
    m, n = state["a"].shape
    if tuple(q.shape) != (m, n) or tuple(r.shape) != (n, n):
        raise ValueError(f"factor shapes {q.shape} {r.shape}")
    e = measure(state["a"].larray, q.larray, r.larray, ref)
    return {"measured": e, "misses": misses_of(e, state["cfg"]["guarantees"])}
