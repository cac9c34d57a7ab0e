"""The two kernels of ``heat_tpu/core/linalg/_pallas_qr.py`` (PR 35) on the CPU
(``interpret=True``), against the whole products they replace in
``qr._gram_qr`` (``_gram_of`` / ``_times`` at ``highest``; the CPU multiplies
in f32 whatever the precision says), and ``_gram_qr`` through them against its
whole-product form.

A grid step's row block is made small here (``rows`` a step), so that a few
hundred rows are several blocks with a ragged last one. What the chip's
compiler says of the kernels at the cell's shape is in
``tests/test_chip_compile.py``.
"""

import functools
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import heat_tpu as ht

Q = importlib.import_module("heat_tpu.core.linalg.qr")
K = importlib.import_module("heat_tpu.core.linalg._pallas_qr")
ht.use_x64()  # a CPU world runs x64: settle the policy before the first jax.numpy call here makes a float64

HI = jax.lax.Precision.HIGHEST
EPS = float(np.finfo(np.float32).eps)
WIDTHS = [256, 512, 1024, 384]  # 2, 4 and 8 panels of 128 columns, and 3
ROWS = 64  # of a grid step's block here


@pytest.fixture
def small_blocks(monkeypatch):
    """``rows`` of a block whatever ``n`` is, and the built kernels out of the caches (before and after)."""
    def clear():
        K._gram_call.cache_clear()
        K._apply_call.cache_clear()

    clear()
    monkeypatch.setattr(K, "row_block", lambda n: ROWS)
    monkeypatch.setattr(K, "_FOLD_ROWS", 2 * ROWS)  # a Gram matrix's first level of summing: two blocks
    yield
    clear()


def tall(m, n, seed=0):
    return np.random.default_rng(seed).standard_normal((m, n)).astype(np.float32)


def upper(n, seed=1, below=np.nan):
    """An upper triangular ``W`` with entries of size 1 / n whose blocks under
    the diagonal blocks hold ``below``: NaN says that a kernel read them."""
    w = np.triu(np.random.default_rng(seed).standard_normal((n, n))).astype(np.float32) / n
    p = K._PANEL
    for k in range(n // p):
        w[(k + 1) * p:, k * p:(k + 1) * p] = below
    return w


def close(got, want, passes, what):
    """Within what ``passes`` bf16 passes leave of a product of ``want``'s size: 2^-8 a pass-pair."""
    tol = {1: 2.0 ** -7, 3: 2.0 ** -15, 6: 64 * EPS}[passes]
    err = np.abs(np.asarray(got, np.float64) - want).max() / np.abs(want).max()
    assert err <= tol, f"{what}: {err:.2e} > {tol:.1e} at {passes} passes"
    return err


@pytest.mark.parametrize("passes", [1, 3, 6])
@pytest.mark.parametrize("m", [2 * ROWS, 3 * ROWS + 17], ids=["whole_blocks", "ragged_last_block"])
@pytest.mark.parametrize("n", WIDTHS)
def test_gram_kernel_against_the_whole_product(n, m, passes, small_blocks):
    x = tall(m, n)
    g = np.asarray(K.gram(jnp.asarray(x), passes, interpret=True))
    assert (g == g.T).all(), "the mirror is exact"
    want = np.asarray(Q._gram_of(jnp.asarray(x, jnp.float64), HI))
    err = close(g, want, passes, "x^T x")
    if passes == 1:
        assert err > 2.0 ** -12, "one pass is one pass"


@pytest.mark.parametrize("how", ["plain", "with_gram", "finish", "in_place", "finish_in_place"])
@pytest.mark.parametrize("m", [2 * ROWS, 3 * ROWS + 17], ids=["whole_blocks", "ragged_last_block"])
@pytest.mark.parametrize("n", WIDTHS)
def test_apply_kernel_against_the_whole_product(n, m, how, small_blocks):
    """``W``'s blocks under its diagonal blocks are NaN: a kernel that read
    one, to mask it after the product, would return NaN."""
    x, w = tall(m, n), upper(n)
    finish, in_place = "finish" in how, "in_place" in how
    passes = 3 if finish else 6
    fn = jax.jit(
        lambda x, w: K.apply(x, w, passes, 6 if how == "with_gram" else None, finish, in_place, interpret=True),
        donate_argnums=(0,) if in_place else (),
    )
    y, g = fn(jnp.asarray(x), jnp.asarray(w))
    assert np.isfinite(np.asarray(y)).all(), "a block under the diagonal blocks was read"
    x64, w64 = x.astype(np.float64), np.nan_to_num(w).astype(np.float64)
    want = np.asarray(Q._times(jnp.asarray(x64), jnp.asarray(w64), HI)) + (x64 if finish else 0)
    close(y, want, passes, how)
    if how == "with_gram":
        y64 = np.asarray(y, np.float64)
        assert (np.asarray(g) == np.asarray(g).T).all()
        close(g, y64.T @ y64, 6, "y^T y of the block just made")
    else:
        assert g is None


def test_split_parts_add_up_to_the_operand():
    x = jnp.asarray(tall(64, 128, seed=3))
    for passes, left in ((1, 2.0 ** -8), (3, 2.0 ** -16)):
        parts = K.split(x, passes)
        assert len(parts) == {1: 1, 3: 2}[passes] and all(p.dtype == jnp.bfloat16 for p in parts)
        rest = np.abs(np.asarray(x) - sum(np.asarray(p, np.float64) for p in parts)).max()
        assert rest <= left * np.abs(np.asarray(x)).max()
    assert K.split(x, 6) == [x], "six passes are Mosaic's HIGHEST on the operand itself"


# --------------------------------------------------------------------- #
# which inputs the kernels serve                                         #
# --------------------------------------------------------------------- #
def test_panels_serve_follows_backend_dtype_and_shape(monkeypatch):
    assert not Q._panels_serve(1 << 20, 1024, np.float32), "a CPU keeps XLA's products"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with jax.enable_x64(False):
        assert Q._panels_serve(1 << 20, 1024, np.float32)
        assert Q._panels_serve(2 * K.row_block(128), 128, np.float32)
        assert not Q._panels_serve(1 << 20, 1024, np.float64), "Mosaic has no f64"
        assert not Q._panels_serve(1 << 20, 1024, jnp.bfloat16)
        for n in (24, 60, 1000, 1100, 2048):
            assert not Q._panels_serve(1 << 20, n, np.float32), n
        assert not Q._panels_serve(K.row_block(1024), 1024, np.float32), "a single row block"
        assert Q._panels_serve(K.row_block(1024) + 1, 1024, np.float32)
    assert not Q._panels_serve(1 << 20, 1024, np.float32), "x64 on: Mosaic refuses 64-bit traces"


# --------------------------------------------------------------------- #
# _gram_qr through the kernels                                           #
# --------------------------------------------------------------------- #
@pytest.fixture
def kernels_form(small_blocks, monkeypatch):
    """``_gram_qr`` choosing the kernels as on a TPU, the kernels interpreted."""
    monkeypatch.setattr(Q, "_panels_serve", lambda m, n, dtype: np.dtype(dtype) == np.dtype(np.float32) and K.serves(m, n))
    monkeypatch.setattr(K, "gram", functools.partial(K.gram, interpret=True))
    monkeypatch.setattr(K, "apply", functools.partial(K.apply, interpret=True))


def kernels_in(fn, *args):
    return str(jax.make_jaxpr(lambda *a: fn(*a))(*args)).count("pallas_call")  # a fresh function: no trace from before a patch


def ill_conditioned(m, n, cond, dtype=np.float32):
    rng = np.random.default_rng(7)
    u, _ = np.linalg.qr(rng.standard_normal((m, n)))
    v, _ = np.linalg.qr(rng.standard_normal((n, n)))
    return ((u * np.logspace(0, -np.log10(cond), n)) @ v.T).astype(dtype)


def hard_cases(m=1000, n=128):
    uniform = np.random.default_rng(5).random((m, n)).astype(np.float32)
    equal = uniform.copy()
    equal[:, 40] = equal[:, 3]
    zero = uniform.copy()
    zero[:, 5] = 0
    return {
        "uniform": uniform, "uniform_256": np.random.default_rng(6).random((m, 256)).astype(np.float32),
        "cond_1e3": ill_conditioned(m, n, 1e3), "cond_1e6": ill_conditioned(m, n, 1e6),
        "zero_column": zero, "equal_columns": equal, "rank_one": np.repeat(uniform[:, :1], n, axis=1),
        "all_zero": np.zeros((m, n), np.float32),
    }


@pytest.mark.parametrize("case", sorted(hard_cases()))
def test_gram_qr_through_the_kernels_against_the_whole_products(case, kernels_form, monkeypatch):
    """The same algorithm on either form of its products: ``Q`` orthonormal,
    ``A = Q R`` and ``R`` triangular to the limits of ``tests/test_qr_local.py``;
    on input the Cholesky steps factor outright, the same ``R`` to rounding."""
    a = hard_cases()[case]
    n = a.shape[1]
    x = jnp.asarray(a)
    assert kernels_in(Q._gram_qr, x) >= 3  # Gram, apply + Gram, finish; the repair's inside its loop
    q, r = (np.asarray(v, np.float64) for v in jax.jit(lambda v: Q._gram_qr(v))(x))
    assert np.isfinite(q).all() and np.isfinite(r).all()
    assert (np.tril(r, -1) == 0).all() and (np.diagonal(r) > 0).all()
    orth = np.abs(q.T @ q - np.eye(n)).max()
    resid = np.linalg.norm(a - q @ r) / (np.linalg.norm(a) or 1.0)
    assert orth <= 100 * EPS and resid <= 100 * EPS, (case, orth, resid)
    if case in ("uniform", "uniform_256", "cond_1e3"):
        monkeypatch.setattr(Q, "_panels_serve", lambda m, n, dtype: False)
        assert kernels_in(Q._gram_qr, x) == 0
        _, r_whole = jax.jit(lambda v: Q._gram_qr(v))(x)
        np.testing.assert_allclose(r, np.asarray(r_whole), rtol=0, atol=2e3 * EPS * np.abs(r).max())


def test_f64_stays_on_the_whole_products(kernels_form):
    """Condition 1e12 needs f64, which the kernels do not serve: the predicate
    says so and the program holds none."""
    a = ill_conditioned(1000, 128, 1e12, np.float64)
    assert not Q._panels_serve(*a.shape, a.dtype)
    assert kernels_in(Q._gram_qr, jnp.asarray(a)) == 0
    q, r = (np.asarray(v) for v in jax.jit(lambda v: Q._gram_qr(v))(jnp.asarray(a)))
    assert np.abs(q.T @ q - np.eye(128)).max() <= 100 * np.finfo(np.float64).eps
    assert np.linalg.norm(a - q @ r) / np.linalg.norm(a) <= 100 * np.finfo(np.float64).eps


def test_counters_say_which_form_the_tall_products_have(kernels_form, monkeypatch):
    from heat_tpu.core.communication import MeshCommunication

    monkeypatch.setattr(Q, "_gram_serves", lambda m, n, dtype: np.dtype(dtype).kind == "f" and m >= 2 * n)
    Q._local_qr_fn.cache_clear()
    ht.telemetry.reset()
    ht.telemetry.enable()
    try:
        comm = MeshCommunication(jax.devices()[:1])
        for a in (hard_cases()["uniform"], hard_cases(1000, 60)["uniform"], hard_cases(100, 60)["uniform"]):
            res = ht.linalg.qr(ht.array(a, comm=comm))
            np.testing.assert_allclose(res.Q.numpy() @ res.R.numpy(), a, atol=1e-4)
        counters = ht.telemetry.report()["counters"]
    finally:
        ht.telemetry.disable()
        ht.telemetry.reset()
        Q._local_qr_fn.cache_clear()
    assert counters["qr.local.gram"] == 2 and counters["qr.local.householder"] == 1
    assert counters["qr.tall.kernel"] == 1 and counters["qr.tall.xla"] == 1
