"""``ht.linalg.qr``'s local factorization (PR 34) against a plain Householder QR.

The plain reference is here: an unblocked Householder QR in ``jax.numpy`` at
precision highest, in the input's floating type (``benchmarks/ops/qr.py``
holds the benchmark's own reference). ``Q`` and ``R`` are compared up to the
signs of ``R``'s diagonal; every case also checks ``Q^T Q``, ``A - Q R``,
triangularity and finiteness.

Tier-1 runs on the CPU, where the public call's own choice is XLA's
Householder QR (LAPACK). The form the chip takes (``_gram_qr`` from twice as
many rows as columns) is reached through ``_local_qr`` with ``_gram_serves``
answering as it does on a TPU (the ``chips_form`` fixture): the function,
not a gate of the program.
"""

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import heat_tpu as ht
from heat_tpu.core.communication import MeshCommunication
from test_qr_kernels import K, kernels_form, small_blocks  # noqa: F401 (the kernels' module; fixtures: _gram_qr's tall products as the interpreted kernels)

Q = importlib.import_module("heat_tpu.core.linalg.qr")  # the package exports the function under that name
ht.use_x64()  # a CPU world runs x64: settle the policy before the first jax.numpy call here makes a float64

SHAPES = [(4096, 64), (1000, 37), (513, 1), (256, 256)]
# Limits, in units of the type's eps (f32: 1.2e-7, f64: 2.2e-16), each with its reason:
# - max |Q^T Q - I|: a Householder Q and a Cholesky step on a Gram matrix within 0.1 of I both leave a few eps
#   times a slowly growing function of n; 100 eps (1.2e-5 in f32) is ten times the largest reading here (9e-7)
#   and three hundred times under what one bf16 pass leaves (4e-3)
# - ||A - Q R||_F / ||A||_F: the same; the repair's perturbation is 8 eps in the spectral norm, under 40 eps in
#   Frobenius' at these widths
# - R and Q against the reference: both are unique up to signs and move by cond(A) eps; the seeded inputs have
#   condition numbers of 10 to 60 (uniform data: the mean is a rank-one part), so 1e4 eps (1.2e-3)
ORTH, RESIDUAL, AGAINST = 100.0, 100.0, 1e4
# (Every jit here wraps a fresh lambda: a jitted module function would hand a later case, with patched products
# or another choice of form, the program an earlier one traced.)


def comm_of(p):
    return MeshCommunication(jax.devices()[:p])


def seeded(m, n, dtype=np.float32, seed=0):
    rng = np.random.default_rng(1000 * m + n + seed)
    if np.issubdtype(dtype, np.integer):
        return rng.integers(-9, 10, (m, n)).astype(dtype)
    return rng.random((m, n)).astype(dtype)


def floating(a):
    return a.dtype if np.issubdtype(a.dtype, np.floating) else np.dtype(np.float32)


@jax.jit
def plain_householder(a):
    """Thin QR by n Householder reflections, one column at a time: R in a
    working copy, then Q = H_0 ... H_{n-1} [I; 0]."""
    m, n = a.shape
    rows = jnp.arange(m)
    hi = jax.lax.Precision.HIGHEST

    def reflect(x, v, tau):
        return x - tau * jnp.outer(v, jnp.matmul(v, x, precision=hi))

    def forward(j, carry):
        r, vs, taus = carry
        x = jnp.where(rows >= j, jnp.take(r, j, axis=1), 0)
        beta = -jnp.where(x[j] >= 0, 1.0, -1.0).astype(a.dtype) * jnp.sqrt(jnp.sum(x * x))
        v = x.at[j].add(-beta)
        vv = jnp.sum(v * v)
        tau = jnp.where(vv > 0, 2.0 / jnp.where(vv > 0, vv, 1), 0.0).astype(a.dtype)
        return reflect(r, v, tau), vs.at[:, j].set(v), taus.at[j].set(tau)

    r, vs, taus = jax.lax.fori_loop(0, n, forward, (a, jnp.zeros_like(a), jnp.zeros((n,), a.dtype)))
    q = jax.lax.fori_loop(0, n, lambda i, q: reflect(q, vs[:, n - 1 - i], taus[n - 1 - i]), jnp.eye(m, n, dtype=a.dtype))
    return q, jnp.triu(r[:n])


def positive(q, r):
    """The same factorization with diag(R) >= 0."""
    s = np.where(np.diagonal(r) < 0, -1.0, 1.0)
    return (None if q is None else q * s[None, :]), r * s[:, None]


def invariants(a, q, r, what=""):
    """Q^T Q, A - Q R, triangularity, finiteness: within the limits above, in float64 arithmetic."""
    eps = np.finfo(floating(a)).eps
    a, q, r = (np.asarray(x, np.float64) for x in (a, q, r))
    n = a.shape[1]
    assert q.shape == a.shape and r.shape == (n, n), (what, q.shape, r.shape)
    assert np.isfinite(q).all() and np.isfinite(r).all(), what
    assert (np.tril(r, -1) == 0).all(), f"{what}: R is not exactly zero below its diagonal"
    orth = np.abs(q.T @ q - np.eye(n)).max()
    assert orth <= ORTH * eps, f"{what}: max |Q^T Q - I| = {orth:.2e}"
    resid = np.linalg.norm(a - q @ r) / (np.linalg.norm(a) or 1.0)  # an all-zero A: Q R itself, absolute
    assert resid <= RESIDUAL * eps, f"{what}: ||A - Q R|| / ||A|| = {resid:.2e}"


def against_plain(a, q, r, what=""):
    f = floating(a)
    q_ref, r_ref = (np.asarray(x) for x in plain_householder(jnp.asarray(a, f)))
    q_ref, r_ref = positive(q_ref, r_ref)
    q, r = positive(None if q is None else np.asarray(q), np.asarray(r))
    tol = AGAINST * np.finfo(f).eps
    assert np.abs(r - r_ref).max() <= tol * np.abs(r_ref).max(), f"{what}: R off the plain reference's"
    if q is not None:
        assert np.abs(q - q_ref).max() <= tol, f"{what}: Q off the plain reference's"


@pytest.fixture
def chips_form(monkeypatch):
    """``_local_qr`` choosing as on a TPU, and the programs built under the
    CPU's own choice out of the caches (before and after)."""

    def on_a_tpu(m, n, dtype):
        return np.dtype(dtype) in (np.dtype(np.float32), np.dtype(np.float64)) and n >= 1 and m >= 2 * n

    def clear():
        Q._local_qr_fn.cache_clear()
        Q._tsqr_fn.cache_clear()

    clear()
    monkeypatch.setattr(Q, "_gram_serves", on_a_tpu)
    yield
    clear()


def gram_calls():
    return ht.telemetry.report()["counters"].get("qr.local.gram", 0)


@pytest.fixture
def counted():
    ht.telemetry.reset()
    ht.telemetry.enable()
    yield
    ht.telemetry.disable()
    ht.telemetry.reset()


# --------------------------------------------------------------------- #
# the form the chip takes, as a function                                 #
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("dtype", [np.float32, np.float64, np.int32], ids=lambda d: np.dtype(d).name)
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: f"{s[0]}x{s[1]}")
def test_chips_form_matches_plain_householder(shape, dtype, chips_form):
    a = seeded(*shape, dtype)
    x = jnp.asarray(a).astype(floating(a))  # qr() casts exact types to float32 before the program
    q, r = jax.jit(lambda v: Q._local_qr(v))(x)
    assert q.dtype == x.dtype and r.dtype == x.dtype
    invariants(np.asarray(x), q, r, "chip's form")
    against_plain(np.asarray(x), q, r, "chip's form")
    if shape[0] >= 2 * shape[1]:
        assert (np.diagonal(np.asarray(r)) > 0).all(), "the Gram form's R has a positive diagonal"


def test_the_gram_form_is_what_a_tall_block_takes_and_householder_a_near_square_one(chips_form):
    tall = str(jax.make_jaxpr(Q._local_qr)(jnp.ones((512, 64), jnp.float32)))
    square = str(jax.make_jaxpr(Q._local_qr)(jnp.ones((100, 64), jnp.float32)))
    assert "cholesky" in tall and "geqrf" not in tall and "householder_product" not in tall
    assert "cholesky" not in square
    assert not Q._gram_serves(512, 64, jnp.bfloat16)


@pytest.mark.parametrize("shape", [(4096, 64), (1000, 37)], ids=lambda s: f"{s[0]}x{s[1]}")
def test_chips_form_without_q_returns_the_same_r(shape, chips_form):
    a = jnp.asarray(seeded(*shape))
    none, r = jax.jit(lambda x: Q._local_qr(x, False))(a)
    _, r_with = jax.jit(lambda v: Q._local_qr(v))(a)
    assert none is None
    # the same arithmetic; the compiler may order a sum otherwise in the program without the last product
    np.testing.assert_allclose(np.asarray(r), np.asarray(r_with), rtol=0, atol=1e-5 * float(jnp.max(jnp.abs(r_with))))
    against_plain(np.asarray(a), None, r, "calc_q=False")


# --------------------------------------------------------------------- #
# the public call: the CPU's own choice, and the chip's                  #
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("split", [None, 0, 1])
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: f"{s[0]}x{s[1]}")
def test_public_call_matches_plain_householder(shape, split, devices):
    a = seeded(*shape)
    res = ht.linalg.qr(ht.array(a, split=split, comm=comm_of(devices)))
    assert res.Q.split == split and res.R.split == (1 if split == 1 else None)
    invariants(a, res.Q.numpy(), res.R.numpy(), f"split={split} on {devices}")
    against_plain(a, res.Q.numpy(), res.R.numpy(), f"split={split} on {devices}")


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("split", [None, 0])
@pytest.mark.parametrize("shape", [(4096, 64), (1000, 37)], ids=lambda s: f"{s[0]}x{s[1]}")
def test_public_call_in_the_chips_form(shape, split, devices, chips_form, counted):
    """One device and level 0 of TSQR go through the same ``_local_qr``:
    the counter of the form says so, and the result is the reference's."""
    a = seeded(*shape, seed=1)
    res = ht.linalg.qr(ht.array(a, split=split, comm=comm_of(devices)))
    assert gram_calls() == 1
    assert ht.telemetry.report()["counters"].get("qr.local.householder", 0) == 0
    if split == 0 and devices == 4:
        fn = Q._tsqr_fn(res.Q.comm.mesh, res.Q.comm.axis_name, -(-shape[0] // 4), shape[1], "float32", True)
        assert "cholesky" in fn.lower(jnp.zeros((4 * -(-shape[0] // 4), shape[1]), jnp.float32)).as_text()
    invariants(a, res.Q.numpy(), res.R.numpy(), f"split={split} on {devices}")
    against_plain(a, res.Q.numpy(), res.R.numpy(), f"split={split} on {devices}")


@pytest.mark.parametrize("split", [None, 0, 1])
def test_public_call_without_q(split, chips_form, counted):
    a = seeded(1000, 37, seed=2)
    res = ht.linalg.qr(ht.array(a, split=split, comm=comm_of(4)), calc_q=False)
    assert res.Q is None
    assert gram_calls() == (0 if split == 1 else 1)  # split=1 keeps XLA's QR on the global array
    against_plain(a, None, res.R.numpy(), f"calc_q=False split={split}")


@pytest.mark.parametrize("split", [None, 0])
def test_wide_input_keeps_the_old_path(split, chips_form, counted):
    a = seeded(24, 64, seed=3)
    res = ht.linalg.qr(ht.array(a, split=split, comm=comm_of(4)))
    assert gram_calls() == 0 and Q._local_qr_fn.cache_info().currsize == 0
    q, r = res.Q.numpy(), res.R.numpy()
    assert q.shape == (24, 24) and r.shape == (24, 64)
    np.testing.assert_allclose(q @ r, a, atol=1e-5)
    np.testing.assert_allclose(q.T @ q, np.eye(24), atol=1e-5)


def test_one_device_call_is_one_observed_program(chips_form, counted):
    x = ht.array(seeded(4096, 64, seed=4), comm=comm_of(1))
    ht.linalg.qr(x)
    ht.linalg.qr(x)
    counters = ht.telemetry.report()["counters"]
    assert counters["qr.local.miss"] == 1 and counters["qr.local.hit"] == 1
    assert Q._local_qr_fn.cache_info().currsize == 1


# --------------------------------------------------------------------- #
# what a Gram matrix cannot factor                                       #
# --------------------------------------------------------------------- #
def ill_conditioned(m, n, cond, dtype=np.float32):
    rng = np.random.default_rng(7)
    u, _ = np.linalg.qr(rng.standard_normal((m, n)))
    v, _ = np.linalg.qr(rng.standard_normal((n, n)))
    return ((u * np.logspace(0, -np.log10(cond), n)) @ v.T).astype(dtype)


def hard_cases():
    zero = seeded(4096, 64, seed=5)
    zero[:, 5] = 0
    equal = seeded(4096, 64, seed=6)
    equal[:, 40] = equal[:, 3]
    unit = np.zeros((4096, 64), np.float32)
    unit[:64] = np.eye(64)
    unit[:, 1] = unit[:, 0]  # the repair's noise must not rely on where the data is
    return {
        "cond_1e3": ill_conditioned(4096, 64, 1e3), "cond_1e6": ill_conditioned(4096, 64, 1e6),
        "cond_1e12_f64": ill_conditioned(4096, 64, 1e12, np.float64),
        "zero_column": zero, "equal_columns": equal, "rank_one": np.repeat(seeded(4096, 1, seed=8), 64, axis=1),
        "unit_vectors_repeated": unit, "all_zero": np.zeros((512, 16), np.float32),
    }


@pytest.mark.parametrize("how", ["function", "public"])
@pytest.mark.parametrize("case", sorted(hard_cases()))
def test_ill_conditioned_and_rank_deficient_input(case, how, chips_form):
    """Householder QR is backward stable whatever the input; so is this."""
    a = hard_cases()[case]
    if how == "function":
        q, r = jax.jit(lambda v: Q._local_qr(v))(jnp.asarray(a))
    else:
        res = ht.linalg.qr(ht.array(a, split=0, comm=comm_of(1)))
        q, r = res.Q.numpy(), res.R.numpy()
    invariants(a, q, r, case)
    assert (np.diagonal(np.asarray(r)) > 0).all()
    if case == "cond_1e3":  # R moves by cond eps: still comparable
        against_plain(a, q, r, case)


def test_a_repair_runs_only_where_it_has_to(monkeypatch):
    """The trip count of the repair loop, read from a run of the program
    whose ``while_loop`` tells it: none on well-conditioned input, some on
    the rest."""
    real, seen = jax.lax.while_loop, []

    def spy(cond, body, init):
        out = real(cond, body, init)
        if len(init) == 8:  # _gram_qr's repair loop carries eight values
            seen.append(int(out[3]))
        return out

    monkeypatch.setattr(jax.lax, "while_loop", spy)

    def repairs(a):
        seen.clear()
        jax.block_until_ready(Q._gram_qr(jnp.asarray(a)))
        return seen[0]

    cases = hard_cases()
    assert repairs(seeded(4096, 64)) == 0
    assert repairs(cases["cond_1e3"]) == 0
    assert 1 <= repairs(cases["cond_1e6"]) <= Q._MAX_REPAIRS
    assert 1 <= repairs(cases["equal_columns"]) <= Q._MAX_REPAIRS


def test_non_finite_input_ends_and_says_so():
    a = seeded(512, 16)
    a[7, 3] = np.inf
    q, r = jax.jit(lambda v: Q._gram_qr(v))(jnp.asarray(a))
    assert not np.isfinite(np.asarray(r)).all()


# --------------------------------------------------------------------- #
# the precision of the tall products                                     #
# --------------------------------------------------------------------- #
def _dots(jaxpr, found, kernels):
    """The ``dot_general``s of a jaxpr outside any kernel, and each kernel's
    (``pallas_call``) own, in program order."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            found.append(eqn)
        elif eqn.primitive.name == "pallas_call":
            kernels.append((eqn.params["name"], _dots(eqn.params["jaxpr"], [], [])[0]))
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _dots(sub, found, kernels)
    return found, kernels


def _stated(eqn):
    precision = eqn.params["precision"]
    return set(precision) if isinstance(precision, tuple) else {precision}


FORMS = ["whole_products", "kernels"]


@pytest.mark.parametrize("form", FORMS)
def test_no_tall_product_runs_at_one_bf16_pass(form, request):
    """On the chip an f32 product at ``Precision.DEFAULT`` is one bf16 pass.
    Every product over an operand with the block's rows states more: XLA's
    whole products by their precision, the kernels', which split their
    operands into bf16 parts themselves, by the terms they add up for each
    panel: at least three for the first Gram matrix and the finish, six for
    the apply and the Gram matrix of what it made."""
    kernels = form == "kernels"
    if kernels:
        request.getfixturevalue("kernels_form")
    n = 256 if kernels else 64
    found, in_kernels = _dots(jax.make_jaxpr(lambda v: Q._gram_qr(v))(jnp.ones((4096, n), jnp.float32)).jaxpr, [], [])
    tall = [e for e in found if any(d > 2 * n for v in e.invars for d in v.aval.shape)]
    for e in tall:
        assert _stated(e) <= {jax.lax.Precision.HIGH, jax.lax.Precision.HIGHEST}, e
    if not kernels:
        assert len(tall) >= 4 and not in_kernels  # Gram, apply, Gram, finish
        return
    assert not tall, "every product over the tall operand is in a kernel"
    assert [name for name, _ in in_kernels] == ["qr.tall.gram", "qr.tall.apply", "qr.tall.apply", "qr.tall.apply"]
    panels = n // K._PANEL
    passes = []
    for _, dots in in_kernels:  # first Gram; apply and Gram; the same in the repair loop; finish
        paid = 0
        for e in dots:  # a dot of bf16 parts is one pass, Mosaic's HIGHEST on f32 operands six
            f32 = [v.aval.dtype == jnp.float32 for v in e.invars]
            assert (not any(f32)) or (all(f32) and _stated(e) == {jax.lax.Precision.HIGHEST}), e
            paid += 6 if all(f32) else 1
        assert paid % panels == 0
        passes.append(paid // panels)
    assert passes[0] >= 3 and passes[-1] >= 3 and all(p >= 6 + 6 for p in passes[1:-1]), passes


@pytest.mark.parametrize("form", FORMS)
def test_one_bf16_pass_would_miss_the_limits(form, request, monkeypatch):
    """The same program with its tall products at one bf16 pass: the whole
    products on operands rounded to bf16 (what one MXU pass multiplies; the
    CPU ignores ``precision``), the kernels with one part an operand. The
    orthogonality limit of every case above catches it."""
    def bf16(x):
        return x.astype(jnp.bfloat16).astype(x.dtype)

    if form == "kernels":
        request.getfixturevalue("kernels_form")
    # one pass, whichever form the products take
    monkeypatch.setattr(Q, "_gram_of", lambda x, p: jax.lax.dot_general(bf16(x), bf16(x), (((0,), (0,)), ((), ()))))
    monkeypatch.setattr(Q, "_times", lambda x, w, p: jnp.matmul(bf16(x), bf16(w)))
    monkeypatch.setattr(Q, "_PASSES", dict.fromkeys(Q._PASSES, 1))
    n = 128 if form == "kernels" else 64
    a = seeded(4096, n)
    jaxpr = str(jax.make_jaxpr(lambda v: Q._gram_qr(v))(jnp.asarray(a)))
    assert ("pallas_call" in jaxpr) == (form == "kernels")
    q, r = (np.asarray(x, np.float64) for x in jax.jit(lambda v: Q._gram_qr(v))(jnp.asarray(a)))
    orth = np.abs(q.T @ q - np.eye(n)).max()
    resid = np.linalg.norm(a - q @ r) / np.linalg.norm(a)
    eps = np.finfo(np.float32).eps
    assert orth > ORTH * eps or resid > RESIDUAL * eps, (orth, resid)
    assert orth > 10 * ORTH * eps, f"one pass leaves {orth:.2e}: the limit {ORTH * eps:.1e} is not tight enough to matter"
