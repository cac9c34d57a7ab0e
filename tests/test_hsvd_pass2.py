"""Pass 2 of the two-pass hSVD sketch, ``z = A @ qw``: which form a
program is built with, and that the two forms agree.

Where pass 1 was the Pallas kernel (the chip) pass 2 is ONE dot; as the
tiled loop ``_pass2_tiles`` the chip's compiler hoists a bf16 cast of all
of A out of it (PERF.md, PR 26). Everywhere else it stays the tiled loop,
bit for bit, which the staged path's pins in ``test_staging.py`` rest on.
What only the chip's compiler can say (no bf16 copy of A in the compiled
program) is in ``test_chip_compile.py``."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import heat_tpu as ht
from heat_tpu.core.linalg import _pallas_sketch, svdtools
from heat_tpu.observability import telemetry


@pytest.fixture
def counters():
    """Telemetry on for the test; hands back a reader of the hsvd.pass2.* counters."""
    telemetry.reset()
    telemetry.enable()
    try:
        yield lambda: {
            k: v for k, v in telemetry.snapshot()["counters"].items() if k.startswith("hsvd.pass2.")
        }
    finally:
        telemetry.disable()
        telemetry.reset()


@pytest.mark.parametrize(
    "case, backend, x64, shape, sketch_l, dtype, form",
    [
        ("the chip, the cell's tiling", "tpu", False, (1024, 256), 25, jnp.float32, "one_dot"),
        ("the chip, a wide shard", "tpu", False, (256, 2048), 25, jnp.float32, "one_dot"),
        ("no chip", None, False, (1024, 256), 25, jnp.float32, "tiled"),
        ("x64 on", "tpu", True, (1024, 256), 25, jnp.float32, "tiled"),
        ("sketch wider than the kernel's pad", "tpu", False, (1024, 256), 40, jnp.float32, "tiled"),
        ("rows no tile divides", "tpu", False, (1000, 256), 25, jnp.float32, "tiled"),
        ("columns no tile divides", "tpu", False, (1024, 200), 25, jnp.float32, "tiled"),
        ("bf16 input", "tpu", False, (1024, 256), 25, jnp.bfloat16, "tiled"),
    ],
)
def test_pass2_form_follows_pass1(monkeypatch, counters, case, backend, x64, shape, sketch_l, dtype, form):
    """One dot exactly where ``sketch_with_norm`` serves pass 1; traced
    only (``eval_shape``), so the kernel need not run here."""
    if backend is not None:
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
    with jax.enable_x64(x64):
        out = jax.eval_shape(
            lambda a: svdtools._sketched_uds_both(a, 15, sketch_l, "both"),
            jax.ShapeDtypeStruct(shape, dtype),
        )
    assert out[0].shape == (shape[0], 15) and out[1].shape == (shape[1], 15)
    assert counters() == {f"hsvd.pass2.{form}": 1}


def _xla_sketch_with_norm(g, a):
    """What the kernel computes, in plain XLA: lets the CPU walk the
    chip's branch of ``_sketched_uds_both``."""
    return g @ a, jnp.sum(a * a)


@pytest.mark.parametrize("want", ["left", "right", "both"])
@pytest.mark.parametrize("shape", [(2048, 384), (384, 2048), (1100, 700)], ids=["tall", "wide", "tails"])
def test_one_dot_agrees_with_tiled(monkeypatch, shape, want):
    """The chip's branch (pass 1 + norm in one stream, pass 2 one dot)
    against the tiled streams on the same matrix: same factors, sigma and
    error estimate up to the order of the f32 sums."""
    m, n = shape
    k1, k2, k3 = jax.random.split(jax.random.key(m + n), 3)
    a = (jax.random.normal(k1, (m, 12), jnp.float32) * (0.7 ** jnp.arange(12))) @ jax.random.normal(
        k2, (12, n), jnp.float32
    ) + 1e-3 * jax.random.normal(k3, (m, n), jnp.float32)
    tiled = svdtools._sketched_uds_both(a, 8, 18, want)
    monkeypatch.setattr(_pallas_sketch, "sketch_with_norm", _xla_sketch_with_norm)
    text = str(jax.make_jaxpr(lambda x: svdtools._sketched_uds_both(x, 8, 18, want))(a))
    assert "while" not in text  # neither pass is a loop on this branch
    one_dot = svdtools._sketched_uds_both(a, 8, 18, want)
    for got, ref in zip(one_dot, tiled):
        assert (got is None) == (ref is None)
        if ref is None:
            continue
        if ref.ndim == 2:  # a factor: compare the projectors, signs are free
            got, ref = got @ got.T, ref @ ref.T
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-4, atol=2e-4 * float(jnp.max(jnp.abs(ref))))


def test_pass2_counter_counts_once_per_built_program(counters):
    """Through the public call on the CPU mesh: the tiled form, counted
    where the choice is made (the trace), so a second call adds nothing."""
    svdtools._sketched_single_rank_fn.cache_clear()
    a = ht.random.randn(523, 96, split=None)
    ht.linalg.hsvd_rank(a, 2, compute_sv=True)
    ht.linalg.hsvd_rank(a, 2, compute_sv=True)
    assert counters() == {"hsvd.pass2.tiled": 1}
