"""``hsvd_rank`` on a split array over several devices: the one program of
the rank-budget call (``svdtools._dist_rank_fn``: level 0 on each device's
block as it lies, the merge, both factors, the error estimate).

On the CPU with 4 of the 8 virtual devices (the shape of the benchmark's
``hsvd-northstar-x4.4chip``, small): the factors against
``numpy.linalg.svd``, and four devices against one device on the same
matrix, which is what ties a device's share to the whole. What only the
chip's compiler can say (skinny products at ``highest``, no copy of the
block) is in ``test_chip_compile.py``."""

import numpy as np
import pytest

import jax

import heat_tpu as ht
from heat_tpu.core.communication import MeshCommunication
from heat_tpu.core.linalg import svdtools
from heat_tpu.observability import telemetry

RANK = 10
pytestmark = pytest.mark.skipif(len(jax.devices()) < 4, reason="needs four devices")


def low_rank(m, n, seed, sigma_max=200.0, decay=0.8, noise=1e-3):
    """The benchmark's data, small: a rank-10 signal with the spectrum
    ``sigma_max * decay^i`` plus Gaussian noise far below it."""
    rng = np.random.default_rng(seed)
    u, _ = np.linalg.qr(rng.standard_normal((m, RANK)))
    v, _ = np.linalg.qr(rng.standard_normal((n, RANK)))
    s = sigma_max * decay ** np.arange(RANK)
    return ((u * s) @ v.T + noise * rng.standard_normal((m, n))).astype(np.float32)


def comm_of(p):
    return MeshCommunication(jax.devices()[:p])


def factors(a, split, p):
    u, s, v, err = ht.linalg.hsvd_rank(ht.array(a, split=split, comm=comm_of(p)), RANK, compute_sv=True)
    assert (u.shape, s.shape, v.shape, err.shape) == ((a.shape[0], RANK), (RANK,), (a.shape[1], RANK), ())
    if p > 1:
        assert (u.split, s.split, v.split) == (0, None, 0)
    return u.numpy().astype(np.float64), s.numpy().astype(np.float64), v.numpy().astype(np.float64), float(err)


def measure(a, u, s, v, err):
    """The benchmark's four checks (``benchmarks/ops/hsvd_rank.py``)
    against ``numpy.linalg.svd`` in float64."""
    a = a.astype(np.float64)
    top = np.linalg.svd(a, compute_uv=False)
    norm = np.linalg.norm(a)
    eye = np.eye(RANK)
    resid = np.linalg.norm(a - (u * s) @ v.T) / norm
    return {
        "orth": max(np.abs(u.T @ u - eye).max(), np.abs(v.T @ v - eye).max()),
        "sigma": np.abs(s - top[:RANK]).max() / top[0],
        "resid_over_optimal": resid / (np.sqrt(np.sum(top[RANK:] ** 2)) / norm),
        "estimate_over_resid": err / resid,
    }


@pytest.fixture
def counters():
    telemetry.reset()
    telemetry.enable()
    try:
        yield lambda: {
            k: v for k, v in telemetry.snapshot()["counters"].items() if k.startswith("hsvd.dist.")
        }
    finally:
        telemetry.disable()
        telemetry.reset()


@pytest.mark.parametrize("seed", [11, 2147483659])
@pytest.mark.parametrize(
    "split, shape",
    [(0, (4 * 1024, 256)), (0, (4 * 1000 + 3, 200)), (1, (256, 4 * 1024))],
    ids=["rows_split", "rows_split_padded", "columns_split"],
)
def test_four_devices_against_numpy_svd(counters, split, shape, seed):
    """The configuration's guarantees at a small size: orthonormal factors,
    sigma, the residual against the optimum of its rank, and the estimate
    against the residual (within [0.95, 2] across devices: the
    hierarchical estimate adds the blocks' errors and the merge's). The
    CPU multiplies in f32, so the limits on the factors are tighter here
    than the chip's 1e-3."""
    a = low_rank(*shape, seed=seed)
    e = measure(a, *factors(a, split, 4))
    assert e["orth"] <= 1e-5
    assert e["sigma"] <= 1e-3
    assert 0.99 <= e["resid_over_optimal"] <= 2.0
    assert 0.95 <= e["estimate_over_resid"] <= 2.0
    assert counters() == {"hsvd.dist.merge.gather": 1, "hsvd.dist.u.local": 1}


@pytest.mark.parametrize("seed", [12, 2147483777])
def test_four_devices_agree_with_one_device(seed):
    """The share and the whole: on the same matrix, four devices and one
    give the same sigma (to 1e-3 of sigma_max) and span the same left and
    right subspaces (every cosine of the principal angles within 1e-3 of
    1). The tolerance is the sketch's: the two runs draw different range
    finders (one over all rows, four over a quarter each), and the
    weakest direction, 13 % of sigma_max over noise of 1e-3 an entry,
    moves by that much."""
    a = low_rank(4 * 1024, 256, seed=seed)
    u4, s4, v4, _ = factors(a, 0, 4)
    u1, s1, v1, _ = factors(a, 0, 1)
    assert np.abs(s4 - s1).max() / s1[0] <= 1e-3
    for f4, f1 in ((u4, u1), (v4, v1)):
        cos = np.linalg.svd(f4.T @ f1, compute_uv=False)
        assert 1.0 - cos.min() <= 1e-3


def test_wide_stack_merges_by_tsqr(counters):
    """Where the stacked factor is wider than one lane tile (here 8 devices
    x (12 + 5) columns = 136 > 128) the merge runs TSQR inside the same
    program; the factors keep the same guarantees."""
    if len(jax.devices()) < 8:
        pytest.skip("needs eight devices")
    a = low_rank(8 * 256 + 5, 160, seed=13)
    u, s, v, err = ht.linalg.hsvd_rank(ht.array(a, split=0), 12, compute_sv=True)
    assert counters() == {"hsvd.dist.merge.tsqr": 1, "hsvd.dist.u.local": 1}
    un, sn, vn = (x.numpy().astype(np.float64) for x in (u, s, v))
    top = np.linalg.svd(a.astype(np.float64), compute_uv=False)
    eye = np.eye(12)
    assert max(np.abs(un.T @ un - eye).max(), np.abs(vn.T @ vn - eye).max()) <= 1e-5
    assert np.abs(sn[:RANK] - top[:RANK]).max() / top[0] <= 1e-3
    resid = np.linalg.norm(a - (un * sn) @ vn.T) / np.linalg.norm(a)
    assert 0.95 * resid <= float(err) <= 2.0 * resid


def test_other_modes_keep_the_staged_path(counters):
    """Tolerance mode reads the merged spectrum on the host, so it stays on
    the staged path (level-0 program, TSQR merge, the complementary factor
    by ``A V / sigma``), and says so."""
    a = low_rank(4 * 1024, 256, seed=14)
    x = ht.array(a, split=0, comm=comm_of(4))
    u, s, v, err = ht.linalg.hsvd_rtol(x, 1e-2, compute_sv=True, maxrank=RANK)
    assert counters() == {"hsvd.dist.merge.tsqr": 1, "hsvd.dist.u.postprocess": 1}
    r = s.shape[0]
    un, vn = u.numpy().astype(np.float64), v.numpy().astype(np.float64)
    assert max(np.abs(un.T @ un - np.eye(r)).max(), np.abs(vn.T @ vn - np.eye(r)).max()) <= 1e-5


def test_one_program_per_call_and_no_read_back():
    """The whole call is one launch of one observed program: the builder's
    cache holds one entry after two calls, and the estimate stays a lazy
    0-d array on the mesh."""
    svdtools._dist_rank_fn.cache_clear()
    x = ht.array(low_rank(4 * 1024, 256, seed=15), split=0, comm=comm_of(4))
    for _ in range(2):
        u, err = ht.linalg.hsvd_rank(x, RANK)
    info = svdtools._dist_rank_fn.cache_info()
    assert (info.misses, info.hits, info.currsize) == (1, 1, 1)
    assert isinstance(err, ht.DNDarray) and err.shape == () and u.split == 0
