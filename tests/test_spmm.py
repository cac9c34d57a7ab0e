"""heat_tpu.kernels.spmm + sparse.DBCSR_matrix — the TPU-native sparse
engine (ISSUE 18 tentpole).

Five pins:

1. DBCSR round-trips: scipy/DCSR/dense -> (8,128) bricks -> back, with
   honest nnz / nbricks / occupancy metadata at every mesh size
   (including brick rows straddling device boundaries);
2. brick SpMM / SDDMM match the scipy oracle at both splits, for vector
   and matrix operands, with f32 accumulation for bf16 data;
3. kernel-on (Pallas, interpret on CPU) is BIT-IDENTICAL to kernel-off
   (the XLA oracle) — the accumulation stays in the same segment-sum,
   so the paths may not differ even in the last ulp;
4. the distributed programs are shard_map LOCAL: the collective census
   is zero for SpMM and SDDMM, and a SPLIT dense operand reshards
   through the redistribution planner BEFORE the local program;
5. the ``HEAT_TPU_SPMM_KERNEL`` escape hatch and the
   ``sparse.kernel.{hit,fallback}`` telemetry counters behave.
"""

import numpy as np
import pytest
import scipy.sparse as sp

import jax
import jax.numpy as jnp

import heat_tpu as ht
from heat_tpu.kernels import spmm as kspmm
from heat_tpu.sparse import BRICK_SHAPE, DBCSR_matrix, sparse_dbcsr_matrix, to_dbcsr

P = len(jax.devices())


@pytest.fixture
def kernel_mode(monkeypatch):
    def _set(mode):
        monkeypatch.setenv("HEAT_TPU_SPMM_KERNEL", mode)

    return _set


def _rand_csr(m, n, nnz, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, m, nnz)
    cols = rng.integers(0, n, nnz)
    csr = sp.csr_matrix(
        (rng.standard_normal(nnz).astype(dtype), (rows, cols)), shape=(m, n)
    )
    csr.sum_duplicates()
    return csr


class TestDBCSRFormat:
    def test_brick_shape_constant(self):
        assert BRICK_SHAPE == (8, 128)

    @pytest.mark.parametrize("split", [0, None])
    def test_from_scipy_round_trip(self, split):
        csr = _rand_csr(100, 300, 400, seed=1)
        A = sparse_dbcsr_matrix(csr, split=split)
        assert isinstance(A, DBCSR_matrix)
        assert A.shape == (100, 300)
        assert A.split == split
        assert A.nnz == csr.nnz
        # bricks tile the padded grid: ceil(100/8) x ceil(300/128)
        assert A.mb == 13 and A.nb == 3
        assert 0 < A.nbricks <= A.mb * A.nb
        assert 0.0 < A.occupancy <= 1.0
        np.testing.assert_allclose(A.todense().numpy(), csr.toarray())

    def test_to_dcsr_and_back(self):
        csr = _rand_csr(64, 256, 500, seed=2)
        A = sparse_dbcsr_matrix(csr, split=0)
        D = A.to_dcsr()
        assert D.nnz == csr.nnz
        np.testing.assert_allclose(np.asarray(D.data), csr.data, rtol=1e-6)
        # and DCSR -> DBCSR keeps the distribution
        A2 = to_dbcsr(D)
        assert A2.split == 0
        assert A2.nnz == csr.nnz
        np.testing.assert_allclose(A2.todense().numpy(), csr.toarray())

    def test_from_dense_dndarray(self):
        rng = np.random.default_rng(3)
        dense = (rng.random((40, 150)) < 0.05) * rng.standard_normal((40, 150))
        dense = dense.astype(np.float32)
        x = ht.array(dense, split=0)
        A = to_dbcsr(x)
        assert A.split == 0
        assert A.nnz == int(np.count_nonzero(dense))
        np.testing.assert_allclose(A.todense().numpy(), dense)

    def test_even_slabs_and_boundary_masks(self):
        """Physical slabs are mesh-even; straddle bricks are stored by
        both neighbors with disjoint row masks (no double counting)."""
        m = 8 * P + 4  # brick rows straddle device boundaries for odd P
        csr = _rand_csr(m, 256, 6 * m, seed=4)
        A = sparse_dbcsr_matrix(csr, split=0)
        bdata, bcol, brow, bmask = A._phys_components
        assert bdata.shape[0] == P * A.slab_bricks
        assert bmask.shape == (P * A.slab_bricks, 8)
        # ownership masks partition each brick row set: summing the
        # per-device mask over duplicates of a (brow) brick covers each
        # dense row at most once
        mask = np.asarray(jax.device_get(bmask))
        rows = np.asarray(jax.device_get(brow))
        cols = np.asarray(jax.device_get(bcol))
        cover = {}
        B = A.slab_bricks
        for r, (g0, g1, nreal) in enumerate(A._slab_meta):
            for t in range(r * B, r * B + nreal):
                key = (rows[t], cols[t])
                seen = cover.setdefault(key, np.zeros(8, bool))
                assert not (seen & mask[t]).any(), "row owned twice"
                seen |= mask[t]
        np.testing.assert_allclose(A.todense().numpy(), csr.toarray())

    def test_component_nbytes_prices_bricks_not_dense(self):
        csr = _rand_csr(512, 1024, 200, seed=5)
        A = sparse_dbcsr_matrix(csr, split=0)
        dense_bytes = 512 * 1024 * 4
        assert 0 < A.component_nbytes < dense_bytes

    def test_astype(self):
        csr = _rand_csr(32, 128, 60, seed=6)
        A = sparse_dbcsr_matrix(csr, split=0).astype(ht.bfloat16)
        assert A.dtype == ht.bfloat16
        np.testing.assert_allclose(
            A.todense().numpy().astype(np.float32), csr.toarray(), atol=0.02
        )

    def test_invalid_split(self):
        with pytest.raises(ValueError):
            sparse_dbcsr_matrix(_rand_csr(8, 128, 4), split=1)


class TestBrickSpMM:
    @pytest.mark.parametrize("split", [0, None])
    @pytest.mark.parametrize("k", [None, 1, 3, 16])
    def test_matches_scipy(self, split, k):
        csr = _rand_csr(90, 260, 700, seed=7)
        A = sparse_dbcsr_matrix(csr, split=split)
        rng = np.random.default_rng(8)
        shape = (260,) if k is None else (260, k)
        x = rng.standard_normal(shape).astype(np.float32)
        y = A @ x
        np.testing.assert_allclose(y.numpy(), csr @ x, rtol=1e-5, atol=1e-5)
        assert y.split == split
        assert y.gshape == ((90,) if k is None else (90, k))

    def test_empty_rows_and_all_zero_bricks(self):
        dense = np.zeros((40, 200), np.float32)
        dense[7, 130] = 3.0  # single brick, most rows empty
        A = sparse_dbcsr_matrix(sp.csr_matrix(dense), split=0)
        x = np.ones(200, np.float32)
        np.testing.assert_allclose((A @ x).numpy(), dense @ x)

    def test_bf16_accumulates_in_f32(self):
        csr = _rand_csr(64, 256, 2000, seed=9)
        A = sparse_dbcsr_matrix(csr, split=0).astype(ht.bfloat16)
        x = np.random.default_rng(10).standard_normal((256, 4)).astype(np.float32)
        y = A @ x
        ref = csr.toarray().astype(np.float32) @ x
        np.testing.assert_allclose(
            y.numpy().astype(np.float32), ref, rtol=5e-2, atol=5e-2
        )

    @pytest.mark.skipif(P < 2, reason="needs a real mesh")
    def test_split_dense_operand_reshards_by_plan(self):
        """A split-0 dense operand is legal: it rides the redistribution
        planner to replicated BEFORE the local program."""
        csr = _rand_csr(80, 256, 600, seed=11)
        A = sparse_dbcsr_matrix(csr, split=0)
        xnp = np.random.default_rng(12).standard_normal((256, 3)).astype(np.float32)
        y = A @ ht.array(xnp, split=0)
        np.testing.assert_allclose(y.numpy(), csr @ xnp, rtol=1e-5, atol=1e-5)

    def test_decide_paths_and_telemetry(self, kernel_mode):
        ht.telemetry.enable()
        try:
            ht.telemetry.reset()
            kernel_mode("1")
            assert kspmm.decide("spmm", 4, 2, "float32") == "pallas"
            kernel_mode("0")
            assert kspmm.decide("spmm", 4, 2, "float32") == "xla"
            kernel_mode("auto")  # off-TPU: the oracle wins without timing
            assert kspmm.decide("spmm", 4, 2, "float32") == "xla"
            counters = ht.telemetry.snapshot()["counters"]
            assert counters.get("sparse.kernel.hit", 0) >= 1
            assert counters.get("sparse.kernel.fallback", 0) >= 2
        finally:
            ht.telemetry.disable()
            ht.telemetry.reset()

    @pytest.mark.parametrize("k", [None, 1, 2, 5])
    def test_kernel_on_equals_off_bitwise(self, kernel_mode, k):
        """The acceptance pin: HEAT_TPU_SPMM_KERNEL=1 (Pallas, interpret
        on CPU) produces byte-identical results to =0 (XLA oracle) —
        including k=1, which pads to the matmul codepath to dodge the
        matvec reduction-order divergence."""
        csr = _rand_csr(100, 300, 900, seed=13)
        A = sparse_dbcsr_matrix(csr, split=0 if P > 1 else None)
        shape = (300,) if k is None else (300, k)
        x = np.random.default_rng(14).standard_normal(shape).astype(np.float32)
        kernel_mode("0")
        y0 = (A @ x).numpy()
        kernel_mode("1")
        y1 = (A @ x).numpy()
        np.testing.assert_array_equal(y0.view(np.uint32), y1.view(np.uint32))


    def test_autotune_records_a_refused_kernel(self):
        """The TPU autotuner compiles the brick kernel out of interpret
        mode; a backend that refuses it (here: the CPU has no Mosaic)
        leaves the oracle serving and the refusal, in the compiler's
        words, in the decision — not a silent fallback."""
        sig = ("spmm", 16, 4, "float32")
        try:
            d = kspmm._autotune(sig)
            assert d["path"] == "xla" and "autotuned" not in d
            assert d["why"].startswith("autotune: pallas refused: ")
            assert len(d["why"]) > len("autotune: pallas refused: X")
        finally:
            kspmm._AUTOTUNE.pop(sig, None)


class TestSDDMM:
    def _setup(self, split, seed=15, dtype=np.float32):
        csr = _rand_csr(70, 260, 500, seed=seed, dtype=dtype)
        S = sparse_dbcsr_matrix(csr, split=split)
        rng = np.random.default_rng(seed + 1)
        u = rng.standard_normal((70, 6)).astype(dtype)
        v = rng.standard_normal((260, 6)).astype(dtype)
        return csr, S, u, v

    @pytest.mark.parametrize("split", [0, None])
    def test_matches_dense_oracle(self, split):
        csr, S, u, v = self._setup(split)
        C = ht.sparse.sddmm(S, u, v)
        assert isinstance(C, DBCSR_matrix)
        assert C.nnz == S.nnz and C.nbricks == S.nbricks
        # only the stored PATTERN of S carries values; compare on it
        ref = csr.toarray() * 0
        mask = csr.toarray() != 0
        ref[mask] = (csr.toarray() * (u @ v.T))[mask]
        got = C.todense().numpy() * mask  # pattern-restricted comparison
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)

    def test_kernel_on_equals_off_bitwise(self, kernel_mode):
        csr, S, u, v = self._setup(0 if P > 1 else None, seed=17)
        kernel_mode("0")
        c0 = np.asarray(jax.device_get(ht.sparse.sddmm(S, u, v)._phys_components[0]))
        kernel_mode("1")
        c1 = np.asarray(jax.device_get(ht.sparse.sddmm(S, u, v)._phys_components[0]))
        np.testing.assert_array_equal(c0.view(np.uint32), c1.view(np.uint32))

    def test_d1_pads_to_matmul_codepath(self, kernel_mode):
        csr, S, _, _ = self._setup(0 if P > 1 else None, seed=19)
        rng = np.random.default_rng(20)
        u = rng.standard_normal((70, 1)).astype(np.float32)
        v = rng.standard_normal((260, 1)).astype(np.float32)
        kernel_mode("0")
        c0 = np.asarray(jax.device_get(ht.sparse.sddmm(S, u, v)._phys_components[0]))
        kernel_mode("1")
        c1 = np.asarray(jax.device_get(ht.sparse.sddmm(S, u, v)._phys_components[0]))
        np.testing.assert_array_equal(c0.view(np.uint32), c1.view(np.uint32))

    def test_shape_validation(self):
        _, S, u, v = self._setup(None)
        with pytest.raises(ValueError):
            ht.sparse.sddmm(S, u[:10], v)
        with pytest.raises(ValueError):
            ht.sparse.sddmm(S, u, v[:, :3])
        with pytest.raises(TypeError):
            ht.sparse.sddmm(np.zeros((3, 3)), u, v)


@pytest.mark.skipif(P < 2, reason="needs a real mesh")
class TestDistributedCensusPin:
    """ISSUE 18 acceptance: the distributed brick programs are LOCAL —
    zero collectives in the compiled SpMM and SDDMM, on both paths."""

    def _spmm_census(self, mode, monkeypatch):
        monkeypatch.setenv("HEAT_TPU_SPMM_KERNEL", mode)
        csr = _rand_csr(16 * P, 512, 64 * P, seed=21)
        A = sparse_dbcsr_matrix(csr, split=0)
        bdata, bcol, brow, bmask = A._phys_components
        x2d = jnp.asarray(
            np.random.default_rng(22).standard_normal((512, 4)).astype(np.float32)
        )
        path = kspmm.decide("spmm", A.slab_bricks, 4, "float32")
        prog = kspmm.spmm_bcsr_program(
            A.comm, A.shape[0], A.nb, A.slab_bricks, 0, 2, "float32", path
        )
        return ht.observability.collective_counts(prog, bdata, bcol, brow, bmask, x2d)

    @pytest.mark.parametrize("mode", ["0", "1"])
    def test_spmm_zero_collectives(self, mode, monkeypatch):
        rep = self._spmm_census(mode, monkeypatch)
        assert all(v == 0 for v in rep.counts.values()), rep.counts

    @pytest.mark.parametrize("mode", ["0", "1"])
    def test_sddmm_zero_collectives(self, mode, monkeypatch):
        monkeypatch.setenv("HEAT_TPU_SPMM_KERNEL", mode)
        csr = _rand_csr(16 * P, 512, 64 * P, seed=23)
        S = sparse_dbcsr_matrix(csr, split=0)
        sdata, bcol, brow, _ = S._phys_components
        rng = np.random.default_rng(24)
        u = jnp.asarray(rng.standard_normal((S.shape[0], 4)).astype(np.float32))
        v = jnp.asarray(rng.standard_normal((S.shape[1], 4)).astype(np.float32))
        path = kspmm.decide("sddmm", S.slab_bricks, 4, "float32")
        prog = kspmm.sddmm_bcsr_program(
            S.comm, S.mb, S.nb, S.slab_bricks, 0, "float32", path
        )
        rep = ht.observability.collective_counts(prog, sdata, bcol, brow, u, v)
        assert all(v == 0 for v in rep.counts.values()), rep.counts

    def test_spmv_result_matches_oracle_distributed(self, monkeypatch):
        """Executed distributed result (not just the census) stays on
        the scipy oracle at the mesh size CI runs (8 and 5)."""
        monkeypatch.setenv("HEAT_TPU_SPMM_KERNEL", "1")
        csr = _rand_csr(16 * P + 3, 384, 900, seed=25)
        A = sparse_dbcsr_matrix(csr, split=0)
        x = np.random.default_rng(26).standard_normal(384).astype(np.float32)
        np.testing.assert_allclose(
            (A @ x).numpy(), csr @ x, rtol=1e-5, atol=1e-5
        )
