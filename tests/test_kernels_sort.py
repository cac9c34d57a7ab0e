"""heat_tpu.kernels.sort — the TPU-native local radix/columnsort engines
(ISSUE 4 tentpole).

Four pins:

1. the key transform is a monotone bijection matching ``lax.sort``'s
   comparator order exactly (±0, ±inf, NaN payloads, subnormals, the
   full i32 range);
2. every kernel engine (XLA radix, Pallas block kernel in interpret
   mode, blocked columnsort) is STABLE-ARGSORT-IDENTICAL to the
   ``lax.sort`` oracle on adversarial inputs;
3. the distributed sort's collective census is UNTOUCHED by the kernel
   wiring (kernel-on HLO == kernel-off HLO collective-for-collective,
   zero all-gathers) and its numerics are bit-identical — the kernel
   only replaced local compute;
4. the ``HEAT_TPU_SORT_KERNEL`` escape hatch and the
   ``sort.kernel.{hit,fallback}`` telemetry counters behave.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import heat_tpu as ht
from heat_tpu.kernels import sort as ksort

P = len(jax.devices())


@pytest.fixture
def kernel_mode(monkeypatch):
    def _set(mode):
        monkeypatch.setenv("HEAT_TPU_SORT_KERNEL", mode)

    return _set


def _adversarial(kind: str, n: int, dtype=np.float32) -> np.ndarray:
    rng = np.random.default_rng({"random": 0, "sorted": 1, "reverse": 2,
                                 "const": 3, "fewuniq": 4, "nan": 5}[kind])
    if np.issubdtype(dtype, np.floating):
        x = rng.standard_normal(n).astype(dtype)
    else:
        x = rng.integers(np.iinfo(dtype).min, np.iinfo(dtype).max, n, dtype=dtype)
    if kind == "sorted":
        x = np.sort(x)
    elif kind == "reverse":
        x = np.sort(x)[::-1].copy()
    elif kind == "const":
        x = np.full(n, x.flat[0])
    elif kind == "fewuniq":
        x = x[rng.integers(0, 7, n)]
    elif kind == "nan":
        x[rng.random(n) < 0.15] = np.nan
    return x


def _oracle(x: jnp.ndarray):
    iota = jnp.arange(x.shape[0], dtype=jnp.int32)
    return jax.lax.sort((x, iota), num_keys=1, is_stable=True)


def _assert_sorted_equal(got_v, got_i, ref_v, ref_i, dtype):
    """Indices must match the oracle EXACTLY (the argsort contract);
    values must match under the comparator's equality (bit-equal except
    NaN slots, where the kernel paths canonicalize the payload)."""
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(ref_i))
    gv, rv = np.asarray(got_v), np.asarray(ref_v)
    if np.issubdtype(np.dtype(dtype), np.floating):
        np.testing.assert_array_equal(np.isnan(gv), np.isnan(rv))
        m = ~np.isnan(rv)
        np.testing.assert_array_equal(gv[m], rv[m])
    else:
        np.testing.assert_array_equal(gv, rv)


class TestKeyTransform:
    """Property tests for the to_sortable/from_sortable bijection."""

    F32_SPECIALS = np.array(
        [
            0x00000000, 0x80000000,              # +0, -0
            0x7F800000, 0xFF800000,              # +inf, -inf
            0x7FC00000, 0xFFC00000,              # quiet NaN, -NaN
            0x7F800001, 0x7FFFFFFF, 0xFFFFFFFF,  # NaN payload extremes
            0x00000001, 0x007FFFFF,              # subnormal min/max
            0x00800000,                          # smallest normal
            0x7F7FFFFF, 0xFF7FFFFF,              # +-float32 max
            0x3F800000, 0xBF800000,              # +-1.0
        ],
        dtype=np.uint32,
    )

    def test_f32_roundtrip_and_tie_classes(self):
        x = jax.lax.bitcast_convert_type(jnp.asarray(self.F32_SPECIALS), jnp.float32)
        u = ksort.to_sortable(x)
        back = np.asarray(
            jax.lax.bitcast_convert_type(ksort.from_sortable(u, jnp.float32), jnp.uint32)
        )
        for pat, got in zip(self.F32_SPECIALS, back):
            if (pat & 0x7FFFFFFF) > 0x7F800000:   # NaN class: stays NaN
                assert (got & 0x7FFFFFFF) > 0x7F800000
            elif pat == 0x80000000:               # -0 canonicalizes to +0
                assert got == 0x00000000
            else:                                  # everything else: bit-exact
                assert got == pat

    def test_f32_order_matches_lax_comparator(self):
        rng = np.random.default_rng(0)
        x = np.concatenate(
            [
                self.F32_SPECIALS.view(np.float32),
                rng.standard_normal(500).astype(np.float32),
            ]
        )
        xj = jnp.asarray(x)
        _, oracle_idx = _oracle(xj)
        u = np.asarray(ksort.to_sortable(xj))
        np.testing.assert_array_equal(np.argsort(u, kind="stable"), np.asarray(oracle_idx))

    def test_subnormal_order_is_strict_refinement(self):
        """XLA's comparator runs on FTZ hardware and TIES every subnormal
        with zero; the transform keeps the strict IEEE magnitude order —
        a refinement: any transform-ordered array is still sorted under
        XLA's comparator, and values round-trip bit-exact."""
        rng = np.random.default_rng(2)
        x = (rng.standard_normal(200) * 1e-42).astype(np.float32)
        x[::17] = 0.0
        x[1::17] = -0.0
        xj = jnp.asarray(x)
        u = ksort.to_sortable(xj)
        back = np.asarray(
            jax.lax.bitcast_convert_type(ksort.from_sortable(u, jnp.float32), jnp.uint32)
        )
        keep = x.view(np.uint32) != 0x80000000  # -0 canonicalizes
        np.testing.assert_array_equal(back[keep], x.view(np.uint32)[keep])
        # strict numeric order (upcast to f64 where subnormals are exact)
        order = np.argsort(np.asarray(u), kind="stable")
        np.testing.assert_array_equal(
            order, np.argsort(x.astype(np.float64), kind="stable")
        )

    @pytest.mark.parametrize("dtype", [np.int32, np.int8, np.int16, np.uint32, np.uint8])
    def test_int_bijection_and_order(self, dtype):
        info = np.iinfo(dtype)
        rng = np.random.default_rng(1)
        x = np.concatenate(
            [
                np.array([info.min, info.min + 1, -1 if info.min < 0 else 0, 0, 1, info.max - 1, info.max], dtype=dtype),
                rng.integers(info.min, info.max, 300, dtype=dtype, endpoint=True),
            ]
        )
        xj = jnp.asarray(x)
        u = ksort.to_sortable(xj)
        np.testing.assert_array_equal(np.asarray(ksort.from_sortable(u, dtype)), x)
        np.testing.assert_array_equal(
            np.argsort(np.asarray(u), kind="stable"), np.argsort(x, kind="stable")
        )

    def test_unsupported_dtype_not_transformable(self):
        assert not ksort.transformable(jnp.complex64)


ENGINE_KINDS = ["random", "sorted", "reverse", "const", "fewuniq", "nan"]


class TestEngineParity:
    """Stable-argsort parity of every engine vs the lax.sort oracle."""

    @pytest.mark.parametrize("kind", ENGINE_KINDS)
    def test_radix_xla(self, kind):
        x = jnp.asarray(_adversarial(kind, 999))
        u = ksort.to_sortable(x)
        idx = jnp.arange(999, dtype=jnp.int32)
        su, si = ksort._radix_sort_xla((0, 1), (u, idx), (4, 4))
        ov, oi = _oracle(x)
        _assert_sorted_equal(ksort.from_sortable(su, x.dtype), si, ov, oi, np.float32)

    @pytest.mark.parametrize("kind", ENGINE_KINDS)
    def test_pallas_block_interpret(self, kind):
        """The Pallas kernel logic on CPU via interpret=True — histogram,
        triangular-matmul scan, rank and one-hot permutation matmul are
        the exact ops the TPU lowering runs."""
        n = 509  # non-multiple of the 512 block: exercises sentinel padding
        x = jnp.asarray(_adversarial(kind, n))
        u = ksort.to_sortable(x)
        su, si = ksort._pallas_pair_sort(u, jnp.arange(n, dtype=jnp.uint32))
        ov, oi = _oracle(x)
        _assert_sorted_equal(
            ksort.from_sortable(su, x.dtype), si.astype(jnp.int32), ov, oi, np.float32
        )

    @pytest.mark.parametrize("kind", ENGINE_KINDS)
    @pytest.mark.parametrize("n", [1600, 1601, 6500])
    def test_columnsort_local(self, kind, n):
        x = jnp.asarray(_adversarial(kind, n))
        u = ksort.to_sortable(x)
        idx = jnp.arange(n, dtype=jnp.int32)
        p, b = ksort._columnsort_p(n)
        assert p is not None and b % p == 0 and b >= 2 * (p - 1) ** 2
        su, si = ksort._columnsort_local((u, idx), 2, p, b, n)
        ov, oi = _oracle(x)
        _assert_sorted_equal(ksort.from_sortable(su, x.dtype), si, ov, oi, np.float32)

    def test_columnsort_scrambled_second_key(self):
        """The distributed programs sort (value, global-position) pairs
        whose positions are NOT presorted — the 2-key lexicographic
        contract must hold for arbitrary index operands."""
        rng = np.random.default_rng(7)
        n = 3200
        v = jnp.asarray(rng.integers(0, 5, n).astype(np.int32))
        i = jnp.asarray(rng.permutation(n).astype(np.int32))
        got = ksort.block_sort((v, i), 0, num_keys=2, impl="1")
        ref = jax.lax.sort((v, i), num_keys=2)
        for g, r in zip(got, ref):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(r))

    @pytest.mark.parametrize("dtype", [np.int32, np.uint32])
    def test_engines_int_dtypes(self, dtype):
        x = jnp.asarray(_adversarial("random", 2100, dtype))
        idx = jnp.arange(2100, dtype=jnp.int32)
        got = ksort.block_sort((x, idx), 0, num_keys=2, impl="1")
        ref = jax.lax.sort((x, idx), num_keys=2)
        for g, r in zip(got, ref):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(r))

    def test_values_only_block_sort(self):
        x = jnp.asarray(_adversarial("fewuniq", 3000))
        (got,) = ksort.block_sort((x,), 0, num_keys=1, impl="1")
        (ref,) = jax.lax.sort((x,), is_stable=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    @pytest.mark.parametrize("n", [300, 5000])
    def test_type_max_keys_survive_sentinel_padding(self, n):
        """Regression (code review): real (NaN/type-max key, index) pairs
        must sort BEFORE the engines' internal sentinel pads — the pad
        tuple is all-max, and a real index never reaches its type-max —
        so the [:n] truncation can only ever drop pads."""
        rng = np.random.default_rng(13)
        v = rng.standard_normal(n).astype(np.float32)
        v[-3:] = np.nan                      # ties the key with the pad sentinel
        gi = jnp.asarray(np.arange(n, dtype=np.int32) + 50_000)  # offset indices
        got = ksort.block_sort((jnp.asarray(v), gi), 0, num_keys=2, impl="1")
        ref = jax.lax.sort((jnp.asarray(v), gi), num_keys=2)
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(ref[1]))
        np.testing.assert_array_equal(
            np.isnan(np.asarray(got[0])), np.isnan(np.asarray(ref[0]))
        )

    def test_pallas_pair_full_width_second_key(self):
        """Regression (code review): tied first keys whose second keys
        differ only ABOVE bit 15 (indices ≥ 65536) must still order by
        the full 32-bit second key on the Pallas block path."""
        n = 300
        v = jnp.zeros((n,), jnp.float32)
        gi = jnp.asarray((np.arange(n)[::-1] * 300 + 1).astype(np.int32))  # up to 89701
        got = ksort.block_sort((v, gi), 0, num_keys=2, impl="1")
        ref = jax.lax.sort((v, gi), num_keys=2)
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(ref[1]))


class TestDispatch:
    """local_sort dispatcher: gates, escape hatch, descending one-pass,
    telemetry counters."""

    def test_escape_hatch_is_oracle_identical(self, kernel_mode):
        x = jnp.asarray(_adversarial("random", 4000))
        kernel_mode("0")
        v0, i0 = ksort.local_sort(x)
        ov, oi = _oracle(x)
        np.testing.assert_array_equal(np.asarray(v0), np.asarray(ov))
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(oi))
        kernel_mode("1")
        v1, i1 = ksort.local_sort(x)
        _assert_sorted_equal(v1, i1, ov, oi, np.float32)

    @pytest.mark.parametrize("kind", ["random", "fewuniq", "const"])
    @pytest.mark.parametrize("mode", ["0", "1"])
    def test_descending_one_pass_stable(self, kind, mode, kernel_mode):
        """The descending satellite: one sort on the complemented
        transform must equal the old two-pass stable-descending argsort
        (ties in original order) — on both the oracle and kernel paths."""
        kernel_mode(mode)
        x = jnp.asarray(_adversarial(kind, 3000))
        v, i = ksort.local_sort(x, descending=True)
        ref_i = jnp.argsort(x, descending=True, stable=True)
        np.testing.assert_array_equal(np.asarray(i), np.asarray(ref_i))
        np.testing.assert_array_equal(
            np.asarray(v), np.asarray(jnp.take_along_axis(x, ref_i, axis=0))
        )

    def test_escape_hatch_descending_preserves_value_bits(self, kernel_mode):
        """Regression (code review): HEAT_TPU_SORT_KERNEL=0 must restore
        the PRE-kernel two-pass descending route byte-identically —
        including -0.0's sign bit, which the transform-based one-pass
        canonicalizes."""
        kernel_mode("0")
        x = np.array([-0.0, 1.0, 0.0, -1.0], dtype=np.float32)
        v, _ = ksort.local_sort(jnp.asarray(x), descending=True)
        ref_i = np.asarray(jnp.argsort(jnp.asarray(x), descending=True, stable=True))
        np.testing.assert_array_equal(
            np.asarray(v).view(np.uint32), x[ref_i].view(np.uint32)
        )

    def test_ht_sort_descending_ties_match_two_pass(self, kernel_mode):
        kernel_mode("0")
        x = np.array([3.0, 1.0, 3.0, 2.0, 1.0, 3.0], dtype=np.float32)
        v, i = ht.sort(ht.array(x), descending=True)
        ref = np.argsort(-x, kind="stable")
        np.testing.assert_array_equal(i.numpy(), ref)
        np.testing.assert_array_equal(v.numpy(), x[ref])

    def test_multidim_descending(self, kernel_mode):
        kernel_mode("0")
        x = jnp.asarray(np.random.default_rng(3).integers(0, 4, (8, 16)).astype(np.int32))
        v, i = ksort.local_sort(x, axis=1, descending=True)
        ref_i = jnp.argsort(x, axis=1, descending=True, stable=True)
        np.testing.assert_array_equal(np.asarray(i), np.asarray(ref_i))

    def test_telemetry_counters(self, kernel_mode):
        ht.telemetry.enable()
        try:
            ht.telemetry.reset()
            x = jnp.asarray(_adversarial("random", 1000))
            kernel_mode("1")
            ksort.local_sort(x)
            kernel_mode("0")
            ksort.local_sort(x)
            counters = ht.telemetry.snapshot()["counters"]
            assert counters.get("sort.kernel.hit", 0) >= 1
            assert counters.get("sort.kernel.fallback", 0) >= 1
        finally:
            ht.telemetry.disable()
            ht.telemetry.reset()

    def test_forced_decision_does_not_poison_autotune(self, kernel_mode, monkeypatch):
        """Regression (code review): a path cached by a FORCED kernel
        call carries no timing evidence — auto mode must not reuse it
        (only entries the autotuner wrote may answer for auto)."""
        kernel_mode("auto")
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        n = 1 << 22
        key = (n, "float32", "pairs")
        try:
            ksort._DECISIONS[key] = {"path": "columnsort", "forced": True}
            # tracing context (concrete=False): no autotune possible, and
            # the forced entry must be ignored -> the oracle serves
            assert ksort._decide(n, "float32", concrete=False) == "lax"
            ksort._DECISIONS[key] = {"path": "columnsort", "autotuned": True,
                                     "timings": {}}
            assert ksort._decide(n, "float32", concrete=False) == "columnsort"
        finally:
            ksort._DECISIONS.pop(key, None)

    def test_autotune_records_a_refused_candidate(self, monkeypatch):
        """A candidate the backend refuses to lower is written into
        last_decisions() with the compiler's words — never a bare inf
        that reads like a slow timing — and the rest are still timed."""
        n = 1 << 14  # past the radix gates: columnsort is the kernel path
        real = ksort._run_pair_path

        def refuse(u, idx, *, path, n):
            if path == "columnsort":
                raise NotImplementedError("Mosaic says no")
            return real(u, idx, path=path, n=n)

        monkeypatch.setattr(ksort, "_run_pair_path", refuse)
        key = (n, "float32", "pairs")
        try:
            assert ksort._autotune(n, "float32") == "lax"
            dec = ksort.last_decisions()[key]
            assert dec["refused"] == {"columnsort": "NotImplementedError: Mosaic says no"}
            assert list(dec["timings"]) == ["lax"] and dec["timings"]["lax"] < float("inf")
        finally:
            ksort._DECISIONS.pop(key, None)

    def test_sort_plan_models(self):
        lax_plan = ksort.sort_plan(1 << 27, "float32", path="lax")
        col_plan = ksort.sort_plan(1 << 27, "float32", path="columnsort")
        radix_plan = ksort.sort_plan(400, "float32", path="radix_xla")
        assert lax_plan["passes"] > col_plan["passes"] > radix_plan["passes"]
        for plan in (lax_plan, col_plan, radix_plan):
            assert plan["hbm_bytes"] > 0 and plan["model"]

    def test_pallas_gate_is_shape_level(self):
        assert ksort.pallas_serviceable(512)
        assert not ksort.pallas_serviceable(513)


@pytest.mark.skipif(P < 2, reason="needs a real mesh")
class TestDistributedCensusPin:
    """ISSUE 4 acceptance: the distributed sort's collective census is
    UNCHANGED by the kernel wiring — columnsort keeps its 2 all-to-alls
    + 2 half-shard ppermutes per operand, odd-even its p rounds, and
    ZERO all-gathers appear — and the executed numerics are identical,
    proving the kernel only touched local compute."""

    def _census(self, n, mode, monkeypatch):
        monkeypatch.setenv("HEAT_TPU_SORT_KERNEL", mode)
        x = ht.random.randn(n, split=0)
        rep = ht.observability.collective_counts(lambda v: ht.sort(v)[0], x)
        return {
            op: rep.counts[op]
            for op in ("all-gather", "all-to-all", "collective-permute")
        }

    def test_columnsort_census_kernel_on_equals_off(self, monkeypatch):
        n = 4 * P * P * max(2 * (P - 1) ** 2, P)  # large-shard: columnsort route
        off = self._census(n, "0", monkeypatch)
        on = self._census(n, "1", monkeypatch)
        assert off == on
        assert off["all-gather"] == 0
        assert off["all-to-all"] >= 2  # the two deal exchanges

    def test_oddeven_census_kernel_on_equals_off(self, monkeypatch):
        n = 3 * P  # tiny shards: odd-even route
        off = self._census(n, "0", monkeypatch)
        on = self._census(n, "1", monkeypatch)
        assert off == on
        assert off["all-gather"] == 0
        assert off["all-to-all"] == 0  # odd-even is ppermute-only

    @pytest.mark.parametrize("n_extra", [0, 3])
    def test_distributed_numerics_kernel_on_equals_off(self, n_extra, monkeypatch):
        """Bit-identical (values, indices) with the kernel on vs off —
        including non-divisible extents (NaN pad sentinels in flight)."""
        n = 8 * P * max(2 * (P - 1) ** 2 // 8 + 1, 2) * P + n_extra
        x = np.random.default_rng(11).standard_normal(n).astype(np.float32)
        monkeypatch.setenv("HEAT_TPU_SORT_KERNEL", "0")
        v0, i0 = ht.sort(ht.array(x, split=0))
        monkeypatch.setenv("HEAT_TPU_SORT_KERNEL", "1")
        v1, i1 = ht.sort(ht.array(x, split=0))
        np.testing.assert_array_equal(v0.numpy(), v1.numpy())
        np.testing.assert_array_equal(i0.numpy(), i1.numpy())
        np.testing.assert_array_equal(v0.numpy(), np.sort(x, kind="stable"))
        np.testing.assert_array_equal(i0.numpy(), np.argsort(x, kind="stable"))

    def test_shardlint_sort_stays_clean(self, monkeypatch):
        """shardlint pin: ht.sort compiles with zero error-severity
        findings (no implicit reshard / replicated materialization is
        introduced by the kernel wiring)."""
        monkeypatch.setenv("HEAT_TPU_SORT_KERNEL", "1")
        x = ht.random.randn(16 * P, split=0)
        report = ht.analysis.check(lambda v: ht.sort(v)[0], x)
        errors = [f for f in report.findings if f.severity == "error"]
        assert errors == [], errors
