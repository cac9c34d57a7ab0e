"""``RobustScaler`` on the counting selection (PR 38): one ``ht.percentile`` call a
``fit``, ``transform`` / ``inverse_transform`` one program each, against a copy
of the benchmark's plain reference on every form of the selection;
``fit_transform`` as ONE program (PR 39) against the staged three, bit for bit;
the transform's kernel in interpret mode; and the benchmark's own ``check`` on
the program and on its bf16 control."""

import contextlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import heat_tpu as ht
from heat_tpu.preprocessing import preprocessing as pp
from test_percentile_select import _recorded, _table, form  # noqa: F401 (``form`` is a fixture)


# --------------------------------------------------------------------- #
# RobustScaler                                                           #
# --------------------------------------------------------------------- #
def _reference(x, q_min=25.0, q_max=75.0):
    """A copy of ``benchmarks/ops/robust_scale.reference`` at a small size:
    the sorted table, the two bracketing rows by index, the interpolation
    in f32, then ``(x - c) / s``."""
    n = x.shape[0]
    pos = np.asarray([q_min, 50.0, q_max], np.float64) / 100.0 * (n - 1)
    lo, hi = np.floor(pos).astype(np.int64), np.ceil(pos).astype(np.int64)
    frac = (pos - lo).astype(np.float32)
    vals = jax.lax.sort(jnp.asarray(x), dimension=0)
    vlo, vhi = vals[lo], vals[hi]
    pct = vlo + frac[:, None] * (vhi - vlo)
    iqr = pct[2] - pct[0]
    center, iqr = np.asarray(pct[1]), np.asarray(jnp.where(iqr > 0, iqr, 1.0))
    return center, iqr, (x - center) / iqr


@pytest.mark.parametrize("form", ["sort", "xla", "pallas"], indirect=True)
@pytest.mark.parametrize("split", [None, 0], ids=["one_device", "split0_mesh8"])
def test_robust_scaler_is_the_plain_references(split, form):
    rng = np.random.default_rng(8)
    x = (rng.normal(size=(2000, 8)) + rng.normal(size=8) * 0.25).astype(np.float32)
    x[::97] *= 50.0  # outliers
    x[:, 7] = 3.0  # a range of 0 scales by 1
    a = ht.array(x, split=split)
    rs = ht.preprocessing.RobustScaler()
    y = rs.fit_transform(a)
    center, iqr, want = _reference(x)
    assert y.split == split and y.shape == a.shape and rs.center_.split is None
    np.testing.assert_allclose(rs.center_.numpy(), center, rtol=2e-7, atol=1e-7)
    np.testing.assert_allclose(np.asarray(rs.iqr_), iqr, rtol=3e-7)
    np.testing.assert_allclose(y.numpy(), want, rtol=1e-6, atol=1e-6)
    back = rs.inverse_transform(y)
    assert back.split == split
    np.testing.assert_allclose(back.numpy(), x, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(a.numpy(), x)  # the caller's table stays


@pytest.mark.parametrize("centering, scaling", [(True, False), (False, True), (False, False)])
def test_robust_scaler_options(centering, scaling):
    from sklearn.preprocessing import RobustScaler as Sk

    x = np.random.default_rng(4).normal(size=(501, 5)).astype(np.float32)
    kwargs = dict(with_centering=centering, with_scaling=scaling, quantile_range=(10.0, 90.0))
    rs = ht.preprocessing.RobustScaler(**kwargs)
    y = rs.fit_transform(ht.array(x, split=0))
    np.testing.assert_allclose(y.numpy(), Sk(**kwargs).fit_transform(x), rtol=1e-5, atol=1e-6)
    assert (rs.center_ is None) == (not centering) and (rs.iqr_ is None) == (not scaling)
    np.testing.assert_allclose(rs.inverse_transform(y).numpy(), x, rtol=1e-5, atol=1e-6)


def test_robust_scaler_of_integers_is_float():
    x = np.random.default_rng(4).integers(0, 100, size=(64, 3))
    y = ht.preprocessing.RobustScaler().fit_transform(ht.array(x, split=0))
    assert y.dtype == ht.float32


@pytest.mark.parametrize("how", ["fused", "staged"])
def test_robust_scaler_runs_its_programs_under_its_spans(how):
    """``fit_transform`` on the counting selection is ONE program under one
    call span, with no ``percentile`` call inside it; ``fit`` then
    ``transform`` are one ``percentile`` call, one small program for the two
    statistics and one program for the transform; nothing is dispatched by
    itself. ``inverse_transform`` keeps its own program either way."""
    x = ht.array(_table("normal", np.random.default_rng(1), 8 * 8192), split=0)

    def run(rs):
        y = rs.fit_transform(x) if how == "fused" else rs.fit(x).transform(x)
        return rs.inverse_transform(y)

    run(ht.preprocessing.RobustScaler())
    with _recorded() as rows:
        run(ht.preprocessing.RobustScaler())
    names = [s["name"] for s in rows]
    calls = {"fused": ["ht.call.robustscaler.fit_transform"],
             "staged": ["ht.call.robustscaler.fit", "ht.call.percentile", "ht.call.robustscaler.transform"]}
    for call in calls["fused"] + calls["staged"] + ["ht.call.robustscaler.inverse_transform"]:
        assert names.count(call) == (call in calls[how] or call.endswith("inverse_transform")), call
    launched = [s["attrs"]["cache"] for s in rows if s["name"] in ("ht.program.launch", "ht.program.compile")]
    assert launched == {"fused": ["scaler.robust_fit_transform"],
                        "staged": ["percentile.select", "scaler.robust_stats", "scaler.transform"]}[how] + ["scaler.transform"]
    assert [s["name"] for s in rows if s["name"].startswith("ht.program.")].count("ht.program.miss") == 0


@contextlib.contextmanager
def _counted():
    """The telemetry counters of the block, in the dict it yields."""
    counters = {}
    ht.telemetry.enable()
    try:
        ht.telemetry.reset()
        yield counters
        counters.update(ht.telemetry.report()["counters"])
    finally:
        ht.telemetry.disable()


def _fused_and_staged(x, split, **kwargs):
    """The two scalers after ``fit_transform(a)`` and ``fit(a).transform(a)``, and what each gave."""
    a = ht.array(x, split=split)
    fused, staged = ht.preprocessing.RobustScaler(**kwargs), ht.preprocessing.RobustScaler(**kwargs)
    y, want = fused.fit_transform(a), staged.fit(a).transform(a)
    np.testing.assert_array_equal(a.numpy(), x)  # the caller's table stays
    return fused, staged, y, want


def _assert_the_same(fused, staged, y, want):
    assert (y.split, y.shape, y.dtype) == (want.split, want.shape, want.dtype)
    np.testing.assert_array_equal(y.numpy(), want.numpy())
    for got, held in ((fused.center_, staged.center_), (fused.iqr_, staged.iqr_)):  # a ``DNDarray``, a ``jax.Array``
        assert type(got) is type(held)
        if held is not None:
            assert (got.dtype, got.shape) == (held.dtype, held.shape)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(held))


@pytest.mark.parametrize("form", ["xla", "pallas"], indirect=True)
@pytest.mark.parametrize("split", [None, 0], ids=["one_device", "split0_mesh8"])
@pytest.mark.parametrize("centering, scaling", [(True, True), (True, False), (False, True)])
def test_fit_transform_in_one_program_is_fit_then_transform(centering, scaling, split, form):
    """The fused call against the staged one, bit for bit on ``y``,
    ``center_`` and ``iqr_``: every combination of the two flags that fits
    anything, on one device and split 0 over the mesh (the fused program
    under ``shard_map`` where its parts are), through the ``jax.numpy``
    passes and the kernels in interpret mode; outliers, a column of one
    value (a range of 0 scales by 1) and a column that holds a NaN."""
    rng = np.random.default_rng(39)
    x = (rng.normal(size=(2000, 8)) + rng.normal(size=8) * 0.25).astype(np.float32)
    x[::97] *= 50.0
    x[:, 7] = 3.0
    x[11, 2] = np.nan
    with _counted() as counters:
        fused, staged, y, want = _fused_and_staged(x, split, with_centering=centering, with_scaling=scaling)
    _assert_the_same(fused, staged, y, want)
    # a median that is NaN takes its whole column with it; a range that is NaN is no range over 0 and scales by 1
    assert np.isnan(y.numpy()[:, 2]).sum() == (2000 if centering else 1) and np.isfinite(np.delete(y.numpy(), 2, axis=1)).all()
    # one fused call and one ``percentile`` call (the staged fit's), each counted as the form it took
    assert counters["scaler.fit_transform.fused"] == 1 and "scaler.fit_transform.staged" not in counters
    assert counters[f"percentile.select.{form}"] == 2 and counters.get("percentile.select.gather", 0) == 2 * (form == "pallas")
    assert counters["scaler.robust_fit_transform.miss"] == 1 and "percentile.select.sort" not in counters


@pytest.mark.parametrize("form", ["xla", "pallas"], indirect=True)
def test_fit_transform_in_one_program_with_a_range_of_one_quantile(form):
    """``quantile_range=(50.0, 50.0)``: three targets with one window, which
    share its keys; the range is 0 in every column and scales by 1."""
    x = _table("around_0_10_100", np.random.default_rng(3), 2001)
    fused, staged, y, want = _fused_and_staged(x, None, quantile_range=(50.0, 50.0))
    _assert_the_same(fused, staged, y, want)
    np.testing.assert_array_equal(np.asarray(fused.iqr_), np.ones(8, np.float32))
    np.testing.assert_array_equal(fused.center_.numpy(), np.median(x, axis=0))
    np.testing.assert_array_equal(y.numpy(), x - np.median(x, axis=0))


@pytest.mark.parametrize("case", ["integers", "under_the_row_floor", "both_flags_off", "counting_selection"])
def test_fit_transform_says_which_form_it_took(case):
    """What ``percentile`` would sort (an integer table, one under
    ``_SELECT_MIN_ROWS_A_DEVICE`` rows a device) and a scaler that fits
    nothing take the staged form; a tall split f32 table the fused one, with
    the counters its ``percentile`` call would have left. No form is
    steered here: the gate reads the input."""
    rng = np.random.default_rng(5)
    rows = 8 * 100 if case == "under_the_row_floor" else 8 * 8192
    x = rng.integers(0, 100, size=(rows, 8)) if case == "integers" else rng.normal(size=(rows, 8)).astype(np.float32)
    flags = dict(with_centering=False, with_scaling=False) if case == "both_flags_off" else {}
    a = ht.array(x, split=0)
    with _counted() as counters, _recorded() as spans:
        rs = ht.preprocessing.RobustScaler(**flags)
        y = rs.fit_transform(a)
    names = [s["name"] for s in spans]
    assert names.count("ht.call.robustscaler.fit_transform") == 1
    took = "fused" if case == "counting_selection" else "staged"
    other = "staged" if took == "fused" else "fused"
    assert counters[f"scaler.fit_transform.{took}"] == 1 and f"scaler.fit_transform.{other}" not in counters
    assert ("ht.call.robustscaler.fit" in names) == (took == "staged") == ("ht.call.robustscaler.transform" in names)
    if case == "counting_selection":
        assert counters["percentile.select.xla"] == 1 and "percentile.select.sort" not in counters
        assert "ht.call.percentile" not in names
    elif case == "both_flags_off":
        assert rs.center_ is None and rs.iqr_ is None and "ht.call.percentile" not in names
        np.testing.assert_array_equal(y.numpy(), x)
    else:
        assert counters["percentile.select.sort"] == 1 and "scaler.robust_fit_transform.miss" not in counters
    want = ht.preprocessing.RobustScaler(**flags).fit(a).transform(a)
    assert y.dtype == want.dtype == ht.float32 and y.split == 0
    np.testing.assert_array_equal(y.numpy(), want.numpy())


@pytest.mark.parametrize("inverse", [False, True])
@pytest.mark.parametrize("split", [None, 0], ids=["one_device", "split0_mesh8"])
def test_affine_pass_in_interpret_mode(split, inverse):
    """The transform's kernel against the plain expression, a masked last
    block; on the mesh under ``shard_map``."""
    comm = ht.MPI_WORLD
    n, d = 8 * 1100, 16
    rng = np.random.default_rng(6)
    x, c, s = rng.normal(size=(n, d)).astype(np.float32), rng.normal(size=d).astype(np.float32), \
        rng.uniform(0.5, 2.0, size=d).astype(np.float32)
    mesh, axis = (comm.mesh, comm.axis_name) if split == 0 else (None, None)
    prog = pp._affine_program((n, d), "float32", "float32", inverse, True, True, True, mesh, axis, True)
    got = prog(jax.device_put(x, comm.sharding(2, split)) if split == 0 else x, c, s)
    if inverse:  # the product and the sum may contract to one rounding
        np.testing.assert_allclose(np.asarray(got), x * s + c, rtol=2e-7, atol=2e-7)
    else:
        np.testing.assert_array_equal(np.asarray(got), (x - c) / s)
    pp._affine_program.cache_clear()


# --------------------------------------------------------------------- #
# the benchmark's check tells the program from a control in bf16         #
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("control, missed", [("program", ()), ("bf16_statistics", ("center_", "iqr_", "y off"))])
def test_benchmark_check_tells_the_program_from_a_bf16_control(control, missed):
    """``benchmarks/ops/robust_scale.check`` at the configuration's limits,
    on its toy twin: the program's call passes; the plain reference's
    statistics taken from a bf16 image of ``X`` put in its place miss
    ``center_``, ``iqr_`` and ``y`` (the way back is consistent with itself
    and passes). ``configs/robustscale-northstar.json`` has the same two at
    the cell's shape on the chip."""
    import os
    import sys

    from heat_tpu.core.dndarray import DNDarray

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    try:
        from benchmarks import run as harness

        op = harness.load_module("ops", "robust_scale")
    finally:
        sys.path.remove(root)
    cfg = harness.load_json(os.path.join(root, "benchmarks", "configs", "robustscale-northstar.json"))
    chips = ht.MPI_WORLD.size
    cfg = {**cfg, "rows_per_chip": cfg["toy"]["rows_per_chip"] // chips}
    with jax.enable_x64(False):  # the cell's policy
        state = op.make(cfg, chips, jax.random.key(38))
        ref = op.reference(state)
        if control == "program":
            out = op.call(state)
        else:
            x, low = state["x"], op.statistics(state, image=jnp.bfloat16)
            rs = ht.preprocessing.RobustScaler()
            rs.center_ = DNDarray(low["center"], tuple(low["center"].shape), ht.float32, None, x.device, x.comm)
            rs.iqr_ = low["iqr"]
            out = {"y": rs.transform(x), "center": rs.center_, "iqr": rs.iqr_, "rs": rs}
        misses = op.check(state, out, ref)["misses"]
    assert len(misses) == len(missed) and all(any(w in m for m in misses) for w in missed), misses


def test_benchmark_op_counts_three_tables_of_bytes():
    """``work_bytes`` is ``X`` once, ``least_bytes`` the chip's rows of it
    three times (read for the statistics, read and written for ``y``)."""
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    try:
        from benchmarks import run as harness

        op = harness.load_module("ops", "robust_scale")
    finally:
        sys.path.remove(root)
    x_bytes = 4 * 18_750_000 * 64 * 4
    state = {"chips": 4, "bytes": x_bytes}
    assert op.work_bytes(state, None) == x_bytes and op.least_bytes(state, None) == 3 * 18_750_000 * 64 * 4
    lo, hi, frac = op.ranks(18_750_000, [25.0, 50.0, 75.0])
    assert list(lo) == [4_687_499, 9_374_999, 14_062_499] and list(hi - lo) == [1, 1, 1]
    np.testing.assert_array_equal(frac, np.array([0.75, 0.5, 0.25], np.float32))
