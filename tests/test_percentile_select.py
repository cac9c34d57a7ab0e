"""``ht.percentile`` / ``ht.median`` along the sample axis by the exact counting
selection (PR 38: ``core/_selection.py``, ``core/_pallas_select.py``), for all
rows: against ``numpy.percentile`` through the ``jax.numpy`` form of the passes
and, in interpret mode, the chip's kernels, on one device and split 0 over the
8-device mesh; and ``RobustScaler`` on it, against a copy of the benchmark's
plain reference."""

import contextlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import heat_tpu as ht
from heat_tpu.core import _pallas_select as ps, _selection as sel, statistics as st
from heat_tpu.preprocessing import preprocessing as pp

METHODS = ["linear", "lower", "higher", "nearest", "midpoint"]
Q = [25.0, 50.0, 75.0, 0.0, 100.0, 50.0, 50.001, 33.3]  # equal and neighbouring q, the ends


@pytest.fixture
def form(monkeypatch, request):
    """Steer ``percentile`` onto one form of the selection whatever the
    input (the gate reads backend, dtype, shape and split only). The
    kernels then run in interpret mode, and gather at any size: no window
    is ever crowded, so the selection gathers after the fourth digit."""
    which = request.param
    monkeypatch.setattr(st, "_selection_form", lambda *a, **k: which)
    if which == "pallas":
        monkeypatch.setattr(ps, "_GATHER_MIN_ROWS_A_CLUSTER", 0)
        monkeypatch.setattr(ps, "_GATHER_MOST_OF_X", 1)
    def clear():
        for cache in (ps.select_passes, st._percentile_select_program, pp._robust_fit_transform_program):
            cache.cache_clear()

    clear()
    yield which
    clear()


def _near(rng, shape, span, at=1.0):
    """f32 values ``at`` + i ulps, i uniform under ``span``: neighbours in key space."""
    return (np.float32(at).view(np.int32) + rng.integers(0, span, size=shape).astype(np.int32)).view(np.float32)


def _table(name, rng, n):
    """An (n, 8) f32 table of one named kind."""
    x = rng.normal(size=(n, 8)).astype(np.float32)
    if name == "around_0_10_100":
        x += np.array([0, 0, 10, 10, 100, 100, -10, 1e-3], np.float32)
    elif name == "repeated_and_sorted":
        x[:, 0] = 0.5  # one repeated value
        x[:, 1] = np.sort(x[:, 1])  # a sorted column
        x[:, 2] = np.round(x[:, 2])  # a few distinct values, zeros of both signs among them
        x[:, 3] = -np.abs(x[:, 3]) * 1e-30
        x[:, 4] = _near(rng, n, 3000)  # neighbours in key space: every window holds them all
        x[:, 5] = (np.float32(2.0).view(np.int32) + np.arange(n, dtype=np.int32)).view(np.float32)  # and sorted: spills
    elif name == "infinities":
        x[::7, 0], x[1::7, 1] = np.inf, -np.inf
        x[:, 2] = np.inf
        x[: n // 2, 3], x[n // 2:, 3] = -np.inf, np.inf
    elif name == "nan_columns":
        x[3, 1] = np.nan
        x[n - 1, 4] = -np.nan  # the sign bit set: as a key it lies under -inf
        x[:, 6] = np.nan
    elif name != "normal":
        raise KeyError(name)
    return x


TABLES = ["normal", "around_0_10_100", "repeated_and_sorted", "infinities", "nan_columns"]


def _assert_percentiles(got, x, q, method):
    """The sorted columns' values: bit for bit where the result is an order
    statistic (``lower``, ``higher``, ``nearest``, a ``q`` that falls on a
    row), ``v[lo] + frac * (v[hi] - v[lo])`` to one rounding elsewhere (NaN
    between two infinities of one sign, as the sort's own formula gives);
    NaN where the column holds one. Where every value is finite that is
    ``numpy.percentile``, which is asked too."""
    v = np.sort(x.astype(np.float64), axis=0)  # NaNs last
    n = x.shape[0]
    pos = np.atleast_1d(np.asarray(q, np.float64)) / 100.0 * (n - 1)
    lo, hi = np.floor(pos).astype(int), np.ceil(pos).astype(int)
    w = {"lower": np.zeros_like(pos), "higher": np.ones_like(pos), "nearest": (np.rint(pos) != lo).astype(np.float64),
         "midpoint": np.where(hi > lo, 0.5, 0.0), "linear": pos - lo}[method]
    with np.errstate(all="ignore"):
        want = np.stack([v[l] if f == 0 else v[h] if f == 1 else v[l] + f * (v[h] - v[l]) for l, h, f in zip(lo, hi, w)])
    want[:, np.isnan(x).any(axis=0)] = np.nan
    want = want.reshape(np.shape(q) + (x.shape[1],))
    if np.isfinite(x).all():
        np.testing.assert_allclose(want, np.percentile(x.astype(np.float64), q, axis=0, method=method), rtol=1e-12, atol=1e-300)
    want32 = want.astype(np.float32)
    assert got.shape == want.shape and got.dtype == np.float32
    if method in ("lower", "higher", "nearest"):
        np.testing.assert_array_equal(got, want32)
    else:
        ok = np.isfinite(want32)
        np.testing.assert_array_equal(got[~ok], want32[~ok])
        np.testing.assert_allclose(got[ok], want[ok], rtol=3e-7, atol=1e-37)


@pytest.mark.parametrize("form", ["xla", "pallas"], indirect=True)
@pytest.mark.parametrize("split", [None, 0], ids=["one_device", "split0_mesh8"])
@pytest.mark.parametrize("table", TABLES)
def test_percentile_is_numpys(table, split, form):
    """Eight ``q`` (the ends, equal ones, neighbours) of one call, on an even
    row count, and ``median`` on the same data. All five interpolations and a
    scalar ``q`` through the ``jax.numpy`` passes; through the kernels (a
    program of its own for each, seconds in interpret mode) ``linear`` and,
    on one device, ``nearest``: what a form decides are the two order statistics, the
    interpolation after them is the same code."""
    n = 2048
    x = _table(table, np.random.default_rng(TABLES.index(table)), n)
    a = ht.array(x, split=split)
    for method in METHODS if form == "xla" else ("linear", "nearest") if split is None else ("linear",):
        _assert_percentiles(ht.percentile(a, Q, axis=0, interpolation=method).numpy(), x, Q, method)
    _assert_percentiles(ht.median(a, axis=0).numpy(), x, 50.0, "linear")
    if form == "xla":
        _assert_percentiles(ht.percentile(a, 75.0, axis=0, interpolation="lower").numpy(), x, 75.0, "lower")


@pytest.mark.parametrize("form", ["xla", "pallas"], indirect=True)
@pytest.mark.parametrize("n, split", [(2501, None), (8 * 313, 0), (1, None), (2, None), (8, 0)],
                         ids=["odd", "odd_shards", "one_row", "two_rows", "a_row_a_device"])
def test_percentile_on_other_row_counts(n, split, form):
    """An odd row count (the median is one row, a masked last block in every
    kernel), and tables of a row or two."""
    x = _table("around_0_10_100", np.random.default_rng(n), n)
    a = ht.array(x, split=split)
    for method in ("linear", "nearest", "midpoint") if form == "xla" else ("midpoint",):
        _assert_percentiles(ht.percentile(a, Q, axis=0, interpolation=method).numpy(), x, Q, method)
    _assert_percentiles(ht.median(a, axis=0).numpy(), x, 50.0, "linear")


@pytest.mark.parametrize("form", ["xla"], indirect=True)
def test_percentile_shapes_and_out(form):
    x = _table("normal", np.random.default_rng(3), 1000)
    a = ht.array(x, split=0)
    assert ht.percentile(a, 30.0, axis=0).shape == (8,)
    assert ht.percentile(a, 30.0, axis=0, keepdims=True).shape == (1, 8)
    assert ht.percentile(a, [30.0, 60.0], axis=0).shape == (2, 8)
    assert ht.percentile(a, [30.0, 60.0], axis=0, keepdims=True).shape == (2, 1, 8)
    assert ht.percentile(a, 30.0, axis=0).split is None
    out = ht.zeros((2, 8), dtype=ht.float32)
    assert ht.percentile(a, [30.0, 60.0], axis=0, out=out) is out
    np.testing.assert_array_equal(out.numpy(), np.percentile(x, [30, 60], axis=0, method="linear").astype(np.float32))
    np.testing.assert_array_equal(ht.percentile(a, ht.array([30.0, 60.0]), axis=0).numpy(), out.numpy())


@pytest.mark.parametrize("form", ["xla", "pallas"], indirect=True)
def test_more_q_than_one_batch_holds(form):
    """Twenty distinct ``q``: three batches of ``_MOST_TARGETS`` targets, one
    program; equal ``q`` are one target."""
    x = _table("around_0_10_100", np.random.default_rng(5), 3000)
    q = list(np.linspace(1.0, 99.0, 20)) + [50.0, 50.0]
    assert len(q) > 2 * ps._MOST_TARGETS
    _assert_percentiles(ht.percentile(ht.array(x), q, axis=0).numpy(), x, q, "linear")


# --------------------------------------------------------------------- #
# which passes a selection for all rows makes                            #
# --------------------------------------------------------------------- #
def _counted(passes, seen):
    """``passes`` that say when they run."""
    def note(what, fn):
        def run(*a):
            jax.debug.callback(lambda: seen.append(what))
            return fn(*a)
        return run

    def gather(arr, base, bits, skip):
        jax.debug.callback(lambda s: seen.append(("gather", bool(s))), skip)
        return passes.gather(arr, base, bits, skip)

    return passes._replace(count_below=note("count", passes.count_below), next_above=note("next", passes.next_above),
                           first=note("first", passes.first), gather=None if passes.gather is None else gather)


def _select(x, ranks, passes):
    """The keys' values at each ``(lo, hi)`` of ``ranks`` in every column,
    and the passes the selection made."""
    seen = []
    passes = _counted(passes, seen)
    lower = jnp.asarray([lo for lo, _ in ranks], jnp.int32)[:, None]
    upper = jnp.asarray([hi for _, hi in ranks], jnp.int32)[:, None]

    @jax.jit
    def run(arr):
        under, _ = passes.first(arr)
        first_under = jnp.broadcast_to(under[:, None, :], (under.shape[0], len(ranks), arr.shape[1]))
        low, high = sel.order_statistics(arr, lower, upper, jnp.full((len(ranks), 1), arr.shape[0], jnp.int32),
                                         passes, None, first_under)
        return ps._from_key(low, arr.dtype), ps._from_key(high, arr.dtype)

    low, high = run(x)
    jax.effects_barrier()
    return np.asarray(low), np.asarray(high), seen


@pytest.fixture
def gather_at_any_size(monkeypatch):
    monkeypatch.setattr(ps, "_GATHER_MIN_ROWS_A_CLUSTER", 0)
    monkeypatch.setattr(ps, "_GATHER_MOST_OF_X", 1)
    monkeypatch.setattr(sel, "_WINDOW_MIN_KEYS", 1)
    ps.select_passes.cache_clear()
    yield
    ps.select_passes.cache_clear()


def _cores(rng, n, d, at=(1.0, 1e3, 1e6), between=(30, 60), core=6):
    """Columns whose quartiles and median sit in runs of ``core`` neighbours
    in key space around the values ``at`` (each run in a window of its own:
    a window of the fourth digit spans a factor of sixteen), the other rows
    far below, between and above them: few keys are kept, no slot spills."""
    fill = [lambda m: -rng.uniform(1e6, 1e7, size=m), lambda m: rng.uniform(*between, size=m),
            lambda m: rng.uniform(1e4, 5e4, size=m), lambda m: rng.uniform(1e8, 1e9, size=m)]
    sizes = [n // 4 - core // 2, n // 4 - core, n // 4 - core, n - 3 * (n // 4) - core // 2]
    cols = []
    for _ in range(d):
        parts = [fill[0](sizes[0])]
        for i, v in enumerate(at):
            parts += [_near(rng, core, 3000, v), fill[i + 1](sizes[i + 1])]
        cols.append(rng.permutation(np.concatenate(parts)))
    return np.stack(cols, axis=1).astype(np.float32)


@pytest.mark.parametrize("case, ends_on", [("far_apart", "kept"), ("same_window", "kept"), ("overlapping_windows", "x"),
                                           ("sorted_column_spills", "x")])
def test_windows_that_coincide_share_their_keys_and_overlapping_ones_end_on_x(case, ends_on, gather_at_any_size):
    """For all rows two targets' windows can meet, which a cluster's never
    do. The same window (equal or neighbouring ranks): the first target
    keeps the keys and the others read them. Windows that overlap without
    being the same: the gathering pass is told to skip and the selection
    ends on ``X``. One pass for the first digit, three counting passes (the
    windows fit after the fourth digit: nothing is crowded here), one
    gathering pass; on ``X`` to the end: sixteen digits and the successor."""
    rng = np.random.default_rng(11)
    n, d = 2048, 8
    if case == "far_apart":
        x, ranks = _cores(rng, n, d), [(n // 4, n // 4 + 1), (n // 2 + 1, n // 2 + 1), (3 * n // 4 - 1, 3 * n // 4)]
    elif case == "same_window":  # three targets in the median's run of neighbours
        x, ranks = _cores(rng, n, d), [(n // 2 - 2, n // 2 - 1), (n // 2, n // 2), (n // 2 + 1, n // 2 + 2)]
    elif case == "overlapping_windows":  # runs in [0.5, 2) and [2, 8): neighbouring brackets of the fourth digit
        x = _cores(rng, n, d, at=(1.0, 7.0, 1e6), between=(1.5, 6.0))
        ranks = [(n // 4, n // 4 + 1), (n // 2, n // 2 + 1)]
    else:  # neighbours in key space row after row: sixteen to a lane position, six slots
        x = np.stack([(np.float32(1.0).view(np.int32) + np.arange(n, dtype=np.int32)).view(np.float32)] * d, axis=1)
        ranks = [(n // 2, n // 2 + 1)]
    low, high, seen = _select(x, ranks, ps.select_passes(x.shape, len(ranks), False, "percentile.select", interpret=True))
    v = np.sort(x, axis=0)
    np.testing.assert_array_equal(low, np.stack([v[lo] for lo, _ in ranks]))
    np.testing.assert_array_equal(high, np.stack([v[hi] for _, hi in ranks]))
    gathers = [e for e in seen if isinstance(e, tuple)]
    assert seen.count("first") == 1 and len(gathers) == 1
    if ends_on == "kept":
        assert gathers == [("gather", False)] and seen.count("count") == 3 and "next" not in seen
    else:
        assert gathers == [("gather", case == "overlapping_windows")] and seen.count("count") == 15 and "next" in seen


def test_window_owners():
    base = jnp.asarray([[0, 0], [64, 0], [0, 4096], [128, 64]], jnp.int32)
    wide = jnp.asarray([[6, 7], [6, 7], [6, 7], [6, 6]], jnp.int32)
    owner, clash = ps.window_owners(base, wide)
    # feature 0: targets 0 and 2 share a window, 1 and 3 stand alone and meet nothing
    # feature 1: targets 0 and 1 share [0, 128), which target 3's [64, 128) overlaps without being the same
    np.testing.assert_array_equal(owner, [[0, 0], [1, 0], [0, 2], [3, 3]])
    assert bool(clash)
    assert not bool(ps.window_owners(base[:, :1], wide[:, :1])[1])
    top = np.iinfo(np.int32).max
    far = jnp.asarray([[-top - 1], [top - 63]], jnp.int32)  # the first and the last window of the key range: the gap wraps
    assert not bool(ps.window_owners(far, jnp.full((2, 1), 6, jnp.int32))[1])


def test_the_passes_agree_with_the_xla_form():
    rng = np.random.default_rng(2)
    x = rng.normal(size=(2500, 16)).astype(np.float32)
    x[7, 3], x[2499, 5] = np.nan, -np.nan
    chip, plain = ps.select_passes(x.shape, 3, False, "percentile.select", interpret=True), sel.passes_xla()
    for got, want in zip(chip.first(x), plain.first(x)):
        np.testing.assert_array_equal(got, want)
    assert list(np.flatnonzero(np.asarray(chip.first(x)[1]))) == [3, 5]
    thr0, step = ps._to_key(jnp.asarray(rng.normal(size=(3, 16)).astype(np.float32))), jnp.int32(1 << 20)
    np.testing.assert_array_equal(chip.count_below(x, thr0, step), plain.count_below(x, thr0, step))
    np.testing.assert_array_equal(chip.next_above(x, thr0), plain.next_above(x, thr0))


# --------------------------------------------------------------------- #
# the gate, the program, the counters                                    #
# --------------------------------------------------------------------- #
@contextlib.contextmanager
def _recorded():
    """The spans committed inside the block, oldest first, as dicts."""
    from heat_tpu.observability import tracing

    rows = []
    tracing.enable()
    tracing.clear()
    try:
        yield rows
        rows.extend(tracing.spans())
    finally:
        tracing.disable()
        tracing.clear()


def test_form_reads_backend_dtype_shape_and_split_only():
    f = st._selection_form
    with jax.enable_x64(False):
        assert f("tpu", "float32", (18_750_000, 64), 0, 0, 1, 3) == "pallas"
        assert f("tpu", "float32", (18_750_000, 64), 0, None, 1, 3) == "pallas"
        assert f("tpu", "float32", (4 * 4_687_500, 64), 0, 0, 4, 3) == "pallas"
        assert f("tpu", "float32", (1 << 20, 64), 0, None, 1, 8) == "pallas"
        assert f("tpu", "float32", (1 << 20, 64), 0, None, 1, 20) == "pallas"  # batches of eight
        assert f("tpu", "float32", (1 << 19, 64), 0, None, 1, 8) == "sort"  # under 2 ** 17 rows a target
        assert f("tpu", "float32", (18_750_000, 128), 0, None, 1, 3) == "sort"
        assert f("tpu", "float32", (18_750_000, 60), 0, None, 1, 3) == "sort"
        assert f("tpu", "bfloat16", (18_750_000, 64), 0, None, 1, 3) == "sort"
        assert f("tpu", "float32", (18_750_000, 64), 1, None, 1, 3) == "sort"
        assert f("tpu", "float32", (18_750_000,), 0, None, 1, 3) == "sort"
        assert f("tpu", "float32", (4 * 4_687_500 + 4, 128), 0, 0, 4, 3) == "xla"  # a split array the kernels do not serve
        assert f("cpu", "float32", (18_750_000, 64), 0, None, 1, 3) == "sort"
        assert f("cpu", "float32", (80_000, 16), 0, 0, 8, 3) == "xla"
        assert f("cpu", "float64", (80_000, 16), 0, 0, 8, 3) == "xla"
        assert f("cpu", "float32", (8_000, 16), 0, 0, 8, 3) == "sort"
        assert f("cpu", "float32", (80_001, 16), 0, 0, 8, 3) == "sort"  # unequal shards
        assert f("cpu", "int32", (80_000, 16), 0, 0, 8, 3) == "sort"
    with jax.enable_x64(True):  # Mosaic refuses 64-bit traces
        assert f("tpu", "float32", (18_750_000, 64), 0, None, 1, 3) == "sort"


@pytest.mark.parametrize("on_chip", [False, True], ids=["xla", "kernels"])
def test_program_sorts_nothing_and_holds_nothing_of_xs_size(on_chip):
    """Where the gate serves: no ``sort`` in the lowered text, no label
    array, and no value of ``X``'s size besides ``X`` (the kept keys are a
    sixtieth of it)."""
    from test_kmedians_select import _value_sizes

    n, d = (18_750_000, 64) if on_chip else (80_000, 16)
    ranks = ((n // 4, n // 4 + 1), (n // 2, n // 2 + 1), (3 * n // 4, 3 * n // 4 + 1))
    with jax.enable_x64(False):
        prog = st._percentile_select_program((n, d), "float32", on_chip, ranks, (0.25, 0.5, 0.75), (3, d), None, None)
        a = jax.ShapeDtypeStruct((n, d), jnp.float32)
        sizes = _value_sizes(jax.make_jaxpr(prog.program)(a).jaxpr)
        if not on_chip:
            assert "stablehlo.sort" not in prog.program.lower(a).as_text()
    st._percentile_select_program.cache_clear()
    if on_chip:
        assert max(sizes) == n * d and d * ps.kept_lanes(n, d, 3) in sizes
        assert sorted(sizes)[-2] == d * ps.kept_lanes(n, d, 3) < n * d // 60
    else:  # a walk over the targets: one comparison of the keys with a threshold at a time
        assert max(sizes) == n * d


def test_percentile_counts_the_form_it_took_and_runs_one_program():
    ht.telemetry.enable()
    try:
        ht.telemetry.reset()
        x = _table("normal", np.random.default_rng(1), 8 * 8192)
        ht.percentile(ht.array(x, split=0), [25.0, 75.0], axis=0)
        ht.percentile(ht.array(x[:999], split=0), [25.0, 75.0], axis=0)
        ht.percentile(ht.array(x), 50.0, axis=0)
        counters = ht.telemetry.report()["counters"]
    finally:
        ht.telemetry.disable()
    assert counters["percentile.select.xla"] == 1 and counters["percentile.select.sort"] == 2
    assert "percentile.select.pallas" not in counters and counters["percentile.select.miss"] == 1


def test_percentile_is_spanned():
    x = ht.array(_table("normal", np.random.default_rng(1), 8 * 8192), split=0)
    ht.percentile(x, [25.0, 75.0], axis=0)
    with _recorded() as rows:
        ht.median(x, axis=0)
    names = [s["name"] for s in rows]
    assert names.count("ht.call.percentile") == 1 and "ht.call.percentile.prepare" in names
    assert "ht.call.percentile.wrap" in names
    assert [n for n in names if n.startswith("ht.program.")] == ["ht.program.miss", "ht.program.compile"]
