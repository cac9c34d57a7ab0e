"""The L1 family's step (PR 32): ``_kcluster._cluster_medians`` is exact
(``numpy.median`` by cluster), ``KMedians.fit`` and ``KMedoids.fit`` follow a
plain L1 Lloyd reference iteration for iteration, on one device and split 0
over the 8-device mesh, the Pallas passes agree with the ``jax.numpy`` form
in interpret mode, and nothing of ``k x n x d`` is ever held."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import heat_tpu as ht
from heat_tpu.cluster import _kcluster as kc, _pallas_l1 as pl1
from heat_tpu.core import _pallas_select as ps, _selection as sel

N_THR = ps._N_THR


def _median_by_cluster(x, labels, k, prev):
    return np.stack([np.median(x[labels == c], axis=0) if (labels == c).any() else prev[c] for c in range(k)])


def _case(name, rng):
    """(x, labels, k) of one named case; every value f32."""
    if name == "odd_counts":
        return rng.normal(size=(3 * 101, 5)), np.repeat(np.arange(3), 101), 3
    if name == "even_counts":
        return rng.normal(size=(3 * 100, 5)), np.repeat(np.arange(3), 100), 3
    if name == "duplicates":  # a few distinct values: ties at and around the median
        return rng.integers(-2, 3, size=(400, 4)).astype(np.float32) / 2, rng.integers(0, 3, size=400), 3
    if name == "even_with_tied_middle":  # the two middle values equal: no successor is looked at
        x = np.concatenate([np.full((50, 2), -1.0), np.full((4, 2), 0.25), np.full((50, 2), 3.0)])
        return x, np.zeros(104, int), 1
    if name == "negative_and_zeros":
        x = -np.abs(rng.normal(size=(257, 3)))
        x[::3] = 0.0
        x[1::3] = -0.0
        return x, rng.integers(0, 2, size=257), 2
    if name == "zero_straddling":  # medians within an ulp-dense stretch around 0
        return rng.normal(size=(999, 6)) * 1e-30, rng.integers(0, 4, size=999), 4
    if name == "huge_and_tiny":
        x = rng.normal(size=(500, 3)) * 10.0 ** rng.integers(-30, 30, size=(500, 3))
        x[0], x[1] = np.inf, -np.inf
        return x, rng.integers(0, 3, size=500), 3
    if name == "one_empty_cluster":
        labels = rng.integers(0, 4, size=300)
        labels[labels == 2] = 0
        return rng.normal(size=(300, 7)), labels, 4
    if name == "single_rows":  # a cluster of one row, a cluster of two
        return rng.normal(size=(6, 3)), np.array([0, 1, 1, 2, 2, 2]), 3
    if name == "k1_d1":
        return rng.normal(size=(1000, 1)), np.zeros(1000, int), 1
    if name == "k8_d64":
        return rng.normal(size=(4099, 64)), rng.integers(0, 8, size=4099), 8
    if name == "k8_d130":
        return rng.normal(size=(1031, 130)), rng.integers(0, 8, size=1031), 8
    raise KeyError(name)


CASES = ["odd_counts", "even_counts", "duplicates", "even_with_tied_middle", "negative_and_zeros",
         "zero_straddling", "huge_and_tiny", "one_empty_cluster", "single_rows", "k1_d1", "k8_d64", "k8_d130"]


@pytest.mark.parametrize("name", CASES)
def test_cluster_medians_are_numpys(name):
    rng = np.random.default_rng(CASES.index(name))
    x, labels, k = _case(name, rng)
    x = x.astype(np.float32)
    prev = rng.normal(size=(k, x.shape[1])).astype(np.float32)
    got = jax.jit(lambda a, l, p: kc._cluster_medians(a, l, k, p))(x, labels, prev)
    with np.errstate(invalid="ignore"):  # inf - inf never arises: the medians of that case are finite
        want = _median_by_cluster(x, labels, k, prev)
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("dtype", [np.float64, np.float16])
def test_cluster_medians_other_float_widths(dtype):
    """The key is as wide as the float: 64 bits (under x64) and 16."""
    rng = np.random.default_rng(5)
    x = rng.normal(size=(301, 3)).astype(dtype)
    labels = rng.integers(0, 3, size=301)
    with jax.enable_x64(dtype == np.float64):
        got = np.asarray(kc._cluster_medians(jnp.asarray(x), labels, 3, jnp.zeros((3, 3), dtype)))
    assert got.dtype == dtype
    for c in range(3):
        rows = np.sort(x[labels == c], axis=0)
        lo, hi = rows[(len(rows) - 1) // 2], rows[len(rows) // 2]
        np.testing.assert_array_equal(got[c], (dtype(0.5) * lo + dtype(0.5) * hi).astype(dtype))


def test_keys_keep_the_order_of_the_floats():
    x = np.array([-np.inf, -3.5, -1e-38, -0.0, 0.0, 1e-45, 2.0, 3.4e38, np.inf], np.float32)
    key = np.asarray(ps._to_key(jnp.asarray(x)))
    assert (np.diff(key.astype(np.int64)) > 0).all()
    np.testing.assert_array_equal(np.asarray(ps._from_key(jnp.asarray(key), np.float32)), x)


# --------------------------------------------------------------------- #
# whole fits against a plain L1 Lloyd reference                          #
# --------------------------------------------------------------------- #
def _blobs(n, d, k, seed):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(k, d)).astype(np.float32)
    x = (centers[rng.integers(0, k, size=n)] + rng.normal(size=(n, d))).astype(np.float32)
    return x, (centers + 0.5 * rng.normal(size=(k, d))).astype(np.float32)


def _l1_labels(x, c):
    dist = np.abs(x[:, None, :] - c[None, :, :]).sum(axis=-1)
    return np.argmin(dist, axis=1), float(dist.min(axis=1).sum())


def _l1_lloyd(x, c, iters, snap):
    for _ in range(iters):
        labels, _ = _l1_labels(x, c)
        new = c.copy()
        for i in range(len(c)):
            rows = x[labels == i]
            if len(rows):
                new[i] = np.median(rows, axis=0)
                if snap:
                    new[i] = rows[np.argmin(np.abs(rows - new[i]).sum(axis=1))]
        c = new
    return (c,) + _l1_labels(x, c)


@pytest.mark.parametrize("split", [None, 0], ids=["one_device", "split0_mesh8"])
@pytest.mark.parametrize("est", ["KMedians", "KMedoids"])
def test_fit_follows_the_plain_l1_lloyd(est, split):
    """All iterations from a given init: centres exact, labels and the
    functional value the reference's. 1001 rows: no mesh of 8 divides them,
    so the split array is padded."""
    n, d, k, iters = 1001, 6, 4, 4
    x, init = _blobs(n, d, k, seed=3)
    kwargs = {} if est == "KMedoids" else {"tol": 0.0}
    model = getattr(ht.cluster, est)(n_clusters=k, init=ht.array(init), max_iter=iters, **kwargs)
    model.fit(ht.array(x, split=split))
    centers, labels, value = _l1_lloyd(x, init, iters, snap=est == "KMedoids")
    # the medoids reach a fixed point before the fourth iteration, and stay
    assert model.n_iter_ == iters if est == "KMedians" else 2 <= model.n_iter_ <= iters
    np.testing.assert_array_equal(model.cluster_centers_.numpy(), centers)
    np.testing.assert_array_equal(model.labels_.numpy(), labels)
    assert model.labels_.split == split
    np.testing.assert_allclose(model.inertia_, value, rtol=1e-5)


def test_fit_keeps_an_empty_clusters_centre():
    x, init = _blobs(300, 3, 3, seed=9)
    init[2] = 1e6  # no row is nearest to it
    model = ht.cluster.KMedians(n_clusters=3, init=ht.array(init), max_iter=2, tol=0.0).fit(ht.array(x, split=0))
    np.testing.assert_array_equal(model.cluster_centers_.numpy()[2], init[2])
    np.testing.assert_array_equal(model.cluster_centers_.numpy(), _l1_lloyd(x, init, 2, False)[0])


# --------------------------------------------------------------------- #
# the Pallas passes, in interpret mode, against the jax.numpy form       #
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("n,d,k", [(300, 8, 3), (1024, 16, 8), (2500, 64, 5)], ids=["short", "one_block", "masked_tail"])
def test_pallas_passes_agree_with_the_xla_form(n, d, k):
    rng = np.random.default_rng(n)
    x = rng.normal(size=(n, d)).astype(np.float32)
    x[::5] = np.round(x[::5])
    centers = rng.normal(size=(k, d)).astype(np.float32)
    chip, plain = pl1.l1_passes(k, (n, d), interpret=True), kc._l1_passes_xla(k)
    labels, counts, fun = chip.assign(x, centers)
    want_labels, want_counts, want_fun = plain.assign(x, centers)
    np.testing.assert_array_equal(labels, want_labels)
    np.testing.assert_array_equal(counts, want_counts)
    np.testing.assert_allclose(fun, want_fun, rtol=1e-5)
    at, step = ps._to_key(jnp.asarray(centers)), jnp.int32(1 << 20)
    np.testing.assert_array_equal(chip.count_below(x, labels, at, step), plain.count_below(x, labels, at, step))
    np.testing.assert_array_equal(chip.next_above(x, labels, at), plain.next_above(x, labels, at))
    got = kc._cluster_medians(jnp.asarray(x), labels, k, jnp.asarray(centers), counts, chip)
    np.testing.assert_array_equal(got, _median_by_cluster(x, np.asarray(labels), k, centers))


@pytest.mark.parametrize("split", [0, None], ids=["split0", "replicated"])
def test_pallas_passes_on_a_mesh_sum_the_shards_counts(split):
    """Under ``shard_map`` over the 8-device mesh: each device counts its own
    rows, the counts are ``psum``med (the successor: ``pmin``) before a
    bracket narrows, and the medians are those of the whole array."""
    comm = ht.MPI_WORLD
    n, d, k = 8 * 160, 8, 3
    rng = np.random.default_rng(2)
    x = rng.normal(size=(n, d)).astype(np.float32)
    centers = rng.normal(size=(k, d)).astype(np.float32)
    chip = pl1.l1_passes(k, (n, d), comm.mesh, comm.axis_name if split == 0 else None, interpret=True)
    xs = jax.device_put(x, comm.sharding(2, split))

    @jax.jit
    def run(a, c):
        labels, counts, _ = chip.assign(a, c)
        return labels, kc._cluster_medians(a, labels, k, c, counts, chip)

    labels, got = run(xs, centers)
    want_labels, _ = _l1_labels(x, centers)
    np.testing.assert_array_equal(labels, want_labels)
    np.testing.assert_array_equal(got, _median_by_cluster(x, want_labels, k, centers))


# --------------------------------------------------------------------- #
# the selection that ends on the keys one gathering pass keeps (PR 33)   #
# --------------------------------------------------------------------- #
@pytest.fixture
def gather_at_any_size(monkeypatch):
    """``gather_pays`` asks for 2**17 rows a cluster and device, and
    ``crowded`` for windows that hold at most one row in
    ``_GATHER_MOST_OF_X`` of a feature; the kernels run here in interpret
    mode, on a few hundred rows, most of them in a window. With no window
    ever crowded the selection gathers as soon as an offset fits under the
    label: after the fourth digit on ``X``."""
    monkeypatch.setattr(ps, "_GATHER_MIN_ROWS_A_CLUSTER", 0)
    monkeypatch.setattr(ps, "_GATHER_MOST_OF_X", 1)
    pl1.l1_passes.cache_clear(), ps.select_passes.cache_clear()
    yield
    pl1.l1_passes.cache_clear(), ps.select_passes.cache_clear()


def _near(rng, shape, span, at=1.0):
    """f32 values ``at`` + i ulps, i uniform under ``span``: neighbours in
    key space, so that one window holds them all, at whatever digit it is
    taken (2 ** 25 keys at the fourth, 2 ** 9 at the twelfth)."""
    return (np.float32(at).view(np.int32) + rng.integers(0, span, size=shape).astype(np.int32)).view(np.float32)


@pytest.fixture
def any_bracket_is_a_window(monkeypatch):
    """A window is a pair's last bracket with ``_WINDOW_MIN_KEYS`` keys: 32.
    The ``_cores`` put six in a bracket, so that a block of a few thousand
    rows keeps few enough not to spill, and ``_spread`` a few thousand rows
    over brackets that hold a handful by the digit at which they fit."""
    monkeypatch.setattr(sel, "_WINDOW_MIN_KEYS", 1)


def _cores(rng, n, d, k, core=6):
    """Random labels; in every (cluster, feature) ``core`` values that are
    neighbours in key space hold the middle ranks, the rest lie far below
    and far above (beyond the window of a fourth bracket, which spans a
    factor of sixteen): few keys are kept, and the medians are among them."""
    labels = rng.integers(0, k, size=n)
    x = np.empty((n, d), np.float32)
    for c in range(k):
        rows = np.flatnonzero(labels == c)
        below = (len(rows) - core) // 2
        for j in range(d):
            col = np.concatenate([-rng.uniform(1e4, 1e5, size=below), _near(rng, core, 3000, 1.0 + c + j),
                                  rng.uniform(1e4, 1e5, size=len(rows) - core - below)]).astype(np.float32)
            x[rows, j] = rng.permutation(col)
    return x, labels, k


def _gather_case(name, rng):
    """(x, labels, k, where the selection has to end: "kept", "x" or None
    for either) of one named case of the gather path."""
    if name in ("odd_counts", "even_counts"):  # all in one window
        m = 101 if name == "odd_counts" else 100
        return _near(rng, (3 * m, 5), 3000), np.repeat(np.arange(3), m), 3, "kept"
    if name == "duplicates_at_the_median":
        return _near(rng, (400, 4), 6), rng.integers(0, 3, size=400), 3, "kept"
    if name == "one_empty_cluster":
        labels = rng.integers(0, 4, size=300)
        labels[labels == 2] = 0
        return _near(rng, (300, 7), 3000), labels, 4, "kept"
    if name in ("even_with_tied_middle", "negative_and_zeros"):  # the second: windows that straddle -0.0 | 0.0
        return _case(name, rng) + ("kept",)
    if name == "masked_tail":  # 2500 rows: 20 lane chunks in one masked block
        return _cores(rng, 2500, 64, 3) + ("kept",)
    if name == "medians_near_zero":  # where f32 keys are sparse: a window of the fourth digit holds a third of the rows
        return rng.normal(size=(3 * 401, 5)) + 0.01, np.repeat(np.arange(3), 401), 3, None
    if name == "window_of_an_earlier_digit":
        # twenty keys in the ninth bracket (and in the eighth) of the lower middle value, the upper one 2 ** 16 keys
        # on: the seventh bracket is the last with 32 keys, and its window holds both (here the fourth's does:
        # ``test_counts_decide_the_digits_on_x`` has the selection go on to the tenth digit on these columns)
        core = np.float32(1.0).view(np.int32) + np.concatenate([100 * np.arange(20), (1 << 16) + 100 * np.arange(20)])
        col = np.concatenate([-rng.uniform(100, 1000, size=105), core.astype(np.int32).view(np.float32),
                              rng.uniform(100, 1000, size=105)]).astype(np.float32)
        return np.stack([rng.permutation(col) for _ in range(2 * 3)]).reshape(2, 3, 250).transpose(0, 2, 1).reshape(500, 3), \
            np.repeat(np.arange(2), 250), 2, "kept"
    if name == "upper_rank_beyond_the_window":  # an even count whose middle values are far apart
        x = np.concatenate([_near(rng, (50, 3), 3000, 1.0), _near(rng, (50, 3), 3000, 100.0)])
        return rng.permutation(x), np.zeros(100, int), 1, "x"
    if name in ("k32_last_cluster_beyond_its_window", "k32_all_on_kept"):
        # the last of 32 clusters: its kept keys end at the type's max, not at ``32 << 26``. 512 rows: four to a lane
        # position; sixteen keys a pair: the windows are of the fourth digit, [0.5, 8). In the first case the last
        # cluster's two middle values lie far apart, and the selection has to see its upper rank beyond the window
        x = _near(rng, (32 * 16, 2), 3000)
        if name == "k32_last_cluster_beyond_its_window":
            x[31 * 16 + 8:] = _near(rng, (8, 2), 3000, 100.0)
        return x, np.repeat(np.arange(32), 16), 32, "x" if name == "k32_last_cluster_beyond_its_window" else "kept"
    if name == "sorted_column":  # 2000 neighbours in key space, row after row: 16 to a lane position, 6 slots
        x = (np.float32(1.0).view(np.int32) + np.arange(2000, dtype=np.int32)).view(np.float32)
        return np.stack([x, x[::-1]], axis=1), rng.integers(0, 2, size=2000), 2, "x"
    if name == "one_repeated_value":
        return np.full((2000, 2), 0.5, np.float32), rng.integers(0, 2, size=2000), 2, "x"
    return _case(name, rng) + (None,)  # wide data: most upper ranks lie beyond their windows


GATHER_CASES = ["odd_counts", "even_counts", "duplicates_at_the_median", "one_empty_cluster", "even_with_tied_middle",
                "negative_and_zeros", "masked_tail", "medians_near_zero", "window_of_an_earlier_digit",
                "upper_rank_beyond_the_window", "sorted_column",
                "one_repeated_value", "zero_straddling", "duplicates", "huge_and_tiny", "single_rows",
                "k32_last_cluster_beyond_its_window", "k32_all_on_kept"]


def _ends_where(passes, ends):
    """``passes`` whose passes over ``X`` say when they run: the digits
    ended on ``X`` iff all sixteen counting passes ran there, the upper
    middle value came from ``X`` iff the successor pass ran; the gathering
    pass says what it was told: to skip or not, and the bits of the windows
    (a window taken after digit ``p`` is 33 - 2 ``p`` bits wide)."""

    def count_below(*a):
        jax.debug.callback(lambda: ends.append("count"))
        return passes.count_below(*a)

    def next_above(*a):
        jax.debug.callback(lambda: ends.append("x"))
        return passes.next_above(*a)

    def gather(arr, lab, base, bits, skip):
        jax.debug.callback(lambda b, s: ends.append(("gather", np.asarray(b), bool(s))), bits, skip)
        return passes.gather(arr, lab, base, bits, skip)

    return passes._replace(count_below=count_below, next_above=next_above, gather=gather)


def _medians_and_end(x, labels, k, prev, passes, counted=None, gathers=None):
    """The medians, and where the selection ended: "x" where anything of it
    came from ``X`` after the gathering pass, else "kept". ``counted``, a
    list, is given the number of counting passes over ``X``; ``gathers``,
    a list, what each gathering pass was told: (bits (k, d), skip)."""
    ends = []
    run = jax.jit(lambda a, l, p: kc._cluster_medians(a, l, k, p, passes=_ends_where(passes, ends)))
    got = np.asarray(run(x, labels, prev))
    jax.effects_barrier()
    if counted is not None:
        counted.append(ends.count("count"))
    if gathers is not None:
        gathers.extend(e[1:] for e in ends if isinstance(e, tuple))
    return got, "x" if "x" in ends else "kept"


@pytest.mark.parametrize("name", GATHER_CASES)
def test_selection_on_the_kept_keys_gives_numpys_medians(name, gather_at_any_size, request):
    """Four digits on ``X`` (no window is crowded here), one gathering pass,
    the other twelve digits and the upper middle value among the kept keys:
    ``numpy.median`` by cluster. Where a lane position holds more keys than
    slots, or an upper rank lies beyond its window, the selection ends on
    ``X`` and gives the same."""
    if name == "masked_tail":
        request.getfixturevalue("any_bracket_is_a_window")
    rng = np.random.default_rng(GATHER_CASES.index(name))
    x, labels, k, end = _gather_case(name, rng)
    x = x.astype(np.float32)
    prev = rng.normal(size=(k, x.shape[1])).astype(np.float32)
    passes = pl1.l1_passes(k, x.shape, interpret=True)
    assert passes.gather is not None
    got, ended = _medians_and_end(x, labels.astype(np.int32), k, prev, passes)
    with np.errstate(invalid="ignore"):
        np.testing.assert_array_equal(got, _median_by_cluster(x, labels, k, prev))
    assert end in (None, ended)


@pytest.mark.parametrize("name, on_x, successor", [("odd_counts", 4, "kept"), ("upper_rank_beyond_the_window", 4, "x"),
                                                   ("k32_last_cluster_beyond_its_window", 4, "x"), ("sorted_column", 16, "x")])
def test_only_what_the_kept_keys_lack_comes_from_x(name, on_x, successor, gather_at_any_size):
    """A spill sends the other digits (here twelve: the windows fit after
    the fourth) and the successor back to ``X``; an upper rank beyond its
    window (a median within 1e-6 of zero on the cell's data) only the
    successor pass: the digits are the kept keys'."""
    rng = np.random.default_rng(GATHER_CASES.index(name))
    x, labels, k, _ = _gather_case(name, rng)
    x, prev, counted = x.astype(np.float32), np.zeros((k, x.shape[1]), np.float32), []
    got, ended = _medians_and_end(x, labels.astype(np.int32), k, prev, pl1.l1_passes(k, x.shape, interpret=True), counted)
    np.testing.assert_array_equal(got, _median_by_cluster(x, labels, k, prev))
    assert (counted, ended) == ([on_x], successor)


def test_window_never_runs_past_the_last_key(gather_at_any_size):
    """Medians of 2 ** 127 or more: the bracket above the fourth digit's
    lies past the type's max, and a window that took it in would wrap around
    to the most negative values. The window is the bracket alone. (Not under
    ``jit``: compiled, ``0.5 a + 0.5 b`` of such values is ``0.5 (a + b)``,
    inf, whichever path found them.)"""
    rng = np.random.default_rng(11)
    huge = lambda m: (rng.uniform(1.0, 1.9, size=(m, 2)) * 2.0 ** 127).astype(np.float32)
    x = np.concatenate([rng.permutation(np.concatenate([-huge(8), huge(13)])),
                        rng.permutation(np.concatenate([-huge(8), huge(12), np.ones((10, 2), np.float32)]))])
    labels, bits_seen = np.repeat(np.arange(2), [21, 30]).astype(np.int32), []
    passes = pl1.l1_passes(2, x.shape, interpret=True)

    def gather(arr, lab, base, bits, skip):
        bits_seen.append(np.asarray(bits))
        return passes.gather(arr, lab, base, bits, skip)

    got = kc._cluster_medians(jnp.asarray(x), labels, 2, np.zeros((2, 2), np.float32), passes=passes._replace(gather=gather))
    want = _median_by_cluster(x.astype(np.float64), labels, 2, None).astype(np.float32)  # rounded once, as 0.5 a + 0.5 b is
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(bits_seen, [[[24, 24], [25, 25]]])  # the second cluster's medians: 1.0 and one of the huge


def test_gather_folds_steps_onto_blocks_of_slots(gather_at_any_size, any_bracket_is_a_window, monkeypatch):
    """Thirty-six grid steps of 256 rows (two lane chunks each, the last one
    masked) fold onto three blocks of ``_KEPT_SLOTS`` slots: the kept array
    is the size ``kept_lanes`` says, holds every key of a window once, and
    the selection ends on it. Told to skip, the pass says "spilled"."""
    n, d, k = 256 * 35 + 7, 8, 3
    assert -(-36 // ps._KEPT_STEPS) == 3
    monkeypatch.setattr(ps, "_pick_tn", lambda n, d, k8: 256)  # for 2 MiB of X a step it picks 65536 rows at d 8
    ps._gather_program.cache_clear()
    x, labels, _ = _cores(np.random.default_rng(1), n, d, k)
    lanes = ps.kept_lanes(n, d, k)
    assert lanes == 3 * ps._KEPT_SLOTS * 128
    passes = pl1.l1_passes(k, (n, d), interpret=True)
    base = np.full((k, d), np.float32(1.0).view(np.int32), np.int32) + (np.arange(k)[:, None] << 23)
    bits = np.full((k, d), 15, np.int32)
    kept, spilled = passes.gather(x, labels.astype(np.int32), base, bits, False)  # 2 ** 15 keys from 1.0, 2.0 and 4.0 on
    assert kept.shape == (d, lanes) and not bool(spilled)
    kept = np.asarray(kept)
    for c in range(k):
        off = np.asarray(ps._to_key(jnp.asarray(x[labels == c]))) - base[c]
        for j in range(d):
            mine = kept[j][kept[j] >> ps._LABEL_SHIFT == c] & ((1 << ps._LABEL_SHIFT) - 1)
            np.testing.assert_array_equal(np.sort(mine), np.sort(off[:, j][(off[:, j] >= 0) & (off[:, j] < 1 << 15)]))
    ahead, in_window = ps.kept_by_target(passes, kept, k)
    np.testing.assert_array_equal(in_window, [[((off := ps._to_key(jnp.asarray(x[labels == c, j])) - base[c, j]) >= 0).sum()
                                               - (off >= 1 << 15).sum() for j in range(d)] for c in range(k)])
    np.testing.assert_array_equal(ahead, np.cumsum(in_window, axis=0) - in_window)
    assert bool(passes.gather(x, labels.astype(np.int32), base, bits, True)[1])
    prev = np.zeros((k, d), np.float32)
    got, ended = _medians_and_end(x, labels.astype(np.int32), k, prev, passes)
    np.testing.assert_array_equal(got, _median_by_cluster(x, labels, k, prev))
    assert ended == "kept"
    ps._gather_program.cache_clear()


@pytest.mark.parametrize("data, skips", [("cores", False), ("sorted_column", True), ("one_repeated_value", True),
                                         ("one_crowded_feature", True)])
def test_crowded_windows_skip_the_gathering_pass(data, skips, gather_at_any_size, any_bracket_is_a_window, monkeypatch):
    """The counting passes say how many keys each window's bracket holds:
    where some feature's hold more than one row in ``_GATHER_MOST_OF_X``
    (here 64) the gathering pass is told to skip, reads nothing, and the
    selection ends on ``X``; numpy's medians either way."""
    monkeypatch.setattr(ps, "_GATHER_MOST_OF_X", 64)
    rng = np.random.default_rng(7)
    if data in ("cores", "one_crowded_feature"):  # 18 keys a feature in the windows' brackets, of 2500 rows
        x, labels, k = _cores(rng, 2500, 8, 3)
        if data == "one_crowded_feature":
            x[:, 5] = _near(rng, 2500, 3000)
    else:
        x, labels, k, _ = _gather_case(data, rng)
    told, prev = [], np.zeros((k, x.shape[1]), np.float32)
    got, ended = _medians_and_end(x.astype(np.float32), labels.astype(np.int32), k, prev,
                                  pl1.l1_passes(k, x.shape, interpret=True), gathers=told)
    np.testing.assert_array_equal(got, _median_by_cluster(x.astype(np.float32), labels, k, prev))
    assert [skip for _, skip in told] == [skips] and ended == ("x" if skips else "kept")


# --------------------------------------------------------------------- #
# the counts decide how many digits are counted on X (PR 37)             #
# --------------------------------------------------------------------- #
def _spread(rng, n, d, k, log2_span, at=2.0):
    """Random labels; every value one of ``2 ** log2_span`` neighbours in key
    space from ``at`` on, uniformly: a bracket of ``2 ** b`` keys (after
    digit ``16 - b / 2``) holds one row in ``2 ** (log2_span - b)``, so the
    data say after which digit the windows fit the slots."""
    return _near(rng, (n, d), 1 << log2_span, at), rng.integers(0, k, size=n), k


def _cores_at_one_lane_position(rng, n, d, k):
    """``_cores`` whose middle values all sit in the rows at lane position 0
    (every 128th): few keys in the windows, which the counts can see, and
    all of them in one lane position, which they cannot."""
    labels = rng.integers(0, k, size=n)
    x = np.empty((n, d), np.float32)
    for c in range(k):
        rows = np.flatnonzero(labels == c)
        core, rest = rows[rows % 128 == 0], rng.permutation(rows[rows % 128 != 0])
        below = (len(rows) - len(core)) // 2
        for j in range(d):
            x[core, j] = _near(rng, len(core), 3000, 2.0 + c + j)
            x[rest[:below], j] = -rng.uniform(1e4, 1e5, size=below)
            x[rest[below:], j] = rng.uniform(1e4, 1e5, size=len(rest) - below)
    return x, labels, k


def _rule_case(name, rng):
    """(x, labels, k, one row in how many a feature's windows may hold) of a
    case of the rule. 4096 rows are one grid step, 32 rows a lane position
    (eight steps of 4 on the mesh of eight)."""
    if name.startswith("fits_after_"):  # windows that hold one row in 256 after that digit, one in 64 a digit earlier
        digit = int(name.rsplit("_", 1)[1])
        return _spread(rng, 4096, 4, 2, 40 - 2 * digit) + (128,)
    if name == "never_fits":  # one row in 64 after the twelfth digit: crowded at the cap
        return _spread(rng, 4096, 4, 2, 14) + (128,)
    if name == "one_repeated_value":
        return _gather_case(name, rng)[:3] + (128,)
    if name == "spills_after_four":  # 64 keys a feature in the windows, of 8192 rows: they fit by the counts; at
        # lane position 0 they are 64 on one device and 8 on each of eight, over the six slots
        return _cores_at_one_lane_position(rng, 8192, 3, 2) + (64,)
    if name == "a_window_of_an_earlier_digit":
        # the columns of ``window_of_an_earlier_digit`` (40 keys a pair in the seventh bracket, 20 in those after it:
        # the window stays the seventh's, 80 of 500 rows a feature) beside one whose windows hold all 500 rows after
        # the eighth digit and a quarter of them after the ninth
        x, labels, k, _ = _gather_case("window_of_an_earlier_digit", rng)
        return np.concatenate([x, _near(rng, (500, 1), 1 << 16, 2.0)], axis=1), labels, k, 2
    raise KeyError(name)


RULE_CASES = [("fits_after_8", 8, "kept"), ("fits_after_10", 10, "kept"), ("fits_after_11", 11, "kept"),
              ("fits_after_12", 12, "kept"), ("never_fits", 12, "skipped"), ("one_repeated_value", 12, "skipped"),
              ("spills_after_four", 4, "spilled"), ("a_window_of_an_earlier_digit", 9, "kept")]


@pytest.mark.parametrize("name, digits, then", RULE_CASES, ids=[c[0] for c in RULE_CASES])
@pytest.mark.parametrize("mesh", [False, True], ids=["one_device", "split0_mesh8"])
def test_counts_decide_the_digits_on_x(name, digits, then, mesh, gather_at_any_size, request, monkeypatch):
    """The selection counts digits on ``X`` until no feature's windows hold
    more than one row in ``_GATHER_MOST_OF_X`` (never fewer than four, never
    more than ``_MOST_DIGITS_ON_X``), gathers, and finishes on the kept keys
    from the digit it reached; windows still crowded at the cap tell the
    gathering pass to skip, and a spill after an early stop sends the other
    digits back to ``X`` from the digit reached. On a split array the counts
    are ``psum``med before they are looked at: every device stops at the
    digit one device stops at. ``numpy.median`` by cluster whatever the
    digit."""
    if name != "a_window_of_an_earlier_digit":
        request.getfixturevalue("any_bracket_is_a_window")
    rng = np.random.default_rng([c[0] for c in RULE_CASES].index(name))
    x, labels, k, most = _rule_case(name, rng)
    monkeypatch.setattr(ps, "_GATHER_MOST_OF_X", most)
    x, labels = x.astype(np.float32), labels.astype(np.int32)
    (n, d), own = x.shape, k
    counted, gathers = [], []
    if mesh:
        comm = ht.MPI_WORLD
        if n % comm.size:  # equal shards: rows far above every window, in a cluster of their own, so that no median moves
            x = np.concatenate([x, np.full((-n % comm.size, d), 1e30, np.float32)])
            labels, k = np.concatenate([labels, np.full(-n % comm.size, k, np.int32)]), k + 1
        passes = pl1.l1_passes(k, x.shape, comm.mesh, comm.axis_name, interpret=True)
        xs, ls = jax.device_put(x, comm.sharding(2, 0)), jax.device_put(labels, comm.sharding(1, 0))
    else:
        passes, xs, ls = pl1.l1_passes(k, x.shape, interpret=True), x, labels
    prev = np.zeros((k, d), np.float32)
    got, ended = _medians_and_end(xs, ls, k, prev, passes, counted, gathers)
    np.testing.assert_array_equal(got, _median_by_cluster(x, labels, k, prev))
    (bits, told_to_skip), = gathers
    assert told_to_skip == (then == "skipped")
    if name == "a_window_of_an_earlier_digit":  # the seventh bracket's window beside the ninth's, in one gathering pass
        np.testing.assert_array_equal(bits[:own], np.repeat([[33 - 2 * 7] * 3 + [33 - 2 * digits]], own, axis=0))
    else:
        assert set(bits[:own].ravel()) == {33 - 2 * digits}
    assert (counted, ended) == ([digits if then == "kept" else 16], "kept" if then == "kept" else "x")


@pytest.mark.parametrize("data", ["cores", "wide"], ids=["ends_on_kept", "ends_on_x"])
@pytest.mark.parametrize("split", [0, None], ids=["split0", "replicated"])
def test_selection_on_the_kept_keys_on_a_mesh(split, data, gather_at_any_size, any_bracket_is_a_window):
    """Under ``shard_map`` every device gathers the keys of its own rows;
    the counts among them are ``psum``med, the successors ``pmin``ned, and
    the spill flag ``pmax``ed, so every device takes the same branch."""
    comm = ht.MPI_WORLD
    n, d, k = 8 * 160, 8, 3
    rng = np.random.default_rng(4)
    if data == "cores":
        x, labels, _ = _cores(rng, n, d, k)
    else:
        x, labels = rng.normal(size=(n, d)).astype(np.float32), rng.integers(0, k, size=n)
    prev = rng.normal(size=(k, d)).astype(np.float32)
    passes = pl1.l1_passes(k, (n, d), comm.mesh, comm.axis_name if split == 0 else None, interpret=True)
    assert passes.gather is not None
    xs = jax.device_put(x, comm.sharding(2, split))
    ls = jax.device_put(labels.astype(np.int32), comm.sharding(1, split))
    got, ended = _medians_and_end(xs, ls, k, prev, passes)
    np.testing.assert_array_equal(got, _median_by_cluster(x, labels, k, prev))
    assert ended == ("kept" if data == "cores" else "x")


def test_gather_pays_from_a_size_on():
    """Which path a selection takes reads the shape a device holds and the
    form of the passes only: the ``jax.numpy`` form has no gather, the
    kernels from ``_GATHER_MIN_ROWS_A_CLUSTER`` rows a cluster and device
    on (what a window keeps does not grow with ``n``)."""
    assert kc._l1_passes_xla(8).gather is None
    assert ps.gather_pays(18_750_000, 8) and ps.gather_pays(4_687_500, 8) and ps.gather_pays(4_194_304, 32)
    assert not ps.gather_pays(524_288, 8) and not ps.gather_pays(1_048_576, 16)
    mesh, axis = ht.MPI_WORLD.mesh, ht.MPI_WORLD.axis_name
    assert pl1.l1_passes(8, (1 << 20, 64)).gather is not None
    assert pl1.l1_passes(8, (1 << 19, 64)).gather is None
    assert pl1.l1_passes(8, (1 << 22, 64), mesh, axis).gather is None  # 2**19 rows a device
    assert pl1.l1_passes(8, (1 << 20, 64), mesh, None).kept_below is not None  # replicated: all rows on each


def test_gate_reads_backend_dtype_shape_and_split_only():
    serves = pl1.l1_passes_serve
    with jax.enable_x64(True):  # Mosaic refuses 64-bit traces
        assert not serves("tpu", "float32", (18_750_000, 64), 8, None)
    with jax.enable_x64(False):
        _gate_corners(serves)


def _gate_corners(serves):
    assert serves("tpu", "float32", (18_750_000, 64), 8, None)
    assert serves("tpu", "float32", (18_750_000, 64), 8, 0, 4)
    assert not serves("cpu", "float32", (18_750_000, 64), 8, None)
    assert not serves("tpu", "bfloat16", (18_750_000, 64), 8, None)
    assert not serves("tpu", "float32", (18_750_000, 128), 8, None)
    assert not serves("tpu", "float32", (18_750_000, 64), 33, None)
    assert not serves("tpu", "float32", (18_750_000, 64), 1, None)
    assert not serves("tpu", "float32", (18_750_001, 64), 8, 0, 4)


# --------------------------------------------------------------------- #
# nothing of k x n x d                                                   #
# --------------------------------------------------------------------- #
def _value_sizes(jaxpr) -> set:
    """Elements of every value the equations of ``jaxpr`` (and of the
    jaxprs inside it) make."""
    sizes = set()
    for eqn in jaxpr.eqns:
        sizes.update(int(np.prod(getattr(v.aval, "shape", ()), dtype=np.int64)) for v in eqn.outvars)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            sizes |= _value_sizes(sub)
    return sizes


def _largest_value(jaxpr) -> int:
    return max(_value_sizes(jaxpr), default=0)


@pytest.mark.parametrize("est", ["kmedians", "kmedoids"])
def test_fit_program_holds_no_k_fold_copy_of_x(est):
    """The whole fit program at a small shape: no value larger than ``X``
    itself (the masked-``nanmedian`` ``vmap`` it replaces held ``k x n x d``,
    and the broadcast L1 distance ``n x k x d``)."""
    n, d, k = 640, 16, 8
    step = kc._l1_step(est, k, (n, d), "float32", None, None, None, est == "kmedoids")
    prog = kc._fused_fit_program(step, k, (n, d), "float32", 0.0, 5, False, "manhattan", False)
    a, c = jax.ShapeDtypeStruct((n, d), jnp.float32), jax.ShapeDtypeStruct((k, d), jnp.float32)
    assert _largest_value(jax.make_jaxpr(prog.program)(a, c).jaxpr) <= n * d
    assert prog.program.lower(a, c).compile().memory_analysis().temp_size_in_bytes < k * n * d * 4


@pytest.mark.parametrize("est", ["kmedians", "kmedoids"])
def test_fit_program_on_the_kernels_keeps_a_sixtieth_of_x(est):
    """The fit program on the Pallas passes at the cell's shard, traced and
    not run: the kept array is ``int32[d, kept_lanes]``, 16 x 128 lanes for
    every 16 x 8192 rows (1.6 % of ``X``: 75 MB of 4.8 GB), and no value is
    larger than ``X``."""
    n, d, k = 18_750_000, 64, 8
    assert ps.kept_lanes(n, d, k) == 144 * 2048  # 2289 steps of 8192 rows, sixteen to a block of sixteen slots
    passes = pl1.l1_passes(k, (n, d))

    def step(arr, centers):
        labels, counts, _ = passes.assign(arr, centers)
        new = kc._cluster_medians(arr, labels, k, centers, counts, passes)
        if est == "kmedoids":
            new = kc._snap_to_members(arr, labels, k, new, counts, centers)
        return new, jnp.sum((new - centers) ** 2)

    step.assign = lambda arr, centers: passes.assign(arr, centers)[::2]
    a, c = jax.ShapeDtypeStruct((n, d), jnp.float32), jax.ShapeDtypeStruct((k, d), jnp.float32)
    with jax.enable_x64(False):  # the chip's policy: Mosaic refuses 64-bit traces
        jaxpr = jax.make_jaxpr(kc.make_fit_loop(step, "float32", 0.0, 5, False))(a, c).jaxpr
    assert _largest_value(jaxpr) == n * d
    assert d * ps.kept_lanes(n, d, k) in _value_sizes(jaxpr) and 64 * d * ps.kept_lanes(n, d, k) < 1.01 * n * d


# --------------------------------------------------------------------- #
# the benchmark's check tells the program from a control in bf16         #
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("control, missed", [("program", ()), ("bf16_medians", ("centres",)),
                                             ("all_bf16", ("centres", "labels"))])
def test_benchmark_check_tells_the_program_from_a_bf16_control(control, missed):
    """``benchmarks/ops/kmedians_fit.check`` at the configuration's limits,
    on its toy twin: the program's fit passes; the plain reference on a bf16
    image of ``X`` put in its place does not (medians alone: the centres;
    the assignment too: the labels as well). PERF.md section 6, PR 32, has
    the same three at the cell's shape on the chip."""
    import os
    import sys
    import types

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    try:
        from benchmarks import run as harness

        op = harness.load_module("ops", "kmedians_fit")
    finally:
        sys.path.remove(root)
    cfg = harness.load_json(os.path.join(root, "benchmarks", "configs", "kmedians-northstar.json"))
    chips = ht.MPI_WORLD.size
    cfg = {**cfg, "rows_per_chip": cfg["toy"]["rows_per_chip"] // chips}
    with jax.enable_x64(False):  # the cell's policy
        state = op.make(cfg, chips, jax.random.key(32))
        ref = op.reference(state)
        if control == "program":
            out = op.call(state)
            op.finish(state, out)
        else:
            xs, k, bf = state["x"].larray, state["k"], jnp.bfloat16
            xb, low = xs.astype(bf), control == "all_bf16"
            c = state["init"].larray
            for _ in range(cfg["max_iter"]):
                labels, _ = op._labels(xb, c.astype(bf)) if low else op._labels(xs, c)
                c = op._medians(xb, labels, c.astype(bf), k).astype(jnp.float32)
            labels, value = op._labels(xb, c.astype(bf)) if low else op._labels(xs, c)
            held = lambda a: types.SimpleNamespace(larray=a, shape=a.shape)
            out = {"centers": held(c), "labels": held(labels), "n_iter": cfg["max_iter"],
                   "km": types.SimpleNamespace(inertia_=float(value))}
        misses = op.check(state, out, ref)["misses"]
    assert len(misses) >= len(missed) and all(any(w in m for m in misses) for w in missed), misses
    assert bool(misses) == bool(missed), misses
