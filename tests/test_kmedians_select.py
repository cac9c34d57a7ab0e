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

N_THR = kc._N_THR


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
    key = np.asarray(kc._to_key(jnp.asarray(x)))
    assert (np.diff(key.astype(np.int64)) > 0).all()
    np.testing.assert_array_equal(np.asarray(kc._from_key(jnp.asarray(key), np.float32)), x)


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
    at, step = kc._to_key(jnp.asarray(centers)), jnp.int32(1 << 20)
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
def _largest_value(jaxpr) -> int:
    """Elements of the largest value any equation of ``jaxpr`` (and of the
    jaxprs inside it) makes."""
    largest = 0
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            largest = max(largest, int(np.prod(getattr(v.aval, "shape", ()), dtype=np.int64)))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            largest = max(largest, _largest_value(sub))
    return largest


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
