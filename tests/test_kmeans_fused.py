"""The fused Lloyd pass of KMeans (``cluster/_pallas.py``) on the CPU, in
interpret mode, reached through the builders' ``interpret=True``: the pass
against a NumPy oracle, a whole fit through it against the XLA step, the
``shard_map`` form over the 8-device mesh, the gate as a pure function, and
the programs of the estimators that share the fit builder, unchanged."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import heat_tpu as ht
from heat_tpu.cluster import _kcluster, _pallas, kmeans
from heat_tpu.core import types


def _bf16(a):
    return np.asarray(jnp.asarray(a).astype(jnp.bfloat16).astype(jnp.float32))


def _oracle(x, c):
    """The pass in NumPy: products of bf16-rounded operands, the squares
    and every sum in f32 or wider. Returns d2 (n, k) too, for the margins."""
    xb, cb = _bf16(x), _bf16(c)
    d2 = np.maximum((x ** 2).sum(1)[:, None] + (c ** 2).sum(1)[None, :] - 2.0 * xb @ cb.T, 0.0)
    labels = d2.argmin(1)
    onehot = np.eye(len(c), dtype=np.float32)[labels]
    return onehot.T @ xb, onehot.sum(0), d2.min(1).sum(), labels, d2


def _clear(d2, margin=1e-3):
    """Rows whose two nearest centres differ by more than ``margin``: there
    the label does not hang on the order of an f32 sum."""
    two = np.sort(d2, axis=1)[:, :2]
    return two[:, 1] - two[:, 0] > margin


@pytest.mark.parametrize("d", [8, 16, 64])
@pytest.mark.parametrize("k", [3, 4, 8, 11])
@pytest.mark.parametrize("n,tn", [(1003, 0), (64, 0), (1025, 1024)], ids=["n1003", "n64", "tile_plus_1"])
def test_pass_matches_oracle(n, tn, k, d):
    """Ragged ``n`` (one part tile; one whole tile and one row), ``k`` off
    and on the 8 sublanes, three widths."""
    rng = np.random.default_rng(n + 13 * k + d)
    x = rng.standard_normal((n, d)).astype(np.float32)
    c = x[rng.choice(n, k, replace=False)] + 0.1
    prog = _pallas.lloyd_pass_program(n, d, k, True, True, tn)
    sums, counts, inertia, labels = prog(jnp.asarray(x), jnp.asarray(c))
    want_sums, want_counts, want_inertia, want_labels, d2 = _oracle(x, c)
    assert labels.shape == (n,) and labels.dtype == jnp.int32
    clear = _clear(d2)
    assert clear.mean() > 0.95
    np.testing.assert_array_equal(np.asarray(labels)[clear], want_labels[clear])
    if clear.all():
        np.testing.assert_allclose(np.asarray(sums), want_sums, rtol=1e-5, atol=1e-4)
        np.testing.assert_array_equal(np.asarray(counts), want_counts)
    assert float(np.asarray(counts).sum()) == n
    np.testing.assert_allclose(float(inertia), want_inertia, rtol=1e-5)
    # the pass without the label output: the same three
    s2, c2, i2 = _pallas.lloyd_pass_program(n, d, k, False, True, tn)(jnp.asarray(x), jnp.asarray(c))
    np.testing.assert_array_equal(np.asarray(s2), np.asarray(sums))
    np.testing.assert_array_equal(np.asarray(c2), np.asarray(counts))
    assert float(i2) == float(inertia)


def _blobs(n, d, k, seed, scale=4.0):
    rng = np.random.default_rng(seed)
    cen = scale * rng.standard_normal((k, d)).astype(np.float32)
    y = rng.integers(0, k, n)
    x = (cen[y] + rng.standard_normal((n, d))).astype(np.float32)
    return x, (cen + 0.5 * rng.standard_normal((k, d))).astype(np.float32)


def _interpreted(mesh=None, axis_name=None):
    """A ``step_factory`` for ``_fit_fused`` that builds the fused step in
    interpret mode, whatever the gate would say of a CPU."""
    return lambda k, shape, jdtype: _pallas.fused_lloyd_step(k, tuple(shape), mesh, axis_name, True)


def test_whole_fit_through_fused_step_matches_xla_step():
    n, d, k = 1003, 16, 4
    x_np, init = _blobs(n, d, k, 5, scale=1.5)
    x = ht.array(x_np)
    fused = ht.cluster.KMeans(k, init=ht.array(init), max_iter=3, tol=0.0)
    fused._fit_fused(x, _interpreted(), returns_inertia=True)
    xla = ht.cluster.KMeans(k, init=ht.array(init), max_iter=3, tol=0.0).fit(x)
    assert fused.n_iter_ == xla.n_iter_ == 3
    np.testing.assert_allclose(fused.cluster_centers_.numpy(), xla.cluster_centers_.numpy(), atol=2e-3)
    # the pass multiplies bf16 operands as the chip's default-precision dot does; the CPU's
    # XLA step multiplies f32: the inertia of the last iteration differs in the third digit
    np.testing.assert_allclose(fused.inertia_, xla.inertia_, rtol=5e-3)
    assert fused.labels_.shape == (n,) and fused.labels_.dtype == xla.labels_.dtype
    clear = _clear(_oracle(x_np, xla.cluster_centers_.numpy())[4])
    np.testing.assert_array_equal(fused.labels_.numpy()[clear], xla.labels_.numpy()[clear])
    # convergence on the device: with a tolerance both stop at the same iteration
    a = ht.cluster.KMeans(k, init=ht.array(init), max_iter=50, tol=1e-4)
    a._fit_fused(x, _interpreted(), returns_inertia=True)
    b = ht.cluster.KMeans(k, init=ht.array(init), max_iter=50, tol=1e-4).fit(x)
    assert 3 < a.n_iter_ == b.n_iter_ < 50


def test_empty_cluster_keeps_its_centre():
    n, d, k = 300, 8, 3
    x, init = _blobs(n, d, k, 9)
    init[2] = 1e3  # nobody's nearest
    step = _pallas.fused_lloyd_step(k, (n, d), None, None, True)
    new, shift, inertia = step(jnp.asarray(x), jnp.asarray(init))
    np.testing.assert_array_equal(np.asarray(new)[2], init[2])
    assert np.isfinite(np.asarray(new)).all() and float(shift) > 0 and float(inertia) > 0
    assert int(np.asarray(step.assign(jnp.asarray(x), jnp.asarray(init))).max()) <= 1


def test_split0_under_shard_map_equals_one_device():
    """``X`` split 0 over the 8-device mesh: the pass on each device's rows
    (125 each: every shard one ragged tile) and one ``psum``."""
    n, d, k = 1000, 16, 5
    x_np, init = _blobs(n, d, k, 21, scale=1.0)
    x = ht.array(x_np, split=0)
    comm = x.comm
    assert comm.size == 8 and x.larray.shape == (n, d)
    one = _pallas.fused_lloyd_step(k, (n, d), None, None, True)
    many = _pallas.fused_lloyd_step(k, (n, d), comm.mesh, comm.axis_name, True)
    c = jnp.asarray(init)
    got, want = jax.jit(many)(x.larray, c), one(jnp.asarray(x_np), c)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5, atol=1e-6)
    labels = jax.jit(many.assign)(x.larray, c)
    assert len(labels.sharding.device_set) == 8
    np.testing.assert_array_equal(np.asarray(labels), np.asarray(one.assign(jnp.asarray(x_np), c)))
    # replicated over the mesh: every device the whole pass, no psum
    whole = _pallas.fused_lloyd_step(k, (n, d), comm.mesh, None, True)
    for g, w in zip(jax.jit(whole)(ht.array(x_np).larray, c), want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-6)
    # and the whole fit on the split array
    km = ht.cluster.KMeans(k, init=ht.array(init), max_iter=2, tol=0.0)
    km._fit_fused(x, _interpreted(comm.mesh, comm.axis_name), returns_inertia=True)
    ref = ht.cluster.KMeans(k, init=ht.array(init), max_iter=2, tol=0.0)
    ref._fit_fused(ht.array(x_np), _interpreted(), returns_inertia=True)
    assert km.labels_.split == 0 and ref.labels_.split is None and km.n_iter_ == ref.n_iter_ == 2
    np.testing.assert_allclose(km.cluster_centers_.numpy(), ref.cluster_centers_.numpy(), atol=1e-5)
    np.testing.assert_array_equal(km.labels_.numpy(), ref.labels_.numpy())


_TALL = (100_000, 64)


@pytest.mark.parametrize(
    "backend,dtype,shape,k,split,devices,want",
    [
        ("tpu", "float32", _TALL, 8, None, 1, True),
        ("tpu", "float32", (64, 8), 1, None, 1, True),
        ("tpu", "float32", (100_000, 120), 128, 0, 4, True),
        ("tpu", "float32", _TALL, 8, None, 4, True),  # replicated over a mesh: every chip the whole pass
        ("cpu", "float32", _TALL, 8, None, 1, False),
        ("gpu", "float32", _TALL, 8, None, 1, False),
        ("tpu", "bfloat16", _TALL, 8, None, 1, False),
        ("tpu", "float64", _TALL, 8, None, 1, False),
        ("tpu", "float32", (100_000, 128), 8, None, 1, False),  # row-major on the chip
        ("tpu", "float32", (100_000, 12), 8, None, 1, False),
        ("tpu", "float32", (100_000, 4), 8, None, 1, False),
        ("tpu", "float32", _TALL, 129, None, 1, False),
        ("tpu", "float32", _TALL, 8, 1, 4, False),
        ("tpu", "float32", (100_001, 64), 8, 0, 4, False),  # unequal shards: the logical array is a slice
    ],
)
def test_gate_is_a_pure_function_of_what_it_sees(backend, dtype, shape, k, split, devices, want):
    with jax.enable_x64(False):  # the chip's policy; this suite runs x64
        assert _pallas.lloyd_pass_serves(backend, dtype, shape, k, split, devices) is want
    assert _pallas.lloyd_pass_serves(backend, dtype, shape, k, split, devices) is False  # x64: never


def test_cpu_fit_runs_and_counts_the_xla_step():
    x_np, init = _blobs(400, 8, 3, 2)
    kmeans._lloyd_step.cache_clear()
    was = ht.telemetry.enabled()
    ht.telemetry.enable()
    try:
        before = dict(ht.telemetry.snapshot()["counters"])
        for _ in range(2):  # the second fit is a cache hit and counts all the same
            ht.cluster.KMeans(3, init=ht.array(init), max_iter=3, tol=0.0).fit(ht.array(x_np, split=0))
        after = ht.telemetry.snapshot()["counters"]
    finally:
        if not was:
            ht.telemetry.disable()
    assert after.get("kmeans.step.xla", 0) - before.get("kmeans.step.xla", 0) == 2
    assert after.get("kmeans.step.fused", 0) == before.get("kmeans.step.fused", 0)
    step = kmeans._lloyd_step(3, (400, 8), "float32", 0, ht.MPI_WORLD.mesh, ht.MPI_WORLD.axis_name)
    assert getattr(step, "assign", None) is None


@pytest.mark.parametrize("name", ["kmedians", "kmedoids"])
def test_shared_fit_builder_takes_the_l1_steps_own_label_pass(name):
    """``_fused_fit_program`` asks a step for its own assignment. The L1
    step (PR 32) returns no inertia, so its ``assign`` gives (labels,
    functional value): both come from one more run of its assignment pass,
    and the builder lowers to the text written out here (no ``_pairwise``:
    nothing of ``n x k x d``)."""
    n, d, k = 96, 5, 3
    step = _kcluster._l1_step(name, k, (n, d), "float32", None, None, None, name == "kmedoids")
    loop = _kcluster.make_fit_loop(step, "float32", 1e-4, 7, False)

    @jax.jit
    def run(arr, init_arg):
        centers0 = init_arg.astype(arr.dtype)
        res = loop(arr, centers0)
        centers, n_iter = res[0], res[1]
        labels, fun = step.assign(arr, centers)
        return centers, n_iter, labels.astype(types.index_jax_type()), fun

    prog = _kcluster._fused_fit_program(step, k, (n, d), "float32", 1e-4, 7, False, "manhattan", False)
    a, c = jax.ShapeDtypeStruct((n, d), jnp.float32), jax.ShapeDtypeStruct((k, d), jnp.float32)
    assert prog.program.lower(a, c).as_text() == run.lower(a, c).as_text()
