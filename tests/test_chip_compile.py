"""Compile the main path's Pallas kernels for the real chip, without one.

The TPU compiler is installed in the sandbox and compiles for a chip that
is described, not attached (on-chip-measurement guide, section 2.3). A
kernel that passes every interpret-mode test can still be refused here —
for a block that breaks the (8, 128) tiling rule, a float iota, a reshape
Mosaic cannot lay out — so these compiles guard each later PR at no chip
time. ``interpret=False`` throughout; shapes are chip_smoke.py's.

The topology is described inside a module-scoped fixture: only the xdist
worker that is handed this file loads libtpu, and it keeps the lock until
it exits — so every compile runs in this process, and all of them live in
this one file.
"""

import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

F32, BF16, I32 = jnp.float32, jnp.bfloat16, jnp.int32


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device executable can be written to the persistent
    # cache but not read back: keep the cache off around these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # the chip's dtype policy: x64 off (Mosaic refuses 64-bit types). The
    # CPU suite runs x64, switched on by whichever test file first touched
    # the backend in this worker
    with jax.enable_x64(False):
        yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.array(topo.devices), ("d",))


def _compile(fn, *specs):
    """The compiled module's text; raises what the chip's compiler raises."""
    return jax.jit(fn).lower(*specs).compile().as_text()


def _holds_kernel(fn, *specs) -> bool:
    return "tpu_custom_call" in _compile(fn, *specs)


def test_sketch_fused_and_dual(one_chip):
    """hSVD's headline shard, 65536 x 8192 f32: the 2-pass sketch+norm
    kernel and the one-view dual-sketch kernel."""
    from heat_tpu.core.linalg import _pallas_sketch as ps

    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    m, n = 65536, 8192
    assert _holds_kernel(
        ps._fused_call(m, n, 1024, 1024), s((ps._L_PAD, m), F32), s((m, n), F32)
    )
    assert _holds_kernel(
        ps._dual_call(m, n, 512, 1024),
        s((ps._L2_PAD, m), F32), s((n, ps._K_PAD), F32), s((m, n), F32),
    )


@pytest.mark.parametrize("m, n", [(131072, 8192), (8192, 131072)], ids=["tall", "wide"])
def test_hsvd_rank_program_reads_a_twice(one_chip, monkeypatch, m, n):
    """The whole one-chip ``hsvd_rank`` program (rank 10 + 5, sketch width
    25) at the benchmark cell's shape, and at the shape of the four-chip
    level 0's shard of A.T: pass 1 is the kernel, pass 2 one dot that
    reads f32 A, and no bf16 copy of A is made. As a tiled loop pass 2
    had its cast of A hoisted out: a third stream and 2.15 GB of
    temporaries (PERF.md, PR 26). The gate reads the backend: steer it."""
    from heat_tpu.core.linalg import svdtools

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    builder = svdtools._sketched_single_rank_fn
    builder.cache_clear()
    try:
        a = jax.ShapeDtypeStruct((m, n), F32, sharding=one_chip)
        compiled = builder(15, 25, 10, "both").lower(a).compile()
    finally:
        builder.cache_clear()
    txt = compiled.as_text()
    assert "tpu_custom_call" in txt
    assert f"bf16[{m},{n}]" not in txt
    assert compiled.memory_analysis().temp_size_in_bytes < m * n  # was half of A's bytes: the copy


def test_sort_block(one_chip):
    """The radix block kernel (integer iota; one-row blocks on a 3-D
    array). 4 blocks: the (8, 128) rule bites only past one block. (One
    case: this kernel alone compiles for ~12 s.)"""
    from heat_tpu.kernels import sort as ks

    n_blocks, t = 4, ks._PALLAS_BLOCK
    blk = jax.ShapeDtypeStruct((n_blocks, 1, t), I32, sharding=one_chip)
    assert _holds_kernel(ks._pallas_block_call(n_blocks, t, 2, 4, False), blk, blk)


@pytest.mark.parametrize("op", ["pack", "unpack"])
def test_relayout_refused_and_off_auto(one_chip, op, monkeypatch):
    """Mosaic refuses the relayout kernels' in-register reshape, so
    ``auto`` must not pick them (kernels/relayout.AUTO_REFUSAL). When a
    toolchain accepts them this test fails: put them back on ``auto``."""
    from heat_tpu.kernels import relayout as rl

    rows, p = 65536, 4
    b = rl._block_rows(rows, 128)
    if op == "pack":
        call = rl._pack_call(rows // b, b, 64, 128, p, "float32", False)
        spec = jax.ShapeDtypeStruct((rows // b, 1, b * 64), F32, sharding=one_chip)
        sig = ("pack", rows, 64, 128, p, "float32")
    else:
        call = rl._unpack_call(rows // b, b, 128, 64, p, "float32", False)
        spec = jax.ShapeDtypeStruct((p, rows * 32), F32, sharding=one_chip)
        sig = ("unpack", rows, 128, 64, p, "float32")
    with pytest.raises(Exception, match="unsupported shape cast"):
        _compile(call, spec)
    monkeypatch.delenv("HEAT_TPU_RELAYOUT_KERNEL", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert rl.decide(*sig) == "xla"
    assert "unsupported shape cast" in rl.last_decisions()[sig]["why"]


@pytest.mark.parametrize("k", [4, 128])
def test_spmm_brick(one_chip, k):
    from heat_tpu.kernels import spmm

    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    B, nb = 16384, 64
    assert _holds_kernel(
        spmm._brick_spmm_call(B, nb, k, "float32", False),
        s((B,), I32), s((B, spmm.BR, spmm.BC), F32), s((nb, spmm.BC, k), F32),
    )


@pytest.mark.parametrize("d", [64, 128])
def test_sddmm_brick(one_chip, d):
    from heat_tpu.kernels import spmm

    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    B, mb, nb = 16384, 64, 64
    assert _holds_kernel(
        spmm._brick_sddmm_call(B, mb, nb, d, "float32", False),
        s((B,), I32), s((B,), I32), s((B, spmm.BR, spmm.BC), F32),
        s((mb, spmm.BR, d), F32), s((nb, spmm.BC, d), F32),
    )


_X_SIZED_OP = re.compile(r"= (?:f32|bf16)\[(\d+),(\d+)\]\S* (copy|transpose|convert)\(")


def _kmeans_fit_compiled(n, d, k, x_sharding, mesh=None, axis_name=None):
    """The whole fused KMeans fit (Lloyd ``while_loop`` + label pass, init
    given) on the fused pass, compiled for the described chip(s)."""
    from heat_tpu.cluster import _kcluster, _pallas as kp

    step = kp.fused_lloyd_step(k, (n, d), mesh, axis_name)
    builder = _kcluster._fused_fit_program
    builder.cache_clear()
    try:
        prog = builder(step, k, (n, d), "float32", 0.0, 10, True, "euclidean", False)
        x = jax.ShapeDtypeStruct((n, d), F32, sharding=x_sharding)
        c = jax.ShapeDtypeStruct((k, d), F32)
        return prog.program.lower(x, c).compile()
    finally:
        builder.cache_clear()


def _assert_x_is_read_as_it_lies(compiled, rows, d):
    txt = compiled.as_text()
    assert txt.count("tpu_custom_call") >= 2  # the loop's pass and the label pass
    sized = [m.group(0) for m in _X_SIZED_OP.finditer(txt) if {int(m.group(1)), int(m.group(2))} == {rows, d}]
    assert sized == []  # no copy, transpose or cast of X: x.T is a bitcast
    assert compiled.memory_analysis().temp_size_in_bytes < rows * d * 4 // 100


@pytest.mark.parametrize("n", [4_194_304, 15_625_000, 18_750_000])
def test_kmeans_fused_assign(one_chip, n):
    """KMeans' fused fit program up to the benchmark cell's shard
    (18 750 000 x 64, no multiple of 128: the last tile is masked in the
    kernel): the chip holds X feature-major, the pass reads blocks of
    ``x.T``, and nothing of X's size is copied, transposed or cast (the XLA
    step's program: a 2.4 GB bf16 copy; the row-major kernel that stood
    here until PR 28: a 9.6 GB relayout)."""
    _assert_x_is_read_as_it_lies(_kmeans_fit_compiled(n, 64, 8, one_chip), n, 64)


@pytest.mark.parametrize("d,k", [(8, 3), (120, 128)], ids=["d8_k3", "d120_k128"])
def test_kmeans_fused_assign_gate_corners(one_chip, d, k):
    """The corners of ``lloyd_pass_serves``: the narrowest and the widest
    feature-major ``d``, the largest ``k`` (VMEM: 4096 rows a step)."""
    _assert_x_is_read_as_it_lies(_kmeans_fit_compiled(1_000_003, d, k, one_chip), 1_000_003, d)


@pytest.mark.parametrize("n", [1, 300, 1024, 1025])
def test_kmeans_fused_assign_short_arrays(one_chip, n):
    """Fewer rows than one block: the chip tiles the 1-D label output by
    the power of two that holds it (128 to 1024) and refuses any other
    block (a 384-row block of 300 labels; a 1024-row block of one)."""
    assert _kmeans_fit_compiled(n, 16, 4, one_chip).as_text().count("tpu_custom_call") >= 2


@pytest.mark.parametrize("split", [0, None], ids=["split0", "replicated"])
def test_kmeans_fused_assign_four_chips(mesh4, split):
    """The same fit over the 2 x 2, under ``shard_map`` (a bare Mosaic call
    is refused on a mesh). ``X`` split 0: the pass on each chip's rows, the
    sums, counts and inertia ``psum``med (one all-reduce an iteration),
    labels split 0. ``X`` replicated: every chip the whole pass, nothing
    crosses."""
    rows = 4_687_500  # the cell's 18.75M over four chips
    n, spec, axis = (4 * rows, P("d", None), "d") if split == 0 else (rows, P(), None)
    compiled = _kmeans_fit_compiled(n, 64, 8, NamedSharding(mesh4, spec), mesh4, axis)
    _assert_x_is_read_as_it_lies(compiled, rows, 64)
    assert ("all-reduce" in compiled.as_text()) == (split == 0)


def _l1_fit_compiled(n, d, k, x_sharding, mesh=None, axis_name=None, snap=False):
    """The whole fused KMedians (``snap``: KMedoids) fit on the Pallas
    passes, compiled for the described chip(s). ``_l1_step`` asks
    ``jax.default_backend()``, which is the CPU here, so the step is put
    together from its two parts as ``_l1_step`` does."""
    from heat_tpu.cluster import _kcluster as kc, _pallas_l1 as pl1

    passes = pl1.l1_passes(k, (n, d), mesh, axis_name)

    def step(arr, centers):
        labels, counts, _ = passes.assign(arr, centers)
        new = kc._cluster_medians(arr, labels, k, centers, counts, passes)
        if snap:
            new = kc._snap_to_members(arr, labels, k, new, counts, centers)
        return new, jnp.sum((new - centers) ** 2)

    step.assign = lambda arr, centers: passes.assign(arr, centers)[::2]
    builder = kc._fused_fit_program
    builder.cache_clear()
    try:
        prog = builder(step, k, (n, d), "float32", 0.0, 5, False, "manhattan", False)
        x = jax.ShapeDtypeStruct((n, d), F32, sharding=x_sharding)
        c = jax.ShapeDtypeStruct((k, d), F32)
        return prog.program.lower(x, c).compile()
    finally:
        builder.cache_clear()


_CUSTOM_CALL = re.compile(r"^\s*(?:ROOT )?%(\S+) = .*? custom-call\((.*?)\), custom_call_target=\"tpu_custom_call\"", re.M)
_DEFINED = re.compile(r"^\s*(?:ROOT )?%(\S+) = (\w+\[[\d,]*\])", re.M)


def _kernels(txt: str) -> list:
    """(name, shapes of the array operands) of the compiled module's Mosaic kernels."""
    shape_of = dict(_DEFINED.findall(txt))
    return [(m.group(1), [shape_of.get(op.strip().lstrip("%"), "") for op in m.group(2).split(",")])
            for m in _CUSTOM_CALL.finditer(txt)]


def _assert_l1_fit_holds_x_alone(compiled, rows, d, gathers=True):
    txt = compiled.as_text()
    # the loop's assignment, its counting pass and its successor pass, and the label pass
    assert txt.count("tpu_custom_call") >= 4
    assert "kmedians.assign.pass" in txt and "kmedians.select.pass" in txt  # the names the benchmark reads
    sized = [m.group(0) for m in _X_SIZED_OP.finditer(txt) if {int(m.group(1)), int(m.group(2))} == {rows, d}]
    assert sized == []  # no copy, transpose or cast of X
    assert compiled.memory_analysis().temp_size_in_bytes < rows * d * 4 // 100  # the 75 MB kept lie in fast memory
    # one op named ``.pass`` is one whole read of X, and no other kernel reads X: the benchmark's
    # readers count the first by name, and divide their bytes by their time
    kernels = _kernels(txt)
    assert len(kernels) == txt.count("tpu_custom_call")
    for name, operands in kernels:
        reads_x = f"f32[{d},{rows}]" in operands or f"f32[{rows},{d}]" in operands
        assert reads_x == (".pass" in name), (name, operands)
        assert name.startswith(("kmedians.assign.pass", "kmedians.select.pass", "kmedians.select.candidates")), name
    over_kept = [name for name, _ in kernels if name.startswith("kmedians.select.candidates")]
    assert bool(over_kept) == gathers
    if gathers:  # the loop's gathering pass, the counts under ``_N_THR`` x k and under k + 1 thresholds, the successor
        from heat_tpu.core import _pallas_select as ps

        assert len(over_kept) >= 3 and f"s32[{d},{ps.kept_lanes(rows, d, 8)}]" in txt
        assert 60 * ps.kept_lanes(rows, d, 8) < rows  # the kept array: under a sixtieth of X, 75.5 MB of 4.8 GB


@pytest.mark.parametrize("snap", [False, True], ids=["kmedians", "kmedoids"])
def test_l1_fit_at_the_north_star_shard(one_chip, snap):
    """``kmedians-northstar``'s shard, 18 750 000 x 64 f32, k 8 (no multiple
    of 128: the last tile is masked in every kernel): the fit program holds
    ``X``, the label vector and k x d x thresholds of integers, where the
    masked-``nanmedian`` ``vmap`` asked for 38.4 GB. Since PR 33 also the
    gathering pass, the array it keeps (``int32[64, 294912]``, 75 MB: blocks
    of sixteen slots for sixteen steps since PR 37) and the ops that finish
    the selection on it, in both branches of the ``cond``; since PR 37 the
    digits on ``X`` are a ``while`` whose condition reads the counts.
    KMedoids' snap, in XLA, reads ``x.T`` and picks its row by a
    masked sum (a slice of a row costs a 9.6 GB row-major copy)."""
    _assert_l1_fit_holds_x_alone(_l1_fit_compiled(18_750_000, 64, 8, one_chip, snap=snap), 18_750_000, 64)


@pytest.mark.parametrize("n,d,k", [(1_000_003, 8, 2), (1_000_003, 120, 32), (300, 16, 4), (1025, 16, 4), (4_194_304, 64, 32)],
                         ids=["d8_k2", "d120_k32", "short", "two_blocks", "k32_gathers"])
def test_l1_fit_gate_corners(one_chip, n, d, k):
    """The corners of ``l1_passes_serve``: the narrowest and the widest
    feature-major ``d``, the least and the largest ``k`` the passes serve
    (and the largest with the gather: the last cluster's kept keys end at the
    type's max), fewer rows than one block of the 1-D label vector."""
    txt = _l1_fit_compiled(n, d, k, one_chip).as_text()
    assert txt.count("tpu_custom_call") >= 4
    assert ("kmedians.select.candidates" in txt) == (n >= k << 17)  # ``gather_pays``: d8_k2 and k32_gathers


@pytest.mark.parametrize("split", [0, None], ids=["split0", "replicated"])
def test_l1_fit_four_chips(mesh4, split):
    """The same fit over the 2 x 2, under ``shard_map``. ``X`` split 0: each
    chip passes over its rows and the counts are summed (an all-reduce a
    counting pass) before a bracket narrows. Replicated: nothing crosses."""
    rows = 4_687_500
    n, spec, axis = (4 * rows, P("d", None), "d") if split == 0 else (rows, P(), None)
    compiled = _l1_fit_compiled(n, 64, 8, NamedSharding(mesh4, spec), mesh4, axis)
    _assert_l1_fit_holds_x_alone(compiled, rows, 64)
    assert ("all-reduce" in compiled.as_text()) == (split == 0)


def _canonical(lowered: str) -> str:
    """The lowered text with every Mosaic kernel's serialized body replaced
    by the hash of its text without debug locations (they hold the file and
    line a kernel was traced from, which a move changes and nothing else)."""
    import base64
    import hashlib

    from jax._src.interpreters import mlir as jmlir
    from jax._src.lib.mlir import ir

    ctx = jmlir.make_ir_context()
    ctx.allow_unregistered_dialects = True

    def body(m):
        with ctx:
            asm = ir.Module.parse(base64.b64decode(m.group(1))).operation.get_asm(enable_debug_info=False)
        return "BODY:" + hashlib.sha256(asm.encode()).hexdigest()[:16]

    lowered = re.sub(r"loc\(.*?\)$|^#loc.*$", "", lowered, flags=re.M)
    return re.sub(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', body, lowered)


@pytest.mark.parametrize("snap, parents", [(False, "17926adab08fbc8f"), (True, "f67ab39fe7258de8")], ids=["kmedians", "kmedoids"])
def test_l1_fit_program_is_the_one_before_the_selection_moved(one_chip, snap, parents):
    """PR 38 moved the selection from ``cluster/`` into ``core/`` and gave it
    a second way to say which rows count; KMedians' and KMedoids' programs
    had to stay what they were. The fit program on the kernels at the
    cell's shape, lowered for the described chip: its text, kernels' bodies
    included (debug locations left out), hashes as the parent's did
    (commit b72b990, recorded by PR 38 with this function on that tree). A
    change to the fit's program is a change to ``kmedians-northstar.fit5``:
    record the new hash with what the cell read before and after."""
    import hashlib

    from heat_tpu.cluster import _kcluster as kc, _pallas_l1 as pl1

    n, d, k = 18_750_000, 64, 8
    passes = pl1.l1_passes(k, (n, d))

    def step(arr, centers):
        labels, counts, _ = passes.assign(arr, centers)
        new = kc._cluster_medians(arr, labels, k, centers, counts, passes)
        if snap:
            new = kc._snap_to_members(arr, labels, k, new, counts, centers)
        return new, jnp.sum((new - centers) ** 2)

    step.assign = lambda arr, centers: passes.assign(arr, centers)[::2]
    kc._fused_fit_program.cache_clear()
    try:
        prog = kc._fused_fit_program(step, k, (n, d), "float32", 0.0, 5, False, "manhattan", False)
        text = prog.program.lower(jax.ShapeDtypeStruct((n, d), F32, sharding=one_chip), jax.ShapeDtypeStruct((k, d), F32)).as_text()
    finally:
        kc._fused_fit_program.cache_clear()
    assert hashlib.sha256(_canonical(text).encode()).hexdigest()[:16] == parents


def _percentile_key(rows, d, chips, topo_mesh):
    """``_percentile_select_program``'s key for ``ht.percentile(x, [25, 75,
    50], axis=0)`` on the kernels (``linear``), as ``_selection_key`` would
    reckon it on the chip(s)."""
    n = rows * chips
    pos = np.array([25.0, 75.0, 50.0]) / 100.0 * (n - 1)
    ranks = tuple(zip(np.floor(pos).astype(int).tolist(), np.ceil(pos).astype(int).tolist()))
    mesh, axis = (topo_mesh, "d") if chips > 1 else (None, None)
    return (n, d), "float32", True, ranks, tuple((pos - np.floor(pos)).tolist()), (3, d), mesh, axis


def _percentile_compiled(rows, d, chips, topo_mesh, sharding):
    """That call's one program, compiled for the described chip(s)."""
    from heat_tpu.core import statistics as st

    st._percentile_select_program.cache_clear()
    try:
        prog = st._percentile_select_program(*_percentile_key(rows, d, chips, topo_mesh))
        return prog.program.lower(jax.ShapeDtypeStruct((rows * chips, d), F32, sharding=sharding)).compile()
    finally:
        st._percentile_select_program.cache_clear()


@pytest.mark.parametrize("chips", [1, 4], ids=["one_chip", "split0_2x2"])
def test_percentile_select_at_the_cells_shape(one_chip, mesh4, chips):
    """``robustscale-northstar``'s shard, 18 750 000 x 64 f32 (and a quarter
    of it on each chip of the 2 x 2, split 0): the one program of
    ``ht.percentile(x, [25, 75, 50], axis=0)`` holds no sort, no label
    vector and no array of ``X``'s size (``memory_analysis``: the kept keys,
    75 MB, and a few counts); every kernel that reads ``X`` is named
    ``percentile.select.pass`` and no other kernel reads it; across chips
    the counts are all-reduced."""
    from heat_tpu.core import _pallas_select as ps

    rows, d = (18_750_000, 64) if chips == 1 else (4_687_500, 64)
    compiled = _percentile_compiled(rows, d, chips, mesh4, one_chip if chips == 1 else NamedSharding(mesh4, P("d", None)))
    txt = compiled.as_text()
    assert " sort(" not in txt
    sized = [m.group(0) for m in _X_SIZED_OP.finditer(txt) if {int(m.group(1)), int(m.group(2))} == {rows, d}]
    assert sized == [] and f"s32[{rows}]" not in txt
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < rows * d * 4 // 50 and memory.output_size_in_bytes < 1 << 16
    kernels = _kernels(txt)
    assert len(kernels) == txt.count("tpu_custom_call") >= 5  # first digit, counting, gathering, successor; the kept keys'
    for name, operands in kernels:
        reads_x = f"f32[{d},{rows}]" in operands or f"f32[{rows},{d}]" in operands
        assert reads_x == (".pass" in name), (name, operands)
        assert name.startswith(("percentile.select.pass", "percentile.select.candidates")), name
    assert f"s32[{d},{ps.kept_lanes(rows, d, 3)}]" in txt and 60 * ps.kept_lanes(rows, d, 3) < rows
    assert ("all-reduce" in txt) == (chips == 4)


@pytest.mark.parametrize("chips", [1, 4], ids=["one_chip", "split0_2x2"])
def test_robust_fit_transform_at_the_cells_shape(one_chip, mesh4, chips):
    """``RobustScaler().fit_transform(x)`` at the cell's shard (and a quarter
    of it on each chip of the 2 x 2) is ONE module (PR 39): the selection's
    kernels under their own names and exactly one ``scaler.transform.pass``,
    no sort; every kernel that reads ``X`` says ``.pass``; one output of
    ``X``'s size (``y``) and no temporary near it (the kept keys: under an
    eighth of ``X``), so the whole call's memory is asked for at one launch."""
    from heat_tpu.preprocessing import preprocessing as pp

    rows, d = (18_750_000, 64) if chips == 1 else (4_687_500, 64)
    select_key = _percentile_key(rows, d, chips, mesh4)
    shape, mesh, axis = select_key[0], select_key[6], select_key[7]
    pp._robust_fit_transform_program.cache_clear()
    try:
        prog = pp._robust_fit_transform_program(select_key, True, True,
                                                (shape, "float32", "float32", False, True, True, True, mesh, axis))
        sharding = one_chip if chips == 1 else NamedSharding(mesh4, P("d", None))
        compiled = prog.program.lower(jax.ShapeDtypeStruct(shape, F32, sharding=sharding)).compile()
    finally:
        pp._robust_fit_transform_program.cache_clear()
    txt = compiled.as_text()
    assert txt.count("HloModule") == 1 and " sort(" not in txt
    kernels = _kernels(txt)
    assert len(kernels) == txt.count("tpu_custom_call")
    names = [name for name, _ in kernels]
    for name, operands in kernels:
        reads_x = f"f32[{d},{rows}]" in operands or f"f32[{rows},{d}]" in operands
        assert reads_x == (".pass" in name), (name, operands)
        assert name.startswith(("percentile.select.pass", "percentile.select.candidates", "scaler.transform.pass")), name
    assert sum(name.startswith("scaler.transform.pass") for name in names) == 1
    assert sum(name.startswith("percentile.select.pass") for name in names) >= 4  # first digit, counting, gathering, successor
    assert any(name.startswith("percentile.select.candidates") for name in names)
    sized = [m.group(0) for m in _X_SIZED_OP.finditer(txt) if {int(m.group(1)), int(m.group(2))} == {rows, d}]
    assert sized == []  # no copy, transpose or cast of X or of y
    memory, x_bytes = compiled.memory_analysis(), rows * d * 4  # a device's
    assert memory.temp_size_in_bytes < x_bytes // 8 and x_bytes <= memory.output_size_in_bytes < x_bytes + (1 << 20)
    assert ("all-reduce" in txt) == (chips == 4)


@pytest.mark.parametrize("n,d,q", [(1_048_576, 8, 8), (2_000_003, 120, 1), (1 << 21, 64, 11)], ids=["d8_q8", "d120_q1", "two_batches"])
def test_percentile_select_gate_corners(one_chip, n, d, q):
    """The narrowest and the widest ``d`` the gate serves, the most targets a
    batch and one, an odd row count (a masked last block), and a ``q`` of
    two batches in one program."""
    from heat_tpu.core import statistics as st

    pos = np.linspace(5.0, 95.0, q) / 100.0 * (n - 1)
    ranks = tuple(zip(np.floor(pos).astype(int).tolist(), np.ceil(pos).astype(int).tolist()))
    st._percentile_select_program.cache_clear()
    try:
        prog = st._percentile_select_program((n, d), "float32", True, ranks, tuple((pos - np.floor(pos)).tolist()), (q, d), None, None)
        txt = prog.program.lower(jax.ShapeDtypeStruct((n, d), F32, sharding=one_chip)).compile().as_text()
    finally:
        st._percentile_select_program.cache_clear()
    assert "percentile.select.candidates" in txt and " sort(" not in txt


@pytest.mark.parametrize("inverse", [False, True], ids=["transform", "inverse_transform"])
@pytest.mark.parametrize("chips", [1, 4], ids=["one_chip", "split0_2x2"])
def test_scaler_transform_at_the_cells_shape(one_chip, mesh4, chips, inverse):
    """``RobustScaler.transform`` / ``inverse_transform`` at the cell's
    shard: one kernel named ``scaler.transform.pass`` between two bitcasts
    (``x.T`` in, ``y.T`` out), one read and one write of the table, nothing
    else of its size."""
    from heat_tpu.preprocessing import preprocessing as pp

    rows, d = (18_750_000, 64) if chips == 1 else (4_687_500, 64)
    n = rows * chips
    mesh, axis = (mesh4, "d") if chips > 1 else (None, None)
    sharding = one_chip if chips == 1 else NamedSharding(mesh4, P("d", None))
    pp._affine_program.cache_clear()
    try:
        prog = pp._affine_program((n, d), "float32", "float32", inverse, True, True, True, mesh, axis)
        vec = jax.ShapeDtypeStruct((d,), F32)
        compiled = prog.program.lower(jax.ShapeDtypeStruct((n, d), F32, sharding=sharding), vec, vec).compile()
    finally:
        pp._affine_program.cache_clear()
    txt = compiled.as_text()
    assert [name.split(".pass")[0] for name, _ in _kernels(txt)] == ["scaler.transform"]
    sized = [m.group(0) for m in _X_SIZED_OP.finditer(txt) if {int(m.group(1)), int(m.group(2))} == {rows, d}]
    assert sized == []
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 1 << 20 and memory.output_size_in_bytes // chips < rows * d * 4 + (1 << 20)


@pytest.mark.parametrize("dtype,family", [("bfloat16", "splash"), ("float32", "flash")])
def test_attention_ring_step(one_chip, dtype, family):
    """The per-ring-step kernels at the smoke's block, S=16384, D=128:
    splash for bf16, the flash residual form for f32; full and causal
    diagonal."""
    from heat_tpu.nn import attention as att

    b, h, s_len, d = 1, 8, 16384, 128
    full, diag = att._ring_step_kernels(b, h, s_len, s_len, d, d ** -0.5, dtype, False)
    q = jax.ShapeDtypeStruct((b, h, s_len, d), jnp.dtype(dtype), sharding=one_chip)
    assert _holds_kernel(full, q, q, q), family
    assert _holds_kernel(diag, q, q, q), family


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_attention_ring_program_four_chips(mesh4, dtype):
    """The whole kernel ring over the 2x2 mesh: bf16 unrolled around
    splash, f32 scan-with-carry around flash, K/V rotating by ppermute."""
    from heat_tpu.nn import attention as att

    b, h, s_len, d = 1, 8, 16384, 128
    fn = att._ring_attention_kernel_callable(
        mesh4, "d", s_len, s_len, b, h, d, True, d ** -0.5, dtype, False
    )
    q = jax.ShapeDtypeStruct(
        (b, h, s_len, d), jnp.dtype(dtype),
        sharding=NamedSharding(mesh4, P(None, None, "d", None)),
    )
    txt = _compile(fn, q, q, q)
    assert "tpu_custom_call" in txt and "collective-permute" in txt


def test_hsvd_level0_under_shard_map(mesh4, monkeypatch):
    """The staged distributed path's level-0 program on four chips (rtol
    mode, the one-view sketch): the sketch kernel inside shard_map, one
    (8192, 16384) column block per chip. The kernel's gate reads the
    backend, which is the CPU here: steer it in the test."""
    from heat_tpu.core.linalg import svdtools

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    prog = svdtools._local_svd_fn(mesh4, "d", 8192, 16384, 15, "float32", 25, None)
    a = jax.ShapeDtypeStruct((8192, 65536), F32, sharding=NamedSharding(mesh4, P(None, "d")))
    txt = prog.lower(a).compile().as_text()
    assert "tpu_custom_call" in txt
    assert "bf16[8192,16384]" not in txt  # pass 2 reads the f32 shard: no copy


_DOT = re.compile(r"^\s*(?:ROOT )?%\S+ = (\S+?)\{[^ ]* (?:dot|convolution)\(", re.M)


def _dots_below_highest(hlo: str) -> list:
    """Result shapes of the compiled module's dots (the chip's compiler
    writes most of them as convolutions) whose operands are not both at
    ``highest`` precision."""
    return [
        m.group(1) for m in _DOT.finditer(hlo)
        if "operand_precision={highest,highest}" not in hlo[m.start(): hlo.index("\n", m.end())]
    ]


def _dots_at(hlo: str, precision: str) -> list:
    """Result shapes of the dots whose operands are both at ``precision``
    (``"default"``: the instruction states none)."""
    want = f"operand_precision={{{precision},{precision}}}"
    lines = ((m.group(1), hlo[m.start(): hlo.index("\n", m.end())]) for m in _DOT.finditer(hlo))
    return [shape for shape, line in lines
            if (want in line if precision != "default" else "operand_precision=" not in line)]


@pytest.mark.parametrize("tsqr", [False, True], ids=["gather_merge", "tsqr_merge"])
def test_hsvd_dist_rank_program_four_chips(mesh4, monkeypatch, tsqr):
    """The one program of ``hsvd_rank`` on a split-0 array over four chips,
    at the cell's size (131072 x 8192 f32 a chip, rank 10 + 5, sketch width
    25), with either merge: each chip's rows go through the sketch kernel
    as they lie (no transposed, f32 or bf16 copy of the block; the program
    needs no temporaries of its size), and the ONLY product below
    ``highest`` precision is pass 2's stream over A (pass 1 is inside the
    kernel). At the parent the four-chip call was ~60 programs, level 0
    read an eager ``A.T`` and Q's updates ran at the MXU's default."""
    from heat_tpu.core.linalg import svdtools

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rows, n, l = 131072, 8192, 25
    builder = svdtools._dist_rank_fn
    builder.cache_clear()
    try:
        a = jax.ShapeDtypeStruct((4 * rows, n), F32, sharding=NamedSharding(mesh4, P("d", None)))
        compiled = builder(mesh4, "d", 0, (rows, n), "float32", 15, l, 10, tsqr).lower(a).compile()
    finally:
        builder.cache_clear()
    txt = compiled.as_text()
    assert "tpu_custom_call" in txt
    assert ("all-to-all" in txt) == tsqr and "all-gather" in txt
    for copy in (f"bf16[{rows},{n}]", f"f32[{n},{rows}]", f"bf16[{n},{rows}]"):
        assert copy not in txt
    assert compiled.memory_analysis().temp_size_in_bytes < rows * n  # a quarter of the block's bytes
    below = _dots_below_highest(txt)
    if tsqr:
        # level 0 of the merge's TSQR is the Gram form since PR 34: its first Gram matrix (a
        # preconditioner) and its last product (Q + Q (R^-1 - I)) run at three bf16 passes, by design
        assert sorted(below) == sorted([f"f32[{rows},{l}]", "f32[60,60]", "f32[2048,60]"])
        below = [shape for shape in below if shape not in _dots_at(txt, "high")]
    assert below == [f"f32[{rows},{l}]"]


@pytest.mark.parametrize("calc_q", [True, False], ids=["with_q", "r_only"])
def test_local_qr_gram_form_at_the_cells_shape(one_chip, monkeypatch, calc_q):
    """``ht.linalg.qr``'s one-device program at the benchmark's shape
    (1048576 x 1024 f32, ``qr-northstar``): the Gram form with its products
    over the tall operand as the two kernels of ``_pallas_qr`` (a custom call
    hides its dots: ``tests/test_qr_local.py`` reads their terms), no XLA
    product left over it and none anywhere at one bf16 pass, ``Q`` written
    by the kernels themselves into its own array (the output: no memset of
    it, no copy of it or of a block) with no temporary of ``A``'s size beside
    it: the harness holds the last call's ``Q`` through the next call, so
    ``A``, two ``Q``s and this program's temporaries have to fit in 16 GB."""
    import importlib

    qr = importlib.import_module("heat_tpu.core.linalg.qr")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    m, n = 1048576, 1024
    qr._local_qr_fn.cache_clear()
    try:
        compiled = qr._local_qr_fn(m, n, "float32", calc_q).lower(
            jax.ShapeDtypeStruct((m, n), F32, sharding=one_chip)).compile()
    finally:
        qr._local_qr_fn.cache_clear()
    txt = compiled.as_text()
    assert "cholesky" in txt.lower()
    kernels = [re.sub(r"\.\d+$", "", name) for name, _ in _kernels(txt)]
    # first Gram; apply with the second Gram; the same in the repair loop; the finish
    assert sorted(set(kernels)) == ["qr.tall.apply", "qr.tall.gram"] and len(kernels) == (4 if calc_q else 3), kernels
    assert _dots_at(txt, "default") == []
    assert not [shape for shape in _DOT.findall(txt) if str(m) in shape or "[8192," in shape], "a product over the tall operand outside the kernels"
    tall = f"f32[{m},{n}]"
    assert not re.search(rf"= {re.escape(tall)}\S* (broadcast|copy)\(", txt), "a memset or a copy of Q"
    assert not re.search(r"= f32\[8192,1024\]\S* ", txt), "a block sliced out of Q"
    mem = compiled.memory_analysis()
    a_bytes = m * n * 4
    # with Q: the output; without: one working array of A's size, nothing more
    assert mem.output_size_in_bytes + mem.temp_size_in_bytes < a_bytes + (256 << 20)


def test_tsqr_q_updates_at_highest(mesh4):
    """``ht.linalg.qr``'s TSQR program (and the merge of the staged
    distributed hSVD): no product at the MXU's default precision, which
    left Q orthonormal to 3e-3 on the chip (PERF.md, PR 25)."""
    import importlib

    qr = importlib.import_module("heat_tpu.core.linalg.qr")  # the package exports the function under this name
    qr._tsqr_fn.cache_clear()
    try:
        a = jax.ShapeDtypeStruct((4 * 2048, 60), F32, sharding=NamedSharding(mesh4, P("d", None)))
        txt = qr._tsqr_fn(mesh4, "d", 2048, 60, "float32", True).lower(a).compile().as_text()
    finally:
        qr._tsqr_fn.cache_clear()
    assert "all-gather" in txt
    assert _dots_below_highest(txt) == []
