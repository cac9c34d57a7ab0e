"""The four-chip cell's two readers (``benchmarks/layers/collective_ms_per_call.py``,
``chip_skew_pct.py``) on hand-written events with known answers: the
two-device list of ``benchmarks/selftest.py``, whose own checks cover the
readers PR 24 brought. And the four readers of ``kmedians-northstar.fit5``
(PR 32), on hand-written events and on the cell's recorded fixture. No JAX
needed: a reader sees a list of events."""

import json

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def bench():
    sys.path.insert(0, ROOT)
    try:
        from benchmarks import run as harness
        from benchmarks import selftest as st
        from benchmarks import trace as T

        yield harness, st, T
    finally:
        sys.path.remove(ROOT)


def two_devices(st, T):
    """One call in a 1000 ns window. Device 0: compute [0, 400), an
    all-reduce [300, 600), a collective-permute-start [700, 800): 400 ns
    under collectives, busy 700. Device 1: compute [0, 900), an all-gather
    [100, 200) under it, an async all-to-all from 850 to 1000 and an async
    copy that is no collective: 250 ns under collectives, busy 900."""
    dev, host = st.dev, st.host
    return [
        host(T.CALL, 0, 100), host(T.WAIT, 100, 900),
        dev(0, "fusion.1", 0, 400), dev(0, "all-reduce.2", 300, 300), dev(0, "%collective-permute-start.3", 700, 100),
        dev(1, "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 0, 900), dev(1, "all-gather.7", 100, 100),
        dev(1, "%all-to-all-start.9 = (f32[8]{0}) all-to-all-start(f32[8]{0} %x)", 850, 150, line=T.ASYNC_LINE),
        dev(1, "%copy-start.4 = (f32[8]{0}) copy-start(f32[8]{0} %y)", 900, 100, line=T.ASYNC_LINE),
    ]


@pytest.mark.parametrize(
    "metric, want",
    [("collective_ms_per_call", (400 + 250) / 2 * 1e-6), ("chip_skew_pct", 100.0 * (900 - 700) / 800)],
)
def test_reader_on_two_devices(bench, metric, want):
    harness, st, T = bench
    got = harness.load_module("layers", metric).reduce(two_devices(st, T), {})
    assert got == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("metric, want", [("collective_ms_per_call", 0.0), ("chip_skew_pct", None)])
def test_reader_on_one_device_without_collectives(bench, metric, want):
    """One device: no collective ran (0 ms, measured), and there is no
    skew to read (nothing, so the line leaves the metric out)."""
    harness, st, T = bench
    ev = [e for e in two_devices(st, T) if e.plane == T.HOST_PLANE] + [st.dev(0, "fusion.1", 0, 400)]
    assert harness.load_module("layers", metric).reduce(ev, {}) == want


@pytest.mark.parametrize("metric", ["collective_ms_per_call", "chip_skew_pct"])
def test_reader_finds_nothing_without_device_events(bench, metric):
    harness, st, T = bench
    ev = [e for e in two_devices(st, T) if e.plane == T.HOST_PLANE]
    assert harness.load_module("layers", metric).reduce(ev, {}) is None


def test_collective_in_flight_from_start_to_done(bench):
    """The chip's compiler overlaps an all-gather with compute as a pair of
    ops on the op line: the exchange counts from the start of
    ``%async-collective-start`` to the end of ``%async-collective-done``,
    the compute between them included. Two devices, 210 and 110 ns, one
    call: 160 ns."""
    harness, st, T = bench
    start = "%async-collective-start = (f32[1,15,8192]{2,1,0}, f32[4,15,8192]{2,1,0}) fusion(f32[1,15,8192]{2,1,0} %x)"
    done = "%async-collective-done = f32[4,15,8192]{2,1,0} fusion(f32[1,15,8192]{2,1,0} %y)"
    ev = [st.host(T.CALL, 0, 100), st.host(T.WAIT, 100, 900)]
    for i, end in ((0, 300), (1, 200)):
        ev += [st.dev(i, start, 100, 50), st.dev(i, "%fusion.81 = f32[8]{0} fusion(f32[8]{0} %p)", 150, end - 150),
               st.dev(i, done, end, 10)]
    got = harness.load_module("layers", "collective_ms_per_call").reduce(ev, {})
    assert got == pytest.approx((210 + 110) / 2 * 1e-6, rel=1e-12)


# --------------------------------------------------------------------- #
# the KMedians cell's readers (PR 32)                                    #
# --------------------------------------------------------------------- #
KMEDIANS = ["kmedians_assign_ms_per_call", "kmedians_select_ms_per_call", "kmedians_x_reads_per_call",
            "kmedians_pass_hbm_pct"]
ASSIGN = "%kmedians.assign.pass.8 = (s32[100]{0}, s32[8,128]{1,0}, f32[1,128]{1,0}) custom-call(f32[64,100]{1,0} %x)"
SELECT = "%kmedians.select.pass.15 = s32[3,8,64]{2,1,0} custom-call(s32[1]{0} %s, f32[64,100]{1,0} %x)"


def kmedians_fits(st, T, devices=1):
    """Two fits in a 2000 ns window, the same on every device. A fit: an
    assignment pass of 100 ns, three selection passes of 60, a fusion that
    reads a pass's counts (it names the pass as its operand) and no ``X``,
    the label pass of 100: five reads of ``X``, 200 ns of assignment, 180
    of selection."""
    ev = []
    for call in (0, 1000):
        ev += [st.host(T.CALL, call, 100), st.host(T.WAIT, call + 100, 900)]
        for i in range(devices):
            ev += [st.dev(i, ASSIGN, call + 100, 100), st.dev(i, "%while.3 = (s32[]) while(%t)", call + 200, 200),
                   st.dev(i, SELECT, call + 200, 60), st.dev(i, SELECT, call + 260, 60),
                   st.dev(i, SELECT, call + 320, 60),
                   st.dev(i, "%fusion.4 = s32[8,64]{1,0} fusion(s32[3,8,64]{2,1,0} %kmedians.select.pass.15)", call + 380, 20),
                   st.dev(i, ASSIGN.replace(".8 =", ".9 ="), call + 400, 100)]
    return ev


# one read is least / 2 assignment passes = 1000 B: 50 ns at 2e10 B/s; 5 reads in 380 ns of passes
KMEDIANS_RUN = {"least_bytes_per_call": 2000, "peak": {"hbm_bytes_per_s": 2e10}}
KMEDIANS_WANT = {"kmedians_assign_ms_per_call": 200e-6, "kmedians_select_ms_per_call": 180e-6,
                 "kmedians_x_reads_per_call": 5.0, "kmedians_pass_hbm_pct": 100.0 * 5 * 50 / 380}


@pytest.mark.parametrize("devices", [1, 2])
@pytest.mark.parametrize("metric", KMEDIANS)
def test_kmedians_reader_on_known_events(bench, metric, devices):
    harness, st, T = bench
    got = harness.load_module("layers", metric).reduce(kmedians_fits(st, T, devices), KMEDIANS_RUN)
    assert got == pytest.approx(KMEDIANS_WANT[metric], rel=1e-12)


def test_kmedians_readers_count_a_fused_pass_once(bench):
    """An assignment fused with a counting pass carries both names: one
    read of ``X``, and its time is in both phases."""
    harness, st, T = bench
    both = "%kmedians.assign.pass_kmedians.select.pass.2 = (s32[100]{0}) custom-call(f32[64,100]{1,0} %x)"
    ev = [st.host(T.CALL, 0, 100), st.host(T.WAIT, 100, 900), st.dev(0, both, 100, 300), st.dev(0, SELECT, 400, 200)]
    got = {m: harness.load_module("layers", m).reduce(ev, KMEDIANS_RUN) for m in KMEDIANS}
    assert got["kmedians_x_reads_per_call"] == 2.0
    assert got["kmedians_assign_ms_per_call"] == pytest.approx(300e-6)
    assert got["kmedians_select_ms_per_call"] == pytest.approx(500e-6)
    # the bytes of one read are least_bytes over the one assignment pass: 2000 B, 100 ns
    assert got["kmedians_pass_hbm_pct"] == pytest.approx(100.0 * 2 * 100 / 500)


def test_kmedians_reads_leave_out_the_slivers_the_clocks_offset_leaves(bench):
    """The device's clock runs ahead of the host's: the window keeps 1 ns of
    the first pass of the call after its last one. That is no read."""
    harness, st, T = bench
    ev = kmedians_fits(st, T) + [st.dev(0, ASSIGN, 1999, 100)]
    assert harness.load_module("layers", "kmedians_x_reads_per_call").reduce(ev, KMEDIANS_RUN) == 5.0


@pytest.mark.parametrize("metric", KMEDIANS)
def test_kmedians_reader_finds_nothing_in_another_programs_trace(bench, metric):
    """A program without the passes (the parent's, another cell's): nothing
    to read, so the line leaves the metric out, and nothing raises."""
    harness, st, T = bench
    reduce = harness.load_module("layers", metric).reduce
    ev = [e for e in two_devices(st, T)]
    assert reduce(ev, KMEDIANS_RUN) is None
    assert reduce([e for e in ev if e.plane == T.HOST_PLANE], KMEDIANS_RUN) is None
    assert reduce([], {}) is None


@pytest.mark.parametrize("metric", KMEDIANS)
def test_kmedians_reader_on_the_recorded_fixture(bench, metric):
    """The cell's trimmed chip trace reduces to what that run printed."""
    harness, st, T = bench
    with open(os.path.join(ROOT, "benchmarks", "fixtures", "kmedians-northstar.fit5.json")) as f:
        fx = json.load(f)
    got = harness.load_module("layers", metric).reduce([T.Event(*e) for e in fx["events"]], fx["run"])
    assert got == pytest.approx(float(fx["expected"][metric]), rel=1e-6)


# --------------------------------------------------------------------- #
# the RobustScaler cell's readers (PR 38)                                #
# --------------------------------------------------------------------- #
ROBUST = ["percentile_select_ms_per_call", "percentile_x_reads_per_call", "percentile_pass_hbm_pct",
          "scaler_transform_ms_per_call", "scaler_transform_hbm_pct"]
PASS = "%percentile.select.pass.15 = s32[576,128]{1,0} custom-call(s32[1]{0} %s, f32[64,100]{1,0} %x, s32[64,3]{1,0} %t)"
CANDIDATES = "%percentile.select.candidates.11 = s32[576,128]{1,0} custom-call(s32[64,2048]{1,0} %kept, s32[64,9]{1,0} %t)"
TRANSFORM = "%scaler.transform.pass.1 = f32[64,100]{1,0} custom-call(f32[64,100]{1,0} %x, f32[64,1]{1,0} %c, f32[64,1]{1,0} %s)"


def robust_calls(st, T, devices=1):
    """Two calls in a 2000 ns window, the same on every device. A call: the
    selection's program (a ``while`` over three passes of 60 ns, a fourth
    after it, a kernel of 10 ns over the kept keys, a fusion that reads a
    pass's counts and names it as its operand), then the transform's, 150
    ns: four reads of ``X``, 250 ns under the selection's names."""
    ev = []
    for call in (0, 1000):
        ev += [st.host(T.CALL, call, 100), st.host(T.WAIT, call + 100, 900)]
        for i in range(devices):
            ev += [st.dev(i, "%while.3 = (s32[]) while(%t)", call + 100, 180),
                   st.dev(i, PASS, call + 100, 60), st.dev(i, PASS, call + 160, 60), st.dev(i, PASS, call + 220, 60),
                   st.dev(i, PASS.replace(".15 =", ".14 ="), call + 280, 60), st.dev(i, CANDIDATES, call + 340, 10),
                   st.dev(i, "%fusion.4 = s32[3,64]{1,0} fusion(s32[576,128]{1,0} %percentile.select.pass.15)", call + 350, 20),
                   st.dev(i, TRANSFORM, call + 400, 150)]
    return ev


# least_bytes is three reads of the chip's rows: one read 1000 B, 50 ns at 2e10 B/s
ROBUST_RUN = {"least_bytes_per_call": 3000, "peak": {"hbm_bytes_per_s": 2e10}}
ROBUST_WANT = {"percentile_select_ms_per_call": 250e-6, "percentile_x_reads_per_call": 4.0,
               "percentile_pass_hbm_pct": 100.0 * 4 * 50 / 240, "scaler_transform_ms_per_call": 150e-6,
               "scaler_transform_hbm_pct": 100.0 * 2 * 50 / 150}


@pytest.mark.parametrize("devices", [1, 2])
@pytest.mark.parametrize("metric", ROBUST)
def test_robust_reader_on_known_events(bench, metric, devices):
    harness, st, T = bench
    got = harness.load_module("layers", metric).reduce(robust_calls(st, T, devices), ROBUST_RUN)
    assert got == pytest.approx(ROBUST_WANT[metric], rel=1e-12)


def test_robust_reads_leave_out_a_skipped_gathering_pass_and_the_clocks_sliver(bench):
    """A gathering pass that was told to skip runs empty over one block: an
    op under half the median pass is no read; nor is the 1 ns the window
    keeps of the first pass after its last call."""
    harness, st, T = bench
    ev = robust_calls(st, T) + [st.dev(0, PASS, 350, 5), st.dev(0, PASS, 1999, 60)]
    assert harness.load_module("layers", "percentile_x_reads_per_call").reduce(ev, ROBUST_RUN) == 4.0


@pytest.mark.parametrize("metric", ROBUST)
def test_robust_reader_finds_nothing_in_another_programs_trace(bench, metric):
    """A program without the kernels (the parent's, which sorts; another
    cell's; KMedians', whose passes carry another prefix): nothing to read,
    so the line leaves the metric out, and nothing raises."""
    harness, st, T = bench
    reduce = harness.load_module("layers", metric).reduce
    for ev in (two_devices(st, T), kmedians_fits(st, T)):
        assert reduce(ev, ROBUST_RUN) is None
        assert reduce([e for e in ev if e.plane == T.HOST_PLANE], ROBUST_RUN) is None
    assert reduce([], {}) is None
    assert reduce(robust_calls(st, T), {}) is None or "hbm" not in metric  # no peak, no share


@pytest.mark.parametrize("metric", ROBUST)
def test_robust_reader_on_the_recorded_fixture(bench, metric):
    """The cell's trimmed chip trace reduces to what that run printed."""
    harness, st, T = bench
    with open(os.path.join(ROOT, "benchmarks", "fixtures", "robustscale-northstar.fit_transform.json")) as f:
        fx = json.load(f)
    got = harness.load_module("layers", metric).reduce([T.Event(*e) for e in fx["events"]], fx["run"])
    assert got == pytest.approx(float(fx["expected"][metric]), rel=1e-6)
