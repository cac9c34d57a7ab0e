"""The four-chip cell's two readers (``benchmarks/layers/collective_ms_per_call.py``,
``chip_skew_pct.py``) on hand-written events with known answers: the
two-device list of ``benchmarks/selftest.py``, whose own checks cover the
readers PR 24 brought. No JAX needed: a reader sees a list of events."""

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
