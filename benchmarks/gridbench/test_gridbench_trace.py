"""The trace reduction and the per-layer metric readers, on a hand-built
event list and on a small profile recorded on the CPU."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchmarks.gridbench import harness, roofline, traffic  # noqa: E402
from benchmarks.gridbench import trace_reduce as tr  # noqa: E402

DEV0, DEV1 = "/device:TPU:0", "/device:TPU:1"


def _op(plane, start, dur, name="fusion.1"):
    """A device op as the TPU trace names it: its HLO instruction."""
    call = " custom-call(f32[16,128] %p)" if not name.startswith(
        ("fusion", "while")) else " fusion(f32[16] %p)"
    return tr.Event(plane, f"%{name} = f32[16,128]{call}", float(start),
                    float(dur))


def _call(start, end):
    return tr.Event("/host:CPU", tr.CALL_SPAN, float(start),
                    float(end - start))


@pytest.fixture
def two_chip_trace():
    """Two calls, [0, 100) and [150, 300) ns, on two chips.  Chip 0 runs
    ops over [10, 40) and [30, 60) (overlapping: 50 ns busy) in call 1
    and [200, 260) in call 2; chip 1 runs [20, 50) and [160, 290).  An
    op outside every call is ignored."""
    return [
        _call(0, 100), _call(150, 300),
        _op(DEV0, 10, 30, "event_scan.3"),
        _op(DEV0, 30, 30, "fusion.7"),
        _op(DEV0, 200, 60, "event_scan_slab.1"),
        _op(DEV1, 20, 30, "event_scan.12"),
        _op(DEV1, 160, 130, "while.2"),
        _op(DEV0, 400, 50, "event_scan.4"),
    ]


def test_union_merges_overlaps_and_clips():
    assert tr.union_length([(0, 10), (5, 20), (30, 40)]) == 30
    assert tr.union_length([(0, 10), (5, 20)], lo=8, hi=15) == 7
    assert tr.union_length([]) == 0


def test_busy_idle_and_window(two_chip_trace):
    red = tr.reduce(two_chip_trace)
    assert red.window_ns == 300
    assert red.busy_ns == {DEV0: 110.0, DEV1: 160.0}
    assert tr.mean_busy_ns(red) == 135.0
    # call 1: chip 0 idle 100-50, chip 1 idle 100-30; call 2: 150-60
    # and 150-130
    assert tr.idle_in_calls_ns(red) == [60.0, 55.0]
    idle = harness.reader("device.idle_share")(dict(red=red))
    assert idle == pytest.approx(100.0 * (1 - 135.0 / 300.0))
    host = harness.reader("entry.host_ms")(dict(red=red))
    assert host == pytest.approx(57.5e-6)


def test_kernel_time_by_instruction_name(two_chip_trace):
    red = tr.reduce(two_chip_trace)
    # event_scan_slab is another kernel; the op at 400 ns lies outside
    # the window
    t, n = tr.kernel_time_ns(red, "event_scan")
    assert (t, n) == (30.0, 1.0)            # (30 + 30) / 2 chips
    assert tr.kernel_of(two_chip_trace[2]) == "event_scan"
    assert tr.hlo_name(two_chip_trace[2]) == "event_scan.3"
    share = harness.reader("event_scan.share")(dict(red=red))
    assert share == pytest.approx(100.0 * 30.0 / 135.0)


def test_loop_readers(two_chip_trace):
    red = tr.reduce(two_chip_trace)
    ctx = dict(red=red, iterations=[[10, 20], [30, 40]])
    assert harness.reader("loop.iters_per_call")(ctx) == 30.0
    # 135 ns busy per chip over 15 + 35 iterations per chip
    assert harness.reader("loop.device_us_per_iter")(ctx) == \
        pytest.approx(135.0 / 50.0 / 1e3)


def test_roofline_arithmetic(two_chip_trace):
    cfg = traffic.load("configs", "gridsim_wwg_20users")
    # WWG: 11 resources pad to 16 rows; 20 users x 2 x 16 PEs = 640
    assert roofline.job_slots(cfg) == 640
    assert roofline.event_scan_bytes(cfg) == 4 * (4 * 16 * 640 + 8 * 16)
    red = tr.reduce(two_chip_trace)
    peak = harness.peaks("TPU v5 lite")
    got = harness.reader("event_scan_roofline")(
        dict(red=red, cfg=cfg, peaks=peak))
    # two kernel calls of 30 ns, one per chip
    least = 1.0 * roofline.event_scan_bytes(cfg) / 819e9 * 1e9
    assert got == pytest.approx(100.0 * least / 30.0)


def test_breakdown_lists_leaf_ops_and_gaps(two_chip_trace):
    b = harness.breakdown_of(tr.reduce(two_chip_trace))
    names = [k for k, _ in b["device_ops"]]
    assert "while.2" not in names and names[0] == "event_scan_slab.1"
    assert b["idle_gaps"][0][1] == pytest.approx(50e-9)   # chip 0


def test_readers_return_nothing_without_device_ops():
    red = tr.reduce([_call(0, 100)])
    ctx = dict(red=red, iterations=[[5]], cfg=None,
               peaks=harness.peaks("TPU v5 lite"))
    for name in ("device.idle_share", "event_scan.share",
                 "event_scan_roofline", "loop.device_us_per_iter"):
        assert harness.reader(name)(ctx) is None, name


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        harness.peaks("TPU v9 imaginary")


def test_trace_without_call_spans_raises():
    with pytest.raises(ValueError):
        tr.reduce([_op(DEV0, 0, 10)])


def test_recorded_profile(tmp_path):
    """A profile recorded by the JAX profiler: the benchmark's call spans
    are read back from the host plane (the CPU has no device plane)."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) * 2.0)
    x = jnp.ones((64,))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for i in range(3):
        with jax.profiler.TraceAnnotation(tr.CALL_SPAN, call=i):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    events = tr.events_from_file(tr.find_xplane(str(tmp_path)))
    red = tr.reduce(events)
    assert len(red.calls) == 3
    assert red.window_ns > 0 and red.chips == {}
