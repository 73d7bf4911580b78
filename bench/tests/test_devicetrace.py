"""The trace reduction on a synthetic trace with known intervals."""
import pytest

import devicetrace

MS = 1e6  # nanoseconds


def _trace():
    window = ("bench.window", 0.0, 100 * MS)
    host = [
        ("python", [window, ("bench.week_pickups", 5 * MS, 40 * MS)]),
        ("bench-client-1", [("bench.window_pickups", 10 * MS, 80 * MS),
                            ("tf_op", 12 * MS, 1 * MS)]),
    ]
    device = [
        ("XLA Modules", [("jit_run(123)", 20 * MS, 30 * MS),
                         ("jit_other(9)", 70 * MS, 20 * MS)]),
        # two overlapping ops and one reaching past the window's close
        ("XLA Ops", [("%sort.1 = f32[8] sort(...)", 20 * MS, 20 * MS),
                     ("%fusion.2 = f32[8] fusion(...)", 30 * MS, 20 * MS),
                     ("%sort.1 = f32[8] sort(...)", 70 * MS, 40 * MS)]),
    ]
    return [("/host:CPU", host), ("/device:TPU:0", device),
            ("/device:CUSTOM:Megascale Trace", [])]


def test_busy_idle_and_window():
    s = devicetrace.reduce(_trace())
    assert s.window_s == pytest.approx(0.100)
    # [20, 50) and [70, 100) after clipping to the window
    assert s.busy_s == pytest.approx(0.060)
    assert s.idle_share == pytest.approx(0.4)
    assert s.devices == 1


def test_top_device_ops_by_module():
    s = devicetrace.reduce(_trace())
    assert s.device_ops[0] == ["jit_other/sort.1", pytest.approx(0.030)]
    assert ["jit_run/sort.1", pytest.approx(0.020)] in s.device_ops
    assert ["jit_run/fusion.2", pytest.approx(0.020)] in s.device_ops


def test_idle_gaps_named_by_open_spans():
    s = devicetrace.reduce(_trace())
    # idle [0, 20): at its middle both clients' spans are open;
    # idle [50, 70): only the whole-window request is
    assert sorted(s.idle_gaps) == [
        ["week_pickups + window_pickups", pytest.approx(0.020)],
        ["window_pickups", pytest.approx(0.020)],
    ]


def test_a_trace_without_a_device_or_window_is_refused():
    planes = _trace()
    with pytest.raises(RuntimeError, match="device"):
        devicetrace.reduce([p for p in planes if not p[0].startswith("/device:TPU")])
    host = [("/host:CPU", [("python", [])])] + planes[1:]
    with pytest.raises(RuntimeError, match="bench.window"):
        devicetrace.reduce(host)


def test_union():
    assert devicetrace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
