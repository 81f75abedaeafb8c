"""The trace reduction on a small synthetic trace (nanoseconds)."""
import pytest

from bench import tracing

KERNEL = ('%k = f32[8] custom-call(f32[8] %a), '
          'custom_call_target="tpu_custom_call"')


def _trace():
    # window [100, 1100]; chip 0 busy [50,300] (clipped to 100..300),
    # [250,400] overlapping, [600,700] kernel, [1000,1200] clipped
    chip0 = [(50, 300, "%fusion.1 = gather"), (250, 400, "%fusion.2 = add"),
             (600, 700, KERNEL), (1000, 1200, "%copy = copy")]
    # chip 1 busy [100, 600]
    chip1 = [(100, 600, KERNEL)]
    host = [(100, 1100, tracing.WINDOW),
            (100, 1100, "$middleware.py:1225 run"),
            (420, 580, "$api.py:2894 device_get"),
            (430, 570, "$array.py:631 _value"),
            (700, 990, "PjitFunction(step)")]
    return tracing.Trace(window=(100, 1100), devices=[chip0, chip1],
                         host=host)


def test_busy_intervals_merge_and_clip():
    ops = _trace().devices[0]
    assert tracing.busy_intervals(ops, 100, 1100) == [
        (100, 400), (600, 700), (1000, 1100)]


def test_busy_and_window_seconds():
    tr = _trace()
    # chip 0: 300 + 100 + 100 = 500 ns; chip 1: 500 ns
    assert tracing.busy_s(tr) == pytest.approx(500e-9)
    assert tr.window_s == pytest.approx(1000e-9)


def test_kernel_events_by_name():
    # chip 0: 100 ns of kernel, chip 1: 500 ns -> mean 300 ns
    assert tracing.op_seconds(_trace(), tracing.PALLAS_MARK) == \
        pytest.approx(300e-9)
    assert tracing.op_seconds(_trace(), "no such op") == 0.0


def test_top_ops_ranks_by_device_time():
    top = tracing.top_ops(_trace(), k=2)
    assert top[0][0].startswith("%k = f32[8] custom-call")
    assert top[0][1] == pytest.approx(300e-9)
    assert len(top) == 2


def test_idle_gaps_go_to_the_innermost_host_frame():
    gaps = dict(tracing.idle_gaps(_trace()))
    # chip 0 idles [400,600] (midpoint 500, inside _value) and
    # [700,1000] (midpoint 850, inside the step dispatch)
    assert gaps == {"$array.py:631 _value": pytest.approx(200e-9),
                    "PjitFunction(step)": pytest.approx(300e-9)}
