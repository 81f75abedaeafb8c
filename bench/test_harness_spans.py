"""The reader of the program's own host spans, on a small synthetic
trace (nanoseconds)."""
import pytest

from bench import harness, spans, tracing

KERNEL = ('%csr_tile.1 = f32[8] custom-call(f32[8] %a), '
          'custom_call_target="tpu_custom_call"')


def _trace():
    # window [0, 1000], two iterations; chip 0 busy [0, 220], [300, 500]
    # and [700, 710]; idle [220, 300] and [500, 700] in plug.fetch,
    # [710, 1000] in plug.result
    chip0 = [(0, 100, "%fusion = pred[8] fusion(pred[8] %p)"),
             (100, 150, KERNEL),
             (150, 220, "%fusion.4 = f32[8] fusion(f32[8] %p)"),
             (300, 400, "%fusion = pred[8] fusion(pred[8] %p)"),
             (400, 450, KERNEL),
             (450, 500, "%fusion.4 = f32[8] fusion(f32[8] %p)"),
             (700, 710, "%copy = f32[8] copy(f32[8] %s)")]
    host = [(0, 1000, tracing.WINDOW),
            (0, 900, "$middleware.py:1247 run"),
            (0, 900, "plug.run"),
            (0, 300, "plug.iteration#it=1#"),
            (0, 2, "plug.poll"),
            (2, 20, "plug.dispatch"),
            (200, 300, "plug.fetch"),
            (210, 290, "$array.py:631 _value"),
            (300, 650, "plug.iteration"),
            (300, 305, "plug.poll"),
            (305, 320, "plug.dispatch"),
            (320, 650, "plug.fetch"),
            (330, 640, "$array.py:631 _value"),
            (650, 900, "plug.result"),
            (660, 890, "$array.py:631 _value")]
    return tracing.Trace(window=(0, 1000), devices=[chip0], host=host)


def _record(trace, iterations=(2,)):
    return harness.Record(
        traffic={"algorithm": "sssp_bf"}, setup_s=30.0, build_s=17.0,
        compile_s=0.3, window_s=1e-6, iterations=list(iterations),
        peak_bytes=1, num_vertices=8, num_edges=8, state_width=4,
        peaks={}, trace=trace)


def test_idle_time_by_innermost_span():
    # the Python frames inside plug.fetch / plug.result do not hide them
    assert spans.idle_by_span(_trace()) == pytest.approx(
        {"plug.fetch": 280e-9, "plug.result": 290e-9})


def test_a_child_that_starts_with_its_parent_is_the_innermost():
    tr = _trace()
    tr.host.append((220, 300, "plug.poll"))  # opens with a gap
    tr.host.append((220, 310, "plug.iteration"))
    assert spans.idle_by_span(tr)["plug.poll"] == pytest.approx(80e-9)


def test_loop_gap_reads_idle_per_iteration():
    rec = _record(_trace())
    # 280 ns of idle under the iterations' spans over 2 iterations
    assert harness.reader("loop_gap_ms")(rec) == pytest.approx(1.4e-4)
    # what the accepted readers read is unchanged by the spans
    assert harness.reader("tile_kernel_ms")(rec) == pytest.approx(5e-5)
    assert harness.reader("device_idle_share")(rec) == pytest.approx(57.0)


def test_loop_gap_times_iterations_is_at_most_the_idle_time():
    rec = _record(_trace())
    idle_s = rec.trace.window_s - tracing.busy_s(rec.trace)
    assert harness.reader("loop_gap_ms")(rec) * 2 * 1e-3 <= idle_s


@pytest.mark.parametrize("trace", [
    None,
    # an older program: Python frames, no plug.* spans
    tracing.Trace(window=(0, 1000),
                  devices=[[(0, 500, "%fusion = f32[8] fusion(f32[8] %p)")]],
                  host=[(0, 1000, tracing.WINDOW),
                        (0, 900, "$middleware.py:1225 run"),
                        (550, 590, "$array.py:631 _value")])])
def test_loop_gap_reads_nothing_in_a_program_without_spans(trace):
    assert harness.reader("loop_gap_ms")(_record(trace)) is None
