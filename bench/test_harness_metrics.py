"""Metric readers: the roofline's byte count and what each reads."""
import pytest

from bench import harness, tracing

agg = harness.reader("agg_roofline")


def _record(trace, algorithm="pagerank", iterations=(10, 10)):
    return harness.Record(
        traffic={"algorithm": algorithm}, setup_s=30.0,
        build_s=17.0, compile_s=0.5, window_s=20.0,
        iterations=list(iterations), peak_bytes=867_000_000,
        num_vertices=1 << 20, num_edges=16 << 20, state_width=1,
        peaks={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        trace=trace)


def test_agg_roofline_byte_count():
    from importlib import util

    spec = util.spec_from_file_location(
        "agg", harness.BENCH / "metrics" / "agg_roofline.py")
    mod = util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    n, e = 1 << 20, 16 << 20
    # ids 4 B/edge, state read + written 2 x 4 B/vertex, degrees 4 B/vertex
    assert mod.iteration_bytes(n, e, 1) == 4 * e + 12 * n == 79_691_776
    assert mod.iteration_bytes(n, e, 4) == 4 * e + 36 * n
    assert mod.iteration_flops(n, e) == 2 * e + 2 * n


def test_agg_roofline_reads_busy_time_per_iteration():
    # 20 iterations in 1 s of busy time: 50 ms per iteration
    trace = tracing.Trace(window=(0, 2e9), devices=[[(0, 1e9, "op")]],
                          host=[])
    least = 79_691_776 / 819e9
    assert agg(_record(trace)) == pytest.approx(100 * least / 0.05)
    assert agg(_record(trace)) < 100.0


@pytest.mark.parametrize("trace, algorithm", [
    (None, "pagerank"),
    (tracing.Trace(window=(0, 1e9), devices=[[(0, 1e9, "op")]], host=[]),
     "sssp_bf")])
def test_agg_roofline_reads_nothing_without_trace_or_dense_work(
        trace, algorithm):
    assert agg(_record(trace, algorithm)) is None


def test_end_to_end_readers():
    rec = _record(None, iterations=(10, 10, 10, 10))
    assert harness.reader("run_s")(rec) == pytest.approx(5.0)
    assert harness.reader("setup_s")(rec) == 30.0
    assert harness.reader("hbm_peak_gb")(rec) == pytest.approx(0.867)
    assert harness.reader("iterations")(rec) == 10.0
    assert harness.reader("tile_kernel_ms")(rec) is None
    assert harness.reader("device_idle_share")(rec) is None


def test_every_metric_in_the_spec_has_a_reader():
    spec = harness.load_json(harness.ROOT / "BENCHMARK.json")
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(harness.reader(m["name"]))
