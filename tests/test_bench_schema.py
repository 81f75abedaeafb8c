"""Golden test pinning the BENCH_plug.json tier-2 baseline schema.

The acceleration summary (benchmarks/run.py) indexes the kernel×model
matrix directly: if a refactor of bench_accel drops a cell, the ratio
computation must KeyError loudly rather than silently shrink the
summary.  This file pins both sides of that contract:

* the recorded artifact carries EVERY kernel×model cell and the
  pallas/reference ratios;
* ``_summarize`` raises on a missing cell and mentions the pallas path.

Timing VALUES are deliberately not pinned (the perf acceptance lives in
the bench itself); only the shape of what gets recorded is.
"""
from __future__ import annotations

import copy
import itertools
import json
import pathlib
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
BASELINE = REPO / "results" / "benchmarks" / "BENCH_plug.json"
SERVE_BASELINE = REPO / "results" / "benchmarks" / "BENCH_serve.json"

ALGS = ("pagerank", "sssp_bf", "label_prop")
KERNELS = ("reference", "pallas")
MODELS = ("bsp", "async")
CELLS = tuple(f"{k}/{m}" for k, m in itertools.product(KERNELS, MODELS))
SERVE_KINDS = ("khop", "sssp", "ppr")


@pytest.fixture(scope="module")
def baseline():
    if not BASELINE.exists():
        pytest.skip("tier-2 baseline not recorded "
                    "(run scripts/verify.sh --tier2)")
    with open(BASELINE) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def serve_baseline():
    if not SERVE_BASELINE.exists():
        pytest.skip("serve tier-2 baseline not recorded "
                    "(run scripts/verify.sh --tier2)")
    with open(SERVE_BASELINE) as f:
        return json.load(f)


def _summarize():
    sys.path.insert(0, str(REPO))
    try:
        from benchmarks.run import _summarize as fn
    finally:
        sys.path.pop(0)
    return fn


# -- artifact schema ---------------------------------------------------------
@pytest.mark.parametrize("alg", ALGS)
def test_baseline_records_every_kernel_model_cell(baseline, alg):
    mx = baseline[alg]["sharded_matrix"]
    assert mx["kernels"] == list(KERNELS)
    assert mx["models"] == list(MODELS)
    assert set(mx["per_iter_s"]) == set(CELLS)
    assert all(v > 0 for v in mx["per_iter_s"].values())


@pytest.mark.parametrize("alg", ALGS)
def test_baseline_ratios_consistent_with_cells(baseline, alg):
    """pallas_vs_reference is derived data; it must agree with the cells
    it claims to summarize (a hand-edited artifact fails here)."""
    mx = baseline[alg]["sharded_matrix"]
    assert set(mx["pallas_vs_reference"]) == set(MODELS)
    for m in MODELS:
        want = mx["per_iter_s"][f"pallas/{m}"] / mx["per_iter_s"][f"reference/{m}"]
        assert mx["pallas_vs_reference"][m] == pytest.approx(want, rel=1e-9)


def test_baseline_meta_and_fault_recovery_rows(baseline):
    meta = baseline["_meta"]
    assert meta["num_devices"] == 8 and meta["quick"] is True
    fr = baseline["fault_recovery"]
    assert fr["state_bit_identical"] is True
    assert fr["devices_after"] < fr["devices_before"]


def test_baseline_compressed_train_row(baseline):
    """The int8 grad-wire comparison: both arms recorded, the wire
    accounting consistent (int8 halves the bf16 baseline volume), and
    the error-feedback residual present for the compressed arm."""
    ct = baseline["compressed_train"]
    for arm in ("baseline", "int8"):
        assert ct[arm]["step_time_s"] > 0
        assert ct[arm]["loss_last"] > 0
    assert "grad_wire_err" in ct["int8"]
    assert ct["wire_bytes_saved"] == ct["wire_bytes_baseline"] // 2
    assert ct["step_time_ratio"] == pytest.approx(
        ct["int8"]["step_time_s"] / ct["baseline"]["step_time_s"], rel=1e-9)


def test_baseline_oocore_table(baseline):
    """The out-of-core acceptance rows: at least two HBM budgets, every
    budget smaller than the column range (the graph must NOT fit —
    that's acceptance (a), and each such row still reports bit-identity
    with the all-resident run), all three arms recorded per row with the
    overlap-efficiency and hot-hit-rate columns in range, and the
    prefetch scheduler recovering ≥2× on the recorded sparse-frontier
    slice somewhere in the table."""
    oc = baseline["oocore"]
    assert oc["algorithm"] == "sssp_bf"  # min monoid → bit-identity holds
    rows = oc["budgets"]
    assert len(rows) >= 2
    for row in rows:
        assert row["hbm_budget"] < oc["column_bytes_per_device"]
        assert row["fits_resident"] is False
        assert row["bit_identical"] is True
        assert row["super_shards"] >= 2
        per = row["per_iter_s"]
        assert all(per[a] > 0 for a in ("resident", "oocore_prefetch",
                                        "oocore_no_prefetch"))
        assert 0.0 <= row["overlap_efficiency"] <= 1.0
        assert 0.0 <= row["hot_hit_rate"] <= 1.0
        # derived data: the speedup is the ratio of the recorded means
        assert row["prefetch_speedup"] == pytest.approx(
            per["oocore_no_prefetch"] / per["oocore_prefetch"], rel=1e-6)
        sl = row["sparse_slice"]
        assert sl["count"] >= 1 and sl["prefetch_speedup"] > 0
        assert len(sl["iterations"]) == 2
    assert oc["best_sparse_speedup"] >= 2.0
    assert oc["best_sparse_speedup"] == pytest.approx(
        max(r["sparse_slice"]["prefetch_speedup"] for r in rows), rel=1e-9)


DYNAMIC_ALGS = ("pagerank", "sssp_bf", "wcc")


def test_baseline_dynamic_table_covers_every_cell(baseline):
    """Every algorithm × batch-size cell of the dynamic-graph table,
    each carrying the mutation-epoch accounting (dirty shards recut vs
    left clean, apply seconds) and a timed cold arm."""
    dy = baseline["dynamic"]
    sizes = dy["_meta"]["batch_sizes"]
    assert len(sizes) >= 2
    for alg in DYNAMIC_ALGS:
        assert set(dy[alg]) == {f"b{b}" for b in sizes}
        for cell in dy[alg].values():
            assert cell["edges_added"] >= 1
            assert cell["dirty_count"] >= 1
            assert cell["shards_recut"] >= 1
            assert (cell["shards_recut"] + cell["shards_clean"]
                    == baseline["_meta"]["num_devices"])
            assert cell["mutation_apply_s"] > 0
            assert cell["cold_s"] > 0 and cell["iterations_cold"] >= 1


def test_baseline_dynamic_incremental_arms(baseline):
    """The idempotent workloads (sssp's min, wcc's min) must take the
    incremental dirty-frontier restart in every cell, land bit-identical
    to the cold restart, and — the acceptance — converge in no more
    iterations than cold, strictly fewer (and faster) at the smallest
    batch."""
    dy = baseline["dynamic"]
    small = f"b{min(dy['_meta']['batch_sizes'])}"
    for alg in ("sssp_bf", "wcc"):
        for key, cell in dy[alg].items():
            assert cell["mode"] == "dirty" and cell["reason"] == ""
            assert cell["bit_identical"] is True
            assert cell["iterations_dirty"] <= cell["iterations_cold"]
            # derived data: speedup must agree with the recorded arms
            assert cell["speedup"] == pytest.approx(
                cell["cold_s"] / cell["dirty_s"], rel=1e-9)
            if key == small:
                assert cell["iterations_dirty"] < cell["iterations_cold"]
                assert cell["dirty_s"] < cell["cold_s"]
    assert set(dy["_meta"]["smallest_batch_winners"]) <= {"sssp_bf", "wcc"}
    assert dy["_meta"]["smallest_batch_winners"]


def test_baseline_dynamic_pagerank_is_cold_fallback(baseline):
    """pagerank's sum monoid cannot reuse the old fixed point; the table
    must record the honest fallback, not a fabricated dirty arm."""
    for cell in baseline["dynamic"]["pagerank"].values():
        assert cell["mode"] == "cold_fallback"
        assert cell["reason"] == "non-idempotent monoid"
        assert cell["dirty_s"] is None and cell["speedup"] is None
        assert cell["bit_identical"] is None


ASYNC_SKEW_ARMS = ("eager", "holding", "buckets")


def test_baseline_async_skew_table(baseline):
    """The async-beats-BSP acceptance on a skewed power-law graph: every
    arm must carry the skipped-Gen accounting (nonzero — a hold that
    still runs its blocks is the bug the table guards against), the
    per-iteration ratio against BSP (derived data, consistent with the
    recorded cells and strictly below 1.0), and fixed-point bit-identity
    with BSP (sssp's min monoid is idempotent)."""
    ak = baseline["async_skew"]
    assert ak["algorithm"] == "sssp_bf"
    assert ak["graph"]["rmat"]["a"] > ak["graph"]["rmat"]["b"]  # skewed
    assert ak["bsp"]["per_iter_s"] > 0 and ak["bsp"]["iterations"] >= 1
    assert set(ak["configs"]) == set(ASYNC_SKEW_ARMS)
    for row in ak["configs"].values():
        assert row["per_iter_s"] > 0 and row["iterations"] >= 1
        assert 0 < row["gen_skipped"] <= row["gen_total"]
        assert row["skip_fraction"] == pytest.approx(
            row["gen_skipped"] / row["gen_total"], rel=1e-9)
        assert row["async_vs_bsp"] == pytest.approx(
            row["per_iter_s"] / ak["bsp"]["per_iter_s"], rel=1e-9)
        assert row["async_vs_bsp"] < 1.0
        assert row["bit_identical"] is True
    assert ak["configs"]["buckets"]["bucket_k"] > 0
    assert ak["configs"]["holding"]["theta0"] > 0


def _validate_async_skew():
    sys.path.insert(0, str(REPO))
    try:
        from benchmarks.bench_accel import _validate_async_skew as fn
    finally:
        sys.path.pop(0)
    return fn


def _good_skew_table():
    row = {"theta0": 10.0, "decay": 0.9, "bucket_k": 0, "per_iter_s": 5e-3,
           "async_vs_bsp": 0.5, "iterations": 6, "gen_skipped": 27,
           "gen_total": 48, "skip_fraction": 27 / 48, "bit_identical": True}
    return {"algorithm": "sssp_bf", "num_shards": 8,
            "bsp": {"per_iter_s": 1e-2, "iterations": 5},
            "configs": {"holding": copy.deepcopy(row)}}


def test_validate_async_skew_accepts_good_table():
    table = _good_skew_table()
    assert _validate_async_skew()(table) is table


@pytest.mark.parametrize("patch,match", [
    ({"gen_skipped": 0}, "gen_skipped=0"),
    ({"bit_identical": False}, "diverged"),
    ({"async_vs_bsp": 1.02}, "did not beat"),
    ({"async_vs_bsp": float("nan")}, "did not beat"),
])
def test_validate_async_skew_refuses_to_record(patch, match):
    """The refuse-to-record contract: a table where holds skipped
    nothing, the fixed point diverged, or async lost to BSP must raise
    at record time instead of silently pinning a regression."""
    table = _good_skew_table()
    table["configs"]["holding"].update(patch)
    with pytest.raises(RuntimeError, match=match):
        _validate_async_skew()(table)


def test_baseline_compressed_wire_rows(baseline):
    """The sync-wire measurement: both sum-monoid workloads, byte
    accounting showing real volume reduction (int8 wire strictly below
    the float32 exact wire, ratio consistent), and finite accuracy
    numbers — errors are expected (int8 quantization) but must be
    recorded, not hidden."""
    import math

    cw = baseline["compressed_wire"]
    assert set(cw) == {"pagerank", "label_prop"}
    for row in cw.values():
        assert 0 < row["compressed_bytes"] < row["exact_bytes"]
        assert row["volume_ratio"] == pytest.approx(
            row["compressed_bytes"] / row["exact_bytes"], rel=1e-9)
        assert math.isfinite(row["max_abs_err"])
        assert 0.0 <= row["mean_abs_err"] <= row["max_abs_err"]
        assert all(v > 0 for v in row["per_iter_s"].values())


# -- serving artifact schema -------------------------------------------------
def test_serve_baseline_batch_sweep_covers_every_cell(serve_baseline):
    """Every query-kind × batch-size cell, ≥3 kinds × ≥2 sizes, each
    with sane percentiles and positive throughput."""
    meta = serve_baseline["_meta"]
    sizes = meta["batch_sizes"]
    assert len(sizes) >= 2 and len(SERVE_KINDS) >= 3
    sweep = serve_baseline["batch_sweep"]
    assert set(sweep) == set(SERVE_KINDS)
    for kind in SERVE_KINDS:
        assert set(sweep[kind]) == {f"b{b}" for b in sizes}
        for cell in sweep[kind].values():
            assert 0 < cell["p50_ms"] <= cell["p99_ms"]
            assert cell["qps"] > 0 and cell["iterations"] >= 1


def test_serve_baseline_offered_load_rows(serve_baseline):
    """One row per offered rate: end-to-end percentiles, achieved
    throughput, and the per-kind breakdown covering every batched kind."""
    meta = serve_baseline["_meta"]
    rows = serve_baseline["offered_load"]
    assert set(rows) == {f"load_{int(r)}" for r in meta["loads"]}
    assert len(rows) >= 2
    for row in rows.values():
        assert row["completed"] == meta["num_requests"]
        assert 0 < row["p50_ms"] <= row["p99_ms"]
        assert row["throughput_qps"] > 0
        assert set(SERVE_KINDS) <= set(row["kinds"])


def test_serve_baseline_cache_hit_row(serve_baseline):
    """The acceptance row: a cache hit is far cheaper than the cold
    fused run that produced the entry."""
    c = serve_baseline["cache"]
    assert c["hit_ms"] < c["cold_ms"]
    assert c["speedup"] > 10
    meta = serve_baseline["_meta"]
    assert meta["num_devices"] == 8 and meta["quick"] is True
    assert meta["families_compiled"] >= len(SERVE_KINDS)


# -- summary contract --------------------------------------------------------
def _fake_result():
    cell = {c: 1e-3 * (i + 1) for i, c in enumerate(CELLS)}
    return {
        alg: {
            "naive": 1.0, "blocked": 0.5, "vectorized": 0.1,
            "speedup_vectorized": 10.0,
            "sharded_matrix": {"kernels": list(KERNELS),
                               "models": list(MODELS),
                               "per_iter_s": dict(cell),
                               "pallas_vs_reference": {m: 1.0
                                                       for m in MODELS}},
        }
        for alg in ALGS
    }


def test_summarize_mentions_pallas_ratio_per_algorithm(capsys):
    _summarize()("bench_accel", _fake_result())
    out = capsys.readouterr().out
    for alg in ALGS:
        assert f"{alg}: pallas/reference" in out


def test_summarize_raises_on_missing_matrix_cell(capsys):
    """The regression this file exists for: a dropped cell must blow up
    the summary, not vanish from it."""
    result = _fake_result()
    del result["sssp_bf"]["sharded_matrix"]["per_iter_s"]["pallas/async"]
    with pytest.raises(KeyError, match="pallas/async"):
        _summarize()("bench_accel", result)


def test_recorded_baseline_summarizes_cleanly(baseline, capsys):
    """The committed artifact itself must flow through the summary —
    ties the golden file to the code that consumes it."""
    _summarize()("bench_accel", baseline)
    out = capsys.readouterr().out
    assert out.count("pallas/reference") == len(ALGS)
