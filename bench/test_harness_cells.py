"""Each cell's path, end to end on the CPU at a tiny size: the same
``Middleware`` composition, window and check as on the chip, with the
Pallas kernel in interpret mode.  The check must pass on the program as
it is, and fail with the timed path broken underneath it."""
import dataclasses
import os
import shutil
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness

CELLS = ["rmat19-pagerank", "rmat19-sssp4"]

# The PageRank cell joins BENCHMARK.json once it has its runs on the
# chip; until then its path is tested here, served as if it were listed.
PAGERANK_CELL = {"name": "rmat19-pagerank", "config": "graph500-s19",
                 "traffic": "pagerank10", "chips": 1}
AGG_ROOFLINE = {"name": "agg_roofline", "unit": "%", "better": "higher",
                "source": "device_trace", "layer": "daemon kernel",
                "moves": "run_s", "workloads": ["rmat19-pagerank"]}


@pytest.fixture(autouse=True)
def pagerank_cell(monkeypatch):
    load_json = harness.load_json

    def with_pagerank(path):
        data = load_json(path)
        if os.path.basename(path) == "BENCHMARK.json" and not any(
                w["name"] == PAGERANK_CELL["name"] for w in data["workloads"]):
            name = PAGERANK_CELL["name"]
            per_layer = [dict(m, workloads=m["workloads"] + [name])
                         if "workloads" in m else m
                         for m in data["per_layer"]]
            data = dict(data, workloads=data["workloads"] + [PAGERANK_CELL],
                        per_layer=per_layer + [AGG_ROOFLINE])
        return data

    monkeypatch.setattr(harness, "load_json", with_pagerank)


def _tiny(cell):
    return dict(harness.load_cell(cell)[2], scale=8)


def _run(cell, seed=2**31 + 17, trace=False, seconds=0.2):
    return harness.run_cell(cell, seed, seconds, trace,
                            t_start=time.perf_counter(), on_chip=False,
                            config=_tiny(cell))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_is_correct_against_the_reference(cell):
    res = _run(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert res["compiles_in_window"] == 0
    assert "run_s" in res["metrics"] and "setup_s" in res["metrics"]


def test_window_closes_at_the_end_of_a_pool_walk():
    pool = harness.load_cell("rmat19-sssp4")[3]["pool_size"]
    assert pool > 1
    res = _run("rmat19-sssp4", seconds=0.0)
    assert res["correct"] and res["attempted"] == pool


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_the_per_layer_metrics(cell):
    res = _run(cell, trace=True)
    assert res["correct"]
    assert {"build_s", "compile_s", "iterations"} <= set(res["metrics"])
    assert res["metrics"]["iterations"]["value"] > 1
    assert res["device"]["window_s"] > 0


def _unchanged_state(monkeypatch):
    from repro.plug import middleware

    build = middleware.DriveLoop._build_step

    def broken(self):
        step = build(self)

        def stuck(state, active, aux, it, stacked):
            _, new_active, _, n_active, blocks_run = step(
                state, active, aux, it, stacked)
            return (state, jnp.zeros_like(new_active), n_active * 0 == 0,
                    n_active * 0, blocks_run)

        return jax.jit(stuck)

    monkeypatch.setattr(middleware.DriveLoop, "_build_step", broken)


def _half_left_out(monkeypatch):
    from repro.plug import daemons

    stack = daemons.ShardedDaemon._stack_csr_tiles

    def broken(self, blocksets, place):
        csr = stack(self, blocksets, place)
        half = csr["emask"].shape[1] // 2
        csr["emask"] = csr["emask"].at[:, half:].set(False)
        return csr

    monkeypatch.setattr(daemons.ShardedDaemon, "_stack_csr_tiles", broken)


def _answer_altered(monkeypatch):
    from repro.plug import middleware

    run = middleware.Middleware.run

    def broken(self, *args, **kwargs):
        res = run(self, *args, **kwargs)
        state = np.array(res.state)
        state.flat[np.argmin(state)] += 1.0
        return dataclasses.replace(res, state=state)

    monkeypatch.setattr(middleware.Middleware, "run", broken)


# one chip, one shard: these cells have no exchange between chips to
# leave out
FAULTS = {"unchanged_state": _unchanged_state,
          "half_left_out": _half_left_out,
          "answer_altered": _answer_altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_broken_path_is_not_correct(monkeypatch, cell, fault):
    FAULTS[fault](monkeypatch)
    res = _run(cell)
    assert not res["correct"], res["checks"]
    assert res["failed"] >= 1


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_a_limit(cell):
    """The bfloat16 reference in the program's place fails the check."""
    from repro.graph.structure import Graph

    from bench import graphs, traffic

    traffic_params = harness.load_cell(cell)[3]
    n, src, dst, w = graphs.make(_tiny(cell))
    work = traffic.make(traffic_params, Graph(n, src, dst, w), 5)
    checks, failed = work.control(3, traffic_params["limits"])
    assert failed == 3
    assert any(c["value"] > c["limit"] for c in checks.values())


def test_no_tpu_exits_nonzero_without_a_result(capsys):
    from importlib import util

    spec = util.spec_from_file_location("bench_run", harness.BENCH / "run.py")
    run = util.module_from_spec(spec)
    spec.loader.exec_module(run)
    rc = run.main(["--workload", "rmat19-sssp4", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    assert rc == 2
    assert capsys.readouterr().out == ""


def test_benchmark_files_alone_do_not_run(tmp_path):
    """A checkout with only BENCHMARK.json and bench/ has no program."""
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "rmat19-sssp4",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert "ModuleNotFoundError" in out.stderr
    assert not any(line.startswith("{") for line in out.stdout.splitlines())
