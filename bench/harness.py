"""Runs one benchmark cell: set-up, the measured window, the check
against the plain reference, and the metrics.

Everything that belongs to one configuration, traffic mix or metric is
data or a reader of its own, found by the name ``BENCHMARK.json`` gives:

* ``bench/configs/<config>.json`` — the deployment: graph generator and
  sizes, and the middleware composition that serves it;
* ``bench/traffic/<traffic>.json`` — the mix ``bench/traffic.py`` reads,
  with the limit of each number the check compares;
* ``bench/metrics/<metric>.py`` — a ``read(record)`` that returns the
  metric from a :class:`Record`, or None where it finds nothing to read.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import pathlib
import sys
import tempfile
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
#: what marks the Pallas kernel in the compiled step's text
PALLAS_CALL = "tpu_custom_call"
#: the warm-up run's iteration cap: it compiles and loads the step the
#: window drives, and no more
WARMUP_ITERATIONS = 2


class NoChip(RuntimeError):
    """JAX found no TPU, fewer chips than the cell asks for, or a chip
    whose peaks ``bench/peaks.json`` does not list."""


@dataclasses.dataclass
class Record:
    """What one run measured; the metric readers read this."""

    traffic: dict
    setup_s: float
    build_s: float
    compile_s: float
    window_s: float
    iterations: list
    peak_bytes: int | None
    num_vertices: int
    num_edges: int
    state_width: int
    peaks: dict
    trace: object = None  # bench.tracing.Trace in a traced run


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str):
    """``(spec, workload, config, traffic)`` for the cell ``name``."""
    spec = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    workload = cells[name]
    config = load_json(BENCH / "configs" / f"{workload['config']}.json")
    traffic = load_json(BENCH / "traffic" / f"{workload['traffic']}.json")
    return spec, workload, config, traffic


def reader(metric: str):
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    path = BENCH / "metrics" / f"{metric}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module.read


def log(**fields) -> None:
    print(json.dumps(fields), file=sys.stderr, flush=True)


def _chips(jax, chips: int) -> list:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX found "
                     f"{len(devices)}")
    return devices


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, on_chip: bool = True,
             config: dict | None = None):
    """Runs the cell ``name`` once and returns its result line.

    ``on_chip=False`` skips the look for a TPU and the check that the
    compiled step holds the Pallas kernel (the tests drive the rest of a
    run on the CPU that way); ``config`` replaces the cell's
    configuration (the tests shrink the graph).
    """
    import jax
    # the program first: a checkout without it stops here, chip or not
    from repro import plug
    from repro.graph.structure import Graph
    from repro.kernels.autotune import CSRConfig

    from bench import graphs, tracing
    from bench import traffic as traffic_mod

    spec, workload, cfg_file, traffic = load_cell(name)
    config = config or cfg_file
    chips = workload["chips"]
    peaks_table = load_json(BENCH / "peaks.json")["devices"]
    if on_chip:
        devices = _chips(jax, chips)
        kind = devices[0].device_kind
        if kind not in peaks_table:
            raise NoChip(f"bench/peaks.json lists no {kind!r}")
        from repro.launch.cache import use_compile_cache

        use_compile_cache()
        # every program the cell runs goes to the persistent cache, so
        # only a checkout's first run compiles
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    else:
        devices = jax.devices()
        kind = devices[0].device_kind
    devices = devices[:chips]

    t = time.perf_counter()
    n, src, dst, w = graphs.make(config)
    graph = Graph(n, src, dst, w)
    work = traffic_mod.make(traffic, graph, seed)
    log(phase="generate", seconds=time.perf_counter() - t,
        num_vertices=n, num_edges=int(src.size))

    mwc = config["middleware"]
    t = time.perf_counter()
    mw = plug.Middleware(
        graph, work.program(),
        daemon=plug.get_daemon(mwc["daemon"], kernel=mwc["kernel"],
                               csr_config=CSRConfig(**mwc["csr_config"])),
        upper=mwc["upper"], model=mwc["model"],
        num_shards=mwc["num_shards"])
    jax.block_until_ready(mw.daemon.stacked)
    build_s = time.perf_counter() - t
    if mw._fused_kind != "bsp":
        raise RuntimeError(f"the fused BSP drive loop was not selected "
                           f"({mw._fused_kind!r})")
    t = time.perf_counter()
    compiled = mw.compile_step()
    compile_s = time.perf_counter() - t
    if on_chip and PALLAS_CALL not in compiled.as_text():
        raise RuntimeError("the compiled step holds no Pallas kernel "
                           f"({PALLAS_CALL})")
    del compiled
    t = time.perf_counter()
    mw.run(max_iterations=WARMUP_ITERATIONS, init=work.init(0))
    warmup_s = time.perf_counter() - t
    # set-up's objects are frozen out of the window's collections, so a
    # collection there scans only what the window's runs made
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    log(phase="setup", build_s=build_s, compile_s=compile_s,
        warmup_s=warmup_s, setup_s=setup_s)

    # programs traced inside the window (each is then compiled or loaded
    # from the persistent cache): there should be none
    compiles = {"on": False, "count": 0}

    def count_compile(event: str, duration: float, **_) -> None:
        if compiles["on"] and event == "/jax/core/compile/jaxpr_trace_duration":
            compiles["count"] += 1

    jax.monitoring.register_event_duration_secs_listener(count_compile)
    # the interpreter's collections inside the window, timed
    pauses = {"start": 0.0, "s": 0.0}

    def time_collection(phase: str, info: dict) -> None:
        if phase == "start":
            pauses["start"] = time.perf_counter()
        else:
            pauses["s"] += time.perf_counter() - pauses["start"]

    gc.callbacks.append(time_collection)
    trace_dir = tempfile.TemporaryDirectory() if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir.name)
    runs, ends, gc_s = [], [], []
    compiles["on"] = True
    with jax.profiler.TraceAnnotation(tracing.WINDOW):
        t0 = time.perf_counter()
        while True:
            res = mw.run(init=work.init(len(runs)))
            runs.append((len(runs), res.state, res.iterations))
            ends.append(time.perf_counter() - t0)
            gc_s.append(pauses["s"])
            if ends[-1] >= seconds and len(runs) % work.cycle == 0:
                break
        window_s = time.perf_counter() - t0
    compiles["on"] = False
    gc.callbacks.remove(time_collection)
    gc.unfreeze()
    if trace:
        jax.profiler.stop_trace()
    peak = max(((d.memory_stats() or {}).get("peak_bytes_in_use") or 0)
               for d in devices) or None
    log(phase="window", runs=len(runs), window_s=window_s,
        iterations=[r[2] for r in runs],
        run_s=[b - a for a, b in zip([0.0] + ends, ends)],
        gc_s=[b - a for a, b in zip([0.0] + gc_s, gc_s)],
        compiles=compiles["count"])
    del mw, res
    gc.collect()

    t = time.perf_counter()
    checks, failed = work.check(runs, traffic["limits"])
    log(phase="check", seconds=time.perf_counter() - t)
    parsed = None
    if trace:
        t = time.perf_counter()
        parsed = tracing.load(trace_dir.name)
        trace_dir.cleanup()
        log(phase="trace", seconds=time.perf_counter() - t)

    record = Record(
        traffic=traffic, setup_s=setup_s, build_s=build_s,
        compile_s=compile_s, window_s=window_s,
        iterations=[r[2] for r in runs], peak_bytes=peak,
        num_vertices=n, num_edges=int(src.size),
        state_width=work.state_width, peaks=peaks_table.get(kind, {}),
        trace=parsed)
    metrics = {}
    for m in spec["per_layer" if trace else "end_to_end"]:
        if name not in m.get("workloads", [name]):
            continue
        value = reader(m["name"])(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {
        "correct": bool(runs) and failed == 0 and all(
            c["value"] <= c["limit"] for c in checks.values()),
        "attempted": len(runs), "failed": failed, "metrics": metrics,
        "device": device}
    if trace:
        device["busy_s"] = tracing.busy_s(parsed)
        device["window_s"] = parsed.window_s
        result["breakdown"] = {"device_ops": tracing.top_ops(parsed),
                               "idle_gaps": tracing.idle_gaps(parsed)}
    result["compiles_in_window"] = compiles["count"]
    result["checks"] = checks
    return result
