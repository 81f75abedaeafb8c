"""Spans, scopes and counters inside the middleware.

The fused BSP step names its device work with ``jax.named_scope``
(``plug.gather``, ``plug.combine.tiles``, ``plug.combine.devices``,
``plug.apply``) and its Pallas kernel ``csr_tile``; the drive loop and
the build open host spans (``jax.profiler.TraceAnnotation``); the build
keeps its spans in ``Middleware.build_spans``; each iteration record
counts the arcs out of the step's frontier (``edges_active``).
"""
import dataclasses
import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import plug
from repro.graph import generate
from repro.graph.algorithms import pagerank, sssp_bf
from repro.kernels.autotune import CSRConfig
from repro.plug.middleware import DriveLoop

SCOPES = ["plug.gather", "plug.combine.tiles", "plug.combine.devices",
          "plug.apply"]
BUILD_SPANS = {"plug.build.partition", "plug.build.blocks",
               "plug.build.tiles", "plug.build.place"}
SOURCES = [0, 1, 2, 3]
EDGE_GATHERS = ["frontier", "src", "src+frontier", "dst", "aux"]  # in plug.gather


def _middleware(graph, program):
    daemon = plug.get_daemon(
        "sharded", kernel="pallas",
        csr_config=CSRConfig(edge_tile=512, lowering="pallas",
                             merge="onehot", gather="take"))
    mw = plug.Middleware(graph, program, daemon=daemon, upper="mesh",
                         model="bsp", num_shards=1)
    assert mw._fused_kind == "bsp"
    return mw


@pytest.fixture(scope="module")
def small():
    return generate.rmat(256, 2048, seed=3)


@pytest.fixture(scope="module")
def sssp_step(small):
    mw = _middleware(small, sssp_bf(small, SOURCES))
    return mw.daemon, DriveLoop(mw).lower().as_text(debug_info=True)


@pytest.fixture(scope="module")
def step_text(sssp_step):
    return sssp_step[1]


@pytest.mark.parametrize("name", SCOPES + ["csr_tile"])
def test_lowered_step_names_each_scope_and_the_kernel(step_text, name):
    assert name in step_text


def _gather_scopes(text) -> set:
    return {g for g in EDGE_GATHERS if f"plug.gather/{g}/" in text}


def test_sssp_step_gathers_no_aux_and_no_dst_state(sssp_step):
    """SSSP's messages read the src state and the weight: the step
    gathers the src state per edge with the frontier bit in the same
    rows, nothing more."""
    daemon, text = sssp_step
    assert _gather_scopes(text) == {"src+frontier"}
    assert set(daemon.edge_gathers) == {"src+frontier"}


def test_a_step_reading_dst_state_gathers_it(small):
    prog = dataclasses.replace(
        sssp_bf(small, SOURCES), name="sssp_bounded",
        msg_gen=lambda s, d, w, a: jnp.minimum(s + w, d))
    mw = _middleware(small, prog)
    text = DriveLoop(mw).lower().as_text(debug_info=True)
    assert _gather_scopes(text) == {"src+frontier", "dst"}
    assert mw.daemon.edge_gathers == ("src+frontier", "dst")


def _self_seconds(build_spans) -> dict:
    out: dict = {}
    for name, start, end, parent in build_spans:
        out[name] = out.get(name, 0) + (end - start) * 1e-9
        if parent is not None:
            out[parent] = out.get(parent, 0) - (end - start) * 1e-9
    return out


def test_build_spans_cover_the_constructor():
    graph = generate.rmat(4096, 1 << 17, seed=5)
    program = sssp_bf(graph, SOURCES)
    _middleware(graph, program)  # JAX's own start-up is not the build's
    t = time.perf_counter_ns()
    mw = _middleware(graph, program)
    total = (time.perf_counter_ns() - t) * 1e-9
    names = {s[0] for s in mw.build_spans}
    assert names == BUILD_SPANS
    for name, start, end, parent in mw.build_spans:
        assert start <= end
        assert parent in BUILD_SPANS | {None}
    # compaction places its tiles: those placements nest in the tiles span
    assert any(s[0] == "plug.build.place" and s[3] == "plug.build.tiles"
               for s in mw.build_spans)
    selfs = _self_seconds(mw.build_spans)
    assert all(v >= 0 for v in selfs.values()), selfs
    assert sum(selfs.values()) >= 0.95 * total, (selfs, total)


def test_a_rebind_after_the_constructor_records_nothing(small):
    mw = _middleware(small, sssp_bf(small, SOURCES))
    before = list(mw.build_spans)
    mw.daemon.bind_shards(mw.blocksets, mesh=mw.upper.mesh,
                          axis=mw.upper.axis)
    assert mw.build_spans == before


def _frontier_arcs(graph, sources) -> list[int]:
    """Arcs out of each iteration's frontier in a plain float32
    Bellman-Ford: every vertex in the first iteration, then those whose
    distance fell; the run ends with the first iteration in which none
    fell, which is counted."""
    n, src, dst = graph.num_vertices, graph.src, graph.dst
    w = graph.weights.astype(np.float32)
    dist = np.full((n, len(sources)), np.finfo(np.float32).max, np.float32)
    dist[sources, np.arange(len(sources))] = 0.0
    active = np.ones(n, dtype=bool)
    counts = []
    while True:
        e = np.flatnonzero(active[src])
        counts.append(int(e.size))
        new = dist.copy()
        np.minimum.at(new, dst[e], dist[src[e]] + w[e][:, None])
        fell = (new < dist).any(axis=1)
        dist = new
        if not fell.any():
            return counts
        active = fell


def test_edges_active_counts_arcs_out_of_the_frontier(small):
    res = _middleware(small, sssp_bf(small, SOURCES)).run()
    want = _frontier_arcs(small, SOURCES)
    assert res.converged
    assert [r["edges_active"] for r in res.per_iteration] == want
    # the frontier swells and drains: some iteration leaves arcs idle
    assert min(want) < small.num_edges


def test_edges_active_without_a_frontier_is_every_arc(small):
    res = _middleware(small, pagerank(small, tol=0.0,
                                      max_iterations=3)).run()
    assert [r["edges_active"] for r in res.per_iteration] == \
        [small.num_edges] * 3


def test_drive_loop_spans_reach_a_profiler_trace(small, tmp_path):
    from jax.profiler import ProfileData

    mw = _middleware(small, sssp_bf(small, SOURCES))
    mw.run(max_iterations=1)  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        res = mw.run()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    events = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
              for plane in ProfileData.from_file(path).planes
              for line in plane.lines for e in line.events
              if e.name.startswith("plug.")]

    def named(name):
        return [ev for ev in events if ev[2] == name]

    run, = named("plug.run")
    result, = named("plug.result")
    iterations = named("plug.iteration")
    assert len(iterations) == res.iterations
    for child in ("plug.poll", "plug.dispatch", "plug.fetch"):
        inner = named(child)
        assert len(inner) == res.iterations
        assert all(any(a <= s and e <= b for a, b, _ in iterations)
                   for s, e, _ in inner)
    assert all(run[0] <= s and e <= run[1] for s, e, _ in iterations)
    assert iterations[-1][1] <= result[0] and result[1] <= run[1]
