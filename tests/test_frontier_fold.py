"""The frontier bit rides in the src-state gather of a frontier step.

On the Pallas tile path a frontier-driven step gathers each arc's source
row once, with the source's activity as one more column, and the kernel
multiplies that column into its edge mask.  The step must send exactly
what the two-pass form sent: the per-arc mask ``emask & active[gsrc]``
ahead of the src-state gather.  The graph is a small Graph500 kernel-0
graph drawn as the benchmark draws ``graph500-s19``.
"""
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from bench import graphs  # noqa: E402
from repro import plug  # noqa: E402
from repro.graph.algorithms import bfs, pagerank, sssp_bf, wcc  # noqa: E402
from repro.graph.structure import Graph  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.kernels.autotune import CSRConfig  # noqa: E402
from repro.plug.middleware import DriveLoop, make_apply_fn  # noqa: E402

SCALE = 8  # 256 vertices, 4096 tuples, 8192 arcs
EDGE_TILE = 128
FRONTIER_PROGRAMS = {
    "sssp": lambda g: sssp_bf(g, [int(v) for v in g.src[:4]]),
    "bfs": lambda g: bfs(g, int(g.src[0])),
    "wcc": wcc,
}


@pytest.fixture(scope="module")
def graph():
    n, src, dst, w = graphs.graph500(SCALE, 16, a=0.57, b=0.19, c=0.19,
                                     seed=0)
    return Graph(n, src, dst, w)


def _middleware(graph, program, gather):
    cfg = CSRConfig(edge_tile=EDGE_TILE, lowering="pallas", merge="onehot",
                    gather=gather)
    daemon = plug.get_daemon("sharded", kernel="pallas", csr_config=cfg)
    mw = plug.Middleware(graph, program, daemon=daemon, upper="mesh",
                         model="bsp", num_shards=1)
    assert mw._fused_kind == "bsp"
    return mw, cfg


def _unfolded_step(daemon, cfg, program, n):
    """The two-pass step: the per-arc frontier mask, then the tile
    aggregate with no activity in its gather."""
    csr = {k: v.reshape((-1,) + v.shape[2:])
           for k, v in daemon.stacked["csr"].items()}

    @jax.jit
    def step(state, aux, act, csr):
        em = csr["emask"] & act[csr["gsrc"]]
        agg, cnt, _ = ops.csr_aggregate(state, aux, dict(csr, emask=em),
                                        program=program, num_vertices=n,
                                        config=cfg)
        return agg, cnt, jnp.any(em, axis=1).sum()

    return lambda state, aux, act: step(state, aux, act, csr)


@pytest.mark.parametrize("gather", ["take", "onehot"])
@pytest.mark.parametrize("name", sorted(FRONTIER_PROGRAMS))
def test_folded_step_matches_the_unfolded_mask_to_the_fixed_point(
        graph, name, gather):
    program = FRONTIER_PROGRAMS[name](graph)
    mw, cfg = _middleware(graph, program, gather)
    daemon, n = mw.daemon, graph.num_vertices
    assert daemon.stacked["csr"]["gsrc"].shape[0] == 1
    folded = jax.jit(lambda s, a, act: daemon.run_all_shards(s, a, act))
    unfolded = _unfolded_step(daemon, cfg, program, n)
    apply_fn = make_apply_fn(program)
    state, aux = (jnp.asarray(x) for x in program.init(graph))
    act = jnp.ones((n,), jnp.bool_)
    it = 0
    while bool(act.any()):
        agg, cnt, tiles = folded(state, aux, act)
        ragg, rcnt, rtiles = unfolded(state, aux, act)
        np.testing.assert_array_equal(np.asarray(agg[0]), np.asarray(ragg))
        np.testing.assert_array_equal(np.asarray(cnt[0]), np.asarray(rcnt))
        assert int(tiles.sum()) == int(rtiles), it
        state, act = apply_fn(state, agg[0], cnt[0] > 0, aux, it)
        it += 1
    assert daemon.edge_gathers == ("src+frontier",)
    # the frontier swelled and drained: some step ran a strict subset
    assert it > 2
    want, _ = plug.run_reference(graph, program)
    np.testing.assert_array_equal(np.asarray(state), want)


def _gathers(text) -> list[tuple[tuple[int, ...], str, tuple[int, ...]]]:
    """(table shape, result element type, index shape) of every gather in
    a lowered step's StableHLO."""
    out = []
    for m in re.finditer(r"stablehlo\.gather.*?: \(tensor<([0-9x]+)x\w+>, "
                         r"tensor<([0-9x]+)xi32>\) -> tensor<[0-9x]+x(\w+)>",
                         text):
        table = tuple(int(x) for x in m.group(1).split("x"))
        index = tuple(int(x) for x in m.group(2).split("x"))
        out.append((table, m.group(3), index))
    return out


def _kernel_src_rows(monkeypatch):
    """Records the src rows and the activity flag of each tile-kernel
    call traced through ``csr_aggregate``."""
    seen = []
    real = ops.csr_tile_pallas

    def spy(src, *args, **kwargs):
        seen.append((src.shape[1], kwargs.get("frontier", False)))
        return real(src, *args, **kwargs)

    monkeypatch.setattr(ops, "csr_tile_pallas", spy)
    return seen


def test_frontier_step_gathers_each_arc_source_once(graph, monkeypatch):
    """The cell's path (take gather): one gather over the arc slots, of
    the K state columns and the activity, and no pred gather left."""
    seen = _kernel_src_rows(monkeypatch)
    program = FRONTIER_PROGRAMS["sssp"](graph)
    mw, _ = _middleware(graph, program, "take")
    text = DriveLoop(mw).lower().as_text()
    slots = mw.daemon.stacked["csr"]["gsrc"].shape[1:]
    k = program.state_width
    per_arc = [g for g in _gathers(text) if g[2][:-1] == slots]
    assert per_arc == [((graph.num_vertices, k + 1), "f32", slots + (1,))]
    assert not [g for g in _gathers(text) if g[1] == "i1"]
    assert seen == [(k + 1, True)]
    assert mw.daemon.edge_gathers == ("src+frontier",)


def test_a_step_with_no_frontier_gathers_no_activity_row(graph,
                                                         monkeypatch):
    """PageRank runs every arc: its src operand keeps its K rows."""
    seen = _kernel_src_rows(monkeypatch)
    program = pagerank(graph)
    mw, _ = _middleware(graph, program, "take")
    text = DriveLoop(mw).lower().as_text()
    slots = mw.daemon.stacked["csr"]["gsrc"].shape[1:]
    k = program.state_width
    widths = sorted(g[0][1] for g in _gathers(text) if g[2][:-1] == slots)
    assert widths == [max(program.aux_width, 1), k]
    assert seen == [(k, False)]
    assert mw.daemon.edge_gathers == ("src", "aux")
