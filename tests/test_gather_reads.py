"""The tile path gathers per edge only what a program's ``msg_gen`` reads.

``VertexProgram.msg_gen_reads`` is found from the program itself (a
liveness walk over its traced ``msg_gen``); ``csr_aggregate`` then
leaves out the dst-state and aux gathers a program never
reads, and hands the kernel zeros in their place.  A program that does
read them keeps its gathers and its answers, bit for bit.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import plug
from repro.core.template import MIN, VertexProgram
from repro.graph import algorithms, generate
from repro.graph.compaction import build_csr_tiles
from repro.kernels import ops
from repro.kernels.autotune import CSRConfig

N_V = 96
SOURCES = [0, 1, 2, 3]
# the graph's largest in-degree is 43: 16-edge tiles split hub rows
# across tiles, 64-edge tiles hold every row whole
EDGE_TILES = [16, 64]

SHIPPED = {
    "sssp_bf": (lambda g: algorithms.sssp_bf(g, SOURCES),
                {"src", "weight"}),
    "batched_sssp": (lambda g: algorithms.batched_sssp(g, [[0], [1]]),
                     {"src", "weight"}),
    "pagerank": (algorithms.pagerank, {"src", "aux"}),
    "batched_ppr": (lambda g: algorithms.batched_ppr(g, [[0], [1]]),
                    {"src", "aux"}),
    "wcc": (algorithms.wcc, {"src"}),
    "bfs": (algorithms.bfs, {"src"}),
    "batched_khop": (lambda g: algorithms.batched_khop(g, [[0], [1]]),
                     {"src"}),
    "label_prop": (algorithms.label_prop, {"src", "weight"}),
}


@pytest.fixture(scope="module")
def graph():
    return generate.rmat(N_V, 700, seed=4)


@pytest.mark.parametrize("name", sorted(SHIPPED))
def test_msg_gen_reads_of_each_shipped_program(graph, name):
    make, want = SHIPPED[name]
    prog = make(graph)
    assert prog.msg_gen_reads == want
    assert prog.msg_gen_reads is prog.msg_gen_reads  # traced once


def _sssp_init(g):
    n = g.num_vertices
    state = np.full((n, len(SOURCES)), algorithms.INF, np.float32)
    state[SOURCES, np.arange(len(SOURCES))] = 0.0
    aux = (np.arange(n) % 3 * 0.25).astype(np.float32).reshape(n, 1)
    return state, aux


def _min_apply(state, merged, has_msg, aux, t):
    new = jnp.minimum(state, merged)
    return new, jnp.any(new < state, axis=-1)


def _custom(reads: str) -> VertexProgram:
    """A shortest-path variant whose messages read the dst state or a
    one-wide aux."""
    gen = {
        "dst": lambda s, d, w, a: jnp.minimum(s + w, d),
        "aux": lambda s, d, w, a: s + w + a,
    }[reads]
    return VertexProgram(
        name=f"reads_{reads}", state_width=len(SOURCES), aux_width=1,
        monoid=MIN, msg_gen=gen, msg_apply=_min_apply, init=_sssp_init)


@pytest.mark.parametrize("reads", ["dst", "aux"])
def test_msg_gen_reads_of_a_custom_program(reads):
    assert _custom(reads).msg_gen_reads == {"src", "weight", reads}


def test_msg_gen_reads_looks_inside_nested_calls():
    inner = jax.jit(lambda s, d: s + 0.0 * d)
    prog = VertexProgram(
        name="nested", state_width=2, aux_width=0, monoid=MIN,
        msg_gen=lambda s, d, w, a: inner(s, d) + w,
        msg_apply=_min_apply, init=_sssp_init)
    assert prog.msg_gen_reads == {"src", "dst", "weight"}


def _aggregate(prog, graph, cfg, state, aux):
    ts = build_csr_tiles(graph.src, graph.dst, graph.weights, N_V,
                         edge_tile=cfg.edge_tile)
    csr = {k: jnp.asarray(v) for k, v in ts.arrays().items()}
    agg, cnt, _ = ops.csr_aggregate(
        jnp.asarray(state), jnp.asarray(aux), csr, program=prog,
        num_vertices=N_V)
    return np.asarray(agg), np.asarray(cnt)


@pytest.mark.parametrize("edge_tile", EDGE_TILES)
@pytest.mark.parametrize("reads", ["dst", "aux"])
def test_a_program_that_reads_keeps_its_gather(graph, reads, edge_tile):
    prog = _custom(reads)
    rng = np.random.default_rng(7)
    state = rng.uniform(0.0, 6.0, (N_V, len(SOURCES))).astype(np.float32)
    aux = rng.uniform(0.0, 2.0, (N_V, 1)).astype(np.float32)
    assert reads in ops.tile_gathers(prog)
    agg, cnt = _aggregate(prog, graph, CSRConfig(edge_tile=edge_tile),
                          state, aux)
    # the definition: Gen on every arc, min by destination
    src, dst = graph.src, graph.dst
    msgs = np.asarray(prog.msg_gen(
        jnp.asarray(state[src]), jnp.asarray(state[dst]),
        jnp.asarray(graph.weights[:, None]), jnp.asarray(aux[src])))
    want = np.full_like(state, MIN.identity)
    np.minimum.at(want, dst, msgs)
    np.testing.assert_array_equal(agg, want)
    np.testing.assert_array_equal(cnt, np.bincount(dst, minlength=N_V))


@pytest.mark.parametrize("edge_tile", EDGE_TILES)
@pytest.mark.parametrize("reads", ["dst", "aux"])
def test_a_program_that_reads_matches_the_reference_run(graph, reads,
                                                        edge_tile):
    prog = _custom(reads)
    daemon = plug.get_daemon("sharded", kernel="pallas",
                             csr_config=CSRConfig(edge_tile=edge_tile))
    mw = plug.Middleware(graph, prog, daemon=daemon, upper="mesh",
                         model="bsp", num_shards=1)
    res = mw.run()
    want, iters = plug.run_reference(graph, prog)
    assert daemon.edge_gathers == ("src+frontier", reads)
    assert res.iterations == iters
    np.testing.assert_array_equal(np.asarray(res.state), want)
