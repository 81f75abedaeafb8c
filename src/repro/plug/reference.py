"""Pure-jnp full-graph reference: the oracle every backend is tested
against (DESIGN.md §8).  No blocks, no shards, no middleware — one dense
Gen → Merge → Apply per iteration over the whole edge list."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.template import VertexProgram
from repro.graph.structure import Graph


def run_reference(graph: Graph, program: VertexProgram,
                  max_iterations: int | None = None) -> tuple[np.ndarray, int]:
    state, aux = program.init(graph)
    state = jnp.asarray(state)
    aux = jnp.asarray(aux)
    src = jnp.asarray(graph.src)
    dst = jnp.asarray(graph.dst)
    w = jnp.asarray(graph.weights if graph.weights is not None
                    else np.ones(graph.num_edges, np.float32))[:, None]
    max_it = max_iterations or program.max_iterations
    n = graph.num_vertices
    # which vertices receive any message is fixed by the edge list
    has = jnp.asarray(np.bincount(graph.dst, minlength=n) > 0)[:, None]

    # the edge arrays are arguments, not constants captured by the jit:
    # at Graph500 scale they would be baked into the compiled program
    @jax.jit
    def step(state, it, src, dst, w, aux, has):
        msgs = program.msg_gen(state[src], state[dst], w, aux[src])
        agg = program.monoid.segment_reduce(msgs, dst, n)
        agg = jnp.where(has, agg, jnp.full_like(agg, program.monoid.identity))
        return program.msg_apply(state, agg, has, aux, it)

    it = 0
    for it in range(1, max_it + 1):
        state, active = step(state, it, src, dst, w, aux, has)
        if not bool(active.any()):
            break
    return np.asarray(state), it
