"""The benchmark's graph generator: Graph500's kernel-0 graph.

The R-MAT draw of the edge tuples copies the one the program's chip runs
were proven on (``repro.graph.generate.rmat_stream``), kept here so a
change to the program cannot move the benchmark's inputs.  Around it,
what the Graph500 specification adds: the vertex labels are permuted at
random, the tuples are shuffled, each tuple carries one weight drawn
uniformly from [0, 1), and the graph is undirected, so each tuple is two
arcs, one each way, with the tuple's weight.

A configuration fixes the whole graph by ``graph_seed``.  The program
compiles its step for the shapes of the structure (tile count, rows per
tile), and its time per iteration follows them, so a graph that moved
with the run's seed would recompile in every run and change the work
from seed to seed.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

#: edges per R-MAT chunk; each chunk draws from its own counter-seeded
#: stream ``(seed, chunk)``, so the graph does not depend on how many
#: threads draw it
RMAT_CHUNK = 1 << 18
#: threads drawing R-MAT chunks (numpy's generators and array ops release
#: the interpreter lock, so the chunks draw in parallel)
RMAT_THREADS = 8
#: the stream, beside the chunks' ``(seed, chunk)``, that draws the label
#: permutation, the tuple order and the weights
SCRAMBLE_STREAM = 1 << 40


def rmat_tuples(scale: int, edgefactor: int, *, a: float, b: float,
                c: float, seed: int):
    """R-MAT edge tuples: ``2**scale`` vertices, ``edgefactor`` tuples per
    vertex, initiator ``(a, b, c, 1-a-b-c)``, drawn exactly as
    ``rmat_stream`` draws its ``src`` and ``dst`` from ``seed``.

    No deduplication: parallel edges and self loops stay, as in
    Graph500's generator.  Returns ``(n, src, dst)``.
    """
    n = 1 << scale
    e = edgefactor * n
    probs = np.array([a, b, c, 1.0 - a - b - c])
    src = np.empty(e, dtype=np.int32)
    dst = np.empty(e, dtype=np.int32)

    def chunk(ci: int) -> None:
        lo = ci * RMAT_CHUNK
        hi = min(lo + RMAT_CHUNK, e)
        rng = np.random.default_rng((seed, ci))
        s = np.zeros(hi - lo, dtype=np.int64)
        d = np.zeros(hi - lo, dtype=np.int64)
        for _ in range(scale):
            quad = rng.choice(4, size=hi - lo, p=probs)
            s = (s << 1) | (quad >> 1)
            d = (d << 1) | (quad & 1)
        src[lo:hi] = s % n
        dst[lo:hi] = d % n

    with ThreadPoolExecutor(RMAT_THREADS) as pool:
        list(pool.map(chunk, range(-(-e // RMAT_CHUNK))))
    return n, src, dst


def graph500(scale: int, edgefactor: int, *, a: float, b: float, c: float,
             seed: int):
    """Graph500's graph from ``seed``: the R-MAT tuples with permuted
    labels, in shuffled order, each with a float32 weight in [0, 1), as
    undirected edges: the arcs of all tuples forward, then of all tuples
    backward.  Returns ``(n, src, dst, w)`` with ``2 * edgefactor * n``
    arcs."""
    n, src, dst = rmat_tuples(scale, edgefactor, a=a, b=b, c=c, seed=seed)
    rng = np.random.default_rng((seed, SCRAMBLE_STREAM))
    label = rng.permutation(n).astype(np.int32)
    order = rng.permutation(src.size)
    src, dst = label[src[order]], label[dst[order]]
    w = rng.random(src.size, dtype=np.float32)
    return (n, np.concatenate([src, dst]), np.concatenate([dst, src]),
            np.concatenate([w, w]))


def make(config: dict):
    """The configuration's graph: ``(n, src, dst, w)``."""
    kind = config["generator"]
    if kind == "graph500":
        ini = config["initiator"]
        return graph500(config["scale"], config["edgefactor"], a=ini["a"],
                        b=ini["b"], c=ini["c"], seed=config["graph_seed"])
    raise ValueError(f"unknown generator {kind!r}")
