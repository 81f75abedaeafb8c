"""The one general traffic generator: it reads a traffic mix's parameters
(``bench/traffic/<name>.json``) and turns them into the runs of a window.

A mix names an ``algorithm`` and its parameters.  ``pagerank`` runs a
fixed number of power iterations from the program's initial state every
run.  ``sssp_bf`` runs Bellman-Ford from ``sources_per_run`` roots per
run.  The window walks a pool of ``pool_size`` root sets, drawn once from
``pool_seed`` without replacement among the vertices with an out-edge
(as Graph500 picks its search keys), in an order drawn from the run's
seed.  A run's iteration count, and with it its time, depends on its
roots; a pool as large as the window's count of runs gives every seed
the same work in another order, so the spread of the time per run is
the system's and not the roots'.  The window closes only at the end of
a walk over the whole pool (``cycle`` runs), so every seed's window holds
the same root sets, and no root set repeats in a window that holds one
walk.

Each algorithm also owns the comparison of a run's answer with the plain
reference (``bench/reference.py``): it returns each number compared
beside its limit, which the mix file states under ``limits``.
"""
from __future__ import annotations

import numpy as np

from bench import reference


class PageRank:
    """``iterations`` power steps with damping ``damping``; tolerance 0,
    so every run takes exactly ``iterations`` steps."""

    def __init__(self, params: dict, graph, seed: int):
        self.params = params
        self.graph = graph
        self.state_width = 1
        self.cycle = 1  # every run is the whole mix

    def program(self):
        from repro.graph.algorithms import pagerank

        p = self.params
        return pagerank(self.graph, damping=p["damping"], tol=0.0,
                        max_iterations=p["iterations"])

    def init(self, i: int):
        return None  # every run starts from the program's initial state

    def check(self, runs, limits: dict):
        """Every run's ranks against the float64 reference (one reference:
        every run computes the same thing)."""
        g, p = self.graph, self.params
        want = reference.pagerank(g.num_vertices, g.src, g.dst,
                                  damping=p["damping"],
                                  iterations=p["iterations"])
        errs = [reference.rank_rel_err(state, want) for _, state, _ in runs]
        iters = [it != p["iterations"] for _, _, it in runs]
        return _judge(limits, {"rank_rel_err": errs, "iter_mismatch": iters})

    def control(self, runs: int, limits: dict):
        """The bfloat16 reference in the program's place."""
        g, p = self.graph, self.params
        got = reference.pagerank(g.num_vertices, g.src, g.dst,
                                 damping=p["damping"],
                                 iterations=p["iterations"], bf16=True)
        return self.check([(i, got, p["iterations"]) for i in range(runs)],
                          limits)


class ShortestPaths:
    """Bellman-Ford from ``sources_per_run`` roots per run."""

    def __init__(self, params: dict, graph, seed: int):
        self.graph = graph
        self.state_width = params["sources_per_run"]
        self.cycle = params["pool_size"]  # runs that walk the pool once
        has_out = np.flatnonzero(np.bincount(graph.src,
                                             minlength=graph.num_vertices))
        rng = np.random.default_rng(params["pool_seed"])
        self._pool = [rng.choice(has_out, self.state_width, replace=False)
                      for _ in range(params["pool_size"])]
        self._order = np.random.default_rng(seed).permutation(
            params["pool_size"])
        self._ref = None

    def sources(self, i: int) -> list[int]:
        """The roots of the window's run ``i``."""
        return [int(r) for r in self._pool[self._order[i % len(self._pool)]]]

    def program(self):
        from repro.graph.algorithms import sssp_bf

        return sssp_bf(self.graph, self.sources(0))

    def init(self, i: int):
        from repro.graph.algorithms import sssp_bf

        return sssp_bf(self.graph, self.sources(i)).init

    def _reference(self):
        if self._ref is None:
            g = self.graph
            self._ref = reference.ShortestPaths(g.num_vertices, g.src, g.dst,
                                                g.weights)
        return self._ref

    def check(self, runs, limits: dict):
        """Every run's distances and iteration count against the float32
        reference, bit for bit."""
        ref = self._reference()
        miss, iters = [], []
        for i, state, it in runs:
            want, want_it = ref.run(self.sources(i))
            miss.append(reference.dist_mismatch(state, want))
            iters.append(it != want_it)
        return _judge(limits, {"dist_mismatch": miss, "iter_mismatch": iters})

    def control(self, runs: int, limits: dict):
        """The bfloat16 reference in the program's place."""
        ref = self._reference()
        got = []
        for i in range(runs):
            dist, it = ref.run(self.sources(i), bf16=True)
            got.append((i, dist, it))
        return self.check(got, limits)


ALGORITHMS = {"pagerank": PageRank, "sssp_bf": ShortestPaths}


def _judge(limits: dict, per_run: dict):
    """Folds per-run readings into ``(checks, failed)``: each number
    compared is its worst run, beside its limit; a run fails when any of
    its readings is over its limit."""
    checks = {}
    for name, vals in per_run.items():
        worst = max(vals)
        checks[name] = {"value": worst if isinstance(worst, float)
                        else int(worst), "limit": limits[name]}
    runs = len(next(iter(per_run.values())))
    failed = sum(any(vals[i] > limits[name] for name, vals in per_run.items())
                 for i in range(runs))
    return checks, failed


def make(params: dict, graph, seed: int):
    """The mix's workload over ``graph`` for ``seed``."""
    return ALGORITHMS[params["algorithm"]](params, graph, seed)
