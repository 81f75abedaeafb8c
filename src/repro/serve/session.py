"""The mesh-facing half of the serving layer (DESIGN.md §5).

A :class:`GraphServeSession` keeps ONE graph resident on the device mesh
and answers query batches against it:

* per query **family** — (kind, params, batch-size bucket) — it builds
  one fused :class:`~repro.plug.middleware.Middleware` whose compiled
  step is reused across every batch of that family: a batch's seeds /
  restart vectors enter as *data* through ``Middleware.run(init=...)``,
  so serving steady-state traffic never re-jits anything.  Batch sizes
  are bucketed to powers of two (short batches are padded by repeating
  the tail query — duplicate columns are exact under the per-query
  freeze contract), bounding compiled variants at log2(max_batch)+1 per
  family.
* **lookup** queries read a host-resident converged analytics state
  (PageRank scores, WCC component ids), computed once per field on the
  same mesh and then served at memory latency.
* all family middlewares share the session's
  :class:`~repro.dist.fault.FleetMonitor` / failure schedule: a device
  kill observed by one family migrates the others at their own next
  poll (``Middleware._poll_faults`` keys off monitor state, not the
  consumed event), and every migration any run observes is surfaced in
  the batch record so the owner of the result cache can flush the
  affected (non-durable) entries — and ONLY those.
"""
from __future__ import annotations

import time

import numpy as np

from repro.core.pow2 import pow2_bucket
from repro.graph import mutation as graph_mutation
from repro.graph.algorithms import (BATCHED_QUERIES, INF, pagerank, wcc)
from repro.graph.structure import Graph
from repro.plug.middleware import Middleware
from repro.plug.protocols import PlugOptions

#: kinds answered by a batched multi-source program
BATCH_KINDS = tuple(sorted(BATCHED_QUERIES))
#: analytics fields a lookup query may read
LOOKUP_FIELDS = ("pagerank", "wcc")


def _bucket(n: int, max_batch: int) -> int:
    """Smallest power of two ≥ n, capped at max_batch."""
    return pow2_bucket(n, max_batch)


def answer_deps(kind: str, seeds, value):
    """Vertex ids a cached answer depends on — the answer's *support*.

    Scoped mutation invalidation is only sound if an entry's dependency
    set covers every vertex whose mutation could change the answer.
    For the monotone propagate-from-seeds kinds that set is not the
    seed set but the support — the vertices the propagation actually
    reached (finite khop/sssp distance, nonzero ppr mass): an edge
    mutation can only alter the answer if the edge's source already
    carries distance/mass, i.e. sits in the support, and a mutation's
    dirty region always contains both endpoints.  Seeds alone go stale
    the moment an edge is added *downstream* of a reachable vertex.
    ``lookup`` answers read a converged global analytics field
    (PageRank/WCC fixed points), which any mutation anywhere can move —
    their support is the whole graph, returned as ``None`` (the cache's
    global-deps sentinel).
    """
    seeds = np.asarray([int(s) for s in np.atleast_1d(np.asarray(seeds))],
                       dtype=np.int64)
    if kind == "lookup":
        return None
    value = np.asarray(value)
    if kind in ("khop", "sssp"):
        reached = np.flatnonzero(value < INF)
    else:  # ppr and future mass-propagation kinds
        reached = np.flatnonzero(value != 0)
    return np.union1d(reached.astype(np.int64), seeds)


class GraphServeSession:
    """Executes query batches against one resident graph."""

    def __init__(self, graph: Graph, *, num_shards: int = 8,
                 daemon: str = "sharded", upper: str = "mesh",
                 kernel: str = "reference", csr_config=None,
                 max_batch: int = 8,
                 block_size: int | str = "auto",
                 monitor=None, failures=None,
                 analytics_iterations: int = 60):
        if max_batch < 1 or max_batch & (max_batch - 1):
            raise ValueError(f"max_batch must be a power of two, got "
                             f"{max_batch}")
        self.graph = graph
        self.num_shards = num_shards
        self.daemon_name = daemon
        self.upper_name = upper
        self.kernel = kernel
        self.csr_config = csr_config  # pinned CSR kernel config; None tunes
        self.max_batch = int(max_batch)
        self.block_size = block_size
        self.monitor = monitor
        self.failures = failures
        self.analytics_iterations = analytics_iterations
        self.mesh_epoch = 0
        self._families: dict[tuple, dict] = {}
        self._analytics: dict[str, np.ndarray] = {}

    # -- family executors --------------------------------------------------
    def _program_factory(self, kind: str, params: tuple):
        kw = dict(params)
        factory = BATCHED_QUERIES[kind]
        return lambda seeds: factory(self.graph, seeds, **kw)

    def _donor_daemon(self):
        """Any already-bound family daemon — its device-placed block
        tensors are the adoption donor for the next family (one graph,
        one set of block tensors on the mesh; see
        ``ShardedDaemon.share_from``)."""
        for fam in self._families.values():
            dm = fam["mw"].daemon
            if getattr(dm, "_stacked", None) is not None:
                return dm
        return None

    def _make_daemon(self):
        if self.daemon_name != "sharded":
            return self.daemon_name
        from repro.plug.daemons import get_daemon

        d = get_daemon("sharded", kernel=self.kernel,
                       csr_config=self.csr_config)
        donor = self._donor_daemon()
        if donor is not None and hasattr(d, "share_from"):
            d.share_from(donor)
        return d

    def _family(self, kind: str, params: tuple, bucket: int) -> dict:
        key = (kind, params, bucket)
        fam = self._families.get(key)
        if fam is not None:
            return fam
        make = self._program_factory(kind, params)
        program = make([0] * bucket)  # placeholder seeds fix the shapes
        mw = Middleware(
            self.graph, program,
            daemon=self._make_daemon(),
            upper=self.upper_name, model="bsp",
            num_shards=self.num_shards,
            monitor=self.monitor, failures=self.failures,
            options=PlugOptions(block_size=self.block_size))
        fam = {"mw": mw, "make": make, "program": program,
               "durable": program.monoid.idempotent}
        self._families[key] = fam
        return fam

    def execute_batch(self, kind: str, params: tuple, seeds_list,
                      ) -> tuple[list[np.ndarray], dict]:
        """Answers ``len(seeds_list)`` queries of one family in ONE fused
        run.  Returns (answers, record): per query its (N,) state column
        (hop distances / BF distances / PPR scores), and the batch
        record — iterations, wall service time, padding, whether the
        answers are durable across migration, and any migrations the run
        observed (the cache-flush signal).
        """
        if kind == "lookup":
            return self._execute_lookup(params, seeds_list)
        if kind not in BATCHED_QUERIES:
            raise ValueError(f"unknown query kind {kind!r}; known: "
                             f"{BATCH_KINDS + ('lookup',)}")
        b = len(seeds_list)
        if b == 0:
            raise ValueError("empty batch")
        if b > self.max_batch:
            raise ValueError(f"batch of {b} exceeds max_batch="
                             f"{self.max_batch}")
        bucket = _bucket(b, self.max_batch)
        fam = self._family(kind, params, bucket)
        padded = list(seeds_list) + [seeds_list[-1]] * (bucket - b)
        init = fam["make"](padded).init
        t0 = time.perf_counter()
        res = fam["mw"].run(init=init)
        service = time.perf_counter() - t0
        migrations = [r["migration"] for r in res.per_iteration
                      if "migration" in r]
        if migrations:
            self.mesh_epoch += len(migrations)
        answers = [np.asarray(res.state[:, q]) for q in range(b)]
        record = {
            "kind": kind, "batch": b, "bucket": bucket,
            "iterations": res.iterations, "converged": res.converged,
            "service_s": service, "durable": fam["durable"],
            "migrations": migrations, "mesh_epoch": self.mesh_epoch,
        }
        return answers, record

    # -- lookup ------------------------------------------------------------
    def _analytics_state(self, field: str) -> np.ndarray:
        if field not in LOOKUP_FIELDS:
            raise ValueError(f"unknown lookup field {field!r}; known: "
                             f"{LOOKUP_FIELDS}")
        state = self._analytics.get(field)
        if state is None:
            if field == "pagerank":
                g, prog = self.graph, pagerank(self.graph)
            else:
                g = self.graph.with_reverse_edges()
                prog = wcc(g)
            # the wcc graph carries reverse edges, so its block stacks
            # digest differently and adoption safely contributes nothing
            mw = Middleware(
                g, prog,
                daemon=self._make_daemon(),
                upper=self.upper_name, model="bsp",
                num_shards=self.num_shards,
                monitor=self.monitor, failures=self.failures,
                options=PlugOptions(block_size=self.block_size))
            res = mw.run(max_iterations=self.analytics_iterations)
            if any("migration" in r for r in res.per_iteration):
                self.mesh_epoch += 1
            state = np.asarray(res.state[:, 0])
            self._analytics[field] = state
        return state

    def _execute_lookup(self, params: tuple, seeds_list):
        kw = dict(params)
        field = kw.get("field", "pagerank")
        epoch0 = self.mesh_epoch
        t0 = time.perf_counter()
        state = self._analytics_state(field)
        n = state.shape[0]
        answers = [np.asarray([float(state[s % n]) for s in seeds])
                   for seeds in seeds_list]
        service = time.perf_counter() - t0
        # a first-touch analytics run may itself observe a migration;
        # surface it so the router's cache flush still fires
        migrations = ([{"during": f"analytics:{field}"}]
                      if self.mesh_epoch != epoch0 else [])
        record = {
            "kind": "lookup", "batch": len(seeds_list),
            "bucket": len(seeds_list), "iterations": 0, "converged": True,
            "service_s": service, "durable": True, "migrations": migrations,
            "mesh_epoch": self.mesh_epoch,
        }
        return answers, record

    # -- dynamic graphs (DESIGN.md §7) -------------------------------------
    def apply_mutations(self, batch) -> np.ndarray:
        """Applies one mutation batch to the served graph and to every
        compiled family middleware; returns the dirty vertex region
        (touched vertices) the owner of the result cache must
        invalidate.

        The batch lands in the mutation layer's deterministic order, so
        the session graph and each family's independently-mutated
        partitions converge to the same structure — families keep their
        compiled steps' clean shards and recut only dirty blocks (each
        publishes its own ``"mutation"`` structure epoch).  Converged
        analytics states are dropped wholesale: PageRank/WCC are global
        fixed points, recomputed on next lookup.  Batches that add
        vertices are only sound for families whose program factories
        derive every shape from ``init(graph)``.
        """
        if isinstance(batch, graph_mutation.MutationLog):
            batch = batch.freeze()
        batch.validate(self.graph.num_vertices)
        if batch.empty:
            return np.empty(0, np.int64)
        self.graph, dirty = graph_mutation.apply_to_graph(self.graph,
                                                          batch)
        for fam in self._families.values():
            fam["mw"].apply_mutations(batch)
        self._analytics.clear()
        return dirty

    # -- introspection -----------------------------------------------------
    @property
    def compiled_families(self) -> list[tuple]:
        """The (kind, params, bucket) executors built so far."""
        return sorted(self._families)
