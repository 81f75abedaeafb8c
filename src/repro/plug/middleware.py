"""The middleware: agents + drive loops composed from the three protocols.

``Middleware`` owns exactly what the paper's *agent* role owns — per-shard
host state (vertex table replicas, LRU boundary caches, block sets, byte
accounting) and the iteration drive loop — and delegates everything else:

* device compute to the :class:`~repro.plug.protocols.Daemon`
  (``daemon.run_blocks`` per shard per iteration, or one
  ``daemon.run_all_shards`` sharded program for all shards at once),
* partitioning / exchange planning / the global merge to the
  :class:`~repro.plug.protocols.UpperSystem`,
* Gen/Merge/Apply ordering to the
  :class:`~repro.plug.protocols.ComputationModel`.

Three drive loops implement the iteration:

* :class:`HostDriveLoop` — the classic per-shard path: every iteration
  calls each shard's daemon, materializes aggregates on the host,
  runs the candidate apply for skip detection, and the upper system's
  global merge.  Full byte/cache accounting lives here.
* :class:`DriveLoop` — the device-resident fused path, feature-detected
  when the daemon can :meth:`run_all_shards`
  (:class:`~repro.plug.protocols.ShardCapableDaemon`) *and* the upper
  system can :meth:`merge_partials`
  (:class:`~repro.plug.protocols.DevicePartialUpper`) over an exact
  wire: one jitted step per iteration fuses gather + Gen + segmented
  Merge + the cross-device collective + Apply + the convergence check,
  and vertex state never leaves the mesh between iterations.
* :class:`AsyncDriveLoop` — the fused step of the asynchronous priority
  model (:class:`~repro.plug.protocols.PriorityAsyncModel`, e.g.
  ``model="async"``): same capabilities as :class:`DriveLoop`, but the
  step additionally carries the model's scheduling state on the mesh —
  per-device held partials/counts, the frontier backlog accumulated
  while a device holds, and the decaying priority threshold.

Lemma-2 capacity-aware block assignment (paper Sec. III-C) plugs in at
partition time: ``Middleware(capacities=...)`` sizes shards with
``core.balance.lemma2_fractions`` so the mesh axis is makespan-balanced,
and :meth:`Middleware.rebalance` re-runs the assignment from per-shard
busy times observed in the iteration records.

No backend, upper-system, or model names appear below — components are
resolved once in ``__init__`` (strings go through the registries) and
only protocol methods are called afterwards.  The legacy ``GXEngine``
flag surface lives in ``repro.core.engine`` as a deprecation shim over
this class.
"""
from __future__ import annotations

import inspect
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import pipeline as pl
from repro.core.balance import CapacityEstimator, lemma2_fractions
from repro.core.blocks import build_blocks
from repro.core.pow2 import next_pow2
from repro.core.sync import LRUVertexCache, SyncStats, can_skip_sync
from repro.core.template import VertexProgram
from repro.dist import fault as dist_fault
from repro.dist import sharding as shd
from repro.graph import mutation as graph_mutation
from repro.graph.structure import EdgePartition, Graph
from repro.plug import spans
from repro.plug.computation import BSP, GAS, AsyncModel, get_model
from repro.plug.daemons import get_daemon
from repro.plug.epoch import StructureEpoch, StructureEpochBus
from repro.plug.protocols import (DevicePartialUpper, ElasticUpper,
                                  MaskCapableDaemon, OutOfCoreCapable,
                                  PlugOptions, PriorityAsyncModel, Result,
                                  ShardCapableDaemon)
from repro.plug.uppers import get_upper_system

# Computation-model orders the barriered fused loop may realize.  BSP
# and GAS produce identical state trajectories on the same template
# (paper Sec. IV-B2; ``plug.computation`` docstring), so one fused step
# serves both; a priority/async model gets its own fused step
# (AsyncDriveLoop); anything else falls back to the host loop, which
# drives the model's hooks verbatim.
_FUSABLE_ORDERS = {("gen", "merge", "apply"), ("merge", "apply", "gen")}
_MODEL_HOOKS = ("prologue", "aggregates", "epilogue")


def _model_is_fusable(model) -> bool:
    """True iff the model's trajectory is the one the fused step realizes:
    a BSP/GAS order AND the three hooks exactly as BSP or GAS implements
    them — a subclass overriding any hook (delta caching, priority
    scheduling, …) must keep the host loop that calls its hooks."""
    if tuple(getattr(model, "order", ())) not in _FUSABLE_ORDERS:
        return False
    cls = type(model)
    return any(
        all(getattr(cls, h, None) is getattr(base, h) for h in _MODEL_HOOKS)
        for base in (BSP, GAS))


def _async_model_is_fusable(model) -> bool:
    """True iff the model's trajectory is what the fused async step
    realizes: the :class:`~repro.plug.protocols.PriorityAsyncModel`
    scheduling state AND the three hooks exactly as ``AsyncModel``
    implements them — the fused step never calls the hooks, so a
    subclass overriding any of them must keep the host loop that does
    (the same rule :func:`_model_is_fusable` applies to BSP/GAS)."""
    if not isinstance(model, PriorityAsyncModel):
        return False
    cls = type(model)
    return all(getattr(cls, h, None) is getattr(AsyncModel, h)
               for h in _MODEL_HOOKS)


def make_apply_fn(program: VertexProgram):
    batched = (program.num_queries > 0
               and program.query_activity is not None)

    @jax.jit
    def apply_fn(state, merged, has_msg, aux, it):
        # Vertices with no message keep identity-merged values; msg_apply
        # implementations treat identity correctly (min/max) or use has_msg.
        merged = jnp.where(has_msg[:, None], merged,
                           jnp.full_like(merged, program.monoid.identity))
        new, active = program.msg_apply(state, merged, has_msg[:, None],
                                        aux, it)
        if batched:
            # Per-query convergence masking (BatchQueryCapable): a query
            # whose column stack went quiet is FROZEN by reverting its
            # columns and dropped from the shared frontier — finished
            # queries early-exit while batch-mates keep running.  Lives
            # here, in the one apply wrapper every drive loop shares, so
            # host, fused-BSP and fused-async paths all mask identically.
            qact = program.query_activity(state, new)      # (N, B) bool
            q_run = qact.any(axis=0)                       # (B,) still going
            per_q = new.shape[1] // program.num_queries
            colmask = jnp.repeat(q_run, per_q)             # (K,)
            new = jnp.where(colmask[None, :], new, state)
            active = (qact & q_run[None, :]).any(axis=1)
        return new, active

    return apply_fn


class Middleware:
    """Drives a VertexProgram through pluggable components.

    Args:
      graph, program: the workload.
      daemon: accelerator backend — a registry name (``"reference"``,
        ``"pallas"``, ``"sharded"``, ``"blocked"``, ``"pipelined"``,
        ``"naive"``, …) or an unbound Daemon instance.
      upper: upper system — ``"host"`` / ``"mesh"`` or an instance.
      model: computation model — ``"bsp"`` / ``"gas"`` or an instance.
      partitions: explicit edge partitions; defaults to the upper
        system's partitioner over ``num_shards``.
      capacities: per-shard per-entity costs c_j (seconds/entity, any
        positive scale); shard sizes follow Lemma 2 so the slowest
        shard is no longer the makespan (paper Sec. III-C Case 1).
        Ignored when explicit ``partitions`` are given.
      monitor: a :class:`~repro.dist.fault.FleetMonitor` with one slot
        per device of the fused mesh — enables elastic fault tolerance
        (DESIGN.md §4.4): between fused iterations the middleware polls
        the monitor and, on a device failure or a fresh straggler,
        migrates the live run onto a survivor mesh checkpoint-free.
        Requires the fused device-resident loop (``daemon="sharded"``,
        ``upper="mesh"`` with an exact wire).
      failures: a :class:`~repro.dist.fault.FailureSchedule` injecting
        deterministic kills/straggler reports into the monitor ("kill
        device d at iteration k" — the test/bench seam).  Implies a
        monitor (one is created if not given).
      mutations: a :class:`~repro.graph.mutation.MutationSchedule`
        injecting deterministic graph-mutation batches between fused
        iterations ("apply batch b at iteration k") — the dynamic-graph
        counterpart of ``failures``.  Batches land between iterations
        through the same structure-epoch publish a migration uses; the
        run continues incrementally (dirty frontier re-activated) when
        the monoid is idempotent and the batch only adds, else the
        carried state resets (cold restart mid-run).  Needs a fused
        loop; between-run mutations go through :meth:`apply_mutations`.
      options: :class:`~repro.plug.protocols.PlugOptions`.

    Every structure rebuild — kill, join, rebalance, out-of-core
    re-plan, mutation — is published on ``self.epochs`` (a
    :class:`~repro.plug.epoch.StructureEpochBus`); the subscribed hooks
    re-target the upper system's collectives, re-place the daemon's
    block tensors, and restart the capacity windows, in that order.
    Drive loops react to the bus version between iterations and never
    call ``remesh``/``replan`` themselves.
    """

    def __init__(
        self,
        graph: Graph,
        program: VertexProgram,
        *,
        daemon="reference",
        upper="host",
        model="bsp",
        partitions: list[EdgePartition] | None = None,
        num_shards: int = 1,
        capacities=None,
        monitor: "dist_fault.FleetMonitor | None" = None,
        failures: "dist_fault.FailureSchedule | None" = None,
        mutations: "graph_mutation.MutationSchedule | None" = None,
        oocore=None,
        options: PlugOptions | None = None,
    ):
        self.graph = graph
        self.program = program
        self.options = options or PlugOptions()
        self.oocore = oocore  # OocoreConfig | None — out-of-core execution
        self.daemon = get_daemon(daemon) if isinstance(daemon, str) else daemon
        self.upper = (get_upper_system(upper) if isinstance(upper, str)
                      else upper)
        self.model = get_model(model) if isinstance(model, str) else model

        self._owns_partitions = partitions is None
        # the build's phases, kept as (name, start_ns, end_ns, parent)
        # (plug/spans.py); the spans in the daemon's bind land here too
        with spans.recording() as self.build_spans:
            if partitions is None:
                with spans.build_span("plug.build.partition"):
                    if capacities is not None:
                        c = np.asarray(capacities, dtype=np.float64)
                        if c.shape != (num_shards,):
                            raise ValueError(
                                f"capacities must have shape ({num_shards},)"
                                f", got {c.shape}")
                        partitions = self.upper.partition(
                            graph, num_shards, fractions=lemma2_fractions(c))
                    else:
                        partitions = self.upper.partition(graph, num_shards)
            self.partitions = list(partitions)
            self.num_shards = len(self.partitions)
            self.n = graph.num_vertices
            self.k = program.state_width
            with spans.build_span("plug.build.blocks"):
                self._setup_blocks()

            self.daemon.bind(program, self.n)
            self.upper.bind(program, self.num_shards)
            self._apply_fn = make_apply_fn(program)
            self.stats = SyncStats()
            self._caches: list[LRUVertexCache] = []  # created per run
            self._estimator = CapacityEstimator(self.num_shards)
            self._fused_kind = self._detect_fused()
            self._fused = self._fused_kind is not None
            self.oocore_stats: dict = {}
            if self._fused_kind == "oocore":
                self.daemon.bind_super_shards(self.blocksets,
                                              mesh=self.upper.mesh,
                                              axis=self.upper.axis,
                                              config=self.oocore)
            elif self._fused:
                self.daemon.bind_shards(self.blocksets, mesh=self.upper.mesh,
                                        axis=self.upper.axis)
            if self._fused_kind == "bsp":
                with spans.build_span("plug.build.place"):
                    self._place_out_degree()
        self._loop = None

        # -- elastic fault tolerance (DESIGN.md §4.4) ----------------------
        self.monitor = monitor
        self.failures = failures
        self._mesh_device_ids: list[int] = []
        self._handled_stragglers: set[int] = set()
        if monitor is not None or failures is not None:
            if not self._fused:
                raise ValueError(
                    "elastic fault tolerance (monitor=/failures=) needs the "
                    "fused device-resident loop: a shard-capable daemon "
                    "(daemon='sharded') with a device-partial upper system "
                    "over an exact wire (upper='mesh') and a fusable model")
            if not isinstance(self.upper, ElasticUpper):
                raise ValueError(
                    f"upper system {type(self.upper).__name__} cannot "
                    "remesh/migrate (see plug.protocols.ElasticUpper)")
            self.fleet_devices = list(np.asarray(self.upper.mesh.devices,
                                                 dtype=object).reshape(-1))
            m0 = len(self.fleet_devices)
            if self.monitor is None:
                self.monitor = dist_fault.FleetMonitor(num_hosts=m0,
                                                       model_parallel=1)
            if self.monitor.num_hosts != m0:
                raise ValueError(
                    f"monitor tracks {self.monitor.num_hosts} hosts but the "
                    f"fused mesh has {m0} devices — one monitor slot per "
                    "mesh device")
            self._mesh_device_ids = list(range(m0))
            # the initial placement acknowledges whatever the monitor
            # already knows; straggler migrations then key off drift
            # relative to this baseline
            self.monitor.ack_capacity()

        # -- dynamic graphs (DESIGN.md §7) ---------------------------------
        self.mutations = mutations
        if mutations is not None and not self._fused:
            raise ValueError(
                "a mid-run MutationSchedule needs a fused device-resident "
                "loop (the host loop re-reads the graph every iteration "
                "and never polls for due batches); apply batches between "
                "runs with apply_mutations() instead")
        self.last_restart: dict | None = None
        self._last_state: np.ndarray | None = None

        # -- the structure-epoch layer (plug/epoch.py) ---------------------
        # Every rebuild trigger publishes here; the hooks run in this
        # order (collective mesh first, block tensors second, capacity
        # windows last) — the chain migrate()/rebalance() used to
        # hand-code, now shared by all five causes.
        self.epochs = StructureEpochBus()
        self.epochs.subscribe("upper", self._epoch_upper)
        self.epochs.subscribe("daemon", self._epoch_daemon)
        self.epochs.subscribe("capacity", self._epoch_capacity)
        self.epochs.initialize(StructureEpoch(
            version=0, cause="init",
            mesh=self.upper.mesh if self._fused else None,
            partitions=tuple(self.partitions),
            blocksets=tuple(self.blocksets),
            oocore_plan=(self.daemon.oocore_plan
                         if self._fused_kind == "oocore" else None)))

    # -- structure-epoch rebuild hooks -------------------------------------
    def _epoch_upper(self, new: StructureEpoch, old) -> None:
        """Re-targets the upper system at the epoch's mesh (fused) or
        re-binds it for the new shard layout (host path)."""
        if self._fused:
            self.upper.remesh(new.mesh)
        else:
            self.upper.bind(self.program, self.num_shards)

    def _epoch_daemon(self, new: StructureEpoch, old) -> None:
        """Re-places the daemon's block tensors for the epoch.  In
        out-of-core mode the daemon's re-plan fills ``new.oocore_plan``
        — the plan is an output of the rebuild, not an input to it.  On
        the host path there is nothing to re-place (blocks upload per
        iteration); stale per-blockset caches are pruned instead."""
        if self._fused:
            cfg = new.meta.get("oocore_config")
            if cfg is not None:
                # explicit re-plan under a NEW budget (oocore_replan());
                # remesh would re-bind under the old stored config
                self.daemon.bind_super_shards(
                    list(new.blocksets), mesh=new.mesh,
                    axis=self.upper.axis, config=cfg)
            else:
                self.daemon.remesh(new.mesh, blocksets=list(new.blocksets))
            if self._fused_kind == "oocore":
                new.oocore_plan = self.daemon.oocore_plan
            if self._fused_kind == "bsp":
                self._place_out_degree()
        else:
            prune = getattr(self.daemon, "prune_block_caches", None)
            if prune is not None:
                prune(new.blocksets)

    def _epoch_capacity(self, new: StructureEpoch, old) -> None:
        """Restarts capacity estimation under the new epoch: per-shard
        costs measured against the old structure say nothing about the
        new one (different shards per device, different tile counts), so
        the estimator is replaced and the fleet monitor's step-time
        windows are re-keyed (``FleetMonitor.on_epoch`` snapshots the
        acked baseline before dropping the samples)."""
        self._estimator = CapacityEstimator(self.num_shards,
                                            epoch=new.version)
        if self.monitor is not None:
            self.monitor.on_epoch(new.version)

    # -- setup ------------------------------------------------------------
    def _resolve_block_size(self) -> int:
        o = self.options
        if o.block_size == "auto":
            d = max(1, max(p.num_edges for p in self.partitions))
            best_b, _ = pl.optimal_integer_blocks(d, o.k1, o.k2, o.k3, o.a)
            return int(min(max(best_b, 64), 1 << 16))
        return int(o.block_size)

    def _place_out_degree(self) -> None:
        """Places the live arcs' (N,) int32 out-degree, replicated on the
        daemon's mesh: the fused BSP step's dot of it with the frontier
        is each iteration record's ``edges_active``."""
        deg = sum(np.bincount(p.src, minlength=self.n)
                  for p in self.partitions)
        self.out_degree = jax.device_put(
            np.asarray(deg, np.int32),
            jax.sharding.NamedSharding(self.daemon.mesh,
                                       jax.sharding.PartitionSpec()))

    def _setup_blocks(self) -> None:
        b = self._resolve_block_size()
        self.block_size = b
        self.blocksets = [build_blocks(p, b) for p in self.partitions]
        # One vertex-block width for all shards → one compiled daemon program.
        vb = max(bs.vblock_size for bs in self.blocksets)
        self.blocksets = [build_blocks(p, b, vblock_size=vb)
                          for p in self.partitions]
        self.vblock_size = vb

    def _detect_fused(self) -> str | None:
        """Which fused device-resident loop (if any) this composition
        gets.  Both need a shard-capable daemon and an upper system that
        merges device partials over an exact wire; the model then picks
        the step: BSP/GAS orders share one barriered step (``"bsp"`` —
        identical trajectories), a priority/async model
        (:class:`~repro.plug.protocols.PriorityAsyncModel`) gets the
        staleness-carrying async step (``"async"``), anything else
        returns None and keeps the host loop that drives its hooks
        verbatim."""
        caps = (isinstance(self.daemon, ShardCapableDaemon)
                and isinstance(self.upper, DevicePartialUpper)
                and getattr(self.upper, "wire", "exact") == "exact")
        if self.oocore is not None:
            # out-of-core is opt-in and never silently falls back: a
            # composition that can't stream super-shards is a config
            # error, not a reason to run all-resident anyway
            if not caps:
                raise ValueError(
                    "oocore= needs the fused device-resident loop: a "
                    "shard-capable daemon (daemon='sharded') with a "
                    "device-partial upper system over an exact wire "
                    "(upper='mesh')")
            if not isinstance(self.daemon, OutOfCoreCapable):
                raise ValueError(
                    f"daemon {type(self.daemon).__name__} cannot bind "
                    "super-shards (see plug.protocols.OutOfCoreCapable)")
            if not _model_is_fusable(self.model):
                raise ValueError(
                    "oocore= supports the barriered BSP/GAS step only — "
                    "the async model's held partials assume the full "
                    "column range is resident every iteration")
            return "oocore"
        if not caps:
            return None
        if _model_is_fusable(self.model):
            return "bsp"
        # The async step additionally needs the upper system's async
        # merge cadence — DevicePartialUpper alone doesn't promise it,
        # and a miss must fall back, not crash.
        if (_async_model_is_fusable(self.model)
                and callable(getattr(self.upper, "merge_partials_async",
                                     None))):
            return "async"
        return None

    # -- the drive loop ---------------------------------------------------
    def run(self, max_iterations: int | None = None, *,
            init=None, frontier=None) -> Result:
        """Drives the program to convergence.

        ``init`` overrides ``program.init`` for this run only — the
        serving layer's seam: one compiled middleware per query family
        is reused across batches whose seeds/restart vectors enter as
        *data* (``init(graph) -> (state0, aux)``, same shapes), so no
        step is ever re-jitted per request batch.

        ``frontier`` overrides the initial active mask (default: every
        vertex) — the incremental-restart seam: :meth:`run_dynamic`
        resumes from the previous fixed point with only the mutation's
        dirty frontier active.
        """
        # Fresh per-run accounting: stats and LRU caches reset at loop
        # entry (regression: a second run() on the same instance reported
        # inflated cache/byte/round counters).
        self.stats = SyncStats()
        self._caches = [
            LRUVertexCache(self.options.cache_capacity)
            for _ in range(self.num_shards)
        ]
        self.oocore_stats = {}
        if self._loop is None:
            loops = {"bsp": DriveLoop, "async": AsyncDriveLoop,
                     "oocore": OocoreDriveLoop, None: HostDriveLoop}
            self._loop = loops[self._fused_kind](self)
        res = self._loop.run(max_iterations, init=init, frontier=frontier)
        # the previous fixed point the next run_dynamic() may resume from
        self._last_state = np.asarray(res.state)
        return res

    def compile_step(self):
        """Compiles the fused BSP step for the current structure ahead of
        the first :meth:`run`, which then reuses it, and returns the
        compiled program (``as_text()``, ``memory_analysis()``)."""
        if self._fused_kind != "bsp":
            raise ValueError(
                "compile_step needs the fused BSP drive loop; this "
                f"composition runs {self._fused_kind or 'the host loop'}")
        if self._loop is None:
            self._loop = DriveLoop(self)
        return self._loop.lower().compile()

    # -- between-iteration structure polling -------------------------------
    def _poll_structure(self, it: int) -> dict:
        """The between-iteration poll of the fused drive loops: feeds
        due failure-schedule events and due mutation batches through
        their structure-epoch publishers.  Returns the extra entries for
        the iteration record ({} when nothing fired) — the loop reacts
        to the bus *version*, never to this dict, so externally
        triggered publishes (a direct ``migrate()`` call from another
        middleware sharing the monitor) are adopted identically."""
        out: dict = {}
        if self.monitor is not None:
            mig = self._poll_faults(it)
            if mig is not None:
                out["migration"] = mig
        mut = self._poll_mutations(it)
        if mut is not None:
            out["mutation"] = mut
        return out

    def _poll_mutations(self, it: int) -> dict | None:
        """Applies the mutation batches due at iteration ``it``.  Each
        batch publishes its own epoch; when several are due at once the
        final epoch's meta is widened (frontier union, incremental AND)
        so the loop's single adoption of the latest version loses
        nothing."""
        if self.mutations is None:
            return None
        due = self.mutations.due_at(it)
        if not due:
            return None
        t0 = time.perf_counter()
        eps = [self.apply_mutations(b) for b in due]
        # an all-empty batch publishes nothing and returns the current
        # epoch, whose meta carries no frontier — drop it
        eps = [e for e in eps if e.meta.get("frontier") is not None]
        if not eps:
            return None
        ep = eps[-1]
        for e in eps[:-1]:
            ep.meta["frontier"] = ep.meta["frontier"] | e.meta["frontier"]
            ep.meta["incremental"] = (ep.meta["incremental"]
                                      and e.meta["incremental"])
        return {
            "batches": len(due),
            "edges_added": sum(e.meta["edges_added"] for e in eps),
            "edges_removed": sum(e.meta["edges_removed"] for e in eps),
            "dirty_vertices": int(sum(e.meta["dirty_count"] for e in eps)),
            "incremental": bool(ep.meta["incremental"]),
            "seconds": time.perf_counter() - t0,
        }

    # -- elastic fault tolerance ------------------------------------------
    def _poll_faults(self, it: int) -> dict | None:
        """The between-iteration elastic check of the fused drive loops.

        Feeds the failure schedule's due events into the monitor
        (injected step-time reports, then kills), and migrates when a
        dead device sits in the active mesh, a straggler is flagged for
        the first time, or an already-handled straggler's capacity has
        kept drifting past the monitor's threshold since the placement
        last acknowledged it (``FleetMonitor.ack_capacity``) — straggler
        handling is continuous, not once-per-device.  Returns the
        migration record for the iteration log, or None when the fleet
        is healthy.
        """
        mon = self.monitor
        if mon is None:
            return None
        newly: list[int] = []
        rejoined: list[int] = []
        if self.failures is not None:
            for dev, seconds in self.failures.slow_reports(it):
                if not mon.failed[dev]:
                    mon.record(dev, seconds)
            for dev in self.failures.recoveries_at(it):
                if mon.failed[dev]:
                    mon.mark_recovered(dev)
                    rejoined.append(dev)
            for dev in self.failures.kills_at(it):
                if not mon.failed[dev]:
                    mon.mark_failed(dev)
                    newly.append(dev)
        failed = mon.failed
        if any(failed[d] for d in self._mesh_device_ids):
            return self.migrate(killed=newly, joined=rejoined)
        if self._feasible_mesh_size() > len(self._mesh_device_ids):
            # elastic JOIN: recovered devices let the mesh grow back —
            # the same checkpoint-free migration, planned from the
            # enlarged survivor set (migrate() is direction-agnostic).
            # Keyed off the monitor's fleet view, not the consumed
            # recovery event, so every middleware sharing this monitor
            # (the serving layer runs one per query family) grows at its
            # own next poll even though another one drained the event.
            return self.migrate(joined=rejoined)
        if self._owns_partitions:
            # like the failure branch: only stragglers that actually
            # carry shards (sit in the active mesh) warrant a migration
            flagged = [int(d) for d in np.nonzero(mon.stragglers())[0]
                       if int(d) in self._mesh_device_ids]
            fresh = [d for d in flagged
                     if d not in self._handled_stragglers]
            # a straggler seen before still warrants a migration when
            # its capacity kept degrading after the placement that
            # absorbed it — drift vs the acked baseline, not a
            # fire-once flag, is what tracks that
            if fresh or (flagged and mon.drifted()):
                self._handled_stragglers.update(fresh)
                return self.migrate(stragglers=fresh or flagged)
        return None

    def _feasible_mesh_size(self) -> int:
        """Largest mesh-axis length the surviving fleet can host: the
        largest divisor of ``num_shards`` ≤ the number of alive devices.
        Shrink and grow are the same computation — only ``alive``
        moves."""
        alive = int(self.monitor.alive_hosts)
        for d in range(min(self.num_shards, alive), 0, -1):
            if self.num_shards % d == 0:
                return d
        return 1

    def migrate(self, *, killed=(), stragglers=(), joined=()) -> dict:
        """Checkpoint-free elastic migration onto the survivor mesh.

        Re-plans the shard placement from the monitor's view of the
        fleet and re-targets the fused composition:

        1. the new mesh-axis length m' is the largest divisor of
           ``num_shards`` the survivors can host, and the m' devices
           with the highest Lemma-2 capacity are kept;
        2. every shard — in particular the orphaned shards of dead
           devices — is reassigned to a survivor with
           :func:`repro.dist.fault.reassign_shards` (Lemma-2
           entitlement, ``cap = num_shards // m'`` so the stacked
           layout stays rectangular);
        3. with capacity data (straggler/step-time reports), the graph
           is re-partitioned so each device's shard slots carry edges
           in proportion to its Lemma-2 fraction; without data — or on
           caller-supplied partitions — the existing partitions are
           kept and merely re-ordered onto their new devices
           (bit-identical block math, different placement);
        4. the rebuild is *published* as a structure epoch (cause
           ``"kill"``/``"join"``/``"rebalance"``): the subscribed hooks
           re-target the upper system's collectives
           (:meth:`~repro.plug.uppers.MeshUpperSystem.remesh`),
           re-stack the daemon's block tensors
           (:meth:`~repro.plug.daemons.ShardedDaemon.remesh`), and
           restart capacity estimation under the new epoch — stale
           costs, possibly measured on now-dead devices, must not leak
           into a later :meth:`rebalance`.

        The fused drive loop notices the epoch version change at its
        next between-iteration poll, ``device_put``s the carried vertex
        state onto the survivor mesh, and rebuilds its jitted step for
        the new axis size — no checkpoint is ever restored.  Also
        callable directly after ``monitor.mark_failed(...)`` for
        externally detected failures.
        """
        t0 = time.perf_counter()
        mon = self.monitor
        if mon is None:
            raise ValueError("migrate() needs a Middleware(monitor=...)")
        alive = [int(d) for d in mon.alive_indices()]
        if not alive:
            raise ValueError("no surviving devices to migrate onto")
        m_new = self._feasible_mesh_size()
        frac_fleet = mon.batch_fractions()  # dead hosts are exactly 0
        order = sorted(alive, key=lambda d: (-frac_fleet[d], d))
        chosen = sorted(order[:m_new])
        frac = np.asarray(frac_fleet[chosen], dtype=np.float64)
        frac = (np.full(m_new, 1.0 / m_new) if frac.sum() <= 0
                else frac / frac.sum())
        cap = self.num_shards // m_new
        assign = dist_fault.reassign_shards(self.num_shards, frac, cap=cap)
        perm = np.argsort(assign, kind="stable")  # device-major slot order
        m_old = len(self._mesh_device_ids)
        cap_old = self.num_shards // max(1, m_old)
        repartitioned = self._owns_partitions and mon.observed
        if repartitioned:
            # capacity-aware re-partition: device chosen[i] holds `cap`
            # slots, each sized frac[i]/cap of the edges (Lemma 2)
            slot_frac = np.repeat(frac / cap, cap)
            self.partitions = list(self.upper.partition(
                self.graph, self.num_shards, fractions=slot_frac))
            self._setup_blocks()
            dirty = None  # arbitrary edges changed shards: no vertex clean
        else:
            # Pure re-placement.  The dirty region is exact: a vertex's
            # merged value depends only on the device *grouping* of the
            # shards holding its in-edges, so when the axis length is
            # unchanged only the destinations of shards that moved device
            # are affected; a changed axis length re-reduces everything.
            if m_new != m_old:
                dirty = None
            else:
                moved = [int(perm[s]) for s in range(self.num_shards)
                         if (self._mesh_device_ids[int(perm[s]) // cap_old]
                             != chosen[s // cap])]
                dirty = (np.empty(0, np.int64) if not moved
                         else np.unique(np.concatenate(
                             [self.partitions[j].dst for j in moved]
                         ).astype(np.int64)))
            self.partitions = [self.partitions[int(i)] for i in perm]
            # reorder, don't rebuild: build_blocks is deterministic per
            # partition and the pinned block/vblock sizes are maxima over
            # the same (reordered) set — bit-identical blocks, and the
            # preserved BlockSet identities keep the daemon's host-side
            # tile caches warm across the migration
            self.blocksets = [self.blocksets[int(i)] for i in perm]
        mesh = shd.make_mesh((len(chosen),), (self.upper.axis,),
                             devices=[self.fleet_devices[d] for d in chosen])
        before, self._mesh_device_ids = self._mesh_device_ids, list(chosen)
        record = {
            "killed": [int(d) for d in killed],
            "stragglers": [int(d) for d in stragglers],
            "joined": [int(d) for d in joined],
            "devices_before": len(before),
            "devices_after": m_new,
            "device_ids": [int(d) for d in chosen],
            "assignment": [int(a) for a in assign],
            "repartitioned": bool(repartitioned),
            "dirty_vertices": (None if dirty is None
                               else [int(v) for v in dirty]),
        }
        cause = ("kill" if killed
                 else "join" if (joined or m_new > m_old) else "rebalance")
        self.epochs.publish(cause, mesh=mesh, partitions=self.partitions,
                            blocksets=self.blocksets, dirty_vertices=dirty,
                            meta=record)
        record["seconds"] = time.perf_counter() - t0
        return record

    # -- Lemma-2 rebalancing ----------------------------------------------
    def rebalance(self, capacities=None) -> np.ndarray:
        """Capacity-aware re-assignment of blocks to shards (Lemma 2).

        Uses explicit per-entity costs when given; otherwise the costs
        the :class:`~repro.core.balance.CapacityEstimator` learned from
        per-shard busy times in the iteration records (the host loop
        feeds it ``shard_busy_s`` / ``shard_entities`` every iteration).
        Re-partitions the graph with ``lemma2_fractions``, rebuilds the
        block sets, re-places the sharded daemon's block tensors, and
        returns the fractions used.

        The fused drive loop runs every shard inside one device program,
        so it observes no per-shard busy times — rebalancing a
        fused-only middleware requires explicit ``capacities`` (raises
        otherwise rather than silently re-partitioning uniformly).
        Likewise, a middleware built on caller-supplied ``partitions``
        refuses to rebalance: re-partitioning would silently replace the
        caller's partitioning strategy with the upper system's default.
        """
        if not self._owns_partitions:
            raise ValueError(
                "rebalance() would replace the explicit partitions this "
                "Middleware was constructed with by the upper system's "
                "default partitioner; construct without partitions= (or "
                "with capacities=) to let the middleware own the "
                "assignment")
        if capacities is not None:
            c = np.asarray(capacities, dtype=np.float64)
            if c.shape != (self.num_shards,):
                raise ValueError(
                    f"capacities must have shape ({self.num_shards},), got "
                    f"{c.shape}")
        elif self._estimator.observed:
            c = self._estimator.costs
        elif self.monitor is not None and self.monitor.observed:
            # Fused loops observe no per-shard busy times; the fleet
            # monitor's per-device step times stand in.  Costs index the
            # CURRENT mesh devices only — dead devices are never in the
            # mesh, so their samples (cleared by mark_failed anyway)
            # cannot mix into survivor capacities.
            t = self.monitor.mean_times()[self._mesh_device_ids]
            fill = np.nanmean(t) if np.any(np.isfinite(t)) else 1.0
            t = np.where(np.isfinite(t), t, fill)
            c = np.repeat(t, self.num_shards // len(self._mesh_device_ids))
        else:
            raise ValueError(
                "rebalance() has no observed per-shard busy times (the "
                "fused drive loop times all shards as one program) — pass "
                "capacities= explicitly, attach a reporting "
                "FleetMonitor, or run the host path first")
        fractions = lemma2_fractions(c)
        self.partitions = list(self.upper.partition(
            self.graph, self.num_shards, fractions=fractions))
        self._setup_blocks()
        self.epochs.publish(
            "rebalance",
            mesh=self.upper.mesh if self._fused else None,
            partitions=self.partitions, blocksets=self.blocksets,
            dirty_vertices=None,  # edges changed shards arbitrarily
            meta={"fractions": [float(f) for f in fractions]})
        return fractions

    # -- out-of-core re-planning -------------------------------------------
    def oocore_replan(self, config=None) -> StructureEpoch:
        """Re-plans super-shard ownership at runtime — the out-of-core
        structure trigger (cause ``"oocore_replan"``).

        ``config`` replaces the composition's ``OocoreConfig`` (a
        shrunken HBM budget mid-deployment, a changed hot fraction);
        omitted, the current config is re-planned as-is (useful after an
        external change to what else occupies the device).  The daemon
        hook recuts the hot set and the cold super-shards under the new
        budget and fills the published epoch's ``oocore_plan``; the
        fused loop recompiles at its next run/poll.  The streaming cut
        never changes merged values for idempotent monoids, but a sum
        accumulates super-shards in plan order — so like every
        placement change the epoch is published with
        ``dirty_vertices=None`` and volatile serve-cache entries cannot
        survive it.
        """
        if self._fused_kind != "oocore":
            raise ValueError(
                "oocore_replan() needs an out-of-core composition "
                "(Middleware(oocore=OocoreConfig(...)))")
        t0 = time.perf_counter()
        if config is not None:
            self.oocore = config
        before = self.daemon.oocore_plan
        ep = self.epochs.publish(
            "oocore_replan", mesh=self.upper.mesh,
            partitions=self.partitions, blocksets=self.blocksets,
            dirty_vertices=None,
            meta={"oocore_config": self.oocore,
                  "super_shards_before": int(before.num_super_shards),
                  "hot_cols_before": int(before.hot_cols)})
        ep.meta["super_shards_after"] = int(ep.oocore_plan.num_super_shards)
        ep.meta["hot_cols_after"] = int(ep.oocore_plan.hot_cols)
        ep.meta["seconds"] = time.perf_counter() - t0
        return ep

    # -- dynamic graphs (DESIGN.md §7) -------------------------------------
    def _rebuild_dirty_blocksets(self, dirty_shards) -> list[int]:
        """Recuts blocks for exactly the shards a mutation touched.

        Clean shards keep their BlockSet *objects* (the mutation layer
        reuses their edge arrays by reference, so the packed blocks are
        still exact) — preserved identity is what keeps the daemons'
        per-blockset tile/CSR caches warm.  Block and vertex-block sizes
        stay pinned so one compiled program keeps serving every shard; a
        dirty shard that outgrows the pinned vertex-block width forces a
        full recut of all shards (returned list says which were recut).
        """
        dirty_shards = [int(j) for j in dirty_shards]
        new_sets = list(self.blocksets)
        try:
            for j in dirty_shards:
                new_sets[j] = build_blocks(self.partitions[j],
                                           self.block_size,
                                           vblock_size=self.vblock_size)
        except ValueError:
            self._setup_blocks()
            return list(range(self.num_shards))
        self.blocksets = new_sets
        return dirty_shards

    def apply_mutations(self, batch) -> StructureEpoch:
        """Applies one batched graph mutation and publishes a
        ``"mutation"`` structure epoch.

        The batch (a :class:`~repro.graph.mutation.MutationBatch`, or a
        :class:`~repro.graph.mutation.MutationLog` which is frozen
        first) lands in deterministic order, so every middleware holding
        the same graph that applies the same log converges to the same
        structure bit-identically.  Only dirty shards' blocks are recut
        (clean tiles untouched); vertex additions re-bind the compiled
        per-vertex programs.  The returned epoch's ``meta`` carries the
        dirty frontier (touched vertices + their out-neighbours) and
        whether an *incremental* restart from the previous fixed point
        is sound — idempotent monoid and no removals; deletions break
        monotonicity even under min/max, and sum re-counts everything —
        which :meth:`run_dynamic` consumes.
        """
        if isinstance(batch, graph_mutation.MutationLog):
            batch = batch.freeze()
        batch.validate(self.n)
        if batch.empty:
            return self.epochs.epoch
        t0 = time.perf_counter()
        n_old = self.n
        (self.graph, self.partitions, dirty_shards,
         dirty) = graph_mutation.apply_to_partitions(
             self.graph, self.partitions, batch)
        self.n = self.graph.num_vertices
        recut = self._rebuild_dirty_blocksets(dirty_shards)
        if self.n != n_old:
            # per-vertex shapes changed: the compiled daemon/upper
            # programs must re-bind.  Programs whose closures captured
            # the old N (pagerank's (1-d)/n) must be rebuilt by the
            # caller — algorithms deriving everything from init(graph)
            # (sssp, wcc, bfs) work unchanged.
            self.daemon.bind(self.program, self.n)
            self.upper.bind(self.program, self.num_shards)
        incremental = (self.program.monoid.idempotent
                       and not batch.has_removals)
        meta = {
            "incremental": bool(incremental),
            "frontier": graph_mutation.dirty_frontier(self.graph, dirty),
            "edges_added": int(batch.num_added_edges),
            "edges_removed": int(batch.num_removed_edges),
            "vertices_added": int(batch.add_vertices),
            "vertices_removed": int(batch.remove_vertices.size),
            "dirty_count": int(dirty.size),
            "shards_recut": len(recut),
            "shards_clean": self.num_shards - len(recut),
        }
        ep = self.epochs.publish(
            "mutation",
            mesh=self.upper.mesh if self._fused else None,
            partitions=self.partitions, blocksets=self.blocksets,
            dirty_vertices=dirty, meta=meta)
        ep.meta["seconds"] = time.perf_counter() - t0
        return ep

    def run_dynamic(self, batch, *, max_iterations: int | None = None
                    ) -> Result:
        """Applies ``batch`` and restarts the program on the mutated
        graph — incrementally when that is sound, cold otherwise.

        Incremental restart resumes from the previous run's fixed point
        with only the dirty frontier active: for an idempotent monoid
        and an add-only batch the old fixed point is a valid
        intermediate of the new computation (min/max only ever improve
        along the added edges), so convergence from it is exact — and
        bit-identical to a cold restart, in far fewer iterations for
        small batches.  Removals or a non-idempotent monoid fall back to
        a cold restart; ``self.last_restart`` records the mode and why.
        """
        prev = self._last_state
        ep = self.apply_mutations(batch)
        meta = ep.meta if ep.cause == "mutation" else {}
        incremental = bool(meta.get("incremental")) and prev is not None
        if incremental:
            if prev.shape[0] < self.n:
                # added vertex ids start at the program's initial state
                state0, _ = self.program.init(self.graph)
                prev = np.concatenate([prev, state0[prev.shape[0]:]],
                                      axis=0)
            prev_state = np.asarray(prev)

            def init(g, _s=prev_state, _i=self.program.init):
                return _s, _i(g)[1]

            res = self.run(max_iterations, init=init,
                           frontier=meta["frontier"])
            mode = "dirty"
        else:
            res = self.run(max_iterations)
            mode = ("cold_fallback"
                    if meta and prev is not None and not meta.get(
                        "incremental") else "cold")
        if incremental:
            reason = ""
        elif prev is None:
            reason = "no previous fixed point"
        elif not self.program.monoid.idempotent:
            reason = "non-idempotent monoid"
        else:
            reason = "batch removes edges/vertices"
        self.last_restart = {
            "mode": mode,
            "incremental": bool(incremental),
            "reason": reason,
            "dirty_count": int(meta.get("dirty_count", 0)),
            "iterations": int(res.iterations),
        }
        return res


class HostDriveLoop:
    """The per-shard host path: exact legacy ``Middleware.run`` semantics.

    Aggregates round-trip through the host every iteration; in exchange
    this loop carries the paper's full inter-iteration machinery — LRU
    boundary caches, lazy-upload byte accounting, candidate apply +
    synchronization skipping — plus per-shard busy-time records feeding
    the Lemma-2 capacity estimator.
    """

    def __init__(self, mw: Middleware):
        self.mw = mw
        # active-set size buckets already compiled (shared across shards:
        # one block_fn serves them all) — first sight of a bucket pays the
        # XLA compile inside the busy-time window and must not reach the
        # capacity estimator
        self._seen_buckets: set[int] = set()

    # -- one shard's Gen + per-block Merge ---------------------------------
    def _shard_aggregate(self, j: int, state_j: np.ndarray, aux: np.ndarray,
                         active_j: np.ndarray | None, record: dict):
        """Agent work for shard j → (N,K) aggregate, (N,) counts, and the
        boundary read ids of the blocks that ran (the exchange's query
        set)."""
        mw = self.mw
        bs = mw.blocksets[j]
        o = mw.options
        if (mw.program.frontier_driven and o.frontier_block_skipping
                and active_j is not None):
            blk_active = np.any(active_j[bs.gsrc] & bs.emask, axis=1)
            sel = np.nonzero(blk_active)[0]
        else:
            sel = np.arange(bs.num_blocks)
        record["blocks_total"] = record.get("blocks_total", 0) + bs.num_blocks
        record["blocks_run"] = record.get("blocks_run", 0) + int(sel.size)
        if sel.size == 0:
            agg = np.full((mw.n, mw.k), mw.program.monoid.identity,
                          np.float32)
            return agg, np.zeros(mw.n, np.int32), np.empty(0, np.int64)

        # LRU cache accounting for boundary reads (Sec. III-B2).
        read_ids = np.unique(bs.gsrc[sel][bs.emask[sel]])
        boundary_reads = read_ids[mw.partitions[j].boundary_mask[read_ids]]
        rowbytes = 4 * mw.k + 8
        if o.sync_caching:
            cache = mw._caches[j]
            hit = cache.lookup(boundary_reads.astype(np.int64))
            cache.insert(boundary_reads[~hit].astype(np.int64))
            mw.stats.cache_hits += int(hit.sum())
            mw.stats.cache_misses += int((~hit).sum())
            mw.stats.download_bytes_cache += int((~hit).sum()) * rowbytes
        mw.stats.download_bytes_nocache += int(boundary_reads.size) * rowbytes

        bucket = next_pow2(int(sel.size))
        compiling = bucket not in self._seen_buckets
        self._seen_buckets.add(bucket)
        t_busy = time.perf_counter()
        agg, cnt = mw.daemon.run_blocks(state_j, aux, bs, sel, record)
        agg, cnt = np.asarray(agg), np.asarray(cnt)
        busy = time.perf_counter() - t_busy
        entities = int(sel.size) * bs.block_size
        shards = mw.num_shards
        record.setdefault("shard_busy_s", [0.0] * shards)[j] += busy
        record.setdefault("shard_entities", [0] * shards)[j] += entities
        # Fed here, not from the record at iteration end (GAS gathers in
        # prologue/epilogue, where the consuming record differs) — and
        # only for steady-state buckets: a first-seen padded size pays
        # one-off XLA compilation inside the window, which would inflate
        # this shard's EMA'd cost by orders of magnitude.
        if not compiling:
            mw._estimator.update(j, entities, busy)
        return agg, cnt, boundary_reads.astype(np.int64)

    def run(self, max_iterations: int | None = None, *,
            init=None, frontier=None) -> Result:
        mw = self.mw
        prog = mw.program
        o = mw.options
        mw.upper.reset()
        max_it = max_iterations or prog.max_iterations
        state0, aux = (init or prog.init)(mw.graph)
        states = [state0.copy() for _ in range(mw.num_shards)]
        active0 = (np.ones(mw.n, dtype=bool) if frontier is None
                   else np.asarray(frontier, dtype=bool))
        actives = [active0.copy() for _ in range(mw.num_shards)]
        skip_ok = o.sync_skipping and prog.supports_sync_skipping()
        per_iter: list[dict] = []
        rowbytes = 4 * mw.k + 8
        t0 = time.perf_counter()
        it = 0
        converged = False

        def gather(rec: dict):
            return [
                self._shard_aggregate(j, states[j], aux, actives[j], rec)
                for j in range(mw.num_shards)
            ]

        pending = mw.model.prologue(gather)

        for it in range(1, max_it + 1):
            rec: dict = {"iteration": it}
            for c in mw._caches:
                c.tick()
            results = mw.model.aggregates(gather, pending, rec)
            pending = None

            aggs = [r[0] for r in results]
            cnts = [r[1] for r in results]
            reads = [r[2] for r in results]

            # Local candidate apply (needed for skip detection).
            new_states, new_actives, updated_ids = [], [], []
            for j in range(mw.num_shards):
                ns, act = mw._apply_fn(
                    jnp.asarray(states[j]), jnp.asarray(aggs[j]),
                    jnp.asarray(cnts[j] > 0), jnp.asarray(aux), it)
                ns, act = np.asarray(ns), np.asarray(act)
                new_states.append(ns)
                new_actives.append(act)
                updated_ids.append(np.nonzero(act)[0])

            boundary_masks = [p.boundary_mask for p in mw.partitions]
            skipped = skip_ok and mw.num_shards > 1 and can_skip_sync(
                updated_ids, boundary_masks)
            mw.stats.rounds_total += 1
            rec["skipped"] = bool(skipped)

            if skipped:
                mw.stats.rounds_skipped += 1
                states = new_states
                actives = new_actives
            else:
                # Global merge ("upper system synchronization").
                states, actives = self._global_sync(
                    states, aggs, cnts, aux, it,
                    updated_ids, boundary_masks, reads, rowbytes, rec)

            rec["active"] = int(np.max([a.sum() for a in actives]))
            per_iter.append(rec)
            if all(a.sum() == 0 for a in actives):
                converged = True
                break
            pending = mw.model.epilogue(gather, rec)

        final = mw.upper.resolve(states)
        return Result(
            state=final,
            iterations=it,
            converged=converged,
            stats=mw.stats,
            wall_time=time.perf_counter() - t0,
            per_iteration=per_iter,
        )

    def _global_sync(self, states, aggs, cnts, aux, it,
                     updated_ids, boundary_masks, reads, rowbytes, rec):
        mw = self.mw
        o = mw.options
        # Byte accounting: dense exchange vs lazy upload (Alg. 3).
        mw.stats.dense_bytes += mw.num_shards * mw.n * mw.k * 4
        # The query set is what the exchange actually needs: the boundary
        # reads of the blocks that were runnable this iteration, already
        # boundary-filtered by the gather.  Regression: deriving it from
        # every edge in the blockset over-counted lazy_bytes whenever
        # frontier block skipping ran a subset.
        queried = list(reads)
        upd_boundary = [
            u[boundary_masks[j][u]].astype(np.int64)
            for j, u in enumerate(updated_ids)
        ]
        gqq, uploads = mw.upper.exchange(upd_boundary, queried)
        mw.stats.lazy_bytes += int(sum(u.size for u in uploads)) * rowbytes
        mw.stats.lazy_bytes += int(gqq.size) * 8  # query-queue broadcast
        if o.sync_caching:
            # Invalidate every updated boundary vertex, not just this
            # round's uploads: a vertex whose consumers' blocks were all
            # skipped this iteration is uploaded only when next queried,
            # but its cached copies are stale the moment it changes.
            changed = np.unique(np.concatenate(
                [u for u in upd_boundary] or [np.empty(0, np.int64)]))
            for c in mw._caches:
                c.invalidate(changed)

        base, agg, cnt = mw.upper.merge(states, aggs, cnts)
        ns, act = mw._apply_fn(jnp.asarray(base), jnp.asarray(agg),
                               jnp.asarray(cnt) > 0, jnp.asarray(aux), it)
        ns, act = np.asarray(ns), np.asarray(act)
        return [ns.copy() for _ in range(mw.num_shards)], [
            act.copy() for _ in range(mw.num_shards)
        ]


def _rec_value(v):
    """Host-native view of an already-fetched record value: numpy
    scalars/arrays become Python scalars/lists so the per-iteration
    records stay JSON-serializable without per-key device syncs."""
    if isinstance(v, dict):
        return {k: _rec_value(x) for k, x in v.items()}
    if isinstance(v, np.ndarray):
        return v.item() if v.ndim == 0 else v.tolist()
    if isinstance(v, np.generic):
        return v.item()
    return v


def _device_source_masks(partitions, m: int, n: int) -> np.ndarray:
    """(m, N) bool: which source vertices device ``i`` owns edges of.

    Shards are laid out device-major over the mesh axis (``migrate``
    re-sorts ``partitions`` that way), so device ``i`` holds shards
    ``[i*cap, (i+1)*cap)``.  Used to deliver a migrated/mutated backlog
    only to the device that can actually generate the source's messages
    — a source no device owns (isolated vertex) matters to nobody.
    """
    masks = np.zeros((m, n), dtype=bool)
    cap = len(partitions) // m
    for i in range(m):
        for p in partitions[i * cap:(i + 1) * cap]:
            src = np.asarray(p.src)
            if src.size:
                masks[i, np.unique(src)] = True
    return masks


class _FusedLoopBase:
    """Shared scaffolding of the device-resident fused drive loops.

    Subclasses define the jitted step (:meth:`_build_step`), the carry
    it threads between iterations (:meth:`_init_carry` — element 0 must
    be the vertex state), and :meth:`_advance`, which runs one step and
    returns ``(carry', done, n_active, blocks_run, extra_rec)``.  The
    base class owns everything both loops share: placement of the
    replicated state/aux/frontier, the iteration loop, per-iteration
    records, and the single final-state materialization.
    """

    def __init__(self, mw: Middleware):
        self.mw = mw
        self._step = None
        self._epoch_seen = -1  # bus version the compiled step targets

    def _build_step(self):
        raise NotImplementedError

    def _init_carry(self, state, active):
        raise NotImplementedError

    def _advance(self, carry, aux, it, stacked):
        raise NotImplementedError

    def _migrate_carry(self, carry):
        raise NotImplementedError

    def _mutate_carry(self, carry, state0, ep, rep):
        """Carry re-placement for a mid-run mutation epoch (the mesh is
        unchanged; the graph under the run is not).  Incremental: keep
        the converged-so-far state and force the dirty frontier active —
        sound for add-only batches under an idempotent monoid, where the
        current state is a valid intermediate of the new computation.
        Cold: reset to the (new graph's) initial state with everything
        active — the rest of the run IS the cold restart."""
        state, active = carry[0], carry[1]
        if ep.meta.get("incremental"):
            fr = jax.device_put(
                np.asarray(ep.meta["frontier"], dtype=bool), rep)
            return (state, jnp.logical_or(active, fr))
        return (jax.device_put(state0, rep),
                jax.device_put(np.ones(self.mw.n, dtype=bool), rep))

    def _adopt_epoch(self, carry, aux_dev, init_fn):
        """Re-places the carry for the epoch the middleware just
        published.  Migrations move the replicated carry onto the
        survivor mesh; mutation epochs recompute aux from the mutated
        graph (degrees changed) and delegate to :meth:`_mutate_carry`."""
        mw = self.mw
        ep = mw.epochs.epoch
        if ep.cause == "mutation":
            rep = jax.sharding.NamedSharding(
                mw.daemon.mesh, jax.sharding.PartitionSpec())
            state0, aux = init_fn(mw.graph)
            return (self._mutate_carry(carry, state0, ep, rep),
                    jax.device_put(aux, rep))
        return self._migrate_carry(carry), mw.upper.migrate(aux_dev)

    def run(self, max_iterations: int | None = None, *,
            init=None, frontier=None) -> Result:
        # host spans: plug.run > plug.iteration > plug.poll,
        # plug.dispatch, plug.fetch; then plug.result.  On a profiler's
        # clock a device-idle gap lands on the host work around it.
        with jax.profiler.TraceAnnotation("plug.run"):
            return self._run(max_iterations, init, frontier)

    def _run(self, max_iterations, init, frontier) -> Result:
        mw = self.mw
        prog = mw.program
        mw.upper.reset()
        max_it = max_iterations or prog.max_iterations
        init_fn = init or prog.init
        state0, aux = init_fn(mw.graph)
        rep = jax.sharding.NamedSharding(mw.daemon.mesh,
                                         jax.sharding.PartitionSpec())
        state = jax.device_put(state0, rep)
        aux_dev = jax.device_put(aux, rep)
        active0 = (np.ones(mw.n, dtype=bool) if frontier is None
                   else np.asarray(frontier, dtype=bool))
        if active0.shape != (mw.n,):
            raise ValueError(f"frontier must have shape ({mw.n},), got "
                             f"{active0.shape}")
        active = jax.device_put(active0, rep)
        carry = self._init_carry(state, active)
        if self._step is None or self._epoch_seen != mw.epochs.version:
            # first run, or the structure advanced between runs
            # (rebalance()/apply_mutations()): recompile against it
            self._step = self._build_step()
            self._epoch_seen = mw.epochs.version
        # captured AFTER _build_step: building the async step may arm
        # priority buckets, which adds their adjacency to the stacked dict
        stacked = mw.daemon.stacked
        blocks_total = int(sum(bs.num_blocks for bs in mw.blocksets))
        per_iter: list[dict] = []
        t0 = time.perf_counter()
        it = 0
        converged = False

        for it in range(1, max_it + 1):
            with jax.profiler.TraceAnnotation("plug.iteration", it=it):
                # Structure check between fused iterations: a device
                # killed (or a mutation batch due) "at iteration k" lands
                # before iteration k executes.  The poll publishes
                # epochs; the loop reacts to the bus VERSION — it never
                # remeshes or replans anything itself — and the run
                # resumes from the carried (replicated) state: no
                # checkpoint.
                with jax.profiler.TraceAnnotation("plug.poll"):
                    ev = mw._poll_structure(it)
                    if mw.epochs.version != self._epoch_seen:
                        t_reb = time.perf_counter()
                        carry, aux_dev = self._adopt_epoch(carry, aux_dev,
                                                           init_fn)
                        # new structure → new program
                        self._step = self._build_step()
                        stacked = mw.daemon.stacked
                        self._epoch_seen = mw.epochs.version
                        blocks_total = int(sum(bs.num_blocks
                                               for bs in mw.blocksets))
                        reb_s = time.perf_counter() - t_reb
                        for r in ev.values():  # charge it to its trigger
                            if "seconds" in r:
                                r["seconds"] += reb_s
                                break
                with jax.profiler.TraceAnnotation("plug.dispatch"):
                    carry, done, n_active, blocks_run, extra = self._advance(
                        carry, aux_dev, jnp.int32(it), stacked)
                mw.stats.rounds_total += 1
                # ONE host sync per iteration: every record scalar
                # (including whatever the subclass put in extra) rides the
                # same fetch — per-key float()/int() casts would each
                # block on the device
                with jax.profiler.TraceAnnotation("plug.fetch"):
                    done, n_active, blocks_run, extra = jax.device_get(
                        (done, n_active, blocks_run, extra))
                shard_blocks = [int(x) for x in blocks_run]
                rec = {"iteration": it, "fused": True,
                       "blocks_total": blocks_total,
                       "blocks_run": int(sum(shard_blocks)),
                       "shard_blocks_run": shard_blocks,
                       "active": int(n_active)}
                rec.update(ev)
                rec.update({k: _rec_value(v) for k, v in extra.items()})
                per_iter.append(rec)
                if bool(done):
                    converged = True
                    break

        with jax.profiler.TraceAnnotation("plug.result"):
            final = np.asarray(carry[0])  # the run's one device→host copy
        return Result(
            state=final,
            iterations=it,
            converged=converged,
            stats=mw.stats,
            wall_time=time.perf_counter() - t0,
            per_iteration=per_iter,
        )


class DriveLoop(_FusedLoopBase):
    """Device-resident fused drive loop (the sharded fast path).

    One jitted step per iteration composes the sharded daemon's
    gather + Gen + segmented Merge ``shard_map``, the upper system's
    cross-device partial merge, Apply, and the convergence check into a
    single device program.  Vertex state and the frontier stay resident
    on the mesh between iterations; only scalars (converged flag, active
    count, ``edges_active``: the arcs out of the frontier the step ran
    on) and the tiny per-shard blocks-run vector cross to the host, and
    the final state is materialized exactly once after the loop.

    Because the collective merge is *inside* every step, shard replicas
    never diverge: there is no candidate apply, no sync round to skip,
    and no host download to LRU-cache — those host-economy options are
    inert here by construction (``stats`` carries ``rounds_total``
    only).  The :class:`HostDriveLoop` remains the path with full byte
    accounting and is what daemons without ``run_all_shards`` fall back
    to.
    """

    def _build_step(self):
        mw = self.mw
        daemon, upper, apply_fn = mw.daemon, mw.upper, mw._apply_fn
        use_frontier = (mw.program.frontier_driven
                        and mw.options.frontier_block_skipping)

        def step(state, active, aux, it, structure):
            stacked, out_degree = structure
            partials, counts, blocks_run = daemon.run_all_shards(
                state, aux, active if use_frontier else None,
                stacked=stacked)
            agg, cnt = upper.merge_partials(partials, counts)
            with jax.named_scope("plug.apply"):
                # base == state: replicas are merged every step, never
                # diverge
                new_state, new_active = apply_fn(state, agg, cnt > 0, aux,
                                                 it)
                n_active = new_active.sum()
            with jax.named_scope("plug.gather"):
                # arcs out of the frontier the step ran on
                edges_active = (jnp.where(active, out_degree, 0).sum()
                                if use_frontier else out_degree.sum())
            return (new_state, new_active, n_active == 0, n_active,
                    (blocks_run, edges_active))

        return jax.jit(step)

    def _init_carry(self, state, active):
        return (state, active)

    def lower(self):
        """Builds the step for the current structure and lowers it; the
        next :meth:`run` reuses the step."""
        mw = self.mw
        state0, aux = mw.program.init(mw.graph)
        rep = jax.sharding.NamedSharding(mw.daemon.mesh,
                                         jax.sharding.PartitionSpec())
        args = (jax.device_put(state0, rep),
                jax.device_put(np.ones(mw.n, dtype=bool), rep),
                jax.device_put(aux, rep), jnp.int32(1))
        self._step = self._build_step()
        self._epoch_seen = mw.epochs.version
        return self._step.lower(*args, (mw.daemon.stacked, mw.out_degree))

    def _migrate_carry(self, carry):
        # both carries are mesh-replicated — the survivors already hold
        # full copies, so the move is a pure re-placement
        return tuple(self.mw.upper.migrate(list(carry)))

    def _advance(self, carry, aux, it, stacked):
        state, active, done, n_active, (blocks_run, edges) = self._step(
            *carry, aux, it, (stacked, self.mw.out_degree))
        return ((state, active), done, n_active, blocks_run,
                {"edges_active": edges})


class OocoreDriveLoop(_FusedLoopBase):
    """Out-of-core fused drive loop: stream super-shards, overlap uploads.

    Each iteration runs the *same* fused gather+Gen+Merge partial step as
    :class:`DriveLoop`, but once per column group instead of once: first
    over the device-resident hot set, then over each cold super-shard as
    it arrives from host memory.  Per-device partials accumulate across
    groups with the program's monoid — neutral by construction (empty
    segments already carry the identity inside every group) — and the
    upper-system collective merge + Apply + convergence run exactly once
    at the end, so the state trajectory matches the all-resident fused
    loop bit-identically for idempotent monoids.

    With ``prefetch`` on, a single background thread ``device_put``s
    super-shard ``i+1`` while super-shard ``i`` computes (double
    buffering: at most two cold groups on device), wrapping around so
    the *next iteration's* first group uploads during this iteration's
    tail.  For frontier-driven programs the same scheduler is
    frontier-aware: a cold group none of whose live sources are active
    contributes exactly the identity, so its upload and compute are
    skipped outright (see ``ShardedDaemon.super_shard_active``).  The
    per-iteration record and ``Middleware.oocore_stats`` carry the
    split the acceptance cares about: transfer seconds (measured in
    the worker), wait seconds (how long the critical path actually
    stalled), their ratio as ``overlap_efficiency``, skipped-group
    counts, and hot-set hit/miss counters (active columns served from
    cache vs streamed).
    """

    def __init__(self, mw: Middleware):
        super().__init__(mw)
        self._uploader = None

    def _build_step(self):
        from repro.oocore.prefetch import AsyncUploader

        mw = self.mw
        daemon, upper, apply_fn = mw.daemon, mw.upper, mw._apply_fn
        monoid = mw.program.monoid
        use_frontier = (mw.program.frontier_driven
                        and mw.options.frontier_block_skipping)

        def partial(state, aux, active, acc_p, acc_c, stacked):
            p, c, blocks_run = daemon.run_all_shards(
                state, aux, active if use_frontier else None,
                stacked=stacked)
            return monoid.combine(acc_p, p), acc_c + c, blocks_run

        def finalize(state, acc_p, acc_c, aux, it):
            agg, cnt = upper.merge_partials(acc_p, acc_c)
            new_state, new_active = apply_fn(state, agg, cnt > 0, aux, it)
            n_active = new_active.sum()
            return new_state, new_active, n_active == 0, n_active

        self._partial = jax.jit(partial)
        self._finalize = jax.jit(finalize)
        self._use_frontier = use_frontier
        # identity-filled per-device partial accumulators, sharded like
        # the daemon's partials so the combine stays collective-free
        part = jax.sharding.NamedSharding(
            mw.upper.mesh, jax.sharding.PartitionSpec(mw.upper.axis))
        self._acc0 = (
            jax.device_put(np.full((daemon.m, mw.n, mw.k),
                                   monoid.identity, np.float32), part),
            jax.device_put(np.zeros((daemon.m, mw.n), np.int32), part),
        )
        if self._uploader is not None:
            self._uploader.close()
        self._uploader = None
        if mw.oocore.prefetch and daemon.num_super_shards > 0:
            self._uploader = AsyncUploader(daemon.upload_super_shard)
            self._uploader.request(0)  # warm the pipe before iteration 1
        return (self._partial, self._finalize)

    def _init_carry(self, state, active):
        return (state, active)

    def _migrate_carry(self, carry):
        return tuple(self.mw.upper.migrate(list(carry)))

    def _advance(self, carry, aux, it, stacked):
        # `stacked` is the resident pytree of the other fused loops —
        # unused here: columns come from the hot cache + the host stream
        mw = self.mw
        daemon = mw.daemon
        state, active = carry
        acc_p, acc_c = self._acc0
        num_ss = daemon.num_super_shards
        t_iter = time.perf_counter()
        transfer_s = wait_s = 0.0
        hot_br = None
        cold_br = None
        if daemon.hot_stacked is not None:
            acc_p, acc_c, hot_br = self._partial(
                state, aux, active, acc_p, acc_c, daemon.hot_stacked)
        todo = list(range(num_ss))
        if (self._uploader is not None and self._use_frontier and num_ss):
            # frontier-aware streaming: a cold group none of whose live
            # sources are active contributes exactly the monoid identity
            # (the kernels mask those edges anyway), so the scheduler
            # skips its upload *and* its compute — the dominant saving on
            # sparse-frontier iterations.  The no-prefetch baseline has
            # no scheduler and streams every group.
            host_active = np.asarray(jax.device_get(active))
            todo = [p for p in todo
                    if daemon.super_shard_active(p, host_active)]
        skipped = num_ss - len(todo)
        uploads = len(todo)
        if self._uploader is not None:
            for i, p in enumerate(todo):
                dev, tr, wt = self._uploader.take(p)
                transfer_s += tr
                wait_s += wt
                # double buffer: next group uploads while this one
                # computes; the wrap-around request is iteration it+1's
                # first-group guess (a stale guess is never wasted —
                # group content is immutable, so a pending upload stays
                # valid until some later iteration takes it)
                self._uploader.request(todo[(i + 1) % len(todo)])
                acc_p, acc_c, br = self._partial(
                    state, aux, active, acc_p, acc_c, dev)
                cold_br = br if cold_br is None else cold_br + br
                del dev
        else:
            for p in range(num_ss):
                # no-prefetch baseline: upload and compute strictly
                # serialized, every transfer fully on the critical path
                t0 = time.perf_counter()
                dev = daemon.upload_super_shard(p)
                jax.block_until_ready(dev)
                tr = time.perf_counter() - t0
                transfer_s += tr
                wait_s += tr
                acc_p, acc_c, br = self._partial(
                    state, aux, active, acc_p, acc_c, dev)
                jax.block_until_ready(acc_c)
                cold_br = br if cold_br is None else cold_br + br
                del dev
        new_state, new_active, done, n_active = self._finalize(
            state, acc_p, acc_c, aux, it)
        jax.block_until_ready(new_state)
        iter_s = time.perf_counter() - t_iter

        hot_hits = int(jax.device_get(hot_br).sum()) if hot_br is not None else 0
        misses = int(jax.device_get(cold_br).sum()) if cold_br is not None else 0
        if hot_br is None:
            blocks_run = cold_br
        elif cold_br is None:
            blocks_run = hot_br
        else:
            blocks_run = hot_br + cold_br
        total = hot_hits + misses
        overlap = 1.0 if transfer_s <= 0 else max(0.0, 1.0 - wait_s / transfer_s)
        rec = {"super_shards": num_ss,
               "hot_cols": int(daemon.oocore_plan.hot_cols),
               "prefetch": self._uploader is not None,
               "seconds": iter_s,
               "transfer_s": transfer_s, "wait_s": wait_s,
               "hidden_s": transfer_s - wait_s,
               "overlap_efficiency": overlap,
               "skipped": skipped,
               "hot_hits": hot_hits, "cold_misses": misses,
               "hot_hit_rate": hot_hits / total if total else 0.0}
        st = mw.oocore_stats
        if not st:
            st.update(iterations=0, transfer_s=0.0, wait_s=0.0,
                      hidden_s=0.0, hot_hits=0, cold_misses=0, uploads=0,
                      upload_bytes=0, skipped=0, super_shards=num_ss,
                      prefetch=self._uploader is not None)
        st["iterations"] += 1
        st["transfer_s"] += transfer_s
        st["wait_s"] += wait_s
        st["hidden_s"] += transfer_s - wait_s
        st["hot_hits"] += hot_hits
        st["cold_misses"] += misses
        st["uploads"] += uploads
        st["upload_bytes"] += uploads * daemon.super_shard_nbytes
        st["skipped"] += skipped
        seen = st["hot_hits"] + st["cold_misses"]
        st["hot_hit_rate"] = st["hot_hits"] / seen if seen else 0.0
        st["overlap_efficiency"] = (
            1.0 if st["transfer_s"] <= 0
            else max(0.0, 1.0 - st["wait_s"] / st["transfer_s"]))
        return ((new_state, new_active), done, n_active, blocks_run,
                {"oocore": rec})


class AsyncDriveLoop(_FusedLoopBase):
    """Device-resident fused drive loop of the asynchronous priority model.

    Like :class:`DriveLoop`, one jitted step per iteration — but the
    step additionally carries the model's scheduling state on the mesh:

    * **held partials/counts** ``(m, N, K)`` / ``(m, N)`` — the
      aggregate each device last *shipped*.  Every step recomputes the
      fresh per-device partials, and the upper system's
      :meth:`~repro.plug.uppers.MeshUpperSystem.merge_partials_async`
      cadence decides per device whether this round's collective
      consumes fresh or held: a device whose contribution moved less
      than the priority threshold holds (its consumers keep reading the
      stale aggregate — the async middleware semantics), the rest
      refresh.
    * **frontier backlog** ``(m, N)`` — for frontier-driven programs,
      the sources that activated while a device held.  The device's next
      run uses the backlog as its private frontier (per-device ``active``
      in ``run_all_shards``), so a message suppressed during a hold is
      re-generated from the source's *current* state on refresh — no
      update is ever lost, which is what makes the fixed point exact.
    * **theta** — the priority threshold: starts at the model's
      ``theta0``, decays by ``decay`` every iteration, and collapses to
      0 the moment the frontier drains, forcing the tail of the run
      into barriered (BSP-equivalent) steps.

    The cadence is split so a hold is *free* instead of
    compute-then-discard:

    * **predict** (pre-Gen, cheap): from the previous iteration's
      committed priority, the per-vertex residual of the last Apply,
      and theta, each device estimates whether its refresh could
      possibly commit.  ``est = max(prev_pri, max residual over
      backlogged sources)`` can only over-estimate the commit half's
      priority (states move monotonically toward the fixed point for
      the idempotent monoids that drive frontiers), so predicting a
      hold is safe — and a predicted-held device never runs Gen: the
      daemon's ``run_mask`` skips gather+Gen+Merge behind ``lax.cond``
      (priority buckets excepted), and ``merge_partials_async``
      consumes the mask so the skipped device's held copy stays
      authoritative.
    * **commit** (post-Gen, exact): the existing refresh decision on
      whatever fresh partials were produced, unchanged — convergence
      certification still happens on real data, and the carried
      ``prev_pri`` is only updated from committed priorities.

    Liveness: theta decays every iteration, so a held device's
    ``prev_pri`` eventually clears it and the device re-runs; the
    mispredict cost is one extra hold iteration, never a lost update
    (the backlog persists until an actual refresh commits).

    Convergence is only reported on an iteration where every device
    refreshed and no backlog is pending, so a drained frontier under
    staleness can never terminate the run early.  Host traffic per
    iteration stays O(1) scalars (plus the tiny per-shard blocks-run
    vector and the (m,) run mask), exactly as in :class:`DriveLoop`.
    """

    def _build_step(self):
        mw = self.mw
        daemon, upper, apply_fn = mw.daemon, mw.upper, mw._apply_fn
        model = mw.model
        decay = float(model.decay)
        floor = float(model.floor)
        m = daemon.m
        use_frontier = (mw.program.frontier_driven
                        and mw.options.frontier_block_skipping)
        # Feature-detect the free-hold fast path: the daemon must take a
        # run_mask (MaskCapableDaemon) AND the upper's async merge must
        # consume it — a custom component missing either keeps the
        # run-everything cadence (correct, just not skipping work).
        maskable = (
            isinstance(daemon, MaskCapableDaemon)
            and "run_mask" in inspect.signature(
                upper.merge_partials_async).parameters)
        src_masks = None
        if maskable:
            daemon.configure_buckets(
                int(getattr(model, "bucket_k", 0) or 0),
                int(getattr(model, "bucket_cap", 32) or 32))
            if use_frontier:
                # private frontiers for real: a newly-active source is
                # delivered only to the device owning its edges, so a
                # device with no owned work has an EMPTY backlog row and
                # the all-inactive fast path skips its Gen outright.
                # Trajectory-identical to the broadcast — a non-owner
                # has no edges from the source and generates nothing.
                src_masks = jax.device_put(
                    _device_source_masks(mw.partitions, m, mw.n),
                    jax.sharding.NamedSharding(
                        mw.upper.mesh,
                        jax.sharding.PartitionSpec(mw.upper.axis)))

        def step(state, active, backlog, held_p, held_c, theta, prev_pri,
                 residual, aux, it, stacked):
            if use_frontier:
                # deliver each device its private backlog ∪ the new
                # frontier; consumed below when the device refreshes
                new_work = (active[None, :] & src_masks
                            if src_masks is not None else active[None, :])
                backlog = backlog | new_work
            if maskable:
                # predict half: a device whose estimated priority cannot
                # clear theta holds WITHOUT running Gen.  The estimate
                # over-approximates the commit priority — the last
                # committed one, raised by the largest residual among
                # this device's backlogged sources — so predicted holds
                # are safe and mispredicts only cost one hold iteration
                # (theta decays under prev_pri eventually: liveness).
                est = prev_pri
                if use_frontier:
                    est = jnp.maximum(est, jnp.max(
                        jnp.where(backlog, residual[None, :], 0.0),
                        axis=1))
                run_mask = (est >= theta) | (theta <= floor)
                fresh_p, fresh_c, blocks_run = daemon.run_all_shards(
                    state, aux, backlog if use_frontier else None,
                    run_mask=run_mask, residual=residual, stacked=stacked)
                (agg, cnt, held_p, held_c, refreshed,
                 pri) = upper.merge_partials_async(
                    fresh_p, fresh_c, held_p, held_c, theta, floor,
                    run_mask)
                # only committed priorities feed the next prediction — a
                # skipped device's identity output says nothing new
                prev_pri = jnp.where(run_mask, pri, prev_pri)
                executed = (run_mask & backlog.any(axis=1)
                            if use_frontier else run_mask)
            else:
                fresh_p, fresh_c, blocks_run = daemon.run_all_shards(
                    state, aux, backlog if use_frontier else None,
                    stacked=stacked)
                out = upper.merge_partials_async(
                    fresh_p, fresh_c, held_p, held_c, theta, floor)
                agg, cnt, held_p, held_c, refreshed = out[:5]
                if len(out) > 5:
                    prev_pri = jnp.where(refreshed, out[5], prev_pri)
                run_mask = jnp.ones((m,), jnp.bool_)
                executed = run_mask
            if use_frontier:
                backlog = backlog & ~refreshed[:, None]
            new_state, new_active = apply_fn(state, agg, cnt > 0, aux, it)
            # per-vertex residual of this Apply — next iteration's
            # predict signal and the bucket score source (NaN/±inf from
            # non-finite identities canonicalize to finite)
            residual = jnp.nan_to_num(
                jnp.max(jnp.abs(new_state - state), axis=1), nan=0.0)
            n_active = new_active.sum()
            pending = (backlog.any() if use_frontier
                       else jnp.asarray(False))
            all_fresh = refreshed.all()
            done = (n_active == 0) & all_fresh & ~pending
            # the threshold decays every iteration and collapses the
            # moment the frontier drains: the tail of the run is
            # barriered, so convergence is certified on fresh data
            theta = jnp.where(n_active == 0, 0.0, theta * decay)
            n_executed = executed.sum()
            return (new_state, new_active, backlog, held_p, held_c, theta,
                    prev_pri, residual, done, n_active, refreshed.sum(),
                    n_executed, jnp.int32(m) - n_executed, run_mask,
                    blocks_run)

        return jax.jit(step)

    def _init_carry(self, state, active):
        mw = self.mw
        m = mw.daemon.m
        # Carries shard their leading (device) axis over the upper's
        # mesh axis — built from the DevicePartialUpper protocol's
        # public mesh/axis, so any conforming upper system works.
        shard = jax.sharding.NamedSharding(
            mw.upper.mesh, jax.sharding.PartitionSpec(mw.upper.axis))
        # scheduling state starts all-stale-at-identity: first fresh
        # partials score maximal priority wherever any message exists
        held_p = jax.device_put(
            np.full((m, mw.n, mw.k), mw.program.monoid.identity,
                    np.float32), shard)
        held_c = jax.device_put(np.zeros((m, mw.n), np.int32), shard)
        backlog = jax.device_put(np.zeros((m, mw.n), dtype=bool), shard)
        rep = jax.sharding.NamedSharding(mw.upper.mesh,
                                         jax.sharding.PartitionSpec())
        # predict-half state: prev_pri at float-max forces every device
        # to run on iteration 1 (no committed priority exists yet);
        # residual zero is exact (nothing has moved)
        prev_pri = jax.device_put(
            np.full((m,), np.finfo(np.float32).max, np.float32), shard)
        residual = jax.device_put(np.zeros(mw.n, np.float32), rep)
        return (state, active, backlog, held_p, held_c,
                jnp.float32(mw.model.theta0), prev_pri, residual)

    def _migrate_carry(self, carry):
        """Survivor-mesh re-placement of the async carry.

        State and frontier are replicated and move via
        ``upper.migrate``.  The per-device scheduling state is
        re-initialized for the new axis length m': held partials restart
        at the monoid identity — the next merge then consumes every
        device's fresh partials, i.e. one barriered step, so nothing a
        device was holding is lost — and the union of all old backlogs
        (dead devices' included) is re-delivered, each source ONLY to
        the survivor that owns its edges after the re-partition: a
        non-owner has no edges from the source, so running it there
        generates nothing — broadcasting was pure wasted Gen work.
        Re-delivery may recompute work but never loses an update, which
        is what keeps the migrated fixed point exact.  ``theta``
        carries over so the priority schedule resumes where it was;
        ``prev_pri`` restarts at float-max (held copies restarted at
        identity, so every survivor must run before it may hold again).
        """
        mw = self.mw
        state, active, backlog, held_p, held_c, theta = carry[:6]
        state, active = mw.upper.migrate((state, active))
        merged_backlog = np.asarray(jax.device_get(backlog)).any(axis=0)
        m = mw.daemon.m
        shard = jax.sharding.NamedSharding(
            mw.upper.mesh, jax.sharding.PartitionSpec(mw.upper.axis))
        masks = _device_source_masks(mw.partitions, m, mw.n)
        backlog = jax.device_put(
            np.ascontiguousarray(merged_backlog[None, :] & masks), shard)
        held_p = jax.device_put(
            np.full((m, mw.n, mw.k), mw.program.monoid.identity,
                    np.float32), shard)
        held_c = jax.device_put(np.zeros((m, mw.n), np.int32), shard)
        rep = jax.sharding.NamedSharding(mw.upper.mesh,
                                         jax.sharding.PartitionSpec())
        prev_pri = jax.device_put(
            np.full((m,), np.finfo(np.float32).max, np.float32), shard)
        residual = jax.device_put(np.zeros(mw.n, np.float32), rep)
        return (state, active, backlog, held_p, held_c,
                jnp.float32(float(theta)), prev_pri, residual)

    def _mutate_carry(self, carry, state0, ep, rep):
        """Mid-run mutation under the async model.  Held partials were
        computed on the pre-mutation graph and must never be consumed —
        they restart at the monoid identity, so the next merge is one
        barriered all-fresh step.  Incremental: state and theta carry
        over, and the dirty frontier joins both the shared frontier and
        every device's backlog (a source suppressed by a hold is
        re-delivered against the mutated graph — delivered only to the
        device owning the source's edges in the re-partitioned graph,
        exactly as :meth:`_migrate_carry` does).  Cold: full async
        reset on the new graph."""
        mw = self.mw
        state, active, backlog, held_p, held_c, theta = carry[:6]
        m = mw.daemon.m
        shard = jax.sharding.NamedSharding(
            mw.upper.mesh, jax.sharding.PartitionSpec(mw.upper.axis))
        held_p = jax.device_put(
            np.full((m, mw.n, mw.k), mw.program.monoid.identity,
                    np.float32), shard)
        held_c = jax.device_put(np.zeros((m, mw.n), np.int32), shard)
        if ep.meta.get("incremental"):
            fr = np.asarray(ep.meta["frontier"], dtype=bool)
            active = jnp.logical_or(active, jax.device_put(fr, rep))
            # merged across old rows because the mutation re-partitioned
            # the graph (a source's owner may have moved), then masked
            # to the new owners — trajectory-identical to a broadcast,
            # since a non-owner generates no messages for the source
            masks = _device_source_masks(mw.partitions, m, mw.n)
            merged = (np.asarray(jax.device_get(backlog)).any(axis=0)
                      | fr)
            backlog = jax.device_put(
                np.ascontiguousarray(merged[None, :] & masks), shard)
            theta = jnp.float32(float(theta))
        else:
            state = jax.device_put(state0, rep)
            active = jax.device_put(np.ones(mw.n, dtype=bool), rep)
            backlog = jax.device_put(np.zeros((m, mw.n), dtype=bool),
                                     shard)
            theta = jnp.float32(mw.model.theta0)
        prev_pri = jax.device_put(
            np.full((m,), np.finfo(np.float32).max, np.float32), shard)
        residual = jax.device_put(np.zeros(mw.n, np.float32), rep)
        return (state, active, backlog, held_p, held_c, theta, prev_pri,
                residual)

    def _advance(self, carry, aux, it, stacked):
        (state, active, backlog, held_p, held_c, theta, prev_pri,
         residual, done, n_active, n_refreshed, n_executed, gen_skipped,
         run_mask, blocks_run) = self._step(*carry, aux, it, stacked)
        # record values stay device-resident here — the base loop's
        # single per-iteration device_get fetches them with done/active,
        # instead of one blocking sync per float()/int() cast
        extra = {"async": True, "refreshed": n_refreshed,
                 "devices": self.mw.daemon.m, "theta": theta,
                 "gen_run": n_executed, "gen_skipped": gen_skipped,
                 "run_mask": run_mask}
        return ((state, active, backlog, held_p, held_c, theta, prev_pri,
                 residual), done, n_active, blocks_run, extra)
