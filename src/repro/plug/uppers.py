"""Upper systems (the distributed side of the middleware, DESIGN.md §2).

An upper system owns everything global: how the graph is partitioned
into shards, the lazy exchange plan between iterations, and the
cross-shard merge of vertex states / message aggregates / counts.

* ``HostUpperSystem`` — the single-host upper system: merge runs as a
  NumPy/jnp fold over per-shard arrays on the host.  This preserves the
  exact semantics the legacy ``GXEngine`` shipped.
* ``MeshUpperSystem`` — shards stacked onto a device mesh (placement via
  ``repro.dist.sharding``) and merged with ``shard_map`` collectives:
  ``pmin``/``pmax`` for idempotent monoids (exact), ``psum`` for sum —
  optionally through the int8 error-feedback compressed wire of
  ``repro.dist.collectives.make_compressed_allreduce``
  (``wire="compressed"``, sum monoid only; exact by default).

Both merge folds associate identically (local fold per device group,
then the cross-group collective), so for idempotent monoids host and
mesh produce bit-identical states.
"""
from __future__ import annotations

import functools

import numpy as np

from repro.core.sync import lazy_exchange_plan
from repro.core.template import VertexProgram
from repro.graph.partition import partition_contiguous
from repro.graph.structure import Graph


class HostUpperSystem:
    """Host-side merge: today's NumPy/jnp fold, exact legacy semantics."""

    name = "host"

    def partition(self, graph: Graph, num_shards: int, fractions=None):
        """Contiguous edge ranges; ``fractions`` (e.g. from
        ``core.balance.lemma2_fractions``) sizes shards capacity-aware."""
        return partition_contiguous(graph, num_shards, fractions)

    def bind(self, program: VertexProgram, num_shards: int):
        self.program = program
        self.monoid = program.monoid
        self.num_shards = num_shards
        return self

    def reset(self):
        """Called at the start of every ``Middleware.run`` — clears any
        per-run state so repeated runs are reproducible."""

    def exchange(self, updated_boundary, queried):
        return lazy_exchange_plan(updated_boundary, queried)

    def merge(self, states, aggs, cnts):
        import jax.numpy as jnp

        monoid = self.monoid
        if monoid.idempotent:
            # States may have diverged across skipped rounds; the
            # idempotent combine over replicas restores consistency.
            base = functools.reduce(monoid.combine,
                                    [jnp.asarray(s) for s in states])
            agg = functools.reduce(monoid.combine,
                                   [jnp.asarray(a) for a in aggs])
        else:
            base = jnp.asarray(states[0])
            agg = functools.reduce(lambda x, y: x + y,
                                   [jnp.asarray(a) for a in aggs])
        cnt = np.sum(np.stack(cnts), axis=0)
        return base, agg, cnt

    def resolve(self, states):
        if len(states) == 1:
            return states[0]
        if self.monoid.idempotent:
            out = states[0]
            for s in states[1:]:
                out = np.asarray(self.monoid.combine(out, s))
            return out
        return states[0]


class MeshUpperSystem(HostUpperSystem):
    """Global merge as ``shard_map`` collectives over a device mesh.

    Shard arrays are stacked along a leading axis, placed with a
    ``NamedSharding`` built by ``dist.sharding.sharding_for``, locally
    folded per device group, and reduced across the mesh axis with
    ``pmin``/``pmax``/``psum``.  The mesh axis length is the largest
    divisor of ``num_shards`` that fits the available devices, so the
    same code runs 4 shards on 1 CPU device (local fold only) and 4
    shards on 4 devices (pure collective).

    ``wire="compressed"`` routes the sum-monoid aggregate through the
    int8 error-feedback all-reduce (``dist.collectives``) — the graph-
    engine analogue of compressed gradient sync; ``wire="exact"`` (the
    default) keeps the merge lossless.
    """

    name = "mesh"
    WIRES = ("exact", "compressed")

    def __init__(self, mesh=None, *, axis: str = "shard",
                 wire: str = "exact", bits: int = 8):
        if wire not in self.WIRES:
            raise ValueError(f"wire must be one of {self.WIRES}, got {wire!r}")
        self.mesh = mesh
        self._auto_mesh = mesh is None
        self.axis = axis
        self.wire = wire
        self.bits = bits
        self._merge_fn = None
        self._pmerge_fn = None
        self._allreduce = None
        self._residual = None
        self.wire_stats = {"exact_bytes": 0, "compressed_bytes": 0}

    def bind(self, program: VertexProgram, num_shards: int):
        import jax

        super().bind(program, num_shards)
        # Rebinding (a reused instance in a new Middleware) must not keep
        # compiled fns or residuals built for the previous shard layout.
        self._merge_fn = None
        self._pmerge_fn = None
        self._allreduce = None
        self._residual = None
        if self.wire == "compressed" and program.monoid.idempotent:
            raise ValueError(
                "wire='compressed' quantizes a summed aggregate; idempotent "
                "(min/max) merges must use wire='exact'")
        if self._auto_mesh:
            from repro.dist.sharding import divisor_mesh

            self.mesh = divisor_mesh(num_shards, self.axis)
        self.m = self.mesh.shape[self.axis]
        if num_shards % self.m:
            raise ValueError(f"num_shards={num_shards} not divisible by "
                             f"mesh axis {self.axis}={self.m}")
        # leading (shard) dim on the mesh axis, everything else replicated —
        # resolved through the dist.sharding rule machinery
        self._rules = {"shards": (self.axis,)}
        if self.wire == "compressed":
            from repro.dist.collectives import make_compressed_allreduce

            self._allreduce = make_compressed_allreduce(
                self.mesh, self.axis, bits=self.bits)
        return self

    def _place(self, arr):
        import jax
        from repro.dist import sharding as shd

        axes = ("shards",) + (None,) * (arr.ndim - 1)
        sh = shd.sharding_for(arr.shape, axes, self.mesh, self._rules)
        return jax.device_put(arr, sh)

    # -- elasticity (the ElasticUpper capability, DESIGN.md §4.4) ----------
    def remesh(self, mesh):
        """Re-targets the merge collectives at a survivor mesh.

        Checkpoint-free migration's upper half: the compiled merge fns
        (and the compressed wire, if any) were built for the old mesh
        axis length and are invalidated; ``m`` is re-derived and the
        stacked-shard divisibility re-checked.  Since the structure-
        epoch refactor (DESIGN.md §7) the only caller is the epoch
        bus's ``"upper"`` rebuild hook — trigger call-sites publish a
        :class:`~repro.plug.epoch.StructureEpoch`, the ordered hooks
        (upper, then daemon, then capacity) do the rebuilding, and the
        drive loops re-place live state when they observe the version
        move; nothing calls ``remesh`` directly.
        """
        if self.axis not in mesh.axis_names:
            raise ValueError(
                f"survivor mesh {mesh.axis_names} lacks the merge axis "
                f"{self.axis!r}")
        if self.num_shards % mesh.shape[self.axis]:
            raise ValueError(
                f"num_shards={self.num_shards} not divisible by the "
                f"survivor mesh axis {self.axis}={mesh.shape[self.axis]}")
        # validated above (before any mutation); rebind does the rest —
        # one invalidation path for compiled fns, residuals, and m
        self.mesh = mesh
        self._auto_mesh = False
        return self.bind(self.program, self.num_shards)

    def migrate(self, tree):
        """``device_put`` a pytree of mesh-replicated arrays onto the
        current (re-meshed) mesh.  Every survivor already holds a full
        replica, so this is the checkpoint-free state move — no host
        snapshot is read back."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        rep = NamedSharding(self.mesh, P())
        return jax.tree.map(lambda a: jax.device_put(a, rep), tree)

    def reset(self):
        # Per-run state: the error-feedback residual AND the wire
        # counters (regression: a second run() on the same instance
        # reported inflated exact/compressed byte totals — the stats and
        # LRU caches were reset at run() entry but the wire counters
        # were not).
        self._residual = None
        self.wire_stats = {"exact_bytes": 0, "compressed_bytes": 0}

    def _build_merge(self, s_per_dev: int, with_agg: bool):
        import jax
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as P

        monoid = self.monoid
        axis = self.axis

        def block(st, ag, cn):
            # st/ag: (S/m, N, K) local slices; cn: (S/m, N)
            base_l, ag_l = st[0], ag[0]
            for i in range(1, s_per_dev):  # static local fold
                if with_agg:
                    ag_l = (monoid.combine(ag_l, ag[i]) if monoid.idempotent
                            else ag_l + ag[i])
                if monoid.idempotent:
                    base_l = monoid.combine(base_l, st[i])
            cn_l = cn.sum(axis=0)
            if monoid.idempotent:
                red = jax.lax.pmin if monoid.name == "min" else jax.lax.pmax
                base = red(base_l, axis)
                agg = red(ag_l, axis) if with_agg else ag_l
            else:
                # sum-monoid replicas never diverge (no sync skipping), so
                # any shard's state is the base
                base = base_l
                agg = jax.lax.psum(ag_l, axis) if with_agg else ag_l
            cnt = jax.lax.psum(cn_l, axis)
            return base, agg, cnt

        spec = P(self.axis)
        fn = shard_map(block, mesh=self.mesh,
                       in_specs=(spec, spec, spec),
                       out_specs=(P(), P(), P()), check_rep=False)
        return jax.jit(fn)

    def _ensure_placed(self, arrs, dtype=None):
        """Stacks + places per-shard numpy arrays; an already-stacked
        device-resident jax.Array (e.g. partials a sharded daemon left on
        the mesh) passes through untouched — no re-``device_put``."""
        import jax

        if isinstance(arrs, jax.Array):
            return arrs
        stacked = np.stack([np.asarray(a) for a in arrs])
        if dtype is not None:
            stacked = stacked.astype(dtype)
        return self._place(stacked)

    def merge(self, states, aggs, cnts):
        s = len(states)
        compressed = self.wire == "compressed"
        stacked_s = self._ensure_placed(states)
        stacked_a = self._ensure_placed(aggs)
        stacked_c = self._ensure_placed(cnts, dtype=np.int32)
        if self._merge_fn is None:
            self._merge_fn = self._build_merge(s // self.m,
                                               with_agg=not compressed)
        base, agg, cnt = self._merge_fn(stacked_s, stacked_a, stacked_c)
        nbytes = int(np.prod(states[0].shape)) * 4
        if compressed:
            # the exact merge fn skipped its agg psum; the aggregate
            # travels the int8 error-feedback wire instead
            agg = self._compressed_sum(aggs)
            self.wire_stats["compressed_bytes"] += (
                (nbytes * self.bits) // 32 + 4) * self.m
        else:
            self.wire_stats["exact_bytes"] += nbytes * self.m
        return base, agg, cnt

    def _compressed_sum(self, aggs):
        """Sum-monoid aggregate over the int8 error-feedback wire."""
        import jax.numpy as jnp

        s = len(aggs)
        parts = np.stack(aggs).reshape(self.m, s // self.m,
                                       *aggs[0].shape).sum(axis=1)
        x = self._place(parts.astype(np.float32))
        if self._residual is None:
            self._residual = self._place(np.zeros_like(parts, np.float32))
        means, self._residual = self._allreduce(x, self._residual)
        # every row of the (m, N, K) output equals the mean of the m
        # per-device partials; sum = mean × m
        return jnp.asarray(np.asarray(means)[0] * self.m)

    # -- device-resident partial merge (the fused drive loop's half) -------
    def _build_pmerge(self):
        import jax
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as P

        monoid = self.monoid
        axis = self.axis

        def block(ag, cn):
            # ag: (1, N, K) this device's partial; cn: (1, N)
            ag_l, cn_l = ag[0], cn[0]
            if monoid.idempotent:
                red = jax.lax.pmin if monoid.name == "min" else jax.lax.pmax
                agg = red(ag_l, axis)
            else:
                agg = jax.lax.psum(ag_l, axis)
            cnt = jax.lax.psum(cn_l, axis)
            return agg, cnt

        spec = P(self.axis)
        return shard_map(block, mesh=self.mesh, in_specs=(spec, spec),
                         out_specs=(P(), P()), check_rep=False)

    def merge_partials(self, partials, counts):
        """Reduces device-resident (m, N, K) / (m, N) per-device partials
        across the mesh axis → replicated ``(agg, cnt)``.

        Traceable: the fused drive loop calls this inside its jitted
        step, composing the daemon's ``shard_map`` with this collective
        into one device program per iteration.  The partials stay where
        the daemon produced them — no host staging, no re-``device_put``.
        Only the exact wire reduces here; the compressed wire's
        error-feedback residual is per-run host state, so compressed
        merges take the classic ``merge`` path.
        """
        if self.wire != "exact":
            raise ValueError("merge_partials supports wire='exact' only; "
                             "compressed merges take the classic path")
        import jax

        if self._pmerge_fn is None:
            self._pmerge_fn = self._build_pmerge()
        with jax.named_scope("plug.combine.devices"):
            return self._pmerge_fn(partials, counts)

    def merge_partials_async(self, fresh_p, fresh_c, held_p, held_c,
                             theta, floor, run_mask=None):
        """Async merge cadence: the fused *async* drive loop's commit half.

        Decides, per device, whether this round's collective consumes
        the device's fresh partial or the stale one it last shipped:

        1. fresh partials are canonicalized to the monoid identity
           wherever the device delivered no message (segment reductions
           fill empty segments with ±inf, which is merge-equivalent to
           the identity but must not register as priority);
        2. each device's priority is how far its fresh contribution
           moved from its held copy (L∞ over values and counts) — NaN
           distances (non-finite identity minus itself) canonicalize to
           0, never to a silent never-refresh;
        3. devices at or above ``theta`` refresh — all of them, once
           ``theta`` has decayed to ``floor`` — the rest hold;
        4. the chosen partials reduce through the same collective
           :meth:`merge_partials` uses.

        ``run_mask`` (m,) bool is the predict half's verdict: a device
        predicted to hold skipped Gen entirely, so its fresh row is not
        a real aggregate — its held copy is authoritative and it can
        never refresh this round.  For idempotent monoids the skipped
        device's fresh row may still carry a vertex-level priority
        *bucket* partial (top-k residual vertices computed despite the
        hold); that is folded into the held copy with
        ``monoid.combine`` — a no-op when the bucket is identity —
        so bucket messages reach the collective without a full refresh.

        Traceable (called inside the fused step's jit).  Returns
        ``(agg, cnt, held_p, held_c, refreshed, pri)``: the merged
        aggregate/counts, the next iteration's held copies, the (m,)
        bool refresh mask, and the (m,) f32 priorities (the predict
        half's estimate source for the next iteration).
        """
        import jax.numpy as jnp

        if self.wire != "exact":
            raise ValueError("merge_partials_async supports wire='exact' "
                             "only; compressed merges take the classic path")
        ident = self.monoid.identity
        fresh_p = jnp.where((fresh_c > 0)[..., None], fresh_p, ident)
        # |inf - inf| = NaN for non-finite identities; NaN >= theta is
        # silently False, which would pin the device stale until the
        # theta floor collapse.  nan→0 is exact (both sides identity ⇒
        # nothing moved); ±inf clamps to float32 max, keeping pri
        # finite for the predict half's carried estimate.
        diff = jnp.nan_to_num(jnp.abs(fresh_p - held_p), nan=0.0)
        pri = jnp.max(diff, axis=(1, 2))
        pri = jnp.maximum(
            pri, jnp.max(jnp.abs(fresh_c - held_c).astype(jnp.float32),
                         axis=1))
        if run_mask is None:
            run_mask = jnp.ones(pri.shape, jnp.bool_)
        refreshed = ((pri >= theta) | (theta <= floor)) & run_mask
        if self.monoid.idempotent:
            # fold skipped devices' bucket partials into the held copy
            # (combine with identity where no bucket ran — a no-op)
            bucket_p = jnp.where(run_mask[:, None, None], ident, fresh_p)
            bucket_c = jnp.where(run_mask[:, None], 0, fresh_c)
            hold_p = self.monoid.combine(held_p, bucket_p)
            hold_c = jnp.maximum(held_c, bucket_c)
        else:
            # sum is not duplication-tolerant: a held device's copy is
            # carried verbatim, and its (identity) fresh row is dropped
            hold_p, hold_c = held_p, held_c
        held_p = jnp.where(refreshed[:, None, None], fresh_p, hold_p)
        held_c = jnp.where(refreshed[:, None], fresh_c, hold_c)
        agg, cnt = self.merge_partials(held_p, held_c)
        return agg, cnt, held_p, held_c, refreshed, pri


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------
_UPPER_SYSTEMS: dict = {}


def register_upper_system(name: str, factory) -> None:
    _UPPER_SYSTEMS[name] = factory


def get_upper_system(name: str, **kwargs):
    try:
        factory = _UPPER_SYSTEMS[name]
    except KeyError:
        raise KeyError(f"unknown upper system {name!r}; registered: "
                       f"{sorted(_UPPER_SYSTEMS)}") from None
    return factory(**kwargs)


def upper_system_names() -> tuple:
    return tuple(sorted(_UPPER_SYSTEMS))


register_upper_system("host", HostUpperSystem)
register_upper_system("mesh", MeshUpperSystem)
