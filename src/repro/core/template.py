"""The GX-Plug algorithm template (paper Sec. IV-A).

A graph algorithm is expressed through three APIs:

  * ``msg_gen``   (MSGGen)   — per-edge message generation from the edge
                               triplet (src state, dst state, edge weight).
  * ``msg_merge`` (MSGMerge) — a *monoid* combining messages destined to the
                               same vertex (min / max / sum). Keeping merge a
                               monoid is what lets the engine split work into
                               blocks, merge per-block partials, and merge
                               across shards with a collective — all without
                               changing the result.
  * ``msg_apply`` (MSGApply) — per-vertex state update from the merged
                               message; also reports per-vertex activity
                               (the frontier) used for convergence, block
                               skipping, and synchronization skipping.

The *call order* of the three realizes different computation models
(Sec. IV-B2): BSP runs Gen→Merge→Apply inside one superstep; GAS runs
Merge→Apply→Gen (scatter at the end, producing messages consumed by the
next iteration). ``repro.plug.computation`` implements both orders as
strategy objects over the same template, as the paper's middleware does
for GraphX vs PowerGraph.

State layout: vertex state is a dense ``(N, K)`` float32 array; messages are
``(E, K)``; static per-vertex features (degrees, seed labels) live in an
``(N, A)`` aux array. Dense fixed-width state is the TPU-native choice: it
keeps every block a fixed shape, so one compiled program serves all blocks.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.extend.core as jex_core
import jax.numpy as jnp
import numpy as np


# Host-side scatter-combine ufuncs per monoid (Monoid.scatter_at) —
# module-level so per-edge/per-block callers pay one dict lookup, not a
# dict construction.  "or" operates on {0.0, 1.0} indicators, where
# logical-or coincides exactly with max (see the OR monoid below).
_SCATTER_UFUNCS = {"sum": np.add, "min": np.minimum, "max": np.maximum,
                   "or": np.maximum}


@dataclasses.dataclass(frozen=True)
class Monoid:
    """Commutative, associative merge with identity (MSGMerge semantics)."""

    name: str
    identity: float
    combine: Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray]
    # Idempotent monoids (min/max) tolerate stale re-delivery and duplicated
    # contributions; only they are eligible for synchronization skipping.
    idempotent: bool

    def segment_reduce(self, msgs: jnp.ndarray, seg_ids: jnp.ndarray, num_segments: int):
        if self.name == "sum":
            return jax.ops.segment_sum(msgs, seg_ids, num_segments)
        if self.name == "min":
            return jax.ops.segment_min(msgs, seg_ids, num_segments)
        if self.name == "max":
            return jax.ops.segment_max(msgs, seg_ids, num_segments)
        if self.name == "or":
            # logical-or over {0,1} indicator floats ≡ max — exact, and
            # it keeps the reduction a selection (bit-identical under
            # any merge order / duplication, like min/max)
            return jax.ops.segment_max(msgs, seg_ids, num_segments)
        raise ValueError(self.name)

    def scatter_at(self, out: np.ndarray, ids, vals) -> None:
        """In-place host scatter-combine: ``out[ids] = combine(out[ids], vals)``.

        The host-side daemons (blocked/pipelined upload, the naive
        per-edge loop) merge block partials into a NumPy aggregate with
        a ufunc ``.at`` call; a monoid with no known ufunc raises rather
        than silently merging with the wrong operator.
        """
        try:
            ufunc = _SCATTER_UFUNCS[self.name]
        except KeyError:
            raise ValueError(
                f"monoid {self.name!r} has no host scatter rule; known: "
                f"{sorted(_SCATTER_UFUNCS)}") from None
        ufunc.at(out, ids, vals)


SUM = Monoid("sum", 0.0, lambda a, b: a + b, idempotent=False)
MIN = Monoid("min", float(np.finfo(np.float32).max), jnp.minimum, idempotent=True)
MAX = Monoid("max", float(np.finfo(np.float32).min), jnp.maximum, idempotent=True)
#: Logical OR over {0.0, 1.0} indicator messages (reachability /
#: flooding style programs).  Implemented as max — exact on indicators —
#: and idempotent, so it qualifies for sync skipping and bit-identity
#: guarantees like min/max.
OR = Monoid("or", 0.0, jnp.maximum, idempotent=True)

MONOIDS = {m.name: m for m in (SUM, MIN, MAX, OR)}


@dataclasses.dataclass(frozen=True)
class VertexProgram:
    """An algorithm instance of the template.

    Functions are jnp-vectorized over the leading (edge or vertex) axis so
    the same code runs on the reference engine, on CPU blocks, inside
    ``shard_map`` bodies, and inside the Pallas edge-block kernel.
    """

    name: str
    state_width: int  # K
    aux_width: int  # A (0 allowed)
    monoid: Monoid
    # msg_gen(src_state (E,K), dst_state (E,K), weight (E,1), src_aux (E,A)) -> (E,K)
    msg_gen: Callable[..., jnp.ndarray]
    # msg_apply(state (N,K), merged (N,K), has_msg (N,1) bool, aux (N,A), t) -> (state', active (N,))
    msg_apply: Callable[..., tuple[jnp.ndarray, jnp.ndarray]]
    # init(graph) -> (state (N,K) np.float32, aux (N,A) np.float32)
    init: Callable[..., tuple[np.ndarray, np.ndarray]]
    max_iterations: int = 100
    # Only edges whose src was active last iteration generate messages.
    frontier_driven: bool = True
    # -- batched multi-query programs (repro.serve) ------------------------
    # B > 0 declares the state a stack of B independent queries, each
    # owning K/B consecutive state columns.  ``query_activity(old, new) ->
    # (N, B) bool`` reports which vertices changed per query; the
    # middleware then freezes converged queries by reverting their
    # columns (early exit per query: a finished query stops contributing
    # frontier work while its batch-mates keep running).  For idempotent
    # monoids a quiet column IS its fixed point, so revert == commit and
    # answers are bit-identical to B independent single-query runs.
    num_queries: int = 0
    query_activity: Callable[..., jnp.ndarray] | None = None

    @functools.cached_property
    def msg_gen_reads(self) -> frozenset[str]:
        """The ``msg_gen`` operands its messages depend on: a subset of
        ``{"src", "dst", "weight", "aux"}``.

        Traced once per program on small abstract shapes and walked
        backwards from the outputs: an equation with a live output (or an
        effect) makes all of its inputs live, which is conservative for
        sub-jaxprs.  The Pallas tile path gathers per edge only what is
        read here; an operand left out reaches ``msg_gen`` as zeros of
        its usual shape.
        """
        k, a = self.state_width, max(self.aux_width, 1)
        shapes = [(8, k), (8, k), (8, 1), (8, a)]
        closed = jax.make_jaxpr(self.msg_gen)(
            *(jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes))
        jaxpr = closed.jaxpr
        live = {v for v in jaxpr.outvars if isinstance(v, jex_core.Var)}
        for eqn in reversed(jaxpr.eqns):
            if eqn.effects or any(v in live for v in eqn.outvars):
                live.update(v for v in eqn.invars
                            if isinstance(v, jex_core.Var))
        return frozenset(name for name, v in zip(
            ("src", "dst", "weight", "aux"), jaxpr.invars) if v in live)

    def supports_sync_skipping(self) -> bool:
        return self.monoid.idempotent

    def is_batched_query(self) -> bool:
        """True iff this program declares the per-query convergence
        contract (``plug.protocols.BatchQueryCapable``)."""
        return self.num_queries > 0 and self.query_activity is not None
