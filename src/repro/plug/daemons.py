"""Accelerator backends (the *daemon* role, DESIGN.md §2).

Every daemon implements the same contract — ``bind(program, n)`` then
``run_blocks(state, aux, blockset, sel, record) -> (agg, cnt)`` — and the
middleware cannot tell them apart:

* ``VectorizedDaemon``  — all selected blocks stacked into one fused jit
  call (gather + Gen + segmented Merge + combine), active set padded to a
  power of two to bound recompiles.  ``kernel="reference"`` lowers pure
  jnp; ``kernel="pallas"`` runs the fused CSR tile program instead
  (graph/compaction.py + kernels.ops.csr_aggregate): the blockset is
  compacted once into dst-grouped tiles of ``CSRConfig.edge_tile`` edges
  (kernels/autotune.py), and block-granularity frontier selection maps
  onto the fixed tile layout as a per-edge mask — no padded-active-set
  buckets, one compiled shape for the whole run.
* ``BlockedDaemon``     — the paper's 5-step flow collapsed to 3:
  sequential Download → Compute → Upload per block.
* ``PipelinedDaemon``   — the 3-thread pipeline shuffle with rotating
  buffers (Sec. III-A); per-stage busy times land in the iteration record.
* ``NaiveDaemon``       — per-edge host loop; the "upper system without
  accelerator" baseline of Fig. 8.
* ``ShardedDaemon``     — all shards' block tensors stacked on a leading
  mesh axis and run as ONE ``shard_map`` program: gather + Gen +
  segmented Merge + a per-device partial combine, handing (m, N, K)
  partials to the upper system.  The extra ``bind_shards`` /
  ``run_all_shards`` capability is feature-detected by the middleware
  (``plug.protocols.ShardCapableDaemon``) and enables the device-
  resident fused drive loop (DESIGN.md §3.1).

New backends register with :func:`register_daemon`; see DESIGN.md §3 for
a worked "write your own daemon" example (a vmapped multi-device daemon
fits in ~20 lines).
"""
from __future__ import annotations

import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import pipeline as pl
from repro.core.blocks import BlockSet
from repro.core.pow2 import pad_pow2
from repro.core.template import VertexProgram
from repro.kernels.autotune import CSRConfig
from repro.plug.spans import build_span

KERNELS = ("reference", "pallas")


# --------------------------------------------------------------------------
# jitted block programs (shared by the vectorized / blocked / pipelined /
# sharded daemons; fixed shapes in, fixed shapes out, compiled once per
# bucket)
# --------------------------------------------------------------------------
def block_partials(program: VertexProgram, state, aux, vids, lsrc, ldst, w,
                   emask):
    """Reference block math: per-block Gen + block-local segmented Merge.

    Traceable (no jit of its own) so the same arithmetic serves the
    per-shard ``VectorizedDaemon`` and the ``shard_map`` body of
    ``ShardedDaemon`` — which is what makes the two paths bit-identical
    for idempotent monoids.
    """
    monoid = program.monoid
    k = program.state_width
    nb, vb = vids.shape
    b = lsrc.shape[1]
    vstate = state[vids]  # (nb, VB, K) gather
    vaux = aux[vids]
    s = jnp.take_along_axis(vstate, lsrc[..., None], axis=1)
    d = jnp.take_along_axis(vstate, ldst[..., None], axis=1)
    sa = jnp.take_along_axis(vaux, lsrc[..., None], axis=1)
    msgs = program.msg_gen(
        s.reshape(nb * b, k), d.reshape(nb * b, k),
        w.reshape(nb * b, 1), sa.reshape(nb * b, -1)).reshape(nb, b, k)
    msgs = jnp.where(emask[..., None], msgs, monoid.identity)
    seg = (ldst + jnp.arange(nb, dtype=ldst.dtype)[:, None] * vb).reshape(-1)
    partial = monoid.segment_reduce(msgs.reshape(nb * b, k), seg, nb * vb)
    counts = jax.ops.segment_sum(
        emask.reshape(-1).astype(jnp.int32), seg, nb * vb)
    # Empty segments: jax fills min/max with ±inf; the block-program
    # contract (kernels/ref.py, and the Pallas kernel's masked
    # reduction) uses the monoid identity — merge-equivalent, and what
    # keeps the reference and Pallas paths bit-identical per slot.
    partial = jnp.where((counts > 0)[:, None], partial, monoid.identity)
    return partial.reshape(nb, vb, k), counts.reshape(nb, vb)


def block_partials_pallas(program: VertexProgram, state, aux, vids, lsrc,
                          ldst, w, emask):
    """The Pallas edge-block kernel behind the same contract as
    :func:`block_partials` (traceable, no jit of its own) — so the one
    kernel dispatch serves the per-shard ``VectorizedDaemon`` and the
    ``shard_map`` body of ``ShardedDaemon``, keeping the two paths
    bit-identical per kernel for idempotent monoids."""
    from repro.kernels import ops as kops

    return kops.edge_block_aggregate(state, aux, vids, lsrc, ldst, w, emask,
                                     program=program)


# One dispatch table for every daemon that runs block programs: the
# traceable per-kernel implementations of the block_partials contract.
BLOCK_PARTIALS = {
    "reference": block_partials,
    "pallas": block_partials_pallas,
}


def make_block_fn(program: VertexProgram, *, kernel: str = "reference"):
    """Per-block Gen + block-local Merge → (nb, VB, K) partials."""
    if kernel not in KERNELS:
        raise ValueError(f"kernel must be one of {KERNELS}, got {kernel!r}")
    impl = BLOCK_PARTIALS[kernel]

    @jax.jit
    def block_fn(state, aux, vids, lsrc, ldst, w, emask):
        return impl(program, state, aux, vids, lsrc, ldst, w, emask)

    return block_fn


def make_combine_fn(program: VertexProgram, n: int):
    monoid = program.monoid

    @jax.jit
    def combine(partial, counts, vids):
        nbvb, k = partial.shape[0] * partial.shape[1], partial.shape[2]
        flat_ids = vids.reshape(-1)
        agg = monoid.segment_reduce(partial.reshape(nbvb, k), flat_ids, n)
        cnt = jax.ops.segment_sum(counts.reshape(-1), flat_ids, n)
        # message-free vertices read the monoid identity, not jax's ±inf
        # segment fill — the contract of kernels/ref.py and the CSR
        # kernel, and what the host streaming daemons (identity-
        # initialized aggregates) already produce.  Consumers mask via
        # has_msg = cnt > 0 either way.
        agg = jnp.where((cnt > 0)[:, None], agg, monoid.identity)
        return agg, cnt

    return combine


# pad_pow2 (imported above) pads selected block ids to the next power of
# two: the active-block count changes every iteration, and padding it
# bounds the number of distinct ``block_fn`` shapes — hence XLA
# recompiles — at ``log2(num_blocks) + 1`` per shard for the whole run.
# Padding entries are -1 and killed via ``emask`` in
# :func:`gather_blocks`.  The implementation lives in
# :mod:`repro.core.pow2`, shared with the serving layer's batch buckets.


def gather_blocks(bs: BlockSet, sel: np.ndarray):
    """Stacks the selected blocks; sel == -1 → dead block (emask False)."""
    live = sel >= 0
    idx = np.where(live, sel, 0)
    vids = bs.vids[idx]
    lsrc = bs.lsrc[idx]
    ldst = bs.ldst[idx]
    w = bs.weights[idx]
    emask = bs.emask[idx] & live[:, None]
    return (jnp.asarray(vids), jnp.asarray(lsrc), jnp.asarray(ldst),
            jnp.asarray(w), jnp.asarray(emask))


# --------------------------------------------------------------------------
# daemons
# --------------------------------------------------------------------------
def _stacked_field(st: dict, name: str):
    """Resolves a flat field name ("vids", "csr/rows") in a stacked pytree."""
    if name.startswith("csr/"):
        return st.get("csr", {}).get(name[4:])
    return st.get(name)


class VectorizedDaemon:
    """All active blocks in one fused jit call — the optimized path."""

    name = "vectorized"

    def __init__(self, kernel: str = "reference", csr_config=None):
        if kernel not in KERNELS:
            raise ValueError(f"kernel must be one of {KERNELS}, got {kernel!r}")
        self.kernel = kernel
        self.program = None
        self.block_fn = None
        self._combine_fn = None
        self._csr_config = csr_config or CSRConfig()
        self._csr_cache: dict = {}  # id(blockset) -> compiled CSR entry

    def bind(self, program: VertexProgram, num_vertices: int):
        self.program = program
        self.n = num_vertices
        self.block_fn = make_block_fn(program, kernel=self.kernel)
        self._combine_fn = make_combine_fn(program, num_vertices)
        # a rebind invalidates the compacted tiles
        self._csr_cache = {}
        return self

    def _csr_entry(self, blockset: BlockSet):
        key = id(blockset)
        entry = self._csr_cache.get(key)
        if entry is not None:
            return entry
        from repro.graph.compaction import tiles_from_blockset
        from repro.kernels import ops as kops

        cfg = self._csr_config
        ts = tiles_from_blockset(blockset, self.n, edge_tile=cfg.edge_tile,
                                 hub_threshold=cfg.hub_threshold)
        program, n = self.program, self.n

        @jax.jit
        def run(state, aux, blk_mask, csr, eblock):
            # block-granularity frontier selection as a per-edge mask:
            # padded slots carry eblock == -1 (wraps to the last block)
            # but their base emask is already False
            em = csr["emask"] & blk_mask[eblock]
            return kops.csr_aggregate(state, aux, dict(csr, emask=em),
                                      program=program, num_vertices=n)[:2]

        entry = {
            "csr": {k: jnp.asarray(v) for k, v in ts.arrays().items()},
            "eblock": jnp.asarray(ts.eblock),
            "num_blocks": blockset.num_blocks,
            "blockset": blockset,  # strong ref: id() keys must not alias
            "run": run,
        }
        self._csr_cache[key] = entry
        return entry

    def prune_block_caches(self, blocksets) -> None:
        """Drops per-blockset cache entries whose blockset is no longer
        bound — called by the middleware's structure-epoch daemon hook
        after a rebuild replaced some (but usually not all) blocksets.
        Surviving blocksets keep their compiled/compacted entries: the
        clean-tiles-untouched contract of dynamic graphs."""
        live = {id(bs) for bs in blocksets}
        self._csr_cache = {k: v for k, v in self._csr_cache.items()
                           if k in live}

    def _run_blocks_csr(self, state, aux, blockset, sel):
        entry = self._csr_entry(blockset)
        blk_mask = np.zeros(entry["num_blocks"], bool)
        blk_mask[sel] = True
        agg, cnt = entry["run"](jnp.asarray(state), jnp.asarray(aux),
                                jnp.asarray(blk_mask), entry["csr"],
                                entry["eblock"])
        return np.asarray(agg), np.asarray(cnt)

    def run_blocks(self, state, aux, blockset, sel, record):
        if self.kernel == "pallas":
            return self._run_blocks_csr(state, aux, blockset, sel)
        sel_p = pad_pow2(sel)
        arrs = gather_blocks(blockset, sel_p)
        partial, counts = self.block_fn(jnp.asarray(state), jnp.asarray(aux),
                                        *arrs)
        agg, cnt = self._combine_fn(partial, counts, arrs[0])
        return np.asarray(agg), np.asarray(cnt)


class ShardedDaemon(VectorizedDaemon):
    """Every shard's blocks as ONE sharded device program.

    All shards' block tensors are stacked on a leading axis (padded to a
    common block count), placed over a mesh axis with
    ``dist.sharding.sharding_for``, and one ``shard_map`` call per
    iteration does gather + Gen + segmented Merge *plus a per-device
    partial combine*: each device folds its shards' block partials into
    a single (N, K) aggregate before the (m, N, K) per-device partials
    are handed to the upper system's cross-device collective.

    The extra capability (``bind_shards`` / ``run_all_shards``) is what
    ``plug.Middleware`` feature-detects to enable the device-resident
    fused drive loop; ``run_blocks`` is inherited from
    :class:`VectorizedDaemon`, so with an upper system that cannot merge
    device partials (``upper="host"``) the same instance simply runs the
    classic per-shard path.

    ``kernel="pallas"`` runs the fused CSR tile program inside the
    ``shard_map`` body instead of the block program: ``bind_shards``
    compacts every shard's blockset into dst-grouped tiles
    (graph/compaction.py) with the daemon's ``CSRConfig``, pads the tile
    sets to a common envelope and stacks them over the mesh axis next to
    the block tensors.  Frontier skipping becomes a per-edge mask (the
    source's activity gathered with its state, folded into the kernel's
    edge mask) — trajectory-identical to block-granularity skipping for
    the idempotent monoids that drive frontiers — and ``blocks_run``
    counts active *tiles*.  The same ``kernels.ops.csr_aggregate`` dispatch
    serves the per-shard ``VectorizedDaemon``, so sharded and
    vectorized stay bit-identical per kernel for idempotent monoids.
    """

    name = "sharded"

    def __init__(self, kernel: str = "reference", mesh=None,
                 axis: str = "shard", csr_config=None):
        super().__init__(kernel, csr_config=csr_config)
        self.mesh = mesh
        self._auto_mesh = mesh is None
        self.axis = axis
        self._stacked = None
        self._stacked_digests: dict = {}
        self._donor = None
        self.adopted_fields = 0  # stacked tensors adopted from the donor
        self._blocksets = None
        self._partials_fns: dict = {}
        self.num_shards = 0
        self.m = 0
        # out-of-core state (bind_super_shards); None => resident mode
        self._oocore_config = None
        self._super_shards = None
        self.oocore_plan = None
        self.hot_stacked = None
        self.num_super_shards = 0
        # per-blockset compacted-tileset cache: a re-bind (migration
        # reorder, mutation with clean shards) reuses each surviving
        # BlockSet's tiles instead of recompacting — cumulative counters
        # are the observability seam the dynamic-graph tests pin
        self._tile_cache: dict = {}
        self.tiles_recut = 0
        self.tilesets_reused = 0
        # the per-edge gathers of the last CSR step built, as named by
        # the device scopes inside plug.gather
        self.edge_gathers: tuple[str, ...] = ()
        # masked execution (MaskCapableDaemon): vertex-level priority
        # buckets + Gen-invocation instrumentation.  ``instrument`` adds
        # a host callback to the cond-guarded shard body, so the counters
        # are honest proof a masked device never executed Gen (tests).
        self._bucket_k = 0
        self._bucket_cap = 32
        self.instrument = False
        self.gen_invocations = 0
        self.bucket_invocations = 0

    def share_from(self, donor: "ShardedDaemon | None"):
        """Declares a donor whose device-placed stacked block tensors
        this daemon may ADOPT at its next :meth:`bind_shards` instead of
        re-placing its own copies — the serving layer's seam: one graph,
        many per-family middlewares, one set of block tensors on the
        mesh.  Adoption is per-field and verified (same mesh/axis, and a
        content digest of the host-side stack must match the donor's),
        so a donor bound to a different graph, partitioning, or — after
        an elastic migration — a different mesh simply contributes
        nothing and this daemon places fresh tensors."""
        self._donor = donor
        return self

    def bind(self, program: VertexProgram, num_vertices: int):
        super().bind(program, num_vertices)
        # a rebind invalidates the stacked layout and compiled bodies —
        # and the tileset cache: tiles were compacted against the old
        # program/num_vertices (segment sizes, kernel config)
        self._stacked = None
        self._partials_fns = {}
        self._super_shards = None
        self.hot_stacked = None
        self.num_super_shards = 0
        self._tile_cache = {}
        return self

    @property
    def stacked(self):
        """The bound block tensors, stacked and device-placed (a pytree
        the fused drive loop threads through jit as arguments)."""
        return self._stacked

    def bind_shards(self, blocksets, *, mesh=None, axis=None):
        """Stacks + places every shard's block tensors over the mesh axis.

        Shards with fewer blocks are padded with dead blocks (``emask``
        all-False → identity partials, zero counts), so the stacked
        layout is rectangular and one compiled program serves all
        devices.
        """
        self._setup_shard_mesh(blocksets, mesh, axis)
        with build_span("plug.build.blocks"):
            host = self._host_block_stacks(blocksets)
        place = self._place_stack

        # Digest-verified adoption (see share_from): a field whose
        # host-side stack hashes identically to the donor's reuses the
        # donor's device-placed array instead of placing a duplicate.
        # Digests are recorded unconditionally so THIS daemon can serve
        # as a donor for the next family.
        donor = self._donor
        donor_ok = (donor is not None and donor is not self
                    and getattr(donor, "_stacked", None) is not None
                    and donor.mesh == self.mesh and donor.axis == self.axis)
        self._stacked_digests = {}
        self.adopted_fields = 0

        def place_or_adopt(name, a):
            with build_span("plug.build.place"):
                d = hashlib.sha1(
                    np.ascontiguousarray(a).tobytes()).hexdigest()
                self._stacked_digests[name] = d
                if donor_ok and donor._stacked_digests.get(name) == d:
                    adopted = _stacked_field(donor._stacked, name)
                    if (adopted is not None
                            and tuple(adopted.shape) == a.shape):
                        self.adopted_fields += 1
                        return adopted
                return place(a)

        self._stacked = {k: place_or_adopt(k, a) for k, a in host.items()}
        if self.kernel == "pallas":
            self._stacked["csr"] = self._stack_csr_tiles(blocksets,
                                                         place_or_adopt)
        # the transfers overlap the host work above; one wait at the end
        with build_span("plug.build.place"):
            jax.block_until_ready(self._stacked)
        self._partials_fns = {}
        self._oocore_config = None
        self._super_shards = None
        self.oocore_plan = None
        self.hot_stacked = None
        self.num_super_shards = 0
        return self

    def _setup_shard_mesh(self, blocksets, mesh, axis):
        """Shared head of bind_shards / bind_super_shards: validate the
        shard layout and resolve the mesh axis it is stacked over."""
        from repro.dist import sharding as shd

        if axis is not None:
            self.axis = axis
        if mesh is not None:
            self.mesh = mesh
            self._auto_mesh = False
        self._blocksets = list(blocksets)
        s = len(blocksets)
        vbs = {bs.vblock_size for bs in blocksets}
        bbs = {bs.block_size for bs in blocksets}
        if len(vbs) != 1 or len(bbs) != 1:
            raise ValueError(
                "bind_shards needs one (block, vblock) shape across shards; "
                f"got B={sorted(bbs)} VB={sorted(vbs)}")
        if self._auto_mesh or self.mesh is None:
            self.mesh = shd.divisor_mesh(s, self.axis)
        self.m = self.mesh.shape[self.axis]
        if s % self.m:
            raise ValueError(f"num_shards={s} not divisible by mesh axis "
                             f"{self.axis}={self.m}")
        self.num_shards = s

    def _host_block_stacks(self, blocksets):
        """Every shard's block tensors stacked on a leading shard axis,
        padded to a common block count with dead blocks — host numpy."""
        nb_max = max(bs.num_blocks for bs in blocksets)

        def stack(field, fill=0):
            arrs = []
            for bs in blocksets:
                a = getattr(bs, field)
                pad = nb_max - a.shape[0]
                if pad:
                    a = np.concatenate(
                        [a, np.full((pad,) + a.shape[1:], fill, a.dtype)])
                arrs.append(a)
            return np.stack(arrs)

        return {"vids": stack("vids"), "lsrc": stack("lsrc"),
                "ldst": stack("ldst"), "weights": stack("weights"),
                "emask": stack("emask", fill=False), "gsrc": stack("gsrc")}

    def _place_stack(self, a):
        """Place one host stack: shard axis 0 over the mesh axis."""
        from repro.dist import sharding as shd

        rules = {"shards": (self.axis,)}
        axes = ("shards",) + (None,) * (a.ndim - 1)
        return jax.device_put(
            a, shd.sharding_for(a.shape, axes, self.mesh, rules))

    @build_span("plug.build.tiles")
    def _stack_csr_tiles(self, blocksets, place):
        """Compacts every shard's blockset into CSR tiles, pads them to a
        common (nt, RT, ST) envelope and places the stacked arrays.

        Every shard is cut with the daemon's ``CSRConfig``, so a mid-run
        ``remesh`` re-stacks with the same tile size.

        Compaction is cached per BlockSet object: a re-bind that keeps
        some blocksets (migration reorder; mutation where clean shards'
        blocks are untouched) reuses their tiles and recompacts only the
        replaced ones (``tiles_recut`` / ``tilesets_reused`` count the
        split).  The cache holds the blockset strongly so an ``id()``
        key can never alias a collected object, and entries whose
        blockset left the binding are pruned.
        """
        from repro.graph.compaction import pad_tileset, tiles_from_blockset

        cfg = self._csr_config
        tiles = []
        for bs in blocksets:
            hit = self._tile_cache.get(id(bs))
            if hit is not None and hit[0] is bs:
                self.tilesets_reused += 1
                tiles.append(hit[1])
                continue
            t = tiles_from_blockset(bs, self.n, edge_tile=cfg.edge_tile,
                                    hub_threshold=cfg.hub_threshold)
            self.tiles_recut += 1
            self._tile_cache[id(bs)] = (bs, t)
            tiles.append(t)
        live = {id(bs) for bs in blocksets}
        self._tile_cache = {k: v for k, v in self._tile_cache.items()
                            if k in live}
        nt = max(t.num_tiles for t in tiles)
        rt = max(t.row_tile for t in tiles)
        st = max(t.src_tile for t in tiles)
        tiles = [pad_tileset(t, num_tiles=nt, row_tile=rt, src_tile=st)
                 for t in tiles]
        keys = tiles[0].arrays().keys()
        return {k: place("csr/" + k, np.stack([t.arrays()[k] for t in tiles]))
                for k in keys}

    # -- out-of-core (OutOfCoreCapable) ----------------------------------
    def bind_super_shards(self, blocksets, *, mesh=None, axis=None,
                          config=None):
        """Out-of-core binding: host column stacks + device hot set.

        Instead of placing the full stacked tensors on the mesh
        (:meth:`bind_shards`), the columns — padded blocks, or CSR tiles
        under ``kernel="pallas"`` — are kept in host numpy memory,
        reordered hottest-first by an access-frequency score (summed
        live out-degree, :func:`repro.graph.compaction.tile_access_scores`),
        and split per ``config`` (an ``OocoreConfig``): the hot prefix is
        placed once and stays device-resident; the cold remainder is cut
        into equal super-shards served by :meth:`upload_super_shard`.
        Super-shard width is planned against the *current* mesh size
        (``dist.fault.oocore_replan``), so a post-kill ``remesh``
        automatically re-plans ownership for the survivors' larger
        per-device column cost.
        """
        from repro.dist import fault as dist_fault
        from repro.graph.compaction import tile_access_scores
        from repro.oocore.supershard import build_super_shards

        if config is None:
            config = self._oocore_config
        if config is None:
            raise ValueError("bind_super_shards needs an OocoreConfig")
        self._setup_shard_mesh(blocksets, mesh, axis)
        if self.kernel == "pallas":
            fields = self._stack_csr_tiles(blocksets, lambda name, a: a)
        else:
            fields = self._host_block_stacks(blocksets)
        gsrc, emask = fields["gsrc"], fields["emask"]
        deg = np.bincount(gsrc[emask].ravel(), minlength=self.n)
        scores = tile_access_scores(gsrc, emask, deg)
        num_cols = scores.shape[1]
        col_bytes_shard = sum(
            int(a.itemsize) * int(np.prod(a.shape[2:], dtype=np.int64))
            for a in fields.values())
        plan = dist_fault.oocore_replan(num_cols, col_bytes_shard,
                                        self.num_shards, self.m, config)
        sss = build_super_shards(fields, scores, plan)
        self._super_shards = sss
        self._oocore_config = config
        self.oocore_plan = plan
        self.num_super_shards = plan.num_super_shards
        self.hot_stacked = (self._wrap_oocore(
            {k: self._place_stack(a) for k, a in sss.hot_host.items()})
            if sss.hot_host is not None else None)
        self._stacked = None
        self._stacked_digests = {}
        self.adopted_fields = 0
        self._partials_fns = {}
        return self

    def upload_super_shard(self, index: int):
        """``device_put`` cold super-shard ``index`` over the mesh axis;
        returns a pytree accepted by ``run_all_shards(stacked=...)``."""
        if self._super_shards is None:
            raise RuntimeError("upload_super_shard before bind_super_shards")
        host = self._super_shards.cold_hosts[index]
        return self._wrap_oocore(
            {k: self._place_stack(a) for k, a in host.items()})

    @property
    def super_shard_nbytes(self) -> int:
        """Host bytes of one cold super-shard (== one transfer)."""
        return (self._super_shards.super_shard_nbytes
                if self._super_shards is not None else 0)

    def super_shard_active(self, index: int, active) -> bool:
        """Does cold super-shard ``index`` touch any active source?

        The host-side twin of the kernels' per-edge ``emask &
        active[gsrc]`` frontier mask: if no live source of the group is
        active, every one of its edges is masked and its partial is
        exactly the monoid identity — the prefetch scheduler skips the
        upload *and* the compute without changing a bit of the result.
        """
        srcs = self._super_shards.cold_srcs[index]
        return bool(np.any(active[srcs])) if srcs.size else False

    def _wrap_oocore(self, placed):
        # run_all_shards dispatches the pallas body on a "csr" key in the
        # stacked pytree; block-kernel stacks pass through unwrapped
        return {"csr": placed} if self.kernel == "pallas" else placed

    def remesh(self, mesh, *, blocksets=None):
        """Re-stacks the bound block tensors over a (smaller) survivor
        mesh axis — the daemon half of checkpoint-free migration.

        Each survivor's slice of the stacked leading axis grows from
        ``s/m`` to ``s/m'`` shards; the compiled ``shard_map`` bodies
        were built for the old axis length and are dropped (the rebind
        clears them), so the fused drive loop's next step recompiles for
        the new mesh.  ``blocksets`` replaces the bound shard layout
        when the migration also re-partitioned or re-ordered shards
        (orphaned shards reassigned to survivors); omitted, the layout
        bound by the last ``bind_shards`` is re-placed as is.
        """
        if blocksets is None:
            blocksets = self._blocksets
            if blocksets is None:
                raise RuntimeError(
                    "ShardedDaemon.remesh called before bind_shards")
        if self._oocore_config is not None:
            # out-of-core binding: re-plan super-shard ownership for the
            # survivor mesh (per-device column cost grew), not just the
            # resident placement
            return self.bind_super_shards(blocksets, mesh=mesh,
                                          axis=self.axis,
                                          config=self._oocore_config)
        return self.bind_shards(blocksets, mesh=mesh, axis=self.axis)

    def _block_body(self, use_frontier: bool):
        """The per-device block compute: gather + Gen + segmented Merge +
        per-device combine.  ``act`` is this device's (N,) frontier (or
        None for non-frontier programs) — frontier slicing and masking
        policy live in the ``shard_map`` wrappers."""
        program = self.program
        monoid = program.monoid
        n = self.n
        k = program.state_width
        # one kernel dispatch with the per-shard daemons (BLOCK_PARTIALS),
        # so sharded and vectorized stay bit-identical per kernel
        partials_impl = BLOCK_PARTIALS[self.kernel]

        def compute(state, aux, act, vids, lsrc, ldst, w, emask, gsrc):
            # local slices (S/m, nb, …); state/aux replicated
            s_l, nb, vb = vids.shape
            b = lsrc.shape[2]
            if use_frontier:
                # same block granularity as the host path: a block with
                # no active source contributes nothing this iteration
                blk_active = jnp.any(act[gsrc] & emask, axis=2)
                emask = emask & blk_active[..., None]
            else:
                blk_active = jnp.any(emask, axis=2)
            partial, counts = partials_impl(
                program, state, aux,
                vids.reshape(s_l * nb, vb), lsrc.reshape(s_l * nb, b),
                ldst.reshape(s_l * nb, b), w.reshape(s_l * nb, b, 1),
                emask.reshape(s_l * nb, b))
            # per-device partial combine: all of this device's shard/block
            # partials fold to one (N, K) aggregate before the upper
            # system's cross-device collective
            flat_ids = vids.reshape(-1)
            agg = monoid.segment_reduce(partial.reshape(-1, k), flat_ids, n)
            cnt = jax.ops.segment_sum(counts.reshape(-1), flat_ids, n)
            # identity (not ±inf fill) at message-free vertices — the
            # same partials contract as the CSR kernel and the host
            # daemons, which keeps run_all_shards bit-identical across
            # kernels slot for slot
            agg = jnp.where((cnt > 0)[:, None], agg, monoid.identity)
            return (agg[None], cnt[None],
                    blk_active.sum(axis=1).astype(jnp.int32))

        return compute

    def _csr_body(self, use_frontier: bool):
        """The per-device CSR tile compute for ``kernel="pallas"``: the
        fused tile program + per-device combine, same output contract as
        :meth:`_block_body` (``blocks_run`` counts active tiles)."""
        from repro.kernels import ops as kops

        program = self.program
        n = self.n
        self.edge_gathers = kops.tile_gathers(program, frontier=use_frontier)

        def compute(state, aux, act, rows, seg, lsrc, svids, w, emask,
                    gsrc, gdst):
            # local slices (S/m, nt, …); state/aux replicated
            s_l, nt, et = lsrc.shape
            csr = {
                "rows": rows.reshape(s_l * nt, -1),
                "seg": seg.reshape(s_l * nt, et),
                "lsrc": lsrc.reshape(s_l * nt, et),
                "w": w.reshape(s_l * nt, et),
                "emask": emask.reshape(s_l * nt, et),
                "gsrc": gsrc.reshape(s_l * nt, et),
                "gdst": gdst.reshape(s_l * nt, et),
            }
            # per-device partial combine happens inside csr_aggregate:
            # every tile's row partials land in one (N, K) aggregate per
            # device.  The per-edge frontier filter (act) is
            # trajectory-identical to the block path's block-granularity
            # skipping for the idempotent monoids that drive frontiers
            agg, cnt, live = kops.csr_aggregate(
                state, aux, csr, program=program, num_vertices=n,
                active=act)
            with jax.named_scope("plug.gather"):
                tiles_run = live.reshape(s_l, nt).sum(axis=1).astype(
                    jnp.int32)
            return agg[None], cnt[None], tiles_run

        return compute

    def _partials_fn(self, use_frontier: bool, per_device: bool = False):
        key = (use_frontier, per_device)
        try:
            return self._partials_fns[key]
        except KeyError:
            pass
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as P

        compute = self._block_body(use_frontier)

        def body(state, aux, active, *arrs):
            # active is replicated (N,) — or this device's (1, N) backlog
            # row when the fused async loop drives per-device frontiers
            act = ((active[0] if per_device else active)
                   if use_frontier else None)
            return compute(state, aux, act, *arrs)

        spec = P(self.axis)
        rep = P()
        act_spec = spec if per_device else rep
        fn = shard_map(
            body, mesh=self.mesh,
            in_specs=(rep, rep, act_spec, spec, spec, spec, spec, spec, spec),
            out_specs=(spec, spec, spec), check_rep=False)
        self._partials_fns[key] = fn
        return fn

    def _csr_partials_fn(self, use_frontier: bool, per_device: bool = False):
        key = ("csr", use_frontier, per_device)
        try:
            return self._partials_fns[key]
        except KeyError:
            pass
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as P

        compute = self._csr_body(use_frontier)

        def body(state, aux, active, *arrs):
            act = ((active[0] if per_device else active)
                   if use_frontier else None)
            return compute(state, aux, act, *arrs)

        spec = P(self.axis)
        rep = P()
        act_spec = spec if per_device else rep
        fn = shard_map(
            body, mesh=self.mesh,
            in_specs=(rep, rep, act_spec) + (spec,) * 8,
            out_specs=(spec, spec, spec), check_rep=False)
        self._partials_fns[key] = fn
        return fn

    # -- masked execution (MaskCapableDaemon) -----------------------------
    def configure_buckets(self, k: int, cap: int = 32):
        """Arms the vertex-level priority buckets of the masked path.

        With ``k > 0`` a device whose ``run_mask`` slot is False still
        runs the out-edges of its top-``k`` residual vertices, capped at
        ``cap`` edges each (``kernels.edge_block.bucket_partials``), so
        skew *inside* a shard is exploited while the shard holds.  The
        src-sorted adjacency is compacted host-side once per binding and
        stacked next to the block tensors.  Only idempotent monoids
        qualify — bucket messages are folded into the held copy by
        re-combine, which must tolerate duplication — so ``k`` is forced
        to 0 otherwise.  Returns self.
        """
        k = int(k)
        cap = int(cap)
        if cap <= 0:
            raise ValueError(f"bucket cap must be positive, got {cap}")
        if self.program is not None and not self.program.monoid.idempotent:
            k = 0
        if self.n:
            k = min(k, self.n)
        if (k, cap) != (self._bucket_k, self._bucket_cap):
            # masked bodies bake the bucket shape in; drop only them
            self._partials_fns = {
                kk: v for kk, v in self._partials_fns.items()
                if not (isinstance(kk, tuple) and kk and kk[0] == "masked")}
        self._bucket_k, self._bucket_cap = k, cap
        if self._stacked is not None:
            if k > 0 and self._blocksets and "bucket" not in self._stacked:
                from repro.graph.compaction import src_adjacency

                adjs = []
                for bs in self._blocksets:
                    live = bs.emask.reshape(-1)
                    adjs.append(src_adjacency(
                        bs.gsrc.reshape(-1)[live],
                        bs.gdst.reshape(-1)[live],
                        bs.weights.reshape(-1)[live], self.n))
                ep = max(1, max(a[1].shape[0] for a in adjs))
                ptr = np.stack([a[0] for a in adjs])
                adst = np.stack([np.pad(a[1], (0, ep - a[1].shape[0]))
                                 for a in adjs])
                aw = np.stack([np.pad(a[2], (0, ep - a[2].shape[0]))
                               for a in adjs])
                # in-place on the SAME stacked dict: callers holding the
                # threaded pytree (the fused loops) see the bucket arrays
                # without re-capturing daemon.stacked
                self._stacked["bucket"] = {"ptr": self._place_stack(ptr),
                                           "dst": self._place_stack(adst),
                                           "w": self._place_stack(aw)}
            elif k == 0 and "bucket" in self._stacked:
                del self._stacked["bucket"]
        return self

    def reset_counters(self):
        """Zeroes the instrumentation counters (``instrument=True``)."""
        self.gen_invocations = 0
        self.bucket_invocations = 0

    def _count_gen(self):
        self.gen_invocations += 1

    def _count_bucket(self):
        self.bucket_invocations += 1

    def _masked_partials_fn(self, use_frontier: bool, per_device: bool,
                            csr: bool, has_bucket: bool):
        """The cond-guarded ``shard_map`` body of the masked path.

        Each device's scalar ``run_mask`` slot picks ONE branch of a
        real XLA conditional: the full shard compute, or a skip branch
        that costs nothing but the priority bucket (when armed) — this
        is what makes an async hold *free* instead of
        compute-then-discard.  For frontier-driven programs the
        predicate also folds in the all-inactive private-frontier fast
        path: an empty backlog row's identity output is exactly the
        device's fresh partial, so skipping it is lossless.
        """
        key = ("masked", csr, use_frontier, per_device, has_bucket,
               self._bucket_k, self._bucket_cap, bool(self.instrument))
        try:
            return self._partials_fns[key]
        except KeyError:
            pass
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as P

        from repro.kernels.edge_block import bucket_partials

        program = self.program
        monoid = program.monoid
        n = self.n
        k = program.state_width
        bucket_k, bucket_cap = self._bucket_k, self._bucket_cap
        instrument = bool(self.instrument)
        count_gen, count_bucket = self._count_gen, self._count_bucket
        compute = (self._csr_body if csr else self._block_body)(use_frontier)
        n_main = 8 if csr else 6

        def body(state, aux, active, run_mask, residual, *arrs):
            main, barrs = arrs[:n_main], arrs[n_main:]
            act = ((active[0] if per_device else active)
                   if use_frontier else None)
            s_l = main[0].shape[0]
            pred = run_mask[0]
            if use_frontier:
                pred = pred & jnp.any(act)

            def run(_):
                if instrument:
                    jax.debug.callback(count_gen)
                return compute(state, aux, act, *main)

            def skip(_):
                zeros = jnp.zeros((s_l,), jnp.int32)
                if has_bucket:
                    if instrument:
                        jax.debug.callback(count_bucket)
                    scores = (jnp.where(act, residual, -1.0)
                              if use_frontier else residual)
                    agg, cnt = bucket_partials(
                        state, aux, scores, *barrs, program=program,
                        k=bucket_k, cap=bucket_cap, num_vertices=n)
                    return agg[None], cnt[None], zeros
                ident = jnp.full((1, n, k), monoid.identity, jnp.float32)
                return ident, jnp.zeros((1, n), jnp.int32), zeros

            return jax.lax.cond(pred, run, skip, 0)

        spec = P(self.axis)
        rep = P()
        act_spec = spec if per_device else rep
        in_specs = ((rep, rep, act_spec, spec, rep)
                    + (spec,) * (n_main + (3 if has_bucket else 0)))
        fn = shard_map(body, mesh=self.mesh, in_specs=in_specs,
                       out_specs=(spec, spec, spec), check_rep=False)
        self._partials_fns[key] = fn
        return fn

    def run_all_shards(self, state, aux, active=None, *, run_mask=None,
                       residual=None, stacked=None):
        """Gen + Merge for ALL shards as one sharded program (traceable).

        Args:
          state, aux: the (replicated) global vertex table.
          active: frontier for block skipping — a replicated (N,) bool
            shared by every device, an (m, N) bool sharded over the mesh
            axis with each row that device's private frontier (the fused
            async loop's backlog), or None to run every block
            (non-frontier programs).
          run_mask: optional (m,) bool sharded over the mesh axis — the
            async predict half's verdict.  A False device's shard body
            is skipped behind ``lax.cond``: it contributes the monoid
            identity (zero counts, zero blocks run) — or its priority
            bucket's partial when :meth:`configure_buckets` armed one —
            without executing gather + Gen + Merge.
          residual: optional replicated (N,) f32 per-vertex last state
            change; the bucket score source (required when buckets are
            armed and ``run_mask`` is given).
          stacked: the ``self.stacked`` pytree threaded through as jit
            arguments (the fused drive loop does this so the block
            tensors are not baked into the compiled step as constants).
        Returns:
          ``(partials (m, N, K), counts (m, N), blocks_run (S,))`` —
          device-resident, leading axes sharded over the mesh axis.
        """
        st = self._stacked if stacked is None else stacked
        if st is None:
            raise RuntimeError(
                "ShardedDaemon.run_all_shards called before bind_shards")
        per_device = active is not None and getattr(active, "ndim", 1) == 2
        use_frontier = active is not None
        if active is None:
            active = jnp.zeros((1,), jnp.bool_)  # placeholder, unread
        csr = self.kernel == "pallas" and "csr" in st
        c = st["csr"] if csr else None
        main = ((c["rows"], c["seg"], c["lsrc"], c["svids"], c["w"],
                 c["emask"], c["gsrc"], c["gdst"]) if csr else
                (st["vids"], st["lsrc"], st["ldst"], st["weights"],
                 st["emask"], st["gsrc"]))
        if run_mask is None:
            fn = (self._csr_partials_fn if csr
                  else self._partials_fn)(use_frontier, per_device)
            return fn(state, aux, active, *main)
        bucket = st.get("bucket") if isinstance(st, dict) else None
        has_bucket = (bucket is not None and self._bucket_k > 0
                      and self.program.monoid.idempotent)
        if has_bucket and residual is None:
            raise ValueError("run_all_shards with armed buckets needs the "
                             "per-vertex residual for the bucket scores")
        if residual is None:
            residual = jnp.zeros((1,), jnp.float32)  # placeholder, unread
        fn = self._masked_partials_fn(use_frontier, per_device, csr,
                                      has_bucket)
        barrs = (bucket["ptr"], bucket["dst"], bucket["w"]) if has_bucket \
            else ()
        return fn(state, aux, active, run_mask, residual, *main, *barrs)


class _StreamingDaemon:
    """Shared Download→Compute→Upload loop for blocked/pipelined daemons."""

    pipelined = False

    def __init__(self, kernel: str = "reference"):
        if kernel not in KERNELS:
            raise ValueError(f"kernel must be one of {KERNELS}, got {kernel!r}")
        self.kernel = kernel
        self.program = None
        self.block_fn = None

    def bind(self, program: VertexProgram, num_vertices: int):
        self.program = program
        self.n = num_vertices
        self.block_fn = make_block_fn(program, kernel=self.kernel)
        return self

    def run_blocks(self, state, aux, bs, sel, record):
        monoid = self.program.monoid
        k = self.program.state_width
        agg = np.full((self.n, k), monoid.identity, np.float32)
        cnt = np.zeros(self.n, np.int64)
        state_dev = jnp.asarray(state)
        aux_dev = jnp.asarray(aux)

        def download(i: int, slot: dict):
            b = int(sel[i])
            slot["arrs"] = tuple(
                jnp.asarray(a[b : b + 1])
                for a in (bs.vids, bs.lsrc, bs.ldst, bs.weights, bs.emask)
            )
            slot["vids"] = bs.vids[b]

        def compute(i: int, slot: dict):
            partial, counts = self.block_fn(state_dev, aux_dev, *slot["arrs"])
            slot["partial"], slot["counts"] = partial, counts  # async refs

        def upload(i: int, slot: dict):
            partial = np.asarray(slot["partial"])[0]
            counts = np.asarray(slot["counts"])[0]
            vids = slot["vids"]
            # dispatch through the monoid (raises ValueError for a custom
            # monoid with no host scatter rule — regression: a bare else
            # silently max-merged unknown monoids into wrong aggregates)
            monoid.scatter_at(agg, vids, partial)
            np.add.at(cnt, vids, counts)

        if self.pipelined:
            res = pl.PipelinedExecutor(download, compute, upload).run(sel.size)
            record.setdefault("pipeline", []).append(res)
        else:
            res = pl.run_sequential(download, compute, upload, sel.size)
            record.setdefault("sequential", []).append(res)
        return agg, cnt.astype(np.int32)


class BlockedDaemon(_StreamingDaemon):
    name = "blocked"
    pipelined = False


class PipelinedDaemon(_StreamingDaemon):
    name = "pipelined"
    pipelined = True


class NaiveDaemon:
    """Per-edge Python loop on the host — deliberately slow; exists so the
    acceleration ratio of real daemons is measurable (Fig. 8)."""

    name = "naive"

    def bind(self, program: VertexProgram, num_vertices: int):
        self.program = program
        self.n = num_vertices
        return self

    def run_blocks(self, state, aux, bs, sel, record):
        prog = self.program
        monoid = prog.monoid
        k = prog.state_width
        agg = np.full((self.n, k), monoid.identity, np.float32)
        cnt = np.zeros(self.n, np.int64)
        for b in sel:
            b = int(b)
            for e in range(bs.block_size):
                if not bs.emask[b, e]:
                    continue
                s, d = int(bs.gsrc[b, e]), int(bs.gdst[b, e])
                msg = np.asarray(prog.msg_gen(
                    state[s : s + 1], state[d : d + 1],
                    bs.weights[b, e : e + 1], aux[s : s + 1]))[0]
                # dispatch through the monoid, not a name chain with a
                # silent max-merge fallback (same regression as
                # _StreamingDaemon.upload)
                monoid.scatter_at(agg, d, msg)
                cnt[d] += 1
        return agg, cnt.astype(np.int32)


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------
_DAEMONS: dict = {}


def register_daemon(name: str, factory) -> None:
    """Registers a daemon factory; ``factory(**kwargs)`` must return an
    object satisfying the :class:`~repro.plug.protocols.Daemon` protocol."""
    _DAEMONS[name] = factory


def get_daemon(name: str, **kwargs):
    """Builds a fresh (unbound) daemon by registry name."""
    try:
        factory = _DAEMONS[name]
    except KeyError:
        raise KeyError(f"unknown daemon {name!r}; registered: "
                       f"{sorted(_DAEMONS)}") from None
    return factory(**kwargs)


def daemon_names() -> tuple:
    return tuple(sorted(_DAEMONS))


register_daemon("vectorized", VectorizedDaemon)
register_daemon("reference", functools.partial(VectorizedDaemon,
                                               kernel="reference"))
register_daemon("pallas", functools.partial(VectorizedDaemon,
                                            kernel="pallas"))
register_daemon("sharded", ShardedDaemon)
register_daemon("blocked", BlockedDaemon)
register_daemon("pipelined", PipelinedDaemon)
register_daemon("naive", NaiveDaemon)
