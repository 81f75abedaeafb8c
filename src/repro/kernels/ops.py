"""jit'd public wrappers for the Pallas kernels.

Backend policy, decided in one place (``_default_interpret``): on a TPU
every Pallas kernel compiles through Mosaic; on any other backend it runs
in ``interpret=True`` mode, which checks correctness only.  Models and
benchmarks can also select the pure-jnp reference implementations
(``impl="reference"``), which is what the 512-device dry-run lowers.

On one TPU v5e chip the graph daemon's CSR tile kernel
(``csr_tile_pallas`` with ``gather="take"``, one-hot merge, 512-edge
tiles) has run inside the sharded fused drive loop at Graph500 SCALE 22,
and on a four-chip v5e mesh at SCALE 18 (``chip_smoke.py``).
``gather="onehot"`` and ``edge_block_aggregate``
have only been compiled for a described v5e
(``tests/test_chip_compile.py``); the flash-attention and SSD kernels
have only run in interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.template import VertexProgram
from repro.kernels import ref
from repro.kernels.edge_block import csr_tile_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.ssd_scan import ssd_chunk_pallas


def _default_interpret() -> bool:
    """Interpret Pallas kernels everywhere but on a TPU, never on one."""
    return jax.default_backend() != "tpu"


def _kmajor(table, ids):
    """``table[ids]`` as K-major blocks: (T, S) ids → (T, K, S)."""
    return jnp.swapaxes(table[ids], 1, 2)


def _one_column_at_least(aux):
    """A zero-width aux as one column of zeros: gathers and BlockSpecs
    need dims >= 1."""
    if aux.shape[1]:
        return aux
    return jnp.zeros((aux.shape[0], 1), aux.dtype)


def tile_gathers(program: VertexProgram,
                 frontier: bool = False) -> tuple[str, ...]:
    """The per-edge operands ``csr_aggregate``'s Pallas path gathers for
    ``program``: its src state always (``src+frontier`` where the
    source's activity rides in the same rows), its dst state and its aux
    only where ``msg_gen`` reads them (``VertexProgram.msg_gen_reads``).
    Each is gathered under its own scope inside ``plug.gather``."""
    reads = program.msg_gen_reads
    return (("src+frontier" if frontier else "src",)
            + tuple(op for op in ("dst", "aux") if op in reads))


# --------------------------------------------------------------------------
# edge_block
# --------------------------------------------------------------------------
def edge_block_aggregate(state, aux, vids, lsrc, ldst, w, emask, *,
                         program: VertexProgram, impl: str = "pallas"):
    """Agent-side wrapper: gathers the paired vertex blocks, then runs the
    daemon program over the block grid — the tile kernel with the vertex
    block as both its src and its row block, merging by ``ldst``."""
    if impl == "reference":
        return ref.edge_block_aggregate(state, aux, vids, lsrc, ldst, w,
                                        emask, program=program)
    aux = _one_column_at_least(aux)
    nb, b = lsrc.shape
    vstate = _kmajor(state, vids)  # agent "download" into block layout

    def edge_rows(x):
        return x.reshape(nb, 1, b)

    partial, counts = csr_tile_pallas(
        vstate, _kmajor(aux, vids), vstate, edge_rows(lsrc),
        edge_rows(ldst), edge_rows(w.astype(jnp.float32)),
        edge_rows(emask.astype(jnp.float32)), row_tile=vids.shape[1],
        program=program, gather="onehot", interpret=_default_interpret())
    return jnp.swapaxes(partial, 1, 2), counts.reshape(nb, -1)


# --------------------------------------------------------------------------
# CSR tile aggregation (the fused daemon program, DESIGN.md §3.1)
# --------------------------------------------------------------------------
def _csr_tiles_xla(vsrc, vaux, rowst, lsrc, seg, w, emask, *,
                   program: VertexProgram, merge: str, gather: str):
    """XLA twin of the Pallas CSR tile kernel: identical per-tile math,
    batched over the tile axis — the lowering the autotuner selects on
    backends where interpret-mode Pallas would pay per-op dispatch."""
    monoid = program.monoid
    k = program.state_width
    t, st, _ = vsrc.shape
    rt = rowst.shape[1]
    et = lsrc.shape[1]
    if gather == "onehot":
        soh = (lsrc[..., None]
               == jnp.arange(st, dtype=lsrc.dtype)[None, None, :]
               ).astype(jnp.float32)
        roh_f = (seg[..., None]
                 == jnp.arange(rt, dtype=seg.dtype)[None, None, :]
                 ).astype(jnp.float32)
        s = jnp.einsum("tes,tsk->tek", soh, vsrc)
        sa = jnp.einsum("tes,tsa->tea", soh, vaux)
        d = jnp.einsum("ter,trk->tek", roh_f, rowst)
    else:
        s = jnp.take_along_axis(vsrc, lsrc[..., None], axis=1)
        sa = jnp.take_along_axis(vaux, lsrc[..., None], axis=1)
        d = jnp.take_along_axis(rowst, seg[..., None], axis=1)
    msgs = program.msg_gen(
        s.reshape(t * et, k), d.reshape(t * et, k),
        w.reshape(t * et, 1), sa.reshape(t * et, -1)).reshape(t, et, k)
    msgs = jnp.where(emask[..., None], msgs, monoid.identity)
    if merge == "sorted":
        # seg is sorted tile-local — a single flat sorted-segment reduce
        segg = (seg + jnp.arange(t, dtype=seg.dtype)[:, None] * rt
                ).reshape(-1)
        partial = monoid.segment_reduce(msgs.reshape(t * et, k), segg,
                                        t * rt)
        counts = jax.ops.segment_sum(
            emask.reshape(-1).astype(jnp.int32), segg, t * rt)
        partial = jnp.where((counts > 0)[:, None], partial,
                            monoid.identity)
        return partial.reshape(t, rt, k), counts.reshape(t, rt)
    # merge == "onehot": the MXU form, kept bit-identical to the kernel
    roh = (seg[..., None] == jnp.arange(rt, dtype=seg.dtype)[None, None, :])
    live = roh & emask[..., None]  # (T, ET, RT)
    if monoid.name == "sum":
        partial = jnp.einsum("ter,tek->trk", live.astype(jnp.float32),
                             msgs)
    elif monoid.name in ("min", "max", "or"):
        sel = jnp.swapaxes(live, 1, 2)  # (T, RT, ET)
        cols = []
        for i in range(k):  # K is small & static
            mat = jnp.where(sel, msgs[..., i][:, None, :], monoid.identity)
            red = (jnp.min(mat, axis=2) if monoid.name == "min"
                   else jnp.max(mat, axis=2))
            cols.append(red)
        partial = jnp.stack(cols, axis=2)
    else:
        raise ValueError(
            f"monoid {monoid.name!r} has no CSR merge rule; known: "
            "['max', 'min', 'or', 'sum']")
    counts = live.sum(axis=1).astype(jnp.int32)
    return partial, counts


def csr_aggregate(state, aux, csr: dict, *, program: VertexProgram,
                  num_vertices: int, config, active=None):
    """Fused gather + Gen + segmented Merge over CSR tiles → (N, K) agg.

    Args:
      state (N, K) f32, aux (N, A) f32 — the shard vertex table.
      csr: dict of per-tile arrays with leading tile axis T (the
        ``CSRTileSet.arrays()`` layout): rows (T, RT), seg/lsrc/gsrc/gdst
        (T, ET), svids (T, ST), w (T, ET), emask (T, ET) bool.
        ``emask`` may already carry per-edge frontier filtering.
      config: a ``kernels.autotune.CSRConfig`` (or any object with
        edge_tile/lowering/merge/gather attributes).  ``merge="flat"``
        skips per-tile partials entirely: one sorted-segment reduce by
        global dst straight to (N, K) — XLA only; the tiled variants run
        the tile body (Pallas kernel or its XLA twin) and finish split
        hub rows with a cross-tile segmented combine.
      active: optional (N,) bool frontier: only arcs out of an active
        vertex send.  The Pallas tiled path gathers it as one more row
        of the src state (one pass over the src ids); the other paths
        mask ``emask & active[gsrc]`` first.
    Returns:
      agg (N, K) f32 — merged messages; vertices with no message read
      the monoid identity.  cnt (N,) i32 — messages per vertex.
      live (T,) bool — the tiles with a live arc.
    Traceable (no jit of its own), so the same dispatch serves the
    per-shard daemon and the ``shard_map`` body of the sharded daemon.
    """
    monoid = program.monoid
    k = program.state_width
    n = num_vertices
    emask = csr["emask"]
    w = csr["w"].astype(jnp.float32)
    fold = (active is not None and config.lowering == "pallas"
            and config.merge != "flat")
    if not fold:
        with jax.named_scope("plug.gather"):
            if active is not None:
                with jax.named_scope("frontier"):
                    emask = emask & active[csr["gsrc"]]
            live = jnp.any(emask, axis=1)
    if config.merge == "flat":
        aux = _one_column_at_least(aux)
        gsrc = csr["gsrc"].reshape(-1)
        gdst = csr["gdst"].reshape(-1)
        emf = emask.reshape(-1)
        msgs = program.msg_gen(state[gsrc], state[gdst],
                               w.reshape(-1, 1), aux[gsrc])
        msgs = jnp.where(emf[:, None], msgs, monoid.identity)
        # dead/padded slots carry dst 0: they merge an identity into
        # vertex 0 — a no-op, same convention as the block layout
        agg = monoid.segment_reduce(msgs, gdst, n)
        cnt = jax.ops.segment_sum(emf.astype(jnp.int32), gdst, n)
    else:
        if config.lowering == "pallas":
            t, et = csr["lsrc"].shape

            def edge_rows(x):
                return x.reshape(t, 1, et)

            # "take" gathers per edge from the global ids (same values as
            # svids[lsrc] / rows[seg] on live slots); "onehot" hands the
            # kernel the compact blocks
            src_ids, row_ids = ((csr["gsrc"], csr["gdst"])
                                if config.gather == "take"
                                else (csr["svids"], csr["rows"]))
            # only what msg_gen reads is gathered: the kernel hands it
            # zeros in place of an absent aux or row operand
            gathers = tile_gathers(program, frontier=fold)
            aux_e = row_e = None
            with jax.named_scope("plug.gather"):
                with jax.named_scope(gathers[0]):
                    table = state
                    if fold:
                        # the activity bit rides as column K of the
                        # src rows; the kernel splits it off
                        table = jnp.concatenate(
                            [state, active[:, None].astype(state.dtype)],
                            axis=1)
                    src_e = _kmajor(table, src_ids)
                if "dst" in gathers:
                    with jax.named_scope("dst"):
                        row_e = _kmajor(state, row_ids)
                if "aux" in gathers:
                    with jax.named_scope("aux"):
                        aux_e = _kmajor(_one_column_at_least(aux), src_ids)
                edges = (edge_rows(csr["lsrc"]), edge_rows(csr["seg"]),
                         edge_rows(w), edge_rows(emask.astype(jnp.float32)))
            partial, counts = csr_tile_pallas(
                src_e, aux_e, row_e, *edges,
                row_tile=csr["rows"].shape[1], program=program,
                gather=config.gather, interpret=_default_interpret(),
                frontier=fold)
            if fold:
                # exact: counts sums the kernel's live (edge, row) pairs
                with jax.named_scope("plug.gather"):
                    live = jnp.any(counts > 0, axis=(1, 2))
            with jax.named_scope("plug.combine.tiles"):
                partial = jnp.swapaxes(partial, 1, 2)
        else:
            aux = _one_column_at_least(aux)
            partial, counts = _csr_tiles_xla(
                state[csr["svids"]], aux[csr["svids"]], state[csr["rows"]],
                csr["lsrc"], csr["seg"], w, emask, program=program,
                merge=config.merge, gather=config.gather)
        with jax.named_scope("plug.combine.tiles"):
            # cross-tile combine: finishes split hub rows and folds every
            # tile's row partials into the shard aggregate
            rows = csr["rows"].reshape(-1)
            agg = monoid.segment_reduce(partial.reshape(-1, k), rows, n)
            cnt = jax.ops.segment_sum(counts.reshape(-1), rows, n)
    with jax.named_scope("plug.combine.tiles"):
        agg = jnp.where((cnt > 0)[:, None], agg, monoid.identity)
    return agg, cnt, live


# --------------------------------------------------------------------------
# flash attention
# --------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("causal", "impl", "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True, impl: str = "pallas",
                    block_q: int = 128, block_k: int = 128):
    if impl == "reference":
        return ref.flash_attention(q, k, v, causal=causal)
    return flash_attention_pallas(q, k, v, causal=causal, block_q=block_q,
                                  block_k=block_k,
                                  interpret=_default_interpret())


# --------------------------------------------------------------------------
# SSD scan (Mamba2)
# --------------------------------------------------------------------------
def ssd_scan(x, dt, a, b_mat, c_mat, *, chunk: int = 64, impl: str = "pallas"):
    """Full SSD: within-chunk kernel + cross-chunk jnp recurrence.

    x (B, S, H, P), dt (B, S, H), a (H,), b_mat/c_mat (B, S, G, N).
    Returns y (B, S, H, P).
    """
    if impl == "reference":
        return ref.ssd_scan_chunked_ref(x, dt, a, b_mat, c_mat, chunk=chunk)
    bsz, s, h, p = x.shape
    g, n = b_mat.shape[2], b_mat.shape[3]
    rep = h // g
    assert s % chunk == 0
    nc = s // chunk
    bh = jnp.repeat(b_mat, rep, axis=2).astype(jnp.float32)
    ch = jnp.repeat(c_mat, rep, axis=2).astype(jnp.float32)

    def to_chunks(t):
        return t.reshape(bsz, nc, chunk, *t.shape[2:])

    xc = to_chunks(x.astype(jnp.float32))
    dtc = to_chunks(dt.astype(jnp.float32))
    bc, cc = to_chunks(bh), to_chunks(ch)

    y_local, states, decays, gates = ssd_chunk_pallas(
        xc, dtc, a, bc, cc, interpret=_default_interpret())

    # Cross-chunk recurrence (the agent-side combine).
    def body(hstate, inp):
        st, dec = inp  # (B,H,N,P), (B,H)
        hnext = hstate * dec[..., None, None] + st
        return hnext, hstate  # emit carry-in for this chunk

    h0 = jnp.zeros((bsz, h, n, p), jnp.float32)
    _, carry_in = jax.lax.scan(
        body, h0, (jnp.moveaxis(states, 1, 0), jnp.moveaxis(decays, 1, 0)))
    carry_in = jnp.moveaxis(carry_in, 0, 1)  # (B, NC, H, N, P)

    y_carry = jnp.einsum("bclhn,bclh,bchnp->bclhp",
                         cc, gates, carry_in)
    y = (y_local + y_carry).reshape(bsz, s, h, p)
    return y.astype(x.dtype)
