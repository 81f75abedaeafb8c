"""jit'd public wrappers for the Pallas kernels.

Backend policy, decided in one place (``_default_interpret``): on a TPU
every Pallas kernel compiles through Mosaic; on any other backend it runs
in ``interpret=True`` mode, which checks correctness only.  Models and
benchmarks can also select the pure-jnp reference implementations
(``impl="reference"``), which is what the 512-device dry-run lowers.

The graph daemon has one CSR tile path on every backend
(``csr_aggregate``): ``take`` gathers per edge, the ``csr_tile_pallas``
kernel with its one-hot merge, the cross-tile combine.  On one TPU v5e
chip it has run inside the sharded fused drive loop at Graph500 SCALE 22
with 512-edge tiles, and on a four-chip v5e mesh at SCALE 18
(``chip_smoke.py``).  The kernel's ``gather="onehot"`` mode, used only
by ``edge_block_aggregate``, has only been compiled for a described v5e
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
    """The per-edge operands ``csr_aggregate`` gathers for
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
def csr_aggregate(state, aux, csr: dict, *, program: VertexProgram,
                  num_vertices: int, active=None):
    """Fused gather + Gen + segmented Merge over CSR tiles → (N, K) agg.

    The one CSR tile path: ``take`` gathers per edge in XLA (only the
    operands ``msg_gen`` reads, :func:`tile_gathers`), the fused tile
    kernel (``csr_tile_pallas``: Gen + one-hot/masked Merge per tile),
    then the cross-tile segmented combine that finishes split hub rows.

    Args:
      state (N, K) f32, aux (N, A) f32 — the shard vertex table.
      csr: dict of per-tile arrays with leading tile axis T (the
        ``CSRTileSet.arrays()`` layout): rows (T, RT), seg/lsrc/gsrc/gdst
        (T, ET), w (T, ET), emask (T, ET) bool.
        ``emask`` may already carry per-edge frontier filtering.
      active: optional (N,) bool frontier: only arcs out of an active
        vertex send.  It is gathered as one more row of the src state
        (one pass over the src ids) and the kernel folds it into its
        edge mask.
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
    fold = active is not None
    t, et = csr["lsrc"].shape
    w = csr["w"].astype(jnp.float32)

    def edge_rows(x):
        return x.reshape(t, 1, et)

    # only what msg_gen reads is gathered: the kernel hands it zeros in
    # place of an absent aux or row operand
    gathers = tile_gathers(program, frontier=fold)
    aux_e = row_e = None
    with jax.named_scope("plug.gather"):
        with jax.named_scope(gathers[0]):
            table = state
            if fold:
                # the activity bit rides as column K of the src rows;
                # the kernel splits it off
                table = jnp.concatenate(
                    [state, active[:, None].astype(state.dtype)], axis=1)
            src_e = _kmajor(table, csr["gsrc"])
        if "dst" in gathers:
            with jax.named_scope("dst"):
                row_e = _kmajor(state, csr["gdst"])
        if "aux" in gathers:
            with jax.named_scope("aux"):
                aux_e = _kmajor(_one_column_at_least(aux), csr["gsrc"])
        edges = (edge_rows(csr["lsrc"]), edge_rows(csr["seg"]),
                 edge_rows(w), edge_rows(csr["emask"].astype(jnp.float32)))
    partial, counts = csr_tile_pallas(
        src_e, aux_e, row_e, *edges, row_tile=csr["rows"].shape[1],
        program=program, gather="take", interpret=_default_interpret(),
        frontier=fold)
    with jax.named_scope("plug.gather"):
        # exact: counts sums the kernel's live (edge, row) pairs
        live = jnp.any(counts > 0, axis=(1, 2))
    with jax.named_scope("plug.combine.tiles"):
        # cross-tile combine: finishes split hub rows and folds every
        # tile's row partials into the shard aggregate
        partial = jnp.swapaxes(partial, 1, 2)
        rows = csr["rows"].reshape(-1)
        agg = monoid.segment_reduce(partial.reshape(-1, k), rows, n)
        cnt = jax.ops.segment_sum(counts.reshape(-1), rows, n)
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
