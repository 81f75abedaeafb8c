"""Pallas TPU kernel: the GX-Plug daemon tile program.

One grid step processes one edge tile with its paired compact vertex
blocks resident in VMEM (paper Sec. II-B: "each edge block is associated
with a paired vertex block").  TPU adaptation (DESIGN.md §2):

* gathers through tile-local indices become **one-hot matmuls** on the
  MXU (``gather="onehot"``), not HBM random access, bit-exact through a
  three-piece bf16 split (``_onehot_dot``); ``gather="take"`` instead
  gathers per edge in XLA ahead of the kernel (Mosaic's in-kernel
  dynamic gather spans one vreg only) and the kernel fuses Gen + Merge;
* the per-destination MSGMerge becomes a dense masked reduction:
  sum-monoid → one-hot matmul (MXU); min/max/or → masked VPU reduction
  per state column;
* the Pallas grid pipeline overlaps the HBM→VMEM DMA of tile *i+1* with
  compute on tile *i* — the hardware form of the paper's pipeline shuffle.

Layout: every operand is K-major — vertex blocks ``(T, K, S)`` and
per-edge vectors ``(T, 1, E)`` — so the long axis sits on the 128 lanes.
A ``(T, S, K)`` or ``(T, E, 1)`` operand with small K would be padded to
128 lanes in HBM (up to 128x its size).

VMEM per grid step (f32, ET = RT = ST = 512, K ≤ 8): the (RT, ET) row
one-hot and one (ET, RT) masked column at a time ≈ 2 MiB, plus the
double-buffered blocks — well inside v5e's 16 MiB default scoped VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.core.template import VertexProgram

_MERGE_MONOIDS = ("max", "min", "or", "sum")
_TOP16 = -(1 << 16)  # 0xFFFF0000: sign, exponent, top 7 mantissa bits


def _onehot_dot(onehot, table):
    """``onehot (M, S) @ table (S, K)`` on the MXU in three bf16 passes.

    Each bf16 piece is the top 8 significant bits of what is left of the
    f32 value, so the three pieces carry all 24 and add up to it exactly.
    A gather (one 1 per one-hot row) is therefore bit-exact, and a
    scatter-add (many 1s per row) is an f32 sum.  A HIGHEST-precision
    f32 dot would round the largest finite f32 (the ``INF`` of the
    shortest-path programs) up to inf, and 0·inf poisons the other rows.
    The one-hot is the streamed operand and the narrow table the
    stationary one, which keeps the MXU's weight loads to S/128.
    """
    oh = onehot.astype(jnp.bfloat16)
    out = None
    rest = table
    for _ in range(3):
        bits = lax.bitcast_convert_type(rest, jnp.int32) & _TOP16
        piece = lax.bitcast_convert_type(bits, jnp.float32)
        rest = rest - piece
        part = jnp.dot(oh, piece.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
        out = part if out is None else out + part
    return out


def _tile_kernel(*refs, program: VertexProgram, gather: str,
                 has_aux: bool, has_row: bool, frontier: bool):
    """One grid step = one edge tile: gather, Gen per edge, Merge per row.

    ``refs`` are the operands of :func:`csr_tile_pallas` that were given
    (``aux`` and ``row`` may be absent), then the two outputs.  ``seg``
    is the tile-local row of every edge.  Where it is sorted and every
    low-degree row lives inside one tile (degree bucketing), the per-row
    merge here is final for those rows; split hub rows are finished by
    the cross-tile segmented combine in ops.csr_aggregate.  With
    ``frontier`` the src operand carries one more row after the K state
    rows, the source's activity (1.0 or 0.0), and an edge is live where
    both it and ``emask`` are.
    """
    refs = list(refs)
    src_ref = refs.pop(0)
    aux_ref = refs.pop(0) if has_aux else None
    row_ref = refs.pop(0) if has_row else None
    lsrc_ref, seg_ref, w_ref, emask_ref, partial_ref, counts_ref = refs
    monoid = program.monoid
    if monoid.name not in _MERGE_MONOIDS:
        # trace-time check, same contract as Monoid.segment_reduce /
        # scatter_at: an unknown monoid must raise, never silently
        # merge with the wrong operator
        raise ValueError(
            f"monoid {monoid.name!r} has no Pallas merge rule; known: "
            f"{list(_MERGE_MONOIDS)}")
    k = program.state_width
    seg = seg_ref[...]                      # (1, ET) i32
    emask_f = emask_ref[...]                # (1, ET) f32
    et = seg.shape[1]
    rt = partial_ref.shape[1]
    seg_col = seg.T                         # (ET, 1)
    row_oh = seg_col == lax.broadcasted_iota(jnp.int32, (et, rt), 1)
    # an operand msg_gen does not read is not handed in: it reaches
    # msg_gen as zeros of the shape it would have had
    sa = jnp.zeros((et, max(program.aux_width, 1)), jnp.float32)
    d = jnp.zeros((et, k), jnp.float32)
    if gather == "onehot":
        st = src_ref.shape[1]
        src_oh = lsrc_ref[...].T == lax.broadcasted_iota(
            jnp.int32, (et, st), 1)                         # (ET, ST)
        s = _onehot_dot(src_oh, src_ref[...].T)             # (ET, K[+1])
        if has_aux:
            sa = _onehot_dot(src_oh, aux_ref[...].T)        # (ET, A)
        if has_row:
            d = _onehot_dot(row_oh, row_ref[...].T)         # (ET, K)
    else:  # "take": gathered per edge ahead of the kernel
        s = src_ref[...].T
        if has_aux:
            sa = aux_ref[...].T
        if has_row:
            d = row_ref[...].T
    if frontier:
        # split after the gather: one row per edge either way
        emask_f = emask_f * s[:, k:].T                      # (1, ET)
        s = s[:, :k]

    msgs = program.msg_gen(s, d, w_ref[...].T, sa)          # (ET, K)

    sel = row_oh & (emask_f.T > 0.0)                        # (ET, RT)
    if monoid.name == "sum":
        live_t = ((seg == lax.broadcasted_iota(jnp.int32, (rt, et), 0))
                  & (emask_f > 0.0))                        # (RT, ET)
        # dead slots may hold any message; zero them so no 0·inf reaches
        # the MXU accumulator
        msgs = jnp.where(emask_f.T > 0.0, msgs, 0.0)
        partial = _onehot_dot(live_t, msgs).T               # (K, RT)
    else:
        # masked reduction per column over the (ET, RT) select matrix
        # ("or" over {0,1} indicators is exactly max — core.template.OR)
        red = jnp.min if monoid.name == "min" else jnp.max
        partial = jnp.concatenate(
            [red(jnp.where(sel, msgs[:, i:i + 1], monoid.identity),
                 axis=0, keepdims=True) for i in range(k)], axis=0)
    counts = jnp.sum(sel.astype(jnp.float32), axis=0, keepdims=True)

    partial_ref[...] = partial.astype(partial_ref.dtype)
    counts_ref[...] = counts.astype(jnp.int32)


def _tile_spec(shape):
    """Block of one tile: the leading tile axis squeezed, the rest whole."""
    nd = len(shape)
    return pl.BlockSpec((None,) + tuple(shape[1:]),
                        lambda i: (i,) + (0,) * (nd - 1))


def csr_tile_pallas(src, aux, row, lsrc, seg, w, emask_f32, *,
                    row_tile: int, program: VertexProgram, gather: str,
                    interpret: bool, frontier: bool = False):
    """Runs the fused tile program over all T tiles (one per grid step).

    Args (K-major, see the module docstring):
      gather="onehot": src (T, K, ST), aux (T, A, ST) — per-tile src
        blocks; row (T, K, RT) — per-tile row (dst) state blocks.
      gather="take": src (T, K, ET), aux (T, A, ET), row (T, K, ET) —
        the same values already gathered per edge.
      aux and row may be None where ``msg_gen`` does not read them
        (``VertexProgram.msg_gen_reads``): no operand, no DMA, and
        ``msg_gen`` gets zeros of the (ET, A) / (ET, K) shape instead.
      lsrc/seg (T, 1, ET) i32, w (T, 1, ET) f32, emask_f32 (T, 1, ET) f32.
      row_tile: RT, the row-block width of the outputs.
      frontier: src holds K + 1 rows, the last the source's activity
        (1.0 or 0.0), which the kernel multiplies into ``emask_f32``; a
        frontier step gathers it with the state in one pass.
    Returns: partial (T, K, RT) f32, counts (T, 1, RT) i32 — per-tile row
    partials, the monoid identity / zero at rows no live edge reaches;
    split hub rows still need the cross-tile combine.
    """
    if gather not in ("take", "onehot"):
        raise ValueError(f"gather must be 'take' or 'onehot', got {gather!r}")
    t, k = src.shape[0], program.state_width
    kern = functools.partial(_tile_kernel, program=program, gather=gather,
                             has_aux=aux is not None,
                             has_row=row is not None, frontier=frontier)
    out_shape = [jax.ShapeDtypeStruct((t, k, row_tile), jnp.float32),
                 jax.ShapeDtypeStruct((t, 1, row_tile), jnp.int32)]
    args = tuple(a for a in (src, aux, row, lsrc, seg, w, emask_f32)
                 if a is not None)
    return pl.pallas_call(
        kern,
        grid=(t,),
        in_specs=[_tile_spec(a.shape) for a in args],
        out_specs=[_tile_spec(o.shape) for o in out_shape],
        out_shape=out_shape,
        interpret=interpret,
        name="csr_tile",
    )(*args)


# --------------------------------------------------------------------------
# Vertex-level priority buckets: the skip-branch program of the masked
# sharded daemon (DESIGN.md §3.1).  A device predicted to hold runs ONLY
# the out-edges of its top-k residual vertices — (k × cap) edges per
# shard, a fixed compiled shape — instead of its full gather+Gen+Merge.
# --------------------------------------------------------------------------
def bucket_partials(state, aux, scores, ptr, adst, aw, *,
                    program: VertexProgram, k: int, cap: int,
                    num_vertices: int):
    """Gen + Merge over the top-``k`` score vertices' out-edges.

    Traceable (runs inside the masked ``shard_map`` body's skip branch,
    under ``lax.cond``).  The adjacency is the src-sorted CSR layout of
    :func:`repro.graph.compaction.src_adjacency`, stacked per local
    shard; each selected vertex contributes at most ``cap`` edges (a
    hub's tail is regenerated by the device's next full refresh — the
    backlog is never cleared by a bucket run, so capping loses nothing).
    Only idempotent monoids may consume the result: bucket messages are
    folded into the device's *held* copy by re-combine, which must
    tolerate duplication.

    Args:
      state (N, K), aux (N, A): the replicated vertex table.
      scores (N,) f32: per-vertex priority (last residual, with
        non-frontier vertices already masked to -1); only strictly
        positive scores run.
      ptr (s_l, N+1) i32, adst (s_l, Ep) i32, aw (s_l, Ep) f32: the
        local shards' src-CSR adjacency.
    Returns ``(agg (N, K) f32, cnt (N,) i32)`` — identity / zero at
    untouched vertices, same partials contract as the full-shard bodies.
    """
    monoid = program.monoid
    s_l = ptr.shape[0]
    ep = adst.shape[1]
    kk = program.state_width
    if ep == 0 or k <= 0:
        return (jnp.full((num_vertices, kk), monoid.identity, jnp.float32),
                jnp.zeros((num_vertices,), jnp.int32))
    top_vals, top = jax.lax.top_k(scores, k)          # (k,)
    vmask = top_vals > 0.0
    start = ptr[:, top]                               # (s_l, k)
    end = ptr[:, top + 1]
    idx = start[..., None] + jnp.arange(cap, dtype=start.dtype)
    valid = (idx < end[..., None]) & vmask[None, :, None]  # (s_l, k, cap)
    flat = jnp.clip(idx, 0, ep - 1).reshape(s_l, k * cap)
    d_ids = jnp.take_along_axis(adst, flat, axis=1)   # (s_l, k*cap)
    wts = jnp.take_along_axis(aw, flat, axis=1)
    src_ids = jnp.broadcast_to(top[None, :, None],
                               (s_l, k, cap)).reshape(-1)
    d_flat = d_ids.reshape(-1)
    msgs = program.msg_gen(state[src_ids], state[d_flat],
                           wts.reshape(-1, 1), aux[src_ids])  # (s_l*k*cap, K)
    # dead slots route to an extra segment that is sliced away — the
    # live ones merge with the same operator as every other kernel
    vflat = valid.reshape(-1)
    seg = jnp.where(vflat, d_flat, num_vertices)
    agg = monoid.segment_reduce(msgs, seg, num_vertices + 1)[:num_vertices]
    cnt = jax.ops.segment_sum(vflat.astype(jnp.int32), seg,
                              num_vertices + 1)[:num_vertices]
    agg = jnp.where((cnt > 0)[:, None], agg, monoid.identity)
    return agg.astype(jnp.float32), cnt
