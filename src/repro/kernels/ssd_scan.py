"""Pallas TPU kernel: Mamba2 SSD within-chunk block (state-space duality).

SSD splits the linear recurrence into (i) a quadratic *within-chunk* dual
form — attention-like, MXU-friendly — and (ii) a tiny cross-chunk state
recurrence. The within-chunk part dominates FLOPs and is the kernel here;
the cross-chunk scan stays in jnp (`ops.ssd_scan`), mirroring how the
paper splits block compute (daemon) from the global combine (agent).

Grid = (batch, chunks, heads); per step everything lives in VMEM:
x (L, P), dt (L,), B/C (L, N), plus (L, L) decay/score matrices. With
L=128, P=64, N=128: ~0.3 MiB — tiny, leaving VMEM for deep pipelining.

Outputs per chunk: local y, carry-out state (N, P), total decay, and the
per-position carry gate used by ops.ssd_scan to apply the carried-in state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref,
            y_ref, state_ref, decay_ref, gate_ref):
    x = x_ref[0, 0].astype(jnp.float32)  # (L, P)
    dt = dt_ref[0, 0].astype(jnp.float32)  # (L,)
    a = a_ref[0]  # scalar (per head)
    bm = b_ref[0, 0].astype(jnp.float32)  # (L, N)
    cm = c_ref[0, 0].astype(jnp.float32)  # (L, N)

    logd = a * dt  # (L,)
    cum = jnp.cumsum(logd)  # (L,)
    # gate[t, s] = exp(cum[t] - cum[s]) for s <= t else 0
    diff = cum[:, None] - cum[None, :]
    l = dt.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (l, l), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (l, l), 1)
    causal = col <= row
    diff = jnp.where(causal, diff, 0.0)  # avoid exp overflow in dead region
    gate = jnp.where(causal, jnp.exp(diff), 0.0)

    cb = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (L, L)
    w = cb * gate * dt[None, :]
    y = jax.lax.dot_general(w, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (L, P)

    tail = jnp.exp(cum[-1] - cum)  # (L,) decay from s+1 .. L
    sb = (dt * tail)[:, None] * bm  # (L, N)
    state = jax.lax.dot_general(sb, x, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (N, P)

    y_ref[0, 0] = y.astype(y_ref.dtype)
    state_ref[0, 0] = state.astype(state_ref.dtype)
    decay_ref[0, 0, 0] = jnp.exp(cum[-1])
    gate_ref[0, 0] = jnp.exp(cum).astype(gate_ref.dtype)


def ssd_chunk_pallas(x, dt, a, b_mat, c_mat, *, interpret: bool):
    """Within-chunk SSD over all (batch, chunk, head) cells.

    Shapes (heads already expanded to H):
      x (B, NC, L, H, P) → arranged (B, H, NC, L, P) internally,
      dt (B, NC, L, H), a (H,), b_mat/c_mat (B, NC, L, H, N).
    Returns: y (B, NC, L, H, P), state (B, NC, H, N, P),
             decay (B, NC, H), carry_gate (B, NC, L, H).
    """
    bsz, nc, l, h, p = x.shape
    n = b_mat.shape[-1]
    # (B*H, NC, L, ...) layout: head becomes part of the leading grid axis.
    xt = jnp.moveaxis(x, 3, 1).reshape(bsz * h, nc, l, p)
    dtt = jnp.moveaxis(dt, 3, 1).reshape(bsz * h, nc, l)
    bt = jnp.moveaxis(b_mat, 3, 1).reshape(bsz * h, nc, l, n)
    ct = jnp.moveaxis(c_mat, 3, 1).reshape(bsz * h, nc, l, n)
    a_exp = jnp.tile(a, bsz)  # (B*H,) per-grid-row scalar

    grid = (bsz * h, nc)
    outs = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, l, p), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, l), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1,), lambda i, j: (i,)),
            pl.BlockSpec((1, 1, l, n), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, l, n), lambda i, j: (i, j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, l, p), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, n, p), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, l), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz * h, nc, l, p), jnp.float32),
            jax.ShapeDtypeStruct((bsz * h, nc, n, p), jnp.float32),
            jax.ShapeDtypeStruct((bsz * h, nc, 1), jnp.float32),
            jax.ShapeDtypeStruct((bsz * h, nc, l), jnp.float32),
        ],
        interpret=interpret,
    )(xt, dtt, a_exp, bt, ct)
    y, state, decay, gate = outs
    y = jnp.moveaxis(y.reshape(bsz, h, nc, l, p), 1, 3)  # (B, NC, L, H, P)
    state = jnp.moveaxis(state.reshape(bsz, h, nc, n, p), 1, 2)  # (B, NC, H, N, P)
    decay = jnp.moveaxis(decay.reshape(bsz, h, nc), 1, 2)  # (B, NC, H)
    gate = jnp.moveaxis(gate.reshape(bsz, h, nc, l), 1, 3)  # (B, NC, L, H)
    return y, state, decay, gate
