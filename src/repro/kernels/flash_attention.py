"""Pallas TPU kernel: block-wise causal flash attention (forward).

Standard online-softmax formulation tiled for the TPU memory hierarchy:
grid = (batch·heads, q_blocks, k_blocks); the innermost (k) dimension is
sequential ("arbitrary"), carrying running max / normalizer / accumulator
in VMEM scratch. Q/K/V tiles stream HBM→VMEM via BlockSpec; the MXU does
q·kᵀ and p·v. GQA is handled in the K/V index maps (a KV head is *shared*
by `group` Q heads — no materialized repeat).

Causal skipping: K blocks strictly above the diagonal are skipped
(pl.when), halving work — block-level frontier skipping, exactly the
paper's "skip blocks with no work" instinct applied to attention.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, bq: int, bk: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    should_run = True
    if causal:
        should_run = qi * bq + bq - 1 >= ki * bk  # any key ≤ last query pos

    @pl.when(should_run)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale  # (bq, d)
        k = k_ref[0].astype(jnp.float32)  # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # (bq, bk)
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            logits = jnp.where(kpos <= qpos, logits, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1))
        p = jnp.exp(logits - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           scale: float | None = None,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool):
    """q (B, Hq, S, D); k, v (B, Hkv, S, D); returns (B, Hq, S, D)."""
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    assert hq % hkv == 0
    group = hq // hkv
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    bq = min(block_q, s)
    bk = min(block_k, s)
    assert s % bq == 0 and s % bk == 0
    nq, nk = s // bq, s // bk

    qf = q.reshape(b * hq, s, d)
    kf = k.reshape(b * hkv, s, d)
    vf = v.reshape(b * hkv, s, d)

    def kv_index(bh, qi, ki):
        batch = bh // hq
        head = bh % hq
        return (batch * hkv + head // group, ki, 0)

    kern = functools.partial(_kernel, scale=scale, causal=causal, bq=bq, bk=bk)
    out = pl.pallas_call(
        kern,
        grid=(b * hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, d), kv_index),
            pl.BlockSpec((1, bk, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, s, d), q.dtype),
        scratch_shapes=[
            # running max, normalizer, accumulator — persist across k blocks
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, hq, s, d)
