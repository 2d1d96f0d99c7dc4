"""Pallas TPU flash attention (prefill): GQA + causal + sliding window.

TPU-native tiling: the grid is (B·KV·G, nQ, nK) with the KV dimension
innermost and ``arbitrary`` (sequential) semantics, so the online-softmax
running state (m, l, acc) lives in VMEM scratch that persists across KV
steps.  Block shapes are MXU-aligned (multiples of 128 where the head
dim allows).  HBM→VMEM movement is expressed entirely through BlockSpecs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  block_q: int, block_k: int, sk: int, causal: bool,
                  window: int, scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                  # [bq, D]
    k = k_ref[0].astype(jnp.float32)                  # [bk, D]
    v = v_ref[0].astype(jnp.float32)                  # [bk, D]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = k_pos < sk
    if causal:
        mask &= q_pos >= k_pos
    if window:
        mask &= q_pos - k_pos < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc = acc_scr[...] * alpha + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q: [B, Sq, H, D]; k, v: [B, Sk, KV, D]; returns [B, Sq, H, D]."""
    b, sq, h, d = q.shape
    _, sk, kv, _ = k.shape
    assert h % kv == 0
    g = h // kv
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    nq = pl.cdiv(sq, block_q)
    nk = pl.cdiv(sk, block_k)

    # layout: fold heads into the leading grid dim
    qh = q.reshape(b, sq, kv, g, d).transpose(0, 2, 3, 1, 4) \
        .reshape(b * kv * g, sq, d)
    kh = k.transpose(0, 2, 1, 3).reshape(b * kv, sk, d)
    vh = v.transpose(0, 2, 1, 3).reshape(b * kv, sk, d)

    grid = (b * kv * g, nq, nk)
    out = pl.pallas_call(
        functools.partial(_flash_kernel, block_q=block_q, block_k=block_k,
                          sk=sk, causal=causal, window=window,
                          scale=d ** -0.5),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, qi, ki, g_=g: (bh // g_, ki, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, qi, ki, g_=g: (bh // g_, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d),
                               lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * kv * g, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qh, kh, vh)
    return out.reshape(b, kv, g, sq, d).transpose(0, 3, 1, 2, 4) \
        .reshape(b, sq, h, d)
