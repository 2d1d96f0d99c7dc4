"""Pallas TPU flash-decoding: single-token attention over a KV cache.

Grid (B·KV, nS) with the cache-length dimension sequential; the running
(m, l, acc) state for all G query heads of the KV group sits in VMEM
scratch.  Invalid cache positions (≥ cache_len) are masked, so the same
kernel serves any fill level of a static cache.  ``cache_len`` reaches
the kernel as a scalar-prefetch operand in SMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *,
                   block_s: int, scale: float):
    si = pl.program_id(1)
    ns = pl.num_programs(1)

    @pl.when(si == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                  # [G, D]
    k = k_ref[0].astype(jnp.float32)                  # [bs, D]
    v = v_ref[0].astype(jnp.float32)                  # [bs, D]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    pos = si * block_s + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1)
    s = jnp.where(pos < len_ref[0], s, NEG_INF)       # [G, bs]

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(si == ns - 1)
    def _finish():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     cache_len: jax.Array | int, *,
                     block_s: int = 256,
                     interpret: bool = False) -> jax.Array:
    """q: [B, 1, H, D]; caches: [B, S, KV, D] -> [B, 1, H, D]."""
    b, _, h, d = q.shape
    _, s, kv, _ = k_cache.shape
    assert h % kv == 0
    g = h // kv
    block_s = min(block_s, s)
    ns = pl.cdiv(s, block_s)

    qh = q.reshape(b, kv, g, d).reshape(b * kv, g, d)
    kh = k_cache.transpose(0, 2, 1, 3).reshape(b * kv, s, d)
    vh = v_cache.transpose(0, 2, 1, 3).reshape(b * kv, s, d)
    lens = jnp.asarray(cache_len, jnp.int32).reshape(1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * kv, ns),
        in_specs=[
            pl.BlockSpec((1, g, d), lambda bh, si, lens: (bh, 0, 0)),
            pl.BlockSpec((1, block_s, d), lambda bh, si, lens: (bh, si, 0)),
            pl.BlockSpec((1, block_s, d), lambda bh, si, lens: (bh, si, 0)),
        ],
        out_specs=pl.BlockSpec((1, g, d), lambda bh, si, lens: (bh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, block_s=block_s,
                          scale=d ** -0.5),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * kv, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lens, qh, kh, vh)
    return out.reshape(b, 1, h, d)
