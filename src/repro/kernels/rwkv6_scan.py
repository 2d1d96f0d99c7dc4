"""Pallas TPU chunked RWKV6 (Finch) scan with data-dependent decay.

Grid (B·H, nChunks), chunks sequential, carried state [K, V] in VMEM
scratch.  Intra-chunk uses the exact pairwise log-space form (exponents
are sums of per-step log decays over (j, i), always ≤ 0 — safe for any
decay magnitude); chunk length is kept small because the pairwise decay
tensor is [L, L, K].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rwkv_kernel(r_ref, k_ref, v_ref, lw_ref, bonus_ref, o_ref, fin_ref,
                 state_scr, *, chunk: int):
    ci = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    d = r_ref.shape[-1]
    r = r_ref[0].astype(jnp.float32)         # [L, K]
    k = k_ref[0].astype(jnp.float32)         # [L, K]
    v = v_ref[0].astype(jnp.float32)         # [L, V]
    lw = lw_ref[0].astype(jnp.float32)       # [L, K] log decay (<= 0)
    bonus = bonus_ref[0].astype(jnp.float32)  # [1, K] -> [K]

    li = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # inclusive prefix sum as a lower-triangular ones matmul (Pallas TPU
    # has no cumsum lowering)
    cum = jax.lax.dot((li >= lj).astype(jnp.float32), lw,
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)    # [L, K]
    # inter-chunk: out_i += (r_i ⊙ prod_{s<i} w_s) @ state
    dec_in = jnp.exp(cum - lw)               # [L, K]
    out = jax.lax.dot(r * dec_in, state_scr[...],
                      preferred_element_type=jnp.float32)
    # intra-chunk, strict lower triangle: pairwise exponents <= 0
    dij = (cum - lw)[:, None, :] - cum[None, :, :]      # [L, L, K]
    strict = (jax.lax.broadcasted_iota(jnp.int32, dij.shape, 0)
              > jax.lax.broadcasted_iota(jnp.int32, dij.shape, 1))
    pair = jnp.where(strict, jnp.exp(jnp.minimum(dij, 0.0)), 0.0)
    scores = jnp.sum(r[:, None, :] * pair * k[None, :, :], axis=-1)
    out += jax.lax.dot(scores, v, preferred_element_type=jnp.float32)
    # diagonal bonus
    diag = jnp.sum(r * bonus * k, axis=1, keepdims=True)  # [L, 1]
    out += diag * v
    # state update
    total = cum[chunk - 1:]                              # [1, K]
    tail = jnp.exp(total - cum)                          # [L, K]
    st_new = jax.lax.dot_general(k * tail, v, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    # state rows decay by exp(total): diag(exp(total)) @ state
    ki = jax.lax.broadcasted_iota(jnp.int32, (d, d), 0)
    kj = jax.lax.broadcasted_iota(jnp.int32, (d, d), 1)
    decay = jnp.where(ki == kj, jnp.exp(total), 0.0)     # [K, K]
    state_scr[...] = jax.lax.dot(
        decay, state_scr[...], precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32) + st_new

    o_ref[0] = out.astype(o_ref.dtype)

    @pl.when(ci == nc - 1)
    def _finish():
        fin_ref[0] = state_scr[...].astype(fin_ref.dtype)


def rwkv6_scan(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
               bonus: jax.Array, *, chunk: int = 32,
               interpret: bool = False):
    """r,k,v,w: [B, S, H, D]; bonus: [H, D].
    Returns (out [B, S, H, D], final state [B, H, D, D])."""
    b, s, h, d = r.shape
    chunk = min(chunk, s)
    assert s % chunk == 0, "pad sequence to a chunk multiple"
    nc = s // chunk
    lw = jnp.log(jnp.clip(w.astype(jnp.float32), 1e-8, 1.0))

    def fold(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)

    rf, kf, vf = fold(r), fold(k), fold(v)
    lwf = lw.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    bonus_f = jnp.broadcast_to(bonus[None], (b, h, d)) \
        .reshape(b * h, 1, d)

    out, fin = pl.pallas_call(
        functools.partial(_rwkv_kernel, chunk=chunk),
        grid=(b * h, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, d), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, d), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, d), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, d), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, 1, d), lambda bh, ci: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, d), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, d, d), lambda bh, ci: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, d), r.dtype),
            jax.ShapeDtypeStruct((b * h, d, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(rf, kf, vf, lwf, bonus_f)
    return (out.reshape(b, h, s, d).transpose(0, 2, 1, 3),
            fin.reshape(b, h, d, d))
