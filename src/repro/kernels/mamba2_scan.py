"""Pallas TPU chunked Mamba2 (SSD) scan.

Grid (B·H, nChunks) with chunks sequential; the carried SSM state
[P, N] lives in VMEM scratch.  Within a chunk, the recurrence is the
dense pairwise-decay form (exponents ≤ 0, numerically safe) computed
with MXU matmuls — the TPU adaptation of the CUDA selective-scan: the
sequential dimension is chunk-granular, everything inside a chunk is a
regular GEMM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mamba_kernel(xh_ref, b_ref, c_ref, dta_ref, dt_ref, o_ref, fin_ref,
                  state_scr, *, chunk: int):
    ci = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = xh_ref[0].astype(jnp.float32)        # [L, P]
    bb = b_ref[0].astype(jnp.float32)        # [L, N]
    cc = c_ref[0].astype(jnp.float32)        # [L, N]
    dta = dta_ref[0].astype(jnp.float32)     # [L, 1]  (dt * a, <= 0)
    dt = dt_ref[0].astype(jnp.float32)       # [L, 1]

    li = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # inclusive prefix sum as a lower-triangular ones matmul (Pallas TPU
    # has no cumsum lowering)
    cum = jax.lax.dot((li >= lj).astype(jnp.float32), dta,
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)    # [L, 1]
    # intra-chunk: y_i += sum_{j<=i} exp(cum_i - cum_j) dt_j (c_i·b_j) x_j
    decay = jnp.where(li >= lj, jnp.exp(cum - cum.T), 0.0)   # [L, L]
    sb = jax.lax.dot_general(cc, bb, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    m = sb * decay * dt.T                                    # [L, L]
    y = jax.lax.dot(m, x, preferred_element_type=jnp.float32)
    # inter-chunk: y_i += exp(cum_i) * c_i @ state^T   (state: [P, N])
    state = state_scr[...]
    y += jnp.exp(cum) * jax.lax.dot_general(
        cc, state, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    # state update: state = exp(total) * state + sum_j exp(total-cum_j) dt_j x_j b_j^T
    total = jnp.sum(dta)                                     # scalar
    tail = jnp.exp(total - cum) * dt                         # [L, 1]
    st_new = jax.lax.dot_general(x, bb * tail,
                                 (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    state_scr[...] = state * jnp.exp(total) + st_new

    o_ref[0] = y.astype(o_ref.dtype)

    @pl.when(ci == nc - 1)
    def _finish():
        fin_ref[0] = state_scr[...].astype(fin_ref.dtype)


def mamba2_scan(xh: jax.Array, b: jax.Array, c: jax.Array, dt: jax.Array,
                a_log: jax.Array, *, chunk: int = 128,
                interpret: bool = False):
    """xh: [B, S, H, P]; b, c: [B, S, N]; dt: [B, S, H] (softplus'd);
    a_log: [H].  Returns (y [B, S, H, P], final_state [B, H, P, N])."""
    bsz, s, h, p = xh.shape
    n = b.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0, "pad sequence to a chunk multiple"
    nc = s // chunk
    a = -jnp.exp(a_log.astype(jnp.float32))
    dta = dt * a[None, None]                                 # [B, S, H]

    xf = xh.transpose(0, 2, 1, 3).reshape(bsz * h, s, p)
    bf = jnp.broadcast_to(b[:, None], (bsz, h, s, n)).reshape(bsz * h, s, n)
    cf = jnp.broadcast_to(c[:, None], (bsz, h, s, n)).reshape(bsz * h, s, n)
    dtaf = dta.transpose(0, 2, 1).reshape(bsz * h, s, 1)
    dtf = dt.transpose(0, 2, 1).reshape(bsz * h, s, 1)

    y, fin = pl.pallas_call(
        functools.partial(_mamba_kernel, chunk=chunk),
        grid=(bsz * h, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, 1), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, 1), lambda bh, ci: (bh, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, p), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, p, n), lambda bh, ci: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz * h, s, p), xh.dtype),
            jax.ShapeDtypeStruct((bsz * h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(xf, bf, cf, dtaf, dtf)
    y = y.reshape(bsz, h, s, p).transpose(0, 2, 1, 3)
    fin = fin.reshape(bsz, h, p, n)
    return y, fin
