"""Pallas TPU grouped (per-expert) GEMM for MoE FFNs.

x [E, C, D] @ w [E, D, F] -> [E, C, F], tiled (bc × bf) with the D
contraction innermost-sequential and an fp32 VMEM accumulator — the
TPU-native replacement for a scatter-based CUDA grouped GEMM: tokens are
pre-sorted into dense per-expert blocks (see ``repro.models.moe``), so
every tile is a regular MXU matmul.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _moe_kernel(x_ref, w_ref, o_ref, acc_scr):
    di = pl.program_id(3)
    nd = pl.num_programs(3)

    @pl.when(di == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[0]                                     # [bc, bd]
    w = w_ref[0]                                     # [bd, bf]
    acc_scr[...] += jax.lax.dot(x, w,
                                preferred_element_type=jnp.float32)

    @pl.when(di == nd - 1)
    def _finish():
        o_ref[0] = acc_scr[...].astype(o_ref.dtype)


def moe_gemm(x: jax.Array, w: jax.Array, *, block_c: int = 128,
             block_f: int = 128, block_d: int = 256,
             interpret: bool = False) -> jax.Array:
    """x: [E, C, D]; w: [E, D, F] -> [E, C, F]."""
    e, c, d = x.shape
    _, _, f = w.shape
    block_c = min(block_c, c)
    block_f = min(block_f, f)
    block_d = min(block_d, d)
    grid = (e, pl.cdiv(c, block_c), pl.cdiv(f, block_f),
            pl.cdiv(d, block_d))
    return pl.pallas_call(
        _moe_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_c, block_d),
                         lambda ei, ci, fi, di: (ei, ci, di)),
            pl.BlockSpec((1, block_d, block_f),
                         lambda ei, ci, fi, di: (ei, di, fi)),
        ],
        out_specs=pl.BlockSpec((1, block_c, block_f),
                               lambda ei, ci, fi, di: (ei, ci, fi)),
        out_shape=jax.ShapeDtypeStruct((e, c, f), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_c, block_f), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(x, w)
