"""jit'd public wrappers for the Pallas kernels.

The kernels compile for the TPU by default.  On the CPU backend, which
has no Pallas TPU lowering, callers pass ``interpret=True`` explicitly
(as the kernel tests do); nothing switches to interpret mode on its
own.
"""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels import flash_attention as _fa
from repro.kernels import decode_attention as _da
from repro.kernels import moe_gemm as _mg
from repro.kernels import mamba2_scan as _ms
from repro.kernels import rwkv6_scan as _rs


@partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                   "block_k", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, block_q=128,
                    block_k=128, interpret=False):
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret)


@partial(jax.jit, static_argnames=("start", "window", "kv_major",
                                   "block_s", "interpret"))
def decode_attention(q, base, tail, new, layer, cache_len, *, start,
                     window=0, kv_major=False, block_s=None,
                     interpret=False):
    return _da.decode_attention(q, base, tail, new, layer, cache_len,
                                start=start, window=window,
                                kv_major=kv_major, block_s=block_s,
                                interpret=interpret)


@partial(jax.jit, static_argnames=("block_c", "block_f", "block_d",
                                   "interpret"))
def moe_gemm(x, w, *, block_c=128, block_f=128, block_d=256,
             interpret=False):
    return _mg.moe_gemm(x, w, block_c=block_c, block_f=block_f,
                        block_d=block_d, interpret=interpret)


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def mamba2_scan(xh, b, c, dt, a_log, *, chunk=128,
                interpret=False):
    return _ms.mamba2_scan(xh, b, c, dt, a_log, chunk=chunk,
                           interpret=interpret)


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_scan(r, k, v, w, bonus, *, chunk=32,
               interpret=False):
    return _rs.rwkv6_scan(r, k, v, w, bonus, chunk=chunk,
                          interpret=interpret)
